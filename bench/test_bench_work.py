"""Needed work, least time and the peaks table, on hand-counted chunks."""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import peaks  # noqa: E402
import work  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def test_hashed_counts_by_hand():
    # row 0: tokens 5, 5, 7 -> two distinct pairs; row 1: 7, 9, pad -> two
    tokens = np.array([[5, 5, 7], [7, 9, 0]])
    assert work.hashed_counts(tokens) == (4, 3)   # nnz 4, touched tokens {5, 7, 9}


def test_power_chunk_by_hand():
    # 2 rows, d = 8, k~ = 4; view a: nnz 3 over 2 columns, view b: nnz 4 over 3
    ops, nbytes = work.chunk_work("power", 2, 4, 8, 8, 3, 4, 2, 3)
    assert ops == 4 * 4 * (3 + 4)
    data = min(2 * 8 * 4, 3 * 8) + min(2 * 8 * 4, 4 * 8)   # value + index beats dense
    q_rows = (2 + 3) * 4 * 4
    assert nbytes == data + q_rows + 2 * q_rows


def test_final_chunk_by_hand():
    ops, nbytes = work.chunk_work("final", 2, 4, 8, 8, 16, 16, 8, 8)
    assert ops == 2 * 4 * 32 + 3 * 2 * 2 * 16
    data = 2 * min(2 * 8 * 4, 16 * 8)                      # dense beats value + index
    assert nbytes == data + 16 * 4 * 4 + 2 * 3 * 16 * 4


def test_dense_counts_and_svcca_power_chunk():
    nnz, cols = work.dense_counts(8192, 4608)
    assert (nnz, cols) == (8192 * 4608, 4608)
    ops, nbytes = work.chunk_work("power", 8192, 512, 4608, 4608, nnz, nnz, cols, cols)
    assert ops == 4 * 512 * 2 * 8192 * 4608                # 1.55e11
    t, bound = work.least_time(ops, nbytes, V5E)
    assert bound == "compute" and t == pytest.approx(ops / 197e12)


def test_least_time_memory_bound():
    t, bound = work.least_time(1e6, 819e6, V5E)
    assert bound == "memory" and t == pytest.approx(1e-3)


def test_peaks_table_has_the_v5e_with_its_source():
    with open(peaks.PEAKS_FILE) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    assert peaks.peaks_for("TPU v5 lite") == V5E


def test_unknown_device_kind_is_refused(tmp_path):
    with pytest.raises(peaks.UnknownDevice, match="cpu"):
        peaks.peaks_for("cpu")
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"source": "x", "devices": {}}))
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v5 lite", str(p))


def test_boundary_ops_by_hand():
    # d = 8 per view, k~ = 4, k = 2: QR of two 8 x 4 sketches; X = Q W for both views
    assert work.boundary_ops("power", 8, 8, 4, 2) == 2 * 8 * 16 + 2 * 8 * 16
    assert work.boundary_ops("final", 8, 8, 4, 2) == 2 * 8 * 4 * 2 * 2
    with pytest.raises(ValueError):
        work.boundary_ops("merge", 8, 8, 4, 2)


def test_fit_mfu_reads_needed_ops_over_the_window():
    import harness

    reader = harness.load_module(os.path.join(BENCH, "metrics", "fit_mfu.py"))

    class Ctx:
        window = (10.0, 14.0)
        peaks = V5E
        records = {"chunk_work": [(197e12, 1), (197e12, 1)], "boundary_ops": 197e12}

    assert reader.read(Ctx()) == pytest.approx(100.0 * 3 / 4)
    Ctx.records = {}
    assert reader.read(Ctx()) is None
