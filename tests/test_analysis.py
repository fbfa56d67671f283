"""repro.analysis: lint fixtures (must-trip AND must-pass per rule),
kernel contract checker (clean registry + injected inconsistencies),
autotune-cache validation, and the determinism sanitizer.

The protocol/race-detector half lives in tests/test_analysis_protocol.py.
"""

import json
import textwrap

import numpy as np
import pytest

from repro.analysis import kernel_check, lint, sanitize
from repro.analysis.report import Violation, render_report
from repro.kernels.plan import BlockDef, KernelPlan, ScratchDef


def codes(violations):
    return sorted(v.code for v in violations)


def lint_src(src, relpath):
    return lint.lint_source(textwrap.dedent(src), relpath)


# ---------------------------------------------------------------------------
# architecture lint: one must-trip + one must-pass fixture per rule
# ---------------------------------------------------------------------------


def test_lint_unparsable_is_rcca000():
    assert codes(lint_src("def broken(:\n", "repro/x.py")) == ["RCCA000"]


def test_rcca001_fold_loop_outside_exec_trips():
    src = """
    def merge_all(partials, acc):
        for p in partials:
            acc = merge_stats(acc, p)
        return acc
    """
    vs = lint_src(src, "repro/cluster/bad.py")
    assert codes(vs) == ["RCCA001"]
    assert "pairwise tree" in vs[0].message


def test_rcca001_comprehension_and_update_fn_trip():
    src = """
    def f(groups, acc):
        [acc.push_group(g, s) for g, s in groups]
        while groups:
            acc2 = jit_update_fn(acc, *groups.pop())
    """
    vs = lint_src(src, "repro/core/bad.py")
    assert codes(vs) == ["RCCA001", "RCCA001"]


def test_rcca001_same_loop_inside_exec_passes():
    src = """
    def merge_all(partials, acc):
        for p in partials:
            acc = merge_stats(acc, p)
        return acc
    """
    assert lint_src(src, "repro/exec/accumulate.py") == []


def test_rcca001_unlooped_call_passes():
    # a single straight-line fold call is delegation, not reimplementation
    src = "def f(acc, s):\n    acc.push_group(0, s)\n"
    assert lint_src(src, "repro/cluster/ok.py") == []


def test_rcca002_version_sensitive_import_trips():
    for src in (
        "from jax.experimental.shard_map import shard_map\n",
        "import jax.experimental.pallas.tpu as pltpu\n",
        "from jax.experimental import shard_map\n",
        "def f(x):\n    return pltpu.roll(x, 1, 0)\n",
    ):
        vs = lint_src(src, "repro/exec/bad.py")
        assert codes(vs) == ["RCCA002"], src


def test_rcca002_compat_shim_is_exempt_and_plain_pallas_passes():
    src = "from jax.experimental.shard_map import shard_map\n"
    assert lint_src(src, "repro/kernels/compat.py") == []
    # plain (non-tpu) pallas is not version-pinned
    assert lint_src("from jax.experimental import pallas as pl\n",
                    "repro/kernels/matmul.py") == []


def test_rcca003_shard_file_reference_trips():
    src = "def f(d, i):\n    return load(f'{d}/shard_{i:05d}.a.npy')\n"
    vs = lint_src(src, "repro/cluster/bad.py")
    assert codes(vs) == ["RCCA003"]


def test_rcca003_store_scope_and_docstrings_pass():
    src = "def f(d, i):\n    return load(f'{d}/shard_{i:05d}.b.npy')\n"
    assert lint_src(src, "repro/store/format.py") == []
    doc = '"""Reads shard_00000.a.npy via the manifest."""\n'
    assert lint_src(doc, "repro/cluster/ok.py") == []


def test_rcca004_nondeterminism_in_pass_path_trips():
    src = """
    def f(groups):
        t = time.time()
        fit = uuid.uuid4()
        x = np.random.randn(3)
        for g in set(groups):
            pass
        return [g for g in set(groups)]
    """
    vs = lint_src(src, "repro/exec/bad.py")
    assert codes(vs) == ["RCCA004"] * 5


def test_rcca004_outside_pass_path_and_deterministic_iter_pass():
    src = "def f():\n    return time.time(), np.random.randn(3)\n"
    assert lint_src(src, "repro/launch/bench.py") == []  # not pass-path
    src = """
    def f(groups):
        for g in sorted(set(groups)):
            pass
        for g in dict.fromkeys(groups):
            pass
    """
    assert lint_src(src, "repro/exec/ok.py") == []


def test_rcca005_bare_write_in_cluster_scope_trips():
    src = """
    def publish(path, obj, arr):
        with open(path, "w") as f:
            f.write(obj)
        np.save(path + ".npy", arr)
    """
    vs = lint_src(src, "repro/cluster/bad.py")
    assert codes(vs) == ["RCCA005", "RCCA005"]


def test_rcca005_appends_reads_and_other_scopes_pass():
    src = """
    def f(path):
        with open(path) as f:
            f.read()
        with open(path, "a") as f:
            f.write("x")
    """
    assert lint_src(src, "repro/cluster/ok.py") == []
    # writes outside cluster/store scope are not this rule's business
    src = "def f(p, a):\n    np.save(p, a)\n"
    assert lint_src(src, "repro/launch/bench.py") == []


def test_rcca007_raw_monotonic_clock_in_pass_path_trips():
    src = """
    def f():
        t0 = time.perf_counter()
        t1 = time.monotonic()
        t2 = time.monotonic_ns()
        return time.perf_counter_ns() - t0
    """
    for relpath in ("repro/exec/bad.py", "repro/store/prefetch.py"):
        assert codes(lint_src(src, relpath)) == ["RCCA007"] * 4


def test_rcca007_obs_clocks_and_other_scopes_pass():
    src = """
    def f():
        t0 = obs.monotonic()
        obs.counter("io", read_s=obs.monotonic() - t0, at=obs.wall())
    """
    assert lint_src(src, "repro/exec/ok.py") == []
    # raw clocks are fine outside the pass path and in obs itself
    src = "def f():\n    return time.perf_counter()\n"
    assert lint_src(src, "repro/launch/bench.py") == []
    assert lint_src(src, "repro/obs/trace.py") == []


def test_noqa_suppression_bare_and_coded():
    trip = "def f(p, a):\n    np.save(p, a)\n"
    base = lint_src(trip, "repro/cluster/x.py")
    assert codes(base) == ["RCCA005"]
    for tail in ("  # rcca: noqa", "  # rcca: noqa[RCCA005]",
                 "  # rcca: noqa[RCCA001, RCCA005]"):
        src = trip.replace("np.save(p, a)", "np.save(p, a)" + tail)
        assert lint_src(src, "repro/cluster/x.py") == [], tail
    # a noqa for a DIFFERENT code does not suppress
    src = trip.replace("np.save(p, a)", "np.save(p, a)  # rcca: noqa[RCCA001]")
    assert codes(lint_src(src, "repro/cluster/x.py")) == ["RCCA005"]


def test_lint_tree_is_clean():
    """Dogfood: the shipped tree has zero unsuppressed violations."""
    assert lint.lint_tree() == []


# ---------------------------------------------------------------------------
# kernel contract checker
# ---------------------------------------------------------------------------


def _plan_2x2(block=(128, 128), padded=(256, 256), *,
              index_map=None, out_dtype="float32", scratch=(),
              accum_outputs=(), out_shape=None, in_dtype="float32"):
    """A minimal one-operand copy-style plan: 2×2 grid of 128² tiles."""
    imap = index_map if index_map is not None else (lambda i, j: (i, j))
    spec = lambda dt: BlockDef(shape=block, index_map=imap,
                               padded=padded, dtype=dt)
    return KernelPlan(
        name="fixture", grid=(2, 2),
        in_specs=(spec(in_dtype),), out_specs=(spec(out_dtype),),
        scratch=tuple(scratch),
        out_shape=(out_shape if out_shape is not None else (250, 250),),
        accum_outputs=tuple(accum_outputs))


def test_check_plan_fixture_is_clean():
    assert kernel_check.check_plan(_plan_2x2()) == []


def test_rcca101_block_does_not_tile_padded():
    vs = kernel_check.check_plan(_plan_2x2(block=(100, 128)))
    assert "RCCA101" in codes(vs)


def test_rcca101_logical_exceeds_padded():
    vs = kernel_check.check_plan(_plan_2x2(out_shape=(300, 250)))
    assert codes(vs) == ["RCCA101"]


def test_rcca102_index_map_arity_and_oob():
    vs = kernel_check.check_plan(_plan_2x2(index_map=lambda i: (i, 0)))
    assert "RCCA102" in codes(vs)
    vs = kernel_check.check_plan(_plan_2x2(index_map=lambda i, j: (i, j + 1)))
    assert "RCCA102" in codes(vs)


def test_rcca103_uncovered_output_tile():
    # every grid point writes tile (i, 0): column 1 never covered
    vs = kernel_check.check_plan(_plan_2x2(index_map=lambda i, j: (i, 0)))
    assert codes(vs) == ["RCCA103"]
    assert "uncovered" in vs[0].message


def test_rcca104_vmem_budget():
    vs = kernel_check.check_plan(_plan_2x2(), budget=128 * 128 - 1)
    assert codes(vs) == ["RCCA104", "RCCA104"]  # the in block and out block
    vs = kernel_check.check_plan(
        _plan_2x2(scratch=(ScratchDef((4096, 4096), "float32"),)))
    # the scratch buffer, and the 64 MiB footprint over the limit cap
    assert codes(vs) == ["RCCA104", "RCCA104"]


def test_rcca104_refuses_the_plan_mosaic_refused(monkeypatch):
    """The recompute powerpass of a paper-width chunk (512 rows,
    d = 2^19, k̃ = 970) holds exactly 16 MiB of double-buffered blocks
    and scratch, and its body's values need more beside them.  Mosaic
    refused it when its launch requested no scoped limit (16 MiB by
    default); the checker refuses that launch too, and passes the plan
    at the limit the launch now requests."""
    from repro.kernels import compat
    from repro.kernels.plan import MOSAIC_DEFAULT_VMEM_LIMIT
    from repro.kernels.powerpass import plan_powerpass

    plan = plan_powerpass(512, 1 << 19, 1 << 19, 970, "float32")
    assert plan.vmem_bytes == 16 << 20
    assert plan.vmem_need_bytes > MOSAIC_DEFAULT_VMEM_LIMIT
    assert plan.vmem_limit_bytes == 34 << 20
    assert kernel_check.check_plan(plan) == []

    real = compat.tpu_compiler_params
    monkeypatch.setattr(compat, "tpu_compiler_params",
                        lambda *, vmem_limit_bytes=None, **kw: real(**kw))
    vs = kernel_check.check_plan(plan)
    assert codes(vs) == ["RCCA104"]
    assert "scoped VMEM limit 16777216 its launch requests" in vs[0].message


def test_rcca105_dtype_rules():
    vs = kernel_check.check_plan(
        _plan_2x2(scratch=(ScratchDef((8, 128), "bfloat16"),)))
    assert codes(vs) == ["RCCA105"]
    vs = kernel_check.check_plan(_plan_2x2(out_dtype="bfloat16",
                                           accum_outputs=(0,)))
    assert codes(vs) == ["RCCA105"]  # declared accumulator must be f32
    vs = kernel_check.check_plan(_plan_2x2(in_dtype="bfloat16",
                                           out_dtype="bfloat16"))
    assert codes(vs) == ["RCCA105"]  # bf16-in/bf16-out, no f32 accumulator


def test_registry_is_clean():
    """The production kernels pass their own contract (incl. RCCA106
    abstract-eval agreement) — the `make analyze` kernel gate."""
    assert kernel_check.check_registry(cache=False) == []


def test_check_kernel_rejects_inconsistent_registered_plan():
    """A registry entry whose plan is inconsistent IS caught — the gate
    is not vacuous."""
    from repro.kernels import KernelDef

    bad = KernelDef(
        name="bad_fixture",
        plan=lambda probe: _plan_2x2(index_map=lambda i, j: (i, 0)),
        probes=({"M": 256, "N": 256, "dtype": "float32"},),
        abstract=None)
    vs = kernel_check.check_kernel(bad, abstract=False)
    assert codes(vs) == ["RCCA103"]


# ---------------------------------------------------------------------------
# autotune-cache validation (RCCA107)
# ---------------------------------------------------------------------------


VALID_KEY = "cpu|matmul_nn|float32|256x256x256"


def _write_cache(tmp_path, cache):
    p = tmp_path / "autotune.json"
    p.write_text(json.dumps(cache))
    return str(p)


def test_autotune_cache_valid_entry_is_clean(tmp_path):
    p = _write_cache(tmp_path, {VALID_KEY: {"blocks": [128, 128, 128]}})
    assert kernel_check.check_autotune_cache(p) == []


def test_autotune_cache_missing_is_clean(tmp_path):
    assert kernel_check.check_autotune_cache(str(tmp_path / "nope.json")) == []


@pytest.mark.parametrize("key,entry", [
    ("not-a-key", {"blocks": [128, 128, 128]}),            # unparsable key
    ("cpu|mystery_op|float32|256x256x256",
     {"blocks": [128, 128, 128]}),                         # unknown op
    ("cpu|matmul_nn|float32|256x256", {"blocks": [128, 128, 128]}),  # ndims
    ("cpu|matmul_nn|float32|256x200x256",
     {"blocks": [128, 128, 128]}),                         # not x128-padded
    (VALID_KEY, {"blocks": [128, 128]}),                   # two blocks
    (VALID_KEY, {"blocks": [128, -128, 128]}),             # negative block
    (VALID_KEY, "not-an-object"),                          # malformed entry
])
def test_autotune_cache_mutations_trip_rcca107(tmp_path, key, entry):
    p = _write_cache(tmp_path, {key: entry})
    vs = kernel_check.check_autotune_cache(p)
    assert vs and all(v.code == "RCCA107" for v in vs)


def test_autotune_cache_unreadable_trips(tmp_path):
    p = tmp_path / "autotune.json"
    p.write_text("{truncated")
    vs = kernel_check.check_autotune_cache(str(p))
    assert codes(vs) == ["RCCA107"]


# ---------------------------------------------------------------------------
# determinism sanitizer (RCCA301)
# ---------------------------------------------------------------------------


@pytest.fixture
def sanitizing(monkeypatch):
    monkeypatch.setenv("RCCA_SANITIZE", "1")
    sanitize.reset()
    yield
    sanitize.reset()


def test_observe_is_noop_when_disabled(monkeypatch):
    monkeypatch.delenv("RCCA_SANITIZE", raising=False)
    sanitize.reset()
    sanitize.observe("group:0", {"y": np.ones(3, np.float32)})
    assert sanitize.snapshot() == []


def test_identical_states_identical_digests(sanitizing):
    tree = {"y": np.arange(4, dtype=np.float32), "n": np.float32(2)}
    sanitize.observe("group:0", tree)
    sanitize.observe("group:0", {k: v.copy() if hasattr(v, "copy") else v
                                 for k, v in tree.items()})
    a, b = sanitize.snapshot()
    assert a["digest"] == b["digest"]
    assert sanitize.first_divergence([a], [b]) is None


def test_first_divergence_pinpoints_bit_flip(sanitizing):
    good = np.arange(8, dtype=np.float32)
    bad = good.copy()
    bad[5] = np.nextafter(bad[5], np.inf)  # one ulp — invisible to allclose
    sanitize.set_context(pass_idx=1, kind="power")
    for g in range(3):
        sanitize.observe(f"group:{g}", {"y": good})
    run_a = sanitize.snapshot()
    sanitize.reset()
    sanitize.set_context(pass_idx=1, kind="power")
    for g in range(3):
        sanitize.observe(f"group:{g}", {"y": bad if g == 2 else good})
    run_b = sanitize.snapshot()
    d = sanitize.first_divergence(run_a, run_b)
    assert d["code"] == "RCCA301" and d["reason"] == "digest"
    assert d["index"] == 2 and d["a"]["label"] == "group:2"


def test_first_divergence_label_and_length(sanitizing):
    sanitize.observe("group:0", {"y": np.ones(2, np.float32)})
    a = sanitize.snapshot()
    sanitize.reset()
    sanitize.observe("group:1", {"y": np.ones(2, np.float32)})
    b = sanitize.snapshot()
    assert sanitize.first_divergence(a, b)["reason"] == "label"
    assert sanitize.first_divergence(a, a + b)["reason"] == "length"


def test_dump_load_roundtrip(sanitizing, tmp_path):
    sanitize.set_context(pass_idx=0, kind="final", site="stream")
    sanitize.observe("pass_end", {"y": np.zeros(2, np.float32)})
    out = str(tmp_path / "trace.json")
    assert sanitize.dump(out) == out
    assert sanitize.load(out) == sanitize.snapshot()


def test_sanitized_fit_trace_is_reproducible(sanitizing):
    """End to end: two identical iterator fits leave identical traces,
    and the trace lands in diagnostics."""
    import jax

    from repro.core.rcca import RCCAConfig, randomized_cca_iterator

    rng = np.random.default_rng(7)
    chunks = [(rng.standard_normal((32, 6), dtype=np.float32),
               rng.standard_normal((32, 5), dtype=np.float32))
              for _ in range(4)]
    cfg = RCCAConfig(k=2, p=1, q=1)
    key = jax.random.PRNGKey(3)

    def run():
        sanitize.reset()
        res = randomized_cca_iterator(lambda: iter(chunks), 6, 5, cfg, key)
        return res.diagnostics["sanitize"]

    t1, t2 = run(), run()
    assert t1 and t1 == t2
    assert sanitize.first_divergence(t1, t2) is None


# ---------------------------------------------------------------------------
# report plumbing
# ---------------------------------------------------------------------------


def test_render_report_sorts_and_counts():
    vs = [Violation("RCCA005", "b.py", 9, "later"),
          Violation("RCCA001", "a.py", 2, "earlier")]
    text = render_report(vs, title="lint")
    assert text.index("a.py:2") < text.index("b.py:9")
    assert "-> 2 violations" in text
    assert "-> clean" in render_report([], title="lint")
