"""The control comes out not correct: the plain reference put in the
program's place at the precision below the configuration's (three-pass
bf16 matmuls, written out so the CPU computes them as the chip does),
held to each throwaway cell's limits.  ``bench/control.py`` makes the
same readings on the chip at each cell's own size."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import control  # noqa: E402
import test_bench_harness as tb  # noqa: E402

checkout = tb.checkout


@pytest.mark.parametrize("cell", sorted(tb.CELLS))
@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_is_not_correct(checkout, cell, seed):
    root, bench = checkout
    line = control.control_line(root, cell, seed, tb.SECONDS[cell], bench)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("cell", sorted(tb.CELLS))
def test_program_is_correct_on_the_control_seeds(checkout, cell):
    assert tb.run(checkout, cell, seed=2**31 + 3)["correct"]
