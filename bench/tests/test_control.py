"""The control: the reference computed in float8, put in the program's
place and judged by the same limits, at the reduced presets a test run
holds (the chip readings at the cells' own sizes are in PERF.md)."""

import time

import pytest

from bench.tests.conftest import TINY, add_cell


@pytest.mark.parametrize("name", sorted(TINY))
def test_control_reads_incorrect_where_the_program_reads_correct(
        bench_copy, name):
    from bench.harness import run
    arch, sizes = TINY[name]
    cell = add_cell(bench_copy, name, f"{arch}.json", sizes)
    res = run(cell, 11, 6.0, False, t_start=time.perf_counter(),
              require_tpu=False, root=bench_copy, control=True)
    assert res["correct"] is True
    assert res["control"]["correct"] is False
    assert list(res)[-1] == "checks"
