"""The control and the planted faults fail the cells' limits, at a size the
CPU holds: the bfloat16 reference in the program's place, the reference with
half of every batch left out, and the reference whose scanned rounds leave θ
unchanged.  The program itself passes them."""
import numpy as np
import pytest

import control
from harness import compare, compile_stats


def _fails(numbers: dict, limits: dict) -> bool:
    return not compare.passed(compare.judge(numbers, limits))


@pytest.mark.parametrize("workload", ["paper_cnn_n10.train",
                                      "xdevice_cnn_c256.train"])
def test_train_control_and_faults_fail_the_limits(workload, tiny_cell,
                                                  tmp_path):
    cell = tiny_cell(workload)
    lines = {r["reading"]: r for r in control.readings(
        cell, 2**33 + 5, compile_stats.CompileStats(), str(tmp_path))}
    assert set(lines) == {"program"} | {n for n, _ in control.OTHERS}
    keys = list(cell.limits)
    pick = (lambda r: {k: lines[r][k] for k in keys})
    assert not _fails(pick("program"), cell.limits), lines["program"]
    for name, _ in control.OTHERS:
        assert _fails(pick(name), cell.limits), lines[name]
    assert len(lines["program"]["medoid_margin"]) == 3


def test_unchanged_state_reads_one_on_update0():
    start = {"a": {"w": np.ones(3), "b": np.zeros(2)}}
    moved = {"a": {"w": np.ones(3) * 2, "b": np.ones(2)}}
    assert compare.norm_gap(compare.leaf_norms(start, start),
                            compare.leaf_norms(moved, start)) == 1.0
