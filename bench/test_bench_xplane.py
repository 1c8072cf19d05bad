"""The trace reduction, on a trace recorded on a TPU v5e and on synthetic
intervals."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pytest  # noqa: E402

import xplane_reduce as X  # noqa: E402

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                     "fused_1chip_smoke.xplane.pb")


@pytest.fixture(scope="module")
def red():
    # a smoke-size phi3 trainer on one chip: 8 steps of run() at tau 4
    return X.reduce_file(TRACE, chips=1)


def test_window_and_busy(red):
    assert red["window_s"] == pytest.approx(0.064907184)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] == pytest.approx(0.00140831)


def test_programs_found_by_jitted_name(red):
    assert len(red["modules"]["jit_fo_step"]) == 2
    assert len(red["modules"]["jit_zo_step"]) == 6
    assert all(0 < s < 1e-3 for s in red["modules"]["jit_zo_step"])


def test_one_chip_has_no_collective(red):
    assert red["collective_s"] == 0 and red["collective_exposed_s"] == 0


def test_breakdown(red):
    ops, gaps = red["breakdown"]["device_ops"], red["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(not n.split("/")[1].startswith("while") for n, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert all(label.startswith(("in run", "bench.on_step"))
               for label, _ in gaps)
    assert sum(s for _, s in gaps) <= red["window_s"] - red["busy_s"] + 1e-9


def test_op_label():
    assert X.op_label("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), "
                      "kind=kLoop") == ("fusion.3", "fusion")
    assert X.op_label("%all-reduce.1 = (f32[4]{0}, f32[2]{0}) all-reduce("
                      "f32[4]{0} %a)") == ("all-reduce.1", "all-reduce")
    assert X.op_label("%while.8 = (s32[]{:T(128)}) while((s32[]) %t)")[1] \
        == "while"


def test_intervals():
    assert X.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert X.intersect([(0, 3), (5, 8)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert X.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert X.clip([(0, 3), (5, 8)], 1, 6) == [(1, 3), (5, 6)]


def test_exposed_collective_time():
    ops = [("fusion", 0, 10), ("all-reduce", 8, 20), ("all-gather", 30, 35),
           ("convolution", 18, 19)]
    # all-reduce 8..20 less 8..10 and 18..19; all-gather 30..35 in full
    assert X.exposed(ops) == 9 + 5
