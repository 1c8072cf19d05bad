"""The reduction of a profile to program spans and device scopes, on a
trace recorded on a TPU v5e, on a profile the training CLI writes on the
CPU, and on synthetic intervals."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import pytest  # noqa: E402

import harness as H  # noqa: E402
import span_reduce as R  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata")
TRACE = os.path.join(TESTDATA, "spans_1chip_smoke.xplane.pb")
SCOPES = os.path.join(TESTDATA, "spans_1chip_smoke.op_scopes.json")


@pytest.fixture(scope="module")
def red():
    # a smoke-size phi3 trainer on one chip: 8 steps of run() at tau 4, in
    # the benchmark's bench.window, each step's callback in bench.on_step
    from jax.profiler import ProfileData
    with open(SCOPES) as f:
        scopes = json.load(f)
    return R.reduce(ProfileData.from_file(TRACE), scopes)


def test_recorded_spans(red):
    assert red["window_s"] == pytest.approx(0.060609236)
    spans = red["spans"]
    for name in ("train.step", "train.data", "train.dispatch", "train.block",
                 "train.log"):
        assert spans[name]["count"] == 8
    assert red["per_step"]["trainer.data_s"] == spans["train.data"]["mean_s"]
    assert spans["train.data"]["mean_s"] == pytest.approx(0.001009926)


def test_recorded_programs_split_by_scope(red):
    fo, zo = red["programs"]["jit_fo_step"], red["programs"]["jit_zo_step"]
    assert (fo["executions"], zo["executions"]) == (2, 6)
    # at this size the FO step's async slices of the stacked layers, which
    # carry no op_name, are 7.5 % of its op time
    assert fo["scoped_share"] == pytest.approx(0.924624, abs=1e-6)
    assert zo["scoped_share"] == pytest.approx(0.987364, abs=1e-6)
    assert fo["unscoped_top"][0][0] == "slice-done.1 (async-done)"
    for p in (fo, zo):
        assert sum(p["by_part"].values()) == pytest.approx(p["op_s"])
        assert sum(p["by_layer"].values()) == pytest.approx(p["op_s"])
    assert red["per_step"] == pytest.approx({
        "zo_step.forward_device_s": 5.47985e-05,
        "zo_step.direction_device_s": 1.4425e-04,
        "zo_step.exchange_device_s": 0.0,
        "fo_step.forward_device_s": 2.8326e-05,
        "fo_step.backward_device_s": 4.0809e-05,
        "fo_step.recompute_device_s": 1.25455e-05,
        "fo_step.update_device_s": 1.001e-05,
        "trainer.data_s": 0.001009926})


def test_recorded_idle_lies_in_named_spans(red):
    idle = red["idle"]
    assert idle["seconds"] == pytest.approx(0.059201123)
    assert idle["gaps_over_1ms_outside_spans"] == 0
    assert sum(idle["by_span"].values()) == pytest.approx(idle["seconds"])
    assert set(idle["by_span"]) == {
        "bench.window", "bench.on_step", "train.step", "train.data",
        "train.dispatch", "train.block", "train.log"}
    # at this size the host's dispatch of each step is the longest gap
    assert idle["gaps"][0] == ["train.dispatch", pytest.approx(0.032697504)]


def test_profile_from_the_training_cli(tmp_path):
    """``launch.train --profile DIR`` on the CPU: its profile and
    ``op_scopes.json`` are what ``span_reduce`` reads (no device plane on
    the CPU: the host spans alone)."""
    from repro.launch import train
    train.main(["--arch", "phi3-mini-3.8b", "--reduce", "smoke", "--batch",
                "2", "--seq", "32", "--tau", "2", "--steps", "4",
                "--profile", str(tmp_path)])
    scopes = R.load_op_scopes(str(tmp_path))
    assert set(scopes) == {"jit_fo_step", "jit_zo_step"}
    red = R.reduce_dir(str(tmp_path))
    assert red["spans"]["train.step"]["count"] == 4
    assert red["spans"]["train.data"]["count"] == 4
    assert red["per_step"] == {
        "trainer.data_s": red["spans"]["train.data"]["mean_s"]}


def test_segments_take_the_innermost_span():
    spans = [(0, 100, "bench.window"), (10, 50, "train.step"),
             (20, 30, "train.block")]
    assert R.segments(spans, 0, 120) == [
        (0, 10, "bench.window"), (10, 20, "train.step"),
        (20, 30, "train.block"), (30, 50, "train.step"),
        (50, 100, "bench.window"), (100, 120, "none")]


def test_attribute_splits_each_gap_over_segments():
    segs = [(0, 10, "a"), (10, 20, "b"), (20, 40, "a")]
    assert R.attribute([(5, 15), (18, 19), (30, 45)], segs) == [
        {"a": 5, "b": 5}, {"b": 1}, {"a": 10}]


def test_per_step():
    programs = {
        "jit_zo_step": {"executions": 2, "by_part": {
            "zo.forward": 4.0, "zo.norm": 0.2, "zo.perturb": 0.2,
            "zo.reconstruct": 0.4, "zo.update": 0.2, "zo.exchange": 0.1}},
        "jit_fo_step": {"executions": 1, "by_part": {
            "fo.grad.forward": 1.0, "fo.grad.backward": 2.0,
            "fo.grad.recompute": 1.0, "fo.accumulate": 0.25,
            "fo.update": 0.5}}}
    spans = {"train.data": {"count": 3, "total_s": 0.03, "mean_s": 0.01}}
    assert R.per_step(programs, spans) == pytest.approx({
        "zo_step.forward_device_s": 2.0, "zo_step.direction_device_s": 0.5,
        "zo_step.exchange_device_s": 0.05, "fo_step.forward_device_s": 1.0,
        "fo_step.backward_device_s": 2.0, "fo_step.recompute_device_s": 1.0,
        "fo_step.update_device_s": 0.75, "trainer.data_s": 0.01})
    assert R.per_step({}, {}) == {}


def test_harness_reads_spans_into_the_record(red, tmp_path):
    """A traced run's reduction: the recorded trace in a profile directory,
    read as ``run_cell`` reads it, puts ``span_reduce``'s result under
    ``rec["spans"]``, and the per-step readers give its numbers; the
    directory is removed."""
    import shutil
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    shutil.copy(TRACE, prof / "host.xplane.pb")
    with open(SCOPES) as f:
        scopes = json.load(f)
    logged = []
    xred, spans = H.read_trace(str(tmp_path), 1, scopes, log=logged.append)
    assert not tmp_path.exists()
    assert spans == red
    assert xred["modules"]["jit_zo_step"] and len(logged) == 2
    wl = H.workload("phi3-8l.hosgd-t4")
    cfg, tf = H.config(wl["config"]), H.traffic(wl["traffic"])
    win = {"wall_s": spans["window_s"], "dts": [0.0075] * 8,
           "kinds": H.step_kinds(4, 8)}
    rec = H.trace_record(cfg, tf, 1, win, xred, spans, "TPU v5 lite")
    assert rec["spans"] is spans and rec["n_steps"] == 8
    got = H.per_layer_metrics(wl["name"], rec)
    for name in ("zo_step.forward_device_s", "zo_step.direction_device_s",
                 "fo_step.backward_device_s", "fo_step.update_device_s",
                 "trainer.data_s"):
        assert got[name]["value"] == red["per_step"][name]
    # recorded before the flash kernels: no attn.flash time, no share
    assert "zo_step.attn_flash_roofline" not in got
    assert "fo_step.attn_flash_roofline" not in got
    assert rec["attention_flops"] == 8 * 4 * 32 * 96 * 2048.5 * 8 * 4096
