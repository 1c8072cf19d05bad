"""The harness finds every cell's files by name, ``BENCHMARK.json`` keeps
to its format, and a run refuses a machine without a TPU."""
import json
import os
import re
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import pytest  # noqa: E402

import harness as H  # noqa: E402

SPEC = H.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51


def test_names_and_units():
    names = [c["name"] for c in SPEC["configs"]] + WORKLOADS + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("wl", WORKLOADS)
def test_cell_files_found_by_name(wl):
    w = H.workload(wl)
    cfg, tf = H.config(w["config"]), H.traffic(w["traffic"])
    assert cfg["name"] == w["config"]
    assert {"loss_gap", "grad_gap"} <= set(H.limits(wl)) <= {
        "loss_gap", "grad_gap", "change_gap_median", "loss_steps"}
    assert H.reference_module(cfg).DenseLM
    assert tf["rows_per_chip"] % cfg["model"]["grad_accum"] == 0
    argv = H.trainer_argv(cfg, tf, w["chips"], 2**31 + 7)
    assert argv[argv.index("--batch") + 1] == str(8 * w["chips"])


def test_config_files():
    for c in SPEC["configs"]:
        cfg = json.load(open(os.path.join(H.ROOT, c["file"])))
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["reduced"]) <= set(cfg["model"]) | {"linear_bias"}


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_found_by_name(metric):
    assert callable(H.metric_reader(metric))


def test_metric_readers_on_a_record():
    rec = {"chips": 1, "tau": 4, "n_steps": 8, "peak_flops": 197e12,
           "step_flops": {"fo": 3e14, "zo": 2e14},
           "window": {"wall_s": 24.0, "dts": [2.9] * 8,
                      "kinds": ["fo", "zo", "zo", "zo"] * 2},
           "trace": {"window_s": 24.0, "busy_s": 23.0,
                     "modules": {"jit_fo_step": [4.5, 4.7],
                                 "jit_zo_step": [2.3] * 6},
                     "collective_s": 0.0, "collective_exposed_s": 0.0}}
    got = {m["name"]: H.metric_reader(m["name"])(rec)
           for m in SPEC["per_layer"]}
    assert got["fo_step.device_s"] == pytest.approx(4.6)
    assert got["zo_step.device_s"] == pytest.approx(2.3)
    assert got["trainer.host_gap_s"] == pytest.approx(0.1)
    assert got["step.mfu"] == pytest.approx(100 * 1.8e15 / (24 * 197e12))
    assert got["device.idle_share"] == pytest.approx(100 / 24)
    assert got["exchange.exposed_s"] is None      # nothing to read: left out


def test_span_readers_on_a_record():
    """The readers of the program's spans: the per-step parts as
    ``span_reduce`` gives them, and attention's roofline shares from the
    FLOPs of a step's forwards over its ``attn.flash`` seconds; with no
    spans, or no ``attn.flash`` time, each finds nothing."""
    per_step = {"zo_step.forward_device_s": 1.15,
                "zo_step.direction_device_s": 0.114,
                "fo_step.backward_device_s": 1.12,
                "fo_step.update_device_s": 0.144, "trainer.data_s": 0.004}
    rec = {"attention_flops": 6.6e12, "peak_flops": 197e12, "spans": {
        "per_step": per_step, "programs": {
            "jit_zo_step": {"executions": 6, "by_layer": {
                "model.attn/attn.flash": 6 * 0.216, "model.mlp": 2.8}},
            "jit_fo_step": {"executions": 2, "by_layer": {
                "model.attn/attn.flash": 2 * 0.452}}}}}
    for name, value in per_step.items():
        assert H.metric_reader(name)(rec) == value
    assert H.metric_reader("zo_step.attn_flash_roofline")(rec) == \
        pytest.approx(100 * 2 * 6.6e12 / (0.216 * 197e12))
    assert H.metric_reader("fo_step.attn_flash_roofline")(rec) == \
        pytest.approx(100 * 4 * 6.6e12 / (0.452 * 197e12))
    dense = {**rec, "spans": {"per_step": {}, "programs": {
        "jit_zo_step": {"executions": 6,
                        "by_layer": {"model.attn/attn.dense": 3.0}}}}}
    for name in list(per_step) + ["zo_step.attn_flash_roofline",
                                  "fo_step.attn_flash_roofline"]:
        assert H.metric_reader(name)({}) is None
        assert H.metric_reader(name)(dense) is None


def tiny_program(**changes):
    from types import SimpleNamespace as NS
    from repro.configs import get_config
    return NS(cfg=get_config("phi3-mini-3.8b").reduced().with_(**changes))


def file_model(tr, **changes):
    keys = [k for k in H.config("phi3-mini-3.8b-8l")["model"]
            if k not in ("window", "embed_scale")]
    return {"name": "tiny", "model": dict(
        {k: getattr(tr.cfg, k) for k in keys}, embed_scale=True, **changes)}


def test_program_config_checked_by_layer_window():
    """A file's ``window`` is one value for every layer or a list, one a
    layer, held to the program's ``layer_windows()``."""
    tr = tiny_program(n_layers=4, window=8, layer_pattern="local_global")
    assert tr.cfg.layer_windows() == (8, None, 8, None)
    H.check_program_config(tr, file_model(tr, window=[8, None, 8, None]))
    for window in (8, None, [None, 8, None, 8], [8, None]):
        with pytest.raises(SystemExit, match="window"):
            H.check_program_config(tr, file_model(tr, window=window))
    full = tiny_program()
    H.check_program_config(full, file_model(full, window=None))
    H.check_program_config(full, file_model(full, window=[None] * 2))


@pytest.mark.parametrize("key,value", [("n_experts", 8), ("top_k", 6),
                                       ("d_ff", 1024)])
def test_program_config_checked_by_field(key, value):
    """Every key of the file's model that names a field of the program's
    ``ModelConfig`` is compared; ``embed_scale`` names none."""
    tr = tiny_program(n_experts=4, top_k=2)
    ok = file_model(tr, window=None, n_experts=4, top_k=2)
    H.check_program_config(tr, ok)
    H.check_program_config(tr, dict(ok, model=dict(ok["model"],
                                                   embed_scale=False)))
    with pytest.raises(SystemExit, match=key):
        H.check_program_config(tr, dict(ok, model=dict(ok["model"],
                                                       **{key: value})))


def test_step_estimates_and_window():
    warm = {"kinds": ["fo", "zo", "zo"], "dts": [6.0, 3.0, 2.3],
            "compile_s": [1.5, 0.7, 0.0]}
    est = H.step_estimates(warm)
    assert est == {"fo": 4.5, "zo": 2.3}
    assert H.window_steps(4, 20, est) == 8          # two periods at least
    assert H.window_steps(4, 30, est) == 12
    assert H.window_steps(1, 20, {"fo": 4.5}) == 5


def test_window_runs_on_where_the_estimate_overstates_a_period(monkeypatch):
    """The window is whole periods: where the periods the estimate gave fall
    short of the seconds, it runs on by as many as its own measured period
    says reach them."""
    from types import SimpleNamespace as NS
    clock = [0.0]
    monkeypatch.setattr(H, "time", NS(perf_counter=lambda: clock[0]))

    def run(tr, on_step):
        for t in range(tr.args.steps):
            kind = "fo" if t % 4 == 0 else "zo"
            dt = 2.3 if kind == "fo" else 1.3
            clock[0] += dt
            on_step(t, kind, 1.0, dt, None, None)

    train, tr = NS(run=run), NS(args=NS(steps=0))
    est = {"fo": 4.6, "zo": 1.37}                   # a period of 8.71 s
    win = H.timed_window(train, tr, 4, 20, est)
    # two estimated periods take 12.4 s: two more reach 20 s
    assert len(win["dts"]) == 16 and win["wall_s"] == pytest.approx(24.8)
    assert win["kinds"] == H.step_kinds(4, 4) * 4 and win["compiles"] == 0
    win = H.timed_window(train, tr, 4, 10, est)
    assert len(win["dts"]) == 8 and win["wall_s"] == pytest.approx(12.4)


def test_seed_program_steps():
    """Set-up's steps through the first ZO step go to the process that
    fills the compile cache; a cell without ZO steps has none."""
    got = {wl: H.seed_program_steps(H.traffic(H.workload(wl)["traffic"]))
           for wl in WORKLOADS}
    assert got["phi3-8l.hosgd-t4"] == 2
    assert got["phi3-8l.sync-t1"] == 0
    assert H.seed_program_steps({"tau": 2, "follow_steps": 1}) == 0


def test_program_feed_held_to_the_reference():
    from types import SimpleNamespace as NS
    from repro.data import token_batches
    train = NS(token_batches=token_batches)
    tr = NS(cfg=NS(vocab_size=500), args=NS(batch=2, seq=32, seed=2**31 + 5))
    H.check_program_feed(train, tr, {"zipf_a": 1.3})
    with pytest.raises(SystemExit, match="differs"):
        H.check_program_feed(train, tr, {"zipf_a": 1.1})


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", WORKLOADS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
