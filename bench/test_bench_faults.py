"""The comparison that decides ``correct``: a sound run passes, and the
control and each fault a training cell can have fail.

Small sizes on the CPU: the program is the smoke-size phi3 trainer (float32,
2 layers, d_model 256) driven by the harness as a run drives it, the chip
check skipped; the limits are those of the ``phi3-8l.hosgd-t4`` cell.  The
control is the reference in the program's place at the precision below the
configuration's (float8 operands for a bfloat16 model).  The exchange fault
needs four devices and runs in a child process with four CPU devices.
The control is also held to each cell's own limits, at the cell's period
and number of workers.
"""
import json
import os
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness as H  # noqa: E402
import pytest  # noqa: E402

LIM = H.limits("phi3-8l.hosgd-t4")
SEED = 2**31 + 99


def tiny(dtype=None, tau=4):
    from repro.configs import get_config
    c = get_config("phi3-mini-3.8b").reduced()
    model = {k: getattr(c, k) for k in H.config("phi3-mini-3.8b-8l")["model"]
             if k not in ("window", "embed_scale")}
    model.update(window=None, embed_scale=True)
    if dtype:
        model["dtype"] = dtype
    cfg = {"name": "tiny", "reference": "dense_lm", "model": model,
           "program": {"arch": "phi3-mini-3.8b", "reduce": "smoke",
                       "layers": 2}}
    tf = {"rows_per_chip": 4, "seq": 64, "zipf_a": 1.3, "tau": tau, "mu": 1e-3,
          "lr": 3e-2, "zo_lr": None, "engine": "fused", "fo_buckets": 1,
          "follow_steps": 3}
    return cfg, tf


def drive(hook=None, chips=1):
    import jax
    cfg, tf = tiny()
    return H.run_cell({"name": "tiny", "chips": chips}, cfg, tf, LIM, SEED,
                      0.5, False, jax.devices(), time.perf_counter(),
                      program_hook=hook, log=lambda *a: None)


def wrap_steps(tr, fn):
    for k in list(tr.steps):
        tr.steps[k] = fn(tr.steps[k])


def state_unchanged(train, tr):
    def broken(step):
        return lambda t, p, o, b: (p, o, step(t, p, o, b)[2])
    wrap_steps(tr, broken)


def keep_rows(lo, hi):
    """Labels outside rows [lo, hi) set to -1: the loss is the mean over
    the rest."""
    import jax.numpy as jnp

    def hook(train, tr):
        def broken(step):
            def f(t, p, o, b):
                lab = b["labels"]
                rows = jnp.arange(lab.shape[0])
                keep = (rows >= lo) & (rows < hi)
                return step(t, p, o, {**b, "labels": jnp.where(
                    keep[:, None], lab, -1)})
            return f
        wrap_steps(tr, broken)
    return hook


def test_fill_cache_runs_set_up_through_the_first_zo_step():
    from repro.launch import train
    cfg, tf = tiny()
    seen = []
    run = train.run
    train.run = lambda tr, on_step=None: seen.append(tr.args.steps) or run(tr)
    try:
        H.fill_cache(cfg, tf, 1, SEED)
    finally:
        train.run = run
    assert seen == [2]


def test_sound_run_is_correct():
    res = drive()
    assert res["correct"], res["checks"]


def test_state_left_unchanged_fails():
    res = drive(state_unchanged)
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] > 0.99


def test_half_the_batch_left_out_fails():
    res = drive(keep_rows(0, 2))
    assert not res["correct"], res["checks"]


def test_control_fails():
    import jax
    cfg, tf = tiny(dtype="bfloat16")
    dev = jax.devices()[:1]
    rm = H.reference_module(cfg)
    ref = H.reference_readings(cfg, tf, 1, SEED, dev, 3)
    ctl = H.reference_readings(cfg, tf, 1, SEED, dev, 3,
                               lowp=rm.LOWER[cfg["model"]["dtype"]])
    checks = H.compare(ctl, ref, LIM, None)
    assert not H.is_correct(checks), checks
    assert H.is_correct(H.compare(ref, ref, LIM, None))


@pytest.mark.parametrize("wl", [w["name"] for w in H.benchmark()["workloads"]
                                if w["name"] != "phi3-8l.hosgd-t4"])
def test_control_fails_each_cell(wl):
    """The control against each other cell's own limits file, with its tau
    and its workers (all followed on one device); ``test_control_fails``
    holds it to ``phi3-8l.hosgd-t4``'s."""
    import jax
    w = H.workload(wl)
    lim = H.limits(wl)
    cfg, tf = tiny(dtype="bfloat16", tau=H.traffic(w["traffic"])["tau"])
    dev = jax.devices()[:1]
    lowp = H.reference_module(cfg).LOWER[cfg["model"]["dtype"]]
    ref = H.reference_readings(cfg, tf, w["chips"], SEED, dev, 3)
    ctl = H.reference_readings(cfg, tf, w["chips"], SEED, dev, 3, lowp=lowp)
    assert not H.is_correct(H.compare(ctl, ref, lim, None))
    assert H.is_correct(H.compare(ref, ref, lim, None))


def test_exchange_left_out_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, broken = json.loads(p.stdout.strip().splitlines()[-1])
    assert sound["correct"], sound["checks"]
    assert not broken["correct"], broken["checks"]


if __name__ == "__main__":
    # four CPU devices: a sound data-parallel run, then one whose device 0
    # learns from its own rows alone (the gradient and coefficient exchange
    # left out, as device 0 would see it)
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    out = [drive(chips=4), drive(keep_rows(0, 4), chips=4)]
    print(json.dumps([{k: r[k] for k in ("correct", "checks")} for r in out]))
