"""Chip smoke test: HO-SGD training of phi3-mini-3.8b on a TPU through the
trainer's own entry points (``repro.launch.train`` ``setup`` + ``run``).

The model keeps every published width (d_model 3072, 32 heads of 96, d_ff
8192, vocab 32064) and is cut to 8 of its 32 layers; weights are random
from ``--seed``.  Run from the root of a checkout on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # data-parallel HO-SGD on four chips

One chip runs two phases: the default ``fused`` engine for one HO-SGD
period plus one FO step (FO, 3 x ZO, FO), then the ``flat`` engine, whose
ZO step runs the Pallas kernels under Mosaic, for two steps, compared with
the first phase at the same step.  ``--chips 4`` runs only one period on a
``data=4`` mesh (m = 4 workers) and compares its first ZO step with the
single-device reference ``make_ho_sgd(...).step``.

Times printed are smoke timings of one short run, not benchmark metrics.
The script exits nonzero without a TPU and never falls back to the CPU.
Its last line on stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

CONFIG = ["--arch", "phi3-mini-3.8b", "--reduce", "full", "--layers", "8",
          "--seq", "4096", "--tau", "4", "--seed", "0"]

# Tolerances of the two comparisons (flat vs fused engine on one chip; the
# data=4 mesh vs the single-device reference on four).  Both sides run the
# same algorithm in different programs.
#
# Loss: the reported ZO-step loss is the mean pre-perturbation loss f0 of
# the same params on the same tokens.  Different programs may tile the bf16
# matmuls differently, which moves fp32 accumulation order and can flip a
# bf16 rounding of an activation; averaged over 32k tokens that stays far
# below 1e-3 relative.
LOSS_RTOL = 1e-3
# Params: the ZO coefficient c = (d / mu) * (f1 - f0) amplifies that
# rounding noise by d / mu (~1e12 here), so the two sides may apply
# different updates.  Each element may therefore differ by up to the
# larger ZO update of the two sides, plus one bf16 rounding step (2**-7
# relative).
PARAM_RTOL = 2.0 ** -7


def check_device(chips: int):
    devs = jax.devices()
    d0 = devs[0]
    print(f"jax {jax.__version__} platform={d0.platform} "
          f"kind={d0.device_kind} count={len(devs)}")
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {d0.platform}")
    if len(devs) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices; "
                         f"JAX found {len(devs)}")
    return d0


def peak_bytes_in_use() -> int:
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


class StepLog:
    """Per-step smoke timings: backend compile seconds (from JAX's
    monitoring events), blocking step seconds, and device 0's
    ``peak_bytes_in_use`` after the step."""

    def __init__(self):
        self.compile_s = 0.0
        self.rows = []
        self.saved = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def take_compile(self) -> float:
        s, self.compile_s = self.compile_s, 0.0
        return s

    def hook(self, keep):
        """``on_step`` callback; ``keep(t, params, batch)`` may store host
        copies in ``self.saved``."""
        def on_step(t, name, loss, dt, params, batch):
            peak = peak_bytes_in_use()
            row = dict(t=t, kind=name, loss=loss, step_s=dt,
                       compile_s=self.take_compile(), peak_bytes_in_use=peak)
            self.rows.append(row)
            print(f"  smoke t={t} {name} loss={loss:.6f} step_s={dt:.3f} "
                  f"compile_s={row['compile_s']:.3f} "
                  f"peak_bytes_in_use={peak}", flush=True)
            keep(t, params, batch)
        return on_step

    def summary(self, label: str):
        """One line per step kind: first call's compile seconds, the
        steady step seconds (calls after the first), the highest peak."""
        for kind in ("fo", "zo"):
            rows = [r for r in self.rows if r["kind"] == kind]
            if not rows:
                continue
            steady = [r["step_s"] for r in rows[1:]]
            print(f"smoke timing [{label}] {kind}: "
                  f"compile_s={rows[0]['compile_s']:.3f} "
                  f"first_step_s={rows[0]['step_s']:.3f} "
                  f"steady_step_s={min(steady) if steady else float('nan'):.3f} "
                  f"peak_bytes_in_use={max(r['peak_bytes_in_use'] for r in rows)}")


def check_losses(log: StepLog):
    bad = [r for r in log.rows if not math.isfinite(r["loss"])]
    if bad:
        raise SystemExit(f"non-finite losses: {bad}")


def check_ledger(tr, zo_bytes: int):
    """Every comm line must show measured == analytic; ZO moves 4*m bytes."""
    for line in tr.comm_lines():
        print(line)
        got = re.search(r"measured=([\d.]+),analytic=([\d.]+)", line)
        if got and float(got.group(1)) != float(got.group(2)):
            raise SystemExit(f"ledger disagrees with the analytic bytes: {line}")
    if tr.ledger.bytes_per_step("zo") != zo_bytes:
        raise SystemExit(f"ZO step booked {tr.ledger.bytes_per_step('zo')} "
                         f"bytes, expected 4*m = {zo_bytes}")
    print(f"ledger ok: fo={tr.ledger.bytes_per_step('fo')} bytes "
          f"(d={tr.d}), zo={tr.ledger.bytes_per_step('zo')} bytes (m={tr.m})")


def host(tree):
    return jax.tree.map(np.asarray, jax.device_get(tree))


def compare_params(a, b, before, label: str):
    """Elementwise |a - b| <= PARAM_RTOL * max(|a|, |b|) + U over every
    leaf, U the largest |update| of either side from ``before``."""
    leaves = list(zip(*(jax.tree.leaves(t) for t in (a, b, before))))
    f32 = lambda *xs: [x.astype(np.float32) for x in xs]
    upd = 0.0
    for x, y, p in leaves:
        x, y, p = f32(x, y, p)
        if x.size:
            upd = max(upd, float(np.max(np.abs(x - p))),
                      float(np.max(np.abs(y - p))))
    n_diff = n_bad = n = 0
    worst = 0.0
    for x, y, _ in leaves:
        x, y = f32(x, y)
        d = np.abs(x - y)
        lim = PARAM_RTOL * np.maximum(np.abs(x), np.abs(y)) + upd
        n += x.size
        n_diff += int(np.count_nonzero(d))
        n_bad += int(np.count_nonzero(d > lim))
        worst = max(worst, float(np.max(d)) if d.size else 0.0)
    print(f"{label}: {n_diff} of {n} elements differ, max |diff|={worst:.3e},"
          f" largest ZO update={upd:.3e}, {n_bad} beyond tolerance")
    if n_bad:
        raise SystemExit(f"{label}: {n_bad} elements beyond tolerance")


def count_changed(a, b) -> int:
    return sum(int(np.count_nonzero(x != y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def assert_mosaic_zo_step(tr, batch):
    """The flat engine's ZO step hands its kernels to Mosaic: interpret
    mode is off, and the compiled step holds ``tpu_custom_call`` sites.
    Lowered under the trainer's mesh, as ``run`` calls it, the step's
    ``compile()`` returns the executable jit already built."""
    from repro.kernels import ops
    if ops.INTERPRET:
        raise SystemExit("kernels would run in interpret mode on this device")
    t0 = time.perf_counter()
    with jax.set_mesh(tr.mesh):
        text = tr.jitted["zo"].lower(jnp.int32(1), tr.params, tr.opt_state,
                                     batch).compile().as_text()
    n_kernels = text.count("tpu_custom_call")
    print(f"flat ZO step: {n_kernels} tpu_custom_call sites in the compiled "
          f"program ({time.perf_counter() - t0:.3f} s to fetch it)")
    if not n_kernels:
        raise SystemExit("the flat ZO step holds no Mosaic kernel")


def one_chip(train):
    log = StepLog()

    def keep_main(t, params, batch):
        if t in (0, 1):
            log.saved[t] = host(params)

    print("== phase 1: engine=fused, FO + 3 x ZO + FO ==", flush=True)
    tr = train.setup(train.parse_args(CONFIG + ["--batch", "8", "--steps", "5"]))
    log.take_compile()                  # set-up's own compiles (init, puts)
    train.run(tr, log.hook(keep_main))
    check_losses(log)
    check_ledger(tr, 4 * tr.m)
    log.summary("fused")
    main_rows, saved = log.rows, log.saved
    print(f"ZO step t=1 changed {count_changed(saved[0], saved[1])} of "
          f"{tr.d} parameters")
    del tr

    print("== phase 2: engine=flat (Pallas kernels under Mosaic), 2 steps ==",
          flush=True)
    log.rows, log.saved = [], {}
    batches = {}

    def keep_flat(t, params, batch):
        if t == 1:
            log.saved[1] = host(params)
            batches[1] = batch

    tr = train.setup(train.parse_args(
        CONFIG + ["--batch", "8", "--steps", "2", "--engine", "flat"]))
    log.take_compile()
    train.run(tr, log.hook(keep_flat))
    check_losses(log)
    log.summary("flat")
    assert_mosaic_zo_step(tr, batches[1])
    flat_loss = log.rows[1]["loss"]
    main_loss = main_rows[1]["loss"]
    print(f"ZO step t=1 loss: fused={main_loss!r} flat={flat_loss!r}")
    if abs(flat_loss - main_loss) > LOSS_RTOL * abs(main_loss):
        raise SystemExit("flat and fused ZO-step losses disagree")
    compare_params(saved[1], log.saved[1], saved[0],
                   "params after ZO step t=1, flat vs fused")


def four_chips(train):
    from repro.core.ho_sgd import HOSGDConfig, make_ho_sgd
    from repro.models import transformer as T
    from repro.opt.optimizers import const_schedule, sgd

    log = StepLog()

    def keep(t, params, batch):
        if t == 0:
            log.saved["before"] = host(params)
        if t == 1:
            log.saved["after"] = host(params)
            log.saved["batch"] = host(batch)

    print("== data-parallel HO-SGD: data=4 mesh, m=4, one period ==",
          flush=True)
    args = train.parse_args(CONFIG + ["--batch", "32", "--steps", "4"])
    tr = train.setup(args)
    log.take_compile()
    if tr.m != 4:
        raise SystemExit(f"expected m=4 workers, got {tr.m}")
    train.run(tr, log.hook(keep))
    check_losses(log)
    check_ledger(tr, 16)
    log.summary("data=4")
    cfg, d, m = tr.cfg, tr.d, tr.m
    dist_loss = log.rows[1]["loss"]
    del tr

    print("== reference: single-device make_ho_sgd(...).step at m=4 ==",
          flush=True)
    ho = HOSGDConfig(tau=1 << 30, mu=args.mu, m=m, lr=args.lr,
                     zo_lr=args.lr * 50.0 / d, seed=args.seed)
    ref = make_ho_sgd(lambda p, b: T.loss_fn(cfg, p, b), ho,
                      sgd(const_schedule(args.lr)))
    dev0 = jax.devices()[0]
    params = jax.device_put(log.saved["before"], dev0)
    batch = jax.device_put(log.saved["batch"], dev0)
    t0 = time.perf_counter()
    params, _, metrics = ref.step(1, params, ref.init(params), batch)
    ref_loss = float(metrics["loss"])
    print(f"reference ZO step: {time.perf_counter() - t0:.3f} s incl. "
          f"compile; loss={ref_loss!r} distributed loss={dist_loss!r}")
    if abs(ref_loss - dist_loss) > LOSS_RTOL * abs(ref_loss):
        raise SystemExit("distributed and reference ZO-step losses disagree")
    ref_params = host(params)
    print(f"ZO step t=1 changed {count_changed(log.saved['before'], ref_params)}"
          f" of {d} parameters (reference)")
    compare_params(log.saved["after"], ref_params, log.saved["before"],
                   "params after ZO step t=1, data=4 vs single device")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the data-parallel path on four chips")
    chips = ap.parse_args(argv).chips
    from repro.launch import train
    from repro.launch.xla import use_compile_cache
    cache = use_compile_cache()
    d0 = check_device(chips)
    print("compile cache:", cache)
    if chips == 4:
        four_chips(train)
    else:
        one_chip(train)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
