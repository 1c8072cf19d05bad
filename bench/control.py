"""Readings that set the upper end of a cell's limits, on the chip.

    python3 bench/control.py --workload NAME --seed S [--seed S ...]

Per seed, in one process: the plain reference follows the cell's first
steps at the configuration's precision, then its control does the same with
every matmul operand rounded to the precision below (float8 e4m3 for
bfloat16), in the program's place.  Read at no extra cost from the
reference's own first step: the same step as a run that left half of its
rows out (the mean over the rest), and on several chips as device 0 sees it
when the gradient exchange is left out (its own rows only).  Prints one JSON
line per seed with each number the comparison reads and, under
``correct``, whether the control and each fault pass the cell's own limits
(``limits/<workload>.json``): each has to come out false.  The benchmark's
runs do not run this; the limits files record what it read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--devices", type=int, default=0,
                    help="chips to spread the rows over (default: the "
                         "cell's); 1 follows every worker on one chip")
    args = ap.parse_args(argv)

    import jax
    import harness as H
    from repro.launch.xla import use_compile_cache
    use_compile_cache()
    wl = H.workload(args.workload)
    cfg, tf = H.config(wl["config"]), H.traffic(wl["traffic"])
    n_dev = args.devices or wl["chips"]
    devices = jax.devices()[:n_dev]
    if devices[0].platform != "tpu" or len(devices) < n_dev:
        raise SystemExit(f"needs {n_dev} TPU chips; JAX found "
                         f"{jax.devices()}")
    lowp = H.reference_module(cfg).LOWER[cfg["model"]["dtype"]]
    lim = H.limits(wl["name"])
    no_limits = {k: float("inf") for k in ("loss_gap", "grad_gap",
                                           "change_gap_median")}
    n = tf["follow_steps"]
    for seed in args.seed:
        t0 = time.perf_counter()
        ref = H.reference_readings(cfg, tf, wl["chips"], seed, devices, n,
                                   faults=True)
        t1 = time.perf_counter()
        ctl = H.reference_readings(cfg, tf, wl["chips"], seed, devices, n,
                                   lowp=lowp)
        t2 = time.perf_counter()
        out = {"seed": seed, "reference_s": t1 - t0, "control_s": t2 - t1,
               "reference_losses": ref["losses"],
               "control_loss_gap_by_step": [
                   abs(c - r) / abs(r)
                   for c, r in zip(ctl["losses"], ref["losses"])],
               "control": {k: v for k, v, _ in
                           H.compare(ctl, ref, no_limits, None)},
               "control_change_gap_worst": float(max(H.leaf_gaps(
                   ctl["change_norms"], ref["change_norms"])))}
        out["correct"] = {"control": H.is_correct(H.compare(ctl, ref, lim,
                                                            None))}
        loss0 = ref["losses"][0]
        for key, fault in (("half", "half_batch"),
                           ("solo", "exchange_left_out")):
            if f"{key}_grad_norms" in ref:
                out[fault] = {
                    "loss_gap": abs(ref[f"{key}_loss0"] - loss0) / abs(loss0),
                    "grad_gap": float(max(H.leaf_gaps(
                        ref[f"{key}_grad_norms"], ref["grad_norms"])))}
                # read at the first step alone, where its loss is step 0's
                out["correct"][fault] = H.is_correct(
                    [(k, v, lim[k]) for k, v in out[fault].items()
                     if k in lim])
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
