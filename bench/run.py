"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, chip count, comparison limits and
per-layer metric readers are found by name (see ``harness.py``).  It needs a
TPU with at least the cell's chips and exits nonzero, printing no result,
without one.  Its last line on stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``), and last ``checks``, each number compared beside its limit.
The same numbers close standard error.

Where set-up has a ZO step, whose program the direction seed is part of, a
child process (``--fill-cache``) first compiles it for this seed into the
compile cache; ``setup_s`` starts when that process has ended, so that it
measures set-up from a cache that holds every program of the run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--fill-cache", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    import harness as H
    wl = H.workload(args.workload)
    cfg, tf = H.config(wl["config"]), H.traffic(wl["traffic"])
    lim = H.limits(wl["name"])
    t_start = T_START
    if not args.fill_cache and H.seed_program_steps(tf):
        # the child holds the chips until it exits; this process touches
        # JAX only after that
        rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                             "--workload", args.workload, "--seed",
                             str(args.seed), "--seconds", str(args.seconds),
                             "--fill-cache"],
                            stdout=sys.stderr, timeout=1200).returncode
        if rc != 0:
            log(f"filling the compile cache failed (exit code {rc})")
            return rc
        t_start = time.perf_counter()

    from repro.launch.xla import use_compile_cache
    log("compile cache:", use_compile_cache())
    import jax
    devices = jax.devices()
    d0 = devices[0]
    log(f"jax {jax.__version__}: {len(devices)} x {d0.platform} "
        f"({d0.device_kind})")
    if d0.platform != "tpu":
        log(f"no TPU: JAX found {d0.platform}; refusing to run")
        return 3
    if len(devices) < wl["chips"]:
        log(f"{wl['name']} needs {wl['chips']} chips; JAX found {len(devices)}")
        return 3
    if args.fill_cache:
        H.fill_cache(cfg, tf, wl["chips"], args.seed)
        return 0

    res = H.run_cell(wl, cfg, tf, lim, args.seed, args.seconds,
                     bool(args.trace), devices, t_start, log=log)
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
