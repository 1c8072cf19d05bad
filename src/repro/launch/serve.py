"""Serving driver: offline batch generate or open-loop Poisson traffic.

Offline (default): submit a batch of random prompts to the continuous-
batching engine, print completions and measured tok/s.

Traffic (``--traffic poisson:RATE[,MIX]``): replay a seeded open-loop
workload (``repro.sim.traffic``) against the engine, price every scheduler
step with the training-side ``ComputeModel``, and report tokens/sec and
p50/p99 TTFT/latency.  ``--log`` writes one CSV row per request
(arrival/ttft/latency) through the context-managed ``CSVLogger``, as the
train/sim CLIs do.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.train import size_override
from repro.metrics import CSVLogger
from repro.models import transformer as T
from repro.serving import Engine, ServeConfig
from repro.sim.traffic import (
    MIXES,
    TrafficSpec,
    replay,
    replay_seed_sync,
    serve_compute_model,
)


def parse_traffic(arg: str, n_requests: int, seed: int, vocab: int) -> TrafficSpec:
    """``poisson:RATE[,MIX]`` -> TrafficSpec (MIX one of repro.sim.traffic.MIXES)."""
    kind, _, rest = arg.partition(":")
    if kind != "poisson" or not rest:
        raise SystemExit(f"unknown --traffic {arg!r}; want poisson:RATE[,MIX]")
    rate_s, _, mix = rest.partition(",")
    mix = mix or "mixed"
    if mix not in MIXES:
        raise SystemExit(f"unknown traffic mix {mix!r}; have {sorted(MIXES)}")
    return TrafficSpec.from_mix(rate=float(rate_s), n_requests=n_requests,
                                mix=mix, seed=seed, vocab=vocab)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=ARCH_IDS)
    ap.add_argument("--reduce", default="smoke", choices=["full", "100m", "smoke"])
    ap.add_argument("--batch", type=int, default=4,
                    help="offline: number of prompts; traffic: n_requests "
                         "(use --requests to override)")
    ap.add_argument("--requests", type=int, default=None,
                    help="traffic mode: number of arrivals (default --batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=8,
                    help="KV-cache slot pool size (max decode batch)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop a request when it emits this token (-1 = off)")
    ap.add_argument("--traffic", default=None,
                    help="open-loop workload, e.g. poisson:50.0,mixed")
    ap.add_argument("--flops-per-sec", type=float, default=1e12,
                    help="traffic mode: simulated accelerator throughput")
    ap.add_argument("--log", default=None,
                    help="CSV path for per-request latency rows")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="traffic mode: write a Perfetto trace of the "
                         "replay (one lane per serving slot)")
    args = ap.parse_args(argv)

    cfg = size_override(get_config(args.arch), args.reduce)
    if cfg.encoder_only or cfg.frontend != "none":
        raise SystemExit("choose a text decoder arch for serving")
    params = T.init_model(jax.random.key(args.seed), cfg)

    if args.traffic:
        spec = parse_traffic(args.traffic, args.requests or args.batch,
                             args.seed, cfg.vocab_size)
        eng = Engine(cfg, params, ServeConfig(
            max_seq=spec.required_max_seq(), temperature=args.temperature,
            eos_id=args.eos_id, slots=args.slots),
            key=jax.random.key(args.seed) if args.temperature > 0 else None)
        cm = serve_compute_model(cfg, args.flops_per_sec)
        tracer = None
        if args.trace:
            from repro.obs import Tracer
            tracer = Tracer()
        res = replay(eng, spec, cm, tracer=tracer)
        sync = replay_seed_sync(spec, cm, batch=args.slots)
        fields = ["rid", "arrival", "prompt_len", "max_new", "ttft",
                  "queue_s", "service_s", "latency", "finish"]
        with CSVLogger(args.log, fields) as log:
            for row in res.rows:
                log.log(**row)
        if tracer is not None:
            from repro.obs import write_trace
            write_trace(args.trace, tracer, title=f"serve:{args.traffic}")
            print(f"wrote trace {args.trace} ({len(tracer.spans)} spans)")
        s = res.summary
        print(f"traffic {args.traffic}: {int(s['n_requests'])} requests, "
              f"{int(s['total_tokens'])} tokens in {s['makespan_s']:.3f} sim-s "
              f"({s['tok_per_sec']:.1f} tok/s; wall {res.wall_s:.2f}s)")
        print(f"  ttft    p50 {s['p50_ttft_s']*1e3:.1f} ms   "
              f"p99 {s['p99_ttft_s']*1e3:.1f} ms   (queue p99 "
              f"{s['p99_queue_s']*1e3:.1f} ms + service p99 "
              f"{s['p99_service_s']*1e3:.1f} ms)")
        print(f"  latency p50 {s['p50_latency_s']*1e3:.1f} ms   "
              f"p99 {s['p99_latency_s']*1e3:.1f} ms")
        print(f"  seed-sync baseline (batch={args.slots}): "
              f"{sync.summary['tok_per_sec']:.1f} tok/s, "
              f"p99 latency {sync.summary['p99_latency_s']*1e3:.1f} ms")
        return

    eng = Engine(cfg, params, ServeConfig(
        max_seq=args.prompt_len + args.max_new, temperature=args.temperature,
        eos_id=args.eos_id, slots=args.slots))
    rng = np.random.default_rng(args.seed)
    prompts = [
        list(rng.integers(0, cfg.vocab_size, rng.integers(4, args.prompt_len + 1)))
        for _ in range(args.batch)
    ]
    t0 = time.perf_counter()
    outs = eng.generate(prompts, args.max_new, key=jax.random.key(args.seed))
    dt = time.perf_counter() - t0
    fields = ["rid", "prompt_len", "generated", "tokens"]
    with CSVLogger(args.log, fields) as log:
        n_tokens = 0
        for i, o in enumerate(outs):
            gen = o[len(prompts[i]):]
            n_tokens += len(gen)
            print(f"req{i}: prompt_len={len(prompts[i])} -> {gen}")
            log.log(rid=i, prompt_len=len(prompts[i]), generated=len(gen),
                    tokens=" ".join(map(str, gen)))
    tps = n_tokens / dt
    print(f"decoded {n_tokens} tokens over {args.slots} slots in {dt:.2f}s "
          f"({tps:.1f} tok/s)")


if __name__ == "__main__":
    main()
