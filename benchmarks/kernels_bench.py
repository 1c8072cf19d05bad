"""Kernel micro-benchmarks: Pallas (interpret) vs jnp oracle.

On this CPU container interpret-mode timing measures Python dispatch, not
TPU performance — the number that matters for the roofline is the HBM-bytes
model printed per kernel (what the fused kernel reads/writes vs the jnp
path; see kernels/*.py docstrings and EXPERIMENTS.md §Perf).

Emits root-level ``BENCH_kernels.json`` (``--out``) so the kernel perf
trajectory is tracked like the sim/serve frontiers: per-kernel rows plus the
per-engine ZO-round comparison (step time, direction-bytes model, kernel
launches per round, and HBM passes over d for the reconstruct→optimizer
commit phase — the axis the ``flat`` backend collapses from 4 to 2).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def timeit(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))        # one warmup dispatch (compile)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return 1e6 * (time.perf_counter() - t0) / reps


def engine_compare(smoke: bool = False):
    """End-to-end ZO step per DirectionEngine backend: step time + the
    direction-algebra HBM-bytes model.

    The bytes column counts only traffic for handling the direction vector
    (the loss evaluations are identical across backends), fp32, d params,
    m workers, per ZO step:

    * tree   — v materialized per use: perturb m*(v write + v read + x
               read + x~ write) = 16*d*m; reconstruct m*(v write + v read +
               acc read + acc write) = 16*d*m.
    * fused  — generation fused into the consuming op (no v buffer):
               perturb m*(x read + x~ write) = 8*d*m; reconstruct
               acc kept live through the worker loop = 8*d*m.
    * pallas — perturb m*(x read + x~ write) = 8*d*m; reconstruct all m
               workers in one pass = one 4*d write (acc in registers).
    * flat   — perturb m*(x read + x~ write) = 8*d*m (the tree-wide sumsq
               accumulates in the same grid, so the separate inv-norm pass
               disappears); the reconstructed update never exists in HBM —
               it goes straight into the in-kernel SGD commit.

    Two more roofline axes, per ZO round (m workers, L leaves, no momentum):

    * ``kernel_launches`` — pallas launches one kernel per leaf per perturb
      plus one per leaf for reconstruct = L*(m+1); flat launches one kernel
      per perturb plus one fused commit = m+1; tree/fused launch none (pure
      XLA programs, counted 0).
    * ``hbm_passes_over_d_commit`` — d-sized buffer passes in the
      reconstruct→optimizer-commit phase: unfused backends write the update
      (1), the optimizer reads it (1) and reads+writes params (2) = 4
      (momentum adds 2 more); flat reads+writes params once in the commit
      kernel = 2 (momentum rides the same launch).

    On this CPU container interpret-mode timing measures dispatch, not TPU
    performance — the bytes model is the roofline-relevant number; the
    timings only sanity-check that every backend drives the identical
    optimizer step.
    """
    from repro.core.ho_sgd import HOSGDConfig, make_ho_sgd

    d_leaf = (1 << 12) + 321 if smoke else (1 << 18) + 321  # odd: tail blocks
    m, B = 4, 8
    params = {"w": jax.random.normal(jax.random.key(1), (d_leaf,)),
              "b": jax.random.normal(jax.random.key(2), (257,))}
    d = d_leaf + 257
    n_leaves = len(jax.tree.leaves(params))

    def loss_fn(p, b):
        return 0.5 * jnp.mean(jnp.sum((p["w"][None, :] - b["t"]) ** 2, -1)) \
            + 0.5 * jnp.sum(p["b"] ** 2)

    batch = {"t": jax.random.normal(jax.random.key(3), (m * B, d_leaf))}
    bytes_model = {
        "tree": 32 * d * m,
        "fused": 16 * d * m,
        "pallas": 8 * d * m + 4 * d,
        "flat": 8 * d * m,
    }
    launches = {
        "tree": 0,
        "fused": 0,
        "pallas": n_leaves * (m + 1),
        "flat": m + 1,
    }
    commit_passes = {"tree": 4, "fused": 4, "pallas": 4, "flat": 2}
    rows = []
    print("engine,us_per_zo_step,direction_bytes_model,kernel_launches,"
          "hbm_passes_over_d_commit,loss")
    for name in ("tree", "fused", "pallas", "flat"):
        cfg = HOSGDConfig(tau=1 << 30, mu=1e-3, m=m, lr=0.05, zo_lr=0.05 / d,
                          engine=name)
        meth = make_ho_sgd(loss_fn, cfg)
        state = meth.init(params)

        def one_step(p, s):
            p, s, metrics = meth.step(1, p, s, batch)
            return p, s, metrics["loss"]

        p1, s1, loss = one_step(params, state)          # compile + warm
        t0 = time.perf_counter()
        reps = 2 if smoke else 5
        for _ in range(reps):
            _, _, l = one_step(params, state)
        jax.block_until_ready(l)
        us = 1e6 * (time.perf_counter() - t0) / reps
        print(f"engine/{name},{us:.0f},{bytes_model[name]},{launches[name]},"
              f"{commit_passes[name]},{float(loss):.6f}")
        rows.append({
            "engine": name,
            "us_per_zo_step": us,
            "direction_bytes_model": bytes_model[name],
            "kernel_launches_per_zo_round": launches[name],
            "hbm_passes_over_d_commit": commit_passes[name],
            "loss": float(loss),
        })
    return {"d": d, "m": m, "n_leaves": n_leaves, "momentum": 0.0,
            "engines": rows}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes / few reps (CI tier-2)")
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCH_kernels.json"),
                    help="BENCH json output path ('' disables)")
    args = ap.parse_args(argv)
    smoke = args.smoke

    key = jax.random.key(0)
    kernel_rows = []

    def row(name, us, hbm_kernel, hbm_jnp):
        print(f"{name},{us:.0f},{hbm_kernel},{hbm_jnp}")
        kernel_rows.append({"name": name, "us_per_call": us,
                            "hbm_bytes_kernel": hbm_kernel,
                            "hbm_bytes_jnp": hbm_jnp})

    print("name,us_per_call,hbm_bytes_kernel,hbm_bytes_jnp")

    # rmsnorm: kernel reads x + writes y; jnp identical (fused either way)
    x = jax.random.normal(key, (2048, 1024))
    s = jnp.ones((1024,))
    nb = x.size * 4 * 2
    row("kern/rmsnorm", timeit(lambda a, b: ops.rmsnorm(a, b), x, s), nb, nb)

    # flash attention: kernel never materializes (S,S) probs
    B, S, H, hd = 1, (128 if smoke else 512), 4, 64
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))
    t = timeit(lambda a, b, c: ops.flash_attention(a, b, c, block_q=128,
                                                   block_k=128), q, k, v)
    io = 4 * B * S * H * hd * 4
    probs = B * H * S * S * 4
    row("kern/flash_attention", t, io, io + 2 * probs)

    # selective scan: kernel keeps (di, n) state in VMEM; jnp materializes
    # (B, S, di, n) twice (deltaA, deltaBu) plus the scanned h
    B, S, di, n = 2, (64 if smoke else 256), (64 if smoke else 256), 16
    u = jax.random.normal(key, (B, S, di)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3), (B, S, di))) * 0.1
    Bm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, n))
    Cm = jax.random.normal(jax.random.fold_in(key, 5), (B, S, n))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 6), (di, n)) * 0.2)
    Dp = jnp.ones((di,))
    t = timeit(lambda *a: ops.selective_scan(*a, block_d=128, block_s=128),
               u, dt, Bm, Cm, A, Dp)
    io = (3 * B * S * di + 2 * B * S * n) * 4
    state4d = 3 * B * S * di * n * 4
    row("kern/selective_scan", t, io, io + state4d)

    # zo perturb: kernel = 1 read + 1 write of x (direction never in HBM);
    # jnp path additionally writes+reads the direction.  Odd size: the tail
    # block exercises the masked-boundary path.
    npar = (1 << 14) + 321 if smoke else (1 << 20) + 321
    xx = jax.random.normal(key, (npar,))
    t = timeit(lambda a: ops.zo_perturb(a, 55, 0.01, 0, block=8192), xx)
    row("kern/zo_perturb", t, npar * 4 * 2, npar * 4 * 4)

    # zo reconstruct (m=8): kernel = 1 write; jnp = m reads + m writes
    m = 8
    salts = jnp.arange(m, dtype=jnp.uint32)
    coeffs = jnp.linspace(-1, 1, m, dtype=jnp.float32)
    t = timeit(lambda s_, c_: ops.zo_reconstruct(npar, s_, c_, 0, block=8192),
               salts, coeffs)
    row("kern/zo_reconstruct", t, npar * 4, npar * 4 * 2 * m)

    # flat multi-leaf kernels on a block-aligned packed buffer: perturb+sumsq
    # is one launch = 1 read + 1 write of x (the inv-norm pass over d is
    # gone — jnp pays an extra generate+reduce read-equivalent); the fused
    # reconstruct+SGD commit is 1 read + 1 write of params with the update
    # never materialized (jnp: update write + update read + params
    # read/write).
    block = 8192
    nblk = -(-npar // block)
    pad = nblk * block - npar
    xflat = jnp.pad(xx, (0, pad))
    # one leaf: the per-leaf layout tables (first block, element count)
    starts = jnp.zeros((1,), jnp.int32)
    sizes = jnp.full((1,), npar, jnp.int32)
    t = timeit(lambda a: ops.zo_perturb_sumsq(
        a, starts, sizes, jnp.full((1,), 55, jnp.uint32), 1e-3, block=block),
        xflat)
    row("kern/zo_perturb_sumsq", t, npar * 4 * 2, npar * 4 * 3)

    bf16 = jnp.zeros((1,), jnp.int32)
    # the params buffer is DONATED (updated in place) — hand the kernel a
    # fresh copy per call so timing iterations don't reuse a deleted buffer
    t = timeit(
        lambda a, c_: ops.zo_reconstruct_update(
            a.copy(), None, starts, sizes, bf16, salts[:, None], c_, 0.05,
            block=block)[0],
        xflat, coeffs)
    row("kern/zo_reconstruct_update", t, npar * 4 * 2, npar * 4 * 4)

    zo_round = engine_compare(smoke)

    if args.out:
        payload = {
            "generated_by": "benchmarks/kernels_bench.py",
            "smoke": smoke,
            "backend": jax.default_backend(),
            "interpret": bool(ops.INTERPRET),
            "kernels": kernel_rows,
            "zo_round": zo_round,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
