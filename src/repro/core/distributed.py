"""Mesh-distributed HO-SGD: the production lowering of the round-program IR.

The method itself — per-worker rounds with an FO gradient sync every tau
iterations — is defined ONCE in ``repro.core.rounds`` (``fo_round`` /
``zo_round`` / ``ho_sgd_program``).  This module LOWERS those rounds to a
device mesh, fusing each round's per-worker locals + collective + apply
into one jitted program:

* ``make_fo_step``  — lowers the FO round (eq. 3): pjit data-parallel step
  whose d-dimensional gradient all-reduce over the worker axes is inserted
  by XLA (this is the expensive collective the paper amortizes over tau).
  The round's wire codec lowers to a per-worker encode + reducer decode
  (``compress_mode="per_worker"``, QSGD's real protocol, booked at
  ``nbytes`` × m) or the legacy post-reduction simulation
  (``"legacy"``, booked at one worker's ``nbytes``).
* ``make_zo_step``  — lowers the ZO round (eq. 4-6): partial-auto
  ``jax.shard_map`` (manual over worker axes).  Each worker evaluates the
  loss twice on its local shard, all-gathers **one scalar per worker**,
  regenerates every worker's direction from the pre-shared seed, and
  reconstructs the update locally.  Inter-worker traffic: 4*m bytes —
  independent of d.

On the synchronous full-membership path the lowered programs are
bit-identical to the pre-IR step functions (pinned by
``tests/test_rounds_equivalence.py``); the simulator replays the SAME
rounds per worker (``repro.sim.runner``) when membership or staleness
makes the monolithic fusion unfaithful.
"""
from __future__ import annotations

import math
import warnings
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import rounds
from repro.core.engine import ENGINES, make_engine
from repro.core.ho_sgd import HOSGDConfig
from repro.dist import collectives as coll
from repro.dist.compress import Compressor, compress_tree
from repro.dist.sharding import batch_specs, named, param_specs, worker_axes
from repro.opt.optimizers import Optimizer, apply_deltas, const_schedule, sgd


def _replicated_specs(tree: Any) -> Any:
    return jax.tree.map(lambda _: P(), tree)


def _mesh_workers(mesh: Mesh) -> int:
    # host-side mesh arithmetic: plain ints, never jax arrays
    return max(1, math.prod(mesh.shape[a] for a in worker_axes(mesh)))


def make_fo_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    mesh: Mesh,
    opt: Optimizer,
    grad_accum: int = 1,
    scan_unroll: bool = False,
    compressor: Optional[Compressor] = None,
    seed: int = 0,
    compress_mode: str = "per_worker",
    m: Optional[int] = None,
    buckets: int = 1,
) -> Callable:
    """jit(train_step): (t, params, opt_state, batch) -> (params, state, loss).

    Lowers ``rounds.fo_round`` to the mesh.  ``grad_accum`` splits the batch
    into microbatches scanned sequentially with an fp32 gradient accumulator
    — bounds the backward residual stack (n_layers * tokens_mb * d_model per
    device) that dominates train memory.

    ``compressor`` hooks a QSGD/signSGD/top-k codec onto the gradient
    all-reduce through the round's wire hook.  ``compress_mode="per_worker"``
    (the faithful protocol) splits the batch over the ``m`` workers
    in-program, encodes each worker's shard gradient independently and
    decodes at the reducer — the step books ``nbytes`` × m wire bytes (each
    worker receives every worker's code).  Cost of that fidelity: the m
    shard gradients are materialized together (m× the gradient memory of
    the fused data-parallel path) and the m codec round-trips serialize —
    fine for the simulator's models and CPU rehearsals; pass
    ``compress_mode="legacy"`` (CLI ``--compress-mode legacy``) on
    LLM-scale meshes where the post-reduction approximation is the right
    trade.  ``"legacy"`` keeps the historical post-reduction simulation
    ``decode(encode(g))`` on the reduced gradient, booked at one worker's
    ``nbytes``; ``grad_accum > 1`` falls back to it with a warning (the
    microbatch scan collapses the per-worker gradients).  ``m`` defaults to
    the mesh's worker count; with ``m == 1`` the two modes coincide and the
    program is bit-identical to the uncompressed-era legacy path.

    ``buckets > 1`` (CLI ``--fo-buckets``) attaches a ``rounds.Overlap``
    spec and chunks the flat gradient into that many independently-reducible
    buckets before the optimizer update — pure data movement (bit-identical
    params, identical ledger bytes), but the gradient all-reduce GSPMD
    inserts splits into per-bucket reduces the async-collective /
    latency-hiding XLA scheduler (``launch.xla``) can overlap with compute.
    """
    rnd = rounds.fo_round(loss_fn, opt,
                          wire=rounds.Wire(compressor, compress_mode),
                          overlap=rounds.Overlap(buckets))
    return lower_fo_round(rnd, mesh, grad_accum=grad_accum,
                          scan_unroll=scan_unroll, seed=seed, m=m)


def _bucketed_reduce_form(grads: Any, buckets: int) -> Any:
    """Rewrite a gradient tree into its chunked flat-gradient reduce form.

    Flattens the tree into one flat vector, splits it into ``buckets``
    contiguous chunks (the last one shorter when the parameter count does
    not divide evenly), and reassembles the original tree from the chunk
    concatenation.  Values are bit-identical — this is pure data movement —
    but each chunk is an independent intermediate, so the GSPMD gradient
    all-reduce lowers to per-bucket reduces the latency-hiding scheduler
    can pipeline against compute (the real-path mirror of the sim's
    ``Overlap`` pricing).  Wire bytes are unchanged: same tree, same dtypes.
    """
    leaves, treedef = jax.tree.flatten(grads)
    flat = (jnp.concatenate([l.reshape(-1) for l in leaves])
            if len(leaves) > 1 else leaves[0].reshape(-1))
    n = flat.shape[0]
    size = max(1, -(-n // buckets))          # ceil; last chunk takes the rest
    chunks = [jax.lax.slice_in_dim(flat, lo, min(lo + size, n))
              for lo in range(0, n, size)]
    flat = jnp.concatenate(chunks) if len(chunks) > 1 else chunks[0]
    out, off = [], 0
    for l in leaves:
        out.append(jax.lax.slice_in_dim(flat, off, off + l.size).reshape(l.shape))
        off += l.size
    return jax.tree.unflatten(treedef, out)


def lower_fo_round(
    rnd: rounds.Round,
    mesh: Mesh,
    *,
    grad_accum: int = 1,
    scan_unroll: bool = False,
    seed: int = 0,
    m: Optional[int] = None,
) -> Callable:
    """Fuse an FO round's per-worker locals + all-reduce + apply into one
    data-parallel program (the gradient reduction is GSPMD-inserted).  The
    round's ``Overlap`` spec selects the chunked reduce form
    (``_bucketed_reduce_form``) — bit-identical math, same booked bytes."""
    loss_fn, opt = rnd.meta["loss_fn"], rnd.meta["opt"]
    compressor, mode = rnd.wire.codec, rnd.wire.mode
    buckets = getattr(rnd.overlap, "buckets", 1)
    m = m if m is not None else _mesh_workers(mesh)
    per_worker = compressor is not None and mode == "per_worker" and m > 1
    if per_worker and grad_accum > 1:
        # per-worker encoding needs the m shard gradients individually,
        # which the microbatch-scan accumulator collapses — fall back to
        # the legacy post-reduction codec instead of refusing to train
        # (previously-working --compress + grad_accum configs keep working)
        warnings.warn(
            "per-worker FO encoding does not compose with grad_accum > 1; "
            "falling back to compress_mode='legacy' (post-reduction codec)",
            stacklevel=2)
        per_worker = False

    def fo_step(t, params, opt_state, batch):
        if per_worker:
            # faithful per-worker encode: the m workers' shard gradients are
            # computed in-program, each encoded with its own key and decoded
            # at the reducer — every worker receives m codes (nbytes * m)
            mb = jax.tree.map(
                lambda x: x.reshape(m, x.shape[0] // m, *x.shape[1:]), batch)
            with jax.named_scope("fo.grad"):
                losses, grads_m = jax.vmap(
                    lambda b: jax.value_and_grad(loss_fn)(params, b))(mb)
            key_t = jax.random.fold_in(jax.random.key(seed), t)
            dec, wire = [], 0
            for w in range(m):
                g_w = jax.tree.map(lambda x: x[w], grads_m)
                d_w, nb = compress_tree(compressor, g_w,
                                        jax.random.fold_in(key_t, w))
                dec.append(d_w)
                wire = nb * m
            grads = jax.tree.map(
                lambda *xs: jnp.mean(jnp.stack(
                    [x.astype(jnp.float32) for x in xs]), 0).astype(xs[0].dtype),
                *dec)
            loss = jnp.mean(losses)
            coll.note_all_reduce(grads, nbytes=wire, tag=compressor.name)
        elif grad_accum <= 1:
            with jax.named_scope("fo.grad"):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            # split so the *major* dim stays the (sharded) batch dim, then
            # transpose: reshape(accum, B/accum, ...) would force GSPMD to
            # split the data-axis sharding across microbatches (4-way-parallel
            # microbatches, constant memory); this keeps every device working
            # on its own rows in every microbatch.
            mb = jax.tree.map(
                lambda x: x.reshape(x.shape[0] // grad_accum, grad_accum,
                                    *x.shape[1:]).swapaxes(0, 1),
                batch,
            )

            def micro(carry, batch_i):
                g_acc, l_acc = carry
                with jax.named_scope("fo.grad"):
                    l, g = jax.value_and_grad(loss_fn)(params, batch_i)
                with jax.named_scope("fo.accumulate"):
                    g_acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l), None

            init = (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params), jnp.float32(0.0))
            (grads, loss), _ = jax.lax.scan(
                micro, init, mb, unroll=grad_accum if scan_unroll else 1)
            with jax.named_scope("fo.accumulate"):
                grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss / grad_accum
        if not per_worker:
            # the d-dim gradient all-reduce is inserted by GSPMD (sharded
            # batch x replicated params); book its wire bytes — or the
            # codec's — here.
            if compressor is not None:
                grads, wire = compress_tree(
                    compressor, grads,
                    jax.random.fold_in(jax.random.key(seed), t))
                coll.note_all_reduce(grads, nbytes=wire, tag=compressor.name)
            else:
                coll.note_all_reduce(grads, tag="grads")
        if buckets > 1:
            grads = _bucketed_reduce_form(grads, buckets)
        with jax.named_scope("fo.update"):
            deltas, opt_state = opt.update(grads, opt_state, params, t)
            return apply_deltas(params, deltas), opt_state, loss

    return fo_step


def make_zo_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    mesh: Mesh,
    ho: HOSGDConfig,
    opt: Optimizer,
    m: Optional[int] = None,
    fsdp: bool = False,
    param_specs_tree: Any = None,
) -> Callable:
    """(t, params, opt_state, batch) -> (params, opt_state, loss).

    Lowers ``rounds.zo_round`` to the mesh.  The shard_map inner function
    returns the reconstructed gradient estimate (replicated across workers —
    every worker computes the same sum); the optimizer update composes
    outside, so HO-SGD's ZO steps can drive any optimizer (beyond-paper:
    ZO-Adam).

    The direction algebra itself lives in ``repro.core.engine`` — the
    backend is picked by ``ho.engine`` ('fused' keeps the direction out of
    program buffers; 'pallas' routes through the kernels; 'tree' is the
    reference; 'flat' packs the tree into one buffer and runs one kernel
    per primitive) and the params' sharding specs are threaded into the
    engine so every hash-generated leaf and accumulator carries a sharding
    constraint (without one the partitioner is free to replicate the full
    d-dim direction per device — 1.8 TB fp32 for arctic).

    ``m`` (default: the mesh's worker count) may be any multiple of the
    mesh's worker devices: each device then evaluates ``m / devices``
    workers in-program on consecutive slices of its batch rows — on a 1x1
    mesh, all m workers of the single-host reference.

    With ``fsdp`` params are sharded over the data axis, so a model replica
    (= the paper's "worker") spans (data, model) and the ZO step runs with
    m=1 (one global direction per iteration, plain pjit).  Running the pod
    axis as a manual worker axis is blocked by an XLA SPMD partitioner
    CHECK-failure when the MoE dispatch gathers meet subgroup-manual
    sharding (spmd_partitioner_util.cc:504; stack in EXPERIMENTS.md §Dry-run
    notes) — a real-XLA limitation we document rather than hide.
    """
    rnd = rounds.zo_round(loss_fn, ho, opt, m=m)
    return lower_zo_round(rnd, mesh, m=m, fsdp=fsdp,
                          param_specs_tree=param_specs_tree)


def lower_zo_round(
    rnd: rounds.Round,
    mesh: Mesh,
    *,
    m: Optional[int] = None,
    fsdp: bool = False,
    param_specs_tree: Any = None,
) -> Callable:
    """Fuse a ZO round's per-worker coefficient evals + scalar all-gather +
    reconstruction into one program: a partial-auto ``jax.shard_map``,
    manual over the worker axes."""
    loss_fn, ho, opt = (rnd.meta["loss_fn"], rnd.meta["ho"], rnd.meta["opt"])
    if fsdp:
        wa = ()
    else:
        wa = worker_axes(mesh)
    # host-side mesh arithmetic: plain ints, never jax arrays
    n_dev = max(1, math.prod(mesh.shape[a] for a in wa))
    m = m or n_dev
    if m % n_dev:
        raise ValueError(f"m={m} workers do not divide over the mesh's "
                         f"{n_dev} worker devices")
    k = m // n_dev          # workers evaluated in-program on each device
    # kernel backends run per device: manual over every mesh axis, with
    # unconstrained (unsharded) leaves
    per_device = ENGINES[ho.engine].per_device
    if per_device and wa:
        sharded = [a for a in mesh.axis_names
                   if a not in wa and mesh.shape[a] > 1]
        if sharded:
            raise ValueError(
                f"engine {ho.engine!r} runs Pallas kernels per device and "
                f"cannot shard params over {sharded}; use a mesh whose "
                f"non-worker axes have size 1, or engine 'fused'")
    manual = set(mesh.axis_names) if per_device else set(wa)
    specs = None if per_device else param_specs_tree

    def engine_for(params):
        return make_engine(ho.engine, params, ho.seed, specs=specs,
                           acc_dtype=ho.acc_dtype)

    def _scaled(eng, cs, t):
        with jax.named_scope("zo.reconstruct"):
            rec = eng.reconstruct(cs, t)
            return jax.tree.map(lambda a: a * (ho.zo_scale / m), rec)

    def zo_inner(t, params, batch_local):
        eng = engine_for(params)
        # device index from the manual axes; device i evaluates workers
        # i*k .. i*k+k-1 on consecutive slices of its batch rows (k = 1 on
        # a mesh with one device per worker)
        idx = jax.lax.axis_index(wa[0])
        if len(wa) == 2:
            idx = idx * mesh.shape[wa[1]] + jax.lax.axis_index(wa[1])
        workers = (idx.astype(jnp.uint32) * jnp.uint32(k)
                   + jnp.arange(k, dtype=jnp.uint32))
        stacked = jax.tree.map(
            lambda x: x.reshape(k, x.shape[0] // k, *x.shape[1:]), batch_local)
        cs, f0s = eng.zo_coeffs(loss_fn, params, stacked, t, workers, ho.mu)
        with jax.named_scope("zo.exchange"):
            cs = coll.all_gather(cs, wa, tag="zo_coeffs")  # (m,) scalars — the
            cs = cs.reshape(-1)                            # paper's entire comm
        g_hat = _scaled(eng, cs, t)
        # averaging the monitoring loss is diagnostics, not Algorithm 1's
        # communication — booked as non-payload so measured bytes stay 4*m
        with jax.named_scope("zo.exchange"):
            loss = coll.pmean(jnp.mean(f0s), wa, tag="loss", payload=False)
        return g_hat, loss

    def zo_single(t, params, batch):
        """m=1 degenerate case (fsdp arch on the single-pod mesh): plain pjit.

        One global direction means a one-scalar "gather" — booked so the
        ledger shows 4 bytes (the m=1 truth) rather than a silent 0 when an
        fsdp arch's ZO step runs; the gap vs. the mesh's nominal worker
        count is the documented fsdp limitation, and it should be visible.
        """
        eng = engine_for(params)
        c, f0 = eng.zo_coeff(loss_fn, params, batch, t, jnp.uint32(0), ho.mu)
        cs = coll.note("all_gather", c.reshape(1), tag="zo_coeffs")
        g_hat = _scaled(eng, cs, t)
        return g_hat, f0

    def zo_step(t, params, opt_state, batch):
        if not wa:
            g_hat, loss = zo_single(t, params, batch)
        else:
            params_specs = _replicated_specs(params)
            bspecs = jax.tree.map(
                lambda x: P(wa, *([None] * (x.ndim - 1))), batch)
            g_hat, loss = jax.shard_map(
                partial(zo_inner, t),
                mesh=mesh,
                in_specs=(params_specs, bspecs),
                out_specs=(params_specs, P()),
                axis_names=manual,
                check_vma=False,
            )(params, batch)
        with jax.named_scope("zo.update"):
            deltas, opt_state = opt.update(g_hat, opt_state, params, t)
            return apply_deltas(params, deltas), opt_state, loss

    return zo_step


def make_distributed_ho_sgd(
    loss_fn: Callable,
    mesh: Mesh,
    ho: HOSGDConfig,
    opt: Optional[Optimizer] = None,
    model_cfg=None,
    params_like: Any = None,
    compressor: Optional[Compressor] = None,
    compress_mode: str = "per_worker",
    fo_buckets: int = 1,
):
    """Returns (fo_step, zo_step) honoring the arch's production knobs.

    ``compressor`` (repro.dist.compress) quantizes the FO gradient exchange
    (``compress_mode``: per-worker encode + reducer decode, or the legacy
    post-reduction simulation); the ZO step is untouched — its traffic is
    already one scalar per worker.  ``fo_buckets > 1`` lowers the FO round
    in its chunked reduce form (bit-identical math, same bytes) for the
    async-collective/latency-hiding XLA scheduler to overlap.
    """
    opt = opt or sgd(const_schedule(ho.lr), ho.momentum)
    ga = getattr(model_cfg, "grad_accum", 1) if model_cfg is not None else 1
    su = getattr(model_cfg, "scan_unroll", False) if model_cfg is not None else False
    fsdp = getattr(model_cfg, "fsdp", False) if model_cfg is not None else False
    specs = None
    if model_cfg is not None and params_like is not None:
        specs = param_specs(model_cfg, params_like, mesh)
    fo = make_fo_step(loss_fn, mesh, opt, grad_accum=ga, scan_unroll=su,
                      compressor=compressor, seed=ho.seed,
                      compress_mode=compress_mode, buckets=fo_buckets)
    zo = make_zo_step(loss_fn, mesh, ho, opt, fsdp=fsdp, param_specs_tree=specs)
    return fo, zo


def jit_with_shardings(step_fn, mesh: Mesh, cfg_model, params, opt_state, batch,
                       donate: bool = True):
    """jit a (t, params, opt_state, batch) step with explicit shardings."""
    pspecs = param_specs(cfg_model, params, mesh)
    o_specs = jax.tree.map(lambda x: NamedSharding(mesh, P()), opt_state) if opt_state is not None else None
    in_sh = (
        NamedSharding(mesh, P()),
        named(mesh, pspecs),
        o_specs,
        named(mesh, batch_specs(mesh, batch)),
    )
    out_sh = (named(mesh, pspecs), o_specs, NamedSharding(mesh, P()))
    return jax.jit(
        step_fn,
        in_shardings=in_sh,
        out_shardings=out_sh,
        donate_argnums=(1, 2) if donate else (),
    )
