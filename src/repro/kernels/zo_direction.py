"""The paper-specific Pallas kernels: fused ZO direction generate+apply.

A ZO iteration is purely memory-bound (stream the d parameters twice: once
to perturb, once to update).  The naive jnp path writes the random direction
``v`` to HBM between generation and use; these kernels regenerate ``v``
on the fly inside the tile (the hash of repro.core.directions, bit-identical)
so the direction never exists in HBM:

* ``zo_sumsq``       — sum of squares of a hashed Gaussian block (for the
                       unit-sphere normalization), zero HBM reads.
* ``zo_perturb``     — ``x + (mu * inv_norm) * v``: one read + one write of x.
* ``zo_reconstruct`` — ``acc += sum_i coeff_i * v_i`` for all m workers in a
                       single pass over the parameters (m gaussians per
                       element generated in registers).

The per-leaf kernels above take one ``(salt, offset)`` pair per call, so the
optimizer hot path launches one kernel per parameter leaf.  The *flat*
kernels below operate on the whole tree packed into ONE contiguous f32
buffer with block-aligned leaves, consuming per-LEAF tables (first block,
element count, salt — built once by ``repro.core.engine.FlatEngine``); each
grid step finds its leaf by scanning the table in scalar memory, so a full
multi-leaf primitive is a single kernel launch:

* ``zo_perturb_flat``     — one launch for the whole tree's perturbation.
* ``zo_reconstruct_flat`` — one launch for the whole tree's m-worker
                            reconstruction.
* ``zo_perturb_sumsq``    — the fused perturb: a two-phase grid over the
                            same call first accumulates the tree-wide
                            ``sum(v^2)`` (zero HBM traffic — this is the
                            ``zo_sumsq`` algebra, finally on the hot path),
                            then writes ``x + mu * rsqrt(sumsq) * v`` with
                            the scale computed in-kernel.  HBM traffic: one
                            read + one write of x; the separate inv-norm
                            pass over d disappears.
* ``zo_reconstruct_update`` — the fused optimizer commit: regenerates all
                            m directions in registers, applies the
                            pre-scaled coefficients, and performs the
                            SGD(+momentum) update in the same pass.  Params
                            (and momentum) are read once and written once
                            via ``input_output_aliases`` (in-place on the
                            donated buffer); the update vector never exists
                            in HBM.

Scalars (salts, offsets, coefficients, learning rate) and the leaf tables
live in SMEM, the TPU's scalar memory: Mosaic refuses rank-1 blocks of one
element, and per-block tables of a billion-parameter buffer would not fit
SMEM's 1 MiB, while per-leaf tables are a few words per leaf.  Reductions
accumulate into an SMEM scalar output (vector memory cannot take a scalar
store).

``offset`` shifts the leaf-local hash counter: the optimizer hashes each
leaf with its own salt and counters starting at 0, the grid shifts each
block by ``i * block`` internally, and callers that split one leaf across
multiple kernel calls pass the chunk's start index (whole-leaf calls pass
0 — see tests/test_directions.py::test_offset_split_consistency).

Arbitrary leaf sizes are supported: the grid is ``ceil(n / block)`` and the
tail block is masked.  Reductions (``zo_sumsq``) mask explicitly in-kernel —
hash values exist for any counter, so out-of-range lanes would otherwise
contribute garbage; elementwise outputs (``zo_perturb``/``zo_reconstruct``)
rely on Pallas's boundary semantics (out-of-bounds stores of a partial
output block are dropped, both in interpret mode and under Mosaic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.directions import _GOLDEN, _SALT2, _TWO_PI, _XOR2, mix32


def _uniform01(bits: jax.Array) -> jax.Array:
    """``directions._uniform01`` with the cast through int32: exact below
    2**24, and the only integer-to-float conversion Mosaic accepts (it
    refuses uint32 -> float32).  Same values as the jnp path, which keeps
    its own cast: on XLA:CPU the int32 form changes FMA contraction around
    it, and the kernel tests' bitwise pins compare against that path."""
    top = (bits >> 8).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(2**-24) + jnp.float32(2**-25)


def _gauss_block(start: jax.Array, n: int, salt: jax.Array) -> jax.Array:
    """n standard normals for flat counters [start, start+n) (Box–Muller)."""
    idx = jax.lax.iota(jnp.uint32, n) + start
    h1 = mix32(idx * _GOLDEN + salt)
    h2 = mix32(idx * _SALT2 + (salt ^ _XOR2))
    u1 = _uniform01(h1)
    u2 = _uniform01(h2)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(_TWO_PI * u2)


def _grid(n: int, block: int) -> int:
    return (n + block - 1) // block


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole array, scalar memory


def _scalars(*xs, dtype) -> jax.Array:
    """Pack scalars into one (k,) array for an SMEM operand."""
    return jnp.stack([jnp.asarray(x, dtype).reshape(()) for x in xs])


# --------------------------------------------------------------------------- #
def _sumsq_kernel(meta_ref, o_ref, *, block: int, n: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[0] = jnp.float32(0.0)

    g = _gauss_block(meta_ref[1] + (i * block).astype(jnp.uint32), block,
                     meta_ref[0])
    # tail mask: the hash yields (garbage) values for any counter, so lanes
    # past the leaf end must be excluded from the reduction explicitly
    lane = jax.lax.iota(jnp.int32, block) + i * block
    o_ref[0] += jnp.sum(jnp.where(lane < n, g * g, 0.0))


def zo_sumsq(n: int, salt, offset=0, *, block: int = 4096,
             interpret: bool) -> jax.Array:
    """||v_leaf||^2 for a hashed Gaussian leaf of n elements (no HBM input)."""
    block = min(block, n)
    out = pl.pallas_call(
        functools.partial(_sumsq_kernel, block=block, n=n),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        grid=(_grid(n, block),),
        in_specs=[_SMEM],
        out_specs=_SMEM,
        interpret=interpret,
    )(_scalars(salt, offset, dtype=jnp.uint32))
    return out[0]


# --------------------------------------------------------------------------- #
def _perturb_kernel(x_ref, meta_ref, scale_ref, o_ref, *, block: int):
    i = pl.program_id(0)
    g = _gauss_block(meta_ref[1] + (i * block).astype(jnp.uint32), block,
                     meta_ref[0])
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = (x + scale_ref[0] * g).astype(o_ref.dtype)


def zo_perturb(
    x: jax.Array,        # flat (n,) parameter leaf
    salt,
    scale,               # mu * inv_norm (fp32 scalar)
    offset=0,
    *,
    block: int = 4096,
    interpret: bool,
) -> jax.Array:
    n = x.shape[0]
    block = min(block, n)
    return pl.pallas_call(
        functools.partial(_perturb_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        grid=(_grid(n, block),),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)), _SMEM, _SMEM],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        interpret=interpret,
    )(x, _scalars(salt, offset, dtype=jnp.uint32),
      _scalars(scale, dtype=jnp.float32))


# --------------------------------------------------------------------------- #
def _reconstruct_kernel(salts_ref, coeffs_ref, off_ref, o_ref, *, block: int,
                        m: int, acc_dtype):
    i = pl.program_id(0)
    start = off_ref[0] + (i * block).astype(jnp.uint32)
    acc = jnp.zeros((block,), jnp.float32)
    for w in range(m):  # static worker unroll: m gaussians live in registers
        g = _gauss_block(start, block, salts_ref[w])
        acc = acc + coeffs_ref[w] * g
        if acc_dtype != jnp.float32:
            # round to the accumulator dtype after every worker — the exact
            # semantics of the tree/fused accumulators, so a bf16 acc_dtype
            # stays bit-identical across DirectionEngine backends
            acc = acc.astype(acc_dtype).astype(jnp.float32)
    o_ref[...] = acc


def zo_reconstruct(
    n: int,
    salts: jax.Array,    # (m,) uint32 — per-worker leaf salts
    coeffs: jax.Array,   # (m,) fp32   — c_i * inv_norm_i, pre-scaled
    offset=0,
    *,
    block: int = 4096,
    acc_dtype=jnp.float32,
    interpret: bool,
) -> jax.Array:
    """sum_i coeffs_i * v_i for one flat leaf, one pass, no HBM directions.

    ``acc_dtype`` rounds the running accumulator after each worker (still in
    registers — never in HBM), matching the optimizer's acc_dtype knob.
    """
    m = salts.shape[0]
    block = min(block, n)
    return pl.pallas_call(
        functools.partial(_reconstruct_kernel, block=block, m=m,
                          acc_dtype=jnp.dtype(acc_dtype)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        grid=(_grid(n, block),),
        in_specs=[_SMEM, _SMEM, _SMEM],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        interpret=interpret,
    )(salts.astype(jnp.uint32), coeffs.astype(jnp.float32),
      _scalars(offset, dtype=jnp.uint32))


# --------------------------------------------------------------------------- #
# flat multi-leaf kernels: the whole tree in one packed buffer, one launch.
#
# Packed-buffer convention (repro.core.engine.FlatEngine): every leaf is
# padded to a multiple of ``block`` so each grid block belongs to exactly
# ONE leaf.  Two per-leaf tables describe the layout — ``starts[l]``, the
# leaf's first block, and ``sizes[l]``, its element count — and each grid
# step derives its block's leaf, leaf-local counter start (the same shift
# the per-leaf grid applies internally) and valid-lane count (tail blocks
# of a leaf mask the padding) from them.  Hash identity is therefore
# bit-compatible with the per-leaf kernels and the jnp/tree backends:
# leaf-local counters from 0, one salt per (t, worker, leaf).
# --------------------------------------------------------------------------- #
def _block_leaf(i, starts_ref, sizes_ref, block: int):
    """Block i's ``(leaf, leaf-local counter start, valid-lane mask)``."""
    n_leaves = starts_ref.shape[0]
    # leaves are laid out in order, so block i belongs to the last leaf
    # whose first block is <= i (a scalar scan over the SMEM table)
    leaf = jax.lax.fori_loop(
        1, n_leaves,
        lambda l, acc: acc + (starts_ref[l] <= i).astype(jnp.int32),
        jnp.int32(0))
    rel = i - starts_ref[leaf]
    ctr = rel.astype(jnp.uint32) * jnp.uint32(block)
    valid = jax.lax.iota(jnp.int32, block) < sizes_ref[leaf] - rel * block
    return leaf, ctr, valid


def _perturb_flat_kernel(starts_ref, sizes_ref, salts_ref, scale_ref, x_ref,
                         o_ref, *, block: int):
    leaf, ctr, valid = _block_leaf(pl.program_id(0), starts_ref, sizes_ref,
                                   block)
    g = _gauss_block(ctr, block, salts_ref[leaf])
    x = x_ref[...]
    # padding lanes carry x through unchanged (zeros stay zeros)
    o_ref[...] = jnp.where(valid, x + scale_ref[0] * g, x)


def zo_perturb_flat(
    x: jax.Array,        # (P,) packed f32 parameter buffer (block-aligned)
    starts: jax.Array,   # (L,) int32 — each leaf's first block
    sizes: jax.Array,    # (L,) int32 — each leaf's element count
    salts: jax.Array,    # (L,) uint32 — each leaf's salt
    scale,               # mu * inv_norm (fp32 scalar, premultiplied)
    *,
    block: int = 4096,
    interpret: bool,
) -> jax.Array:
    """Whole-tree ``x + scale * v`` in ONE kernel launch (vs one per leaf)."""
    nb = x.shape[0] // block
    assert x.shape[0] == nb * block, (x.shape, block)
    blk = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        functools.partial(_perturb_flat_kernel, block=block),
        out_shape=jax.ShapeDtypeStruct((nb * block,), jnp.float32),
        grid=(nb,),
        in_specs=[_SMEM, _SMEM, _SMEM, _SMEM, blk],
        out_specs=blk,
        interpret=interpret,
    )(starts, sizes, salts, _scalars(scale, dtype=jnp.float32), x)


def _reconstruct_flat_kernel(starts_ref, sizes_ref, salts_ref, coeffs_ref,
                             o_ref, *, block: int, m: int, acc_dtype):
    leaf, ctr, valid = _block_leaf(pl.program_id(0), starts_ref, sizes_ref,
                                   block)
    n_leaves = starts_ref.shape[0]
    acc = jnp.zeros((block,), jnp.float32)
    for w in range(m):  # static worker unroll: m gaussians live in registers
        g = _gauss_block(ctr, block, salts_ref[w * n_leaves + leaf])
        acc = acc + coeffs_ref[w] * g
        if acc_dtype != jnp.float32:
            acc = acc.astype(acc_dtype).astype(jnp.float32)
    o_ref[...] = jnp.where(valid, acc, 0.0)


def zo_reconstruct_flat(
    n_blocks: int,
    starts: jax.Array,   # (L,) int32
    sizes: jax.Array,    # (L,) int32
    salts: jax.Array,    # (m, L) uint32 — per-(worker, leaf) salts
    coeffs: jax.Array,   # (m,) fp32 — c_i * inv_norm_i, pre-scaled
    *,
    block: int = 4096,
    acc_dtype=jnp.float32,
    interpret: bool,
) -> jax.Array:
    """Whole-tree ``sum_i coeffs_i * v_i`` in ONE launch; padding lanes 0."""
    m = salts.shape[0]
    return pl.pallas_call(
        functools.partial(_reconstruct_flat_kernel, block=block, m=m,
                          acc_dtype=jnp.dtype(acc_dtype)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * block,), jnp.float32),
        grid=(n_blocks,),
        in_specs=[_SMEM, _SMEM, _SMEM, _SMEM],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        interpret=interpret,
    )(starts, sizes, salts.reshape(-1), coeffs.astype(jnp.float32))


def _perturb_sumsq_kernel(starts_ref, sizes_ref, salts_ref, mu_ref, x_ref,
                          o_ref, ss_ref, *, block: int):
    p = pl.program_id(0)          # phase: 0 = accumulate sumsq, 1 = perturb
    i = pl.program_id(1)

    @pl.when((p == 0) & (i == 0))
    def _():
        ss_ref[0] = jnp.float32(0.0)

    leaf, ctr, valid = _block_leaf(i, starts_ref, sizes_ref, block)
    g = _gauss_block(ctr, block, salts_ref[leaf])

    @pl.when(p == 0)
    def _():
        # tail mask: hash values exist for any counter, so padding lanes
        # must be excluded from the reduction explicitly
        ss_ref[0] += jnp.sum(jnp.where(valid, g * g, 0.0))

    @pl.when(p == 1)
    def _():
        # the tree-wide sumsq is fully accumulated (the grid is sequential),
        # so the unit-sphere scale is computed in-kernel — no separate
        # inv-norm pass over d
        scale = mu_ref[0] * jax.lax.rsqrt(ss_ref[0] + 1e-30)
        x = x_ref[...]
        o_ref[...] = jnp.where(valid, x + scale * g, x)


def zo_perturb_sumsq(
    x: jax.Array,        # (P,) packed f32 parameter buffer (block-aligned)
    starts: jax.Array,   # (L,) int32
    sizes: jax.Array,    # (L,) int32
    salts: jax.Array,    # (L,) uint32
    mu,                  # smoothing parameter (fp32 scalar; NOT premultiplied)
    *,
    block: int = 4096,
    interpret: bool,
) -> tuple:
    """Fused ``(x + mu * rsqrt(sum v^2) * v, sum v^2)`` in one launch.

    A two-phase grid over one call: phase 0 streams NO HBM data (the
    direction is hash-generated) and accumulates the tree-wide ``sum(v^2)``
    into the scalar output; phase 1 re-generates each block's gaussians,
    reads x once and writes the perturbed buffer once with the scale
    ``mu * rsqrt(sumsq + 1e-30)`` computed in-kernel.  Returns
    ``(x_perturbed, sumsq)`` so the caller reuses the same ``inv_norm`` for
    the reconstruction coefficients.

    Note the kernel's blockwise-sequential reduction order differs from the
    shared jnp reduction of ``DirectionEngine.sumsq``, so the perturbed
    point may differ from the per-primitive path in the last ulp — the
    fused-step seam documented in README §DirectionEngine.
    """
    nb = x.shape[0] // block
    assert x.shape[0] == nb * block, (x.shape, block)
    # phase 0 never consumes x / the output block: pin both to block 0
    # (p * i) so no extra HBM pass happens during accumulation; phase 1
    # rewrites block 0 first, so the phase-0 garbage write never survives.
    blk = pl.BlockSpec((block,), lambda p, i: (p * i,))
    return pl.pallas_call(
        functools.partial(_perturb_sumsq_kernel, block=block),
        out_shape=(jax.ShapeDtypeStruct((nb * block,), jnp.float32),
                   jax.ShapeDtypeStruct((1,), jnp.float32)),
        grid=(2, nb),
        in_specs=[_SMEM, _SMEM, _SMEM, _SMEM, blk],
        out_specs=(blk, _SMEM),
        interpret=interpret,
    )(starts, sizes, salts, _scalars(mu, dtype=jnp.float32), x)


def _reconstruct_update_kernel(starts_ref, sizes_ref, bf16_ref, salts_ref,
                               coeffs_ref, lr_ref, p_ref, *refs, block: int,
                               m: int, acc_dtype, momentum: float,
                               use_momentum: bool):
    if use_momentum:
        v_ref, po_ref, vo_ref = refs
    else:
        (po_ref,) = refs
    leaf, ctr, valid = _block_leaf(pl.program_id(0), starts_ref, sizes_ref,
                                   block)
    n_leaves = starts_ref.shape[0]
    acc = jnp.zeros((block,), jnp.float32)
    for w in range(m):  # static worker unroll: m gaussians live in registers
        g = _gauss_block(ctr, block, salts_ref[w * n_leaves + leaf])
        acc = acc + coeffs_ref[w] * g
        if acc_dtype != jnp.float32:
            # round after every worker — the exact semantics of the
            # DirectionEngine accumulators (bit-identical under bf16 acc)
            acc = acc.astype(acc_dtype).astype(jnp.float32)
    # padding lanes contribute nothing: params/momentum padding stays 0
    acc = jnp.where(valid, acc, 0.0)
    # optimizers.sgd computes deltas = -lr * v and apply_deltas adds them;
    # mirror that expression shape (p + (-lr)*v, not p - lr*v) so XLA's FMA
    # contraction matches the unfused path bit-for-bit
    neg_lr = -lr_ref[0]
    if use_momentum:
        # optimizers.sgd: v' = momentum * v + g;  p' = p + (-lr) * v'
        v_new = jnp.float32(momentum) * v_ref[...] + acc
        vo_ref[...] = v_new
        p_new = p_ref[...] + neg_lr * v_new
    else:
        p_new = p_ref[...] + neg_lr * acc
    # leaves stored in bf16 round-trip through their dtype on commit, the
    # apply_deltas semantics (per-leaf flag)
    is_bf16 = bf16_ref[leaf] != 0

    @pl.when(is_bf16)
    def _():
        po_ref[...] = p_new.astype(jnp.bfloat16).astype(jnp.float32)

    @pl.when(jnp.logical_not(is_bf16))
    def _():
        po_ref[...] = p_new


def zo_reconstruct_update(
    p: jax.Array,                  # (P,) packed f32 params (donated, aliased)
    mom,                           # (P,) packed f32 momentum, or None
    starts: jax.Array,             # (L,) int32
    sizes: jax.Array,              # (L,) int32
    bf16_mask: jax.Array,          # (L,) int32 — 1 where the leaf is bf16
    salts: jax.Array,              # (m, L) uint32
    coeffs: jax.Array,             # (m,) fp32 — fully pre-scaled
    lr,                            # learning rate (fp32 scalar)
    momentum: float = 0.0,
    *,
    block: int = 4096,
    acc_dtype=jnp.float32,
    interpret: bool,
):
    """Fused reconstruct + SGD(+momentum) commit: the update vector never
    exists in HBM.

    One pass: per block, all m directions are regenerated in registers and
    contracted with the pre-scaled ``coeffs`` (``c_i * inv_norm_i *
    zo_scale / m``, with per-worker ``acc_dtype`` rounding), then the
    SGD(+momentum) update runs in-kernel: params (and momentum) are read
    once and written once, in place (``input_output_aliases``).  Returns
    ``(p', mom')`` (``mom'`` is None when ``mom`` is None — the
    momentum-free optimizer carries no state buffer).
    """
    m = salts.shape[0]
    nb = p.shape[0] // block
    assert p.shape[0] == nb * block, (p.shape, block)
    use_momentum = mom is not None
    kern = functools.partial(
        _reconstruct_update_kernel, block=block, m=m,
        acc_dtype=jnp.dtype(acc_dtype), momentum=float(momentum),
        use_momentum=use_momentum)
    blk = pl.BlockSpec((block,), lambda i: (i,))
    scalars = (starts, sizes, bf16_mask, salts.reshape(-1),
               coeffs.astype(jnp.float32), _scalars(lr, dtype=jnp.float32))
    bufs = (p, mom) if use_momentum else (p,)
    shape = jax.ShapeDtypeStruct((nb * block,), jnp.float32)
    n_in = len(scalars)
    out = pl.pallas_call(
        kern,
        out_shape=(shape,) * len(bufs),
        grid=(nb,),
        in_specs=[_SMEM] * n_in + [blk] * len(bufs),
        out_specs=(blk,) * len(bufs),
        # in place: params (and momentum) are read and written once
        input_output_aliases={n_in + k: k for k in range(len(bufs))},
        interpret=interpret,
    )(*scalars, *bufs)
    return (out[0], out[1]) if use_momentum else (out[0], None)
