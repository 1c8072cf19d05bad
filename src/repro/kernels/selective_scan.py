"""Mamba-1 selective-scan Pallas kernel.

TPU adaptation of the CUDA fused scan: the (d_inner, n) running state lives
in VMEM scratch and persists across sequential sequence-block grid steps, so
the (B, S, d_inner, n) intermediate the pure-jnp associative scan
materializes (see models/ssm.py) never touches HBM.  HBM traffic drops from
O(S*di*n) to O(S*(di + n)) — the memory-roofline win quantified in
EXPERIMENTS.md §Perf.

Layout: channels tiled (block_d), sequence tiled (block_s, sequential), time
recurrence is an in-register ``fori_loop`` over tile-aligned slabs of the
block's steps, each slab's rows walked statically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# time steps per aligned slab: the (16, 128) tile of a 16-bit array, so a
# slab load/store at a multiple of it is provably aligned for bf16 and fp32
_SLAB = 16


def _scan_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, o_ref, h_scr,
                 y_scr, *, block_s: int):
    sb = pl.program_id(2)

    @pl.when(sb == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = a_ref[...].astype(jnp.float32)            # (bd, n)
    Dp = d_ref[0].astype(jnp.float32)             # (bd,)
    slab = min(_SLAB, block_s)

    def slab_step(c, h):                           # h: (bd, n)
        # Mosaic lowers only tile-aligned dynamic row offsets, so the
        # recurrence reads a slab of rows at an aligned offset and walks
        # its rows statically
        t0 = pl.multiple_of(c * slab, slab)
        rows = pl.ds(t0, slab)
        dt_s = dt_ref[0, rows, :].astype(jnp.float32)         # (slab, bd)
        u_s = u_ref[0, rows, :].astype(jnp.float32)
        B_s = b_ref[0, rows, :].astype(jnp.float32)           # (slab, n)
        C_s = c_ref[0, rows, :].astype(jnp.float32)
        for j in range(slab):
            dt_t, u_t = dt_s[j], u_s[j]                        # (bd,)
            dA = jnp.exp(dt_t[:, None] * A)                    # (bd, n)
            dBu = (dt_t * u_t)[:, None] * B_s[j][None, :]
            h = dA * h + dBu
            y_scr[j, :] = jnp.sum(h * C_s[j][None, :], axis=1) + Dp * u_t
        o_ref[0, rows, :] = y_scr[...].astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_s // slab, slab_step, h_scr[...])


def selective_scan_pallas(
    u: jax.Array,      # (B, S, di) — post-conv, post-silu activations
    dt: jax.Array,     # (B, S, di) — softplus'd timestep
    Bmat: jax.Array,   # (B, S, n)
    Cmat: jax.Array,   # (B, S, n)
    A: jax.Array,      # (di, n) — negative decay matrix
    D: jax.Array,      # (di,)
    *,
    block_d: int = 256,
    block_s: int = 128,
    interpret: bool,
) -> jax.Array:
    B, S, di = u.shape
    n = A.shape[1]
    block_d = min(block_d, di)
    block_s = min(block_s, S)
    assert di % block_d == 0 and S % block_s == 0
    assert block_s % min(_SLAB, block_s) == 0, (block_s, _SLAB)
    kern = functools.partial(_scan_kernel, block_s=block_s)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((B, S, di), u.dtype),
        # sequence dim must be innermost-sequential: state carries across it
        grid=(B, di // block_d, S // block_s),
        in_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda b, d, s: (b, s, d)),
            pl.BlockSpec((1, block_s, block_d), lambda b, d, s: (b, s, d)),
            pl.BlockSpec((1, block_s, n), lambda b, d, s: (b, s, 0)),
            pl.BlockSpec((1, block_s, n), lambda b, d, s: (b, s, 0)),
            pl.BlockSpec((block_d, n), lambda b, d, s: (d, 0)),
            # D as a (1, di) row: a 1-D operand's XLA tiling differs
            # from the block's
            pl.BlockSpec((1, block_d), lambda b, d, s: (0, d)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_d), lambda b, d, s: (b, s, d)),
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32),
                        pltpu.VMEM((min(_SLAB, block_s), block_d),
                                   jnp.float32)],
        interpret=interpret,
    )(u, dt, Bmat, Cmat, A, D.reshape(1, di))
