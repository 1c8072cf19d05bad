"""Fused RMSNorm Pallas kernel (rows tiled to VMEM, fp32 accumulation)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * (1.0 + s_ref[...].astype(jnp.float32))
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_pallas(
    x: jax.Array,            # (R, D) — callers flatten leading dims
    scale: jax.Array,        # (D,)
    eps: float = 1e-6,
    block_rows: int = 128,
    *,
    interpret: bool,
) -> jax.Array:
    R, D = x.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0, (R, block_rows)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        interpret=interpret,
    )(x, scale)
