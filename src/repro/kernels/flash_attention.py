"""Flash attention Pallas-TPU kernel: blocked online softmax.

Supports the whole feature matrix of the assigned archs: causal masking,
sliding window (gemma2 local layers / long-context variants), gemma2 logit
soft-capping, and GQA (kv head = q head // group).

VMEM tiling: (block_q x hd) query tile streams over (block_k x hd) key/value
tiles along the innermost sequential grid dim; running max / denominator /
accumulator live in VMEM scratch across that dim.  Blocks are MXU-aligned
(128 default).  Fully-masked key blocks are skipped via ``@pl.when`` — with
a sliding window this is what makes prefill O(S*W) instead of O(S^2).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, block_q: int, block_k: int, n_kb: int, causal: bool,
    window: Optional[int], softcap: Optional[float], scale: float,
):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qb * block_q
    k_start = kb * block_k

    # block-level skip: this key block is live iff some (i, j) pair passes
    # causal (j <= i) and window (i - j < W) tests for the block extents
    live = True
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window is not None:
        live = jnp.logical_and(live, (q_start - (k_start + block_k - 1)) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)                  # (bk, hd)
        s = q @ k.T                                       # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        rel = qi - kj
        mask = jnp.ones_like(rel, dtype=bool)
        if causal:
            mask &= rel >= 0
        if window is not None:
            mask &= rel < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new

    @pl.when(kb == n_kb - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,            # (BH, Sq, hd)  — batch*q_heads flattened
    k: jax.Array,            # (BH, Sk, hd)  — kv heads pre-expanded to BH
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_qb, n_kb = Sq // block_q, Sk // block_k
    scale = 1.0 / (hd ** 0.5)
    kern = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, n_kb=n_kb,
        causal=causal, window=window, softcap=softcap, scale=scale,
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        grid=(BH, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
