"""Flash attention Pallas-TPU kernels: blocked online softmax, forward and
backward, under one ``custom_vjp``.

Supports the whole feature matrix of the assigned archs: causal masking,
sliding window (gemma2 local layers / long-context variants), gemma2 logit
soft-capping, and GQA.

Layout: q ``(B, H, S, hd)``, k and v ``(B, KV, S, hd)``.  A q head reads
its kv head (``h // (H // KV)``) through the index maps, so no repeated
k/v is written to HBM, and a block's last dim is the whole head dim (96
for phi3: no padding in HBM).

VMEM tiling: a (block_q x hd) query tile streams over (block_k x hd)
key/value tiles along the innermost sequential grid dim; running max /
denominator / accumulator live in VMEM scratch across that dim, so the
S x S logits never reach HBM.  The grid walks only the live block pairs
(a scalar-prefetched table of them): key blocks that are fully masked
(above the causal diagonal, outside the window) take no grid step and no
DMA, and only blocks that straddle a mask edge build the mask.

Arithmetic is the dense path's on a TPU at DEFAULT precision: q.k^T takes
the inputs' dtype into f32 accumulation; scale, soft cap, mask, max, exp
and sums stay f32; P (and dS in the backward) go to the MXU in the dtype of
v (k, q), i.e. one bf16 pass, as DEFAULT does with the dense path's f32 P.

Backward (FlashAttention-2): the forward saves q, k, v, its f32 output and
the row log-sum-exp.  The dq kernel walks key blocks for each query block;
the dk/dv kernel walks, for each key block, every query block of every q
head that shares the kv head, accumulating in VMEM.  Both recompute P per
block from the log-sum-exp.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


@dataclasses.dataclass(frozen=True)
class _Geom:
    """Static block geometry and masking of one attention call."""
    seq: int
    block_q: int
    block_k: int
    causal: bool
    window: Optional[int]
    softcap: Optional[float]
    scale: float
    interpret: bool

    @property
    def n_qb(self) -> int:
        return self.seq // self.block_q

    @property
    def n_kb(self) -> int:
        return self.seq // self.block_k

    def kv_range(self, qb):
        """First and last key block that query block ``qb`` (a Python or a
        traced int) can see."""
        bq, bk = self.block_q, self.block_k
        hi = (qb * bq + bq - 1) // bk if self.causal else self.n_kb - 1
        lo = 0
        if self.window is not None:
            lo = _max(qb * bq - self.window + 1, 0) // bk
        return lo, hi

    def q_range(self, kb):
        """First and last query block that can see key block ``kb``."""
        bq, bk = self.block_q, self.block_k
        lo = (kb * bk) // bq if self.causal else 0
        hi = self.n_qb - 1
        if self.window is not None:
            hi = _min((kb * bk + bk + self.window - 2) // bq, hi)
        return lo, hi

    def q_major_steps(self) -> np.ndarray:
        """(qb, kb) of every live block pair, query block major: (2, n)."""
        steps = []
        for qb in range(self.n_qb):
            lo, hi = self.kv_range(qb)
            steps += [(qb, kb) for kb in range(lo, hi + 1)]
        return np.asarray(steps, np.int32).T

    def k_major_steps(self, rep: int) -> np.ndarray:
        """(kb, r, qb) of every live block pair of each of the ``rep`` q
        heads that share a kv head, key block major: (3, n)."""
        steps = []
        for kb in range(self.n_kb):
            lo, hi = self.q_range(kb)
            steps += [(kb, r, qb) for r in range(rep)
                      for qb in range(lo, hi + 1)]
        return np.asarray(steps, np.int32).T

    def unmasked(self, qb, kb):
        """True when every (query, key) pair of the two blocks is visible."""
        bq, bk = self.block_q, self.block_k
        full = True
        if self.causal:
            full = kb * bk + bk - 1 <= qb * bq
        if self.window is not None:
            full = jnp.logical_and(full, qb * bq + bq - 1 - kb * bk < self.window)
        return full

    def visible(self, q_pos, k_pos):
        rel = q_pos - k_pos
        ok = jnp.ones(rel.shape, bool)
        if self.causal:
            ok &= rel >= 0
        if self.window is not None:
            ok &= rel < self.window
        return ok

    def logits(self, a, b):
        """Scaled, soft-capped ``a @ b.T`` in f32, and tanh of the cap (for
        the backward) or None."""
        s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
        s = s * self.scale
        if self.softcap is None:
            return s, None
        t = jnp.tanh(s / self.softcap)
        return self.softcap * t, t


def _max(a, b):
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _min(a, b):
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _split_by_mask(full, step):
    """Run ``step(masked)``, building the mask only when the block pair
    straddles a mask edge (``full`` False)."""
    if full is True:
        step(False)
        return
    pl.when(full)(lambda: step(False))
    pl.when(jnp.logical_not(full))(lambda: step(True))


def _block_positions(g: _Geom, qb, kb, rows_are_q: bool):
    bq, bk = g.block_q, g.block_k
    shape = (bq, bk) if rows_are_q else (bk, bq)
    q_dim, k_dim = (0, 1) if rows_are_q else (1, 0)
    q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, shape, k_dim)
    return q_pos, k_pos


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #
def _fwd_kernel(qb_tab, kb_tab, q_ref, k_ref, v_ref, o_ref, *rest, g: _Geom,
                save: bool):
    lse_ref = rest[0] if save else None
    m_scr, l_scr, acc_scr = rest[-3:]
    t = pl.program_id(2)
    qb, kb = qb_tab[t], kb_tab[t]
    lo, hi = g.kv_range(qb)

    @pl.when(kb == lo)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        v = v_ref[...]
        s, _ = g.logits(q_ref[...], k_ref[...])           # (bq, bk) f32
        if masked:
            s = jnp.where(g.visible(*_block_positions(g, qb, kb, True)),
                          s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _split_by_mask(g.unmasked(qb, kb), step)

    @pl.when(kb == hi)
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        if save:
            lse_ref[...] = m_scr[...] + jnp.log(l)


def _q_major_specs(g: _Geom, rep: int, hd: int):
    """Block specs of a (B, H, live pairs) grid whose step t is the pair
    (qb_tab[t], kb_tab[t])."""
    q_map = lambda b, h, t, qb_tab, kb_tab: (b, h, qb_tab[t], 0)
    kv_map = lambda b, h, t, qb_tab, kb_tab: (b, h // rep, kb_tab[t], 0)
    q_spec = pl.BlockSpec((None, None, g.block_q, hd), q_map)
    kv_spec = pl.BlockSpec((None, None, g.block_k, hd), kv_map)
    col_spec = pl.BlockSpec((None, None, g.block_q, 1), q_map)
    return q_spec, kv_spec, col_spec


def _call(kernel, g: _Geom, tables: np.ndarray, grid, in_specs, out_specs,
          out_shape, scratch_shapes, *args):
    """``pallas_call`` over a (B, heads, live pairs) grid that walks the
    scalar-prefetched ``tables`` of block indices."""
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=g.interpret,
    )(*(jnp.asarray(tab) for tab in tables), *args)


def _forward(q, k, v, g: _Geom, save: bool):
    B, H, S, hd = q.shape
    rep = H // k.shape[1]
    q_spec, kv_spec, col_spec = _q_major_specs(g, rep, hd)
    out_shape = [jax.ShapeDtypeStruct(q.shape, jnp.float32 if save else q.dtype)]
    out_specs = [q_spec]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32))
        out_specs.append(col_spec)
    steps = g.q_major_steps()
    outs = _call(
        functools.partial(_fwd_kernel, g=g, save=save), g, steps,
        (B, H, steps.shape[1]), [q_spec, kv_spec, kv_spec], out_specs,
        out_shape,
        [pltpu.VMEM((g.block_q, 1), jnp.float32),     # running max
         pltpu.VMEM((g.block_q, 1), jnp.float32),     # running denominator
         pltpu.VMEM((g.block_q, hd), jnp.float32)],   # output accumulator
        q, k, v)
    return outs if save else outs[0]


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #
def _dq_kernel(qb_tab, kb_tab, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               dq_ref, acc_scr, *, g: _Geom):
    t = pl.program_id(2)
    qb, kb = qb_tab[t], kb_tab[t]
    lo, hi = g.kv_range(qb)

    @pl.when(kb == lo)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        k = k_ref[...]
        s, capped = g.logits(q_ref[...], k)               # (bq, bk)
        if masked:
            s = jnp.where(g.visible(*_block_positions(g, qb, kb, True)),
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[...])
        dp = jax.lax.dot_general(do_ref[...], v_ref[...], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        if capped is not None:
            ds = ds * (1.0 - capped * capped)
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _split_by_mask(g.unmasked(qb, kb), step)

    @pl.when(kb == hi)
    def _finish():
        dq_ref[...] = (acc_scr[...] * g.scale).astype(dq_ref.dtype)


def _dkv_kernel(kb_tab, r_tab, qb_tab, q_ref, k_ref, v_ref, do_ref, lse_ref,
                di_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, g: _Geom, rep: int):
    t = pl.program_id(2)
    kb, r, qb = kb_tab[t], r_tab[t], qb_tab[t]
    lo, hi = g.q_range(kb)

    @pl.when(jnp.logical_and(r == 0, qb == lo))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        q, do = q_ref[...], do_ref[...]
        s, capped = g.logits(k_ref[...], q)               # (bk, bq): S^T
        if masked:
            s = jnp.where(g.visible(*_block_positions(g, qb, kb, False)),
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[...])                     # lse as a row
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[...], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        if capped is not None:
            ds = ds * (1.0 - capped * capped)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _split_by_mask(g.unmasked(qb, kb), step)

    @pl.when(jnp.logical_and(r == rep - 1, qb == hi))
    def _finish():
        dk_ref[...] = (dk_scr[...] * g.scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _backward(g: _Geom, res, do):
    q, k, v, o, lse = res
    B, H, S, hd = q.shape
    KV = k.shape[1]
    rep = H // KV
    di = jnp.sum(o * do.astype(jnp.float32), axis=-1)    # (B, H, S)

    q_spec, kv_spec, col_spec = _q_major_specs(g, rep, hd)
    steps = g.q_major_steps()
    dq = _call(
        functools.partial(_dq_kernel, g=g), g, steps, (B, H, steps.shape[1]),
        [q_spec, kv_spec, kv_spec, q_spec, col_spec, col_spec], q_spec,
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((g.block_q, hd), jnp.float32)],
        q, k, v, do, lse, di[..., None])

    # key block major, over every q head that reads the kv head: step t is
    # (kb_tab[t], head kvh * rep + r_tab[t], qb_tab[t])
    def qside_map(b, kvh, t, kb_tab, r_tab, qb_tab):
        return b, kvh * rep + r_tab[t], qb_tab[t], 0

    def row_map(b, kvh, t, kb_tab, r_tab, qb_tab):
        return b, kvh * rep + r_tab[t], 0, qb_tab[t]

    kside_map = lambda b, kvh, t, kb_tab, r_tab, qb_tab: (b, kvh, kb_tab[t], 0)
    qside = pl.BlockSpec((None, None, g.block_q, hd), qside_map)
    kside = pl.BlockSpec((None, None, g.block_k, hd), kside_map)
    row = pl.BlockSpec((None, None, 1, g.block_q), row_map)
    steps = g.k_major_steps(rep)
    dk, dv = _call(
        functools.partial(_dkv_kernel, g=g, rep=rep), g, steps,
        (B, KV, steps.shape[1]), [qside, kside, kside, qside, row, row],
        [kside, kside],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((g.block_k, hd), jnp.float32),
         pltpu.VMEM((g.block_k, hd), jnp.float32)],
        q, k, v, do, lse.reshape(B, H, 1, S), di[:, :, None, :])
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom_vjp
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, g: _Geom):
    return _forward(q, k, v, g, save=False)


def _flash_fwd(q, k, v, g: _Geom):
    o, lse = _forward(q, k, v, g, save=True)
    return o.astype(q.dtype), (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _backward)


def flash_attention_pallas(
    q: jax.Array,            # (B, H, S, hd)
    k: jax.Array,            # (B, KV, S, hd); H a multiple of KV
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    """Differentiable blocked attention over full sequences; (B, H, S, hd)."""
    S, hd = q.shape[2], q.shape[3]
    block_q, block_k = min(block_q, S), min(block_k, S)
    assert k.shape[2] == S and S % block_q == 0 and S % block_k == 0
    assert q.shape[1] % k.shape[1] == 0
    g = _Geom(seq=S, block_q=block_q, block_k=block_k, causal=causal,
              window=window, softcap=softcap, scale=1.0 / hd ** 0.5,
              interpret=interpret)
    return _flash(q, k, v, g)
