"""GQA attention: RoPE, qk-norm, logit soft-capping, sliding window, KV cache."""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.sharding import WORKER_AXIS_ORDER
from repro.models.layers import apply_rope, dense_init, rmsnorm, softcap

Params = Dict[str, jax.Array]


def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype),
        "wk": dense_init(ks[1], (d, kv * hd), dtype),
        "wv": dense_init(ks[2], (d, kv * hd), dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _project_qkv(cfg: ModelConfig, p: Params, x: jax.Array, positions: jax.Array):
    B, S, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, h, hd)
    k = (x @ p["wk"]).reshape(B, S, kv, hd)
    v = (x @ p["wv"]).reshape(B, S, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.encoder_only:
        return q, k, v  # hubert/w2v2 use absolute (stub) features, no rope
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attend(
    cfg: ModelConfig,
    q: jax.Array,                 # (B, Sq, H, hd)
    k: jax.Array,                 # (B, Sk, KV, hd)
    v: jax.Array,                 # (B, Sk, KV, hd)
    q_positions: jax.Array,       # (B, Sq) or (Sq,)
    k_positions: jax.Array,       # (B, Sk) or (Sk,)
    window: Optional[jax.Array],  # scalar int32 or None (None = full attention)
    causal: bool,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    qg = q.reshape(B, Sq, KV, rep, hd)
    logits = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if cfg.attn_softcap:
        logits = softcap(logits, cfg.attn_softcap)
    qp = jnp.broadcast_to(jnp.atleast_2d(q_positions), (B, Sq))
    kp = jnp.broadcast_to(jnp.atleast_2d(k_positions), (B, k.shape[1]))
    rel = qp[:, :, None] - kp[:, None, :]               # (B, Sq, Sk)
    mask = jnp.ones_like(rel, dtype=bool)
    if causal:
        mask &= rel >= 0
    if window is not None:
        mask &= rel < window
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H * hd).astype(q.dtype)


def _kernel_platform() -> str:
    """Device kind the traced program is lowered for: the ambient mesh's
    devices (a mesh of described TPUs lowers for a TPU on a CPU host), else
    the default backend."""
    dev = jax.sharding.get_abstract_mesh().abstract_device
    return dev.device_kind if dev is not None else jax.default_backend()


def _use_flash(cfg: ModelConfig, S: int, hd: int, platform: str) -> bool:
    """Whether full-sequence attention takes the blocked (flash) kernel.

    Taken on a TPU (anywhere else Pallas would only interpret) when the
    sequence is at least two 128-row blocks, every layer shares one static
    window, and the kernel takes the head dim; otherwise the chunked dense
    path runs."""
    return (platform.lower().startswith("tpu")
            and S % 128 == 0 and S >= 256
            and len(set(cfg.layer_windows())) == 1
            and hd % 16 == 0 and hd <= 256)


def _flash_block(S: int) -> int:
    # 1024 x 1024 blocks: 14.1 ms a layer's forward at phi3 widths, batch
    # 8, against 20.8 ms at 512 (TPU v5 lite); larger ones overflow VMEM
    return next(b for b in (1024, 512, 256, 128) if S % b == 0)


def _attend_seq(cfg: ModelConfig, q, k, v, positions, window) -> jax.Array:
    """Full-sequence attention: the blocked kernel where ``_use_flash``
    allows, else dense masked attention, q-chunked when configured.

    Dense attention holds (B, H, Sq, Sk) fp32 scores; streaming query blocks
    of ``cfg.attn_chunk`` bounds that to (B, H, chunk, Sk), but each chunk's
    scores still go through HBM.
    """
    B, S = q.shape[0], q.shape[1]
    causal = not cfg.encoder_only
    if _use_flash(cfg, S, q.shape[-1], _kernel_platform()):
        return _attend_blocked(cfg, q, k, v, causal, cfg.layer_windows()[0])
    with jax.named_scope("attn.dense"):
        chunk = cfg.attn_chunk
        if chunk:
            while S % chunk:
                chunk //= 2
        if not chunk or S <= chunk:
            return _attend(cfg, q, k, v, positions, positions, window, causal)
        nc = S // chunk

        def body(_, xs):
            q_i, pos_i = xs                      # (B, chunk, H, hd), (chunk,)
            o = _attend(cfg, q_i, k, v, pos_i, positions, window, causal)
            return None, o

        if cfg.remat:
            body = jax.checkpoint(body)
        q_c = q.reshape(B, nc, chunk, *q.shape[2:]).swapaxes(0, 1)
        pos_c = positions.reshape(nc, chunk)
        _, outs = jax.lax.scan(
            body, None, (q_c, pos_c), unroll=nc if cfg.scan_unroll else 1)
        return outs.swapaxes(0, 1).reshape(B, S, -1)


def _attend_blocked(cfg: ModelConfig, q, k, v, causal: bool,
                    window: Optional[int], *, interpret: bool = False):
    """Blocked attention through the Pallas flash kernels (differentiable);
    q (B, S, H, hd), k/v (B, S, KV, hd) -> (B, S, H * hd).

    Under an ambient mesh the kernel runs in a ``shard_map`` over the mesh
    axes the trace has not made manual yet (all of them in the GSPMD FO
    step, the non-worker ones inside the ZO step's), so each device attends
    over its own sequences and heads and nothing is gathered.
    """
    from repro.kernels.flash_attention import flash_attention_pallas
    block = _flash_block(q.shape[1])

    def attend(q, k, v):
        heads_major = lambda x: x.transpose(0, 2, 1, 3)
        o = flash_attention_pallas(
            heads_major(q), heads_major(k), heads_major(v), causal=causal,
            window=window, softcap=cfg.attn_softcap, block_q=block,
            block_k=block, interpret=interpret)
        return heads_major(o).reshape(q.shape[0], q.shape[1], -1)

    with jax.named_scope("attn.flash"):
        spec = _flash_shard_spec(q.shape[0], q.shape[2], k.shape[2])
        if spec is None:
            return attend(q, k, v)
        # a nested shard_map names the axes already manual too
        axes = frozenset(jax.sharding.get_abstract_mesh().axis_names)
        return jax.shard_map(
            attend, in_specs=spec, out_specs=P(*spec[:3]), axis_names=axes,
            check_vma=False)(q, k, v)


def _flash_shard_spec(B: int, H: int, KV: int) -> Optional[P]:
    """The (B, S, H, hd) spec of the kernel's shard_map: the batch over the
    worker axes and the heads over ``model``, where the trace left them to
    the partitioner and they divide; None when no ambient mesh axis is left
    to it (a Mosaic kernel cannot be partitioned, not even over an axis of
    size 1)."""
    am = jax.sharding.get_abstract_mesh()
    free = frozenset(am.axis_names) - frozenset(am.manual_axes)
    if not free:
        return None
    batch = tuple(a for a in WORKER_AXIS_ORDER if a in free)
    if B % math.prod(am.shape[a] for a in batch):
        batch = ()
    heads = None
    if "model" in free and H % am.shape["model"] == 0 \
            and KV % am.shape["model"] == 0:
        heads = "model"
    return P(batch or None, None, heads, None)


def attention_forward(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,               # (B, S, D)
    window: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence attention (train / prefill), causal unless encoder_only."""
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, positions)
    out = _attend_seq(cfg, q, k, v, positions, window)
    return out @ p["wo"]


def attention_prefill(
    cfg: ModelConfig, p: Params, x: jax.Array, window: Optional[jax.Array] = None
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Like forward but also returns the (k, v) cache."""
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, positions)
    out = _attend_seq(cfg, q, k, v, positions, window)
    return out @ p["wo"], (k, v)


def _hd_model_spec(ndim: int):
    """P(..., 'model') on the trailing head_dim, when a mesh is ambient.

    Decode attention with an hd-sharded cache needs q/k/v contraction dims
    aligned, or the partitioner all-gathers the WHOLE cache over the model
    axis per layer (measured: 1 GiB fp32/layer for gemma2 decode_32k —
    EXPERIMENTS.md §Perf iteration 3)."""
    try:
        am = jax.sharding.get_abstract_mesh()
    except Exception:
        return None
    if am is None or not am.axis_names or "model" not in am.axis_names:
        return None
    return P(*([None] * (ndim - 1) + ["model"]))


def _constrain_hd(x: jax.Array) -> jax.Array:
    spec = _hd_model_spec(x.ndim)
    if spec is None:
        return x
    ms = jax.sharding.get_abstract_mesh().shape["model"]
    if x.shape[-1] % ms:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def attention_decode(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                       # (B, 1, D) current token's hidden
    cache: Tuple[jax.Array, jax.Array],  # k,v (B, S, KV, hd); positions 0..S-1
    pos: jax.Array,                      # scalar int32: index of current token
    window: Optional[jax.Array] = None,
    static_window: Optional[int] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One-token decode against a KV cache; writes the new k/v at ``pos``.

    ``pos`` may be a scalar (the whole batch sits at one position — the seed
    synchronous path) or a ``(B,)`` vector (the serving slot pool, where every
    slot decodes at its own position; ``pos == -1`` marks an inactive slot:
    nothing is written and the causal mask blanks every read).

    When every layer shares one static window, ``static_window`` lets us read
    only the last ``W`` cache slots (a dynamic_slice) instead of streaming the
    whole cache — this is what makes windowed decode sub-linear in cache size.
    (Scalar-``pos`` only; the per-slot path masks the window via relative
    positions instead, since slots sit at different offsets.)
    """
    if jnp.ndim(pos) > 0:
        return _attention_decode_slots(cfg, p, x, cache, pos, window)
    k_cache, v_cache = cache
    S = k_cache.shape[1]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    q = _constrain_hd(q)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
    if static_window is not None and static_window < S:
        W = static_window
        start = jnp.clip(pos - W + 1, 0, S - W)
        k_read = jax.lax.dynamic_slice_in_dim(k_cache, start, W, axis=1)
        v_read = jax.lax.dynamic_slice_in_dim(v_cache, start, W, axis=1)
        k_positions = start + jnp.arange(W, dtype=jnp.int32)
    else:
        k_read, v_read = k_cache, v_cache
        k_positions = jnp.arange(S, dtype=jnp.int32)
    k_read = _constrain_hd(k_read)
    v_read = _constrain_hd(v_read)
    # beyond-pos slots are masked by the causal rel>=0 test (q position == pos)
    out = _attend(
        cfg, q, k_read, v_read, positions, k_positions, window, causal=True
    )
    return out @ p["wo"], (k_cache, v_cache)


def _attention_decode_slots(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                        # (B, 1, D) current token per slot
    cache: Tuple[jax.Array, jax.Array],  # k,v (B, S, KV, hd)
    pos: jax.Array,                      # (B,) int32 per-slot position, -1 = inactive
    window: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Per-slot decode: each batch row writes/reads at its OWN position.

    The write is a masked select (one row of the length-S axis per slot)
    rather than a dynamic_update_slice, because start indices differ per
    row; inactive slots (``pos == -1``) match no row and write nothing.
    Reads stream the full cache — the causal test ``q_pos - k_pos >= 0``
    limits each slot to its own live prefix, and the sliding window (when
    configured) is enforced by the same relative-position mask."""
    k_cache, v_cache = cache
    S = k_cache.shape[1]
    positions = pos[:, None].astype(jnp.int32)           # (B, 1) q positions
    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    q = _constrain_hd(q)
    write = (jnp.arange(S, dtype=jnp.int32)[None, :] == positions)[..., None, None]
    k_cache = jnp.where(write, k_new.astype(k_cache.dtype), k_cache)
    v_cache = jnp.where(write, v_new.astype(v_cache.dtype), v_cache)
    k_positions = jnp.arange(S, dtype=jnp.int32)
    out = _attend(
        cfg, q, _constrain_hd(k_cache), _constrain_hd(v_cache),
        positions, k_positions, window, causal=True,
    )
    return out @ p["wo"], (k_cache, v_cache)
