"""Composable decoder/encoder transformer covering all assigned architectures.

One homogeneous ``lax.scan`` over stacked per-layer params drives every arch;
per-layer attention windows are a scanned int32 array (FULL = 2**30 means no
window).  This keeps HLO size O(1) in depth — the roofline reader corrects
the scan-body single-count (see benchmarks/roofline.py).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    embed_init,
    init_mlp,
    init_norm,
    rmsnorm,
    softcap,
)

Params = Dict
FULL_WINDOW = 1 << 30
MOE_AUX_COEF = 0.01


def windows_array(cfg: ModelConfig) -> jax.Array:
    return jnp.asarray(
        [FULL_WINDOW if w is None else int(w) for w in cfg.layer_windows()],
        jnp.int32,
    )


def uniform_static_window(cfg: ModelConfig) -> Optional[int]:
    """The single static window if every layer shares one, else None."""
    ws = set(cfg.layer_windows())
    if len(ws) == 1 and None not in ws:
        return int(next(iter(ws)))
    return None


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #
def _init_layer(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {
        "norm1": init_norm(cfg, cfg.d_model),
        "norm2": init_norm(cfg, cfg.d_model),
    }
    if cfg.post_norms:
        p["post_norm1"] = init_norm(cfg, cfg.d_model)
        p["post_norm2"] = init_norm(cfg, cfg.d_model)
    if cfg.has_attention:
        p["attn"] = attn.init_attention(ks[0], cfg, dtype)
    if cfg.has_ssm:
        p["mamba"] = ssm_mod.init_mamba(ks[1], cfg, dtype)
    if cfg.arch_type == "hybrid":
        p["attn_out_scale"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["mamba_out_scale"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if cfg.is_moe:
        p["moe"] = moe_mod.init_moe(ks[2], cfg, dtype)
        if cfg.moe_dense_residual:
            p["dense_mlp"] = init_mlp(ks[3], cfg, cfg.dense_d_ff, dtype)
    elif cfg.d_ff:
        p["mlp"] = init_mlp(ks[2], cfg, cfg.d_ff, dtype)
    return p


def init_model(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    params: Params = {}
    if cfg.frontend != "audio":
        params["embed"] = embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params["layers"] = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(layer_keys)
    params["final_norm"] = init_norm(cfg, cfg.d_model)
    if cfg.tie_embeddings and cfg.frontend != "audio":
        pass  # head = embed.T
    else:
        params["head"] = embed_init(k_head, (cfg.d_model, cfg.vocab_size), dtype)
    return params


# --------------------------------------------------------------------------- #
# blocks
# --------------------------------------------------------------------------- #
def _mix(cfg: ModelConfig, lp: Params, xn: jax.Array, window: jax.Array) -> jax.Array:
    """Sequence-mixing sublayer (attention / mamba / hymba parallel fusion)."""
    if cfg.arch_type == "ssm":
        return ssm_mod.mamba_forward(cfg, lp["mamba"], xn)
    if cfg.arch_type == "hybrid":
        a = attn.attention_forward(cfg, lp["attn"], xn, window)
        m = ssm_mod.mamba_forward(cfg, lp["mamba"], xn)
        return 0.5 * (
            rmsnorm(a, lp["attn_out_scale"], cfg.norm_eps)
            + rmsnorm(m, lp["mamba_out_scale"], cfg.norm_eps)
        )
    return attn.attention_forward(cfg, lp["attn"], xn, window)


def _ffn(cfg: ModelConfig, lp: Params, xn: jax.Array) -> Tuple[jax.Array, jax.Array]:
    if cfg.is_moe:
        y, aux = moe_mod.moe_forward(cfg, lp["moe"], xn)
        if cfg.moe_dense_residual:
            y = y + apply_mlp(cfg, lp["dense_mlp"], xn)
        return y, aux
    if cfg.d_ff:
        return apply_mlp(cfg, lp["mlp"], xn), jnp.zeros((), jnp.float32)
    return jnp.zeros_like(xn), jnp.zeros((), jnp.float32)


def _block(cfg: ModelConfig, lp: Params, x: jax.Array, window: jax.Array):
    with jax.named_scope("model.attn"):
        mix = _mix(cfg, lp, apply_norm(cfg, lp["norm1"], x), window)
        if cfg.post_norms:
            mix = apply_norm(cfg, lp["post_norm1"], mix)
        x = x + mix
    with jax.named_scope("model.mlp"):
        ff, aux = _ffn(cfg, lp, apply_norm(cfg, lp["norm2"], x))
        if cfg.post_norms:
            ff = apply_norm(cfg, lp["post_norm2"], ff)
        return x + ff, aux


# --------------------------------------------------------------------------- #
# embedding / inputs
# --------------------------------------------------------------------------- #
def embed_batch(cfg: ModelConfig, params: Params, batch: Dict) -> jax.Array:
    if cfg.frontend == "audio":
        return batch["features"]
    scale = math.sqrt(cfg.d_model)
    if cfg.frontend == "vision":
        text = jnp.take(params["embed"], batch["tokens"], axis=0) * scale
        return jnp.concatenate(
            [batch["image_embeds"].astype(text.dtype), text], axis=1
        )
    return jnp.take(params["embed"], batch["tokens"], axis=0) * scale


def compute_logits(cfg: ModelConfig, params: Params, h: jax.Array) -> jax.Array:
    h = apply_norm(cfg, params["final_norm"], h)
    head = params["embed"].T if "head" not in params else params["head"]
    logits = h @ head
    if cfg.final_softcap:
        logits = softcap(logits, cfg.final_softcap)
    return logits


# --------------------------------------------------------------------------- #
# forward (train / prefill)
# --------------------------------------------------------------------------- #
def _unroll(cfg: ModelConfig):
    # the dry-run's depth-point lowerings unroll so cost_analysis sees every
    # layer (a lax.scan body is counted once regardless of trip count)
    return cfg.n_layers if cfg.scan_unroll else 1


def forward_hidden(cfg: ModelConfig, params: Params, h: jax.Array):
    windows = windows_array(cfg)

    def body(carry, xs):
        x, aux = carry
        lp, win = xs
        x, a = _block(cfg, lp, x, win)
        return (x, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (h, aux), _ = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32)), (params["layers"], windows),
        unroll=_unroll(cfg),
    )
    return h, aux


def forward_logits(cfg: ModelConfig, params: Params, batch: Dict):
    h = embed_batch(cfg, params, batch)
    h, aux = forward_hidden(cfg, params, h)
    return compute_logits(cfg, params, h), aux


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over positions with label >= 0. logits (B,S,V), labels (B,S)."""
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), safe[..., None], axis=-1
    )[..., 0]
    ce = (lse - gold) * mask
    return jnp.sum(ce) / jnp.maximum(jnp.sum(mask), 1)


def ce_chunk_size(cfg: ModelConfig) -> int:
    """Vocab-chunk size for the streaming CE (0 = dense logits).

    Production default: chunk vocabularies >= 16384 so the live logits buffer
    is B*S*chunk instead of B*S*V — large-vocab archs cannot fit dense fp32
    logits + their gradients in HBM at the assigned batch sizes.
    """
    if cfg.ce_chunk > 0:
        return cfg.ce_chunk if cfg.vocab_size > cfg.ce_chunk else 0
    if cfg.ce_chunk < 0 or cfg.vocab_size < 16384:
        return 0
    return 8192


def cross_entropy_streaming(cfg: ModelConfig, head: jax.Array, h: jax.Array,
                            labels: jax.Array) -> jax.Array:
    """CE with vocab-chunked logits: scan over (D, chunk) head slices with a
    running (max, sumexp, gold) carry; logits are rematerialized in the
    backward pass instead of stored.  The head is zero-padded to a multiple
    of the chunk; padded columns are masked out of the running stats."""
    chunk = ce_chunk_size(cfg)
    B, S, D = h.shape
    V = head.shape[1]
    if not chunk or V <= chunk:
        return cross_entropy(jnp.einsum("bsd,dv->bsv", h, head), labels)
    T = B * S
    hf = h.reshape(T, D)
    lab = labels.reshape(T)
    mask = lab >= 0
    safe = jnp.maximum(lab, 0)
    n_chunks = (V + chunk - 1) // chunk

    # dynamic_slice of the head per chunk (no padded / transposed copy of the
    # (D, V) matrix — for a 152k-vocab model that copy is 1.5 GiB per eval).
    # The final chunk's slice start clamps to V-chunk, so it may overlap the
    # previous chunk; already-counted columns are masked out.
    def body(carry, c_idx):
        m, s, gold = carry
        start = jnp.maximum(jnp.minimum(c_idx * chunk, V - chunk), 0)
        W_c = jax.lax.dynamic_slice(head, (0, start), (D, chunk))
        logits = (hf @ W_c).astype(jnp.float32)          # (T, chunk)
        if cfg.final_softcap:
            logits = softcap(logits, cfg.final_softcap)
        col = start + jnp.arange(chunk, dtype=jnp.int32)
        fresh = col >= c_idx * chunk                     # mask overlap columns
        logits = jnp.where(fresh[None, :], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(logits - m_new[:, None]), -1)
        rel = safe - start
        in_r = (rel >= 0) & (rel < chunk) & (safe >= c_idx * chunk)
        got = jnp.take_along_axis(logits, jnp.clip(rel, 0, chunk - 1)[:, None], 1)[:, 0]
        gold = gold + jnp.where(in_r, got, 0.0)
        return (m_new, s, gold), None

    body = jax.checkpoint(body)
    init = (jnp.full((T,), -1e30, jnp.float32), jnp.zeros((T,), jnp.float32),
            jnp.zeros((T,), jnp.float32))
    (m, s, gold), _ = jax.lax.scan(
        body, init, jnp.arange(n_chunks, dtype=jnp.int32),
        unroll=_unroll(cfg),
    )
    ce = (m + jnp.log(s) - gold) * mask
    return jnp.sum(ce) / jnp.maximum(jnp.sum(mask), 1)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict) -> jax.Array:
    with jax.named_scope("model.embed"):
        h = embed_batch(cfg, params, batch)
    h, aux = forward_hidden(cfg, params, h)
    with jax.named_scope("model.head"):
        if ce_chunk_size(cfg):
            h = apply_norm(cfg, params["final_norm"], h)
            head = params["embed"].T if "head" not in params else params["head"]
            ce = cross_entropy_streaming(cfg, head, h, batch["labels"])
        else:
            logits = compute_logits(cfg, params, h)
            ce = cross_entropy(logits, batch["labels"])
        return ce + MOE_AUX_COEF * aux


# --------------------------------------------------------------------------- #
# serving: prefill + single-token decode with stacked per-layer caches
# --------------------------------------------------------------------------- #
def init_caches(cfg: ModelConfig, batch: int, seq_len: int, dtype) -> Dict:
    caches: Dict = {}
    L = cfg.n_layers
    if cfg.has_attention:
        shape = (L, batch, seq_len, cfg.n_kv_heads, cfg.head_dim)
        caches["k"] = jnp.zeros(shape, dtype)
        caches["v"] = jnp.zeros(shape, dtype)
    if cfg.has_ssm:
        caches["conv"] = jnp.zeros((L, batch, cfg.ssm_conv - 1, cfg.d_inner), dtype)
        caches["ssm"] = jnp.zeros((L, batch, cfg.d_inner, cfg.ssm_state), jnp.float32)
    return caches


def _block_decode(cfg: ModelConfig, lp: Params, x, pos, cache_l: Dict, window):
    new_cache: Dict = {}
    xn = apply_norm(cfg, lp["norm1"], x)
    static_w = uniform_static_window(cfg)
    if cfg.arch_type == "ssm":
        mix, (new_cache["conv"], new_cache["ssm"]) = ssm_mod.mamba_decode(
            cfg, lp["mamba"], xn, (cache_l["conv"], cache_l["ssm"])
        )
    elif cfg.arch_type == "hybrid":
        a, (new_cache["k"], new_cache["v"]) = attn.attention_decode(
            cfg, lp["attn"], xn, (cache_l["k"], cache_l["v"]), pos, window,
            static_window=static_w,
        )
        m, (new_cache["conv"], new_cache["ssm"]) = ssm_mod.mamba_decode(
            cfg, lp["mamba"], xn, (cache_l["conv"], cache_l["ssm"])
        )
        mix = 0.5 * (
            rmsnorm(a, lp["attn_out_scale"], cfg.norm_eps)
            + rmsnorm(m, lp["mamba_out_scale"], cfg.norm_eps)
        )
    else:
        mix, (new_cache["k"], new_cache["v"]) = attn.attention_decode(
            cfg, lp["attn"], xn, (cache_l["k"], cache_l["v"]), pos, window,
            static_window=static_w,
        )
    if cfg.post_norms:
        mix = apply_norm(cfg, lp["post_norm1"], mix)
    x = x + mix
    ff, _ = _ffn(cfg, lp, apply_norm(cfg, lp["norm2"], x))
    if cfg.post_norms:
        ff = apply_norm(cfg, lp["post_norm2"], ff)
    return x + ff, new_cache


def decode_step(cfg: ModelConfig, params: Params, token: jax.Array, pos, caches: Dict):
    """One decode step. token (B,) int32, pos scalar int32; returns (logits(B,V), caches)."""
    scale = math.sqrt(cfg.d_model)
    h = jnp.take(params["embed"], token, axis=0)[:, None, :] * scale  # (B,1,D)
    windows = windows_array(cfg)

    def body(x, xs):
        lp, win, cache_l = xs
        x, new_cache = _block_decode(cfg, lp, x, pos, cache_l, win)
        return x, new_cache

    h, new_caches = jax.lax.scan(
        body, h, (params["layers"], windows, caches), unroll=_unroll(cfg))
    logits = compute_logits(cfg, params, h)[:, 0]
    return logits, new_caches


def decode_step_slots(cfg: ModelConfig, params: Params, tokens: jax.Array,
                      pos: jax.Array, caches: Dict):
    """One decode step over a slot pool: every row at its OWN position.

    tokens (B,) int32 (row b's current token), pos (B,) int32 (row b's
    position; -1 = inactive slot — nothing written, logits are don't-care);
    returns (logits (B, V), caches).  This is the continuous-batching decode
    program: the batch axis is the KV-cache slot pool, and admission/eviction
    only change ``tokens``/``pos``, never the jitted program's shapes.
    """
    scale = math.sqrt(cfg.d_model)
    h = jnp.take(params["embed"], tokens, axis=0)[:, None, :] * scale  # (B,1,D)
    windows = windows_array(cfg)
    pos = jnp.asarray(pos, jnp.int32)

    def body(x, xs):
        lp, win, cache_l = xs
        x, new_cache = _block_decode(cfg, lp, x, pos, cache_l, win)
        return x, new_cache

    h, new_caches = jax.lax.scan(
        body, h, (params["layers"], windows, caches), unroll=_unroll(cfg))
    logits = compute_logits(cfg, params, h)[:, 0]
    return logits, new_caches


def prefill(cfg: ModelConfig, params: Params, batch: Dict):
    """Process the prompt, returning last-position logits and filled caches."""
    h, caches = _prefill_hidden(cfg, params, batch)
    logits = compute_logits(cfg, params, h[:, -1:, :])[:, 0]
    return logits, caches


def prefill_at(cfg: ModelConfig, params: Params, batch: Dict, last_idx: jax.Array):
    """Prefill over a (possibly right-padded) prompt rectangle, returning the
    logits at per-row position ``last_idx`` (B,) int32 — the last REAL prompt
    token — and the filled caches.

    This is the bucketed-prefill target: prompts are right-padded to a fixed
    bucket length so one jitted executable serves every prompt in the bucket,
    and causal attention guarantees positions <= last_idx never see the pad
    tail.  (Attention-only configs; an SSM's post-prompt state integrates the
    whole sequence, so SSM/hybrid prefills must run at exact length where
    ``last_idx`` is simply the final position.)
    """
    h, caches = _prefill_hidden(cfg, params, batch)
    h_last = jnp.take_along_axis(
        h, last_idx.astype(jnp.int32)[:, None, None], axis=1)  # (B, 1, D)
    logits = compute_logits(cfg, params, h_last)[:, 0]
    return logits, caches


def _prefill_hidden(cfg: ModelConfig, params: Params, batch: Dict):
    """Shared prefill scan: full-sequence hidden states + per-layer caches."""
    h = embed_batch(cfg, params, batch)
    windows = windows_array(cfg)

    # Mirrors _block but captures per-layer caches as scan outputs.
    def body_cache(carry, xs):
        x = carry
        lp, win = xs
        cache: Dict = {}
        xn = apply_norm(cfg, lp["norm1"], x)
        if cfg.arch_type == "ssm":
            mix = ssm_mod.mamba_forward(cfg, lp["mamba"], xn)
            cache["conv"], cache["ssm"] = _mamba_tail_state(cfg, lp["mamba"], xn)
        elif cfg.arch_type == "hybrid":
            a, (cache["k"], cache["v"]) = attn.attention_prefill(cfg, lp["attn"], xn, win)
            m = ssm_mod.mamba_forward(cfg, lp["mamba"], xn)
            cache["conv"], cache["ssm"] = _mamba_tail_state(cfg, lp["mamba"], xn)
            mix = 0.5 * (
                rmsnorm(a, lp["attn_out_scale"], cfg.norm_eps)
                + rmsnorm(m, lp["mamba_out_scale"], cfg.norm_eps)
            )
        else:
            mix, (cache["k"], cache["v"]) = attn.attention_prefill(cfg, lp["attn"], xn, win)
        if cfg.post_norms:
            mix = apply_norm(cfg, lp["post_norm1"], mix)
        x = x + mix
        ff, _ = _ffn(cfg, lp, apply_norm(cfg, lp["norm2"], x))
        if cfg.post_norms:
            ff = apply_norm(cfg, lp["post_norm2"], ff)
        return x + ff, cache

    h, caches = jax.lax.scan(
        body_cache, h, (params["layers"], windows), unroll=_unroll(cfg))
    return h, caches


def _mamba_tail_state(cfg: ModelConfig, mp: Params, xn: jax.Array):
    """Recompute the post-prompt (conv, ssm) state for decode continuation."""
    u, _ = jnp.split(xn @ mp["in_proj"], 2, axis=-1)
    K = cfg.ssm_conv
    conv_state = u[:, -(K - 1) :, :]
    u_c = jax.nn.silu(ssm_mod._causal_conv(mp, u, K))
    deltaA, deltaBu, _ = ssm_mod._ssm_inputs(cfg, mp, u_c)
    h = ssm_mod._assoc_scan(deltaA, deltaBu)[:, -1]
    return conv_state, h
