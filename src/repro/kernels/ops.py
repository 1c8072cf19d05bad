"""Public jit'd wrappers around the Pallas kernels.

The one place that decides interpret mode: every raw kernel takes
``interpret`` as a required keyword, and these wrappers pass ``INTERPRET``.
On a CPU backend the kernel body runs per-block in interpret mode; on a TPU
it lowers through Mosaic.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.selective_scan import selective_scan_pallas
from repro.kernels import zo_direction as zo_k

INTERPRET = jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
            block_rows: int = 128) -> jax.Array:
    """Fused RMSNorm over the last dim; any leading shape."""
    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])
    rows = flat.shape[0]
    br = block_rows
    while rows % br:
        br //= 2
    out = rmsnorm_pallas(flat, scale, eps, max(br, 1), interpret=INTERPRET)
    return out.reshape(*lead, x.shape[-1])


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, KV, hd)
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Differentiable GQA flash attention; returns (B, Sq, H, hd)."""
    heads_major = lambda x: x.transpose(0, 2, 1, 3)
    out = flash_attention_pallas(
        heads_major(q), heads_major(k), heads_major(v), causal=causal,
        window=window, softcap=softcap, block_q=block_q, block_k=block_k,
        interpret=INTERPRET,
    )
    return heads_major(out)


@partial(jax.jit, static_argnames=("block_d", "block_s"))
def selective_scan(u, dt, Bmat, Cmat, A, D, block_d: int = 256, block_s: int = 128):
    return selective_scan_pallas(
        u, dt, Bmat, Cmat, A, D, block_d=block_d, block_s=block_s,
        interpret=INTERPRET,
    )


@partial(jax.jit, static_argnames=("n", "block"))
def zo_sumsq(n: int, salt, offset=0, block: int = 4096):
    return zo_k.zo_sumsq(n, salt, offset, block=block, interpret=INTERPRET)


@partial(jax.jit, static_argnames=("block",))
def zo_perturb(x, salt, scale, offset=0, block: int = 4096):
    return zo_k.zo_perturb(x, salt, scale, offset, block=block, interpret=INTERPRET)


@partial(jax.jit, static_argnames=("n", "block", "acc_dtype"))
def zo_reconstruct(n: int, salts, coeffs, offset=0, block: int = 4096,
                   acc_dtype="float32"):
    return zo_k.zo_reconstruct(n, salts, coeffs, offset, block=block,
                               acc_dtype=jnp.dtype(acc_dtype),
                               interpret=INTERPRET)


# ---- flat (packed multi-leaf) kernels: one launch for the whole tree ---- #
# ``starts``/``sizes`` are the per-leaf layout tables of the packed buffer
# (first block, element count); salts are per leaf, (m, L) for m workers.

@partial(jax.jit, static_argnames=("block",))
def zo_perturb_flat(x, starts, sizes, salts, scale, block: int = 4096):
    return zo_k.zo_perturb_flat(x, starts, sizes, salts, scale, block=block,
                                interpret=INTERPRET)


@partial(jax.jit, static_argnames=("n_blocks", "block", "acc_dtype"))
def zo_reconstruct_flat(n_blocks: int, starts, sizes, salts, coeffs,
                        block: int = 4096, acc_dtype="float32"):
    return zo_k.zo_reconstruct_flat(n_blocks, starts, sizes, salts, coeffs,
                                    block=block,
                                    acc_dtype=jnp.dtype(acc_dtype),
                                    interpret=INTERPRET)


@partial(jax.jit, static_argnames=("block",))
def zo_perturb_sumsq(x, starts, sizes, salts, mu, block: int = 4096):
    return zo_k.zo_perturb_sumsq(x, starts, sizes, salts, mu, block=block,
                                 interpret=INTERPRET)


@partial(jax.jit, static_argnames=("momentum", "block", "acc_dtype"),
         donate_argnums=(0, 1))
def zo_reconstruct_update(p, mom, starts, sizes, bf16_mask, salts, coeffs, lr,
                          momentum: float = 0.0, block: int = 4096,
                          acc_dtype="float32"):
    """Fused reconstruct+SGD commit.  ``p``/``mom`` are donated (the kernel
    aliases them in place); when called under an outer jit the donation is
    simply inherited from the caller."""
    return zo_k.zo_reconstruct_update(
        p, mom, starts, sizes, bf16_mask, salts, coeffs, lr,
        momentum=momentum, block=block, acc_dtype=jnp.dtype(acc_dtype),
        interpret=INTERPRET)
