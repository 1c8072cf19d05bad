"""The use_pallas model path (interpret mode) equals the jnp path.

Attention picks its kernel from the target, not from ``use_pallas``: on the
CPU both sides run dense attention, and ``tests/test_attention.py`` checks
the blocked path against it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T


@pytest.mark.parametrize("arch", ["qwen3-14b", "falcon-mamba-7b", "gemma2-2b"])
def test_pallas_forward_matches_jnp(arch):
    # kernel-aligned smoke shapes: S multiple of 64, d_inner multiple of 64
    cfg = get_config(arch).reduced().with_(remat=False, ssm_expand=2)
    if cfg.layer_pattern == "local_global":
        # the uniform-window variant, the one the attention kernel takes
        cfg = cfg.with_(long_context=True)
    if cfg.has_ssm:
        cfg = cfg.with_(d_model=128)  # d_inner = 256, 64-aligned
    params = T.init_model(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    B, S = 2, 128
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    base, _ = T.forward_logits(cfg, params, {"tokens": toks})
    fast, _ = T.forward_logits(cfg.with_(use_pallas=True), params,
                               {"tokens": toks})
    np.testing.assert_allclose(np.asarray(fast), np.asarray(base),
                               rtol=3e-3, atol=3e-3)
