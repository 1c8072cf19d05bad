"""Beyond-paper ZO knobs: bf16 reconstruction accumulator stays close to the
fp32 path (runs on a degenerate 1x1 mesh, no extra devices needed)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.distributed import make_zo_step
from repro.core.ho_sgd import HOSGDConfig
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T
from repro.opt.optimizers import const_schedule, sgd


def test_bf16_accumulator_close_to_fp32():
    mesh = make_test_mesh(data=1, model=1)
    cfg = get_config("gemma2-2b").reduced()
    params = T.init_model(jax.random.key(0), cfg)
    loss_fn = lambda p, b: T.loss_fn(cfg, p, b)
    d = sum(x.size for x in jax.tree.leaves(params))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    labels = np.concatenate([toks[:, 1:], -np.ones((4, 1), np.int32)], 1)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}

    outs = {}
    with jax.set_mesh(mesh):
        for dt in ("float32", "bfloat16"):
            ho = HOSGDConfig(tau=1 << 30, mu=1e-3, m=1, lr=0.05,
                             zo_lr=0.05 / d, acc_dtype=dt)
            opt = sgd(const_schedule(ho.lr))
            zo = jax.jit(make_zo_step(loss_fn, mesh, ho, opt))
            p1, _, loss = zo(jnp.int32(3), params, opt.init(params), batch)
            outs[dt] = (jax.device_get(p1), float(loss))

    assert outs["float32"][1] == outs["bfloat16"][1]  # same loss eval
    # updates agree to bf16 resolution relative to the update magnitude
    for a, b, p0 in zip(jax.tree.leaves(outs["float32"][0]),
                        jax.tree.leaves(outs["bfloat16"][0]),
                        jax.tree.leaves(params)):
        upd = np.asarray(a, np.float32) - np.asarray(p0, np.float32)
        diff = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
        scale = max(np.abs(upd).max(), 1e-12)
        assert diff.max() <= 0.02 * scale + 1e-7, (diff.max(), scale)
