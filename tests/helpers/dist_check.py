"""Subprocess helper: distributed HO-SGD on an 8-device mesh must equal the
single-host reference (run by test_distributed.py with its own XLA_FLAGS)."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.distributed import make_distributed_ho_sgd
from repro.core.ho_sgd import HOSGDConfig, make_ho_sgd
from repro.dist.sharding import batch_specs, named, param_specs
from repro.launch.mesh import auto_mesh
from repro.models import transformer as T
from repro.opt.optimizers import const_schedule, sgd


def main():
    assert jax.device_count() == 8, jax.device_count()
    mesh = auto_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen3-14b").reduced()
    params = T.init_model(jax.random.key(0), cfg)
    loss_fn = lambda p, b: T.loss_fn(cfg, p, b)
    d = sum(x.size for x in jax.tree.leaves(params))
    ho = HOSGDConfig(tau=4, mu=1e-3, m=4, lr=0.05, zo_lr=0.05 / d)
    opt = sgd(const_schedule(ho.lr))
    fo, zo = make_distributed_ho_sgd(loss_fn, mesh, ho, opt, model_cfg=cfg,
                                     params_like=params)

    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    labels = np.concatenate([toks[:, 1:], -np.ones((8, 1), np.int32)], 1)
    batch = {"tokens": toks, "labels": labels}

    with jax.set_mesh(mesh):
        params_d = jax.device_put(params, named(mesh, param_specs(cfg, params, mesh)))
        batch_d = jax.device_put(batch, named(mesh, batch_specs(mesh, batch)))
        opt_state = opt.init(params_d)
        fo_j, zo_j = jax.jit(fo), jax.jit(zo)
        p1, s1, l_fo = fo_j(jnp.int32(0), params_d, opt_state, batch_d)
        p2, s2, l_zo = zo_j(jnp.int32(1), p1, s1, batch_d)
        assert np.isfinite(float(l_fo)) and np.isfinite(float(l_zo))
        # descent over a hybrid schedule
        p, s = p2, s2
        for t in range(2, 14):
            step = fo_j if t % ho.tau == 0 else zo_j
            p, s, l = step(jnp.int32(t), p, s, batch_d)
        assert float(l) < float(l_fo), (float(l), float(l_fo))

        # one distributed ZO step == single-host reference (same seed/t)
        pz, _, _ = zo_j(jnp.int32(5), params_d, opt.init(params_d), batch_d)
    ref = make_ho_sgd(loss_fn, HOSGDConfig(tau=1 << 30, mu=ho.mu, m=4,
                                           lr=ho.lr, zo_lr=ho.zo_lr,
                                           seed=ho.seed))
    pr, _, _ = ref.step(5, params, ref.init(params), batch)
    diff = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(jax.device_get(pz)), jax.tree.leaves(pr))
    )
    assert diff < 2e-5, diff
    print("DIST_CHECK_OK", diff)


if __name__ == "__main__":
    main()
