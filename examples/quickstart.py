"""Quickstart: train a small LM with HO-SGD on whatever devices exist.

    PYTHONPATH=src python examples/quickstart.py

Shows the whole public API surface in ~40 lines: config -> model -> data ->
distributed HO-SGD steps -> checkpoint.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.distributed import make_distributed_ho_sgd
from repro.core.ho_sgd import HOSGDConfig
from repro.data import shard_batches, token_batches
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T
from repro.opt.optimizers import sgd, const_schedule


def main():
    cfg = get_config("qwen3-14b").reduced()          # same family, smoke size
    mesh = make_test_mesh(data=1, model=1)           # single CPU device here
    params = T.init_model(jax.random.key(0), cfg)
    d = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name}  params={d:,}")

    ho = HOSGDConfig(tau=4, mu=1e-3, lr=5e-2, zo_lr=5e-2 * 20 / d)
    opt = sgd(const_schedule(ho.lr))
    loss_fn = lambda p, b: T.loss_fn(cfg, p, b)
    fo, zo = make_distributed_ho_sgd(loss_fn, mesh, ho, opt, model_cfg=cfg,
                                     params_like=params)

    with jax.set_mesh(mesh):
        fo_j, zo_j = jax.jit(fo), jax.jit(zo)
        opt_state = opt.init(params)
        data = shard_batches(token_batches(cfg.vocab_size, 8, 64), mesh)
        for t, batch in zip(range(24), data):
            step = fo_j if t % ho.tau == 0 else zo_j
            params, opt_state, loss = step(jnp.int32(t), params, opt_state, batch)
            kind = "FO" if t % ho.tau == 0 else "ZO"
            print(f"step {t:3d} [{kind}] loss={float(loss):.4f}")
    print("done")


if __name__ == "__main__":
    main()
