"""Subprocess helper: measured communication on a REAL 4-worker (4x2) mesh
must reproduce Table 1 — ZO moves exactly 4*m bytes (independent of d), the
dense FO all-reduce 4*d, and a QSGD-compressed FO step strictly less than
4*d.  Run by test_distributed.py with its own XLA_FLAGS."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from repro.core.distributed import make_distributed_ho_sgd
from repro.core.ho_sgd import HOSGDConfig
from repro.dist import CommLedger, get_compressor
from repro.dist.sharding import batch_specs, n_workers, named
from repro.launch.mesh import auto_mesh
from repro.opt.optimizers import const_schedule, sgd


def quad_loss(params, batch):
    return 0.5 * jnp.mean(jnp.sum((params["x"] - batch["t"]) ** 2, -1))


def run(mesh, d, compressor=None):
    m = n_workers(mesh)
    ho = HOSGDConfig(tau=4, mu=1e-3, m=m, lr=0.05, zo_lr=0.05 / d)
    opt = sgd(const_schedule(ho.lr))
    fo, zo = make_distributed_ho_sgd(quad_loss, mesh, ho, opt,
                                     compressor=compressor)
    ledger = CommLedger()
    fo_j, zo_j = ledger.wrap("fo", jax.jit(fo)), ledger.wrap("zo", jax.jit(zo))
    with jax.set_mesh(mesh):
        params = {"x": jnp.zeros((d,), jnp.float32)}
        state = opt.init(params)
        batch = {"t": jnp.ones((8 * m, d), jnp.float32)}
        batch = jax.device_put(batch, named(mesh, batch_specs(mesh, batch)))
        for t in range(8):
            step = fo_j if t % ho.tau == 0 else zo_j
            params, state, loss = step(jnp.int32(t), params, state, batch)
        assert np.isfinite(float(loss))
    return ledger, m


def main():
    assert jax.device_count() == 8, jax.device_count()
    mesh = auto_mesh((4, 2), ("data", "model"))
    d = 4096

    ledger, m = run(mesh, d)
    assert m == 4, m
    # Table 1, measured: ZO is 4*m bytes — independent of d — FO is 4*d
    assert ledger.bytes_per_step("zo") == 4 * m, ledger.summary()
    assert ledger.bytes_per_step("fo") == 4 * d, ledger.summary()
    amortized = ledger.total_bytes() / 8
    analytic = 4 * (d + 3 * m) / 4
    assert abs(amortized - analytic) < 1e-9, (amortized, analytic)

    qledger, _ = run(mesh, d, compressor=get_compressor("qsgd"))
    assert qledger.bytes_per_step("fo") < 4 * d, qledger.summary()
    assert qledger.bytes_per_step("zo") == 4 * m, qledger.summary()

    print("LEDGER_CHECK_OK",
          ledger.bytes_per_step("zo"), ledger.bytes_per_step("fo"),
          qledger.bytes_per_step("fo"))


if __name__ == "__main__":
    main()
