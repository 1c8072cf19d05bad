"""Compile each cell's FO and ZO step programs for a described TPU v5e.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/rehearse.py [--workload NAME]

No chip is needed: the TPU compiler compiles for the devices of a described
``v5e:2x2`` topology (one of them for a one-chip cell, all four for a
four-chip one).  The step programs are built as ``repro.launch.train.setup``
builds them, from the cell's configuration and traffic files, on shapes
alone.  Prints each program's ``memory_analysis()``, its compile seconds and
the collectives in its compiled text.  It compiles whole steps at published
widths (tens of seconds each on a CPU host), so it is no test.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import time
from collections import Counter

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)

COLL = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                  r"collective-permute)(-start|-done)?\b")


def rehearse(wl: dict, topo) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    import harness as H
    from repro.configs import get_config
    from repro.core.distributed import make_distributed_ho_sgd
    from repro.core.ho_sgd import HOSGDConfig
    from repro.dist.sharding import param_specs
    from repro.launch.train import size_override
    from repro.models import transformer as T
    from repro.opt.optimizers import const_schedule, sgd

    cfg, tf = H.config(wl["config"]), H.traffic(wl["traffic"])
    chips = wl["chips"]
    prog = cfg["program"]
    mcfg = size_override(get_config(prog["arch"]), prog["reduce"],
                         prog["layers"])
    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    shapes = jax.eval_shape(lambda k: T.init_model(k, mcfg), jax.random.key(0))
    d = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    zo_lr = tf["lr"] * 50.0 / d if tf["zo_lr"] is None else tf["zo_lr"]
    ho = HOSGDConfig(tau=tf["tau"], mu=tf["mu"], m=chips, lr=tf["lr"],
                     zo_lr=zo_lr, seed=0, engine=tf["engine"])
    fo, zo = make_distributed_ho_sgd(
        lambda p, b: T.loss_fn(mcfg, p, b), mesh, ho,
        sgd(const_schedule(tf["lr"])), model_cfg=mcfg, params_like=shapes,
        fo_buckets=tf["fo_buckets"])
    specs = param_specs(mcfg, shapes, mesh)
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, specs)
    B = H.global_batch(tf, chips)
    row = NamedSharding(mesh, P("data"))
    batch = {k: jax.ShapeDtypeStruct((B, tf["seq"]), jnp.int32, sharding=row)
             for k in ("tokens", "labels")}
    t = jax.ShapeDtypeStruct((), jnp.int32,
                             sharding=NamedSharding(mesh, P()))
    print(f"== {wl['name']}: {mcfg.name} {mcfg.n_layers} layers, d = {d:,}, "
          f"{chips} chip(s), batch {B} x {tf['seq']}", flush=True)
    kinds = ("fo", "zo") if tf["tau"] > 1 else ("fo",)
    for kind, fn in zip(("fo", "zo"), (fo, zo)):
        if kind not in kinds:
            continue
        t0 = time.perf_counter()
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn).lower(t, params, (), batch).compile()
        secs = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        per_dev = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                   + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        colls = Counter(m.group(0) for m in COLL.finditer(compiled.as_text()))
        print(f"{kind}_step: compile {secs:.1f} s; per device: arguments "
              f"{ma.argument_size_in_bytes:,} + outputs "
              f"{ma.output_size_in_bytes:,} + temporaries "
              f"{ma.temp_size_in_bytes:,} - aliased "
              f"{ma.alias_size_in_bytes:,} = {per_dev:,} bytes; "
              f"collectives {dict(colls)}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append",
                    help="a workload of BENCHMARK.json (default: all)")
    args = ap.parse_args(argv)
    from jax.experimental import topologies

    import harness as H
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for wl in H.benchmark()["workloads"]:
        if not args.workload or wl["name"] in args.workload:
            rehearse(wl, topo)


if __name__ == "__main__":
    main()
