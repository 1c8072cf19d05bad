"""repro.dist.sharding: the spec contract every layer builds on.

Spec *placement* logic is pure (only reads ``mesh.shape``), so most tests
drive it with AbstractMesh shapes a single CPU device could never host;
``test_worker_axes_real_mesh`` exercises the same rules on a real 4x2 mesh
when the process has devices for one (the CI tier-1 run forces 8).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.dist.sharding import (
    batch_specs, cache_specs, n_workers, param_specs, worker_axes,
)
from repro.launch.mesh import auto_mesh
from repro.models import transformer as T


def mesh_of(*axes):
    names, sizes = zip(*axes)
    return AbstractMesh(sizes, names)


POD_MESH = mesh_of(("data", 16), ("model", 16))
MULTIPOD_MESH = mesh_of(("pod", 2), ("data", 16), ("model", 16))


def test_worker_axes_and_count():
    assert worker_axes(POD_MESH) == ("data",)
    assert worker_axes(MULTIPOD_MESH) == ("pod", "data")
    assert n_workers(POD_MESH) == 16
    assert n_workers(MULTIPOD_MESH) == 32
    assert worker_axes(mesh_of(("model", 4))) == ()
    assert n_workers(mesh_of(("model", 4))) == 1


def abstract_params(cfg):
    return jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.key(0))


def specs_by_path(cfg, mesh):
    params = abstract_params(cfg)
    specs = param_specs(cfg, params, mesh)
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    return {
        "/".join(str(k.key) for k in path): s for path, s in flat
    }, params


def test_param_specs_tensor_parallel_rules():
    cfg = get_config("gemma2-2b")
    by, _ = specs_by_path(cfg, POD_MESH)
    # column-parallel: output dim over model; row-parallel: contraction dim
    assert by["layers/attn/wq"] == P(None, None, "model")
    assert by["layers/attn/wo"] == P(None, "model")
    assert by["layers/mlp/wg"] == P(None, None, "model")
    assert by["layers/mlp/wd"] == P(None, "model")
    # norms replicated
    assert by["layers/norm1/scale"] == P()
    assert by["final_norm/scale"] == P()
    # embed: vocab rows over model (gemma2 ties the head to embed.T)
    assert by["embed"] == P("model")
    by_q, _ = specs_by_path(get_config("qwen3-14b"), POD_MESH)
    assert by_q["head"] == P(None, "model")   # untied head: vocab cols


def test_param_specs_never_name_worker_axes_without_fsdp():
    for arch in ("gemma2-2b", "qwen3-moe-235b-a22b", "falcon-mamba-7b"):
        cfg = get_config(arch)
        if cfg.fsdp:
            continue
        by, _ = specs_by_path(cfg, MULTIPOD_MESH)
        for path, spec in by.items():
            named = {a for part in spec for a in
                     ((part,) if isinstance(part, str) else (part or ()))}
            assert "data" not in named and "pod" not in named, (path, spec)


def test_param_specs_divisibility_guard():
    # reduced configs have dims a 16-way model axis can't divide: replicate
    cfg = get_config("gemma2-2b").reduced()
    by, params = specs_by_path(cfg, mesh_of(("data", 4), ("model", 7)))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    shapes = {"/".join(str(k.key) for k in p): x.shape for p, x in flat}
    for path, spec in by.items():
        for dim, part in enumerate(spec):
            if part == "model":
                assert shapes[path][dim] % 7 == 0, (path, spec, shapes[path])


def test_param_specs_fsdp_adds_data_axis():
    cfg = get_config("gemma2-2b").with_(fsdp=True)
    by, _ = specs_by_path(cfg, POD_MESH)
    named = set()
    for spec in by.values():
        for part in spec:
            named.update((part,) if isinstance(part, str) else (part or ()))
    assert "data" in named and "model" in named


def test_param_specs_fsdp_moe_expert_dim_over_data():
    cfg = get_config("qwen3-moe-235b-a22b")
    assert cfg.is_moe
    by, _ = specs_by_path(cfg.with_(fsdp=True), POD_MESH)
    # stacked (L, E, D, F): expert dim over data, hidden over model — the
    # contract moe._expert_spec's dispatch constraints assume
    assert by["layers/moe/wg"] == P(None, "data", None, "model")
    assert by["layers/moe/wd"] == P(None, "data", "model")


def test_batch_specs_worker_leading_dim():
    batch = {"tokens": jax.ShapeDtypeStruct((64, 128), jnp.int32),
             "labels": jax.ShapeDtypeStruct((64, 128), jnp.int32)}
    specs = batch_specs(MULTIPOD_MESH, batch)
    assert specs["tokens"] == P(("pod", "data"))
    # non-divisible leading dim -> replicated, not an unshardable program
    odd = {"tokens": jax.ShapeDtypeStruct((7, 128), jnp.int32)}
    assert batch_specs(MULTIPOD_MESH, odd)["tokens"] == P()
    scalar = {"pos": jax.ShapeDtypeStruct((), jnp.int32)}
    assert batch_specs(MULTIPOD_MESH, scalar)["pos"] == P()


def test_cache_specs_decode_and_long_context():
    cfg = get_config("gemma2-2b")
    caches = jax.eval_shape(
        lambda: T.init_caches(cfg, 128, 4096, jnp.bfloat16))
    specs = cache_specs(cfg, POD_MESH, caches, seq_sharded=False)
    # (L, B, S, KV, hd): batch over workers; kv-heads over model when they
    # divide, else head_dim
    # (a one-axis tuple entry normalizes to the bare axis name)
    kspec = specs["k"]
    assert kspec[1] == "data"
    assert "model" in (kspec[3] if len(kspec) > 3 else None,
                       kspec[4] if len(kspec) > 4 else None)
    # long_500k: sequence carries the worker axes, batch=1 replicated
    long = jax.eval_shape(lambda: T.init_caches(cfg, 1, 1 << 19, jnp.bfloat16))
    specs = cache_specs(cfg, POD_MESH, long, seq_sharded=True)
    assert specs["k"][2] == "data"
    assert len(specs["k"]) < 2 or specs["k"][1] is None


def test_cache_specs_ssm():
    cfg = get_config("falcon-mamba-7b")
    caches = jax.eval_shape(lambda: T.init_caches(cfg, 128, 1024, jnp.bfloat16))
    specs = cache_specs(cfg, POD_MESH, caches, seq_sharded=False)
    assert specs["conv"][1] == "data" and specs["conv"][3] == "model"
    assert specs["ssm"][1] == "data" and specs["ssm"][2] == "model"


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs the CI 8-device tier-1 run")
def test_worker_axes_real_mesh():
    """The spec contract on a real multi-device mesh (CI forces 8 devices)."""
    mesh = auto_mesh((4, 2), ("data", "model"))
    assert worker_axes(mesh) == ("data",) and n_workers(mesh) == 4
    cfg = get_config("gemma2-2b").reduced()
    params = abstract_params(cfg)
    specs = param_specs(cfg, params, mesh)
    from jax.sharding import NamedSharding
    # every spec is realizable on the mesh (NamedSharding construction checks)
    jax.tree.map(lambda x, s: NamedSharding(mesh, s), params, specs,
                 is_leaf=lambda x: isinstance(x, P))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    assert batch_specs(mesh, batch)["tokens"] == P(("data",))
