"""Compile rehearsals for a TPU v5e that is described, not attached.

Each test compiles one Pallas kernel of the main path with interpret mode
off, at the widths the chip runs, and asserts that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs, so nothing
here is a result or a time: these tests catch what the chip's compiler
refuses (unaligned blocks, unsupported casts, vector-memory scalars) without
a chip.

The topology is described inside a module fixture, never at import: every
test worker then collects the same tests, and only the worker given this
file loads the TPU compiler.  The persistent compile cache is off around
these compiles (an entry written for a described chip cannot be read back
without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.engine import FlatEngine
from repro.kernels import zo_direction as Z
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.selective_scan import selective_scan_pallas
from repro.launch.train import size_override
from repro.models import transformer as T

BLOCK = 4096        # the ZO kernels' default block
M = 4               # workers reconstructed in one pass
LEAF = 3072 * 8192  # phi3-mini's largest per-layer weight (d_model x d_ff)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)
    cc.reset_cache()


def _compiles_to_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _layout(size: str):
    """(n, n_blocks, n_leaves): one real leaf, or the packed buffer of the
    chip smoke configuration (phi3-mini-3.8b at 8 layers, every width)."""
    if size == "leaf":
        return LEAF, LEAF // BLOCK, 1
    cfg = size_override(get_config("phi3-mini-3.8b"), "full", layers=8)
    params = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.key(0))
    eng = FlatEngine(params, 0, block=BLOCK)
    return eng.padded_dim, eng.n_blocks, len(eng.sizes)


def _zo_case(kernel: str, size: str, sd):
    n, nb, L = _layout(size)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sd)
    buf, tab = s((nb * BLOCK,), jnp.float32), s((L,), jnp.int32)
    f32, u32 = s((), jnp.float32), s((), jnp.uint32)
    salts1, saltsm = s((L,), jnp.uint32), s((M, L), jnp.uint32)
    coeffs = s((M,), jnp.float32)
    return {
        "zo_sumsq": (lambda salt: Z.zo_sumsq(n, salt, interpret=False), [u32]),
        "zo_perturb": (
            lambda x, salt, scale: Z.zo_perturb(x, salt, scale,
                                                interpret=False),
            [s((n,), jnp.bfloat16), u32, f32]),
        "zo_reconstruct": (
            lambda salts, c: Z.zo_reconstruct(n, salts, c, interpret=False),
            [s((M,), jnp.uint32), coeffs]),
        "zo_perturb_flat": (
            lambda x, st, sz, salts, scale: Z.zo_perturb_flat(
                x, st, sz, salts, scale, interpret=False),
            [buf, tab, tab, salts1, f32]),
        "zo_reconstruct_flat": (
            lambda st, sz, salts, c: Z.zo_reconstruct_flat(
                nb, st, sz, salts, c, interpret=False),
            [tab, tab, saltsm, coeffs]),
        "zo_perturb_sumsq": (
            lambda x, st, sz, salts, mu: Z.zo_perturb_sumsq(
                x, st, sz, salts, mu, interpret=False),
            [buf, tab, tab, salts1, f32]),
        "zo_reconstruct_update": (
            lambda p, mom, st, sz, bf, salts, c, lr: Z.zo_reconstruct_update(
                p, mom, st, sz, bf, salts, c, lr, momentum=0.9,
                interpret=False),
            [buf, buf, tab, tab, tab, saltsm, coeffs, f32]),
    }[kernel]


@pytest.mark.parametrize("size", ["leaf", "packed"])
@pytest.mark.parametrize("kernel", [
    "zo_sumsq", "zo_perturb", "zo_reconstruct", "zo_perturb_flat",
    "zo_reconstruct_flat", "zo_perturb_sumsq", "zo_reconstruct_update",
])
def test_zo_kernel_compiles_for_v5e(kernel, size, one_chip):
    fn, args = _zo_case(kernel, size, one_chip)
    _compiles_to_mosaic(fn, *args)


def test_rmsnorm_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((4096, 3072), jnp.bfloat16, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((3072,), jnp.float32, sharding=one_chip)
    _compiles_to_mosaic(
        lambda x, s: rmsnorm_pallas(x, s, interpret=False), x, scale)


def _phi3_attention(sd):
    """phi3-mini's attention: 32 heads of 96 over a 4096-token sequence,
    one sequence (an FO microbatch), the model path's blocks."""
    q = jax.ShapeDtypeStruct((1, 32, 4096, 96), jnp.bfloat16, sharding=sd)
    fn = lambda q, k, v: flash_attention_pallas(
        q, k, v, block_q=1024, block_k=1024, interpret=False)
    return fn, q


def test_flash_attention_compiles_for_v5e(one_chip):
    fn, q = _phi3_attention(one_chip)
    _compiles_to_mosaic(fn, q, q, q)


def test_flash_attention_backward_compiles_for_v5e(one_chip):
    """The forward that saves the log-sum-exp, and the dq and dk/dv
    kernels: three Mosaic calls."""
    fn, q = _phi3_attention(one_chip)
    grads = jax.grad(lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2))
    text = jax.jit(grads).lower(q, q, q).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


def test_model_attention_takes_the_kernel_on_v5e(topo, one_chip):
    """Under a mesh of described v5e devices the model's full-sequence
    attention selects the kernels, in a shard_map (its FO gradient holds
    the three Mosaic calls and no collective)."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.models import attention as A
    cfg = get_config("phi3-mini-3.8b")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    q = jax.ShapeDtypeStruct((1, 4096, 32, 96), jnp.bfloat16,
                             sharding=one_chip)
    pos = jnp.arange(4096, dtype=jnp.int32)
    loss = lambda q, k, v: A._attend_seq(
        cfg, q, k, v, pos, jnp.int32(T.FULL_WINDOW)).astype(jnp.float32).sum()
    with jax.set_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "all-gather" not in text and "all-reduce" not in text


def test_selective_scan_compiles_for_v5e(one_chip):
    # falcon-mamba-7b: d_inner 8192, state 16; fp32 inputs as models/ssm.py
    # passes them, the model path's block sizes
    cfg = get_config("falcon-mamba-7b")
    di, n, S = cfg.d_inner, cfg.ssm_state, 4096
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    _compiles_to_mosaic(
        lambda u, dt, b, c, a, d: selective_scan_pallas(
            u, dt, b, c, a, d, block_d=256, block_s=128, interpret=False),
        s((1, S, di)), s((1, S, di)), s((1, S, n)), s((1, S, n)),
        s((di, n)), s((di,)))
