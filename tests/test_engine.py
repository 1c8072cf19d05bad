"""DirectionEngine backend equivalence: tree == fused == pallas == flat.

The engine contract (README §DirectionEngine) promises the backends
evaluate the *identical* algebra: same hashed gaussians, same fp32
elementwise expressions, same per-worker acc_dtype rounding.  With tiles
covering whole leaves the outputs are bit-identical; with sub-leaf tiles
XLA's shape-dependent transcendental vectorization can move the last ulp,
so the tiled assertions allow a few-ulp tolerance.

The ``flat`` backend additionally ships a fused single-buffer step path
(perturb+sumsq in one launch, reconstruct+SGD commit in one launch) whose
kernel-side sumsq has a different reduction order than the shared jnp one —
that path is pinned loss-equivalent (rtol) to the ``fused`` engine rather
than bitwise, with donation safety and the non-SGD fallback pinned here too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import directions as D
from repro.core.engine import ENGINES, make_engine
from repro.core.ho_sgd import HOSGDConfig, make_ho_sgd, run_method

KEY = jax.random.key(0)
SEED, T = 3, jnp.int32(5)

# odd leaf sizes on purpose: none is a multiple of the pallas block below,
# and the scalar leaf exercises the degenerate (1,)-flat kernel path
SHAPE_SETS = [
    {"w": (37, 3), "b": (129,), "s": ()},
    {"a": (1000,), "c": (261,)},
]
WHOLE_LEAF_BLOCK = 4096   # >= every leaf above: bitwise regime
TILED_BLOCK = 64          # tail blocks everywhere: few-ulp regime


def _params(shapes, dtype):
    return {
        k: (jax.random.normal(jax.random.fold_in(KEY, i), s, jnp.float32)
            .astype(dtype))
        for i, (k, s) in enumerate(sorted(shapes.items()))
    }


def _engines(params, acc_dtype="float32", block=WHOLE_LEAF_BLOCK):
    return {
        name: make_engine(name, params, SEED, acc_dtype=acc_dtype, block=block)
        for name in ENGINES
    }


def _leaves32(tree):
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("shapes", SHAPE_SETS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_inv_norm_identical_and_matches_directions(shapes, dtype):
    params = _params(shapes, dtype)
    engines = _engines(params)
    w = jnp.uint32(2)
    invs = {n: float(jax.jit(e.inv_norm)(T, w)) for n, e in engines.items()}
    assert len(set(invs.values())) == 1, invs
    v = D.raw_direction(params, SEED, T, w)
    ssq = sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(v))
    assert invs["tree"] == pytest.approx(float(jax.lax.rsqrt(ssq + 1e-30)),
                                         rel=1e-6)


@pytest.mark.parametrize("shapes", SHAPE_SETS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_perturb_bit_identical_across_backends(shapes, dtype):
    params = _params(shapes, dtype)
    engines = _engines(params)
    w = jnp.uint32(1)
    scale = jnp.float32(1e-2) * engines["tree"].inv_norm(T, w)
    outs = {
        n: jax.jit(lambda p, e=e: e.perturb(p, T, w, scale))(params)
        for n, e in engines.items()
    }
    for n in ("fused", "pallas", "flat"):
        for a, b in zip(_leaves32(outs["tree"]), _leaves32(outs[n])):
            np.testing.assert_array_equal(a, b, err_msg=n)
    # and it actually perturbs: every (non-scalar) leaf moved
    for p0, p1 in zip(_leaves32(params), _leaves32(outs["tree"])):
        if p0.size > 1:
            assert np.any(p0 != p1)


@pytest.mark.parametrize("shapes", SHAPE_SETS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_zo_coeff_bit_identical_across_backends(shapes, dtype):
    params = _params(shapes, dtype)
    target = jax.tree.map(lambda x: jnp.zeros_like(x), params)

    def loss_fn(p, b):
        return sum(
            0.5 * jnp.sum(jnp.square(x.astype(jnp.float32) - t.astype(jnp.float32)))
            for x, t in zip(jax.tree.leaves(p), jax.tree.leaves(b)))

    outs = {}
    for n, e in _engines(params).items():
        c, f0 = jax.jit(
            lambda p, b, e=e: e.zo_coeff(loss_fn, p, b, T, jnp.uint32(0), 1e-2)
        )(params, target)
        outs[n] = (float(c), float(f0))
    assert outs["tree"] == outs["fused"] == outs["pallas"] == outs["flat"], outs


@pytest.mark.parametrize("shapes", SHAPE_SETS)
@pytest.mark.parametrize("acc_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_reconstruct_equivalent_across_backends(shapes, dtype, acc_dtype):
    """Same algebra, same per-worker acc_dtype rounding, in every backend.

    With a sub-fp32 accumulator the rounding absorbs XLA's FMA-contraction
    freedom and the three backends are bit-identical.  With an fp32
    accumulator the chained multiply-adds may or may not be contracted to
    fma depending on the surrounding program (unrolled vs fori_loop vs
    kernel), so equality is to a couple of ulps — the only non-bitwise
    seam in the contract, and inherent to XLA, not to the backends.
    """
    params = _params(shapes, dtype)
    engines = _engines(params, acc_dtype=acc_dtype)
    cs = jnp.asarray([0.5, -1.0, 2.0, 0.1], jnp.float32)
    recs = {n: jax.jit(lambda e=e: e.reconstruct(cs, T))()
            for n, e in engines.items()}
    for n in ("fused", "pallas", "flat"):
        for a, b in zip(_leaves32(recs["tree"]), _leaves32(recs[n])):
            if acc_dtype == "bfloat16":
                np.testing.assert_array_equal(a, b, err_msg=f"{n} acc={acc_dtype}")
            else:
                np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8,
                                           err_msg=f"{n} acc={acc_dtype}")


@pytest.mark.parametrize("backend", ["pallas", "flat"])
@pytest.mark.parametrize("shapes", SHAPE_SETS)
def test_tiled_kernel_backends_match_within_ulps(shapes, backend):
    """Sub-leaf tiles (tail-masked blocks) may differ from the whole-leaf
    evaluation only by XLA's shape-dependent transcendental rounding."""
    params = _params(shapes, jnp.float32)
    whole = make_engine(backend, params, SEED, block=WHOLE_LEAF_BLOCK)
    tiled = make_engine(backend, params, SEED, block=TILED_BLOCK)
    w = jnp.uint32(1)
    scale = jnp.float32(1e-2) * whole.inv_norm(T, w)
    a = jax.jit(lambda p: whole.perturb(p, T, w, scale))(params)
    b = jax.jit(lambda p: tiled.perturb(p, T, w, scale))(params)
    for x, y in zip(_leaves32(a), _leaves32(b)):
        np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-6)
    cs = jnp.asarray([0.5, -1.0, 2.0, 0.1], jnp.float32)
    a = jax.jit(lambda: whole.reconstruct(cs, T))()
    b = jax.jit(lambda: tiled.reconstruct(cs, T))()
    for x, y in zip(_leaves32(a), _leaves32(b)):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["tree", "fused", "flat"])
def test_vmapped_vs_unrolled_reconstruct(backend):
    params = _params(SHAPE_SETS[0], jnp.float32)
    eng = make_engine(backend, params, SEED)
    cs = jnp.asarray([0.5, -1.0, 2.0, 0.1], jnp.float32)
    seq = jax.jit(lambda: eng.reconstruct(cs, T))()
    vm = jax.jit(lambda: eng.reconstruct(cs, T, vmap_workers=True))()
    for a, b in zip(_leaves32(seq), _leaves32(vm)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_vmapped_reconstruct_hlo_o1_in_m():
    """The vmapped-worker variant's program size must not grow with m
    (the ROADMAP large-m CPU-rehearsal item); the unrolled tree path does."""
    params = _params(SHAPE_SETS[0], jnp.float32)
    eng = make_engine("tree", params, SEED)

    def size(m, vmap_workers):
        cs = jnp.zeros((m,), jnp.float32)
        return len(
            jax.jit(lambda c: eng.reconstruct(c, T, vmap_workers=vmap_workers))
            .lower(cs).as_text())

    assert size(16, True) < 1.15 * size(4, True)
    assert size(16, False) > 2.0 * size(4, False)  # the unrolled contrast


def test_engine_metadata_offsets():
    params = _params(SHAPE_SETS[0], jnp.float32)
    eng = make_engine("tree", params, SEED)
    assert eng.dim == sum(eng.sizes) == D.tree_dim(params)
    np.testing.assert_array_equal(eng.offsets,
                                  np.cumsum([0] + eng.sizes[:-1]))


@pytest.mark.parametrize("engine", ["tree", "fused", "pallas"])
def test_hot_path_zo_steps_identical_across_engines(engine):
    """make_ho_sgd's jitted ZO step produces the same trajectory on every
    backend (the backends see identical losses, coefficients, updates)."""

    def quad_loss(p, b):
        return 0.5 * jnp.mean(jnp.sum((p["x"] - b["t"]) ** 2, -1))

    m, B, d = 4, 4, 63                     # odd d: pallas tail block
    p0 = {"x": jnp.zeros((d,))}

    def batches():
        rng = np.random.default_rng(0)
        while True:
            yield {"t": (1.0 + 0.1 * rng.normal(size=(m * B, d))).astype(np.float32)}

    hists = {}
    for name in ("tree", engine):
        # bf16 accumulator: per-worker rounding absorbs FMA-contraction
        # freedom, so whole trajectories are bit-identical across backends
        cfg = HOSGDConfig(tau=1 << 30, mu=1e-3, m=m, lr=0.1, zo_lr=0.1 / d,
                          engine=name, acc_dtype="bfloat16")
        hists[name] = run_method(make_ho_sgd(quad_loss, cfg), p0, batches(), 5)
    np.testing.assert_array_equal(
        np.asarray(hists["tree"]["params"]["x"]),
        np.asarray(hists[engine]["params"]["x"]))
    assert hists["tree"]["loss"] == hists[engine]["loss"]


def test_zo_step_engines_agree_on_1x1_mesh():
    """distributed make_zo_step (the shard_map lowering) agrees across
    backends."""
    from repro.core.distributed import make_zo_step
    from repro.launch.mesh import make_test_mesh
    from repro.opt.optimizers import const_schedule, sgd

    def loss_fn(p, b):
        return 0.5 * jnp.mean(jnp.sum((p["x"] - b["t"]) ** 2, -1))

    d = 130
    params = {"x": jnp.linspace(-1.0, 1.0, d)}
    batch = {"t": jnp.ones((4, d), jnp.float32)}
    mesh = make_test_mesh(data=1, model=1)
    outs = {}
    with jax.set_mesh(mesh):
        for name in ("tree", "fused", "pallas"):
            ho = HOSGDConfig(tau=1 << 30, mu=1e-3, m=2, lr=0.05,
                             zo_lr=0.05 / d, engine=name,
                             acc_dtype="bfloat16")
            opt = sgd(const_schedule(ho.lr))
            zo = jax.jit(make_zo_step(loss_fn, mesh, ho, opt, m=2))
            p1, _, loss = zo(jnp.int32(3), params, opt.init(params), batch)
            outs[name] = (np.asarray(p1["x"]), float(loss))
    np.testing.assert_array_equal(outs["tree"][0], outs["fused"][0])
    np.testing.assert_array_equal(outs["tree"][0], outs["pallas"][0])
    assert outs["tree"][1] == outs["fused"][1] == outs["pallas"][1]


@pytest.mark.parametrize("engine", ["fused", "pallas"])
def test_zo_step_memory_o_params_independent_of_m(engine):
    """No materialized full-leaf direction buffer: the compiled ZO step's
    temp memory is O(params) — flat in m (ISSUE 2 acceptance criterion)."""
    from repro.core.distributed import make_zo_step
    from repro.launch.hlo import memory_summary
    from repro.launch.mesh import make_test_mesh
    from repro.opt.optimizers import const_schedule, sgd

    def loss_fn(p, b):
        return 0.5 * jnp.mean(jnp.sum((p["x"] - b["t"]) ** 2, -1))

    d = 1 << 16
    params = {"x": jnp.zeros((d,))}
    mesh = make_test_mesh(data=1, model=1)
    temps = {}
    with jax.set_mesh(mesh):
        for m in (2, 8):
            batch = {"t": jnp.ones((m, d), jnp.float32)}
            ho = HOSGDConfig(tau=1 << 30, mu=1e-3, m=m, lr=0.05, zo_lr=1e-6,
                             engine=engine)
            opt = sgd(const_schedule(ho.lr))
            zo = jax.jit(make_zo_step(loss_fn, mesh, ho, opt, m=m))
            comp = zo.lower(jnp.int32(1), params, opt.init(params),
                            batch).compile()
            temps[m] = memory_summary(comp).get("temp_size_in_bytes")
    if temps[2] is None:
        pytest.skip("memory_analysis unavailable on this backend")
    # flat in m, and params-order overall (a few live d-vectors, not m of
    # them; 6*4*d leaves headroom for backend/XLA scheduling variation)
    assert temps[8] <= 1.2 * temps[2], temps
    assert temps[8] <= 6 * 4 * d, temps


def test_unknown_engine_raises():
    with pytest.raises(ValueError, match="unknown direction engine"):
        make_engine("mosaic", {"x": jnp.zeros((3,))}, 0)


# --------------------------------------------------------------------------- #
# flat backend: packed buffer + fused single-buffer step path                  #
# --------------------------------------------------------------------------- #

def _quad_loss(p, b):
    return 0.5 * jnp.mean(jnp.sum((p["x"] - b["t"]) ** 2, -1))


def _quad_batches(m, B, d, seed=0):
    rng = np.random.default_rng(seed)
    while True:
        yield {"t": (1.0 + 0.1 * rng.normal(size=(m * B, d))).astype(np.float32)}


def test_flat_pack_unpack_roundtrip():
    """pack/unpack is lossless through the block-padded fp32 buffer —
    including scalar leaves and bf16 leaves (bf16 -> f32 -> bf16 is exact)."""
    params = {
        "w": jax.random.normal(KEY, (37, 3), jnp.float32),
        "b": jnp.linspace(-1.0, 1.0, 129).astype(jnp.bfloat16),
        "s": jnp.asarray(0.25, jnp.float32),
    }
    eng = make_engine("flat", params, SEED)
    buf = eng.pack(params)
    assert buf.dtype == jnp.float32 and buf.shape == (eng.padded_dim,)
    assert eng.padded_dim % eng.block == 0
    out = eng.unpack(buf)
    assert jax.tree.structure(out) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # cast=False keeps fp32 leaves (update / momentum trees)
    for x in jax.tree.leaves(eng.unpack(buf, cast=False)):
        assert x.dtype == jnp.float32


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_flat_fused_step_loss_equivalent_to_fused(momentum):
    """ISSUE 10 acceptance: --engine flat is pinned loss-equivalent (rtol)
    to --engine fused on a toy problem, with and without momentum.  Not
    bitwise: the fused path consumes the kernel's blockwise sumsq, whose
    reduction order differs from the shared jnp inv-norm."""
    m, B, d = 4, 4, 63
    p0 = {"x": jnp.zeros((d,))}
    hists = {}
    for name in ("fused", "flat"):
        cfg = HOSGDConfig(tau=1 << 30, mu=1e-3, m=m, lr=0.1, zo_lr=0.1 / d,
                          engine=name, momentum=momentum)
        hists[name] = run_method(make_ho_sgd(_quad_loss, cfg), p0,
                                 _quad_batches(m, B, d), 12)
    np.testing.assert_allclose(hists["flat"]["loss"], hists["fused"]["loss"],
                               rtol=1e-4)
    # the params pin is looser than the loss pin: the ulp-level sumsq
    # difference enters each step scaled by (d/mu)*(f1-f0) and compounds
    np.testing.assert_allclose(np.asarray(hists["flat"]["params"]["x"]),
                               np.asarray(hists["fused"]["params"]["x"]),
                               rtol=5e-3, atol=1e-5)


def test_flat_fused_step_donation_safe():
    """The fused commit kernel donates its packed buffers; the jitted step
    must still leave the caller's params/opt_state usable (the donation is
    of the *packed copy*, never of caller-visible arrays)."""
    m, B, d = 2, 2, 37
    p0 = {"x": jnp.linspace(-1.0, 1.0, d)}
    cfg = HOSGDConfig(tau=1 << 30, mu=1e-3, m=m, lr=0.1, zo_lr=0.1 / d,
                      engine="flat", momentum=0.9)
    meth = make_ho_sgd(_quad_loss, cfg)
    state = meth.init(p0)
    batch = next(_quad_batches(m, B, d))
    p1, s1, met1 = meth.step(1, p0, state, batch)
    # same arrays again: donation must not have consumed them
    assert not p0["x"].is_deleted()
    p2, s2, met2 = meth.step(1, p0, state, batch)
    np.testing.assert_array_equal(np.asarray(p1["x"]), np.asarray(p2["x"]))
    assert float(met1["loss"]) == float(met2["loss"])


def test_flat_fused_step_bf16_params():
    """bf16 param leaves round-trip the packed fp32 buffer and are rounded
    back to bf16 inside the commit kernel (bf16_mask path)."""
    m, B, d = 2, 2, 37

    def loss_fn(p, b):
        x = p["x"].astype(jnp.float32)
        return 0.5 * jnp.mean(jnp.sum((x - b["t"]) ** 2, -1)) \
            + 0.5 * jnp.square(p["s"])

    p0 = {"x": jnp.zeros((d,), jnp.bfloat16), "s": jnp.asarray(1.0)}
    hists = {}
    for name in ("fused", "flat"):
        cfg = HOSGDConfig(tau=1 << 30, mu=1e-2, m=m, lr=0.1, zo_lr=0.1 / d,
                          engine=name, momentum=0.9)
        hists[name] = run_method(make_ho_sgd(loss_fn, cfg), p0,
                                 _quad_batches(m, B, d), 5)
    assert hists["flat"]["params"]["x"].dtype == jnp.bfloat16
    assert hists["flat"]["params"]["s"].dtype == jnp.float32
    # bf16 rounding of near-identical fp32 commits: bf16-eps agreement
    np.testing.assert_allclose(
        np.asarray(hists["flat"]["params"]["x"], np.float32),
        np.asarray(hists["fused"]["params"]["x"], np.float32),
        rtol=2e-2, atol=1e-3)
    np.testing.assert_allclose(hists["flat"]["loss"], hists["fused"]["loss"],
                               rtol=1e-3)


def test_flat_nonsgd_optimizer_falls_back_to_generic_path():
    """adam on flat takes the reconstruct-then-opt.apply path, which is the
    shared engine contract — bit-identical to tree under a bf16 accumulator."""
    from repro.opt.optimizers import adam, const_schedule

    m, B, d = 2, 2, 63
    p0 = {"x": jnp.zeros((d,))}
    hists = {}
    for name in ("tree", "flat"):
        cfg = HOSGDConfig(tau=1 << 30, mu=1e-3, m=m, lr=0.05, zo_lr=0.05 / d,
                          engine=name, acc_dtype="bfloat16")
        meth = make_ho_sgd(_quad_loss, cfg, opt=adam(const_schedule(0.05)))
        hists[name] = run_method(meth, p0, _quad_batches(m, B, d), 5)
    np.testing.assert_array_equal(np.asarray(hists["tree"]["params"]["x"]),
                                  np.asarray(hists["flat"]["params"]["x"]))
    assert hists["tree"]["loss"] == hists["flat"]["loss"]


def test_zo_step_flat_matches_fused_on_1x1_mesh():
    """distributed make_zo_step: the flat engine's kernels (one launch per
    primitive inside the shard_map lowering) are loss/params-equivalent
    (rtol) to the fused engine."""
    from repro.core.distributed import make_zo_step
    from repro.launch.mesh import make_test_mesh
    from repro.opt.optimizers import const_schedule, sgd

    d = 130
    params = {"x": jnp.linspace(-1.0, 1.0, d)}
    batch = {"t": jnp.ones((4, d), jnp.float32)}
    mesh = make_test_mesh(data=1, model=1)
    outs = {}
    with jax.set_mesh(mesh):
        for name in ("fused", "flat"):
            ho = HOSGDConfig(tau=1 << 30, mu=1e-3, m=2, lr=0.05,
                             zo_lr=0.05 / d, engine=name, momentum=0.9)
            opt = sgd(const_schedule(ho.lr), ho.momentum)
            zo = jax.jit(make_zo_step(_quad_loss, mesh, ho, opt, m=2))
            p1, _, loss = zo(jnp.int32(3), params, opt.init(params), batch)
            outs[name] = (np.asarray(p1["x"]), float(loss))
    np.testing.assert_allclose(outs["flat"][0], outs["fused"][0],
                               rtol=1e-5, atol=1e-7)
    assert outs["flat"][1] == pytest.approx(outs["fused"][1], rel=1e-6)
