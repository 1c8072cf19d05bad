"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("rows,d", [(8, 64), (64, 256), (32, 1024), (128, 80)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rows, d, dtype):
    x = jax.random.normal(KEY, (rows, d), jnp.float32).astype(dtype)
    s = jax.random.normal(jax.random.fold_in(KEY, 1), (d,), jnp.float32)
    out = ops.rmsnorm(x, s)
    want = ref.ref_rmsnorm(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("S,H,KV,hd,bq,bk", [
    (128, 2, 1, 64, 64, 64),
    (256, 4, 2, 64, 128, 64),
    (128, 8, 8, 32, 32, 128),   # MHA
    (192, 3, 1, 128, 64, 64),   # non-power-of-two heads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, H, KV, hd, bq, bk, dtype):
    B = 2
    q = jax.random.normal(KEY, (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, KV, hd), jnp.float32).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    rep = H // KV
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), rep, 1).reshape(B * H, S, hd)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), rep, 1).reshape(B * H, S, hd)
    want = ref.ref_attention(qh, kh, vh, causal=True).reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window,softcap,causal", [
    (32, None, True), (None, 50.0, True), (64, 30.0, True), (None, None, False),
])
def test_flash_attention_features(window, softcap, causal):
    B, S, H, hd = 1, 128, 2, 64
    q = jax.random.normal(KEY, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, H, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 5), (B, S, H, hd), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=32, block_k=32)
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    want = ref.ref_attention(qh, kh, vh, causal=causal, window=window,
                             softcap=softcap).reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_attention_matches_model_attention():
    """The kernel agrees with the model's _attend (same masks/scaling)."""
    from repro.configs import get_config
    from repro.models import attention as A
    cfg = get_config("gemma2-2b").reduced().with_(attn_chunk=0)
    B, S = 2, 64
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jax.random.normal(KEY, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 6), (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 7), (B, S, KV, hd), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    want = A._attend(cfg, q, k, v, pos, pos, jnp.int32(8), causal=True)
    out = ops.flash_attention(q, k, v, causal=True, window=8,
                              softcap=cfg.attn_softcap, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out.reshape(B, S, H * hd)),
                               np.asarray(want), rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("S,di,n,bd,bs", [
    (64, 64, 16, 32, 32), (128, 128, 8, 128, 64), (96, 32, 4, 16, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_sweep(S, di, n, bd, bs, dtype):
    B = 2
    u = (jax.random.normal(KEY, (B, S, di), jnp.float32) * 0.5).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 8), (B, S, di))) * 0.1).astype(dtype)
    Bm = jax.random.normal(jax.random.fold_in(KEY, 9), (B, S, n), jnp.float32).astype(dtype)
    Cm = jax.random.normal(jax.random.fold_in(KEY, 10), (B, S, n), jnp.float32).astype(dtype)
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 11), (di, n)) * 0.2)
    Dp = jnp.ones((di,))
    out = ops.selective_scan(u, dt, Bm, Cm, A, Dp, block_d=bd, block_s=bs)
    want = ref.ref_selective_scan(u, dt, Bm, Cm, A, Dp)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **(_tol(dtype) if dtype == jnp.bfloat16
                                  else dict(rtol=1e-4, atol=1e-4)))


def test_selective_scan_matches_model_ssm():
    """Kernel output matches models/ssm.py's associative-scan mixing core."""
    from repro.configs import get_config
    from repro.models import ssm as M
    cfg = get_config("falcon-mamba-7b").reduced()
    p = M.init_mamba(jax.random.key(1), cfg, jnp.float32)
    B, S = 2, 64
    u = jax.random.normal(KEY, (B, S, cfg.d_inner), jnp.float32) * 0.3
    u_c = jax.nn.silu(M._causal_conv(p, u, cfg.ssm_conv))
    dA, dBu, Cm = M._ssm_inputs(cfg, p, u_c)
    want = M.mamba_mix(cfg, p, u)
    # reconstruct kernel inputs (dt recomputed the same way)
    x_dbl = (u_c @ p["x_proj"]).astype(jnp.float32)
    dtr, n = cfg.dt_rank_actual, cfg.ssm_state
    dt_low, Bm, Cm2 = jnp.split(x_dbl, [dtr, dtr + n], axis=-1)
    dt = jax.nn.softplus(dt_low @ p["dt_w"].astype(jnp.float32) + p["dt_b"])
    A = -jnp.exp(p["A_log"])
    out = ops.selective_scan(u_c.astype(jnp.float32), dt, Bm, Cm2, A, p["D"],
                             block_d=64, block_s=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n,block", [
    (4096, 1024), (8192, 4096), (2048, 2048),
    # tail blocks: n not a multiple of block (masked boundary path)
    (5000, 4096), (1000, 512), (37, 8), (3, 4096), (1, 4096),
])
def test_zo_kernels_sweep(n, block):
    ss = ops.zo_sumsq(n, 1234, offset=77, block=block)
    np.testing.assert_allclose(float(ss), float(ref.ref_zo_sumsq(n, 1234, 77)),
                               rtol=1e-5)
    x = jax.random.normal(KEY, (n,), jnp.float32)
    out = ops.zo_perturb(x, 55, 0.01, offset=3, block=block)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.ref_zo_perturb(x, 55, 0.01, 3)),
                               rtol=1e-6, atol=1e-6)
    salts = jnp.asarray([1, 2, 3, 4], jnp.uint32)
    coeffs = jnp.asarray([0.5, -1.0, 2.0, 0.1], jnp.float32)
    out = ops.zo_reconstruct(n, salts, coeffs, offset=9, block=block)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.ref_zo_reconstruct(n, salts, coeffs, 9)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,block", [(1000, 512), (2048, 2048)])
def test_zo_reconstruct_acc_dtype(n, block):
    """Per-worker bf16 accumulator rounding matches the oracle bit-for-bit
    (the rounding quantizes away the kernel/oracle fma-order freedom)."""
    salts = jnp.asarray([7, 11, 13, 17], jnp.uint32)
    coeffs = jnp.asarray([0.25, -0.75, 1.5, 0.3], jnp.float32)
    out = ops.zo_reconstruct(n, salts, coeffs, offset=0, block=block,
                             acc_dtype="bfloat16")
    want = ref.ref_zo_reconstruct(n, salts, coeffs, 0, acc_dtype="bfloat16")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# --------------------------------------------------------------------------- #
# flat (packed multi-leaf) kernels: block metadata sweeps + the fused commit
# --------------------------------------------------------------------------- #
# All bit-level comparisons hold jit-ness constant (jitted kernel vs jitted
# oracle): XLA contracts a + s*b to fma under jit but not eagerly, so an
# eager oracle differs in the last ulp for structural — not numerical —
# reasons.

FLAT_LAYOUTS = [
    ([1000, 261], 256),   # tail blocks on both leaves
    ([37, 3, 1], 8),      # tiny leaves incl. a scalar-sized one
    ([129], 64),          # single leaf, odd tail
]


def _flat_layout(sizes, block, base_salt=100):
    """The kernels' per-leaf tables: (first block, element count, salt)."""
    starts, b0 = [], 0
    for n in sizes:
        starts.append(b0)
        b0 += max(1, -(-n // block))
    return (jnp.asarray(starts, jnp.int32), jnp.asarray(sizes, jnp.int32),
            base_salt + jnp.arange(len(sizes), dtype=jnp.uint32))


def _flat_meta(sizes, block, base_salt=100):
    """The oracles' per-block (leaf salt, leaf-local counter start, valid
    lanes) — built independently of the kernels' leaf lookup."""
    salts, ctrs, nvalid = [], [], []
    for li, n in enumerate(sizes):
        for b in range(max(1, -(-n // block))):
            salts.append(base_salt + li)
            ctrs.append(b * block)
            nvalid.append(min(block, n - b * block))
    return (jnp.asarray(salts, jnp.uint32), jnp.asarray(ctrs, jnp.uint32),
            jnp.asarray(nvalid, jnp.int32))


def _packed(sizes, block, key=KEY):
    """Block-aligned packed buffer: leaf data, zero padding lanes."""
    parts = []
    for li, n in enumerate(sizes):
        nb = max(1, -(-n // block))
        x = jax.random.normal(jax.random.fold_in(key, li), (n,), jnp.float32)
        parts.append(jnp.pad(x, (0, nb * block - n)))
    return jnp.concatenate(parts)


@pytest.mark.parametrize("sizes,block", FLAT_LAYOUTS)
def test_zo_perturb_flat_sweep(sizes, block):
    salts, ctrs, nvalid = _flat_meta(sizes, block)
    starts, lsizes, lsalts = _flat_layout(sizes, block)
    x = _packed(sizes, block)
    scale = jnp.float32(3e-3)
    out = ops.zo_perturb_flat(x, starts, lsizes, lsalts, scale, block=block)

    @jax.jit
    def oracle(x, scale):
        outs = []
        for b in range(int(salts.shape[0])):
            g = ref._ref_flat_gauss(salts[b], ctrs[b], nvalid[b], block)
            xb = x[b * block:(b + 1) * block]
            valid = jnp.arange(block) < nvalid[b]
            outs.append(jnp.where(valid, xb + scale * g, xb))
        return jnp.concatenate(outs)

    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle(x, scale)))


@pytest.mark.parametrize("sizes,block", FLAT_LAYOUTS)
@pytest.mark.parametrize("acc_dtype", ["float32", "bfloat16"])
def test_zo_reconstruct_flat_sweep(sizes, block, acc_dtype):
    m = 4
    salts1, ctrs, nvalid = _flat_meta(sizes, block)
    msalts = jnp.stack([salts1 + jnp.uint32(w * 1009) for w in range(m)], axis=1)
    starts, lsizes, lsalts = _flat_layout(sizes, block)
    wsalts = jnp.stack([lsalts + jnp.uint32(w * 1009) for w in range(m)])
    coeffs = jnp.asarray([0.5, -1.0, 2.0, 0.1], jnp.float32)
    out = ops.zo_reconstruct_flat(int(salts1.shape[0]), starts, lsizes, wsalts,
                                  coeffs, block=block, acc_dtype=acc_dtype)

    @jax.jit
    def oracle(coeffs):
        adt = jnp.dtype(acc_dtype)
        outs = []
        for b in range(int(msalts.shape[0])):
            acc = jnp.zeros((block,), jnp.float32)
            for w in range(m):
                g = ref._ref_flat_gauss(msalts[b, w], ctrs[b], nvalid[b], block)
                acc = (acc + coeffs[w] * g).astype(adt).astype(jnp.float32)
            outs.append(acc)
        return jnp.concatenate(outs)

    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle(coeffs)))


@pytest.mark.parametrize("sizes,block", FLAT_LAYOUTS)
def test_zo_perturb_sumsq_matches_oracle(sizes, block):
    """One launch = perturb AND the tree-wide sumsq (blockwise-sequential
    accumulation, mirrored exactly by the oracle)."""
    salts, ctrs, nvalid = _flat_meta(sizes, block)
    starts, lsizes, lsalts = _flat_layout(sizes, block)
    x = _packed(sizes, block)
    out, ss = ops.zo_perturb_sumsq(x, starts, lsizes, lsalts, 1e-3, block=block)
    oracle = jax.jit(lambda x: ref.ref_zo_perturb_sumsq(
        x, salts, ctrs, nvalid, 1e-3, block=block))
    want, wss = oracle(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(ss).reshape(()), np.asarray(wss))
    # padding lanes never contribute to the norm
    g = (np.asarray(want) - np.asarray(x))
    valid_total = sum(sizes)
    assert np.count_nonzero(g) <= valid_total


@pytest.mark.parametrize("momentum", [0.0, 0.9])
def test_zo_reconstruct_update_matches_ref(momentum):
    """Fused commit kernel vs its jnp oracle, incl. the bf16-leaf rounding
    path (bf16_mask marks the second leaf's blocks)."""
    sizes, block = [1000, 261], 256
    salts1, ctrs, nvalid = _flat_meta(sizes, block)
    m = 4
    msalts = jnp.stack([salts1 + jnp.uint32(w * 613) for w in range(m)], axis=1)
    starts, lsizes, lsalts = _flat_layout(sizes, block)
    wsalts = jnp.stack([lsalts + jnp.uint32(w * 613) for w in range(m)])
    # leaf 0 (4 blocks of 256) fp32; leaf 1 (2 blocks) commits through bf16
    bf16 = jnp.asarray([0, 0, 0, 0, 1, 1], jnp.int32)
    leaf_bf16 = jnp.asarray([0, 1], jnp.int32)
    coeffs = jnp.asarray([0.25, -0.75, 1.5, 0.3], jnp.float32)
    p = _packed(sizes, block)
    mom = None if momentum == 0.0 else jnp.zeros_like(p) + 0.1
    lr = 0.05
    got_p, got_m = ops.zo_reconstruct_update(
        p.copy(), None if mom is None else mom.copy(), starts, lsizes,
        leaf_bf16, wsalts, coeffs, lr, momentum=momentum, block=block)
    oracle = jax.jit(lambda p, mom, c: ref.ref_zo_reconstruct_update(
        p, mom, msalts, ctrs, nvalid, bf16, c, lr, momentum=momentum,
        block=block))
    want_p, want_m = oracle(p, mom, coeffs)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    if momentum:
        np.testing.assert_array_equal(np.asarray(got_m), np.asarray(want_m))
    else:
        assert got_m is None


@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("acc_dtype", ["float32", "bfloat16"])
def test_zo_reconstruct_update_matches_opt_apply(m, acc_dtype):
    """ISSUE 10 satellite pin: the fused commit kernel equals the unfused
    composition ``apply_deltas ∘ sgd.update ∘ zo_reconstruct_flat`` across
    momentum steps, m, accumulator dtypes, and uneven tail blocks.

    The kernel commits ``p + (-lr)*v`` with the same multiply-add structure
    the composition lowers to, so with both sides jitted the trajectories
    are bit-identical (the ISSUE floor is ulp-bounded fp32 / bit-identical
    bf16-acc; the structural match gives bitwise in both)."""
    from repro.opt.optimizers import apply_deltas, const_schedule, sgd

    sizes, block = [1000, 261], 256
    starts, lsizes, lsalts = _flat_layout(sizes, block)
    wsalts = jnp.stack([lsalts + jnp.uint32(w * 271) for w in range(m)])
    n_blocks = int(starts[-1]) + 2
    bf16 = jnp.zeros((len(sizes),), jnp.int32)
    lr, momentum = 0.05, 0.9
    opt = sgd(const_schedule(lr), momentum)

    @jax.jit
    def step_unfused(p, state, coeffs, t):
        g = ops.zo_reconstruct_flat(n_blocks, starts, lsizes, wsalts, coeffs,
                                    block=block, acc_dtype=acc_dtype)
        deltas, state = opt.update(g, state, p, t)
        return apply_deltas(p, deltas), state

    p_ref = _packed(sizes, block)
    state = opt.init(p_ref)
    p_k, mom_k = p_ref, jnp.zeros_like(p_ref)
    for t in range(3):
        coeffs = jnp.linspace(-1.0, 1.0, m + 1)[1:] * jnp.float32(t + 1)
        p_ref, state = step_unfused(p_ref, state, coeffs, t)
        p_k, mom_k = ops.zo_reconstruct_update(
            p_k, mom_k, starts, lsizes, bf16, wsalts, coeffs, lr,
            momentum=momentum, block=block, acc_dtype=acc_dtype)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_ref))
    np.testing.assert_array_equal(np.asarray(mom_k), np.asarray(state))


def test_zo_reconstruct_update_donates_eagerly():
    """The commit op consumes its packed buffers in place (donation) — the
    flat engine's fused step path relies on never re-reading them."""
    sizes, block = [129], 64
    starts, lsizes, lsalts = _flat_layout(sizes, block)
    p = _packed(sizes, block)
    out, _ = ops.zo_reconstruct_update(
        p, None, starts, lsizes, jnp.zeros((1,), jnp.int32), lsalts[None],
        jnp.ones((1,), jnp.float32), 0.1, block=block)
    assert p.is_deleted()
    assert not out.is_deleted()


def test_zo_kernel_matches_optimizer_directions():
    """The Pallas hash is bit-identical to the optimizer's direction gen:
    perturbing leaf-by-leaf with the kernel == directions.sphere + axpy."""
    from repro.core import directions as D
    params = {"w": jax.random.normal(KEY, (4096,)), "b": jax.random.normal(KEY, (2048,))}
    seed, t, worker, mu = 3, jnp.int32(5), jnp.uint32(2), 1e-2
    v = D.sphere_direction(params, seed, t, worker)
    want = D.tree_axpy(jnp.float32(mu), v, params)
    # kernel path: per-leaf salts, global norm via zo_sumsq, then zo_perturb
    leaves, treedef = jax.tree.flatten(params)
    salts = [D.fold(seed, t, worker, i) for i in range(len(leaves))]
    ssq = sum(float(ops.zo_sumsq(x.size, s, 0, block=2048))
              for x, s in zip(leaves, salts))
    inv = 1.0 / np.sqrt(ssq)
    got = [ops.zo_perturb(x, s, mu * inv, 0, block=2048)
           for x, s in zip(leaves, salts)]
    for g, w in zip(got, jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-6)
