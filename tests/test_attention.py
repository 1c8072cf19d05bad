"""Attention semantics: windows, decode cache slicing, encoder mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as A
from repro.models import transformer as T

KEY = jax.random.key(0)


def _cfg(**kw):
    return get_config("qwen3-14b").reduced().with_(remat=False, **kw)


def test_window_limits_context():
    """With window W, logits at position i ignore keys before i-W+1."""
    cfg = _cfg(attn_chunk=0)
    p = A.init_attention(KEY, cfg, jnp.float32)
    S, W = 24, 4
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (1, S, cfg.d_model)) * 0.2
    out_w = A.attention_forward(cfg, p, x, jnp.int32(W))
    # perturb a token far outside every later window
    x2 = x.at[:, 2].set(5.0)
    out_w2 = A.attention_forward(cfg, p, x2, jnp.int32(W))
    # positions >= 2+W see no difference; positions < 2+W do
    np.testing.assert_allclose(np.asarray(out_w[:, 2 + W:]),
                               np.asarray(out_w2[:, 2 + W:]), atol=1e-5)
    assert bool(jnp.any(jnp.abs(out_w[:, 2] - out_w2[:, 2]) > 1e-3))


def test_decode_static_window_slice_matches_masked_full():
    """The long-context decode fast path (dynamic_slice of the last W cache
    slots) must equal masked full-cache attention."""
    cfg = _cfg()
    p = A.init_attention(KEY, cfg, jnp.float32)
    B, S, W = 2, 32, 8
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, cfg.n_kv_heads, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, cfg.n_kv_heads, 32))
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (B, 1, cfg.d_model)) * 0.2
    for pos in (3, 7, 20, 31):
        full, _ = A.attention_decode(cfg, p, x, (k, v), jnp.int32(pos),
                                     window=jnp.int32(W))
        sliced, _ = A.attention_decode(cfg, p, x, (k, v), jnp.int32(pos),
                                       window=jnp.int32(W), static_window=W)
        np.testing.assert_allclose(np.asarray(full), np.asarray(sliced),
                                   rtol=1e-4, atol=1e-5, err_msg=f"pos={pos}")


def test_long_context_variant_decode_consistency():
    """gemma2's long_500k SWA variant: step-by-step decode == forward."""
    cfg = get_config("gemma2-2b").reduced().with_(
        remat=False, long_context=True)
    assert cfg.subquadratic
    params = T.init_model(jax.random.key(5), cfg)
    B, S = 1, 20
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    full_logits, _ = T.forward_logits(cfg, params, {"tokens": toks})
    caches = T.init_caches(cfg, B, S, jnp.float32)
    outs = []
    for t in range(S):
        lg, caches = T.decode_step(cfg, params, toks[:, t], jnp.int32(t), caches)
        outs.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full_logits), rtol=3e-3, atol=3e-3)


def test_encoder_attention_is_bidirectional():
    cfg = get_config("hubert-xlarge").reduced().with_(remat=False, attn_chunk=0)
    p = A.init_attention(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 6), (1, 12, cfg.d_model)) * 0.2
    out = A.attention_forward(cfg, p, x)
    # changing a FUTURE token changes the FIRST position's output
    x2 = x.at[:, 11].set(3.0)
    out2 = A.attention_forward(cfg, p, x2)
    assert bool(jnp.any(jnp.abs(out[:, 0] - out2[:, 0]) > 1e-4))


def test_adaptive_tau_beyond_paper():
    from repro.core.ho_sgd import HOSGDConfig, make_adaptive_ho_sgd, run_method
    def quad_loss(params, batch):
        return 0.5 * jnp.mean(jnp.sum((params["x"] - batch["t"]) ** 2, -1))
    rng = np.random.default_rng(0)
    def batches():
        while True:
            yield {"t": (1.0 + 0.1 * rng.normal(size=(16, 32))).astype(np.float32)}
    meth = make_adaptive_ho_sgd(
        quad_loss, HOSGDConfig(tau=8, mu=1e-4, m=4, lr=0.3, zo_lr=0.3 / 16),
        tau_schedule=lambda t: 2 + t // 20)
    hist = run_method(meth, {"x": jnp.zeros((32,))}, batches(), 80)
    final = float(quad_loss(hist["params"], {"t": np.ones((1, 32), np.float32)}))
    assert final < 0.1, final
    assert 1 in hist["order"] and 0 in hist["order"]


# --------------------------------------------------------------------------- #
# blocked (flash) attention: the kernel path of _attend_seq
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("causal,window,cap,H,KV,hd,dtype", [
    (True, None, None, 4, 4, 96, jnp.float32),     # phi3: MHA, head dim 96
    (True, None, None, 4, 2, 64, jnp.float32),     # GQA
    (True, 160, None, 4, 2, 128, jnp.float32),     # window across blocks
    (True, None, 30.0, 4, 1, 96, jnp.float32),     # soft cap, MQA
    (True, 100, 50.0, 2, 2, 64, jnp.float32),      # gemma2-like local layer
    (False, None, None, 4, 4, 64, jnp.float32),    # encoder
    (False, 96, None, 4, 2, 96, jnp.float32),
    (True, None, None, 4, 2, 96, jnp.bfloat16),    # the chip's dtype
])
def test_blocked_attention_matches_dense(causal, window, cap, H, KV, hd,
                                         dtype):
    """Output and dq, dk, dv of the kernel path (interpret mode, 3 x 3
    blocks of 128) against the dense ``_attend``."""
    cfg = _cfg(attn_softcap=cap)
    B, S = 2, 384
    ks = jax.random.split(jax.random.fold_in(KEY, 7), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd)).astype(dtype)
    dout = jax.random.normal(ks[3], (B, S, H * hd)).astype(dtype)
    pos = jnp.arange(S, dtype=jnp.int32)
    w = None if window is None else jnp.int32(window)
    dense = lambda q, k, v: A._attend(cfg, q, k, v, pos, pos, w, causal)
    blocked = lambda q, k, v: A._attend_blocked(cfg, q, k, v, causal, window,
                                                interpret=True)
    want, vjp_want = jax.vjp(dense, q, k, v)
    got, vjp_got = jax.vjp(blocked, q, k, v)
    tol = (dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16
           else dict(rtol=1e-4, atol=1e-4))
    for name, a, b in zip(("out", "dq", "dk", "dv"),
                          (got,) + vjp_got(dout), (want,) + vjp_want(dout)):
        assert a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), err_msg=name,
                                   **tol)


@pytest.mark.parametrize("platform,arch,S,hd,long_context,want", [
    ("TPU v5 lite", "phi3-mini-3.8b", 4096, None, False, True),
    ("tpu", "qwen3-14b", 256, None, False, True),
    ("TPU v5 lite", "gemma2-2b", 4096, None, True, True),   # uniform window
    ("cpu", "phi3-mini-3.8b", 4096, None, False, False),    # interpret only
    ("TPU v5 lite", "phi3-mini-3.8b", 4000, None, False, False),
    ("TPU v5 lite", "phi3-mini-3.8b", 128, None, False, False),  # one block
    ("TPU v5 lite", "gemma2-2b", 4096, None, False, False),  # mixed windows
    ("TPU v5 lite", "hymba-1.5b", 4096, None, False, False),
    ("TPU v5 lite", "phi3-mini-3.8b", 4096, 72, False, False),
    ("TPU v5 lite", "phi3-mini-3.8b", 4096, 512, False, False),
])
def test_flash_selection_rule(platform, arch, S, hd, long_context, want):
    cfg = get_config(arch).with_(long_context=long_context)
    assert A._use_flash(cfg, S, hd or cfg.head_dim, platform) is want


def test_kernel_platform_reads_the_ambient_mesh():
    from repro.launch.mesh import auto_mesh
    assert A._kernel_platform() == "cpu"
    with jax.set_mesh(auto_mesh((1, 1), ("data", "model"))):
        assert A._kernel_platform() == "cpu"


_SHARDED_FO = r'''
import json, jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import auto_mesh
from repro.models import attention as A

cfg = get_config("qwen3-14b").reduced().with_(remat=False)
mesh = auto_mesh((4, 1), ("data", "model"))
B, S, D, H, KV, hd = 8, 256, 64, 4, 2, 32
pos = jnp.arange(S, dtype=jnp.int32)

def loss(w, x, blocked=True):
    q = (x @ w["q"]).reshape(x.shape[0], S, H, hd)
    k = (x @ w["k"]).reshape(x.shape[0], S, KV, hd)
    v = (x @ w["v"]).reshape(x.shape[0], S, KV, hd)
    if blocked:
        o = A._attend_blocked(cfg, q, k, v, True, None, interpret=True)
    else:
        o = A._attend(cfg, q, k, v, pos, pos, None, True)
    return jnp.mean(o.astype(jnp.float32) ** 2)

def zo_like(w, x):
    # a forward inside a shard_map manual over the worker axis, as the ZO
    # step runs it: the kernel's shard_map nests over the axes left
    per = jax.shard_map(lambda w, x: jax.lax.pmean(loss(w, x), "data"),
                        mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
                        axis_names={"data"}, check_vma=False)
    return per(w, x)

ks = jax.random.split(jax.random.key(0), 4)
w = {n: (jax.random.normal(kk, (D, m * hd)) * 0.2).astype(jnp.bfloat16)
     for kk, (n, m) in zip(ks, (("q", H), ("k", KV), ("v", KV)))}
x = jax.random.normal(ks[3], (B, S, D)).astype(jnp.bfloat16)
want_l, want_g = jax.value_and_grad(partial(loss, blocked=False))(w, x)
fo = jax.value_and_grad(loss)
with jax.set_mesh(mesh):
    ws = jax.device_put(w, NamedSharding(mesh, P()))
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    jaxpr = str(jax.make_jaxpr(fo)(ws, xs))
    hlo = jax.jit(fo).lower(ws, xs).compile().as_text()
    got_l, got_g = jax.jit(fo)(ws, xs)
    zo_l = jax.jit(zo_like)(ws, xs)
err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
          for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)))
print(json.dumps({"shard_map": jaxpr.count("shard_map"),
                  "pallas_call": jaxpr.count("pallas_call"),
                  "all_gather": hlo.count("all-gather"),
                  "all_reduce": hlo.count("all-reduce"),
                  "loss": [float(want_l), float(got_l), float(zo_l)],
                  "grad_err": err}))
'''


def test_flash_runs_per_shard_under_a_data_mesh():
    """An FO-style value_and_grad on a 4-device CPU mesh: the kernels sit in
    a shard_map over the batch, nothing gathers q, k or v (only the weight
    gradients are all-reduced), and loss and gradients are dense
    attention's."""
    import json
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([src, os.environ.get("PYTHONPATH",
                                                               "")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _SHARDED_FO],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["shard_map"] >= 1 and got["pallas_call"] >= 1, got
    assert got["all_gather"] == 0, got
    assert got["all_reduce"] >= 1, got
    # the same numbers as dense attention on one device, in the FO form and
    # nested in a worker-manual shard_map (the ZO form)
    want, fo, zo = got["loss"]
    assert abs(fo - want) < 1e-3 * abs(want) and abs(zo - want) < 1e-3 * abs(want), got
    assert got["grad_err"] < 2e-2, got
