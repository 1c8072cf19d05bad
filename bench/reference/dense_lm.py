"""Plain float32 reference of a dense decoder LM trained with HO-SGD.

It follows the layer equations that a configuration file states, in
``jax.numpy`` at ``Precision.HIGHEST``, one sequence at a time and layer by
layer, so that it fits on one chip beside nothing else.  It imports nothing
of the program under test.  What it shares with the program is only what
the configuration and the method define:

* the weights, made from ``--seed`` by the recipe the configuration names
  (``init``: truncated-normal fan-in projections, N(0, 0.02) embedding and
  head, zero norm parameters), and held in the configuration's dtype;
* the model: pre-norm blocks (RMSNorm or LayerNorm with a ``1 + scale``
  gain), RoPE on the two halves of each head, causal GQA attention, a SwiGLU
  or tanh-GELU MLP, a final norm, an untied head, and the token-mean
  cross-entropy over positions whose label is >= 0;
* HO-SGD's two steps: the FO step, plain SGD on the batch-mean gradient;
  the ZO step, eq. (4)-(6) with the pre-shared hashed Gaussian directions
  (leaf ``i`` of worker ``w`` at iteration ``t`` hashes the salt
  ``fold(seed, t, w, i)`` with leaf-local counters; leaves are numbered in
  the sorted-key order of the parameter tree).  Every update is applied in
  float32 and rounded to the leaf's dtype, as the configuration's dtype
  states.

``lowp`` replaces every matmul operand by its round trip through a lower
precision (per-tensor scaled float8 e4m3, or bfloat16): that is the control
that the comparison has to fail.  Rows are spread over ``devices`` in
contiguous blocks, as a data-parallel mesh holds them; each device keeps its
own copy of the weights and computes the same updates.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
ATTN_CHUNK = 512

# --------------------------------------------------------------------------- #
# the direction hash (lowbias32 counters, Box-Muller cos branch)
# --------------------------------------------------------------------------- #
_M1, _M2 = np.uint32(0x7FEB352D), np.uint32(0x846CA68B)
_GOLDEN, _SALT2, _XOR2 = (np.uint32(0x9E3779B9), np.uint32(0x85EBCA6B),
                          np.uint32(0xC2B2AE35))


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    return x ^ (x >> 16)


def salt(*ints) -> jax.Array:
    acc = jnp.zeros((), jnp.uint32)
    for v in ints:
        acc = _mix32(acc ^ (jnp.asarray(v, jnp.uint32) * _GOLDEN))
    return acc


def gaussian(shape, salt_, offset) -> jax.Array:
    """Standard normals for flat counters ``offset + row-major index``."""
    n = math.prod(shape)
    idx = (jnp.arange(n, dtype=jnp.uint32) + jnp.asarray(offset, jnp.uint32))
    u1 = (_mix32(idx * _GOLDEN + salt_) >> 8).astype(F32) * F32(2**-24) \
        + F32(2**-25)
    u2 = (_mix32(idx * _SALT2 + (salt_ ^ _XOR2)) >> 8).astype(F32) \
        * F32(2**-24) + F32(2**-25)
    g = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(6.283185307179586 * u2)
    return g.reshape(shape)


# --------------------------------------------------------------------------- #
# precision of the matmul operands
# --------------------------------------------------------------------------- #
def _roundtrip(dtype: Optional[str]):
    """Forward-only rounding of a matmul operand (identity gradient)."""
    if dtype is None:
        return lambda x: x
    if dtype == "bfloat16":
        def rt(x):
            return x.astype(jnp.bfloat16).astype(F32)
    elif dtype == "float8_e4m3fn":
        def rt(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
            return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    else:
        raise ValueError(f"no control precision {dtype!r}")
    return lambda x: x + jax.lax.stop_gradient(rt(x) - x)


LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


# --------------------------------------------------------------------------- #
# the model equations
# --------------------------------------------------------------------------- #
class DenseLM:
    def __init__(self, model: Dict, devices: Sequence, lowp: Optional[str] = None):
        self.m = dict(model)
        self.devices = list(devices)
        self.q = _roundtrip(lowp)
        m = self.m
        self.L, self.D, self.V = m["n_layers"], m["d_model"], m["vocab_size"]
        self.H, self.KV, self.hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
        self.F = m["d_ff"]
        self.gated = m["activation"] == "swiglu"
        self.layernorm = m["norm"] == "layernorm"
        self.dtype = jnp.dtype(m["dtype"])
        self.leaf_specs = self._leaf_specs()
        self.dim = sum(math.prod(s) for _, s, _ in self.leaf_specs)
        self._jit()

    # ---- parameter layout --------------------------------------------- #
    def _norm_keys(self):
        return ["bias", "scale"] if self.layernorm else ["scale"]

    def _layer_shapes(self) -> Dict[str, tuple]:
        D, H, KV, hd, F = self.D, self.H, self.KV, self.hd, self.F
        s = {"attn/wk": (D, KV * hd), "attn/wo": (H * hd, D),
             "attn/wq": (D, H * hd), "attn/wv": (D, KV * hd),
             "mlp/wd": (F, D), "mlp/wu": (D, F)}
        if self.gated:
            s["mlp/wg"] = (D, F)
        for n in ("norm1", "norm2"):
            for k in self._norm_keys():
                s[f"{n}/{k}"] = (D,)
        return s

    def _leaf_specs(self):
        """(path, stacked shape, per-layer?) in sorted-key flatten order."""
        specs = [("embed", (self.V, self.D), False)]
        specs += [(f"final_norm/{k}", (self.D,), False) for k in self._norm_keys()]
        specs.append(("head", (self.D, self.V), False))
        specs += [(f"layers/{p}", (self.L,) + s, True)
                  for p, s in sorted(self._layer_shapes().items())]
        return specs

    # ---- weights from the seed ---------------------------------------- #
    def init(self, seed: int):
        """{'top': {path: array}, 'layers': [{path: array}] * L} on every
        device (the stacked tree's layer slices)."""
        dt = self.dtype

        def dense(k, shape):
            std = 1.0 / jnp.sqrt(jnp.asarray(shape[-2], F32))
            return (jax.random.truncated_normal(k, -2.0, 2.0, shape, F32)
                    * std).astype(dt)

        def norm_leaves(prefix):
            return {f"{prefix}/{k}": jnp.zeros((self.D,), F32)
                    for k in self._norm_keys()}

        def layer(k):
            ks = jax.random.split(k, 6)
            ka = jax.random.split(ks[0], 4)
            km = jax.random.split(ks[2], 3)
            p = {"attn/wq": dense(ka[0], (self.D, self.H * self.hd)),
                 "attn/wk": dense(ka[1], (self.D, self.KV * self.hd)),
                 "attn/wv": dense(ka[2], (self.D, self.KV * self.hd)),
                 "attn/wo": dense(ka[3], (self.H * self.hd, self.D))}
            if self.gated:
                p.update({"mlp/wg": dense(km[0], (self.D, self.F)),
                          "mlp/wu": dense(km[1], (self.D, self.F)),
                          "mlp/wd": dense(km[2], (self.F, self.D))})
            else:
                p.update({"mlp/wu": dense(km[0], (self.D, self.F)),
                          "mlp/wd": dense(km[1], (self.F, self.D))})
            p.update(norm_leaves("norm1"))
            p.update(norm_leaves("norm2"))
            return p

        # op by op, as the program's init runs: a jitted init fuses the
        # N(0, 0.02) scaling and can round a few bf16 weights the other way
        def make(key):
            k_embed, k_layers, k_head = jax.random.split(key, 3)
            top = {"embed": (jax.random.normal(k_embed, (self.V, self.D), F32)
                             * 0.02).astype(dt),
                   "head": (jax.random.normal(k_head, (self.D, self.V), F32)
                            * 0.02).astype(dt)}
            top.update(norm_leaves("final_norm"))
            stacked = jax.vmap(layer)(jax.random.split(k_layers, self.L))
            return top, [jax.tree.map(lambda a, i=i: a[i], stacked)
                         for i in range(self.L)]

        top, layers = make(jax.random.key(seed))
        return [jax.device_put({"top": top, "layers": layers}, d)
                for d in self.devices]

    # ---- forward pieces (float32) ------------------------------------- #
    def _mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HI)

    def _norm(self, p, prefix, x):
        g = 1.0 + p[f"{prefix}/scale"].astype(F32)
        eps = self.m["norm_eps"]
        if self.layernorm:
            mu = jnp.mean(x, -1, keepdims=True)
            var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
            return (x - mu) * jax.lax.rsqrt(var + eps) * g \
                + p[f"{prefix}/bias"].astype(F32)
        var = jnp.mean(jnp.square(x), -1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * g

    def _rope(self, x, pos):
        hd = x.shape[-1]
        freqs = 1.0 / (self.m["rope_theta"]
                       ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
        ang = pos[:, None].astype(F32) * freqs
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def _attention(self, p, x):
        B, S, _ = x.shape
        H, KV, hd = self.H, self.KV, self.hd
        w = lambda k: p[k].astype(F32)
        pos = jnp.arange(S, dtype=jnp.int32)
        q = self._rope(self._mm(x, w("attn/wq")).reshape(B, S, H, hd), pos)
        k = self._rope(self._mm(x, w("attn/wk")).reshape(B, S, KV, hd), pos)
        v = self._mm(x, w("attn/wv")).reshape(B, S, KV, hd)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        chunk = min(S, ATTN_CHUNK)
        if S % chunk:
            raise ValueError(f"sequence {S} is not a multiple of {chunk}")
        window = self.m.get("window")

        def one(c):
            qc = jax.lax.dynamic_slice_in_dim(q, c * chunk, chunk, axis=1)
            s = jnp.einsum("bqhd,bkhd->bhqk", self.q(qc), self.q(k),
                           precision=HI) / math.sqrt(hd)
            rel = (c * chunk + jnp.arange(chunk))[:, None] - pos[None, :]
            mask = rel >= 0
            if window:
                mask &= rel < window
            s = jnp.where(mask, s, -jnp.inf)
            pr = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", self.q(pr), self.q(v),
                              precision=HI)

        outs = jax.lax.map(jax.checkpoint(one), jnp.arange(S // chunk))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H * hd)
        return self._mm(out, w("attn/wo"))

    def _mlp(self, p, x):
        w = lambda k: p[k].astype(F32)
        if self.gated:
            h = jax.nn.silu(self._mm(x, w("mlp/wg"))) * self._mm(x, w("mlp/wu"))
        else:
            h = jax.nn.gelu(self._mm(x, w("mlp/wu")), approximate=True)
        return self._mm(h, w("mlp/wd"))

    def _layer(self, lp, x):
        x = x + self._attention(lp, self._norm(lp, "norm1", x))
        return x + self._mlp(lp, self._norm(lp, "norm2", x))

    def _embed(self, top, tokens):
        x = jnp.take(top["embed"].astype(F32), tokens, axis=0)
        return x * math.sqrt(self.D) if self.m["embed_scale"] else x

    def _ce_sum(self, top, x, labels):
        h = self._norm(top, "final_norm", x)
        logits = self._mm(h, top["head"].astype(F32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(jnp.where(labels >= 0, lse - gold, 0.0))

    def _jit(self):
        self.j_embed = jax.jit(self._embed)
        self.j_layer = jax.jit(self._layer)
        self.j_ce = jax.jit(self._ce_sum)

        def layer_bwd(lp, x, ct):
            return jax.vjp(self._layer, lp, x)[1](ct)

        def head_bwd(top, x, labels):
            f = lambda hp, x: self._ce_sum({**top, **hp}, x, labels)
            hp = {k: v for k, v in top.items() if k != "embed"}
            s, (d_hp, dx) = jax.value_and_grad(f, argnums=(0, 1))(hp, x)
            return s, d_hp, dx

        def embed_bwd(tokens, dx):
            scale = math.sqrt(self.D) if self.m["embed_scale"] else 1.0
            return jnp.zeros((self.V, self.D), F32).at[tokens.reshape(-1)].add(
                dx.reshape(-1, self.D) * scale)

        self.j_layer_bwd = jax.jit(layer_bwd)
        self.j_head_bwd = jax.jit(head_bwd)
        self.j_embed_bwd = jax.jit(embed_bwd)
        self.j_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                             donate_argnums=0)

    # ---- losses and gradients over rows ------------------------------- #
    def _row_blocks(self, n_rows: int) -> List[List[int]]:
        per = n_rows // len(self.devices)
        return [list(range(i * per, (i + 1) * per))
                for i in range(len(self.devices))]

    def ce_rows(self, params: Dict, dev_i: int, tokens, labels) -> List:
        """Per-row sums of per-token CE on device ``dev_i`` (not waited on)."""
        dev = self.devices[dev_i]
        out = []
        for r in range(tokens.shape[0]):
            tok = jax.device_put(tokens[r:r + 1], dev)
            lab = jax.device_put(labels[r:r + 1], dev)
            x = self.j_embed(params["top"], tok)
            for lp in params["layers"]:
                x = self.j_layer(lp, x)
            out.append(self.j_ce(params["top"], x, lab))
        return out

    def grad_sums(self, params: List[Dict], tokens, labels, hooks=None):
        """Sums of per-token CE gradients over the rows, in float32.

        Returns ``(grads on device 0, per-row sums of CE)``.  Each device
        sums its own rows; ``hooks[n](grads)`` sees device 0's sum over its
        first ``n`` rows (rows ``0 .. n-1``), before the devices' sums are
        added.
        """
        hooks = hooks or {}
        blocks = self._row_blocks(tokens.shape[0])
        accs, ce = [None] * len(self.devices), {}
        for j in range(len(blocks[0])):
            for di, rows in enumerate(blocks):
                r = rows[j]
                g, ce[r] = self._row_grad(params[di], di, tokens[r:r + 1],
                                          labels[r:r + 1])
                accs[di] = g if accs[di] is None else self.j_add(accs[di], g)
            if j + 1 in hooks:
                hooks[j + 1](accs[0])
        total = accs[0]
        for di in range(1, len(accs)):
            total = self.j_add(total, jax.device_put(accs[di], self.devices[0]))
            accs[di] = None
        return total, [float(ce[r]) for r in sorted(ce)]

    def _row_grad(self, p, di, tok, lab):
        dev = self.devices[di]
        tok, lab = jax.device_put(tok, dev), jax.device_put(lab, dev)
        xs = [self.j_embed(p["top"], tok)]
        for lp in p["layers"][:-1]:
            xs.append(self.j_layer(lp, xs[-1]))
        x_last = self.j_layer(p["layers"][-1], xs[-1])
        s, d_top, dx = self.j_head_bwd(p["top"], x_last, lab)
        d_layers = [None] * self.L
        for l in range(self.L - 1, -1, -1):
            d_layers[l], dx = self.j_layer_bwd(p["layers"][l], xs[l], dx)
        d_top = dict(d_top)
        d_top["embed"] = self.j_embed_bwd(tok, dx)
        return {"top": d_top, "layers": d_layers}, s

    # ---- per-leaf views ------------------------------------------------ #
    def leaf_parts(self, tree, path):
        """The arrays that make up stacked leaf ``path``: one per layer."""
        if path.startswith("layers/"):
            key = path[len("layers/"):]
            return [lp[key] for lp in tree["layers"]]
        return [tree["top"][path]]

    def leaf_sq_norms(self, fn, *trees) -> List[float]:
        """Per-leaf sum over its parts of ``fn(*parts)`` (a scalar)."""
        out = []
        for path, _, _ in self.leaf_specs:
            parts = zip(*(self.leaf_parts(t, path) for t in trees))
            out.append(float(sum(np.float64(fn(*ps)) for ps in parts)))
        return out


# --------------------------------------------------------------------------- #
# HO-SGD steps
# --------------------------------------------------------------------------- #
def _sgd(p, g, lr, factor):
    """p <- dtype(f32(p) - lr * (g * factor)) on every leaf."""
    return jax.tree.map(
        lambda a, b: (a.astype(F32) - lr * (b * factor)).astype(a.dtype), p, g)


_j_sgd = jax.jit(_sgd)


class Stepper:
    """Follows the first steps of an HO-SGD run from the seed's weights."""

    def __init__(self, ref: DenseLM, seed: int, lr: float, mu: float,
                 zo_lr: float, m: int):
        self.ref, self.seed, self.lr, self.mu, self.m = ref, seed, lr, mu, m
        self.zo_scale = zo_lr / lr
        n_leaves = len(ref.leaf_specs)
        self.n_leaves = n_leaves
        self._j_sumsq = jax.jit(self._sumsq)
        self._j_perturb = jax.jit(self._perturb)
        self._j_zo_apply = jax.jit(self._zo_apply)

    # ---- direction algebra, one worker or all ---------------------------- #
    def _parts(self, p):
        """(leaf index, part array, counter offset) over the whole tree."""
        out = []
        for i, (path, shape, stacked) in enumerate(self.ref.leaf_specs):
            parts = self.ref.leaf_parts(p, path)
            n = math.prod(shape[1:]) if stacked else 0
            out += [(i, a, l * n) for l, a in enumerate(parts)]
        return out

    def _sumsq(self, p, salts):
        return [jnp.sum(jnp.square(gaussian(a.shape, salts[i], off)))
                for i, a, off in self._parts(p)]

    def _rebuild(self, p, new_parts):
        it = iter(new_parts)
        top = dict(p["top"])
        layers = [dict(lp) for lp in p["layers"]]
        for path, _, stacked in self.ref.leaf_specs:
            if stacked:
                key = path[len("layers/"):]
                for lp in layers:
                    lp[key] = next(it)
            else:
                top[path] = next(it)
        return {"top": top, "layers": layers}

    def _perturb(self, p, salts, scale):
        return self._rebuild(p, [
            (a.astype(F32) + scale * gaussian(a.shape, salts[i], off)
             ).astype(a.dtype) for i, a, off in self._parts(p)])

    def _zo_apply(self, p, salts, coefs, lr, factor):
        """Eq. (6): p - lr * factor * sum_w coefs[w] * v_w, per leaf."""
        new = []
        for i, a, off in self._parts(p):
            acc = jnp.zeros(a.shape, F32)
            for w in range(salts.shape[0]):
                acc = acc + coefs[w] * gaussian(a.shape, salts[w, i], off)
            new.append((a.astype(F32) - lr * (acc * factor)).astype(a.dtype))
        return self._rebuild(p, new)

    def _salts(self, t, w):
        return jnp.stack([salt(self.seed, t, w, i)
                          for i in range(self.n_leaves)])

    # ---- the steps -------------------------------------------------------- #
    def fo(self, params, tokens, labels, hooks=None):
        ref = self.ref
        count = int(np.sum(labels >= 0))
        g, ce = ref.grad_sums(params, tokens, labels, hooks)
        new0 = _j_sgd(params[0], g, np.float32(self.lr), np.float32(1.0 / count))
        out = [new0] + [jax.device_put(new0, d) for d in ref.devices[1:]]
        return out, ce, g, count

    def zo(self, params, t, tokens, labels):
        """Worker ``w`` runs on device ``w`` where there is one per worker;
        every device is dispatched before any result is waited on."""
        ref, m = self.ref, self.m
        blocks = np.array_split(np.arange(tokens.shape[0]), m)
        dev = [w if len(ref.devices) == m else 0 for w in range(m)]
        salts = [jax.device_put(self._salts(t, w), ref.devices[dev[w]])
                 for w in range(m)]
        sumsq = [self._j_sumsq(params[dev[w]], salts[w]) for w in range(m)]
        invs = [np.float32(1.0 / math.sqrt(sum(np.float64(x) for x in ss)
                                           + 1e-30)) for ss in sumsq]
        f0, f1 = [], []
        for w in range(m):
            p, rows = params[dev[w]], blocks[w]
            f0.append(ref.ce_rows(p, dev[w], tokens[rows], labels[rows]))
            pp = self._j_perturb(p, salts[w], np.float32(self.mu) * invs[w])
            f1.append(ref.ce_rows(pp, dev[w], tokens[rows], labels[rows]))
            if len(ref.devices) < m:
                jax.block_until_ready(f1[-1])   # one perturbed copy at a time
            del pp
        f0s, coefs = [], []
        for w in range(m):
            count = int(np.sum(labels[blocks[w]] >= 0))
            a = sum(np.float64(x) for x in f0[w]) / count
            b = sum(np.float64(x) for x in f1[w]) / count
            c = np.float32((ref.dim / self.mu) * (b - a))
            f0s.append(a)
            coefs.append(np.float32(c * invs[w]))
        all_salts = jnp.stack([self._salts(t, w) for w in range(m)])
        coefs = np.asarray(coefs, np.float32)
        factor = np.float32(self.zo_scale / m)
        out = [self._j_zo_apply(p, jax.device_put(all_salts, d),
                                jax.device_put(coefs, d),
                                np.float32(self.lr), factor)
               for p, d in zip(params, ref.devices)]
        return out, float(np.mean(f0s))
