"""DirectionEngine: the one home of the ZO direction algebra.

Before this module the same four primitives — direction norm, parameter
perturbation, the scalar ZO coefficient, and the update reconstruction —
were implemented four times (tree-materialized jnp in ``core.ho_sgd`` /
``core.zo_grad``, fused XLA closures private to ``core.distributed``, the
Pallas kernels in ``kernels.zo_direction``, and the oracles in
``kernels.ref``).  Every consumer now goes through a ``DirectionEngine``;
backends are interchangeable and adding one (real-TPU Mosaic, bf16
accumulators, fused optimizer updates) is a one-file change.

Backends
--------
* ``tree``   — the readable jnp reference: materializes the whole raw
               direction tree per primitive and maps over it; the worker
               loop in ``reconstruct`` is statically unrolled (HLO O(m)).
* ``fused``  — the production XLA formulation (lifted out of the old
               ``make_zo_step`` closures): per-leaf generation inlined into
               the consuming op so the direction never exists as a program
               buffer, worker loop as ``fori_loop`` (HLO O(1) in m).
* ``pallas`` — routes ``perturb``/``reconstruct`` through the
               ``kernels.ops`` Pallas kernels: the direction is regenerated
               inside the tile and never touches HBM; all m workers are
               reconstructed in one pass over the parameters.
* ``flat``   — packs the tree into ONE contiguous block-aligned f32 buffer
               and runs one multi-leaf kernel per primitive (vs one per
               leaf), plus a fused step path (perturb+sumsq in one launch,
               reconstruct+SGD-commit in one launch on donated buffers) used
               by ``core.ho_sgd``/``core.distributed`` when the optimizer is
               plain SGD(+momentum).

Contract (see README §DirectionEngine)
--------------------------------------
* Directions are the hashed gaussians of ``repro.core.directions``: leaf i
  of worker w at iteration t uses salt ``fold(seed, t, w, i)`` with
  leaf-local counters starting at 0 — bit-compatibility REQUIRES leaf-local
  counters (the kernels' ``offset`` argument shifts the intra-leaf counter
  when one leaf is split across calls; whole-leaf calls pass 0, and the
  grid blocks shift by ``i * block`` internally).  The engine precomputes
  per-leaf ``(salt_index, offset)`` metadata at construction — ``offsets``
  records each leaf's base index in the flat d-dim vector, layout metadata
  for backends that pack the tree into one flat buffer (such a backend
  still hashes each leaf with its own salt from counter 0).  Salts depend
  on traced ``(t, worker)`` and are folded per call.
* ``inv_norm`` is computed by the shared jnp reduction in *every* backend,
  so the perturb scale and reconstruction coefficients are bit-identical
  across backends by construction (a kernel-side ``zo_sumsq`` exists but
  changes the reduction order; it stays a benchmarking primitive).
* ``perturb(params, t, w, scale)`` applies ``x_f32 + scale * v_raw`` cast
  back to ``x.dtype`` — ``scale`` is the premultiplied fp32
  ``mu * inv_norm``, so every backend applies the identical elementwise
  expression.
* ``reconstruct(coeffs, t)`` returns ``sum_w (coeffs[w] * inv_norm_w) *
  v_raw_w`` as an fp32 tree, rounding the accumulator to ``acc_dtype``
  after each worker (the distributed semantics; callers apply the final
  ``zo_scale / m``).
* Sharding hooks: ``specs`` (per-leaf PartitionSpecs, or a matching tree)
  are applied to every generated direction leaf and accumulator, so the
  partitioner can never replicate a hash-generated tree (the O(d)-per-
  device failure mode of unconstrained iota).
* Bit-equality caveat: backends evaluate the identical algebra, but XLA's
  transcendental vectorization is shape-dependent, so the Pallas backend is
  bitwise equal to tree/fused only when its tile covers the whole leaf;
  sub-leaf tiles may differ in the last ulp (the equivalence suite pins
  both regimes).
"""
from __future__ import annotations

import math
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import directions as D


def _as_worker(w) -> jax.Array:
    return jnp.asarray(w, jnp.uint32)


class DirectionEngine:
    """Base class: shared metadata, norm algebra, and the coefficient eval."""

    name = "base"
    #: the backend runs Pallas kernels, which execute per device: a mesh
    #: program must call it inside a shard_map that is manual over every
    #: mesh axis, on unsharded leaves (Mosaic kernels cannot be
    #: auto-partitioned)
    per_device = False

    def __init__(self, params_like: Any, seed: int, *, specs: Any = None,
                 acc_dtype: Any = "float32", block: int = 4096):
        leaves, self.treedef = jax.tree.flatten(params_like)
        self.shapes: List[Tuple[int, ...]] = [tuple(x.shape) for x in leaves]
        self.dtypes = [jnp.dtype(x.dtype) for x in leaves]
        self.sizes = [int(math.prod(s)) for s in self.shapes]
        # per-leaf base index in the flat d-dim vector: layout metadata for
        # backends that pack the tree into one flat buffer.  NOT a hash
        # counter — counters are leaf-local (see the module docstring).
        self.offsets: List[int] = []
        off = 0
        for n in self.sizes:
            self.offsets.append(off)
            off += n
        self.dim = off
        self.seed = seed
        self.acc_dtype = jnp.dtype(acc_dtype)
        self.block = block
        if specs is None:
            self.specs: List[Optional[P]] = [None] * len(leaves)
        elif isinstance(specs, (list, tuple)):
            self.specs = list(specs)
        else:
            self.specs = jax.tree.leaves(
                specs, is_leaf=lambda x: x is None or isinstance(x, P))
        assert len(self.specs) == len(leaves), \
            f"{len(self.specs)} specs for {len(leaves)} leaves"

    # ---- metadata ------------------------------------------------------- #
    def salts(self, t, worker) -> List[jax.Array]:
        """Per-leaf salts for (t, worker) — the hash identity of one v."""
        w = _as_worker(worker)
        return [D.fold(self.seed, t, w, i) for i in range(len(self.shapes))]

    def _constrain(self, x: jax.Array, i: int) -> jax.Array:
        s = self.specs[i]
        return x if s is None else jax.lax.with_sharding_constraint(x, s)

    def _gauss(self, i: int, salt: jax.Array) -> jax.Array:
        """Leaf i's raw (unnormalized) direction, sharding-constrained."""
        return self._constrain(D.gaussian_from_salt(self.shapes[i], salt), i)

    # ---- primitive 1: the unit-sphere normalization --------------------- #
    def sumsq(self, t, worker) -> jax.Array:
        """||v_raw||^2 over the whole tree — shared jnp reduction in every
        backend (keeps the scale bit-identical across backends)."""
        salts = self.salts(t, worker)
        return sum(
            jnp.sum(jnp.square(self._gauss(i, s))) for i, s in enumerate(salts)
        )

    def inv_norm(self, t, worker) -> jax.Array:
        with jax.named_scope("zo.norm"):
            return jax.lax.rsqrt(self.sumsq(t, worker) + 1e-30)

    # ---- primitive 2: perturb ------------------------------------------- #
    def perturb(self, params: Any, t, worker, scale) -> Any:
        """x + scale * v_raw per leaf, cast back to each leaf's dtype.

        ``scale`` is the premultiplied fp32 ``mu * inv_norm(t, worker)``.
        """
        raise NotImplementedError

    # ---- primitive 3: the scalar ZO coefficient (eq. 4) ----------------- #
    def zo_coeff(self, loss_fn: Callable[[Any, Any], jax.Array], params: Any,
                 batch: Any, t, worker, mu: float) -> Tuple[jax.Array, jax.Array]:
        """Two function evaluations -> (c, f0) with
        c = (d/mu) * [F(x + mu*v) - F(x)]."""
        inv = self.inv_norm(t, worker)
        with jax.named_scope("zo.forward"):
            f0 = loss_fn(params, batch)
        with jax.named_scope("zo.perturb"):
            x = self.perturb(params, t, worker, jnp.float32(mu) * inv)
        with jax.named_scope("zo.forward"):
            f1 = loss_fn(x, batch)
        return ((self.dim / mu) * (f1 - f0)).astype(jnp.float32), f0

    def zo_coeffs(self, loss_fn: Callable, params: Any, batches: Any, t,
                  workers: jax.Array, mu: float
                  ) -> Tuple[jax.Array, jax.Array]:
        """All m workers' coefficients; ``batches`` is worker-stacked
        (m, B, ...)."""
        cs, f0s = [], []
        for i in range(int(workers.shape[0])):
            b_i = jax.tree.map(lambda x: x[i], batches)
            c, f0 = self.zo_coeff(loss_fn, params, b_i, t, workers[i], mu)
            cs.append(c)
            f0s.append(f0)
        return jnp.stack(cs), jnp.stack(f0s)

    # ---- primitive 4: reconstruct --------------------------------------- #
    def reconstruct(self, coeffs: jax.Array, t, workers: Optional[jax.Array]
                    = None, *, vmap_workers: bool = False) -> Any:
        """sum_w (coeffs[w] * inv_norm_w) * v_raw_w as an fp32 tree.

        The accumulator is rounded to ``acc_dtype`` after every worker —
        the exact semantics of the distributed reconstruction.  With
        ``vmap_workers`` the per-worker terms are generated under one vmap
        and contracted (HLO O(1) in m; one fp32 sum, single final rounding —
        equal to the sequential path within accumulation-order tolerance).
        """
        m = int(coeffs.shape[0])
        if workers is None:
            workers = jnp.arange(m, dtype=jnp.uint32)
        coeffs = coeffs.astype(jnp.float32)
        if vmap_workers:
            return self._reconstruct_vmapped(coeffs, t, workers)
        return self._reconstruct(coeffs, t, workers)

    def _reconstruct(self, coeffs, t, workers) -> Any:
        raise NotImplementedError

    def _reconstruct_vmapped(self, coeffs, t, workers) -> Any:
        scaled = coeffs * jax.vmap(lambda w: self.inv_norm(t, w))(workers)
        outs = []
        for i in range(len(self.shapes)):
            gen = jax.vmap(
                lambda w, i=i: D.gaussian_from_salt(
                    self.shapes[i], D.fold(self.seed, t, _as_worker(w), i)))
            g = gen(workers)                                # (m, *leaf)
            acc = jnp.tensordot(scaled, g, axes=(0, 0))     # fp32 contraction
            outs.append(self._constrain(
                acc.astype(self.acc_dtype).astype(jnp.float32), i))
        return jax.tree.unflatten(self.treedef, outs)

    def _acc_init(self) -> List[jax.Array]:
        return [
            self._constrain(jnp.zeros(s, self.acc_dtype), i)
            for i, s in enumerate(self.shapes)
        ]


# --------------------------------------------------------------------------- #
class TreeEngine(DirectionEngine):
    """Materialized-tree jnp reference (the historical core.zo_grad path)."""

    name = "tree"

    def perturb(self, params, t, worker, scale):
        leaves = jax.tree.leaves(params)
        salts = self.salts(t, worker)
        vs = [self._gauss(i, s) for i, s in enumerate(salts)]  # materialized
        out = [
            (x.astype(jnp.float32) + scale * g).astype(x.dtype)
            for x, g in zip(leaves, vs)
        ]
        return jax.tree.unflatten(self.treedef, out)

    def _reconstruct(self, coeffs, t, workers):
        acc = self._acc_init()
        for i in range(int(coeffs.shape[0])):  # static unroll over workers
            w = _as_worker(workers[i])
            coeff = coeffs[i] * self.inv_norm(t, w)
            salts = self.salts(t, w)
            vs = [self._gauss(li, s) for li, s in enumerate(salts)]
            acc = [
                (a.astype(jnp.float32) + coeff * g).astype(self.acc_dtype)
                for a, g in zip(acc, vs)
            ]
        return jax.tree.unflatten(
            self.treedef, [a.astype(jnp.float32) for a in acc])


# --------------------------------------------------------------------------- #
class FusedEngine(DirectionEngine):
    """Fused-XLA formulation (the closures lifted out of make_zo_step).

    Generation is inlined into every consuming op, one leaf at a time, so
    XLA fuses the hash into the reduce/add/accumulate and the direction
    never exists as a program buffer; the worker loop is a ``fori_loop`` so
    the lowered HLO is O(1) in m.
    """

    name = "fused"

    def perturb(self, params, t, worker, scale):
        leaves = jax.tree.leaves(params)
        salts = self.salts(t, worker)
        out = [
            (x.astype(jnp.float32) + scale * self._gauss(i, s)).astype(x.dtype)
            for i, (x, s) in enumerate(zip(leaves, salts))
        ]
        return jax.tree.unflatten(self.treedef, out)

    def _reconstruct(self, coeffs, t, workers):
        def body(i, acc):
            w = _as_worker(workers[i])
            coeff = coeffs[i] * self.inv_norm(t, w)
            salts = self.salts(t, w)
            return [
                (a.astype(jnp.float32)
                 + coeff * self._gauss(li, s)).astype(self.acc_dtype)
                for li, (a, s) in enumerate(zip(acc, salts))
            ]

        acc = jax.lax.fori_loop(0, int(coeffs.shape[0]), body, self._acc_init())
        return jax.tree.unflatten(
            self.treedef, [a.astype(jnp.float32) for a in acc])


# --------------------------------------------------------------------------- #
class PallasEngine(DirectionEngine):
    """Pallas-kernel backend: the direction never touches HBM.

    ``perturb`` is one read + one write of x per leaf; ``reconstruct`` is a
    single pass over the parameters with all m gaussians generated in
    registers (``kernels.zo_direction``).  Leaves are processed flattened;
    arbitrary sizes are handled by the kernels' masked tail blocks.  The
    kernels run per-device (interpret mode on CPU, Mosaic on TPU) — use
    ``tree``/``fused`` for meshes where leaves are sharded across devices.
    """

    name = "pallas"
    per_device = True

    def perturb(self, params, t, worker, scale):
        from repro.kernels import ops  # deferred: keeps core importable early

        leaves = jax.tree.leaves(params)
        salts = self.salts(t, worker)
        out = [
            self._constrain(
                ops.zo_perturb(x.reshape(-1), s, scale,
                               block=self.block).reshape(x.shape), i)
            for i, (x, s) in enumerate(zip(leaves, salts))
        ]
        return jax.tree.unflatten(self.treedef, out)

    def _reconstruct(self, coeffs, t, workers):
        from repro.kernels import ops

        m = int(coeffs.shape[0])
        invs = jnp.stack(
            [self.inv_norm(t, _as_worker(workers[i])) for i in range(m)])
        scaled = coeffs * invs
        per_leaf_salts = [
            jnp.stack([D.fold(self.seed, t, _as_worker(workers[i]), li)
                       for i in range(m)])
            for li in range(len(self.shapes))
        ]
        out = [
            self._constrain(
                ops.zo_reconstruct(self.sizes[li], per_leaf_salts[li], scaled,
                                   block=self.block,
                                   acc_dtype=str(self.acc_dtype)
                                   ).reshape(self.shapes[li]), li)
            for li in range(len(self.shapes))
        ]
        return jax.tree.unflatten(self.treedef, out)


# --------------------------------------------------------------------------- #
class FlatEngine(DirectionEngine):
    """Packed single-buffer backend: the whole tree in one Pallas launch.

    The parameter tree is packed once into a single contiguous f32 buffer
    with every leaf padded to a multiple of ``block``, so each grid block
    belongs to exactly one leaf; per-leaf ``(first block, element count,
    is-bf16)`` tables are precomputed at construction, and the kernels
    derive each block's counter start and valid lanes from them.  The
    hash identity is unchanged — leaf-local counters from 0, one salt per
    ``(t, worker, leaf)`` — so the algebra matches the other backends.

    * The standard primitives (``perturb``/``reconstruct``) pack, run ONE
      kernel for the whole tree (vs one per leaf in ``pallas``), and unpack;
      ``inv_norm`` stays the shared jnp reduction so the coefficients are
      bit-identical across backends by construction.
    * The fused step path (``pack``/``fused_perturb_sumsq``/
      ``fused_reconstruct_update``) keeps the buffer packed across the whole
      ZO round: the perturb pass accumulates the tree-wide ``sum(v^2)``
      in the same launch (no separate inv-norm pass over d), and the
      reconstruct pass applies the SGD(+momentum) update in-kernel with the
      params/momentum buffers donated and aliased in place — the update
      vector never exists in HBM.  The fused sumsq's blockwise reduction
      order differs from the jnp reduction, so the fused step is
      loss-equivalent (not bitwise) to the per-primitive path.

    Like ``pallas``, kernels run per-device (interpret on CPU, Mosaic on
    TPU) — use ``tree``/``fused`` for meshes where leaves are sharded.
    """

    name = "flat"
    per_device = True

    def __init__(self, params_like: Any, seed: int, *, specs: Any = None,
                 acc_dtype: Any = "float32", block: int = 4096):
        super().__init__(params_like, seed, specs=specs, acc_dtype=acc_dtype,
                         block=block)
        starts = []
        self.pad_offsets: List[int] = []   # leaf start in the PACKED buffer
        off = 0
        for n in self.sizes:
            self.pad_offsets.append(off)
            starts.append(off // block)
            off += max(1, -(-n // block)) * block   # scalars still take a block
        self.padded_dim = off
        self.n_blocks = off // block
        # per-leaf layout tables (first block, element count, is-bf16): the
        # kernels derive each block's leaf, counter start and valid lanes
        self._starts = jnp.asarray(starts, jnp.int32)
        self._sizes = jnp.asarray(self.sizes, jnp.int32)
        self._bf16 = jnp.asarray(
            [1 if dt == jnp.bfloat16 else 0 for dt in self.dtypes], jnp.int32)

    # ---- packed-buffer layout ------------------------------------------- #
    def pack(self, tree: Any) -> jax.Array:
        """Tree -> (padded_dim,) contiguous f32 buffer (bf16 -> f32 exact)."""
        parts = []
        for i, x in enumerate(jax.tree.leaves(tree)):
            flat = x.astype(jnp.float32).reshape(-1)
            pad = -(-max(self.sizes[i], 1) // self.block) * self.block \
                - self.sizes[i]
            parts.append(jnp.pad(flat, (0, pad)) if pad else flat)
        return jnp.concatenate(parts)

    def unpack(self, buf: jax.Array, cast: bool = True) -> Any:
        """(padded_dim,) buffer -> tree; ``cast`` restores leaf dtypes
        (False returns fp32 leaves — update/momentum trees)."""
        outs = []
        for i, shape in enumerate(self.shapes):
            off = self.pad_offsets[i]
            leaf = buf[off:off + self.sizes[i]].reshape(shape)
            if cast:
                leaf = leaf.astype(self.dtypes[i])
            outs.append(self._constrain(leaf, i))
        return jax.tree.unflatten(self.treedef, outs)

    def leaf_salts(self, t, workers) -> jax.Array:
        """(m, L) uint32 — per-(worker, leaf) salts."""
        return jnp.stack([jnp.stack(self.salts(t, _as_worker(workers[i])))
                          for i in range(int(workers.shape[0]))])

    # ---- standard primitives (pack -> one launch -> unpack) -------------- #
    def perturb(self, params, t, worker, scale):
        from repro.kernels import ops  # deferred: keeps core importable early

        out = ops.zo_perturb_flat(
            self.pack(params), self._starts, self._sizes,
            jnp.stack(self.salts(t, worker)), scale, block=self.block)
        return self.unpack(out)

    def _reconstruct(self, coeffs, t, workers):
        from repro.kernels import ops

        m = int(coeffs.shape[0])
        invs = jnp.stack(
            [self.inv_norm(t, _as_worker(workers[i])) for i in range(m)])
        out = ops.zo_reconstruct_flat(
            self.n_blocks, self._starts, self._sizes,
            self.leaf_salts(t, workers), coeffs * invs, block=self.block,
            acc_dtype=str(self.acc_dtype))
        return self.unpack(out, cast=False)

    # ---- fused step path (buffer stays packed across the round) ---------- #
    def fused_perturb_sumsq(self, buf: jax.Array, t, worker, mu
                            ) -> Tuple[jax.Array, jax.Array]:
        """One launch: ``(buf + mu*rsqrt(sumsq)*v, sumsq)`` — the inv-norm
        pass over d disappears into the perturb's grid."""
        from repro.kernels import ops

        out, ss = ops.zo_perturb_sumsq(
            buf, self._starts, self._sizes, jnp.stack(self.salts(t, worker)),
            mu, block=self.block)
        return out, ss[0]

    def fused_reconstruct_update(self, buf: jax.Array, mom, t, workers,
                                 scaled_coeffs: jax.Array, lr,
                                 momentum: float = 0.0):
        """One launch: regenerate all m directions in registers, contract
        with ``scaled_coeffs`` (= c_w * inv_norm_w * zo_scale / m), and
        commit the SGD(+momentum) update in place (donated buffers).

        Returns ``(buf', mom')``; ``mom'`` is None when ``mom`` is None.
        """
        from repro.kernels import ops

        return ops.zo_reconstruct_update(
            buf, mom, self._starts, self._sizes, self._bf16,
            self.leaf_salts(t, workers), scaled_coeffs, lr,
            momentum=float(momentum), block=self.block,
            acc_dtype=str(self.acc_dtype))


# --------------------------------------------------------------------------- #
ENGINES = {
    "tree": TreeEngine,
    "fused": FusedEngine,
    "pallas": PallasEngine,
    "flat": FlatEngine,
}


def make_engine(name: str, params_like: Any, seed: int, *, specs: Any = None,
                acc_dtype: Any = "float32", block: int = 4096
                ) -> DirectionEngine:
    """Build a DirectionEngine backend by name
    ('tree' | 'fused' | 'pallas' | 'flat')."""
    try:
        cls = ENGINES[name]
    except KeyError:
        raise ValueError(
            f"unknown direction engine {name!r}; have {sorted(ENGINES)}"
        ) from None
    return cls(params_like, seed, specs=specs, acc_dtype=acc_dtype, block=block)
