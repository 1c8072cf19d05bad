"""Round-program IR: one per-worker method representation shared by the
distributed runtime, the baselines, and the simulator.

The paper's method is a *schedule of per-worker rounds*: ZO rounds where each
worker contributes a directional-derivative scalar in a pre-shared direction,
punctuated by FO gradient syncs.  Before this module the repo encoded that
schedule three times — as monolithic all-m-workers step programs in
``core.distributed``, as vmapped single-host loops in ``core.baselines``, and
implicitly in ``repro.sim``'s replay (which therefore could reprice
async/elastic scenarios but never change the computed trajectory).  A method
is now written ONCE as a ``RoundProgram``:

  * ``init(params) -> state`` and a host-side schedule
    ``round_for(t, state) -> RoundStep`` picking this iteration's ``Round``;
  * each ``Round`` is a per-worker ``local(t, worker, model, shard) ->
    (payload, aux)`` plus a collective op — ``all_reduce`` (mean of
    payloads), ``all_gather`` (stacked payloads), ``tree_average`` (model
    tree averaging), ``masked_average`` (per-coordinate weighted average
    over the workers that sent a nonzero value — the federated
    FedDropoutAvg commit), ``neighbor_exchange`` (ring-gossip mixing) or
    ``none`` — with an explicit wire codec hook (``Wire``);
  * ``apply(t, params, state, reduced, workers, aux)`` commits the reduced
    payload into the global ``(params, state)``.

Consumers (README §RoundProgram):

  * ``core.distributed.make_fo_step`` / ``make_zo_step`` LOWER the HO-SGD
    rounds to the mesh (the ZO round as a shard_map) —
    the whole schedule fuses into monolithic jitted programs, bit-identical
    to the pre-IR step functions on the synchronous full-membership path.
  * ``core.baselines`` builds PA/RI/QSGD (and gossip-PA) as round programs
    and derives their single-host ``Method`` via ``to_method``.
  * ``repro.sim.runner`` replays rounds PER WORKER through a
    ``RoundExecutor`` so bounded-staleness and elastic membership feed each
    worker the params/membership it actually has — trajectories genuinely
    diverge instead of only being repriced, and the live-W collective
    prices the payload each active worker actually sent.

Wire accounting follows the ``CommLedger`` receive convention (bytes
received per worker per collective):

  * ``all_gather``  — bytes of the gathered result: payload × n_active;
  * ``all_reduce``  — dense: bytes of the reduced payload (independent of
    W); with a per-worker codec: ``codec.nbytes`` × n_active (each worker
    receives every active worker's code — QSGD's real protocol); with the
    legacy post-reduction codec: ``codec.nbytes`` × 1;
  * ``tree_average`` — dense: bytes of the averaged model tree; with a
    per-worker codec: ``codec.nbytes`` × n_active (the reducer receives
    every active worker's encoded tree); legacy: ``codec.nbytes`` × 1;
  * ``masked_average`` — per-client payload bytes (codec bytes when a
    codec rides the wire, dense otherwise) × n_active: exactly what the
    live sampled cohort uploads, never × the client population N;
  * ``neighbor_exchange`` — min(2, W-1) neighbor payloads per worker;
  * ``none`` — 0.

A ``Wire`` codec only composes with the collectives that actually move an
encodable payload — see ``CODEC_COLLECTIVES``; ``Round.__post_init__``
fails fast on any other (collective, codec) pairing instead of silently
booking dense bytes.

The executor both returns the byte count (``metrics["comm_bytes"]``) and
books it through ``repro.dist.collectives.note`` so a ledger-wrapped replay
records the identical number — the wire model lives in exactly one place.

Federated partial participation (``core.federated``): a ``RoundProgram``
with a ``client_sampling`` spec runs each round over a freshly sampled
K-of-N client cohort — the executor draws the cohort, feeds every sampled
client its own data shard (``federated.cohort_shards``), and weighs the
``masked_average`` commit by client dataset size.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.ho_sgd import Method, _split_workers
from repro.dist import collectives as coll
from repro.dist.collectives import _tree_nbytes
from repro.dist.compress import Compressor, compress_tree

#: collective ops a Round may request (the executor's reduce semantics)
COLLECTIVES = ("all_reduce", "all_gather", "tree_average", "masked_average",
               "neighbor_exchange", "none")

#: the (collective, codec) support matrix: collectives a ``Wire`` codec
#: composes with — both booked by ``wire_nbytes`` and round-tripped by
#: ``reduce_payloads``.  ``all_gather`` moves raw (typically scalar)
#: payloads and ``none`` moves nothing; a codec there would silently book
#: dense bytes, so ``Round.__post_init__`` rejects it.
CODEC_COLLECTIVES = ("all_reduce", "tree_average", "masked_average",
                     "neighbor_exchange")

#: wire codec application modes
WIRE_MODES = ("per_worker", "legacy")


@dataclass(frozen=True)
class Wire:
    """How a round's payload crosses the wire.

    ``per_worker`` encodes every worker's payload independently and decodes
    at the reducer (the faithful QSGD/signSGD protocol: per-worker wire
    bytes = ``codec.nbytes`` × active workers).  ``legacy`` keeps the
    historical post-reduction simulation — ``decode(encode(mean))`` on the
    already-reduced payload, booked at one worker's wire bytes.

    ``seed`` roots the per-worker encode keys (``fold(key(seed), t,
    worker_id)`` — the worker ID, not its position in the live membership,
    so a worker's quantization stream survives other workers leaving, and
    matches the mesh lowering's keys).
    """

    codec: Optional[Compressor] = None
    mode: str = "per_worker"
    seed: int = 0

    def __post_init__(self):
        assert self.mode in WIRE_MODES, \
            f"unknown wire mode {self.mode!r}; have {WIRE_MODES}"


@dataclass(frozen=True)
class Overlap:
    """Compute/communication overlap spec for a round.

    ``buckets > 1`` splits the round's wire payload into that many buckets:
    bucket k's collective may run concurrently with the local compute that
    produces chunk k+1, so only the tail of the collective is exposed on
    the critical path.  ``buckets=1`` is the strict compute-then-communicate
    round (the historical price, and the default).

    The spec changes *time only, never bytes*: the simulator prices an
    overlapped round as ``compute + max(0, comm − compute·(B−1)/B)`` (the
    first chunk must finish before the first bucket can depart; see
    ``sim.costs.exposed_comm_time``), the mesh lowering chunks the flat
    gradient into ``B`` independently-reducible buckets
    (``core.distributed.lower_fo_round``), and the ``CommLedger`` books the
    identical wire bytes either way — pinned in ``tests/test_comm_ledger.py``.
    """

    buckets: int = 1

    def __post_init__(self):
        assert self.buckets >= 1, f"overlap buckets must be >= 1, got {self.buckets}"

    @property
    def enabled(self) -> bool:
        return self.buckets > 1

    @property
    def overlappable_fraction(self) -> float:
        """Fraction of the round's local compute a pipelined collective can
        hide behind: (B−1)/B — chunk 1 must exist before bucket 1 departs."""
        return (self.buckets - 1) / self.buckets


@dataclass(frozen=True, eq=False)
class Round:
    """One per-worker round: local computation + collective + apply.

    ``eq=False`` keeps object identity for ``__eq__``/``__hash__``: rounds
    are compared and cached (``RoundExecutor``'s jit caches) by the object
    itself, which both matches the semantics (two rounds with identical
    fields still close over distinct jitted ``local``s) and pins a strong
    reference in the cache — a dynamically built round can never alias a
    dead round's cache entry the way the historical ``id(rnd)`` keys could.

    ``local(t, worker, model, shard) -> (payload, aux)`` runs on each
    participating worker; ``model`` is the worker's model view — the global
    params for data-parallel methods, the worker's own replica (from
    ``state["replicas"]``) when ``replica=True``.  ``aux`` is a monitoring
    scalar (typically the local loss) — diagnostics, never part of the
    algorithm's communication (booked ``payload=False``, like the loss
    pmean in the distributed ZO step).

    ``apply(t, params, state, reduced, workers, aux)`` commits the round:
    ``reduced`` is the collective's output, ``workers`` the uint32 array of
    contributing worker ids (the live membership under elastic execution),
    ``aux`` the worker-stacked aux values.  Programs jit their own apply
    internals; host-side schedule state (e.g. ``since_fo``) stays out of it
    (see ``RoundStep.host_updates``).

    ``meta`` carries builder configuration for lowerings (e.g. the
    ``HOSGDConfig`` the mesh lowering of a ZO round needs) — opaque to the
    executor.
    """

    tag: str
    order: int                       # 1 = gradient round, 0 = function-eval
    collective: str
    local: Callable[..., Tuple[Any, Any]]
    apply: Callable[..., Tuple[Any, Any, Dict[str, Any]]]
    wire: Wire = field(default_factory=Wire)
    replica: bool = False
    meta: Any = None
    overlap: Overlap = field(default_factory=Overlap)

    def __post_init__(self):
        assert self.collective in COLLECTIVES, \
            f"unknown collective {self.collective!r}; have {COLLECTIVES}"
        if self.wire.codec is not None:
            assert self.collective in CODEC_COLLECTIVES, (
                f"a Wire codec ({self.wire.codec.name!r}) is not supported "
                f"on collective {self.collective!r}: codecs compose with "
                f"{CODEC_COLLECTIVES} (all_gather moves raw payloads, "
                f"'none' moves nothing — dense booking would silently "
                f"misreport compression)")
        if self.collective == "masked_average":
            assert self.wire.mode == "per_worker", (
                "masked_average is inherently per-client: each sampled "
                "client uploads its own (possibly masked) payload; the "
                "legacy post-reduction wire mode has no meaning here")


class RoundStep(NamedTuple):
    """One scheduled iteration: the round, the iteration index to run it at
    (``t_step`` — the adaptive-tau seed mapping), and host-side state
    updates the executor merges AFTER ``apply`` (python scalars such as the
    ``since_fo`` counter, kept out of jitted code so checkpoints keep
    canonical python leaves)."""

    round: Round
    t_step: int
    host_updates: Dict[str, Any]


@dataclass(frozen=True)
class RoundProgram:
    """A method as ``init`` + a schedule of per-worker rounds.

    ``round_for(t, state)`` is a PURE host-side function — the executor (and
    the simulator, which peeks at the coming round's order for pricing) may
    call it repeatedly for the same ``(t, state)``.  ``prepare(t, batch,
    key)`` optionally transforms the global batch before sharding (RI-SGD's
    redundancy mixing).  ``comm_scalars``/``fevals``/``gevals`` are the
    Table-1 analytic per-iteration cost hooks (``Method`` compatibility).

    ``client_sampling`` (a ``core.federated.ClientSampling``, default None)
    makes the program federated: ``m`` must equal the spec's ``cohort_k``
    (the worker slots ARE the sampled cohort), and the executor draws each
    round's live cohort from the spec instead of assuming workers 0..m-1,
    feeding every sampled client its own identity-keyed data shard.
    """

    name: str
    m: int
    init: Callable[[Any], Any]
    round_for: Callable[[int, Any], RoundStep]
    comm_scalars: Callable[[int], float]
    fevals: Callable[[int], float]
    gevals: Callable[[int], float]
    prepare: Optional[Callable[[int, Any, Any], Any]] = None
    client_sampling: Any = None

    def __post_init__(self):
        if self.client_sampling is not None:
            assert self.client_sampling.cohort_k == self.m, (
                f"federated program {self.name!r}: m={self.m} must equal "
                f"cohort_k={self.client_sampling.cohort_k} — the worker "
                f"slots are the sampled cohort")


# --------------------------------------------------------------------------- #
# shared helpers
# --------------------------------------------------------------------------- #
#: (m*B, ...) -> (m, B, ...) on every leaf (worker i owns row i) — the ONE
#: sharding convention, shared with the monolithic reference step
#: (``repro.core.ho_sgd._split_workers``)
split_shards = _split_workers


def _stack_trees(trees: Sequence[Any]) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _slice_tree(tree: Any, idx) -> Any:
    return jax.tree.map(lambda x: x[idx], tree)


def payload_nbytes(payload_slice: Any) -> int:
    """Dense wire bytes of ONE worker's payload tree."""
    return _tree_nbytes(payload_slice)


def codec_nbytes(codec: Compressor, payload_slice: Any) -> int:
    """Codec wire bytes of ONE worker's payload tree (per-leaf wire model)."""
    return sum(codec.nbytes(int(x.size))
               for x in jax.tree.leaves(payload_slice))


def wire_nbytes(rnd: Round, payload_slice: Any, n_active: int) -> int:
    """Bytes received per worker for this round's collective — the one wire
    model both the executor's booking and the simulator's pricing use."""
    if rnd.collective == "none" or n_active <= 0:
        return 0
    dense = payload_nbytes(payload_slice)
    codec = rnd.wire.codec
    if rnd.collective == "all_gather":
        return dense * n_active
    if rnd.collective == "all_reduce":
        if codec is None:
            return dense
        per = codec_nbytes(codec, payload_slice)
        return per * (n_active if rnd.wire.mode == "per_worker" else 1)
    if rnd.collective == "tree_average":
        if codec is None:
            return dense
        per = codec_nbytes(codec, payload_slice)
        return per * (n_active if rnd.wire.mode == "per_worker" else 1)
    if rnd.collective == "masked_average":
        # the sampled cohort's uploads: per-client payload × |live cohort|,
        # NEVER × the client population N
        per = dense if codec is None else codec_nbytes(codec, payload_slice)
        return per * n_active
    if rnd.collective == "neighbor_exchange":
        k = min(2, n_active - 1)
        per = dense if codec is None else codec_nbytes(codec, payload_slice)
        return per * k
    raise AssertionError(rnd.collective)


def neighbor_mix(stacked: Any, n_active: int) -> Any:
    """Ring-gossip mixing over the ACTIVE workers in listed order: worker j's
    result is the mean of its own payload and its ring neighbors'
    (``(P[j-1] + P[j] + P[j+1]) / 3``; with two workers the single neighbor,
    with one itself).  fp32 accumulation, cast back to the payload dtype."""
    if n_active == 1:
        return stacked

    def mix(x):
        x32 = x.astype(jnp.float32)
        left = jnp.roll(x32, 1, axis=0)
        right = jnp.roll(x32, -1, axis=0)
        if n_active == 2:          # left and right are the same worker
            out = (x32 + left) / 2.0
        else:
            out = (left + x32 + right) / 3.0
        return out.astype(x.dtype)

    return jax.tree.map(mix, stacked)


def masked_average(stacked: Any, weights) -> Tuple[Any, Any]:
    """FedDropoutAvg's masked weighted average over a worker-stacked tree.

    Per coordinate: ``avg = Σ_c w_c·x_c / Σ_c w_c·1[x_c ≠ 0]`` — each
    client's weight (``weights[c]``, typically its dataset size) counts
    only toward the coordinates it actually sent; a zero value is an
    absent value (FedDropoutAvg's sub-model semantics).  Returns
    ``(avg, wsum)`` trees: ``wsum`` is the per-coordinate surviving weight
    mass so ``apply`` can keep the server value where nobody contributed
    (``wsum == 0`` ⇒ ``avg == 0`` there).  fp32 accumulation, cast back.
    """
    w = jnp.asarray(weights, jnp.float32)

    def num_den(x):
        x32 = x.astype(jnp.float32)
        wb = w.reshape((w.shape[0],) + (1,) * (x32.ndim - 1))
        num = jnp.sum(x32 * wb, axis=0)
        den = jnp.sum(jnp.where(x32 != 0, wb, 0.0), axis=0)
        return num, den

    def avg_leaf(x):
        num, den = num_den(x)
        out = jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)
        return out.astype(x.dtype)

    avg = jax.tree.map(avg_leaf, stacked)
    wsum = jax.tree.map(lambda x: num_den(x)[1], stacked)
    return avg, wsum


def _wire_key(wire: Wire, key, t) -> jax.Array:
    base = key if key is not None else jax.random.key(wire.seed)
    return jax.random.fold_in(base, t)


def wire_roundtrip(wire: Wire, stacked: Any, workers: Sequence[int],
                   key_t) -> Any:
    """Per-worker encode + reducer decode of a worker-stacked payload tree.

    Each worker's slice goes through ``decode(encode(.))`` with its own key
    — ``fold_in(key_t, worker_id)``, keyed on the worker's IDENTITY so the
    stream is invariant to who else is in the live membership (and matches
    the mesh lowering's per-worker keys).  No-op without a codec or in
    legacy mode (legacy decodes after the reduction instead)."""
    if wire.codec is None or wire.mode != "per_worker":
        return stacked
    outs = []
    for j, w in enumerate(workers):
        dec, _ = compress_tree(wire.codec, _slice_tree(stacked, j),
                               jax.random.fold_in(key_t, int(w)))
        outs.append(dec)
    return _stack_trees(outs)


def reduce_payloads(rnd: Round, stacked: Any, workers: Sequence[int],
                    key_t, weights=None) -> Any:
    """Apply the wire codec and the round's collective to a worker-stacked
    payload tree; returns what ``apply`` receives as ``reduced``.

    ``weights`` (len(workers), default uniform) only matters for
    ``masked_average`` — the per-client weight of the masked weighted
    average (client dataset sizes under ``ClientSampling``)."""
    n_active = len(workers)
    if rnd.collective in ("none", "all_gather"):
        return stacked
    if rnd.collective == "neighbor_exchange":
        stacked = wire_roundtrip(rnd.wire, stacked, workers, key_t)
        return neighbor_mix(stacked, n_active)
    if rnd.collective == "masked_average":
        stacked = wire_roundtrip(rnd.wire, stacked, workers, key_t)
        if weights is None:
            weights = jnp.ones((n_active,), jnp.float32)
        return masked_average(stacked, weights)
    # all_reduce / tree_average: mean over the contributing workers
    stacked = wire_roundtrip(rnd.wire, stacked, workers, key_t)
    mean = jax.tree.map(
        lambda x: jnp.mean(x.astype(jnp.float32), 0).astype(x.dtype), stacked)
    if rnd.wire.codec is not None and rnd.wire.mode == "legacy":
        mean, _ = compress_tree(rnd.wire.codec, mean, key_t)
    return mean


# --------------------------------------------------------------------------- #
# the reference executor
# --------------------------------------------------------------------------- #
class RoundExecutor:
    """Runs a ``RoundProgram`` one round at a time, per worker.

    ``run(t, params, state, batch, workers=..., views=...)`` executes one
    scheduled round over an arbitrary subset of workers (``workers``, the
    live membership — default all ``m``), optionally feeding each worker its
    own stale model view (``views``: worker -> params, the simulator's
    bounded-staleness replay).  Locals are evaluated under one jitted vmap
    when every worker shares the current model; divergent views fall back to
    per-worker calls of the same jitted local.

    Byte accounting: the round's wire bytes land in
    ``metrics["comm_bytes"]`` AND are booked via ``dist.collectives.note``
    (a no-op outside a ``CommLedger.wrap``), so wrapped replays record the
    identical number.
    """

    def __init__(self, prog: RoundProgram):
        self.prog = prog
        self._vmapped: Dict[Any, Callable] = {}
        self._single: Dict[Any, Callable] = {}

    # -- cached jitted pieces ------------------------------------------------ #
    # keyed by the Round OBJECT (identity hash, and a strong reference): the
    # historical ``id(rnd)`` keys let a dynamically built round alias a dead
    # round's id and silently run the wrong jitted local
    # (tests/test_rounds_equivalence.py pins the regression)
    def _vmapped_local(self, rnd: Round, replica_axis: Optional[int]):
        key = (rnd, replica_axis)
        fn = self._vmapped.get(key)
        if fn is None:
            fn = jax.jit(jax.vmap(rnd.local,
                                  in_axes=(None, 0, replica_axis, 0)))
            self._vmapped[key] = fn
        return fn

    def _single_local(self, rnd: Round):
        fn = self._single.get(rnd)
        if fn is None:
            fn = jax.jit(rnd.local)
            self._single[rnd] = fn
        return fn

    # -- one round ----------------------------------------------------------- #
    def run(self, t: int, params: Any, state: Any, batch: Any, *,
            workers: Optional[Sequence[int]] = None,
            views: Optional[Dict[int, Any]] = None,
            key=None) -> Tuple[Any, Any, Dict[str, Any]]:
        prog = self.prog
        step = prog.round_for(t, state)
        rnd, t_step = step.round, step.t_step
        if prog.prepare is not None:
            batch = prog.prepare(t, batch, key)
        tj = jnp.int32(t_step)
        cs = prog.client_sampling
        weights = None

        if cs is not None:
            # federated replay: the live cohort (sampled here unless the
            # caller already drew it), each client on its own identity-keyed
            # shard; the masked-average weights are the client dataset sizes
            from repro.core.federated import cohort_shards
            assert not rnd.replica, \
                "client-sampling rounds keep one server model, not replicas"
            assert views is None, \
                "client-sampling rounds are server-synchronous (no views)"
            ws = list(cs.cohort_for(t)) if workers is None else list(workers)
            assert ws, "a round needs at least one participating worker"
            w_arr = jnp.asarray(ws, jnp.uint32)
            shards_sel = cohort_shards(batch, ws, t, cs)
            payloads, aux = self._vmapped_local(rnd, None)(
                tj, w_arr, params, shards_sel)
            if rnd.collective == "masked_average":
                weights = cs.client_weights(ws)
        else:
            shards = split_shards(batch, prog.m)
            ws = list(range(prog.m)) if workers is None else list(workers)
            assert ws, "a round needs at least one participating worker"
            idx = jnp.asarray(ws, jnp.int32)
            w_arr = jnp.asarray(ws, jnp.uint32)
            shards_sel = _slice_tree(shards, idx)

            if rnd.replica:
                models = _slice_tree(state["replicas"], idx)
                payloads, aux = self._vmapped_local(rnd, 0)(
                    tj, w_arr, models, shards_sel)
            elif views is None:
                payloads, aux = self._vmapped_local(rnd, None)(
                    tj, w_arr, params, shards_sel)
            else:
                single = self._single_local(rnd)
                outs = [single(tj, jnp.uint32(w), views.get(w, params),
                               _slice_tree(shards, w)) for w in ws]
                payloads = _stack_trees([p for p, _ in outs])
                aux = jnp.stack([a for _, a in outs])

        one = _slice_tree(payloads, 0)
        nbytes = wire_nbytes(rnd, one, len(ws))
        reduced = reduce_payloads(rnd, payloads, ws,
                                  _wire_key(rnd.wire, key, t_step),
                                  weights=weights)
        if nbytes:
            coll.note(rnd.collective, None, nbytes=nbytes, tag=rnd.tag)
        if aux is not None:
            coll.note("pmean", jnp.zeros((), jnp.float32), tag="loss",
                      payload=False)

        params, state, metrics = rnd.apply(tj, params, state, reduced,
                                           w_arr, aux)
        if step.host_updates:
            state = {**state, **step.host_updates}
        metrics = dict(metrics)
        metrics.setdefault("order", rnd.order)
        metrics["comm_bytes"] = nbytes
        metrics["n_live"] = len(ws)
        return params, state, metrics


def to_method(prog: RoundProgram) -> Method:
    """Adapt a ``RoundProgram`` to the uniform ``Method`` interface: the
    step runs the scheduled round over all ``m`` workers through a
    ``RoundExecutor`` (the single-host reference execution)."""
    ex = RoundExecutor(prog)

    def step(t, params, state, batch, key=None):
        return ex.run(t, params, state, batch, key=key)

    return Method(prog.name, prog.init, step, prog.comm_scalars, prog.fevals,
                  prog.gevals, program=prog)


# --------------------------------------------------------------------------- #
# the HO-SGD family as a round program
# --------------------------------------------------------------------------- #
def fo_round(loss_fn: Callable, opt, *, wire: Optional[Wire] = None,
             overlap: Optional[Overlap] = None) -> Round:
    """Eq. (3): each worker's shard gradient, all-reduce mean, optimizer
    update.  The mesh lowering (``core.distributed.make_fo_step``) fuses the
    per-worker locals into one data-parallel ``value_and_grad`` whose
    gradient all-reduce GSPMD inserts — same math, booked identically.
    An ``overlap`` spec buckets the gradient all-reduce (chunked lowering on
    the mesh, exposed-comm pricing in the sim) without changing bytes."""
    from repro.opt.optimizers import apply_deltas

    wire = wire or Wire()

    def local(t, worker, model, shard):
        loss, grads = jax.value_and_grad(loss_fn)(model, shard)
        return grads, loss

    @jax.jit
    def _apply_j(t, params, opt_state, grads, f_mean):
        deltas, opt_state = opt.update(grads, opt_state, params, t)
        return apply_deltas(params, deltas), opt_state, f_mean

    def apply(t, params, state, reduced, workers, aux):
        params, opt_state, loss = _apply_j(t, params, state["opt"], reduced,
                                           jnp.mean(aux))
        return params, {**state, "opt": opt_state}, {"loss": loss}

    return Round("fo", 1, "all_reduce", local, apply, wire=wire,
                 meta={"loss_fn": loss_fn, "opt": opt},
                 overlap=overlap or Overlap())


def zo_round(loss_fn: Callable, ho, opt, *, m: Optional[int] = None,
             overlap: Optional[Overlap] = None) -> Round:
    """Eq. (4)-(6): each worker's directional-derivative scalar in its
    pre-shared direction, all-gathered; every receiver reconstructs the
    update from the coefficients of the workers that actually contributed
    (``workers`` — the live membership divides the estimate, not the nominal
    ``m``)."""
    from repro.core.engine import make_engine
    from repro.opt.optimizers import apply_deltas

    def local(t, worker, model, shard):
        eng = make_engine(ho.engine, model, ho.seed, acc_dtype=ho.acc_dtype)
        c, f0 = eng.zo_coeff(loss_fn, model, shard, t, worker, ho.mu)
        return c, f0

    @jax.jit
    def _apply_j(t, params, opt_state, coeffs, workers, f0s):
        eng = make_engine(ho.engine, params, ho.seed, acc_dtype=ho.acc_dtype)
        k = int(coeffs.shape[0])
        rec = eng.reconstruct(coeffs, t, workers)
        g_hat = jax.tree.map(lambda a: a * (ho.zo_scale / k), rec)
        deltas, opt_state = opt.update(g_hat, opt_state, params, t)
        return apply_deltas(params, deltas), opt_state, jnp.mean(f0s)

    def apply(t, params, state, reduced, workers, aux):
        params, opt_state, loss = _apply_j(t, params, state["opt"], reduced,
                                           workers, aux)
        return params, {**state, "opt": opt_state}, {"loss": loss}

    return Round("zo", 0, "all_gather", local, apply,
                 meta={"loss_fn": loss_fn, "ho": ho, "opt": opt, "m": m},
                 overlap=overlap or Overlap())


def ho_sgd_program(
    loss_fn: Callable,
    ho,
    opt=None,
    *,
    name: str = "ho_sgd",
    wire: Optional[Wire] = None,
    tau_schedule: Optional[Callable[[int], int]] = None,
    zo_only: bool = False,
    overlap: Optional[Overlap] = None,
    client_sampling: Any = None,
) -> RoundProgram:
    """HO-SGD (Algorithm 1) as a round program: FO sync rounds every tau
    iterations (or per ``tau_schedule`` through the shared
    ``adaptive_tau_decision``), ZO rounds in between; ``zo_only`` never
    syncs (distributed ZO-SGD).  State is ``{"opt": ..., "since_fo": int}``
    — the same layout the simulator checkpoints.  ``overlap`` buckets both
    round kinds' collectives (time only, never bytes).

    ``client_sampling`` (``core.federated.ClientSampling``, cohort_k must
    equal ``ho.m``) makes the program federated: every round runs over a
    freshly sampled client cohort on identity-keyed shards.  The ZO
    direction streams survive sampling unchanged — they were always keyed
    on worker IDENTITY, so client 812's direction at round t does not
    depend on who else was sampled."""
    from repro.core.ho_sgd import adaptive_tau_decision
    from repro.opt.optimizers import const_schedule, sgd

    opt = opt or sgd(const_schedule(ho.lr), ho.momentum)
    fo = fo_round(loss_fn, opt, wire=wire, overlap=overlap)
    zo = zo_round(loss_fn, ho, opt, m=ho.m, overlap=overlap)

    def init(params):
        return {"opt": opt.init(params), "since_fo": 0}

    def round_for(t: int, state) -> RoundStep:
        if zo_only:
            return RoundStep(zo, t, {"since_fo": int(state["since_fo"]) + 1})
        if tau_schedule is not None:
            is_fo, t_step, since = adaptive_tau_decision(
                t, int(state["since_fo"]), tau_schedule(t), ho.tau)
            return RoundStep(fo if is_fo else zo, t_step, {"since_fo": since})
        is_fo = t % ho.tau == 0
        since = 0 if is_fo else int(state["since_fo"]) + 1
        return RoundStep(fo if is_fo else zo, t, {"since_fo": since})

    tau = max(1, ho.tau)
    return RoundProgram(
        name, ho.m, init, round_for,
        comm_scalars=lambda d: (d + (tau - 1)) / tau,
        fevals=lambda d: 2.0 * (tau - 1) / tau,
        gevals=lambda d: 1.0 / tau,
        client_sampling=client_sampling,
    )
