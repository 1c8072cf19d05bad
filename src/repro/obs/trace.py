"""Span tracing for the simulator and the serving replay.

A ``Span`` is a half-open interval ``[t0, t1]`` on a *lane* (one lane per
simulated worker, pod link or serving slot) with a ``kind`` drawn from the
fixed taxonomy below and an optional byte payload (``nbytes`` — always
ledger-measured, never re-derived).

Every span's ``t0``/``t1`` is supplied by the caller (the discrete-event
loop, the traffic replay).  Nothing here reads a wall clock, so same spec
seed ⇒ identical spans ⇒ byte-identical Perfetto export
(``repro.obs.export``).  The training path's spans are ``jax.profiler``
annotations instead, on the device trace's clock (``launch.train.run``).

The tracer is bookkeeping-free by design: consumers derive timelines
(``export``) and attribution (``report``) from the SAME spans — there is
never a second accounting path that could drift from what the pricing or
the ledger recorded.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

#: the span taxonomy — every span's ``kind`` is one of these
KINDS = (
    "compute",          # local FLOPs (oracle calls, prefill/decode math)
    "comm.exposed",     # collective time on the critical path
    "comm.overlapped",  # collective time hidden behind compute (buckets)
    "queue.contention", # waiting on a shared link / admission queue
    "barrier",          # waiting on slower participants (+ round markers)
    "checkpoint",       # save/restore round-trips, failure recovery
    "prefill",          # serving: admission prefill on a slot
    "decode",           # serving: decode occupancy of a slot
)


def worker_lane(worker: int) -> str:
    """Canonical lane name for a simulated worker (-1 = cluster-wide)."""
    return f"worker/{worker}" if worker >= 0 else "cluster"


def slot_lane(slot: int) -> str:
    """Canonical lane name for a serving slot (-1 = retired at prefill)."""
    return f"slot/{slot}" if slot >= 0 else "slot/prefill-only"


@dataclass
class Span:
    """One traced interval.  ``src_kind`` carries the legacy event-tuple
    kind for spans that ARE committed events of the sim's event loop — the
    ``(time, kind, worker)`` determinism trace is derived from exactly
    those spans (``src_kind is None`` marks annotation-only spans that add
    timeline detail without entering the tuple view)."""

    kind: str
    lane: str
    t0: float
    t1: float
    name: str = ""
    nbytes: int = 0
    worker: int = -1
    src_kind: Optional[str] = None

    def __post_init__(self):
        assert self.kind in KINDS, \
            f"unknown span kind {self.kind!r}; have {KINDS}"
        assert self.t1 >= self.t0 - 1e-12, \
            f"span ends before it starts: [{self.t0}, {self.t1}]"

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


#: a counter sample: (t, lane, name, value) — e.g. cumulative ledger bytes
CounterSample = Tuple[float, str, str, float]


class Tracer:
    """Collects spans and counter samples at caller-supplied times."""

    def __init__(self):
        self.spans: List[Span] = []
        self.counters: List[CounterSample] = []

    # ------------------------------------------------------------------ #
    def add(self, kind: str, lane: str, t0: float, t1: float, *,
            name: str = "", nbytes: int = 0, worker: int = -1,
            src_kind: Optional[str] = None) -> int:
        """Record a completed span; returns its index."""
        self.spans.append(Span(kind, lane, float(t0), float(t1), name=name,
                               nbytes=int(nbytes), worker=worker,
                               src_kind=src_kind))
        return len(self.spans) - 1

    def counter(self, t: float, lane: str, name: str, value: float) -> None:
        self.counters.append((float(t), lane, name, float(value)))

    # ------------------------------------------------------------------ #
    def lanes(self) -> List[str]:
        """Lane names in deterministic first-appearance order."""
        seen: List[str] = []
        for s in self.spans:
            if s.lane not in seen:
                seen.append(s.lane)
        for _, lane, _, _ in self.counters:
            if lane not in seen:
                seen.append(lane)
        return seen

    def extend(self, spans: List[Span],
               counters: Optional[List[CounterSample]] = None) -> None:
        """Adopt pre-built spans (e.g. ``SimResult.spans``) wholesale."""
        self.spans.extend(spans)
        if counters:
            self.counters.extend(counters)
