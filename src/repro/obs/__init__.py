"""repro.obs — tracing: the real path on the profiler's clock, the simulator's
replay on its own.

The training path (README §repro.obs):

  * host spans are ``jax.profiler`` annotations in ``launch.train.run``
    (``train.step`` over ``train.data``, ``train.dispatch``,
    ``train.block``, ``train.log``; ``train.checkpoint``), on the same clock
    as the device ops of a profiler trace;
  * device parts are ``jax.named_scope``s in the step programs; ``scopes``
    maps a compiled program's instructions to them (``launch.train
    --profile DIR`` writes both).

The simulator and the serving replay (``repro.sim``, ``launch.serve
--trace``):

  * ``trace``  — ``Span``s on per-worker/link/slot lanes at caller-supplied
    (simulated) times, kind taxonomy ``compute | comm.exposed |
    comm.overlapped | queue.contention | barrier | checkpoint | prefill |
    decode``, byte counters.
  * ``export`` — Chrome/Perfetto ``trace_event`` JSON, deterministically
    serialized (same spec seed ⇒ byte-identical artifact) and
    round-trippable (``spans_from_events``).
  * ``report`` — per-kind/per-lane time + byte attribution with the
    exposed-comm / queue-wait headline fractions, computable from the
    exported JSON alone.

Those spans are derived from the same events the pricing uses (the sim's
event loop, the traffic replay's clock, the CommLedger's bytes) — never a
second bookkeeping path.
"""
from repro.obs.export import (  # noqa: F401
    dumps,
    load_trace_events,
    spans_from_events,
    trace_events,
    validate_trace_events,
    write_trace,
)
from repro.obs.report import (  # noqa: F401
    attribution,
    attribution_from_file,
    format_report,
)
from repro.obs.trace import (  # noqa: F401
    KINDS,
    Span,
    Tracer,
    slot_lane,
    worker_lane,
)
