"""repro.obs: span tracing, Perfetto export, trace-derived attribution.

Pins (ISSUE 8):
  (a) the legacy (time, kind, worker) tuple trace is BIT-IDENTICAL to the
      pre-obs event loop (golden fixture tests/golden/pre_pr8_traces.json,
      captured before the span refactor) — the tuple view is derived from
      the committed spans, so the determinism contract now pins the span
      path too;
  (b) the Perfetto export is deterministic: same spec seed => byte-identical
      JSON artifact; a different seed changes it;
  (c) span invariants: kinds from the fixed taxonomy, no negative durations,
      per-worker compute spans never overlap, every src_kind-bearing span
      round-trips into exactly the tuple trace;
  (d) trace-derived attribution equals the costs.exposed_comm_time closed
      forms within 1e-9, across collective kinds x overlap buckets;
  (e) TTFT decomposes exactly: ttft == queue_s + service_s per request, in
      both the continuous replay and the seed-sync baseline;
  (f) CSVLogger rejects unknown keys (no silent drop);
  (g) launch.hlo.async_overlap_stats counts the ops scheduled between async
      collective start/done pairs.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.metrics import CSVLogger
from repro.obs import (
    KINDS,
    Span,
    Tracer,
    attribution,
    attribution_from_file,
    dumps,
    format_report,
    load_trace_events,
    slot_lane,
    spans_from_events,
    trace_events,
    validate_trace_events,
    worker_lane,
    write_trace,
)
from repro.launch import hlo
from repro.sim import (
    ClusterSpec,
    Topology,
    compute_model_for,
    make_sim_methods,
    simulate,
)
from repro.sim.costs import exposed_comm_time

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "pre_pr8_traces.json")

QUAD_D, QUAD_M = 48, 4
N_ITERS, TAU = 10, 4


def quad_loss(params, batch):
    return 0.5 * jnp.mean(jnp.sum((params["x"] - batch["t"]) ** 2, -1))


QUAD_PARAMS = {"x": jnp.zeros((QUAD_D,), jnp.float32)}
QUAD_BATCH = {"t": jnp.ones((2 * QUAD_M, QUAD_D), jnp.float32)}


def _batches():
    while True:
        yield QUAD_BATCH


def run_sim(spec, which="ho_sgd", overlap=1, n_iters=N_ITERS):
    sm = make_sim_methods(quad_loss, QUAD_PARAMS, spec, tau=TAU, lr=0.1,
                          zo_lr=0.05, which=[which],
                          overlap_buckets=overlap)[which]
    compute = compute_model_for(QUAD_PARAMS, spec, 2)
    return simulate(sm, QUAD_PARAMS, _batches(), spec, n_iters,
                    compute=compute)


BASE = ClusterSpec(m=QUAD_M, flops_per_sec=1e9, alpha=1e-5, bandwidth=1e6,
                   straggler_prob=0.3, straggler_slowdown=4.0,
                   jitter_sigma=0.1, seed=1234)

GOLDEN_SPECS = {
    "sync_b1": (BASE, 1),
    "sync_b4": (BASE, 4),
    "async2_b1": (BASE.with_(max_staleness=2), 1),
    "ring2pod_b4": (BASE.with_(collective="ring",
                               topology=Topology(pods=2, inter_alpha=1e-4,
                                                 inter_bandwidth=2.5e5)), 4),
    "elastic_b1": (BASE.with_(elastic=True, fail_rate=5000.0, downtime=5e-5,
                              restart_time=1e-5), 1),
}

_cache = {}


def cached_run(name):
    if name not in _cache:
        spec, ov = GOLDEN_SPECS[name]
        _cache[name] = run_sim(spec, overlap=ov)
    return _cache[name]


# --------------------------------------------------------------------------- #
# (a) the tuple trace is a derived view, bit-identical to the pre-obs loop
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", sorted(GOLDEN_SPECS))
def test_tuple_trace_unchanged_vs_pre_pr8(name):
    with open(GOLDEN) as f:
        golden = json.load(f)
    res = cached_run(name)
    assert [[t, k, w] for (t, k, w) in res.trace] == golden[name]


def test_trace_is_derived_from_spans():
    res = cached_run("async2_b1")
    derived = [(s.t1, s.src_kind, s.worker) for s in res.spans
               if s.src_kind is not None]
    assert derived == res.trace
    # annotation spans exist (queue waits / barrier waits / overlap detail)
    # but never enter the tuple view
    assert len(res.spans) > len(res.trace)


# --------------------------------------------------------------------------- #
# (b) deterministic export: same seed => byte-identical artifact
# --------------------------------------------------------------------------- #
def test_export_byte_identical_per_seed(tmp_path):
    a = run_sim(GOLDEN_SPECS["sync_b4"][0], overlap=4)
    b = run_sim(GOLDEN_SPECS["sync_b4"][0], overlap=4)
    sa, sb = dumps(a.spans), dumps(b.spans)
    assert sa == sb
    pa = write_trace(str(tmp_path / "a.json"), a.spans, title="t")
    pb = write_trace(str(tmp_path / "b.json"), b.spans, title="t")
    assert open(pa, "rb").read() == open(pb, "rb").read()


def test_export_differs_across_seeds():
    a = run_sim(BASE, overlap=1, n_iters=4)
    b = run_sim(BASE.with_(seed=99), overlap=1, n_iters=4)
    assert dumps(a.spans) != dumps(b.spans)


def test_trace_event_schema():
    res = cached_run("sync_b1")
    events = trace_events(res.spans, title="quad")
    validate_trace_events(events)
    # one process_name + one thread_name per lane, lanes in first-appearance
    # order; every X event lands on a declared lane
    meta = [e for e in events if e["ph"] == "M"]
    lanes = [e["args"]["name"] for e in meta if e["name"] == "thread_name"]
    assert lanes[0] in (worker_lane(0), "cluster") or lanes[0].startswith("worker/")
    tids = {e["tid"] for e in events if e["ph"] == "X"}
    assert tids <= set(range(len(lanes)))


# --------------------------------------------------------------------------- #
# (c) span invariants
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", sorted(GOLDEN_SPECS))
def test_span_invariants(name):
    res = cached_run(name)
    per_worker = {}
    for s in res.spans:
        assert s.kind in KINDS
        assert s.t1 >= s.t0 - 1e-12
        if s.kind == "compute" and s.worker >= 0:
            per_worker.setdefault(s.worker, []).append((s.t0, s.t1))
            assert s.lane == worker_lane(s.worker)
    # a worker computes one thing at a time: compute spans on one lane are
    # disjoint (touching endpoints allowed)
    for w, iv in per_worker.items():
        iv.sort()
        for (a0, a1), (b0, b1) in zip(iv, iv[1:]):
            assert b0 >= a1 - 1e-9, (w, (a0, a1), (b0, b1))


def test_async_round_emits_queue_and_comm_annotations():
    spec = BASE.with_(max_staleness=2, topology=Topology(
        pods=2, inter_alpha=1e-4, inter_bandwidth=2.5e5))
    res = run_sim(spec, overlap=1)
    kinds = {s.kind for s in res.spans}
    assert "comm.exposed" in kinds
    assert "queue.contention" in kinds  # shared-link waits made visible


# --------------------------------------------------------------------------- #
# (d) attribution: trace == closed form, across collectives x buckets
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("collective", ["flat", "ring", "tree"])
@pytest.mark.parametrize("buckets", [1, 4])
def test_attribution_matches_closed_form(collective, buckets):
    spec = ClusterSpec(m=QUAD_M, flops_per_sec=1e9, alpha=1e-6,
                       bandwidth=5e7, collective=collective, seed=7)
    res = run_sim(spec, overlap=buckets)
    att = attribution(res.spans)
    compute = compute_model_for(QUAD_PARAMS, spec, 2)
    cm = spec.collective_model
    closed = 0.0
    for order, nb in zip(res.orders, res.comm_bytes):
        dt = compute.time(2.0, 0.0) if order == 0 else compute.time(0.0, 1.0)
        closed += exposed_comm_time(cm, nb, spec.m, buckets, dt)
    assert abs(att["kind_seconds"]["comm.exposed"] - closed) <= 1e-9
    assert abs(closed - res.comm_s) <= 1e-9          # and the runner agrees
    # no stragglers/jitter: makespan is the last commit time exactly
    assert abs(att["makespan_s"] - res.sim_seconds) <= 1e-9
    assert att["kind_bytes"]["comm.exposed"] == res.bytes_total


def test_attribution_roundtrips_through_file(tmp_path):
    res = cached_run("ring2pod_b4")
    path = write_trace(str(tmp_path / "t.json"), res.spans, title="rt")
    att_file = attribution_from_file(path)
    att_live = attribution(res.spans)
    assert att_file["n_spans"] == att_live["n_spans"]
    assert att_file["kind_bytes"] == att_live["kind_bytes"]
    for k in KINDS:
        assert att_file["kind_seconds"][k] == pytest.approx(
            att_live["kind_seconds"][k], abs=1e-12)
    # durations survive the µs round-trip exactly (export stores dur, the
    # reader reconstructs t1 = t0 + dur/1e6)
    back = spans_from_events(load_trace_events(path))
    assert len(back) == sum(1 for _ in res.spans)
    for orig, rt in zip(res.spans, back):
        assert rt.duration == pytest.approx(orig.duration, abs=1e-15)
        assert rt.kind == orig.kind and rt.lane == orig.lane
    lines = format_report(att_file, title="rt")
    assert any("exposed_comm_fraction" in ln for ln in lines)


# --------------------------------------------------------------------------- #
# span invariants
# --------------------------------------------------------------------------- #
def test_sim_tracer_rejects_wall_api():
    with pytest.raises(AssertionError):
        Span("not-a-kind", "lane", 0.0, 1.0)
    with pytest.raises(AssertionError):
        Span("compute", "lane", 1.0, 0.5)


# --------------------------------------------------------------------------- #
# (e) TTFT decomposition (queue_s + service_s)
# --------------------------------------------------------------------------- #
def _serving_stack():
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import Engine, ServeConfig
    cfg = get_config("qwen3-14b").reduced().with_(remat=False)
    params = T.init_model(jax.random.key(0), cfg)
    return cfg, params, Engine, ServeConfig


def test_ttft_decomposition_and_traffic_spans():
    from repro.sim.traffic import TrafficSpec, replay, serve_compute_model
    cfg, params, Engine, ServeConfig = _serving_stack()
    spec = TrafficSpec(rate=400.0, n_requests=10, prompt_lens=(4, 9),
                       out_lens=(3, 6), seed=3)
    cm = serve_compute_model(cfg, flops_per_sec=1e9)
    tracer = Tracer()
    eng = Engine(cfg, params, ServeConfig(max_seq=spec.required_max_seq(),
                                          slots=2))
    res = replay(eng, spec, cm, tracer=tracer)
    for r in res.rows:
        assert r["queue_s"] >= 0.0 and r["service_s"] > 0.0
        assert r["ttft"] == pytest.approx(r["queue_s"] + r["service_s"],
                                          abs=1e-12)
    for k in ("p50_queue_s", "p99_queue_s", "p50_service_s", "p99_service_s"):
        assert k in res.summary
    # tracing is an observer: an untraced replay is bit-identical
    eng2 = Engine(cfg, params, ServeConfig(max_seq=spec.required_max_seq(),
                                           slots=2))
    res2 = replay(eng2, spec, cm)
    assert res2.events == res.events
    assert res2.rows == res.rows
    assert res2.summary == res.summary
    # per-request lifecycle spans on slot lanes: one prefill per request,
    # prefill duration == service_s, queue span == queue_s
    prefills = [s for s in tracer.spans if s.kind == "prefill"]
    assert len(prefills) == spec.n_requests
    by_rid = {int(s.name.split("/r")[1]): s
              for s in tracer.spans if s.kind == "queue.contention"}
    for r in res.rows:
        q = by_rid[r["rid"]]
        assert q.duration == pytest.approx(r["queue_s"], abs=1e-12)
        assert q.lane.startswith("slot/")
    assert any(s.kind == "decode" for s in tracer.spans)
    validate_trace_events(trace_events(tracer.spans, tracer.counters))


def test_seed_sync_ttft_decomposition():
    from repro.sim.traffic import (TrafficSpec, replay_seed_sync,
                                   serve_compute_model)
    from repro.configs import get_config
    cfg = get_config("qwen3-14b").reduced()
    spec = TrafficSpec(rate=200.0, n_requests=9, prompt_lens=(4, 8),
                       out_lens=(3, 5), seed=11)
    res = replay_seed_sync(spec, serve_compute_model(cfg, 1e9), batch=4)
    for r in res.rows:
        assert r["ttft"] == pytest.approx(r["queue_s"] + r["service_s"],
                                          abs=1e-12)
    assert "p99_queue_s" in res.summary


# --------------------------------------------------------------------------- #
# (f) CSVLogger: unknown keys raise instead of silently dropping
# --------------------------------------------------------------------------- #
def test_csvlogger_unknown_key_raises(tmp_path):
    path = str(tmp_path / "log.csv")
    with CSVLogger(path, ["a", "b"]) as log:
        log.log(a=1, b=2)
        with pytest.raises(ValueError, match="unknown keys"):
            log.log(a=1, typo=3)
    # validation applies to the disabled logger too (path=None)
    nolog = CSVLogger(None, ["a"])
    nolog.log(a=1)
    with pytest.raises(ValueError, match="unknown keys"):
        nolog.log(zz=1)


# --------------------------------------------------------------------------- #
# (g) HLO async-overlap stats
# --------------------------------------------------------------------------- #
SYNTH_HLO = """\
ENTRY %main {
  %p0 = f32[128]{0} parameter(0)
  %ar-start = f32[128]{0} all-reduce-start(%p0), replica_groups={{0,1}}
  %m0 = f32[128]{0} multiply(%p0, %p0)
  %m1 = f32[128]{0} add(%m0, %p0)
  %ar-done = f32[128]{0} all-reduce-done(%ar-start)
  %ag-start = f32[256]{0} all-gather-start(%m1), replica_groups={{0,1}}
  %ag-done = f32[256]{0} all-gather-done(%ag-start)
  ROOT %out = f32[128]{0} add(%ar-done, %m1)
}
"""


def test_async_overlap_stats_counts_gaps():
    st = hlo.async_overlap_stats(SYNTH_HLO)
    assert st["pairs"] == 2
    assert st["by_kind"] == {"all-reduce": 1, "all-gather": 1}
    # two ops (%m0, %m1) between ar-start/done; zero between ag pair
    assert st["overlapped_pairs"] == 1
    assert st["max_gap"] == 2
    assert st["mean_gap"] == pytest.approx(1.0)


def test_async_overlap_stats_empty_on_sync_hlo():
    st = hlo.async_overlap_stats("""\
ENTRY %main {
  %p0 = f32[8]{0} parameter(0)
  %ar = f32[8]{0} all-reduce(%p0), replica_groups={{0,1}}
  ROOT %r = f32[8]{0} add(%ar, %p0)
}
""")
    assert st["pairs"] == 0 and st["overlapped_pairs"] == 0
    assert st["mean_gap"] == 0.0 and st["max_gap"] == 0


# --------------------------------------------------------------------------- #
# slot lanes helper
# --------------------------------------------------------------------------- #
def test_lane_helpers():
    assert worker_lane(3) == "worker/3"
    assert worker_lane(-1) == "cluster"
    assert slot_lane(2) == "slot/2"
    assert slot_lane(-1) == "slot/prefill-only"
