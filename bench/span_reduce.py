"""Reduction of a profile of the training loop to its program spans and
device scopes.

    python3 bench/span_reduce.py DIR

reads what ``python -m repro.launch.train ... --profile DIR`` writes: a JAX
profiler trace (``.xplane.pb``) and the step programs' op scopes
(``DIR/op_scopes.json``: ``{program: {instruction: op_name}}``, as
``Trainer.op_scopes`` gives them).  ``reduce`` takes the ``ProfileData`` and
the op scopes, with ``xplane_reduce``'s helpers, and returns:

* the window: the host span ``bench.window`` where the benchmark placed one,
  else from the start of the first ``train.step`` to the end of the last;
* ``programs``: per step program on the first device, its executions in the
  window, its op seconds by part of the step and by model layer
  (``repro.obs.scopes.classify``; ``none`` where no scope is), the share of
  its op seconds that some known scope covers (``scoped_share``), and the
  ops without one that took most time, by name and opcode;
* ``idle``: the first device's idle seconds in the window by the innermost
  ``train.*`` or ``bench.*`` host span over them (``none`` where no such
  span is), and the longest gaps, each with the span over most of it;
* ``spans``: count and mean seconds of each ``train.*`` span in the window;
* ``per_step``: the per-step device seconds of the FO and ZO steps' parts
  and the mean ``train.data`` seconds.

Op seconds leave out container ops (``while``, ``call``, ...), whose events
span their bodies' ops, as ``xplane_reduce`` does.
"""
from __future__ import annotations

import bisect
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import xplane_reduce as X  # noqa: E402
from repro.obs import scopes as S  # noqa: E402

HOST_SPAN = re.compile(r"^(train|bench)\.")
DIRECTION = ("zo.norm", "zo.perturb", "zo.reconstruct", "zo.update")
TOP_N = 10


def _host_spans(pd) -> List[Tuple[int, int, str]]:
    """(start, end, name) of the ``train.*`` and ``bench.*`` host spans."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                         e.name) for e in line.events
                        if HOST_SPAN.match(e.name)]
    return sorted(out)


def segments(spans: Sequence[Tuple[int, int, str]], lo: int, hi: int
             ) -> List[Tuple[int, int, str]]:
    """[lo, hi) cut where a span starts or ends; each piece named by the
    innermost (shortest) span over it, ``none`` where no span is."""
    cuts = sorted({lo, hi} | {x for a, b, _ in spans for x in (a, b)
                              if lo < x < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [(e - s, n) for s, e, n in spans if s <= a and b <= e]
        out.append((a, b, min(over)[1] if over else "none"))
    return out


def attribute(gaps: Sequence[Tuple[int, int]],
              segs: Sequence[Tuple[int, int, str]]) -> List[Dict[str, int]]:
    """Per gap, its nanoseconds by the name of the segments over them
    (both lists sorted, neither overlapping itself)."""
    out, j = [], 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        by: Dict[str, int] = defaultdict(int)
        k = j
        while k < len(segs) and segs[k][0] < b:
            s, e, n = segs[k]
            by[n] += min(b, e) - max(a, s)
            k += 1
        out.append(dict(by))
    return out


def _device0(pd):
    for plane in pd.planes:
        m = X.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == 0:
            return plane
    return None


def _programs(plane, op_scopes: Dict[str, Dict[str, str]], lo: int, hi: int
              ) -> Dict[str, Dict]:
    mods = sorted(((X.module_name(n), a, b) for n, a, b in
                   X._events(plane, "XLA Modules") if lo <= a < hi),
                  key=lambda x: x[1])
    starts = [a for _, a, _ in mods]
    out: Dict[str, Dict] = {}
    for prog in op_scopes:
        out[prog] = {"executions": sum(1 for n, _, _ in mods if n == prog),
                     "op_s": 0.0, "scoped_share": None,
                     "by_part": defaultdict(float),
                     "by_layer": defaultdict(float),
                     "unscoped": defaultdict(float)}
    classes: Dict[Tuple[str, str], Tuple] = {}
    for name, opcode, a, b in X._ops(plane):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or a >= mods[i][2] or mods[i][0] not in out:
            continue
        prog = mods[i][0]
        key = (prog, name)
        if key not in classes:
            op_name = op_scopes[prog].get(name)
            classes[key] = (S.classify(op_name) if op_name
                            else (None, None))
        part, layer = classes[key]
        s = (b - a) * 1e-9
        p = out[prog]
        p["op_s"] += s
        p["by_part"][part or "none"] += s
        p["by_layer"][layer or "none"] += s
        if part is None and layer is None:
            p["unscoped"][f"{name} ({opcode})"] += s
    for p in out.values():
        if p["op_s"] > 0:
            p["scoped_share"] = 1.0 - sum(p["unscoped"].values()) / p["op_s"]
        p["by_part"] = dict(p["by_part"])
        p["by_layer"] = dict(p["by_layer"])
        p["unscoped_top"] = [[n, s] for n, s in sorted(
            p.pop("unscoped").items(), key=lambda kv: -kv[1])[:TOP_N]]
    return out


def per_step(programs: Dict[str, Dict], spans: Dict[str, Dict]
             ) -> Dict[str, float]:
    """Device seconds per execution of each step part, and the mean
    ``train.data`` seconds; a number whose program or span is not in the
    window is left out."""
    out: Dict[str, float] = {}
    fo, zo = programs.get("jit_fo_step"), programs.get("jit_zo_step")
    if zo and zo["executions"]:
        p, n = zo["by_part"], zo["executions"]
        out["zo_step.forward_device_s"] = p.get("zo.forward", 0.0) / n
        out["zo_step.direction_device_s"] = sum(
            p.get(k, 0.0) for k in DIRECTION) / n
        out["zo_step.exchange_device_s"] = p.get("zo.exchange", 0.0) / n
    if fo and fo["executions"]:
        p, n = fo["by_part"], fo["executions"]
        for phase in ("forward", "backward", "recompute"):
            out[f"fo_step.{phase}_device_s"] = p.get(f"fo.grad.{phase}",
                                                     0.0) / n
        out["fo_step.update_device_s"] = (p.get("fo.accumulate", 0.0)
                                          + p.get("fo.update", 0.0)) / n
    if "train.data" in spans:
        out["trainer.data_s"] = spans["train.data"]["mean_s"]
    return out


def reduce(pd, op_scopes: Dict[str, Dict[str, str]]) -> Dict:
    host = _host_spans(pd)
    windows = [(a, b) for a, b, n in host if n == "bench.window"]
    steps = [(a, b) for a, b, n in host if n == "train.step"]
    if windows:
        lo, hi = windows[0]
    elif steps:
        lo, hi = steps[0][0], max(b for _, b in steps)
    else:
        raise ValueError("the trace holds neither bench.window nor "
                         "train.step")
    inside = [(a, b, n) for a, b, n in host if a < hi and b > lo]
    spans: Dict[str, Dict] = {}
    for a, b, n in inside:
        if n.startswith("train.") and lo <= a < hi:
            s = spans.setdefault(n, {"count": 0, "total_s": 0.0})
            s["count"] += 1
            s["total_s"] += (b - a) * 1e-9
    for s in spans.values():
        s["mean_s"] = s["total_s"] / s["count"]
    out: Dict = {"window_s": (hi - lo) * 1e-9, "spans": spans,
                 "programs": {}, "idle": None}
    plane = _device0(pd)
    if plane is not None:
        out["programs"] = _programs(plane, op_scopes, lo, hi)
        busy = X.merge(X.clip([(a, b) for _, _, a, b in X._ops(plane)],
                              lo, hi))
        gaps = X.gaps(busy, lo, hi)
        by_gap = attribute(gaps, segments(inside, lo, hi))
        total: Dict[str, float] = defaultdict(float)
        for by in by_gap:
            for n, ns in by.items():
                total[n] += ns * 1e-9
        longest = sorted(zip(gaps, by_gap), key=lambda g: g[0][0] - g[0][1])
        out["idle"] = {
            "seconds": sum(total.values()),
            "by_span": dict(total),
            "gaps": [[max(by, key=by.get), (b - a) * 1e-9]
                     for (a, b), by in longest[:TOP_N]],
            "gaps_over_1ms_outside_spans": sum(
                1 for (a, b), by in zip(gaps, by_gap)
                if b - a > 1_000_000 and by.get("none", 0) > 0)}
    out["per_step"] = per_step(out["programs"], spans)
    return out


def load_op_scopes(trace_dir: str) -> Dict[str, Dict[str, str]]:
    with open(os.path.join(trace_dir, "op_scopes.json")) as f:
        return json.load(f)


def reduce_dir(trace_dir: str,
               op_scopes: Optional[Dict[str, Dict[str, str]]] = None) -> Dict:
    from jax.profiler import ProfileData
    if op_scopes is None:
        op_scopes = load_op_scopes(trace_dir)
    return reduce(ProfileData.from_file(X.find_xplane(trace_dir)), op_scopes)


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1]), indent=1))
