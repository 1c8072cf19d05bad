"""Reduction of a JAX profiler trace (``.xplane.pb``) to device timings.

Reads, with nothing but JAX's ``ProfileData``:

* the traced window: the host span ``bench.window`` (a
  ``jax.profiler.TraceAnnotation`` the harness places around the window's
  ``run``), else the first to the last device event;
* per device plane (``/device:TPU:<n>``), the ``XLA Ops`` events inside the
  window: busy time is the union of their intervals;
* on the first device, the ``XLA Modules`` events (one per execution of a
  compiled program, named after the jitted function), the time of
  collective ops (all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute) during which no other op ran (exposed), the ops that
  took most time, and the longest idle gaps, each labelled by the host span
  it falls in (``bench.on_step``: the trainer's step callback; else
  ``in run``: inside the trainer's loop) and by the program that ran next.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[int, int]
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# an op event's name is its HLO text: "%name = <shape> opcode(operands), ..."
OPCODE = re.compile(r"[\]})]\s([a-z][\w\-]*)\(")
# ops whose events span the ops of their bodies
CONTAINERS = ("while", "conditional", "call")
TOP_N = 10


# --------------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------------- #
def merge(iv: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(iv: Sequence[Interval]) -> int:
    return sum(b - a for a, b in iv)


def clip(iv: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def intersect(x: Sequence[Interval], y: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def exposed(ops: Sequence[Tuple[str, int, int]]) -> int:
    """Nanoseconds of collective ops (by opcode) during which no other op
    runs."""
    coll = merge([(a, b) for n, a, b in ops if COLLECTIVE.search(n)])
    other = merge([(a, b) for n, a, b in ops if not COLLECTIVE.search(n)])
    return total(coll) - total(intersect(coll, other))


# --------------------------------------------------------------------------- #
# the trace
# --------------------------------------------------------------------------- #
def module_name(name: str) -> str:
    """'jit_fo_step(1234)' -> 'jit_fo_step'."""
    return re.sub(r"\(\d+\)$", "", name)


def op_label(text: str) -> Tuple[str, str]:
    """(name, opcode) of an op event: '%fusion.3 = f32[8]{0} fusion(...)'
    -> ('fusion.3', 'fusion')."""
    head = text.split(" = ", 1)
    name = head[0].lstrip("%")
    m = OPCODE.search(head[1]) if len(head) > 1 else None
    return name, (m.group(1) if m else name)


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events]
    return []


def _ops(plane) -> List[Tuple[str, str, int, int]]:
    """(name, opcode, start, end) of the device's ops, without containers."""
    out = []
    for text, a, b in _events(plane, "XLA Ops"):
        name, opcode = op_label(text)
        if opcode not in CONTAINERS:
            out.append((name, opcode, a, b))
    return out


def _host_spans(pd, name: str) -> List[Interval]:
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events if e.name == name]
    return sorted(out)


def _inside(spans: Sequence[Interval], t: int) -> bool:
    return any(a <= t < b for a, b in spans)


def reduce(pd, chips: int) -> Dict:
    devices = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            devices.append((int(m.group(1)), plane))
    devices.sort(key=lambda x: x[0])
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    windows = _host_spans(pd, "bench.window")
    ops_by_dev = [_ops(p) for _, p in devices]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(a for ops in ops_by_dev for _, _, a, _ in ops)
        hi = max(b for ops in ops_by_dev for _, _, _, b in ops)
    busy_by_dev = [merge(clip([(a, b) for _, _, a, b in ops], lo, hi))
                   for ops in ops_by_dev]
    ops0 = [(n, op, max(a, lo), min(b, hi)) for n, op, a, b in ops_by_dev[0]
            if b > lo and a < hi]
    modules: Dict[str, List[float]] = defaultdict(list)
    mod_events = sorted(((module_name(n), a, b) for n, a, b in
                         _events(devices[0][1], "XLA Modules") if lo <= a < hi),
                        key=lambda x: x[1])
    for n, a, b in mod_events:
        modules[n].append((b - a) * 1e-9)
    starts = [a for _, a, _ in mod_events]
    per_op: Dict[str, int] = defaultdict(int)
    for n, op, a, b in ops0:
        i = bisect.bisect_right(starts, a) - 1
        prog = mod_events[i][0] if i >= 0 and a < mod_events[i][2] else "?"
        per_op[f"{prog}/{n} ({op})"] += b - a
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP_N]
    on_step = _host_spans(pd, "bench.on_step")
    idle = sorted(gaps(busy_by_dev[0], lo, hi), key=lambda g: g[0] - g[1])
    labelled = []
    for a, b in idle[:TOP_N]:
        mid = (a + b) // 2
        where = "bench.on_step" if _inside(on_step, mid) else "in run"
        i = bisect.bisect_left(starts, b)
        nxt = mod_events[i][0] if i < len(mod_events) else "window end"
        labelled.append([f"{where}, before {nxt}", (b - a) * 1e-9])
    busy = [total(b) * 1e-9 for b in busy_by_dev]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "modules": dict(modules),
        "collective_exposed_s": exposed([(op, a, b) for _, op, a, b in ops0])
        * 1e-9,
        "collective_s": total(merge([(a, b) for _, op, a, b in ops0
                                     if COLLECTIVE.search(op)])) * 1e-9,
        "breakdown": {"device_ops": [[n, v * 1e-9] for n, v in top],
                      "idle_gaps": labelled},
    }


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce_file(path: str, chips: int) -> Dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), chips)


def reduce_dir(trace_dir: str, chips: int) -> Dict:
    return reduce_file(find_xplane(trace_dir), chips)
