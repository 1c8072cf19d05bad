"""Compiled-HLO analysis: collective-bytes parsing + cost extraction.

``cost_analysis()`` counts while-loop (lax.scan) bodies ONCE, so totals for
the layer-scanned models are corrected by linear extrapolation over depth:
lower the same config at L = p and L = 2p layers (p = pattern period);
per-layer cost = c(2p) - c(p); total = c(p) + (n_layers/p - 1) * per-layer.
The same correction applies to collective bytes parsed from the HLO text.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\(?[a-z0-9\[\],{}: #*\"]*\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _classify_axis(line: str, model_size: int) -> str:
    """Does this collective run over the model axis (inside one worker) or
    across workers (the traffic the paper optimizes)?

    Device ids are worker-major (id = worker*model_size + model): a group
    stays inside one worker iff its ids all fall in one model_size-aligned
    block.  For iota forms the discriminator is the *stride span* of the
    fastest-varying grouped axis: stride * extent <= model_size (and the
    block-aligned start) keeps it within the model axis.
    """
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}", 1)[0].lstrip("{")
        ids = [int(x) for x in first.split(",") if x.strip() != ""]
        if len(ids) <= 1:
            return "model"  # degenerate singleton groups
        block = ids[0] // model_size
        same_block = all(i // model_size == block for i in ids)
        return "model" if same_block else "worker"
    m = _IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        perm = ([int(x) for x in m.group(4).split(",")]
                if m.group(4) else list(range(len(dims))))
        # group elements vary over the trailing transposed axes covering s
        # device-ids; span = max stride*extent over those axes
        strides = {}
        acc = 1
        for ax in range(len(dims) - 1, -1, -1):
            strides[ax] = acc
            acc *= dims[ax]
        span = 1
        need = s
        for ax in reversed(perm):
            if need <= 1:
                break
            take = min(dims[ax], need)
            span = max(span, strides[ax] * take)
            need = (need + take - 1) // take
        return "model" if span <= model_size else "worker"
    return "unknown"


def collective_bytes(hlo_text: str, model_size: int = 16) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op, by kind and by axis.

    Uses the op *result* size (for all-gather that's the gathered size — the
    standard per-device wire approximation); async ``-done`` ops are skipped
    to avoid double counting.
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    out["axis_model"] = 0.0
    out["axis_worker"] = 0.0
    out["axis_unknown"] = 0.0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        b = _shape_bytes(m.group(1))
        out[m.group(2)] += b
        out["axis_" + _classify_axis(line, model_size)] += b
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


_LHS_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_START_RE = re.compile(
    r"=\s*[^=]*\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)-start\(")
_DONE_RE = re.compile(
    r"=\s*[^=]*\b(?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)-done\(\s*%?([\w.\-]+)")


def async_overlap_stats(hlo_text: str) -> Dict:
    """How much work the scheduler put between each async collective's
    ``-start`` and its matching ``-done``.

    Walks the HLO text counting instruction lines (`` = `` assignments);
    for every ``<kind>-start`` whose ``-done`` consumes it, the *gap* is the
    number of instructions scheduled strictly between the two — the direct
    HLO-level witness of compute/comm overlap (gap 0 = the collective is
    synchronous in effect, whatever its op names say).  Returns::

        {"pairs": N, "overlapped_pairs": M,          # M pairs with gap > 0
         "by_kind": {kind: count}, "mean_gap": g, "max_gap": G}
    """
    open_starts: Dict[str, Tuple[str, int]] = {}   # lhs name -> (kind, idx)
    gaps = []
    kinds: Dict[str, int] = {}
    idx = 0
    for line in hlo_text.splitlines():
        lhs = _LHS_RE.match(line)
        if not lhs:
            continue
        idx += 1
        m = _START_RE.search(line)
        if m:
            open_starts[lhs.group(1)] = (m.group(1), idx)
            continue
        m = _DONE_RE.search(line)
        if m and m.group(1) in open_starts:
            kind, start_idx = open_starts.pop(m.group(1))
            gaps.append(idx - start_idx - 1)
            kinds[kind] = kinds.get(kind, 0) + 1
    return {
        "pairs": len(gaps),
        "overlapped_pairs": sum(1 for g in gaps if g > 0),
        "by_kind": kinds,
        "mean_gap": (sum(gaps) / len(gaps)) if gaps else 0.0,
        "max_gap": max(gaps) if gaps else 0,
    }


def extrapolate(c1: float, c2: float, n_groups: int) -> float:
    """c(L=p), c(L=2p) -> c(full): c1 + (G-1)*(c2-c1) with G = n_layers/p."""
    per = c2 - c1
    return c1 + (n_groups - 1) * per


def cost_summary(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def memory_summary(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes", "host_argument_size_in_bytes",
              "host_output_size_in_bytes", "host_temp_size_in_bytes",
              "peak_memory_in_bytes", "serialized_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    return out
