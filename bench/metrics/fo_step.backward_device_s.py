"""Device seconds per FO step in the backward pass: the op seconds of the
``jit_fo_step`` executions under ``fo.grad`` that carry ``transpose(`` and
are no recompute (on several chips the gradient all-reduce with them), over
the executions (``span_reduce.per_step``; program spans, first device)."""


def read(rec):
    return rec.get("spans", {}).get("per_step", {}).get(
        "fo_step.backward_device_s")
