"""Device seconds per ZO step in its two forwards: the op seconds of the
``jit_zo_step`` executions under ``zo.forward``, over the executions
(``span_reduce.per_step``; program spans, first device)."""


def read(rec):
    return rec.get("spans", {}).get("per_step", {}).get(
        "zo_step.forward_device_s")
