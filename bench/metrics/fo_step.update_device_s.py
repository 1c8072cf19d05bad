"""Device seconds per FO step in the gradient accumulator and the update:
the op seconds of the ``jit_fo_step`` executions under ``fo.accumulate``
and ``fo.update``, over the executions (``span_reduce.per_step``; program
spans, first device)."""


def read(rec):
    return rec.get("spans", {}).get("per_step", {}).get(
        "fo_step.update_device_s")
