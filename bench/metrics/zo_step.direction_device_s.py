"""Device seconds per ZO step in the direction algebra: the op seconds of
the ``jit_zo_step`` executions under ``zo.norm``, ``zo.perturb``,
``zo.reconstruct`` and ``zo.update``, over the executions
(``span_reduce.per_step``; program spans, first device)."""


def read(rec):
    return rec.get("spans", {}).get("per_step", {}).get(
        "zo_step.direction_device_s")
