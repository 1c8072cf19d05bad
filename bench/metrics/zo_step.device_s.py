"""Device seconds per ZO step: the mean duration of the device's executions
of the program jitted from ``zo_step`` (trace, first device)."""


def read(rec):
    runs = rec["trace"]["modules"].get("jit_zo_step")
    return sum(runs) / len(runs) if runs else None
