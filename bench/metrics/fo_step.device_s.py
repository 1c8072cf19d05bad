"""Device seconds per FO step: the mean duration of the device's executions
of the program jitted from ``fo_step`` (trace, first device)."""


def read(rec):
    runs = rec["trace"]["modules"].get("jit_fo_step")
    return sum(runs) / len(runs) if runs else None
