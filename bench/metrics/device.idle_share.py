"""Share of the traced window, in %, in which no op ran on a device: one
less the union of op intervals over the window, averaged over the chips."""


def read(rec):
    t = rec["trace"]
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
