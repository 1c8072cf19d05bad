"""Seconds per HO-SGD period in which a collective (all-reduce, all-gather,
...) ran on the first device and no other op did (trace)."""


def read(rec):
    t = rec["trace"]
    if not t["collective_s"]:
        return None
    periods = rec["n_steps"] / rec["tau"]
    return t["collective_exposed_s"] / periods
