"""Host seconds per step outside the blocking step calls: the window's wall
time less the sum of the step seconds ``run`` hands to ``on_step``, over the
number of steps."""


def read(rec):
    w = rec["window"]
    return (w["wall_s"] - sum(w["dts"])) / len(w["dts"]) if w["dts"] else None
