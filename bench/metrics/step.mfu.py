"""Whole-step share of the chips' peak, in %: the model FLOPs of the
window's steps (``step_flops``: FO 3 forwards, ZO 2; no remat, no direction
algebra) over window seconds x chips x peak bf16 FLOP/s."""


def read(rec):
    w = rec["window"]
    flops = sum(rec["step_flops"][k] for k in w["kinds"])
    if not flops or w["wall_s"] <= 0:
        return None
    return 100.0 * flops / (w["wall_s"] * rec["chips"] * rec["peak_flops"])
