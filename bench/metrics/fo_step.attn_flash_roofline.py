"""Share of its roofline, in %, of the flash attention path in an FO step:
attention's causal FLOPs of four forwards over one chip's rows (the
forward, the remat forward and a backward counted as two; a lower bound on
what the kernels compute: the backward takes more, and the blocked kernels
run whole diagonal blocks) over the op seconds per execution of
``jit_fo_step`` under ``model.attn/attn.flash`` (the kernels and their
layout transposes; program spans, first device) x the chip's peak bf16
FLOP/s.  Compute bounds it, as in the ZO step."""

PROGRAM, FORWARDS = "jit_fo_step", 4


def read(rec):
    p = rec.get("spans", {}).get("programs", {}).get(PROGRAM)
    secs = p and p["by_layer"].get("model.attn/attn.flash")
    if not secs or not p["executions"]:
        return None
    per_exec = secs / p["executions"]
    return 100.0 * FORWARDS * rec["attention_flops"] / (
        per_exec * rec["peak_flops"])
