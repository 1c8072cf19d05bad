"""Share of its roofline, in %, of the flash attention path in a ZO step:
attention's causal FLOPs of the step's two forwards over one chip's rows
(``attention_flops``, a lower bound on what the blocked kernels compute:
they run whole diagonal blocks) over the op seconds per execution of
``jit_zo_step`` under ``model.attn/attn.flash`` (the kernels and their
layout transposes; program spans, first device) x the chip's peak bf16
FLOP/s.  Compute bounds it at 4096 tokens: there q, k, v and o move in
under a quarter of the FLOPs' time at the chip's HBM bandwidth."""

PROGRAM, FORWARDS = "jit_zo_step", 2


def read(rec):
    p = rec.get("spans", {}).get("programs", {}).get(PROGRAM)
    secs = p and p["by_layer"].get("model.attn/attn.flash")
    if not secs or not p["executions"]:
        return None
    per_exec = secs / p["executions"]
    return 100.0 * FORWARDS * rec["attention_flops"] / (
        per_exec * rec["peak_flops"])
