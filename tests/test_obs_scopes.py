"""The training path's tracing: host spans of ``launch.train.run`` on the
profiler's clock, and the named scopes of the compiled step programs
(``repro.obs.scopes``)."""
import json
import os
import subprocess
import sys

import pytest

from repro.obs import scopes as S

SMOKE = ["--arch", "phi3-mini-3.8b", "--reduce", "smoke", "--batch", "4",
         "--seq", "32", "--tau", "2", "--seed", "3", "--steps", "0"]


@pytest.mark.parametrize("op_name,want", [
    ("jit(fo_step)/while/body/fo.grad/jvp()/while/body/closed_call/"
     "model.attn/dot_general", ("fo.grad.forward", "model.attn")),
    ("jit(fo_step)/while/body/fo.grad/transpose(jvp())/while/body/"
     "closed_call/model.mlp/dot_general", ("fo.grad.backward", "model.mlp")),
    ("jit(fo_step)/fo.grad/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/model.mlp/rsqrt",
     ("fo.grad.recompute", "model.mlp")),
    ("jit(fo_step)/fo.grad/jvp()/while/body/model.attn/"
     "bgrqk,bkgd->bqgrd/transpose", ("fo.grad.forward", "model.attn")),
    ("jit(zo_step)/shard_map/zo.reconstruct/while/body/zo.norm/reduce_sum",
     ("zo.norm", None)),
    ("jit(zo_step)/shard_map/zo.forward/model.head/log", ("zo.forward",
                                                         "model.head")),
    ("jit(zo_step)/fo.gradient/model.attn2/add", (None, None)),
    ("jit(fo_step)/model.attn/sin", (None, "model.attn")),
    ("jit(zo_step)/zo.forward/while/body/closed_call/checkpoint/model.attn/"
     "attn.flash/pallas_call", ("zo.forward", "model.attn/attn.flash")),
    ("jit(fo_step)/fo.grad/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/model.attn/attn.dense/while/body/"
     "exp", ("fo.grad.recompute", "model.attn/attn.dense")),
    ("jit(fo_step)/fo.grad/jvp()/model.mlp/attn.flash/add",
     ("fo.grad.forward", "model.mlp")),
    ("jit(fo_step)/model.attn/attn.flashy/add", (None, "model.attn")),
])
def test_classify(op_name, want):
    assert S.classify(op_name) == want


def test_op_names_and_module_name():
    text = """HloModule jit_f, is_scheduled=true

%fused (p: f32[4]) -> f32[4] {
  ROOT %mul.0 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/zo.update/mul" stack_frame_id=2}
}

ENTRY %main.1 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %c = f32[] constant(1)
  ROOT %fusion.3 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/zo.update/mul"}
}
"""
    assert S.module_name(text) == "jit_f"
    assert S.op_names(text) == {"mul.0": "jit(f)/zo.update/mul",
                                "x.1": "x", "fusion.3": "jit(f)/zo.update/mul"}
    with pytest.raises(ValueError):
        S.module_name("ENTRY %main")


def scope_summary(grad_accum: int = 2) -> dict:
    """Which scopes each step program of a smoke phi3 trainer names once it
    has run, and the share of its instructions with an op_name under
    ``jit(`` that a known scope covers, constants left out (the
    partitioner's carry the bare ``shard_map`` name)."""
    from repro.launch import train
    size_override = train.size_override
    train.size_override = lambda *a: size_override(*a).with_(
        grad_accum=grad_accum)
    try:
        tr = train.setup(train.parse_args(SMOKE))
    finally:
        train.size_override = size_override
    tr.args.steps = 2              # one FO and one ZO step: both programs
    train.run(tr)
    out = {}
    for prog, names in tr.op_scopes().items():
        named = [v for k, v in names.items()
                 if v.startswith("jit(") and not k.startswith("constant")]
        found = sorted({s for v in named for s in S.scopes_of(v)})
        covered = sum(1 for v in named if S.scopes_of(v)) / len(named)
        out[prog] = {"scopes": found, "covered": covered, "m": tr.m}
    return out


@pytest.mark.parametrize("devices", [1, 4])
def test_op_scopes_name_every_part_of_both_steps(devices):
    if devices == 1:
        got = scope_summary()
    else:
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [src, os.environ.get("PYTHONPATH", "")]),
                   XLA_FLAGS=f"--xla_force_host_platform_device_count="
                             f"{devices}")
        p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           capture_output=True, text=True, env=env,
                           timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        got = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(got) == {"jit_fo_step", "jit_zo_step"}
    fo, zo = got["jit_fo_step"], got["jit_zo_step"]
    assert fo["m"] == zo["m"] == devices
    assert fo["scopes"] == sorted(
        [s for s in S.STEP if s.startswith("fo.")] + list(S.MODEL))
    assert zo["scopes"] == sorted(
        [s for s in S.STEP if s.startswith("zo.")] + list(S.MODEL))
    # no scope: the microbatch loop's own ops in the FO step; on four
    # devices the worker index and the partitioner's broadcasts inside the
    # ZO step's shard_map
    assert fo["covered"] > 0.9 and zo["covered"] > 0.85, got


def test_run_records_step_spans_on_the_profiler_clock(tmp_path):
    """``run`` under a profiler session: one ``train.step`` per step with
    its number, kind and the ledger's bytes, over ``train.data``,
    ``train.dispatch``, ``train.block`` and ``train.log`` in that order;
    ``on_step`` runs after ``train.log``, inside the step."""
    import jax
    from jax.profiler import ProfileData

    from repro.launch import train
    tr = train.setup(train.parse_args(SMOKE))
    tr.args.steps = 4
    seen = []

    def on_step(t, name, loss, dt, params, batch):
        with jax.profiler.TraceAnnotation("bench.on_step"):
            seen.append(name)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        train.run(tr, on_step)
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name,
               dict(e.stats))
              for p in ProfileData.from_file(str(path)).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events
              if e.name.startswith(("train.", "bench."))]
    steps = sorted((e for e in events if e[2] == "train.step"))
    assert [s[3]["step_num"] for s in steps] == [0, 1, 2, 3]
    assert [s[3]["kind"] for s in steps] == seen == ["fo", "zo", "fo", "zo"]
    assert [s[3]["wire_bytes"] for s in steps] == [
        tr.ledger.bytes_per_step(k) for k in seen]
    assert steps[1][3]["wire_bytes"] == 4 * tr.m
    for a, b, _, _ in steps:
        inner = sorted(e for e in events
                       if e[2] != "train.step" and a <= e[0] and e[1] <= b)
        assert [e[2] for e in inner] == ["train.data", "train.dispatch",
                                         "train.block", "train.log",
                                         "bench.on_step"]
        assert all(x[1] <= y[0] for x, y in zip(inner, inner[1:]))


if __name__ == "__main__":
    print(json.dumps(scope_summary()))
