from repro.launch import dryrun_flags  # noqa: F401  (must precede any jax import)

# Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).
#
# For each target this records, as JSON under --out:
#   * compiled memory analysis (proves the program fits),
#   * cost analysis (FLOPs / bytes), scan-corrected via depth extrapolation,
#   * collective bytes by kind parsed from the compiled HLO,
#   * lower/compile wall times.
#
# Step kinds per shape: train_4k lowers the HO-SGD FO step (and the ZO step —
# the paper's technique — so the collective-load difference is visible);
# prefill_32k lowers ``prefill`` (plain forward for encoder-only archs);
# decode shapes lower ``serve_step`` (one token against a full KV cache).

import argparse
import json
import os
import time
from typing import Dict, Optional, Tuple

import jax

from repro.configs import (
    ARCH_IDS, SHAPES, config_for_shape, get_config, shape_applicable,
)
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.distributed import make_fo_step, make_zo_step
from repro.core.ho_sgd import HOSGDConfig
from repro.dist.sharding import param_specs
from repro.launch import hlo
from repro.launch.mesh import auto_mesh, make_production_mesh
from repro.launch.specs import input_specs
from repro.models import transformer as T
from repro.opt.optimizers import const_schedule, sgd
from repro.serving.engine import serve_step


def step_kinds(shape: ShapeConfig) -> Tuple[str, ...]:
    if shape.kind == "train":
        return ("fo", "zo")
    return (shape.kind,)  # prefill | decode


def build_target(cfg: ModelConfig, shape: ShapeConfig, mesh, step: str):
    """Returns (jitted_fn, arg_structs)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if step in ("fo", "zo"):
        loss_fn = lambda p, b: T.loss_fn(cfg, p, b)
        opt = sgd(const_schedule(1e-2))
        args, shardings = input_specs(cfg, shape, mesh, "train")
        if step == "fo":
            fn = make_fo_step(loss_fn, mesh, opt, grad_accum=cfg.grad_accum,
                              scan_unroll=cfg.scan_unroll)
        else:
            from repro.launch.specs import abstract_params
            ho = HOSGDConfig(tau=8, mu=1e-3, lr=1e-2, zo_lr=1e-2 / 1e6,
                             acc_dtype=os.environ.get(
                                 "REPRO_ZO_ACC_DTYPE", "float32"))
            fn = make_zo_step(loss_fn, mesh, ho, opt, fsdp=cfg.fsdp,
                              param_specs_tree=param_specs(
                                  cfg, abstract_params(cfg), mesh))
        pshard = shardings[1]
        out_sh = (pshard, (), NamedSharding(mesh, P()))
        jf = jax.jit(fn, in_shardings=shardings, out_shardings=out_sh)
        return jf, args
    if step == "prefill":
        args, shardings = input_specs(cfg, shape, mesh, "prefill")
        if cfg.encoder_only:
            fn = lambda p, b: T.forward_logits(cfg, p, b)[0]
            jf = jax.jit(fn, in_shardings=shardings)
        else:
            fn = lambda p, b: T.prefill(cfg, p, b)
            # prefill returns the filled caches: pin their output shardings
            # (batch over workers + kv-head/hd over model) or they'd be
            # left to the compiler and could come back replicated
            from repro.dist.sharding import cache_specs
            from repro.launch.specs import decode_structs
            _, _, cstructs = decode_structs(cfg, shape)
            csh = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                cache_specs(cfg, mesh, cstructs, seq_sharded=False),
                is_leaf=lambda x: isinstance(x, P),
            )
            # prefill caches are dicts keyed like init_caches minus mamba? no:
            # prefill returns exactly the per-layer cache pytree shape
            jf = jax.jit(fn, in_shardings=shardings,
                         out_shardings=(None, csh))
        return jf, args
    if step == "decode":
        args, shardings = input_specs(cfg, shape, mesh, "decode")
        fn = lambda p, tok, pos, c: serve_step(cfg, p, tok, pos, c)
        # pin cache output shardings to the inputs (stable steady-state decode)
        jf = jax.jit(fn, in_shardings=shardings,
                     out_shardings=(None, shardings[3]))
        return jf, args
    raise ValueError(step)


def lower_compile(cfg, shape, mesh, step):
    jf, args = build_target(cfg, shape, mesh, step)
    t0 = time.perf_counter()
    lowered = jf.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    return lowered, compiled, t1 - t0, t2 - t1


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token


def run_one(arch: str, shape_name: str, multi_pod: bool, step: str,
            scan_correct: bool = True, verbose: bool = True,
            save_hlo: str = "") -> Dict:
    shape = SHAPES[shape_name]
    base = get_config(arch)
    ok, reason = shape_applicable(base, shape)
    mesh_name = "multipod" if multi_pod else "pod"
    rec: Dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "step": step,
        "applicable": ok, "skip_reason": reason,
    }
    if not ok:
        if verbose:
            print(f"[skip] {arch} x {shape_name} ({step}): {reason}")
        return rec

    cfg = config_for_shape(base, shape)
    tm = os.environ.get("REPRO_TEST_MESH")  # e.g. "4x2" / "2x2x2" (CI rehearsal)
    if tm:
        dims = tuple(int(x) for x in tm.split("x"))
        axes = ("pod", "data", "model") if len(dims) == 3 else ("data", "model")
        mesh = auto_mesh(dims, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    p = cfg.pattern_period
    G = cfg.n_groups
    rec.update(n_layers=cfg.n_layers, period=p, n_groups=G,
               params=cfg.param_count(), params_active=cfg.param_count(True),
               model_flops=model_flops(cfg, shape))

    with jax.set_mesh(mesh):
        lowered, compiled, t_lower, t_compile = lower_compile(cfg, shape, mesh, step)
        rec["lower_s"] = round(t_lower, 2)
        rec["compile_s"] = round(t_compile, 2)
        rec["cost_raw"] = hlo.cost_summary(compiled)
        rec["memory"] = hlo.memory_summary(compiled)
        text = compiled.as_text()
        ms = mesh.shape["model"]
        rec["collectives_raw"] = hlo.collective_bytes(text, ms)
        rec["async_overlap"] = hlo.async_overlap_stats(text)
        rec["hlo_bytes"] = len(text)
        if save_hlo:
            import gzip
            with gzip.open(save_hlo, "wt") as zf:
                zf.write(text)

        if scan_correct and G > 1:
            cost1 = cost2 = coll1 = coll2 = None
            for nl, tag in ((p, 1), (2 * p, 2)):
                # unrolled so cost_analysis counts every layer (scan bodies
                # are otherwise counted once); full-depth keeps the scan.
                # attn/CE chunking is disabled here: those scans would be
                # unrolled too (16 q-chunks x 32 vocab-chunks x accum -> HLO
                # explosion) and the dense forms have identical FLOPs/bytes
                # semantics (streaming CE adds ~one remat pass of the head
                # matmul, a documented small underestimate for large vocabs)
                cfg_s = cfg.with_(n_layers=nl, scan_unroll=True,
                                  attn_chunk=0, ce_chunk=-1)
                _, comp_s, _, _ = lower_compile(cfg_s, shape, mesh, step)
                cs = hlo.cost_summary(comp_s)
                cb = hlo.collective_bytes(comp_s.as_text(), ms)
                if tag == 1:
                    cost1, coll1 = cs, cb
                else:
                    cost2, coll2 = cs, cb
            rec["cost_depth_points"] = {"L1": cost1, "L2": cost2}
            rec["cost"] = {
                k: hlo.extrapolate(cost1[k], cost2[k], G) for k in cost1
            }
            rec["collectives"] = {
                k: hlo.extrapolate(coll1[k], coll2[k], G) for k in coll1
            }
        else:
            rec["cost"] = dict(rec["cost_raw"])
            rec["collectives"] = dict(rec["collectives_raw"])

    if verbose:
        c = rec["cost"]
        mem = rec["memory"]
        ov = rec["async_overlap"]
        print(
            f"[ok] {arch} x {shape_name} x {mesh_name} ({step}): "
            f"flops={c['flops']:.3e} bytes={c['bytes']:.3e} "
            f"coll={rec['collectives']['total']:.3e}B "
            f"argbytes={mem.get('argument_size_in_bytes', 0):.3e} "
            f"temp={mem.get('temp_size_in_bytes', 0):.3e} "
            f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)"
        )
        if ov["pairs"]:
            print(f"     async collectives: {ov['pairs']} start/done pairs, "
                  f"{ov['overlapped_pairs']} overlapped, mean gap "
                  f"{ov['mean_gap']:.1f} ops, max {ov['max_gap']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--step", default="auto")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-correct", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true",
                    help="also write <tag>.hlo.txt.gz of the full lowering")
    ap.add_argument("--xla-overlap", action="store_true",
                    help="compile under the async-collective + latency-"
                         "hiding scheduler flags (launch.xla) so the "
                         "recorded async_overlap stats show what the "
                         "scheduler actually hid")
    args = ap.parse_args()

    if args.xla_overlap:
        # must land before the first device query initializes the backend;
        # the flags are GPU-only and XLA aborts on unknown CPU flags, so on
        # the forced-host-device matrix we skip them (async_overlap stats
        # are still parsed from whatever HLO the backend schedules)
        if any(os.environ.get(k, "").lower() in ("cpu",)
               for k in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME")):
            print("xla-overlap: CPU backend — GPU scheduler flags skipped")
        else:
            from repro.launch.xla import enable_collective_overlap
            enable_collective_overlap()

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multipod"]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                kinds = (
                    step_kinds(SHAPES[shape_name]) if args.step == "auto"
                    else (args.step,)
                )
                for step in kinds:
                    tag = f"{arch}__{shape_name}__{'multipod' if mp else 'pod'}__{step}"
                    out_path = os.path.join(args.out, tag + ".json")
                    if os.path.exists(out_path) and not args.force:
                        with open(out_path) as f:
                            prev = json.load(f)
                        if "error" not in prev:
                            print(f"[resume] {tag}: already done")
                            n_ok += prev.get("applicable", False)
                            n_skip += not prev.get("applicable", False)
                            continue
                    try:
                        # the roofline table reads single-pod numbers only;
                        # multipod runs prove lower+compile (skip the extra
                        # depth-point lowerings there)
                        rec = run_one(
                            arch, shape_name, mp, step,
                            scan_correct=not args.no_correct and not mp,
                            save_hlo=(out_path[:-5] + ".hlo.txt.gz"
                                      if args.save_hlo else ""))
                        n_ok += rec.get("applicable", False)
                        n_skip += not rec.get("applicable", False)
                    except Exception as e:  # a failure here is a bug: report it
                        n_fail += 1
                        rec = {"arch": arch, "shape": shape_name,
                               "mesh": "multipod" if mp else "pod",
                               "step": step, "applicable": True,
                               "error": f"{type(e).__name__}: {e}"}
                        print(f"[FAIL] {tag}: {rec['error']}")
                    with open(os.path.join(args.out, tag + ".json"), "w") as f:
                        json.dump(rec, f, indent=1)
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_fail} FAILED")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
