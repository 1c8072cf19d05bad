"""End-to-end distributed training driver (HO-SGD or any baseline).

Runs the real thing on whatever devices exist (CPU devices here; the same
code drives a TPU slice).  Example — train a ~100M model for 200 steps:

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --reduce 100m \
        --steps 200 --tau 8 --batch 16 --seq 256
"""
from __future__ import annotations

import argparse
import json
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import save as ckpt_save
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ModelConfig
from repro.core.distributed import make_distributed_ho_sgd
from repro.core.ho_sgd import (
    HOSGDConfig, adaptive_tau_decision, parse_tau_schedule,
)
from repro.data import shard_batches, token_batches
from repro.dist import CommLedger, get_compressor
from repro.dist.sharding import named, param_specs, n_workers
from repro.launch.mesh import make_test_mesh
from repro.launch.xla import use_compile_cache
from repro.metrics import CSVLogger, comm_report
from repro.models import transformer as T
from repro.obs import scopes
from repro.opt.optimizers import sgd, const_schedule


def size_override(cfg: ModelConfig, preset: str, layers: int = 0) -> ModelConfig:
    """Depth/width presets so examples fit the local device.

    ``layers`` (CLI ``--layers``) then cuts the depth alone and keeps every
    width; it must be a multiple of the config's ``pattern_period``.
    """
    if preset == "full":
        out = cfg
    elif preset == "100m":
        out = cfg.with_(
            n_layers=max(cfg.pattern_period * 4, 8), d_model=768,
            n_heads=12, n_kv_heads=max(1, min(cfg.n_kv_heads, 4)),
            head_dim=64, d_ff=2048, dense_d_ff=min(cfg.dense_d_ff, 2048),
            vocab_size=min(cfg.vocab_size, 32768),
            n_experts=min(cfg.n_experts, 8), dt_rank=48,
            dtype="float32",
        )
    elif preset == "smoke":
        out = cfg.reduced()
    else:
        raise ValueError(preset)
    if layers:
        if layers % out.pattern_period:
            raise ValueError(
                f"--layers {layers} is not a multiple of {out.name}'s layer "
                f"pattern period {out.pattern_period}")
        out = out.with_(n_layers=layers)
    return out


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b", choices=ARCH_IDS)
    ap.add_argument("--reduce", default="smoke", choices=["full", "100m", "smoke"])
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers, keeping every "
                         "width (0 = the preset's depth)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--tau", type=int, default=8)
    ap.add_argument("--tau-schedule", default=None,
                    help="adaptive period: 'const:K' or "
                         "'linear:start,end,horizon' (needs --tau >= 2; "
                         "default: fixed --tau)")
    ap.add_argument("--mu", type=float, default=1e-3)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--zo-lr", type=float, default=None)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-axis", type=int, default=0, help="0 = all devices")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log", default=None)
    ap.add_argument("--compress", default="none",
                    choices=["none", "qsgd", "signsgd", "topk"],
                    help="codec on the FO gradient all-reduce")
    ap.add_argument("--compress-mode", default="per_worker",
                    choices=["per_worker", "legacy"],
                    help="per_worker: each worker encodes its shard "
                         "gradient, the reducer decodes (wire = nbytes x m);"
                         " legacy: post-reduction decode(encode(mean))")
    ap.add_argument("--engine", default="fused",
                    choices=["tree", "fused", "pallas", "flat"],
                    help="DirectionEngine backend for the ZO direction "
                         "algebra (repro.core.engine); 'flat' packs the "
                         "tree into one buffer and runs one kernel per "
                         "primitive")
    ap.add_argument("--fo-buckets", type=int, default=1,
                    help="chunk the FO gradient all-reduce into this many "
                         "independently-reducible buckets (bit-identical "
                         "math, same ledger bytes; pairs with --xla-overlap "
                         "so the scheduler hides them behind compute)")
    ap.add_argument("--xla-overlap", action="store_true",
                    help="append the async-collective + latency-hiding "
                         "scheduler XLA flags (launch.xla, composed with "
                         "any user-set XLA_FLAGS, never replacing them)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="run under a jax.profiler trace written to DIR "
                         "(host spans and device ops on one clock), with "
                         "the step programs' op scopes in DIR/op_scopes.json")
    return ap.parse_args(argv)


@dataclass
class Trainer:
    """One configured training run: what ``setup`` builds and ``run`` drives.

    ``jitted`` holds the bare jitted FO/ZO step programs (for lowering and
    inspection); ``steps`` holds the same programs wrapped by ``ledger``.
    """
    args: argparse.Namespace
    cfg: ModelConfig
    mesh: Any
    m: int
    d: int
    leaf_dims: List[int]
    codec: Any
    params: Any
    opt_state: Any
    ledger: CommLedger
    jitted: Dict[str, Callable]
    steps: Dict[str, Callable]
    tau_sched: Optional[Callable[[int], int]]

    def comm_lines(self) -> List[str]:
        """Measured (ledger) vs analytic communication lines."""
        # dense FO exchange moves gradients in the param dtype (fp32
        # accumulator when grad_accum microbatches); ZO coefficients are
        # always fp32
        grad_bytes = (4 if self.cfg.grad_accum > 1
                      else jnp.dtype(self.cfg.dtype).itemsize)
        return comm_report(self.ledger, d=self.d, m=self.m,
                           tau=self.args.tau, codec=self.codec,
                           leaf_dims=self.leaf_dims, grad_bytes=grad_bytes)

    def op_scopes(self) -> Dict[str, Dict[str, str]]:
        """``{program: {instruction: op_name}}`` of each step program that
        has run (``fo``, ``zo``), compiled for the current state's shapes
        and shardings, keyed by the module name a profiler trace gives it
        (``jit_fo_step``); ``repro.obs.scopes`` classifies the op_names."""
        a = self.args
        host = next(token_batches(self.cfg.vocab_size, a.batch, a.seq,
                                  seed=a.seed))
        out = {}
        with jax.set_mesh(self.mesh):
            batch = next(shard_batches(iter([host]), self.mesh))
            for name, fn in self.jitted.items():
                if not self.ledger.steps.get(name):
                    continue
                text = fn.lower(jnp.int32(0), self.params, self.opt_state,
                                batch).compile().as_text()
                out[scopes.module_name(text)] = scopes.op_names(text)
        return out


def setup(args: argparse.Namespace) -> Trainer:
    """Mesh, model, HO-SGD step programs and device-resident state."""
    if args.xla_overlap:
        # must land before the first device query initializes the backend
        from repro.launch.xla import enable_collective_overlap
        enable_collective_overlap()
    n_dev = jax.device_count()
    data_ax = args.data_axis or max(1, n_dev // args.model_axis)
    mesh = make_test_mesh(data=data_ax, model=args.model_axis)
    m = n_workers(mesh)

    cfg = size_override(get_config(args.arch), args.reduce, args.layers)
    if cfg.frontend != "none":
        raise SystemExit("use examples/ drivers for frontend archs")
    print(f"arch={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} params={cfg.param_count():,} "
          f"mesh={dict(mesh.shape)} workers={m}")

    params = T.init_model(jax.random.key(args.seed), cfg)
    loss_fn = lambda p, b: T.loss_fn(cfg, p, b)
    leaf_dims = [int(x.size) for x in jax.tree.leaves(params)]
    d = sum(leaf_dims)
    zo_lr = args.zo_lr if args.zo_lr is not None else args.lr * 50.0 / d
    ho = HOSGDConfig(tau=args.tau, mu=args.mu, m=m, lr=args.lr, zo_lr=zo_lr,
                     seed=args.seed, engine=args.engine)
    opt = sgd(const_schedule(args.lr))
    codec = get_compressor(args.compress)
    fo, zo = make_distributed_ho_sgd(loss_fn, mesh, ho, opt, model_cfg=cfg,
                                     params_like=params, compressor=codec,
                                     compress_mode=args.compress_mode,
                                     fo_buckets=args.fo_buckets)

    # adaptive tau: the same decision logic the Method and the simulator use
    # (core.ho_sgd.adaptive_tau_decision); the fixed-tau default path stays
    # bit-identical to before (t % tau, step keyed on t itself)
    tau_sched = parse_tau_schedule(args.tau_schedule) if args.tau_schedule else None
    if tau_sched is not None and args.tau < 2:
        raise SystemExit("--tau-schedule needs --tau >= 2 (the ZO seed map)")

    with jax.set_mesh(mesh):
        params = jax.device_put(params, named(mesh, param_specs(cfg, params, mesh)))
        opt_state = opt.init(params)
    ledger = CommLedger()
    jitted = {"fo": jax.jit(fo), "zo": jax.jit(zo)}
    steps = {name: ledger.wrap(name, fn) for name, fn in jitted.items()}
    return Trainer(args, cfg, mesh, m, d, leaf_dims, codec, params, opt_state,
                   ledger, jitted, steps, tau_sched)


def run(tr: Trainer, on_step: Optional[Callable] = None) -> float:
    """The HO-SGD loop; returns the last step's loss.

    ``on_step(t, name, loss, dt, params, batch)`` is called after every
    step with the step's ("fo" or "zo") wall time ``dt`` from its dispatch
    to the blocking read of its loss, and the updated device-resident
    params.

    Host spans, recorded while a profiler session is active: each step is
    a ``train.step`` (args ``step_num``, ``kind`` and ``wire_bytes``, the
    ledger's payload bytes of the step) over ``train.data`` (the next
    sharded batch: the token draw and its ``device_put``),
    ``train.dispatch`` (the step call, up to its return), ``train.block``
    (``float(loss)``), ``train.log`` and then ``on_step``; a checkpoint
    save is ``train.checkpoint``.
    """
    args = tr.args
    span = jax.profiler.TraceAnnotation
    with jax.set_mesh(tr.mesh):
        batches = shard_batches(token_batches(
            tr.cfg.vocab_size, args.batch, args.seq, seed=args.seed), tr.mesh)
        since_fo = 0
        with CSVLogger(args.log,
                       ["step", "order", "loss", "dt", "comm_bytes"]) as logger:
            t_prev = time.perf_counter()
            for t in range(args.steps):
                if tr.tau_sched is None:
                    is_fo, t_step = t % args.tau == 0, t
                else:
                    is_fo, t_step, since_fo = adaptive_tau_decision(
                        t, since_fo, tr.tau_sched(t), args.tau)
                name = "fo" if is_fo else "zo"
                with jax.profiler.StepTraceAnnotation(
                        "train.step", step_num=t, kind=name) as step_span:
                    with span("train.data"):
                        batch = next(batches)
                    t0 = time.perf_counter()
                    with span("train.dispatch"):
                        tr.params, tr.opt_state, loss = tr.steps[name](
                            jnp.int32(t_step), tr.params, tr.opt_state, batch)
                    with span("train.block"):
                        loss = float(loss)       # blocks: dispatch is async
                    dt_step = time.perf_counter() - t0
                    # booked when the program was traced: known only now on
                    # the first step of each kind
                    wire = tr.ledger.bytes_per_step(name)
                    step_span.set_metadata(wire_bytes=wire)
                    with span("train.log"):
                        if t % 10 == 0 or t == args.steps - 1:
                            now = time.perf_counter()
                            print(f"step {t:5d} ({'FO' if is_fo else 'ZO'}) "
                                  f"loss={loss:.4f} dt={now - t_prev:.2f}s")
                            t_prev = now
                        logger.log(step=t, order=int(is_fo), loss=loss,
                                   dt=dt_step, comm_bytes=wire)
                    if on_step is not None:
                        on_step(t, name, loss, dt_step, tr.params, batch)
            if args.ckpt:
                with span("train.checkpoint"):
                    path = ckpt_save(args.ckpt, args.steps,
                                     jax.device_get(tr.params))
                print("checkpoint:", path)
    return loss


def profile(tr: Trainer, out_dir: str) -> float:
    """``run`` under a ``jax.profiler`` trace written to ``out_dir``, then
    the step programs' op scopes (``Trainer.op_scopes``) to
    ``out_dir/op_scopes.json``; returns the last step's loss."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(out_dir, profiler_options=opts):
        loss = run(tr)
    path = os.path.join(out_dir, "op_scopes.json")
    with open(path, "w") as f:
        json.dump(tr.op_scopes(), f)
    print(f"wrote a profile and {path}")
    return loss


def main(argv=None):
    use_compile_cache()
    tr = setup(parse_args(argv))
    loss = profile(tr, tr.args.profile) if tr.args.profile else run(tr)
    for line in tr.comm_lines():
        print(line)
    print("done; final loss", loss)
    return loss


if __name__ == "__main__":
    main()
