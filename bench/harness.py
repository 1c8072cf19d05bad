"""The benchmark's harness: one cell of ``BENCHMARK.json``, run once.

Everything a cell needs is found by name:

* ``BENCHMARK.json``'s workload entry gives the configuration, the traffic
  mix and the chips;
* ``configs/<config>.json`` holds the model as it is run (``model``), how the
  program is told to build it (``program``) and which plain reference
  follows it (``reference``: a module under ``reference/``, which may also
  give the configuration's FLOP counts, ``flop_counts``);
* ``traffic/<traffic>.json`` holds the job: rows per chip, sequence length,
  the token distribution, the HO-SGD period and step sizes;
* ``limits/<workload>.json`` holds the limits of the comparison that decides
  ``correct``, with the readings they were set from;
* ``metrics/<metric>.py`` reads one per-layer metric from a traced run.

A run builds the program's ``Trainer`` (``repro.launch.train.setup``),
drives it through its first steps with ``run`` (set-up: it compiles both
step programs, or reads them from the compile cache), times a window of
whole HO-SGD periods through the same ``run``, reads the device's peak
memory, frees the program's state, follows the same first steps with the
plain reference, and compares.  The program's ZO step holds the direction
seed (``--seed``) as a constant, so before that a process of its own
(``fill_cache``) compiles it for this seed into the cache, and set-up finds
every program there.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

import step_flops as sf

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


# --------------------------------------------------------------------------- #
# specs, found by name
# --------------------------------------------------------------------------- #
def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT, "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(BENCH, "configs", f"{name}.json")


def traffic(name: str) -> dict:
    return _json(BENCH, "traffic", f"{name}.json")


def limits(workload_name: str) -> dict:
    """The limits of the numbers a cell compares; a number that has no limit
    in the file is not compared.  ``loss_steps`` (default: every followed
    step) names the steps whose losses ``loss_gap`` reads."""
    spec = _json(BENCH, "limits", f"{workload_name}.json")
    return dict(spec["limits"], loss_steps=spec.get("loss_steps"))


def load_module(*parts):
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + parts[-1].replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    return load_module("metrics", f"{name}.py").read


def reference_module(cfg: dict):
    return load_module("reference", f"{cfg['reference']}.py")


# --------------------------------------------------------------------------- #
# traffic: the token stream and the trainer's arguments
# --------------------------------------------------------------------------- #
def token_batches(vocab: int, batch: int, seq: int, seed: int,
                  zipf_a: float) -> Iterator[Dict[str, np.ndarray]]:
    """Zipf(``zipf_a``) tokens over the vocabulary, next-token labels, -1 on
    the last position (no target).  The reference's feed: the program's
    ``run`` draws its own, which ``check_program_feed`` holds to this one."""
    rng = np.random.default_rng(seed)
    probs = np.arange(1, vocab + 1, dtype=np.float64) ** (-zipf_a)
    probs /= probs.sum()
    while True:
        toks = rng.choice(vocab, size=(batch, seq), p=probs).astype(np.int32)
        labels = np.full((batch, seq), -1, np.int32)
        labels[:, :-1] = toks[:, 1:]
        yield {"tokens": toks, "labels": labels}


def global_batch(tf: dict, chips: int) -> int:
    return tf["rows_per_chip"] * chips


def trainer_argv(cfg: dict, tf: dict, chips: int, seed: int) -> List[str]:
    prog = cfg["program"]
    return ["--arch", prog["arch"], "--reduce", prog["reduce"],
            "--layers", str(prog["layers"]),
            "--batch", str(global_batch(tf, chips)), "--seq", str(tf["seq"]),
            "--tau", str(tf["tau"]), "--mu", str(tf["mu"]),
            "--lr", str(tf["lr"]), "--engine", tf["engine"],
            "--fo-buckets", str(tf["fo_buckets"]),
            "--seed", str(seed), "--steps", "0"] + (
                [] if tf["zo_lr"] is None else ["--zo-lr", str(tf["zo_lr"])])


def step_kinds(tau: int, n: int) -> List[str]:
    return ["fo" if t % tau == 0 else "zo" for t in range(n)]


def seed_program_steps(tf: dict) -> int:
    """Steps of set-up through the first ZO step, whose program holds the
    direction seed as a constant and so is compiled anew for each seed; 0
    where set-up has no ZO step."""
    kinds = step_kinds(tf["tau"], tf["follow_steps"])
    return kinds.index("zo") + 1 if "zo" in kinds else 0


# --------------------------------------------------------------------------- #
# compile counter
# --------------------------------------------------------------------------- #
class Compiles:
    """Counts compilations and persistent-cache loads that JAX reports, and
    sums their seconds; ``backend`` counts the compilations alone."""

    n = 0
    backend = 0
    secs = 0.0
    _on = False

    @classmethod
    def install(cls):
        if not cls._on:
            import jax
            jax.monitoring.register_event_duration_secs_listener(cls._event)
            cls._on = True

    @classmethod
    def _event(cls, event, duration, **_):
        if event in COMPILE_EVENTS:
            cls.n += 1
            cls.backend += event == COMPILE_EVENTS[0]
            cls.secs += duration


# --------------------------------------------------------------------------- #
# the program under test
# --------------------------------------------------------------------------- #
def check_program_config(tr, cfg: dict):
    """The program builds the model the configuration file states: every
    key of the file's ``model`` that names a field of the program's
    ``ModelConfig`` (``embed_scale`` names none), and ``window``, one value
    for every layer or a list with one a layer, against the program's
    ``layer_windows()``."""
    model = cfg["model"]
    fields = {f.name for f in dataclasses.fields(tr.cfg)} - {"window"}
    bad = {k: (getattr(tr.cfg, k), v) for k, v in model.items()
           if k in fields and getattr(tr.cfg, k) != v}
    windows = list(tr.cfg.layer_windows())
    if windows != sf.layer_windows(model):
        bad["window"] = (windows, model.get("window"))
    if bad:
        raise SystemExit(f"program config differs from {cfg['name']}: "
                         f"(program, file) {bad}")


def check_program_feed(train, tr, tf: dict):
    """The tokens ``run`` draws are the ones the reference is fed: the first
    batch of the program's generator equals the harness's."""
    a = tr.args
    prog = next(train.token_batches(tr.cfg.vocab_size, a.batch, a.seq,
                                    seed=a.seed))
    ours = next(token_batches(tr.cfg.vocab_size, a.batch, a.seq, a.seed,
                              tf["zipf_a"]))
    for k in ("tokens", "labels"):
        if not np.array_equal(prog[k], ours[k]):
            raise SystemExit(f"the program's first batch differs from the "
                             f"reference's in {k!r}")


def build_program(cfg: dict, tf: dict, chips: int, seed: int):
    """``repro.launch.train.setup`` for this cell."""
    from repro.launch import train
    tr = train.setup(train.parse_args(trainer_argv(cfg, tf, chips, seed)))
    check_program_config(tr, cfg)
    check_program_feed(train, tr, tf)
    if tr.m != chips:
        raise SystemExit(f"expected m = {chips} workers, the trainer has {tr.m}")
    return train, tr


def fill_cache(cfg: dict, tf: dict, chips: int, seed: int):
    """The first steps of set-up through the first ZO step, in a process of
    their own: they put the seed's ZO program into the compile cache."""
    train, tr = build_program(cfg, tf, chips, seed)
    tr.args.steps = seed_program_steps(tf)
    train.run(tr)


def _diff_norms():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                            - y.astype(jnp.float32))))
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return f


def warm_up(train, tr, n_steps: int) -> dict:
    """The first ``n_steps`` steps through ``run``: compiles both programs
    and records what the comparison reads (losses, the first gradient from
    the optimizer's state after step 0, the change after ``n_steps``)."""
    import jax
    norms = _diff_norms()
    p0 = tr.params
    lr = tr.args.lr
    rec: Dict[str, Any] = {"losses": [], "dts": [], "kinds": [],
                           "compile_s": [],
                           "shapes": [x.shape for x in jax.tree.leaves(p0)]}
    c_prev = [Compiles.secs]

    def on_step(t, name, loss, dt, params, batch):
        rec["losses"].append(loss)
        rec["dts"].append(dt)
        rec["kinds"].append(name)
        rec["compile_s"].append(Compiles.secs - c_prev[0])
        c_prev[0] = Compiles.secs
        if t == 0:
            rec["grad_norms"] = [float(x) / lr for x in norms(p0, params)]
        if t == n_steps - 1:
            rec["change_norms"] = [float(x) for x in norms(params, p0)]

    tr.args.steps = n_steps
    train.run(tr, on_step)
    return rec


def step_estimates(warm: dict) -> Dict[str, float]:
    """Seconds per step of each kind, from the warm-up: a later step of the
    kind where there is one, else the first less its compile seconds."""
    est: Dict[str, float] = {}
    for k, dt, cs in zip(warm["kinds"], warm["dts"], warm["compile_s"]):
        est[k] = max(dt - cs, 1e-3) if k not in est else dt
    return est


def window_steps(tau: int, seconds: float, est: Dict[str, float]) -> int:
    """Whole periods: at least two, the fewest whose estimated time reaches
    ``seconds``."""
    period = est["fo"] + (tau - 1) * est.get("zo", 0.0)
    return tau * max(2, math.ceil(seconds / max(period, 1e-9)))


def timed_window(train, tr, tau: int, seconds: float, est: Dict[str, float],
                 annotate: bool = False) -> dict:
    """Whole periods through ``run``, timed on the host clock: the fewest
    that ``est`` says reach ``seconds`` (``window_steps``), then, where their
    time fell short, as many more as their measured period says reach it.
    ``est`` reads the FO step from set-up's first one, which also traces and
    loads its program, so it can overstate a period.  With ``annotate`` the
    window and each step callback are host spans in the profiler's trace."""
    import contextlib
    import jax
    span = (jax.profiler.TraceAnnotation if annotate
            else lambda name: contextlib.nullcontext())
    rec: Dict[str, Any] = {"dts": [], "kinds": [], "losses": []}

    def on_step(t, name, loss, dt, params, batch):
        with span("bench.on_step"):
            rec["dts"].append(dt)
            rec["kinds"].append(name)
            rec["losses"].append(loss)

    n = window_steps(tau, seconds, est)
    c0 = Compiles.n
    t0 = time.perf_counter()
    with span("bench.window"):
        tr.args.steps = n
        train.run(tr, on_step)
        elapsed = time.perf_counter() - t0
        if elapsed < seconds:
            period = elapsed * tau / n
            tr.args.steps = tau * math.ceil((seconds - elapsed) / period)
            train.run(tr, on_step)
    rec["wall_s"] = time.perf_counter() - t0
    rec["compiles"] = Compiles.n - c0
    return rec


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# --------------------------------------------------------------------------- #
# the reference's readings and the comparison
# --------------------------------------------------------------------------- #
def reference_readings(cfg: dict, tf: dict, chips: int, seed: int, devices,
                       n_steps: int, lowp: Optional[str] = None,
                       faults: bool = False) -> dict:
    """Follows the first ``n_steps`` steps with the plain reference (or its
    lower-precision control) on ``devices``.  With ``faults`` it also reads,
    at no extra cost, the first step as a run that left out half of its
    rows would show it (on one device), and with several workers as worker
    0 would see it with the exchange left out (its own rows alone)."""
    import jax
    import jax.numpy as jnp
    ref_mod = reference_module(cfg)
    ref = ref_mod.DenseLM(cfg["model"], devices, lowp=lowp)
    m = chips
    d = ref.dim
    # the trainer's default ZO step size: lr * 50 / d
    zo_lr = tf["lr"] * 50.0 / d if tf["zo_lr"] is None else tf["zo_lr"]
    st = ref_mod.Stepper(ref, seed, tf["lr"], tf["mu"], zo_lr, m)
    feed = token_batches(cfg["model"]["vocab_size"], global_batch(tf, chips),
                         tf["seq"], seed, tf["zipf_a"])
    params = ref.init(seed)
    p0 = params[0]
    out: Dict[str, Any] = {"losses": [],
                           "paths": [p for p, _, _ in ref.leaf_specs],
                           "shapes": [s for _, s, _ in ref.leaf_specs]}
    sq = jax.jit(lambda a, b: jnp.sum(jnp.square(a.astype(jnp.float32)
                                                 - b.astype(jnp.float32))))
    sq1 = jax.jit(lambda a: jnp.sum(jnp.square(a)))
    lr = np.float32(tf["lr"])

    def recovered_norms(g, scale):
        p1 = ref_mod._j_sgd(p0, g, lr, np.float32(scale))
        return [math.sqrt(v) / tf["lr"] for v in ref.leaf_sq_norms(sq, p0, p1)]

    for t, kind in enumerate(step_kinds(tf["tau"], n_steps)):
        batch = next(feed)
        tok, lab = batch["tokens"], batch["labels"]
        if kind == "fo":
            hooks = {}
            if faults and t == 0:
                def fault(key, n):
                    def hook(g0):
                        cnt = int(np.sum(lab[:n] >= 0))
                        out[f"{key}_grad_norms"] = recovered_norms(g0, 1 / cnt)
                    return hook
                # half the batch: rows 0 .. B/2-1, where one device holds
                # them all; device 0's share alone (worker 0's rows, as it
                # would step with the exchange left out) where m > 1
                if len(devices) == 1:
                    hooks[tok.shape[0] // 2] = fault("half", tok.shape[0] // 2)
                if m > 1:
                    hooks[tok.shape[0] // m] = fault("solo", tok.shape[0] // m)
            params, ce, g, count = st.fo(params, tok, lab, hooks)
            loss = sum(ce) / count
            if faults and t == 0:
                for key, n in (("half", tok.shape[0] // 2),
                               ("solo", tok.shape[0] // m)):
                    out[f"{key}_loss0"] = sum(ce[:n]) / int(np.sum(lab[:n] >= 0))
            if t == 0:
                out["grad_norms"] = recovered_norms(g, 1.0 / count)
                out["grad_norms_exact"] = [
                    math.sqrt(v) / count for v in ref.leaf_sq_norms(sq1, g)]
            del g
        else:
            params, loss = st.zo(params, t, tok, lab)
        out["losses"].append(loss)
    out["change_norms"] = [math.sqrt(v) for v in
                           ref.leaf_sq_norms(sq, params[0], p0)]
    return out


def compare(prog: dict, ref: dict, lim: dict, window: Optional[dict]) -> List:
    """[(name, value, limit)]; the run is correct when no value is over its
    limit and every value is finite."""
    if [tuple(s) for s in prog["shapes"]] != [tuple(s) for s in ref["shapes"]]:
        raise SystemExit(f"parameter leaves differ: program {prog['shapes']} "
                         f"reference {ref['shapes']}")
    steps = lim.get("loss_steps") or range(len(ref["losses"]))
    lp = np.asarray(prog["losses"])[list(steps)]
    lr_ = np.asarray(ref["losses"])[list(steps)]
    exact = np.asarray(ref["grad_norms_exact"])
    keep = exact >= 1e-3 * np.median(exact)
    numbers = {
        "loss_gap": float(np.max(np.abs(lp - lr_) / np.abs(lr_))),
        "grad_gap": float(np.max(leaf_gaps(prog["grad_norms"],
                                           ref["grad_norms"]))),
        "change_gap_median": float(np.median(leaf_gaps(
            prog["change_norms"], ref["change_norms"], keep))),
    }
    checks = [(k, v, lim[k]) for k, v in numbers.items() if k in lim]
    if window is not None:
        bad = sum(1 for x in window["losses"] if not math.isfinite(x))
        checks.append(("window_nonfinite_losses", float(bad), 0.0))
        checks.append(("window_compiles", float(window["compiles"]), 0.0))
    return checks


def leaf_gaps(prog, ref, keep=None) -> np.ndarray:
    """Per leaf, |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf; leaves whose
    ``keep`` is False are left out."""
    ref = np.asarray(ref, np.float64)
    prog = np.asarray(prog, np.float64)
    keep = np.ones(len(ref), bool) if keep is None else np.asarray(keep)
    den = np.maximum(ref, float(np.median(ref[keep])))
    return (np.abs(prog - ref) / np.where(den > 0, den, 1.0))[keep]


def is_correct(checks) -> bool:
    return all(math.isfinite(v) and v <= lim for _, v, lim in checks)


def free_program(tr):
    """Drops the program's device state before the reference runs."""
    tr.params = tr.opt_state = None
    tr.jitted.clear()
    tr.steps.clear()
    gc.collect()


# --------------------------------------------------------------------------- #
# one run of one cell
# --------------------------------------------------------------------------- #
def flop_counts(cfg: dict) -> tuple:
    """``(forward_flops_per_token, attention_flops)`` of the configuration:
    its reference module's where it defines them, else ``step_flops``'
    counts of a dense decoder."""
    ref = reference_module(cfg)
    return (getattr(ref, "forward_flops_per_token",
                    sf.forward_flops_per_token),
            getattr(ref, "attention_flops", sf.attention_flops))


def read_trace(trace_dir: str, chips: int, scopes: dict, log=print) -> tuple:
    """The traced window's device timings (``xplane_reduce``) and its
    program spans and device scopes (``span_reduce``, with the step
    programs' op scopes); the trace directory is removed."""
    import shutil
    import span_reduce
    import xplane_reduce
    try:
        red = xplane_reduce.reduce_dir(trace_dir, chips)
        spans = span_reduce.reduce_dir(trace_dir, scopes)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"trace: window {red['window_s']:.3f} s, busy per device "
        f"{red['busy_s_per_device']}, collectives {red['collective_s']:.4f}"
        f" s ({red['collective_exposed_s']:.4f} s exposed), programs "
        f"{ {k: len(v) for k, v in red['modules'].items()} }")
    log(f"spans: idle {spans['idle']}; scoped share "
        f"{ {k: p['scoped_share'] for k, p in spans['programs'].items()} }")
    return red, spans


def trace_record(cfg: dict, tf: dict, chips: int, win: dict, red: dict,
                 spans: dict, device_kind: str) -> dict:
    """What the per-layer readers read: the window, its trace and spans,
    the model FLOPs of a step of each kind (all chips), attention's FLOPs
    in one forward over one chip's rows, and the chip's peak."""
    fwd, attn = flop_counts(cfg)
    model, seq = cfg["model"], tf["seq"]
    step_tokens = global_batch(tf, chips) * seq
    return {"chips": chips, "tau": tf["tau"], "window": win,
            "n_steps": len(win["dts"]), "trace": red, "spans": spans,
            "step_flops": {k: sf.step_flops(model, seq, step_tokens, k, fwd)
                           for k in sf.PASSES},
            "attention_flops": attn(model, seq) * step_tokens / chips,
            "peak_flops": sf.peaks(device_kind)["bf16_flops_per_s"]}


def per_layer_metrics(wl_name: str, rec: dict) -> Dict[str, dict]:
    """Every per-layer metric of ``BENCHMARK.json`` that this cell lists (or
    that lists no cells), as its reader finds it."""
    out = {}
    for m in benchmark()["per_layer"]:
        if wl_name not in m.get("workloads", [wl_name]):
            continue
        v = metric_reader(m["name"])(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(wl: dict, cfg: dict, tf: dict, lim: dict, seed: int,
             seconds: float, trace: bool, devices, t_start: float,
             program_hook=None, log=print) -> dict:
    """Set-up, window, memory, reference, comparison: the result's dict."""
    import jax
    chips = wl["chips"]
    devices = list(devices)[:chips]
    Compiles.install()
    train, tr = build_program(cfg, tf, chips, seed)
    if program_hook is not None:
        program_hook(train, tr)
    n_follow = tf["follow_steps"]
    warm = warm_up(train, tr, n_follow)
    est = step_estimates(warm)
    log(f"warm-up losses {warm['losses']} step seconds {warm['dts']} "
        f"compile seconds {warm['compile_s']}; step seconds estimated "
        f"{est}; set-up: {Compiles.backend} compilations, "
        f"{Compiles.n - Compiles.backend} cache loads")
    if trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    win = timed_window(train, tr, tf["tau"], seconds, est, annotate=trace)
    if trace:
        jax.profiler.stop_trace()
    n_steps = len(win["dts"])
    mem = memory_peak_bytes(devices)
    if trace:
        scopes = tr.op_scopes()
    tokens = n_steps * global_batch(tf, chips) * tf["seq"]
    log(f"window: {n_steps} steps in {win['wall_s']:.3f} s, step seconds "
        f"{[round(x, 4) for x in win['dts']]}, compiles {win['compiles']}")
    free_program(tr)
    del tr
    t_ref = time.perf_counter()
    ref = reference_readings(cfg, tf, chips, seed, devices, n_follow)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s, losses "
        f"{ref['losses']}")
    checks = compare(warm, ref, lim, win)
    for key in ("grad_norms", "change_norms"):
        log(f"per-leaf {key} (program, reference): " + ", ".join(
            f"{p}={a:.6g}/{b:.6g}" for p, a, b in
            zip(ref["paths"], warm[key], ref[key])))
    result = {"correct": is_correct(checks), "attempted": n_steps,
              "failed": int(sum(1 for x in win["losses"]
                                if not math.isfinite(x)))}
    if trace:
        red, spans = read_trace(trace_dir, chips, scopes, log)
        rec = trace_record(cfg, tf, chips, win, red, spans,
                           devices[0].device_kind)
        result["metrics"] = per_layer_metrics(wl["name"], rec)
        result["breakdown"] = red["breakdown"]
        busy, window_s = red["busy_s"], red["window_s"]
    else:
        result["metrics"] = {
            "train_tokens_per_s": {"value": tokens / win["wall_s"],
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    d0 = devices[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": jax.device_count(),
                        "memory_peak_bytes": mem}
    if trace:
        result["device"].update(busy_s=busy, window_s=window_s)
    result["checks"] = {n: {"value": v, "limit": l} for n, v, l in checks}
    return result
