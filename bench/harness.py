"""One run of one benchmark cell: set-up, measured window, trace, check.

A cell is ``bench/workloads/<cell>.json``; it names a configuration
(``bench/configs/<config>.json``), a traffic mix (``bench/traffic/<mix>.json``)
and the chips it takes.  Per-layer metrics are the readers under
``bench/metrics/``.  Everything is found by name, so a new cell, mix,
configuration or metric is a new file.

The run drives the program's own training step (``build_train_step``, the
XLA path, LAMB and the cosine schedule, as ``repro.launch.train.train``
builds it) on parameters the benchmark makes from the seed:

1. set-up: the step, its parameters and optimizer state; three steps through
   the window's own loop, which compile and warm every shape and give the
   numbers the check compares;
2. the window: steps back to back for ``--seconds``, batches prefetched by a
   host thread, each step's loss fetched two steps behind; a step's
   completion time is when its loss arrives;
3. after the window: peak memory, the state freed, the plain reference runs
   the same three steps, and ``correct`` is decided from the gaps.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from bench import data as D
from bench import flops as F
from bench import trace as T

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
LAG = 2                       # a step's loss is fetched two steps later
CHECK_STEPS = 3               # set-up steps the reference repeats
TRACE_AFTER = 3               # window steps before the profiler starts
CHECKS = ("loss_gap", "grad_gap", "change_gap", "grad_diff", "grad_diff_worst")


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# =============================================================================
# Files
# =============================================================================

def load_json(kind: str, name: str, base: Path = BENCH) -> dict:
    return json.loads((base / kind / f"{name}.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict

    @property
    def chips(self) -> int:
        return self.workload["chips"]

    @property
    def mesh(self) -> tuple:
        return tuple(self.workload["mesh"])

    @property
    def batch(self) -> int:
        return self.traffic["batch"]

    @property
    def seq(self) -> int:
        return self.traffic["seq"]

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq


def load_cell(name: str, base: Path = BENCH) -> Cell:
    w = load_json("workloads", name, base)
    return Cell(name, w, load_json("configs", w["config"], base),
                load_json("traffic", w["traffic"], base))


def metric_modules(base: Path = BENCH) -> dict:
    """Every per-layer reader under ``bench/metrics/``, by metric name."""
    out = {}
    for path in sorted((base / "metrics").glob("*.py")):
        if path.stem.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            f"bench.metrics.{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[mod.NAME] = mod
    return out


def peak(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def reference_module(cfg: dict):
    """The configuration's plain reference, ``bench/references/<name>.py``
    (what it exports: ``bench/references/__init__.py``)."""
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def train_flops_per_token(cfg: dict, seq: int) -> int:
    """Model FLOPs of a training step per token: the reference's count where
    it gives one, else the MLM encoder's (``bench/flops.py``)."""
    count = getattr(reference_module(cfg), "train_flops_per_token",
                    F.train_flops_per_token)
    return count(cfg, seq)


def enable_compile_cache(metadata: bool = False) -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one.

    JAX keys the cache on the program without its metadata, so a step
    compiled before a scope was renamed would come back with the old
    ``op_name``s, and every op of it unattributed.  ``metadata`` keys on
    them too: a run that reads the named scopes sets it; a run that does not
    leaves the key, and its set-up, as they were."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", metadata)
    return path


# =============================================================================
# The program under test
# =============================================================================

def model_config(cfg: dict):
    from repro.common.config import ModelConfig, MoEConfig
    moe = dict(cfg["moe"], grid=tuple(cfg["moe"]["grid"]))
    return ModelConfig(name=cfg["name"], source=cfg["source"],
                       moe=MoEConfig(**moe), **cfg["model"])


def _shapes(tree) -> dict:
    return {path: tuple(x.shape) for path, x in flat_with_paths(tree).items()}


def flat_with_paths(tree) -> dict:
    """Leaves keyed by their path, ``a/b/0/c``."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)] = leaf
    return out


class Program:
    """The jitted train step with its state, and the loop that drives it."""

    def __init__(self, cell: Cell, seed: int, devices: list):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, SingleDeviceSharding
        from jax.sharding import PartitionSpec as P
        from repro.common.config import TrainConfig
        from repro.launch.mesh import make_test_mesh
        from repro.models.transformer import init_model
        from repro.optim import make_optimizer, make_schedule
        from repro.sharding.plan import plan_from_mesh, single_device_plan
        from repro.sharding.specs import batch_specs, param_specs
        from repro.train.step import build_train_step, opt_state_specs

        ref = reference_module(cell.config)
        tr = cell.config["train"]
        cfg = model_config(cell.config)
        self.cell = cell
        if cell.chips == 1:
            mesh, plan = None, single_device_plan()
        else:
            mesh = make_test_mesh(*cell.mesh)
            plan = plan_from_mesh(mesh)
        got = _shapes(jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0),
                                                        cfg, plan)))
        want = _shapes(jax.eval_shape(
            lambda: ref.init_params(cell.config, jax.random.key(0))))
        if got != want:
            raise ValueError(f"the program's parameter layout differs from "
                             f"the reference's: {sorted(set(got.items()) ^ set(want.items()))}")
        opt = make_optimizer(tr["optimizer"], weight_decay=tr["weight_decay"],
                             b1=tr["b1"], b2=tr["b2"], eps=tr["eps"])
        sched = make_schedule("cosine", tr["lr"], tr["warmup_steps"],
                              tr["total_steps"])
        tcfg = TrainConfig(global_batch_size=cell.batch, seq_len=cell.seq,
                           steps=tr["total_steps"], optimizer=tr["optimizer"],
                           lr=tr["lr"], warmup_steps=tr["warmup_steps"],
                           weight_decay=tr["weight_decay"],
                           grad_clip=tr["grad_clip"], eps=tr["eps"],
                           b1=tr["b1"], b2=tr["b2"], seed=seed)
        batch_like = {k: jax.ShapeDtypeStruct((cell.batch, cell.seq), jnp.int32)
                      for k in ("tokens", "labels")}

        def init_state(key):
            p = ref.init_params(cell.config, key)
            return p, opt.init(p)

        params_like = jax.eval_shape(init_state, jax.random.key(0))[0]
        if mesh is None:
            state_sh = self.batch_sh = SingleDeviceSharding(devices[0])
        else:
            pspec = param_specs(params_like, cfg, plan)
            state_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                (pspec, opt_state_specs(pspec, plan)),
                is_leaf=lambda x: isinstance(x, P))
            self.batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                         batch_specs(batch_like, plan),
                                         is_leaf=lambda x: isinstance(x, P))
        self._ref = ref
        self._init = jax.jit(init_state, out_shardings=state_sh)
        self.step_fn, _ = build_train_step(cfg, tcfg, plan, opt, sched,
                                           params_like, batch_like, mesh=mesh)
        self._norms = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))
        self._change = jax.jit(lambda p, k: jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p,
            ref.init_params(cell.config, k)))
        self.b1 = tr["b1"]
        self.loader = None
        self.reset(seed)

    def reset(self, seed: int) -> None:
        """Fresh parameters, optimizer state and data stream for ``seed``."""
        if self.loader is not None:
            self.loader.close()
        self.key = self._ref.seed_key(seed)
        self.params, self.opt_state = self._init(self.key)
        self.loader = D.Loader(self.cell.traffic, self.cell.batch,
                               self.cell.config["model"]["vocab_size"], seed)
        self.steps = 0

    def put(self, batch: dict):
        import jax
        return jax.device_put(batch, self.batch_sh)

    def run(self, *, steps: Optional[int] = None, seconds: Optional[float] = None,
            after: Optional[Callable] = None, tracer=None) -> dict:
        """Run steps back to back until ``steps`` are done or ``seconds``
        have passed.  Returns the start time, each step's completion time and
        loss, and the host seconds spent waiting for input."""
        import jax
        span = jax.profiler.TraceAnnotation
        pending = collections.deque()
        done, losses = [], []
        wait = 0.0
        longest = dict.fromkeys(("batch_wait", "dispatch", "fetch_metrics"), 0.0)
        n = 0
        t0 = time.perf_counter()

        def fetch():
            a = time.perf_counter()
            with span("fetch_metrics"):
                losses.append(float(pending.popleft()))
                done.append(time.perf_counter())
            longest["fetch_metrics"] = max(longest["fetch_metrics"], done[-1] - a)

        while True:
            if steps is not None and n >= steps:
                break
            if seconds is not None and time.perf_counter() - t0 >= seconds:
                break
            if tracer is not None:
                tracer.at_step(n)
            with span("batch_wait"):
                a = time.perf_counter()
                b = self.put(self.loader.get())
                dt = time.perf_counter() - a
                wait += dt
            longest["batch_wait"] = max(longest["batch_wait"], dt)
            a = time.perf_counter()
            with span("dispatch"):
                self.params, self.opt_state, m = self.step_fn(
                    self.params, self.opt_state, b, np.int32(self.steps + 1))
            longest["dispatch"] = max(longest["dispatch"], time.perf_counter() - a)
            self.steps += 1
            n += 1
            if after is not None:
                after(self.steps)
            pending.append(m["loss"])
            if len(pending) > LAG:
                fetch()
        while pending:
            fetch()
        if tracer is not None:
            tracer.at_step(None)
        return {"t0": t0, "done": done, "losses": losses, "wait": wait,
                "longest": longest}

    def check_steps(self, keep_grads: bool = False) -> dict:
        """The set-up steps: compile, warm, and read what the check compares:
        each step's loss, each leaf's norm of the first gradient as LAMB got
        it (first moment after one step / (1 - b1)), and each leaf's change
        over the steps.  ``keep_grads`` also copies that gradient to the host."""
        import jax
        got = {}

        def after(step):
            if step == 1:
                got["m"] = self._norms(self.opt_state["m"])
                if keep_grads:
                    got["grads"] = {k: np.asarray(v) / (1 - self.b1) for k, v in
                                    flat_with_paths(jax.device_get(
                                        self.opt_state["m"])).items()}
            if step == CHECK_STEPS:
                got["change"] = self._change(self.params, self.key)

        out = self.run(steps=CHECK_STEPS, after=after)
        m, change = jax.device_get((got["m"], got["change"]))
        res = {"losses": out["losses"],
               "grad_norms": {k: float(v) / (1 - self.b1)
                              for k, v in flat_with_paths(m).items()},
               "change_norms": {k: float(v)
                                for k, v in flat_with_paths(change).items()}}
        if keep_grads:
            res["grads"] = got["grads"]
        return res

    def free_state(self) -> None:
        """Free the parameters and optimizer state on the device."""
        import jax
        for x in jax.tree.leaves((self.params, self.opt_state)):
            x.delete()
        self.params = self.opt_state = None

    def close(self) -> None:
        """Stop the loader and free the state on the device."""
        self.loader.close()
        self.loader = None
        if self.params is not None:
            self.free_state()


class Tracer:
    """Starts the profiler ``TRACE_AFTER`` steps into the window and stops
    it ``steps`` later; the stretch between is marked by a host span."""

    def __init__(self, directory: str, steps: int):
        self.dir, self.steps = directory, steps
        self.span = None
        self.stopped = False

    def at_step(self, n: Optional[int]) -> None:
        import jax
        if n == TRACE_AFTER and self.span is None:
            jax.profiler.start_trace(self.dir)
            self.span = jax.profiler.TraceAnnotation(T.WINDOW_SPAN)
            self.span.__enter__()
        elif self.span is not None and not self.stopped and (
                n is None or n == TRACE_AFTER + self.steps):
            self.span.__exit__(None, None, None)
            self.stopped = True
            jax.profiler.stop_trace()


class CompileCounter:
    """Counts tracing and compilation events while ``active``."""

    PREFIX = "/jax/core/compile/"

    def __init__(self):
        self.active = False
        self.count = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.active and event.startswith(self.PREFIX):
            self.count += 1


# =============================================================================
# Correctness
# =============================================================================

def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Per leaf: the gap between the program's and the reference's norms of
    the first gradient (``grad``) and of the change over the steps
    (``change``), each over the larger of that leaf's reference norm and the
    median leaf's; and the norm of the difference of the first gradients over
    the reference's norm (``diff``).  Leaves whose reference gradient is
    under a thousandth of the median leaf's move by rounding alone and have
    no ``change``."""
    rg, rc = ref["grad_norms"], ref["change_norms"]
    if set(rg) != set(prog["grad_norms"]) or set(rc) != set(prog["change_norms"]):
        raise ValueError("program and reference leaves differ")
    med_g = statistics.median(rg.values())
    moving = [k for k, v in rg.items() if v >= 1e-3 * med_g]
    med_c = statistics.median(rc[k] for k in moving)
    out = {
        "grad": {k: abs(prog["grad_norms"][k] - v) / max(v, med_g) for k, v in rg.items()},
        "change": {k: abs(prog["change_norms"][k] - rc[k]) / max(rc[k], med_c)
                   for k in moving},
    }
    if "grads" in prog and "grads" in ref:
        out["diff"] = {k: float(np.linalg.norm((prog["grads"][k] - g).ravel())
                                / max(np.linalg.norm(g.ravel()), 1e-30))
                       for k, g in ref["grads"].items()}
    return out


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers ``correct`` compares, from the program's and the
    reference's readings of the same three steps:

    * ``loss_gap``: the largest relative gap of a step's loss;
    * ``grad_gap``: the worst leaf's gap of first-gradient norms;
    * ``change_gap``: the median leaf's gap of change norms (the worst leaf's
      swings with routing: see PERF.md);
    * ``grad_diff``: the median leaf's relative difference of the first
      gradients, the number that tells a lower precision from rounding;
    * ``grad_diff_worst``: the worst leaf's, which tells tokens sent to the
      wrong experts (an exchange left out) from routing flips.
    A cell compares the numbers its ``limits`` name."""
    lg = leaf_gaps(prog, ref)
    out = {"loss_gap": max(abs(a - b) / abs(b)
                           for a, b in zip(prog["losses"], ref["losses"])),
           "grad_gap": max(lg["grad"].values()),
           "change_gap": statistics.median(lg["change"].values())}
    if "diff" in lg:
        out["grad_diff"] = statistics.median(lg["diff"].values())
        out["grad_diff_worst"] = max(lg["diff"].values())
    return out


def judge(g: dict, limits: dict) -> bool:
    """Every number the cell has a limit for is finite and within it."""
    return all(math.isfinite(g[k]) and g[k] <= v for k, v in limits.items())


def run_reference(cell: Cell, seed: int, device, precision: str = "fp32",
                  keep_grads: bool = False) -> dict:
    ref = reference_module(cell.config)
    r = ref.Reference(cell.config, cell.mesh, cell.batch, cell.seq,
                      precision=precision, device=device)
    vocab = cell.config["model"]["vocab_size"]
    batches = [D.make_batch(cell.traffic, cell.batch, vocab, seed, i)
               for i in range(CHECK_STEPS)]
    return r.run(batches, ref.seed_key(seed), keep_grads=keep_grads)


# =============================================================================
# One run
# =============================================================================

def spans(done: list, t0: float, span_steps: int) -> list:
    """Per-step times over sliding spans of ``span_steps`` completions."""
    t = [t0] + list(done)
    return [(t[i + span_steps] - t[i]) / span_steps
            for i in range(len(t) - span_steps)]


def p95(values: list) -> float:
    return float(np.percentile(np.asarray(values), 95))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_accelerator: bool = True,
             cell: Optional[Cell] = None, plant=contextlib.nullcontext,
             log=lambda s: print(s, file=sys.stderr, flush=True)) -> dict:
    """Run cell ``name`` once and return its result line.

    ``plant`` is a context manager entered around the program's part of the
    run (tests and the calibration use it to break the timed path)."""
    import jax
    cell = cell or load_cell(name)
    devices = jax.devices()
    platform = devices[0].platform
    if require_accelerator and platform == "cpu":
        raise NoAccelerator("JAX found no accelerator")
    if len(devices) < cell.chips:
        raise NoAccelerator(f"cell {name} needs {cell.chips} chips, JAX "
                            f"found {len(devices)}")
    devices = devices[:cell.chips]
    kind = devices[0].device_kind
    pk = peak(kind) if platform != "cpu" else None
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    metrics_mods = metric_modules() if trace else {}
    try:
        with plant():
            prog = Program(cell, seed, devices)
            got = prog.check_steps(keep_grads=True)
            t_window = time.perf_counter()
            setup_s = t_window - t_start
            log(f"[bench] {name} seed {seed}: set-up {setup_s:.3f} s, "
                f"check-step losses {got['losses']}")
            step_module = None
            tdir, tracer = None, None
            if trace:
                step_module = _module_name(prog)
                tdir = tempfile.mkdtemp(prefix="bench_trace_")
                tracer = Tracer(tdir, cell.workload["trace_steps"])
            counter.active = True
            win = prog.run(seconds=seconds, tracer=tracer)
            counter.active = False
            mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
            scope_map = _op_scopes(prog) if trace else None
            prog.close()
            del prog
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    n = len(win["done"])
    window_s = win["done"][-1] - win["t0"]
    gaps_s = np.diff([win["t0"]] + win["done"])
    log(f"[bench] window: {n} steps in {window_s:.3f} s, compiles in window "
        f"{counter.count}; longest step gap {gaps_s.max():.4f} s at step "
        f"{int(gaps_s.argmax())}; longest host phase (s) "
        + ", ".join(f"{k} {v:.4f}" for k, v in win["longest"].items()))
    tokens_per_s = n * cell.tokens_per_step / window_s
    losses = got["losses"] + win["losses"]
    failed = sum(1 for x in losses if not math.isfinite(x))
    device = {"platform": platform, "kind": kind, "count": len(jax.devices()),
              "memory_peak_bytes": max((m for m in mem if m is not None), default=None)}
    if trace:
        tr = T.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        metrics, bd = per_layer_metrics(cell, tr, win, step_module,
                                        metrics_mods, pk, device, log,
                                        op_scopes=scope_map)
    else:
        step_s = spans(win["done"], win["t0"], cell.workload["span_steps"])
        metrics = {"tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"},
                   "step_ms_p95": {"value": 1e3 * p95(step_s), "unit": "ms"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        bd = None
    ref = run_reference(cell, seed, devices[0], keep_grads=True)
    g = gaps(got, ref)
    limits = cell.workload["limits"]
    correct = judge(g, limits) and failed == 0
    checks = {k: {"value": g[k], "limit": v} for k, v in limits.items()}
    out = {"correct": correct, "attempted": len(losses), "failed": failed,
           "metrics": metrics, "device": device}
    if bd is not None:
        out["breakdown"] = bd
    out["checks"] = checks
    return out


def _lower_step(prog: Program):
    """The step lowered for the arguments the window gives it."""
    import jax
    sh = prog.batch_sh
    b = {k: jax.ShapeDtypeStruct((prog.cell.batch, prog.cell.seq), np.int32,
                                 sharding=sh[k] if isinstance(sh, dict) else sh)
         for k in ("tokens", "labels")}
    return prog.step_fn.lower(prog.params, prog.opt_state, b, np.int32(1))


def _module_name(prog: Program) -> str:
    """Name of the step program's HLO module, as the trace shows it."""
    low = _lower_step(prog)
    return str(low.compiler_ir().operation.attributes["sym_name"]).strip('"')


def _op_scopes(prog: Program) -> dict:
    """``{instruction: op_name}`` of the step executable that ran, from its
    HLO text (the compile is a hit in the persistent cache)."""
    from bench import scopes
    return scopes.op_scopes(_lower_step(prog).compile().as_text())


class RunView:
    """What a per-layer reader may read of a traced run.  ``op_scopes`` maps
    the step's instructions to their ``op_name`` (``bench/scopes.py``); None
    where the run did not read it."""

    op_scopes: Optional[dict] = None

    def __init__(self, **kw):
        self.__dict__.update(kw)


def per_layer_metrics(cell, tr, win, step_module, mods, pk, device, log,
                      op_scopes=None):
    """The per-layer metrics of a traced run, read from its trace ``tr``
    once each device's window is cut to its whole steps.  Sets ``device``'s
    ``busy_s`` and ``window_s``; returns the metrics and the breakdown."""
    T.trim_to_steps(tr, step_module)
    for i, dev in enumerate(tr.devices):
        d = [b - a for _, a, b in dev.step_events] or [0.0]
        log(f"[bench] device {i}: {dev.steps} whole steps of {step_module} in "
            f"{dev.window_s:.6f} s from {dev.window[0]:.6f}: module "
            f"{min(d):.6f}-{max(d):.6f} s, busy {T.busy_s(dev, dev.window):.6f} s; "
            f"{sum(T.is_module(n, step_module) for n, _, _ in dev.modules)} "
            f"step module events in the trace")
    rate = T.traced_steps_per_s(tr)
    view = RunView(trace=tr, step_module=step_module, chips=cell.chips, peak=pk,
                   traced_tokens_per_s=rate and rate * cell.tokens_per_step,
                   flops_per_token=train_flops_per_token(cell.config, cell.seq),
                   input_wait_ms=1e3 * win["wait"] / max(len(win["done"]), 1),
                   op_scopes=op_scopes)
    metrics = {}
    for name, mod in mods.items():
        v = mod.read(view)
        if v is not None:
            metrics[name] = {"value": v, "unit": mod.UNIT}
    if tr.devices:
        n = len(tr.devices)
        device["busy_s"] = sum(T.busy_s(d, d.window) for d in tr.devices) / n
        device["window_s"] = sum(d.window_s for d in tr.devices) / n
    return metrics, T.breakdown(tr)
