"""Training driver.

Runs real optimization steps on whatever devices exist: one device with no
mesh, or every chip of a mesh (``train(..., mesh=...)``) — the same code
path, only the mesh changes.  For CPU-scale runs pass a reduced arch
(``--reduced``).

  PYTHONPATH=src python -m repro.launch.train --arch smile-3.7b --reduced \
      --steps 50 --batch 16 --seq 128
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.common.config import (MOE_OPTIONS, TRAIN_OPTIONS, ModelConfig,
                                 TrainConfig)
from repro.configs import get_config, get_reduced
from repro.data.pipeline import DataPipeline
from repro.models.transformer import init_model
from repro.optim import make_optimizer, make_schedule
from repro.sharding.plan import plan_from_mesh, single_device_plan
from repro.sharding.specs import param_specs
from repro.train.checkpoint import CheckpointManager, save_checkpoint
from repro.train.step import build_train_step, opt_state_specs, zero1_state

_UNSET = object()       # float-flag default (argparse type-converts string
                        # defaults, so "" cannot be the sentinel there)


def _float_or_off(v: str):
    """argparse type for float options: a number, or off/none -> None.
    Raising ValueError here gives the clean 'usage:' argparse error instead
    of a traceback."""
    if v in ("off", "none"):
        return None
    return float(v)


def add_option_flags(ap, options) -> None:
    """Add one CLI flag per registry entry (generic over option kinds).

    Empty string / unset = keep the config's setting; bools take
    ``on``/``off`` (bare ``--flag`` means ``on``); floats take a number or
    ``off`` (-> None); ints and strings pass through.  The registry is the
    single source of truth, so a knob registered in ``MOE_OPTIONS`` or
    ``TRAIN_OPTIONS`` cannot silently miss this launcher.
    """
    for opt in options:
        if opt.kind == "choice":
            ap.add_argument(opt.flag, default="",
                            choices=("",) + opt.choices, help=opt.help)
        elif opt.kind == "bool":
            ap.add_argument(opt.flag, default="", nargs="?", const="on",
                            choices=("", "on", "off"), help=opt.help)
        elif opt.kind == "float":
            ap.add_argument(opt.flag, default=_UNSET, type=_float_or_off,
                            help=opt.help + " (number, or 'off' for None)")
        elif opt.kind == "int":
            ap.add_argument(opt.flag, default=_UNSET, type=int,
                            help=opt.help)
        else:  # "str"
            ap.add_argument(opt.flag, default="", help=opt.help)


def parse_option_flags(args, options) -> dict:
    """Collect registry-derived flags back into a {field: value} dict —
    only the flags the user actually set."""
    opts = {}
    for opt in options:
        v = getattr(args, opt.field)
        if v is _UNSET or v == "":
            continue
        if opt.kind == "bool":
            opts[opt.field] = v == "on"
        else:       # choice/str (str) / float / int (argparse-converted)
            opts[opt.field] = v
    return opts


def add_moe_option_flags(ap) -> None:
    """MoE registry flags (``--dispatch-backend``, ``--ragged-a2a``, ...)."""
    add_option_flags(ap, MOE_OPTIONS)


def parse_moe_option_flags(args) -> dict:
    """Collect the MoE registry flags back into a with_options dict."""
    return parse_option_flags(args, MOE_OPTIONS)


def train(arch: str | ModelConfig, *, reduced: bool = True, steps: int = 50,
          batch: int = 16, seq: int = 128, lr: float = 3e-4,
          optimizer: str = "lamb", seed: int = 0, log_every: int = 10,
          ckpt: str = "", mesh=None, micro_batch: int = 0,
          log_file: str = "", zero1: bool = False, eval_every: int = 0,
          moe_options: dict | None = None, dispatch_backend: str = "",
          ragged_a2a: str = "", sort_impl: str = "",
          sentinel: bool = False, resume: bool = False,
          ckpt_every: int = 0, ckpt_keep: int = 3, ckpt_dir: str = "",
          halt_after: int = 0):
    """Run (or resume) a training run.

    ``arch`` is a registry name (``reduced`` picks its 2-layer variant) or
    a :class:`ModelConfig`, trained as given.  With a ``mesh``, parameters
    and optimizer state are created already sharded by the step's specs.

    Robust-runtime knobs: ``sentinel`` turns on the in-jit step sentinel
    (bad steps skipped, anomaly counters carried + checkpointed);
    ``ckpt_dir`` + ``ckpt_every`` keep a ``ckpt_keep``-deep checksummed
    rotation; ``resume`` restores the newest valid snapshot from
    ``ckpt_dir`` (corrupt ones fall back) and fast-forwards the
    deterministic data stream so a resumed run is bit-identical to an
    uninterrupted one.  ``halt_after`` stops after that many steps while
    keeping the FULL ``steps`` schedule horizon — the crash-simulation
    hook the resume-determinism test uses.
    """
    if isinstance(arch, ModelConfig):
        cfg = arch
    else:
        cfg = get_reduced(arch) if reduced else get_config(arch)
    # moe_options is the registry-validated path; the three string kwargs
    # are the legacy surface, folded in for backward compatibility
    opts = dict(moe_options or {})
    if dispatch_backend:
        opts.setdefault("dispatch_backend", dispatch_backend)
    if ragged_a2a:
        opts.setdefault("ragged_a2a", ragged_a2a == "on")
    if sort_impl:
        opts.setdefault("sort_impl", sort_impl)
    if opts:
        from repro.configs import with_options
        cfg = with_options(cfg, **opts)
    plan = plan_from_mesh(mesh) if mesh is not None else single_device_plan()
    tcfg = TrainConfig(global_batch_size=batch, seq_len=seq, steps=steps,
                       optimizer=optimizer, lr=lr, warmup_steps=max(steps // 10, 1),
                       micro_batch_size=micro_batch, seed=seed,
                       sentinel=sentinel, ckpt_every=ckpt_every,
                       ckpt_keep=ckpt_keep, ckpt_dir=ckpt_dir)

    key = jax.random.PRNGKey(seed)
    opt = make_optimizer(optimizer)
    sched = make_schedule("cosine", lr, tcfg.warmup_steps, steps)

    def init_state():
        params = init_model(key, cfg, plan)
        if zero1:
            return params, zero1_state(params, cfg, plan)
        return params, opt.init(params)

    shardings = None
    if mesh is None:
        params, opt_state = init_state()
    else:
        # initialised in place, by the step's own specs: no leaf is ever
        # whole on one chip (the partitionable threefry draws the same
        # numbers however the arrays are sharded)
        pspec = param_specs(jax.eval_shape(init_state)[0], cfg, plan)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            (pspec, opt_state_specs(pspec, plan, zero1=zero1)),
            is_leaf=lambda x: isinstance(x, P))
        params, opt_state = jax.jit(init_state, out_shardings=shardings)()
    sent = None
    if sentinel:
        from repro.train.sentinel import init_sentinel_state
        sent = init_sentinel_state()

    mgr = CheckpointManager(ckpt_dir, keep=ckpt_keep) if ckpt_dir else None
    start = 0
    if resume:
        if mgr is None:
            raise ValueError("--resume needs --ckpt-dir (the rotation to "
                             "resume from)")
        got = mgr.restore_latest(params, opt_state, extra_like=sent)
        if got is not None:
            if sentinel:
                params, opt_state, start, sent = got
            else:
                params, opt_state, start = got
            if shardings is not None:
                params, opt_state = jax.device_put((params, opt_state),
                                                  shardings)
            print(f"resumed from step {start} ({mgr.dir})")
        else:
            print(f"no valid checkpoint in {mgr.dir} — starting fresh")

    pipe = DataPipeline(cfg, batch, seq, seed=seed)
    sample = next(pipe)                          # draw 0 (step 1's batch)
    batch0 = {k: jnp.asarray(v) for k, v in sample.items()}
    # the data stream is deterministic in (seed, draw index): skip the
    # draws the restored steps already consumed so step S+1 sees the same
    # batch it would have in the uninterrupted run
    for _ in range(max(start - 1, 0)):
        next(pipe)
    step_fn, _ = build_train_step(cfg, tcfg, plan, opt, sched, params,
                                  batch0, mesh=mesh, zero1=zero1,
                                  sentinel=sentinel)

    history = []
    t0 = time.time()
    until = min(steps, halt_after + start) if halt_after else steps
    for i in range(start, until):
        b = batch0 if i == 0 else {k: jnp.asarray(v) for k, v in next(pipe).items()}
        if sentinel:
            params, opt_state, m, sent = step_fn(params, opt_state, b,
                                                 jnp.int32(i + 1), sent)
            anomaly = float(m["skip"]) > 0
        else:
            params, opt_state, m = step_fn(params, opt_state, b,
                                           jnp.int32(i + 1))
            anomaly = False
        if (i + 1) % log_every == 0 or i == start:
            m = {k: float(v) for k, v in m.items()}
            toks = batch * seq * (i + 1 - start)
            dt = time.time() - t0
            extra = (f" skip {m['skip']:.0f}" if sentinel else "")
            print(f"step {i+1:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
                  f"lb {m['lb']:.4f} drop {m['drop_frac']:.3f} "
                  f"gnorm {m['grad_norm']:.2f} tok/s {toks/dt:,.0f}{extra}")
            history.append({"step": i + 1, **m, "tokens_per_s": toks / dt})
        if anomaly and mgr is not None:
            # the skipped step left params bit-unchanged: this snapshot IS
            # the last good state, taken while it is still current
            mgr.save(i + 1, params, opt_state, extra=sent)
            print(f"step {i+1}: anomaly (update skipped) — snapshot saved")
        elif mgr is not None and ckpt_every and (i + 1) % ckpt_every == 0:
            mgr.save(i + 1, params, opt_state, extra=sent)
        if eval_every and (i + 1) % eval_every == 0:
            from repro.train.evaluate import evaluate
            ev = evaluate(params, cfg, plan, batch=batch, seq=seq, seed=seed,
                          n_batches=2)
            print(f"  eval ce {ev['eval_ce']:.4f} ppl {ev['eval_ppl']:.1f}")
            history.append({"step": i + 1, **ev})
    pipe.close()
    if sentinel and sent is not None:
        history.append({"sentinel": {
            k: float(getattr(sent, k)) for k in
            ("steps", "skipped", "nonfinite", "spikes", "router_alarms")}})
    if ckpt:
        save_checkpoint(ckpt, params, opt_state, until, extra=sent)
        print(f"saved checkpoint -> {ckpt}")
    if log_file:
        with open(log_file, "w") as f:
            json.dump(history, f, indent=1)
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="lamb")
    ap.add_argument("--micro-batch", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-file", default="")
    ap.add_argument("--zero1", action="store_true",
                    help="shard optimizer state over replicated axes")
    ap.add_argument("--eval-every", type=int, default=0)
    # MoE dispatch flags AND the robust-runtime flags (--sentinel,
    # --resume, --ckpt-every, --ckpt-keep, --ckpt-dir) are DERIVED from the
    # option registries (repro.common.config.MOE_OPTIONS / TRAIN_OPTIONS) —
    # a knob registered there is automatically reachable here, and the
    # dryrun --opt tokens stay in sync by construction
    add_moe_option_flags(ap)
    add_option_flags(ap, TRAIN_OPTIONS)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    train(args.arch, reduced=args.reduced, steps=args.steps, batch=args.batch,
          seq=args.seq, lr=args.lr, optimizer=args.optimizer, seed=args.seed,
          ckpt=args.ckpt, micro_batch=args.micro_batch,
          log_file=args.log_file, zero1=args.zero1,
          eval_every=args.eval_every,
          moe_options=parse_moe_option_flags(args),
          **parse_option_flags(args, TRAIN_OPTIONS))


if __name__ == "__main__":
    main()
