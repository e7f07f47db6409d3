import os
# a CPU-only tool: 512 fake host devices stand in for the production mesh,
# and pinning the platform keeps it off any chip the machine has
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

For each combination this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod);
  2. lowers the REAL step function (train_step / prefill / decode_step —
     chosen by the input shape's kind) against ShapeDtypeStruct stand-ins
     (zero device allocation);
  3. compiles, printing ``memory_analysis()`` (fits-or-not evidence) and
     ``cost_analysis()`` (FLOPs / bytes for the roofline);
  4. parses the compiled HLO for collective ops and sums their bytes per
     class (all-to-all / all-reduce / ...), attributing DCN vs ICI by
     replica-group span;
  5. writes everything to ``experiments/dryrun/<arch>__<shape>__<mesh>.json``
     — the §Roofline and §Perf analyses read these files.

Usage:
  python -m repro.launch.dryrun --arch deepseek-v3-671b --shape train_4k
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  python -m repro.launch.dryrun --all --jobs 6          # full 10x4x2 sweep
"""

import argparse
import json
import re
import subprocess
import sys
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.common.config import (INPUT_SHAPES, MOE_DRYRUN_OPTS,
                                 TRAIN_DRYRUN_OPTS, TrainConfig)
from repro.configs import config_for_shape, supports_shape
from repro.launch import inputs as I
from repro.launch.hlo_analysis import analyze_hlo, collective_summary
from repro.launch.mesh import make_production_mesh
from repro.optim import make_optimizer, make_schedule
from repro.serve.decode import build_decode_step, build_prefill
from repro.sharding.plan import plan_from_mesh
from repro.train.step import build_train_step

def lower_one(arch: str, shape_name: str, multi_pod: bool,
              smile: Optional[bool] = None, opts: str = ""):
    shape = INPUT_SHAPES[shape_name]
    cfg = config_for_shape(arch, shape)
    if smile is not None and cfg.moe is not None:
        import dataclasses
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, router="smile" if smile else "switch"))
    opt_set = set(o for o in opts.split(",") if o)
    if "rsc" in opt_set:
        cfg = cfg.replace(remat_save_collectives=True)
    if "kvseq" in opt_set:
        cfg = cfg.replace(kv_seq_shard=True)
    # MoE --opt tokens are DERIVED from the options registry
    # (repro.common.config.MOE_DRYRUN_OPTS): "dropless", "padded_a2a",
    # "radix_sort", "recv_bound", "tightcap", ... — a knob registered there
    # is automatically reachable here, validated by MoEConfig.with_options.
    # Each token carries its prerequisites (recv_bound implies dropless +
    # ragged hops); contradictory tokens (e.g. padded_a2a + recv_bound)
    # fail loudly instead of one silently overriding the other.
    moe_kw, moe_src = {}, {}
    for tok in sorted(opt_set & MOE_DRYRUN_OPTS.keys()):
        for fld, val in MOE_DRYRUN_OPTS[tok].items():
            if fld in moe_kw and moe_kw[fld] != val:
                raise ValueError(
                    f"--opt tokens {moe_src[fld]!r} and {tok!r} disagree "
                    f"on {fld} ({moe_kw[fld]!r} vs {val!r})")
            moe_kw[fld] = val
            moe_src.setdefault(fld, tok)
    if moe_kw and cfg.moe is not None:
        from repro.configs import with_options
        cfg = with_options(cfg, **moe_kw)
    mesh = make_production_mesh(multi_pod=multi_pod)
    inter = ("pod", "data") if "epxpod" in opt_set else None
    plan = plan_from_mesh(mesh, smile_inter_axes=inter)
    pdtype = jnp.bfloat16 if "bf16p" in opt_set else None
    pstruct, pspec = I.params_struct(cfg, plan, mesh, dtype=pdtype)

    if shape.kind == "train":
        # train-loop --opt tokens come from the SAME registry contract as
        # the MoE ones (TRAIN_DRYRUN_OPTS): "sentinel" lowers the guarded
        # 5-ary step so its mesh cost/memory is measurable like any knob
        train_kw = {}
        for tok in sorted(opt_set & TRAIN_DRYRUN_OPTS.keys()):
            train_kw.update(TRAIN_DRYRUN_OPTS[tok])
        sentinel = bool(train_kw.get("sentinel", False))
        tcfg = TrainConfig(global_batch_size=shape.global_batch,
                           seq_len=shape.seq_len, micro_batch_size=1,
                           optimizer="lamb", sentinel=sentinel)
        opt = make_optimizer("lamb")
        sched = make_schedule("cosine", 3e-4, 100, 10000)
        bstruct, _ = I.train_batch_struct(cfg, shape, plan, mesh)
        zero1 = "zero1" in opt_set
        if zero1:
            from repro.optim.zero1 import state_specs
            from repro.sharding.specs import shard_axes, sharded_axes_only
            from repro.train.step import zero1_state
            ostruct = jax.eval_shape(
                lambda: zero1_state(pstruct, cfg, plan))
            ospec = state_specs(pspec, shard_axes(pspec, plan),
                                sharded_axes_only(pspec, plan))
            ostruct = I._sds(ostruct, ospec, mesh)
        else:
            ostruct = jax.eval_shape(opt.init, pstruct)
            ospec = {"m": pspec, "v": pspec, "step": P()}
            ostruct = I._sds(ostruct, ospec, mesh)
        sstruct = jax.ShapeDtypeStruct((), jnp.int32,
                                       sharding=NamedSharding(mesh, P()))
        step, _ = build_train_step(cfg, tcfg, plan, opt, sched, pstruct,
                                   bstruct, mesh=mesh, zero1=zero1,
                                   sentinel=sentinel)
        if sentinel:
            from repro.train.sentinel import init_sentinel_state
            xstruct = jax.eval_shape(init_sentinel_state)
            xstruct = I._sds(xstruct, jax.tree.map(lambda _: P(), xstruct),
                             mesh)
            lowered = step.lower(pstruct, ostruct, bstruct, sstruct, xstruct)
        else:
            lowered = step.lower(pstruct, ostruct, bstruct, sstruct)
    elif shape.kind == "prefill":
        from repro.models.transformer import init_caches
        from repro.sharding.specs import cache_specs
        tstruct, _ = I.prefill_batch_struct(cfg, shape, plan, mesh)
        cshapes = jax.eval_shape(lambda: init_caches(
            cfg, shape.global_batch, I.cache_length(cfg, shape), plan))
        cspec = cache_specs(cshapes, cfg, plan, shape.global_batch)
        cstruct = I._sds(cshapes, cspec, mesh)
        fn = build_prefill(cfg, plan, pstruct, tstruct, cstruct, mesh=mesh)
        lowered = fn.lower(pstruct, tstruct, cstruct)
    else:  # decode
        (tstruct, cstruct, sstruct), _ = I.decode_state_struct(
            cfg, shape, plan, mesh)
        fn = build_decode_step(cfg, plan, pstruct, tstruct, cstruct, mesh=mesh)
        lowered = fn.lower(pstruct, tstruct, cstruct, sstruct)
    return lowered, mesh, cfg


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            smile: Optional[bool] = None, tag: str = "",
            opts: str = "") -> Dict:
    shape = INPUT_SHAPES[shape_name]
    if not supports_shape(arch, shape):
        return {"skipped": True}
    t0 = time.time()
    lowered, mesh, cfg = lower_one(arch, shape_name, multi_pod, smile=smile,
                                   opts=opts)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    print(mem)
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):        # older jax: list of per-device dicts
        ca = ca[0] if ca else {}
    print({k: ca.get(k) for k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()
    ndev = 512 if multi_pod else 256
    costs = analyze_hlo(hlo, ndev, multi_pod)
    csec = collective_summary(costs)
    by_group = {}
    for c in costs.collectives:
        key = f"{c['op']}|g{c['group']}|{'dcn' if c['dcn'] else 'ici'}"
        by_group[key] = by_group.get(key, 0.0) + c["bytes"] * c.get("count", 1.0)

    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "router": (cfg.moe.router if cfg.moe else None),
        "flops": float(ca.get("flops", 0.0)),            # scan bodies once!
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "dot_flops_corrected": costs.dot_flops,          # loop-aware
        "dot_bytes_corrected": costs.dot_bytes,          # HBM proxy (matmuls)
        "traffic_bytes_corrected": costs.traffic_bytes,  # upper bound
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "collectives": csec,
        "collectives_by_group": by_group,
        "lower_s": t_lower, "compile_s": t_compile,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fn = os.path.join(out_dir, f"{arch}__{shape_name}__"
                      f"{'multi' if multi_pod else 'single'}{suffix}.json")
    with open(fn, "w") as f:
        json.dump(res, f, indent=1)
    print(f"[dryrun] {arch} {shape_name} "
          f"{'2x16x16' if multi_pod else '16x16'}{suffix}: "
          f"flops={res['flops']:.3e} a2a_bytes="
          f"{csec['bytes_per_op']['all-to-all']:.3e} "
          f"compile={t_compile:.1f}s -> {fn}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--router", choices=["smile", "switch"], default=None,
                    help="override MoE router (baseline comparisons)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--opt", default="",
                    help="comma list: rsc,kvseq,zero1,bf16p,epxpod + the "
                         "registry-derived MoE tokens "
                         f"({','.join(sorted(MOE_DRYRUN_OPTS))}) + train "
                         f"tokens ({','.join(sorted(TRAIN_DRYRUN_OPTS))})")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if not args.all:
        smile = None if args.router is None else (args.router == "smile")
        run_one(args.arch, args.shape, args.multi_pod, args.out,
                smile=smile, tag=args.tag, opts=args.opt)
        return

    # full sweep via subprocesses (each gets a fresh 512-device runtime)
    from repro.configs import ASSIGNED
    jobs = []
    for arch in ASSIGNED:
        for shape in INPUT_SHAPES:
            for mp in (False, True):
                fn = os.path.join(args.out, f"{arch}__{shape}__"
                                  f"{'multi' if mp else 'single'}.json")
                if args.skip_existing and os.path.exists(fn):
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", args.out]
                if mp:
                    cmd.append("--multi-pod")
                jobs.append((arch, shape, mp, cmd))

    running: List = []
    fails = []
    while jobs or running:
        while jobs and len(running) < args.jobs:
            arch, shape, mp, cmd = jobs.pop(0)
            p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            running.append((arch, shape, mp, p))
        time.sleep(2)
        still = []
        for arch, shape, mp, p in running:
            if p.poll() is None:
                still.append((arch, shape, mp, p))
                continue
            out = p.stdout.read()
            if p.returncode != 0:
                fails.append((arch, shape, mp))
                print(f"FAIL {arch} {shape} mp={mp}:\n{out[-2000:]}")
            else:
                print(out.strip().splitlines()[-1])
        running = still
    print(f"\n{len(fails)} failures: {fails}")
    sys.exit(1 if fails else 0)


if __name__ == "__main__":
    main()
