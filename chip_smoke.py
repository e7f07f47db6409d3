#!/usr/bin/env python3
"""Chip smoke: SMILE training on a TPU through the trainer's own path.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # one 2x2 host (four chips)

One chip: smile-3.7b at its published widths (d_model 768, 12 heads, d_ff
and d_ff_expert 3072, vocab 32128, 128 experts, top-1, capacity 2.0, LAMB),
cut to 2 layers (one MoE layer), with the logical SMILE grid set to (2, 2)
so both routing levels run on the one device.  It trains a few steps with
``repro.launch.train.train`` (the XLA path).  Then, with the Pallas kernels
(``use_kernel=True``, radix group sort, fused router) on the same initial
parameters and first batch, it checks every main-path kernel call of the
forward pass against its ``repro.kernels.ref`` twin on the same operands,
checks that the compiled train step holds each kernel, and checks that
step against the XLA path's first step.  Last, it plants two faults in the
combine gather's forward and requires each to fail that check.

``--four-chips`` runs only the mesh phase: smile-3.7b and switch-3.7b at the
same cut on a (2, 2) ("data", "model") mesh, plus SMILE with dropless
dispatch over the native ragged all-to-all, each compared with the same
training run on ``jax.devices()[0]`` alone.

The script needs a TPU: with any other platform it exits nonzero before
running anything.  Times and memory it prints are smoke output, not
benchmark metrics.  The last line of stdout, printed only when every phase
passed, is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import base64
import contextlib
import dataclasses
import json
import math
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BATCH, SEQ = 16, 256            # 4096 tokens per step
STEPS = 5                       # one-chip XLA-path steps
MESH_STEPS = 3                  # steps per run in the four-chip phase
LR = 3e-4
SEED = 0

# Mesh-vs-one-device agreement, as in the repository's distributed
# train-step equivalence test.  Both sides compute in bf16 with fp32 loss and
# norm reductions but split the same sums differently across devices, and a
# top-1 router whose two best logits are within a bf16 ulp may pick another
# expert for that token.
MAX_DLOSS = 2e-2                # absolute, on a loss of ~10.4 (ln 32128)
MAX_DGNORM_REL = 6e-2           # relative grad-norm difference
MAX_DPARAM = 5e-3               # max abs parameter difference after a run

# Kernel-vs-XLA agreement of the first train step.  The forward kernels are
# checked against their ref twins one by one (phase 2); these bound what
# the kernels change in the whole step (phase 3), at about 10x what healthy
# kernels read on a TPU v5 lite (|dloss| 2.38e-05, grad-norm rel 9.28e-05).
# Phase 4 plants faults in the combine's forward and requires each to land
# outside them: on that chip, the combine's output zeroed read |dloss|
# 1.05e-03 and every token combining the next row 1.12e-03.  Their
# grad-norm readings (1.45e-04, 3.10e-04) stay inside, since the backward
# pass is the twin's VJP: the loss bound is the one that catches them.
KERNEL_MAX_DLOSS = 2.5e-4
KERNEL_MAX_DGNORM_REL = 1e-3

# Mosaic function names of the main-path kernels, as they appear in the
# serialized kernel body of each tpu_custom_call
KERNELS = {
    "grouped_ffn": (b"_kernel_mlp", b"_kernel_glu"),
    "dispatch_gather": (b"_dispatch_kernel",),
    "combine_gather": (b"_combine_kernel",),
    "radix_sort": (b"_group_sort_kernel",),
    "router_fused": (b"_router_fused_kernel",),
}


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def kernel_counts(hlo_text: str) -> dict:
    """Count the tpu_custom_calls of each main-path kernel in HLO text."""
    counts = dict.fromkeys(KERNELS, 0)
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'"body":"([^"]*)"', line)
        body = base64.b64decode(m.group(1)) if m else b""
        for name, syms in KERNELS.items():
            if any(s in body for s in syms):
                counts[name] += 1
    return counts


def smile_cut(name: str):
    """``name`` at its published widths, cut to 2 layers (one MoE layer),
    with the logical expert grid (2, 2): both SMILE levels route even on
    one device, and the expert layout matches a 2x2 mesh."""
    from repro.configs import get_config
    cfg = get_config(name)
    return cfg.replace(num_layers=2,
                       moe=dataclasses.replace(cfg.moe, grid=(2, 2)))


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def step_times(history) -> list:
    """Wall seconds per logged step, from train()'s cumulative tok/s (the
    first includes compilation)."""
    ends = [BATCH * SEQ * h["step"] / h["tokens_per_s"] for h in history]
    return [b - a for a, b in zip([0.0] + ends[:-1], ends)]


def check_history(tag: str, history) -> None:
    losses = [h["loss"] for h in history]
    log(f"{tag}: losses " + " ".join(f"{x:.4f}" for x in losses))
    if len(losses) == 0 or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{tag}: non-finite or missing losses {losses}")


def compare(tag: str, got: dict, want: dict, max_dloss: float,
            max_dgnorm_rel: float, same_drops: bool = False) -> None:
    """First-step agreement; ``same_drops``: the drop fractions must be
    equal, as they are when both sides make the same routing decisions."""
    dl = abs(got["loss"] - want["loss"])
    dg = abs(got["grad_norm"] - want["grad_norm"])
    rel_g = dg / max(abs(want["grad_norm"]), 1e-6)
    log(f"{tag}: drop_frac {got['drop_frac']!r} vs {want['drop_frac']!r}")
    log(f"{tag}: loss {got['loss']:.6f} vs {want['loss']:.6f} "
        f"(|d| {dl:.2e}, bound {max_dloss:g}); grad norm "
        f"{got['grad_norm']:.6f} vs {want['grad_norm']:.6f} "
        f"(rel {rel_g:.2e}, bound {max_dgnorm_rel:g})")
    if same_drops and got["drop_frac"] != want["drop_frac"]:
        raise RuntimeError(f"{tag}: drop fractions differ")
    if not (dl < max_dloss and rel_g < max_dgnorm_rel):
        raise RuntimeError(f"{tag}: first steps disagree")


@contextlib.contextmanager
def patched_ops(**fns):
    """Replace ``repro.kernels.ops`` wrappers while a program is traced."""
    from repro.kernels import ops
    real = {name: getattr(ops, name) for name in fns}
    try:
        for name, fn in fns.items():
            setattr(ops, name, fn)
        yield
    finally:
        for name, fn in real.items():
            setattr(ops, name, fn)


def first_step(step, params, opt_state, batch):
    """Compile ``step`` and run it once: (compiled, metrics, compile s)."""
    import jax
    import jax.numpy as jnp
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, batch, jnp.int32(1)).compile()
    dt = time.perf_counter() - t0
    _, _, m = compiled(params, opt_state, batch, jnp.int32(1))
    return compiled, {k: float(v) for k, v in jax.device_get(m).items()}, dt


def planted_combine_faults():
    """ops.combine_gather with a wrong forward and the twin's VJP as its
    backward, as a faulty kernel would have: (tag, wrapper) pairs."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    real = ops.combine_gather

    def plant(bad):
        def wrapper(rows, src, scale):
            good = real(rows, src, scale)
            return good + jax.lax.stop_gradient(bad(rows, src, scale) - good)
        return wrapper

    def zeroed(rows, src, scale):
        return jnp.zeros((src.shape[0], rows.shape[1]), rows.dtype)

    def neighbour(rows, src, scale):
        nxt = jnp.where(src >= 0, (src + 1) % rows.shape[0], src)
        return real(rows, nxt, scale)

    return [("combine output zeroed", plant(zeroed)),
            ("combine reads the next row", plant(neighbour))]


def kernel_inputs(cfg):
    """The kernel-path train step for ``cfg``, with the initial parameters,
    LAMB state and first batch ``train()`` draws for the same seed."""
    import jax
    import jax.numpy as jnp

    from repro.common.config import TrainConfig
    from repro.data.pipeline import DataPipeline
    from repro.models.transformer import init_model
    from repro.optim import make_optimizer, make_schedule
    from repro.sharding.plan import single_device_plan
    from repro.train.step import build_train_step

    plan = single_device_plan()
    params = init_model(jax.random.PRNGKey(SEED), cfg, plan)
    opt = make_optimizer("lamb")
    opt_state = opt.init(params)
    pipe = DataPipeline(cfg, BATCH, SEQ, seed=SEED)
    batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
    pipe.close()
    tcfg = TrainConfig(global_batch_size=BATCH, seq_len=SEQ, steps=STEPS,
                       optimizer="lamb", lr=LR, seed=SEED,
                       warmup_steps=max(STEPS // 10, 1))
    sched = make_schedule("cosine", LR, tcfg.warmup_steps, STEPS)
    step, _ = build_train_step(cfg, tcfg, plan, opt, sched, params, batch,
                               use_kernel=True)
    return step, params, opt_state, batch


def _f32_combine_twin(rows, src, scale):
    """The combine's ref twin in fp32, rounded once: the kernel's contract
    (gate-weighted fp32 sum in j order; with top-1 there is one term)."""
    import jax.numpy as jnp
    from repro.kernels import ref
    return ref.combine_gather_ref(rows.astype(jnp.float32), src,
                                  scale.astype(jnp.float32)).astype(rows.dtype)


def _ffn_twin(x, w1, w3, w2, *, act):
    from repro.kernels import ref
    cast = None if w3 is None else w3.astype(x.dtype)
    return ref.grouped_ffn_ref(x, w1.astype(x.dtype), cast,
                               w2.astype(x.dtype), act=act)


# ops wrapper -> (its ref twin, called with the wrapper's arguments; whether
# float outputs must match bit for bit).  Gathers and sorts move or count
# values and must be exact.  The router's fp32 GEMM and softmax may round
# differently in Mosaic than in XLA.  The expert FFN adds its d_ff blocks'
# partial sums into its bf16 output tile, one rounding per block, where the
# twin sums in fp32 and rounds once.  Integer outputs (expert ids, ranks,
# group starts) must always match.
def _twins():
    from repro.kernels import ref
    return {
        "dispatch_gather": (ref.dispatch_gather_ref, True),
        "combine_gather": (_f32_combine_twin, True),
        "group_sort": (lambda keys, n, *, impl: ref.group_sort_ref(keys, n),
                       True),
        "router_fused": (ref.router_fused_ref, False),
        "grouped_ffn": (_ffn_twin, False),
    }


def bits_differ(a, b):
    """Elementwise: the float arrays differ bit for bit, a zero of either
    sign counting as zero.  For a dropped assignment the combine kernel
    keeps the sign of 0 * row, where its twin's sum adds +0."""
    import jax
    import jax.numpy as jnp
    uint = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
    ua = jax.lax.bitcast_convert_type(a, uint)
    ub = jax.lax.bitcast_convert_type(b, uint)
    mag = jnp.array(jnp.iinfo(uint).max >> 1, uint)      # all but the sign
    both_zero = ((ua & mag) == 0) & ((ub & mag) == 0)
    return (ua != ub) & ~both_zero


# bounds for the twins that may round differently: the worst element's
# |kernel - twin| over the largest |twin| of that float output.  The router
# may differ by a few fp32 ulps; the expert FFN by a few bf16 ulps, and its
# bound is the one the repository's kernel tests give bf16 (3e-2).
OP_MAX_REL = {"router_fused": 1e-5, "grouped_ffn": 3e-2}


def check_ops(cfg, params, batch) -> None:
    """Run the kernel path's forward pass once with every main-path ops
    wrapper also evaluating its ref twin on the same operands, on the chip,
    and compare each call's outputs."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.sharding.plan import single_device_plan

    # name -> {"calls", "int_diff", "float_diff", "n", "rel", "shapes"}
    seen = {}

    def record(name, shapes, int_diff, float_diff, n, rel):
        s = seen.setdefault(name, {"calls": 0, "int_diff": 0,
                                   "float_diff": 0, "n": 0, "rel": 0.0,
                                   "shapes": []})
        s["calls"] += 1
        s["int_diff"] += int(int_diff)
        s["float_diff"] += int(float_diff)
        s["n"] += int(n)
        s["rel"] = max(s["rel"], float(rel))
        if shapes not in s["shapes"]:
            s["shapes"].append(shapes)

    def checked(name, real, twin):
        def wrapper(*args, **kw):
            got = real(*args, **kw)
            want = twin(*args, **kw)
            int_diff = float_diff = jnp.int32(0)
            n, rel = 0, jnp.float32(0)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                n += a.size
                if not jnp.issubdtype(a.dtype, jnp.floating):
                    int_diff += jnp.sum(a != b).astype(jnp.int32)
                    continue
                float_diff += jnp.sum(bits_differ(a, b)).astype(jnp.int32)
                a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
                rel = jnp.maximum(rel, jnp.max(jnp.abs(a32 - b32))
                                  / jnp.maximum(jnp.max(jnp.abs(b32)), 1e-30))
            shapes = " ".join(str(tuple(a.shape)) for a in args
                              if hasattr(a, "shape"))
            jax.debug.callback(functools.partial(record, name, f"[{shapes}]"),
                               int_diff, float_diff, n, rel)
            return got
        return wrapper

    twins = _twins()
    fwd = jax.jit(lambda p, tokens: T.forward(
        p, tokens, cfg, single_device_plan(),
        positions=jnp.arange(tokens.shape[-1]), use_kernel=True)[1])
    with patched_ops(**{name: checked(name, getattr(ops, name), twin)
                        for name, (twin, _) in twins.items()}):
        lowered = fwd.lower(params, batch["tokens"])
    # without excess precision XLA rounds wherever the program converts to
    # bf16, so the twins' outputs do not depend on how XLA fuses them
    compiled = lowered.compile({"xla_allow_excess_precision": False})
    jax.block_until_ready(compiled(params, batch["tokens"]))
    jax.effects_barrier()
    bad = []
    for name, (_, exact) in twins.items():
        if name not in seen:
            bad.append(f"{name} never called")
            continue
        s = seen[name]
        log(f"kernel vs ref twin, {name}: {s['calls']} call(s), operands "
            f"{' '.join(s['shapes'])}; of {s['n']} output elements "
            f"{s['int_diff']} integer and {s['float_diff']} float differ, "
            f"worst rel {s['rel']:.2e}"
            + ("" if exact else f" (bound {OP_MAX_REL[name]:g})"))
        if s["int_diff"] or (exact and s["float_diff"]):
            bad.append(f"{name} differs from its twin")
        if not exact and not s["rel"] <= OP_MAX_REL[name]:
            bad.append(f"{name} is off its twin by {s['rel']:.2e}")
    if bad:
        raise RuntimeError("kernel vs ref twin: " + "; ".join(bad))


def one_chip(dev) -> None:
    from repro.configs import with_options
    from repro.launch.train import train

    cfg = smile_cut("smile-3.7b")
    log(f"model {cfg.name}: layers {cfg.num_layers}, d_model "
        f"{cfg.d_model}, heads {cfg.num_heads}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, experts {cfg.moe.num_experts} (grid "
        f"{cfg.moe.grid}), top-{cfg.moe.top_k}, capacity "
        f"{cfg.moe.capacity_factor}, {cfg.param_count() / 1e9:.3f} B params;"
        f" batch {BATCH} x {SEQ}")

    # ---- phase 1: the trainer's own path (XLA) ---------------------------
    t0 = time.perf_counter()
    params, history = train(cfg, steps=STEPS, batch=BATCH, seq=SEQ, lr=LR,
                            optimizer="lamb", seed=SEED, log_every=1)
    log(f"xla path: {STEPS} steps in {time.perf_counter() - t0:.1f} s "
        f"wall; step wall times (first includes compile) "
        + " ".join(f"{t:.3f}" for t in step_times(history)) + " s")
    check_history("xla path", history)
    log(f"xla path: peak_bytes_in_use {peak_bytes(dev)}")
    del params

    # ---- phase 2: each kernel against its ref twin ------------------------
    kcfg = with_options(cfg, sort_impl="radix", router_impl="fused")
    step, params, opt_state, batch = kernel_inputs(kcfg)
    t0 = time.perf_counter()
    check_ops(kcfg, params, batch)
    log(f"kernel vs ref twin: {time.perf_counter() - t0:.1f} s wall")

    # ---- phase 3: the same first step with the Pallas kernels ------------
    compiled, m, dt = first_step(step, params, opt_state, batch)
    log(f"kernel path: compile {dt:.1f} s")
    counts = kernel_counts(compiled.as_text())
    log("kernel path: tpu_custom_call count per kernel "
        + json.dumps(counts))
    missing = [k for k, n in counts.items() if n == 0]
    if missing:
        raise RuntimeError(f"kernel path: no tpu_custom_call for {missing}")
    if not math.isfinite(m["loss"]):
        raise RuntimeError(f"kernel path: non-finite loss {m['loss']}")
    compare("kernel vs xla, step 1", m, history[0], KERNEL_MAX_DLOSS,
            KERNEL_MAX_DGNORM_REL, same_drops=True)
    log(f"kernel path: peak_bytes_in_use {peak_bytes(dev)}")
    del compiled

    # ---- phase 4: the bounds catch a planted combine fault ---------------
    for tag, fault in planted_combine_faults():
        with patched_ops(combine_gather=fault):
            _, m, _ = first_step(*kernel_inputs(kcfg))
        try:
            compare(f"planted fault, {tag}", m, history[0],
                    KERNEL_MAX_DLOSS, KERNEL_MAX_DGNORM_REL)
        except RuntimeError:
            log(f"planted fault, {tag}: outside the bounds, as it must be")
        else:
            raise RuntimeError(f"planted fault, {tag}: within the bounds")


def four_chips(devices) -> None:
    import jax
    import numpy as np

    from repro.configs import with_options
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import train

    mesh = make_test_mesh(2, 2)
    log(f"mesh {dict(mesh.shape)} over {len(devices)} devices")
    runs = [("smile-3.7b sort", smile_cut("smile-3.7b")),
            ("switch-3.7b sort", smile_cut("switch-3.7b")),
            ("smile-3.7b dropless+ragged a2a",
             with_options(smile_cut("smile-3.7b"),
                          dispatch_backend="dropless", ragged_a2a=True))]
    kw = dict(steps=MESH_STEPS, batch=BATCH, seq=SEQ, lr=LR,
              optimizer="lamb", seed=SEED, log_every=1)
    for tag, cfg in runs:
        if cfg.moe.dispatch_backend == "dropless":
            n = ragged_a2a_ops(cfg, mesh)
            log(f"{tag}: ragged_all_to_all ops in the lowered mesh step: {n}")
            if n == 0:
                raise RuntimeError(f"{tag}: the native exchange is not used")
        t0 = time.perf_counter()
        p_one, h_one = train(cfg, **kw)              # jax.devices()[0] alone
        log(f"{tag}: one device, {MESH_STEPS} steps in "
            f"{time.perf_counter() - t0:.1f} s wall")
        check_history(f"{tag} one device", h_one)
        p_one = jax.device_get(p_one)
        t0 = time.perf_counter()
        p_mesh, h_mesh = train(cfg, mesh=mesh, **kw)
        log(f"{tag}: mesh, {MESH_STEPS} steps in "
            f"{time.perf_counter() - t0:.1f} s wall")
        check_history(f"{tag} mesh", h_mesh)
        compare(f"{tag}: mesh vs one device, step 1", h_mesh[0], h_one[0],
                MAX_DLOSS, MAX_DGNORM_REL)
        dparam = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(np.max(np.abs(np.asarray(a, np.float32)
                                             - np.asarray(b, np.float32)))),
            jax.device_get(p_mesh), p_one)))
        log(f"{tag}: max |param diff| after {MESH_STEPS} steps {dparam:.2e} "
            f"(bound {MAX_DPARAM:g})")
        if not dparam < MAX_DPARAM:
            raise RuntimeError(f"{tag}: parameters disagree")
        del p_one, p_mesh
    for d in devices:
        log(f"device {d.id}: peak_bytes_in_use {peak_bytes(d)}")


def ragged_a2a_ops(cfg, mesh) -> int:
    """Native ragged all-to-all ops in the lowered mesh train step."""
    import jax
    import jax.numpy as jnp

    from repro.common.config import TrainConfig
    from repro.data.pipeline import make_batch
    from repro.models.transformer import init_model
    from repro.optim import make_optimizer, make_schedule
    from repro.sharding.plan import plan_from_mesh
    from repro.train.step import build_train_step

    plan = plan_from_mesh(mesh)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(SEED), cfg,
                                               plan))
    opt = make_optimizer("lamb")
    batch = {k: jnp.asarray(v)
             for k, v in make_batch(cfg, BATCH, SEQ, SEED, 0).items()}
    tcfg = TrainConfig(global_batch_size=BATCH, seq_len=SEQ)
    step, _ = build_train_step(cfg, tcfg, plan, opt,
                               make_schedule("cosine", LR, 1, MESH_STEPS),
                               params, batch, mesh=mesh)
    text = step.lower(params, jax.eval_shape(opt.init, params), batch,
                      jnp.int32(1)).as_text()
    return len(re.findall(r"ragged_all_to_all", text))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (2, 2) mesh phase on four chips")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"device count {len(devices)}")
    if dev.platform != "tpu":
        log("FAIL: no TPU visible; this smoke runs only on the chip")
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        log(f"FAIL: needs {want} chips, found {len(devices)}")
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    log(f"persistent compilation cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chips(devices[:4])
        count = 4
    else:
        one_chip(dev)
        count = len(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
