"""The training step: loss + backward + grad-sync + clip + optimizer update,
all inside a single ``shard_map`` over the production mesh.

Because every collective is explicit (manual-collectives style), gradient
reduction is also explicit: each parameter leaf's gradient is psum'd over
exactly the mesh axes the leaf is *replicated* on (``specs.shard_axes``).
Expert leaves are sharded over the expert grid, so their gradients are only
reduced over ``pod`` (and ``model`` for replicated-expert layouts) — the
data-parallel AllReduce never touches expert weights, which is the hybrid
data+expert parallelism of the paper (§2).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.config import ModelConfig, TrainConfig
from repro.models import transformer as T
from repro.models.layers import vocab_parallel_xent
from repro.optim.optimizers import Optimizer, clip_by_global_norm
from repro.optim.zero1 import (Zero1State, init_state_shapes, state_specs,
                               zero1_apply, zero1_reduce_and_clip)
from repro.train import sentinel as SEN
from repro.sharding import comm
from repro.sharding.plan import MeshPlan
from repro.sharding.specs import (batch_specs, param_specs, shard_axes,
                                  sharded_axes_only)

IGNORE = -1
MTP_LAMBDA = 0.1


def _ce_loss(params, batch, cfg: ModelConfig, plan: MeshPlan,
             use_kernel: bool = False):
    tokens, labels = batch["tokens"], batch["labels"]
    S = tokens.shape[-1]
    positions = jnp.arange(S)
    extra = {k: batch[k] for k in ("image_embeds", "image_pos") if k in batch}
    h, logits, stats, _ = T.forward(params, tokens, cfg, plan,
                                    positions=positions, extra=extra or None,
                                    remat=cfg.remat, use_kernel=use_kernel)
    with jax.named_scope("lm_head"):
        if cfg.num_codebooks > 1:
            labels_t = jnp.swapaxes(labels, 1, 2)        # (B,S,K)
            ce = vocab_parallel_xent(logits, labels_t, plan)
            mask = labels_t != IGNORE
        else:
            ce = vocab_parallel_xent(logits, labels, plan)
            mask = labels != IGNORE
        loss_sum = jnp.sum(ce * mask)
        cnt = jnp.sum(mask).astype(jnp.float32)
        # tokens are distinct across dp axes only (replicated over tp)
        cnt_global = comm.psum(cnt, plan.dp_axes)
        ce_mean = (comm.psum(loss_sum, plan.dp_axes)
                   / jnp.maximum(cnt_global, 1.0))
    # --- partition loss for the gradient path --------------------------------
    # Under shard_map autodiff (check_vma=False) the backward pass effectively
    # differentiates the SUM of every device's loss output. A replicated loss
    # would therefore scale all gradients by the device count. Instead the
    # grad-path loss is each device's *share*: local_sum / (tp * global_count)
    # — shares sum to the true global mean across the mesh, so the assembled
    # (psum'd) gradients are exact. Verified against the single-device oracle.
    n_dev = 1
    for _, s in plan.axis_sizes:
        n_dev *= s
    tp = max(plan.tp, 1)
    ce_part = loss_sum / tp / jnp.maximum(cnt_global, 1.0)

    mtp_loss = jnp.float32(0.0)
    mtp_part = jnp.float32(0.0)
    if cfg.mtp_depth and cfg.causal and "mtp" in params:
        nxt = jnp.where(labels == IGNORE, 0, labels)     # token t+1
        tgt = jnp.full_like(labels, IGNORE)
        tgt = tgt.at[:, :-1].set(labels[:, 1:])          # token t+2
        ml = T.mtp_logits(params, h, nxt, cfg, plan, positions)
        mce = vocab_parallel_xent(ml, tgt, plan)
        mmask = (tgt != IGNORE) & (labels != IGNORE)
        ms = jnp.sum(mce * mmask)
        mc = comm.psum(jnp.sum(mmask).astype(jnp.float32), plan.dp_axes)
        mtp_loss = comm.psum(ms, plan.dp_axes) / jnp.maximum(mc, 1.0)
        mtp_part = ms / tp / jnp.maximum(mc, 1.0)

    # aux losses are computed replicated (internally psum'd) -> share = /n_dev
    aux_part = (stats.lb_loss + stats.z_loss) / n_dev
    total_grad = ce_part + aux_part + MTP_LAMBDA * mtp_part
    total = ce_mean + stats.lb_loss + stats.z_loss + MTP_LAMBDA * mtp_loss
    metrics = {"ce": ce_mean, "lb": stats.lb_loss, "z": stats.z_loss,
               "mtp": mtp_loss, "drop_frac": stats.drop_frac,
               "loss": total,
               # robustness feed: global sanitizer rejections + the
               # layer-worst router watchdog inputs (see train/sentinel.py)
               "fault_events": stats.fault_events.sum(),
               "wire_faults": stats.wire_faults.sum(),
               "max_load": jnp.max(stats.hop_max_load),
               "load_entropy": jnp.min(stats.hop_load_entropy)}
    return total_grad, metrics


def train_step_fn(params, opt_state, batch, step, sent=None, *,
                  cfg: ModelConfig, tcfg: TrainConfig, plan: MeshPlan,
                  opt: Optimizer, schedule, sync_axes_tree, norm_axes_tree,
                  n_micro: int = 1, use_kernel: bool = False,
                  zero1: bool = False, sentinel: bool = False):
    """One optimizer step (call inside shard_map or on a single device).

    With ``sentinel=True`` the step takes/returns a fifth value — the
    :class:`repro.train.sentinel.SentinelState` carry — and the optimizer
    apply is ``lax.cond``-guarded by the step verdict: a non-finite
    loss/grad or a loss spike leaves params and opt-state bit-unchanged
    and bumps the anomaly counters instead (metrics gain ``"skip"``).
    """

    loss = partial(_ce_loss, cfg=cfg, plan=plan, use_kernel=use_kernel)

    if n_micro <= 1:
        grads, metrics = jax.grad(lambda p: loss(p, batch), has_aux=True)(params)
    else:
        def micro(carry, mb):
            acc, _ = carry
            g, m = jax.grad(lambda p: loss(p, mb), has_aux=True)(params)
            acc = jax.tree.map(jnp.add, acc, g)
            return (acc, m), None
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        mb_batch = jax.tree.map(
            lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
            batch)
        m0 = {k: jnp.float32(0.0) for k in
              ("ce", "lb", "z", "mtp", "drop_frac", "loss",
               "fault_events", "wire_faults", "max_load", "load_entropy")}
        (grads, metrics), _ = jax.lax.scan(micro, (zeros, m0), mb_batch)
        grads = jax.tree.map(lambda g: g / n_micro, grads)

    lr = schedule(step)
    if zero1:
        # ZeRO-1: reduce-scatter raw grads + global clip scale first; the
        # apply (moments + owned-chunk update + re-gather) is a separate
        # stage so the sentinel can gate it (see optim/zero1.py)
        with jax.named_scope("grad_sync"):
            g_upd, gnorm, scale = zero1_reduce_and_clip(
                grads, sync_axes_tree=sync_axes_tree,
                norm_axes_tree=norm_axes_tree, plan=plan,
                grad_clip=tcfg.grad_clip)
        apply_fn = lambda g, o, p: zero1_apply(
            g, scale, o, p, lr, sync_axes_tree=sync_axes_tree,
            norm_axes_tree=norm_axes_tree, plan=plan, b1=tcfg.b1,
            b2=tcfg.b2, eps=tcfg.eps, weight_decay=tcfg.weight_decay)
    else:
        # ---- explicit gradient reduction over replicated axes ---------------
        with jax.named_scope("grad_sync"):
            grads = jax.tree.map(
                lambda g, a: comm.psum(g, a) if a else g, grads,
                sync_axes_tree, is_leaf=lambda x: isinstance(x, jax.Array))
        with jax.named_scope("optimizer"):
            g_upd, gnorm = clip_by_global_norm(grads, tcfg.grad_clip,
                                               norm_axes_tree)
        apply_fn = lambda g, o, p: opt.update(g, o, p, lr,
                                              shard_axes=norm_axes_tree)
    if sentinel:
        # verdict AFTER grad reduction (+ clip / owned-chunk scatter —
        # non-finite values survive both), BEFORE the moments see
        # anything: the guarded apply leaves params/opt-state (including
        # the ZeRO-1 sharded chunks and step clock) bit-unchanged on a
        # bad step
        ok, nonfin, spike = SEN.step_verdict(metrics["loss"], g_upd,
                                             sent, plan.all_axes)
        with jax.named_scope("optimizer"):
            params, opt_state = SEN.gated_update(ok, apply_fn, g_upd,
                                                 opt_state, params)
        alarm = SEN.router_alarm(metrics["max_load"],
                                 metrics["load_entropy"])
        sent = SEN.update_sentinel(sent, metrics["loss"], ok, nonfin,
                                   spike, alarm)
        metrics = dict(metrics)
        metrics["skip"] = (~ok).astype(jnp.float32)
    else:
        with jax.named_scope("optimizer"):
            params, opt_state = apply_fn(g_upd, opt_state, params)
    metrics = dict(metrics)
    metrics["grad_norm"] = gnorm
    metrics["lr"] = lr
    if sentinel:
        return params, opt_state, metrics, sent
    return params, opt_state, metrics


def build_train_step(cfg: ModelConfig, tcfg: TrainConfig, plan: MeshPlan,
                     opt: Optimizer, schedule, params_like, batch_like,
                     mesh=None, use_kernel: bool = False,
                     zero1: bool = False, sentinel: bool = False):
    """Return a jitted step(params, opt_state, batch, step) for this mesh.

    ``params_like`` / ``batch_like`` may be ShapeDtypeStructs (for lowering)
    or real arrays. With ``mesh=None`` the step runs on one device (oracle).
    With ``zero1=True`` optimizer state is sharded over each leaf's
    replicated axes (init with ``zero1_state(...)``).  With
    ``sentinel=True`` the step is 5-ary — ``step(params, opt_state, batch,
    step, sent) -> (params, opt_state, metrics, sent)`` where ``sent`` is
    ``repro.train.sentinel.init_sentinel_state()`` — and bad steps are
    skipped instead of applied (see ``train_step_fn``).
    """
    pspec = param_specs(params_like, cfg, plan)
    sync_tree = shard_axes(pspec, plan)
    norm_tree = sharded_axes_only(pspec, plan)
    n_micro = 1
    if tcfg.micro_batch_size:
        local_b = batch_like["tokens"].shape[0] // max(plan.dp, 1)
        n_micro = max(1, local_b // tcfg.micro_batch_size)

    fn = partial(train_step_fn, cfg=cfg, tcfg=tcfg, plan=plan, opt=opt,
                 schedule=schedule, sync_axes_tree=sync_tree,
                 norm_axes_tree=norm_tree, n_micro=n_micro,
                 use_kernel=use_kernel, zero1=zero1, sentinel=sentinel)
    # jit names the module after the function: ``jit_train_step_fn`` on one
    # device as on a mesh, where a bare partial gives ``jit__unknown``
    fn.__name__ = train_step_fn.__name__
    if mesh is None:
        return jax.jit(fn, donate_argnums=(0, 1)), pspec

    ospec = opt_state_specs(pspec, plan, zero1=zero1)
    bspec = batch_specs(batch_like, plan)
    mkeys = ["ce", "lb", "z", "mtp", "drop_frac", "loss", "grad_norm", "lr",
             "fault_events", "wire_faults", "max_load", "load_entropy"]
    if sentinel:
        mkeys.append("skip")
    mspec = {k: P() for k in mkeys}
    if sentinel:
        from repro.train.sentinel import init_sentinel_state
        sspec = jax.tree.map(lambda _: P(), init_sentinel_state())
        sm = jax.shard_map(fn, mesh=mesh,
                           in_specs=(pspec, ospec, bspec, P(), sspec),
                           out_specs=(pspec, ospec, mspec, sspec),
                           check_vma=False)
    else:
        sm = jax.shard_map(fn, mesh=mesh,
                           in_specs=(pspec, ospec, bspec, P()),
                           out_specs=(pspec, ospec, mspec), check_vma=False)
    return jax.jit(sm, donate_argnums=(0, 1)), pspec


def opt_state_specs(pspec, plan: MeshPlan, *, zero1: bool = False):
    """Partition specs of the optimizer state the step takes and returns,
    for parameters with specs ``pspec``."""
    if zero1:
        return state_specs(pspec, shard_axes(pspec, plan),
                           sharded_axes_only(pspec, plan))
    return {"m": pspec, "v": pspec, "step": P()}


def zero1_state(params_like, cfg: ModelConfig, plan: MeshPlan):
    """Init the ZeRO-1 optimizer state (global shapes; shard via its specs)."""
    pspec = param_specs(params_like, cfg, plan)
    sync_tree = shard_axes(pspec, plan)
    norm_tree = sharded_axes_only(pspec, plan)
    return init_state_shapes(params_like, sync_tree, norm_tree, plan)
