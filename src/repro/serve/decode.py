"""Serving: batched prefill + single-token decode steps.

``decode_step`` is what the ``decode_32k`` / ``long_500k`` dry-run shapes
lower: ONE new token per sequence against a KV/SSM cache of the configured
length. Attention archs use the ring-buffer KV cache (window-sized for
sliding-window variants); SSM archs carry O(1) recurrent state.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.config import ModelConfig
from repro.models import transformer as T
from repro.models.layers import gather_full_logits
from repro.sharding import comm
from repro.sharding.plan import MeshPlan
from repro.sharding.specs import batch_specs, cache_specs, param_specs


def greedy_sample(logits_sharded: jax.Array, plan: MeshPlan) -> jax.Array:
    """Distributed greedy argmax over vocab-sharded logits (..., V_loc)."""
    v_loc = logits_sharded.shape[-1]
    start = comm.axis_index(plan.tp_axis) * v_loc
    local_max = logits_sharded.max(-1)
    local_arg = logits_sharded.argmax(-1) + start
    gmax = comm.pmax(local_max, plan.tp_axis)
    cand = jnp.where(local_max >= gmax, local_arg, jnp.iinfo(jnp.int32).max)
    return comm.pmax(-cand, plan.tp_axis) * -1        # lowest winning index


def prefill_fn(params, tokens, caches, *, cfg: ModelConfig, plan: MeshPlan):
    """Run the prompt through the model, filling caches.

    tokens: (B, S) (or (B, K, S) for multi-codebook). Returns
    (next_token (B,) int32, caches).
    """
    S = tokens.shape[-1]
    positions = jnp.arange(S)
    _, logits, _, caches = T.forward(params, tokens, cfg, plan,
                                     positions=positions, caches=caches)
    nxt = greedy_sample(logits[..., -1, :] if cfg.num_codebooks <= 1
                        else logits[:, -1], plan)
    return nxt, caches


def decode_step_fn(params, token, caches, step, *, cfg: ModelConfig,
                   plan: MeshPlan):
    """One decode step. token: (B,) (or (B, K)); step: scalar position."""
    tok = token[..., None]                              # (B, 1) / (B, K, 1)
    positions = step[None] if step.ndim == 0 else step
    _, logits, _, caches = T.forward(params, tok, cfg, plan,
                                     positions=positions, caches=caches)
    if cfg.num_codebooks > 1:
        nxt = greedy_sample(logits[:, -1], plan)        # (B, K)
    else:
        nxt = greedy_sample(logits[:, -1, :], plan)     # (B,)
    return nxt, caches


def build_decode_step(cfg: ModelConfig, plan: MeshPlan, params_like,
                      token_like, caches_like, mesh=None):
    """Jitted decode step for this mesh (or single device when mesh=None)."""
    fn = partial(decode_step_fn, cfg=cfg, plan=plan)
    if mesh is None:
        return jax.jit(fn, donate_argnums=(2,))
    batch = token_like.shape[0]
    pspec = param_specs(params_like, cfg, plan)
    cspec = cache_specs(caches_like, cfg, plan, batch)
    tspec = batch_specs({"t": token_like}, plan)["t"]
    sm = jax.shard_map(fn, mesh=mesh,
                       in_specs=(pspec, tspec, cspec, P()),
                       out_specs=(tspec, cspec), check_vma=False)
    return jax.jit(sm, donate_argnums=(2,))


def build_prefill(cfg: ModelConfig, plan: MeshPlan, params_like,
                  tokens_like, caches_like, mesh=None):
    fn = partial(prefill_fn, cfg=cfg, plan=plan)
    if mesh is None:
        return jax.jit(fn, donate_argnums=(2,))
    batch = tokens_like.shape[0]
    pspec = param_specs(params_like, cfg, plan)
    cspec = cache_specs(caches_like, cfg, plan, batch)
    tok_spec = batch_specs({"t": tokens_like}, plan)["t"]
    out_tok = P(tok_spec[0]) if cfg.num_codebooks <= 1 else \
        P(tok_spec[0], None)
    sm = jax.shard_map(fn, mesh=mesh,
                       in_specs=(pspec, tok_spec, cspec),
                       out_specs=(out_tok, cspec), check_vma=False)
    return jax.jit(sm, donate_argnums=(2,))
