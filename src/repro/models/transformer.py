"""Model assembly: stages of scanned blocks covering all six arch families.

A model is a list of *stages*; each stage is a homogeneous stack of blocks
whose parameters are stacked on a leading ``(repeats, ...)`` axis and executed
with ``lax.scan`` (keeps HLO size independent of depth — essential for
126-layer dry-runs on 512 devices). Heterogeneous architectures compose
multiple stages:

* dense archs                 -> [dense x L]
* deepseek-v3                 -> [dense x 3, moe x 58] (+ MTP head)
* qwen3-moe                   -> [moe x 48]
* paper SMILE/Switch (MLM)    -> [pair(dense, moe) x L/2]  (every-other-FFN MoE)
* zamba2 (hybrid)             -> [mamba_group x 9] (6 mamba2 + shared attn)
* rwkv6                       -> [rwkv x 24]
* musicgen / phi-3-vision     -> dense stacks + modality input handling
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.common.config import ModelConfig
from repro.core.moe import MoEStats, init_moe_params, moe_layer, zero_stats
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import rwkv6 as RW
from repro.sharding import comm
from repro.sharding.plan import MeshPlan


# =============================================================================
# Stage plan
# =============================================================================

@dataclasses.dataclass(frozen=True)
class Stage:
    kind: str        # dense | moe | pair | mamba_group | rwkv
    repeats: int


def build_stages(cfg: ModelConfig) -> List[Stage]:
    if cfg.arch_type in ("ssm",) and cfg.rwkv is not None:
        return [Stage("rwkv", cfg.num_layers)]
    if cfg.arch_type == "hybrid":
        g = cfg.ssm_layers_per_attn
        assert cfg.num_layers % g == 0
        return [Stage("mamba_group", cfg.num_layers // g)]
    if cfg.moe is not None and cfg.moe.num_experts:
        stages = []
        fd = cfg.moe.first_dense_layers
        if fd:
            stages.append(Stage("dense", fd))
        rest = cfg.num_layers - fd
        if cfg.moe.every_n_layers == 2:
            assert rest % 2 == 0
            stages.append(Stage("pair", rest // 2))
        else:
            stages.append(Stage("moe", rest))
        return stages
    return [Stage("dense", cfg.num_layers)]


def _phys_heads(cfg: ModelConfig, plan: MeshPlan) -> int:
    """Pad query heads up to a tp multiple (e.g. deepseek-coder 56 -> 64)."""
    tp = max(plan.tp, 1)
    return ((cfg.num_heads + tp - 1) // tp) * tp


def _model_cfg(cfg: ModelConfig, plan: MeshPlan) -> ModelConfig:
    h = _phys_heads(cfg, plan)
    if h != cfg.num_heads:
        hd = cfg.resolved_head_dim
        cfg = cfg.replace(num_heads=h, head_dim=hd)
    return cfg


# =============================================================================
# Block init
# =============================================================================

def _init_attn(key, cfg: ModelConfig) -> Dict:
    if cfg.attention == "mla":
        return L.init_mla(key, cfg)
    return L.init_attention(key, cfg)


def init_block(key, cfg: ModelConfig, kind: str, plan: MeshPlan) -> Dict:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    if kind == "rwkv":
        return {
            "ln1": L._norm_init(d, "layernorm"),
            "tmix": RW.init_rwkv_tmix(ks[0], cfg),
            "ln2": L._norm_init(d, "layernorm"),
            "cmix": RW.init_rwkv_cmix(ks[1], cfg),
        }
    if kind == "mamba":
        return {
            "ln1": L._norm_init(d, cfg.norm),
            "mamba": M2.init_mamba2(ks[0], cfg),
        }
    p = {
        "ln1": L._norm_init(d, cfg.norm),
        "attn": _init_attn(ks[0], cfg),
        "ln2": L._norm_init(d, cfg.norm),
    }
    if kind == "dense":
        p["ffn"] = L.init_ffn(ks[1], cfg)
    elif kind == "moe":
        p["moe"] = init_moe_params(ks[1], cfg.moe, d, plan, glu=cfg.glu)
        if cfg.moe.num_shared_experts:
            p["shared"] = L.init_ffn(
                ks[2], cfg, d_ff=cfg.moe.num_shared_experts * cfg.moe.d_ff_expert)
    return p


# =============================================================================
# Block forward
# =============================================================================

def _attn_fwd(p, x, cfg, plan, positions, cache, window, use_kernel=False):
    if cfg.attention == "mla":
        return L.mla_forward(p, x, cfg, plan, positions=positions,
                             cache=cache, window=window)
    return L.attention_forward(p, x, cfg, plan, positions=positions,
                               cache=cache, window=window,
                               use_kernel=use_kernel)


def _zero_stats() -> MoEStats:
    return zero_stats()


def _add_stats(a: MoEStats, b: MoEStats) -> MoEStats:
    # losses/drops/fault events sum across layers; the watchdog fields keep
    # the worst layer (max load fraction, min load entropy) — a single
    # collapsed layer must not be averaged away by healthy siblings
    return MoEStats(a.lb_loss + b.lb_loss, a.z_loss + b.z_loss,
                    a.drop_frac + b.drop_frac,
                    a.hop_drop_frac + b.hop_drop_frac,
                    a.fault_events + b.fault_events,
                    jnp.maximum(a.hop_max_load, b.hop_max_load),
                    jnp.minimum(a.hop_load_entropy, b.hop_load_entropy),
                    a.wire_faults + b.wire_faults)


def dense_block(p, x, cfg, plan, positions, cache, *, use_kernel=False,
                token_valid=None):
    window = cfg.window if cfg.attention == "sliding" else 0
    with jax.named_scope("attention"):
        h, cache = _attn_fwd(p["attn"], L.apply_norm(p["ln1"], x, cfg.norm),
                             cfg, plan, positions, cache, window, use_kernel)
        x = x + h
    with jax.named_scope("ffn"):
        h = L.ffn_forward(p["ffn"], L.apply_norm(p["ln2"], x, cfg.norm), cfg,
                          plan)
        x = x + h
    return x, _zero_stats(), cache


def moe_block(p, x, cfg, plan, positions, cache, *, use_kernel=False,
              token_valid=None):
    window = cfg.window if cfg.attention == "sliding" else 0
    with jax.named_scope("attention"):
        h, cache = _attn_fwd(p["attn"], L.apply_norm(p["ln1"], x, cfg.norm),
                             cfg, plan, positions, cache, window, use_kernel)
        x = x + h
    with jax.named_scope("moe"):
        x, stats = _moe_ffn(p, x, cfg, plan, use_kernel, token_valid)
    return x, stats, cache


def _moe_ffn(p, x, cfg, plan, use_kernel, token_valid):
    """The MoE sublayer of :func:`moe_block`: norm, token split, routed and
    shared experts, unsplit, residual."""
    hn = L.apply_norm(p["ln2"], x, cfg.norm)
    B, T, d = hn.shape
    flat = hn.reshape(B * T, d)
    loc, _ = comm.split_tokens(flat, plan.tp_axis, max(plan.tp, 1))
    # decode-tick validity rides the same token split as the activations,
    # so each shard masks exactly its own rows (split padding lands False)
    valid_loc = None
    if token_valid is not None:
        valid_loc, _ = comm.split_tokens(token_valid.reshape(B * T),
                                         plan.tp_axis, max(plan.tp, 1))
    y_loc, stats = moe_layer(p["moe"], loc, cfg.moe, plan, act=cfg.act,
                             use_kernel=use_kernel, token_valid=valid_loc)
    if "shared" in p:
        # shared ("always-on") expert computed on the token-split shard with
        # REPLICATED weights: same FLOPs/device as the tensor-parallel
        # formulation (tokens/tp x full d_ff vs tokens x d_ff/tp) but ZERO
        # collectives — removes one psum per MoE layer (EXPERIMENTS §Perf-2c).
        ps = p["shared"]
        actf = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        hh = actf(loc @ ps["w1"].astype(loc.dtype))
        if "w3" in ps:
            hh = hh * (loc @ ps["w3"].astype(loc.dtype))
        y_loc = y_loc + hh @ ps["w2"].astype(loc.dtype)
    y = comm.name_saved(
        comm.unsplit_tokens(y_loc, plan.tp_axis, B * T)).reshape(B, T, d)
    return x + y, stats


def rwkv_block(p, x, cfg, plan, positions, cache, *, use_kernel=False,
               token_valid=None):
    c_t = None if cache is None else cache
    h, c1 = RW.rwkv_tmix_forward(p["tmix"],
                                 L.apply_norm(p["ln1"], x, "layernorm"),
                                 cfg, plan, cache=c_t, use_kernel=use_kernel)
    x = x + h
    h, c2 = RW.rwkv_cmix_forward(p["cmix"],
                                 L.apply_norm(p["ln2"], x, "layernorm"),
                                 cfg, plan, cache=c_t)
    x = x + h
    cache = None if cache is None else {**c1, **c2}
    return x, _zero_stats(), cache


def mamba_block(p, x, cfg, plan, positions, cache, *, use_kernel=False,
                token_valid=None):
    h, cache = M2.mamba2_forward(p["mamba"],
                                 L.apply_norm(p["ln1"], x, cfg.norm),
                                 cfg, plan, cache=cache)
    return x + h, _zero_stats(), cache


BLOCK_FNS = {"dense": dense_block, "moe": moe_block, "rwkv": rwkv_block,
             "mamba": mamba_block}


# =============================================================================
# Stage init / forward (scan over stacked block params)
# =============================================================================

def init_stage(key, cfg: ModelConfig, stage: Stage, plan: MeshPlan) -> Dict:
    R = stage.repeats
    keys = jax.random.split(key, R + 2)
    if stage.kind == "pair":
        dense = jax.vmap(lambda k: init_block(k, cfg, "dense", plan))(keys[:R])
        moe = jax.vmap(lambda k: init_block(k, cfg, "moe", plan))(
            jax.random.split(keys[R], R))
        return {"dense": dense, "moe": moe}
    if stage.kind == "mamba_group":
        g = cfg.ssm_layers_per_attn
        def group_init(k):
            kk = jax.random.split(k, g)
            return jax.vmap(lambda kx: init_block(kx, cfg, "mamba", plan))(kk)
        blocks = jax.vmap(group_init)(keys[:R])               # (R, g, ...)
        shared = init_block(keys[R], cfg, "dense", plan)      # shared attn+ffn
        return {"mamba": blocks, "shared_attn": shared}
    blocks = jax.vmap(lambda k: init_block(k, cfg, stage.kind, plan))(keys[:R])
    return {"blocks": blocks}


def stage_forward(params: Dict, x, cfg: ModelConfig, stage: Stage,
                  plan: MeshPlan, positions, caches, *, remat: bool,
                  use_kernel: bool = False, token_valid=None):
    """Scan the stage's blocks over the stacked leading axis."""

    def run(kind, p_stacked, x, caches):
        fn = BLOCK_FNS[kind]

        def body(carry, inp):
            x, acc = carry
            p, cache = inp
            y, stats, cache = fn(p, x, cfg, plan, positions, cache,
                                 use_kernel=use_kernel,
                                 token_valid=token_valid)
            return (y, _add_stats(acc, stats)), cache

        if remat:
            policy = (comm.save_collectives_policy()
                      if cfg.remat_save_collectives else None)
            body = jax.checkpoint(body, policy=policy)
        (x, acc), new_caches = lax.scan(body, (x, _zero_stats()),
                                        (p_stacked, caches))
        return x, acc, new_caches

    if stage.kind == "pair":
        x, s1, c1 = run("dense", params["dense"], x,
                        None if caches is None else caches["dense"])
        x, s2, c2 = run("moe", params["moe"], x,
                        None if caches is None else caches["moe"])
        cc = None if caches is None else {"dense": c1, "moe": c2}
        return x, _add_stats(s1, s2), cc

    if stage.kind == "mamba_group":
        shared = params["shared_attn"]

        def body(carry, inp):
            x, acc = carry
            p_group, cache = inp
            # inner: g mamba blocks
            def inner(c2, inp2):
                xx, acc2 = c2
                pb, cb = inp2
                y, st, cb = mamba_block(pb, xx, cfg, plan, positions, cb)
                return (y, _add_stats(acc2, st)), cb
            (x, acc), mcache = lax.scan(
                inner, (x, acc),
                (p_group, None if cache is None else cache["mamba"]))
            # shared attention block (same params every group)
            x, st, acache = dense_block(shared, x, cfg, plan, positions,
                                        None if cache is None else cache["attn"])
            acc = _add_stats(acc, st)
            return (x, acc), (None if cache is None
                              else {"mamba": mcache, "attn": acache})

        if remat:
            policy = (comm.save_collectives_policy()
                      if cfg.remat_save_collectives else None)
            body = jax.checkpoint(body, policy=policy)
        (x, acc), new_caches = lax.scan(body, (x, _zero_stats()),
                                        (params["mamba"], caches))
        return x, acc, new_caches

    return run(stage.kind, params["blocks"], x, caches)


# =============================================================================
# Whole model
# =============================================================================

def init_model(key: jax.Array, cfg0: ModelConfig, plan: MeshPlan) -> Dict:
    cfg = _model_cfg(cfg0, plan)
    stages = build_stages(cfg)
    keys = jax.random.split(key, len(stages) + 6)
    params: Dict[str, Any] = {}
    if cfg.num_codebooks > 1:
        params["embed"] = {"table": L.dense_init(
            keys[-1], (cfg.num_codebooks, cfg.vocab_size, cfg.d_model),
            scale=0.02)}
        params["heads"] = {"w": L.dense_init(
            keys[-2], (cfg.num_codebooks, cfg.vocab_size, cfg.d_model),
            scale=0.02)}
    else:
        params["embed"] = L.init_embedding(keys[-1], cfg, plan)
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": L.dense_init(
                keys[-2], (cfg.vocab_size, cfg.d_model), scale=0.02)}
    if cfg.vision_tokens:
        params["vision_proj"] = {
            "w": L.dense_init(keys[-3], (cfg.vision_embed_dim, cfg.d_model))}
    params["stages"] = tuple(
        init_stage(k, cfg, st, plan) for k, st in zip(keys, stages))
    params["final_norm"] = L._norm_init(cfg.d_model, cfg.norm)
    if cfg.mtp_depth:
        params["mtp"] = {
            "proj": L.dense_init(keys[-4], (2 * cfg.d_model, cfg.d_model)),
            "block": init_block(keys[-5], cfg, "dense", plan),
            "norm_h": L._norm_init(cfg.d_model, cfg.norm),
            "norm_e": L._norm_init(cfg.d_model, cfg.norm),
        }
    return params


def embed_inputs(params: Dict, tokens: jax.Array, cfg: ModelConfig,
                 plan: MeshPlan, extra: Optional[Dict] = None,
                 dtype=jnp.bfloat16) -> jax.Array:
    """Token (and modality) embedding. musicgen: tokens (B, K, S) summed over
    codebooks; phi-3-vision: image patch embeddings merged at given positions."""
    if cfg.num_codebooks > 1:
        table = params["embed"]["table"]                 # (K, V_loc, d) sharded
        v_loc = table.shape[1]
        start = comm.axis_index(plan.tp_axis) * v_loc
        local = tokens - start                           # (B, K, S)
        hit = (local >= 0) & (local < v_loc)
        emb = jax.vmap(lambda tab, ids: jnp.take(tab, ids, axis=0),
                       in_axes=(0, 1), out_axes=1)(
            table, jnp.clip(local, 0, v_loc - 1))        # (B, K, S, d)
        emb = emb * hit[..., None].astype(table.dtype)
        x = comm.psum(emb.sum(axis=1), plan.tp_axis).astype(dtype)
        return x
    x = L.embed_tokens(params["embed"], tokens, plan, dtype)
    if cfg.vision_tokens and extra is not None and "image_embeds" in extra:
        proj = jnp.einsum("bpe,ed->bpd", extra["image_embeds"].astype(dtype),
                          params["vision_proj"]["w"].astype(dtype))
        pos = extra["image_pos"]                          # (B, P) int32
        x = jax.vmap(lambda xb, pb, vb: xb.at[pb].set(vb))(x, pos, proj)
    return x


def model_logits(params: Dict, x: jax.Array, cfg: ModelConfig,
                 plan: MeshPlan) -> jax.Array:
    """Vocab-sharded fp32 logits. musicgen: (B, T, K, V_loc)."""
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    if cfg.num_codebooks > 1:
        return jnp.einsum("btd,kvd->btkv", x.astype(jnp.float32),
                          params["heads"]["w"].astype(jnp.float32))
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return L.output_logits(head, x, plan)


def forward(params: Dict, tokens: jax.Array, cfg0: ModelConfig,
            plan: MeshPlan, *, positions: jax.Array,
            caches: Optional[Tuple] = None, extra: Optional[Dict] = None,
            remat: bool = False, use_kernel: bool = False,
            token_valid: Optional[jax.Array] = None):
    """Full forward. Returns (hidden (B,T,d), logits, MoEStats, new_caches).

    ``token_valid`` (B, T) bool, optional: live-token mask for decode-shaped
    calls (continuous batching, bucketed prefill tails).  Only the MoE blocks
    consume it — invalid tokens route nowhere and are excluded from the
    router losses; attention over dead rows is masked by the caller via
    negative ``positions`` (see ``serve/engine.py``)."""
    cfg = _model_cfg(cfg0, plan)
    stages = build_stages(cfg)
    with jax.named_scope("embed"):
        x = embed_inputs(params, tokens, cfg, plan, extra)
    acc = _zero_stats()
    new_caches = []
    for i, st in enumerate(stages):
        c = None if caches is None else caches[i]
        x, stats, c = stage_forward(params["stages"][i], x, cfg, st, plan,
                                    positions, c, remat=remat,
                                    use_kernel=use_kernel,
                                    token_valid=token_valid)
        acc = _add_stats(acc, stats)
        new_caches.append(c)
    with jax.named_scope("lm_head"):
        logits = model_logits(params, x, cfg, plan)
    return x, logits, acc, (None if caches is None else tuple(new_caches))


def mtp_logits(params: Dict, hidden: jax.Array, next_tokens: jax.Array,
               cfg0: ModelConfig, plan: MeshPlan,
               positions: jax.Array) -> jax.Array:
    """DeepSeek-V3 multi-token prediction head (depth 1): predict t+2 from
    (h_t, Emb(t+1)). Returns vocab-sharded logits."""
    cfg = _model_cfg(cfg0, plan)
    p = params["mtp"]
    e = L.embed_tokens(params["embed"], next_tokens, plan, hidden.dtype)
    h = jnp.concatenate([L.apply_norm(p["norm_h"], hidden, cfg.norm),
                         L.apply_norm(p["norm_e"], e, cfg.norm)], axis=-1)
    h = jnp.einsum("btd,dk->btk", h, p["proj"].astype(h.dtype))
    h, _, _ = dense_block(p["block"], h, cfg, plan, positions, None)
    return model_logits(params, h, cfg, plan)


# =============================================================================
# Caches
# =============================================================================

def init_caches(cfg0: ModelConfig, batch: int, length: int, plan: MeshPlan):
    """Per-stage stacked decode caches sized ``length`` (window for sliding)."""
    cfg = _model_cfg(cfg0, plan)
    stages = build_stages(cfg)
    if cfg.attention == "sliding":
        length = min(length, cfg.window)

    def attn_cache():
        if cfg.attention == "mla":
            return L.init_mla_cache(cfg, batch, length, plan)
        return L.init_attention_cache(cfg, batch, length, plan)

    def stack(tree, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)

    out = []
    for st in stages:
        if st.kind == "rwkv":
            out.append(stack(RW.init_rwkv_cache(cfg, batch, plan), st.repeats))
        elif st.kind == "mamba_group":
            g = cfg.ssm_layers_per_attn
            out.append(stack({"mamba": stack(M2.init_mamba2_cache(cfg, batch, plan), g),
                              "attn": attn_cache()}, st.repeats))
        elif st.kind == "pair":
            out.append({"dense": stack(attn_cache(), st.repeats),
                        "moe": stack(attn_cache(), st.repeats)})
        else:
            out.append(stack(attn_cache(), st.repeats))
    return tuple(out)
