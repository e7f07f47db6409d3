"""Mixture-of-Experts layers with one-hop (Switch) and bi-level (SMILE) routing.

This module is the paper's contribution.  Two collective schedules are
implemented behind the same layer interface — and, as of the hop-pipeline
refactor, as two *thin definitions over one shared executor*:

* ``router="switch"`` — one-hop routing: a single flat All2All over the whole
  expert grid ``(n x m slots)``, exactly the Switch-Transformer baseline the
  paper measures against (paper §3.1, Fig. 2/3).

* ``router="smile"`` — bi-level routing (paper §3.2): an inter-node router
  ``p(x) in R^n`` dispatches tokens across the *inter* mesh axes only, then an
  intra-node router ``q(x) in R^{E/n}`` dispatches within the node across the
  *intra* mesh axes. Combine weight is ``p_i * q_j`` (Eq. 3). Four All2Alls
  per layer (two forward, two reversed — paper Fig. 5), each confined to one
  level of the network hierarchy.

**Hop-pipeline architecture** (:mod:`repro.core.pipeline`).  SMILE's thesis
is that routing is compositional — Switch is one dispatch hop, SMILE is two
nested ones — so the layer bodies here only *declare* that composition:

* :func:`switch_moe` builds ONE :class:`~repro.core.pipeline.ExpertHop`
  whose router maps each token's top-k experts onto the flat virtual-group
  grid and whose :class:`~repro.core.pipeline.HopSpec` spans the joint
  ``(inter x intra)`` mesh axes;
* :func:`smile_moe` builds TWO hops — an inter-node hop (groups = nodes,
  axes = ``plan.ep_inter``) whose inner compute is an intra-node hop
  (groups = per-node virtual experts, axes = ``plan.ep_intra``) — the
  level-2 router running on *arrived* tokens exactly as the paper draws it;

and both hand their hop list to the same
:func:`~repro.core.pipeline.execute_pipeline`, which owns every mechanism
the old monolithic bodies duplicated: dispatch backend selection
(``MoEConfig.dispatch_backend``: ``"sort"`` / ``"dense"`` capacity buffers
vs ``"dropless"`` tile-aligned ragged layouts), the exchange kind per hop
(``local`` | ``padded`` fixed-shape All2All | ``ragged`` exact-segment
All2All, ``MoEConfig.ragged_a2a``), the group sort implementation
(``MoEConfig.sort_impl``: XLA argsort vs the one-pass Pallas counting
sort), the routing-stage implementation (``MoEConfig.router_impl``:
separate XLA ops vs the fused Pallas routing megakernel, consumed by the
shared :func:`router_topk` prologue every hop router calls),
rank-major group relabeling so every wire format sees contiguous
per-rank segments, the ragged receive-bound factor
(``MoEConfig.recv_bound_factor`` — bounded receive slabs with clamp-drops
echoed on the reverse path), the expert-FFN flavor (padded / ragged /
compact, Pallas kernels via ``use_kernel``), and one
:class:`~repro.core.pipeline.MoEStats` accumulation path with per-hop
``drop_frac``.  A backend, wire, or kernel improvement lands in the
executor once and every schedule — Switch's flat hop and both SMILE levels
— inherits it; see the pipeline module docstring for how each existing
backend maps onto the IR.

The expert grid is *logical* ``(n, m)`` (from config) and is folded onto the
physical mesh axes, so the identical code runs on a single device (pure-jnp
oracle for tests), on small fake-device test meshes, and on the 256/512-chip
production meshes.

Capacity semantics follow the paper: per-group capacity
``C = ceil(k * T * capacity_factor / groups)``; overflow tokens are dropped
(contribute zeros through the residual connection).  The ``"dropless"``
backend replaces capacity buffers with exact ragged layouts — zero padding
into the FFN and zero drops end-to-end (unless a receive bound is
configured, which trades bounded worst-case clamp drops for a ~P-fold
smaller post-hop FFN bound).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.common.config import MoEConfig
from repro.core import pipeline as PL
from repro.core.dispatch import (combine_gather, dispatch_scatter,
                                 positions_in_group, scatter_flags)
from repro.core.layout import ExpertLayout, make_layout
# re-exported for backward compatibility (tests, benchmarks and downstream
# code import the loss/FFN/stats machinery from here)
from repro.core.pipeline import (MoEStats, execute_pipeline, experts_ffn,
                                 experts_ffn_compact,
                                 experts_ffn_compact_rows, experts_ffn_ragged,
                                 lb_loss_terms, scaled_lb_loss, z_loss,
                                 zero_stats)
from repro.sharding import comm
from repro.sharding.plan import MeshPlan

__all__ = [
    "MoEStats", "zero_stats", "router_probs", "topk_gates", "router_topk",
    "capacity",
    "lb_loss_terms", "scaled_lb_loss", "z_loss", "experts_ffn",
    "experts_ffn_ragged", "experts_ffn_compact", "experts_ffn_compact_rows",
    "switch_moe", "smile_moe", "moe_layer", "init_moe_params",
    "combine_gather", "dispatch_scatter", "positions_in_group",
    "scatter_flags",
]


# =============================================================================
# Routing math (pure, per-device)
# =============================================================================

def router_probs(x: jax.Array, w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Eq. 1: softmax router probabilities, computed in fp32.

    Returns ``(probs, logits)`` — both (t, E); logits feed the z-loss.
    """
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        w.astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1), logits


def topk_gates(probs: jax.Array, k: int, renorm: bool) -> Tuple[jax.Array, jax.Array]:
    """Top-k expert selection. Returns (gates (t,k), idx (t,k))."""
    gates, idx = lax.top_k(probs, k)
    if renorm and k > 1:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def router_topk(x: jax.Array, w: jax.Array, k: int, renorm: bool,
                impl: str = "unfused"
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The routing prologue every hop shares: GEMM -> softmax -> top-k.

    Returns ``(gates (t,k), idx (t,k), probs (t,E), logits (t,E))``.
    ``impl`` is ``MoEConfig.router_impl``: ``"unfused"`` runs the separate
    XLA ops above; ``"fused"`` runs the single-pass Pallas routing
    megakernel (:func:`repro.kernels.ops.router_fused` — which also emits
    the counting-sort dispatch positions over the chosen ids without a
    separate sort pass), with bit-compatible outputs either way.  All three
    hop routers — switch's flat hop and both SMILE levels — route through
    here, so the impl switch needs zero per-caller code.
    """
    if impl == "fused":
        from repro.kernels import ops as kops
        gates, idx, probs, logits, _, _ = kops.router_fused(
            x, w, k, renorm=renorm)
        return gates, idx, probs, logits
    if impl != "unfused":
        raise ValueError(f"unknown router_impl {impl!r}; "
                         f"expected \"unfused\" or \"fused\"")
    probs, logits = router_probs(x, w)
    gates, idx = topk_gates(probs, k, renorm)
    return gates, idx, probs, logits


def capacity(tokens: int, k: int, factor: float, groups: int) -> int:
    return max(1, math.ceil(tokens * k * factor / groups))


# =============================================================================
# Mesh/layout helpers shared by the hop builders
# =============================================================================

def _sync_axes(plan: MeshPlan) -> Tuple[str, ...]:
    """All mesh axes across which this step's tokens are distinct (dedup'd)."""
    return tuple(dict.fromkeys(
        tuple(plan.dp_axes) + tuple(plan.ep_axes) + tuple(plan.tp_axes())))


def _grid(cfg: MoEConfig, plan: MeshPlan) -> Tuple[int, int]:
    n, m = cfg.grid
    if n == 0 or m == 0:
        n, m = max(plan.n_inter, 1), max(plan.n_intra, 1)
    if n % max(plan.n_inter, 1) or m % max(plan.n_intra, 1):
        raise ValueError(f"logical grid {(n, m)} must fold onto mesh grid "
                         f"({plan.n_inter}, {plan.n_intra})")
    return n, m


def _my_expert_weights(w: Dict[str, jax.Array], layout: ExpertLayout,
                       plan: MeshPlan, b_n: int, b_m: int):
    """Select this device's expert weights (``wsel``): b_n * owned groups.

    Weights are stored (n_g, E_pn, d, f) sharded (inter, intra?) so the local
    leaf is (b_n, E_pn_local, d, f). For replicated layouts (r > 1) the leaf
    holds all per-node experts and we gather the ones backing our slots.

    With the experts sharded intra-node and b_n > 1 the leaf is passed on
    unmerged, (b_n, E_pn_local, d, f): merging b_n into the expert dimension
    is a reshape XLA does not fuse across, so the weights' bf16 cast would
    run stand-alone and the weight gradient would reach the optimizer in
    the merged shape, where its clip scale cannot fuse into LAMB.  The padded
    XLA FFN (:func:`~repro.core.pipeline.experts_ffn`) batches over both
    group dimensions; every other consumer (the Pallas kernels, the ragged
    and compact FFNs) merges it to (G, d, f) at its own entry
    (:func:`~repro.core.pipeline.merge_groups`).  With b_n == 1 the merge
    only drops a unit dimension and is kept; replicated layouts gather
    (b_n * b_m, d, f).
    """
    out = {}
    if layout.shard_intra:
        # leaf dim1 already == b_m * h experts owned by this device
        for k, v in w.items():
            if v is None:
                continue
            out[k] = v if b_n > 1 else v.reshape((-1,) + v.shape[2:])
        return out, b_n * w["w1"].shape[1]
    # replicated layout: slots j_lo..j_lo+b_m map to experts slot // r
    j = comm.axis_index(plan.ep_intra) * b_m
    slot_ids = j + jnp.arange(b_m)
    expert_ids = slot_ids // layout.r                     # (b_m,)
    for k, v in w.items():
        if v is None:
            continue
        sel = jnp.take(v, expert_ids, axis=1)             # (b_n, b_m, d, f)
        out[k] = sel.reshape((-1,) + v.shape[2:])
    return out, b_n * b_m


def _rank_major_perm(V: int, vpn: int, b_n: int, b_mh: int,
                     m_mesh: int) -> Optional[jax.Array]:
    """Canonical (node-major) virtual-group id -> rank-major id.

    Canonical ``g = node * vpn + v_in_node``; joint rank over
    ``(inter, intra)`` owns nodes ``[rank_n*b_n, ...)`` and per-node slots
    ``[rank_m*b_mh, ...)``.  Identity (None) when the hop's mesh is 1x1 —
    and a pure *label* permutation otherwise: per-group contents, positions
    and capacity decisions are label-invariant (see pipeline docstring).
    """
    g = np.arange(V)
    node, vin = g // vpn, g % vpn
    rank = (node // b_n) * m_mesh + vin // b_mh
    local = (node % b_n) * b_mh + vin % b_mh
    perm = rank * (b_n * b_mh) + local
    if np.array_equal(perm, g):
        return None
    return jnp.asarray(perm, jnp.int32)


def _exchange_kind(cfg: MoEConfig, n_ranks: int, innermost: bool) -> str:
    """Map MoEConfig onto a HopSpec exchange kind (one place, all hops)."""
    if cfg.dispatch_backend != "dropless":
        return "padded"
    if innermost and n_ranks == 1:
        return "local"                    # capacity- and exchange-free
    return "ragged" if cfg.ragged_a2a else "padded"


# =============================================================================
# One-hop (Switch) schedule — the baseline, as a 1-hop pipeline
# =============================================================================

def switch_moe(params: Dict, x: jax.Array, cfg: MoEConfig, plan: MeshPlan,
               *, act: str = "gelu", renorm: bool = False,
               use_kernel: bool = False,
               token_valid=None) -> Tuple[jax.Array, MoEStats]:
    """One-hop MoE layer over local tokens ``x``: (t, d) -> (t, d).

    A single :class:`~repro.core.pipeline.ExpertHop` spanning the whole
    (inter x intra) expert grid; all mechanics live in the executor.
    ``token_valid`` (t,) bool masks dead rows (decode ticks); ``None``
    means all valid.
    """
    t, d = x.shape
    n_g, m_g = _grid(cfg, plan)
    layout = make_layout(cfg.num_experts, n_g, m_g)
    E, k = cfg.num_experts, cfg.top_k
    e_pn = layout.experts_per_node
    vpn = layout.virtual_per_node
    n_mesh, m_mesh = max(plan.n_inter, 1), max(plan.n_intra, 1)
    nm_mesh = plan.ep
    b_n, b_m = n_g // n_mesh, m_g // m_mesh
    b_mh = vpn // m_mesh
    V = layout.virtual_total

    def route(xx, token_valid, outer_gid):
        gates, eidx, probs, logits = router_topk(
            xx, params["router"]["w"], k, renorm, cfg.router_impl)   # (t, E)
        # map expert -> (node, slot-in-node, expert-in-slot) -> virtual group
        e_flat = eidx.reshape(-1)                                   # (A,)
        A = e_flat.shape[0]
        node = e_flat // e_pn
        e_local = e_flat % e_pn
        if layout.r > 1:
            # spread token assignments round-robin over the r replicas
            rr = (jnp.arange(A) // k + jnp.arange(A) % k) % layout.r
            v_in_node = e_local * layout.r + rr
        else:
            v_in_node = e_local                     # == slot * h + in-slot
        v = node * vpn + v_in_node                                  # (A,)
        valid = jnp.repeat(token_valid, k) if k > 1 else token_valid
        return PL.RouteDecision(gates.reshape(-1), v, valid, token_valid,
                                probs, logits, eidx[:, 0], k)

    spec = PL.HopSpec(
        name="flat", axes=plan.ep_axes, n_ranks=nm_mesh, num_groups=V,
        exchange=_exchange_kind(cfg, nm_mesh, innermost=True),
        capacity=capacity(t, k, cfg.capacity_factor, V),
        perm=_rank_major_perm(V, vpn, b_n, b_mh, m_mesh),
        recv_bound_factor=cfg.recv_bound_factor,
        lb_coef=cfg.lb_alpha, loss_groups=E,
        wire_integrity=cfg.wire_integrity)

    wsel, n_groups = _my_expert_weights(params["experts"], layout, plan,
                                        b_n, b_m)
    assert n_groups == spec.groups_per_rank, (n_groups, spec)
    return execute_pipeline(x, [PL.ExpertHop(route, spec)], wsel, cfg,
                            act=act, use_kernel=use_kernel,
                            sync=_sync_axes(plan), token_valid=token_valid)


# =============================================================================
# Bi-level (SMILE) schedule — the paper's contribution, as a 2-hop pipeline
# =============================================================================

def smile_moe(params: Dict, x: jax.Array, cfg: MoEConfig, plan: MeshPlan,
              *, act: str = "gelu", renorm: bool = False, top_g: int = 1,
              use_kernel: bool = False,
              token_valid=None) -> Tuple[jax.Array, MoEStats]:
    """Bi-level MoE layer over local tokens ``x``: (t, d) -> (t, d).

    Hop 1: inter-node router p (t, n) over ``plan.ep_inter``.  Hop 2
    (hop 1's inner compute): intra-node router q on *arrived* tokens over
    ``plan.ep_intra``.  The executor mirrors both reverse hops (4 All2Alls
    total); combine weight = p_i * q_j (Eq. 3) falls out of the nested
    gate-weighted combines.  Routers are shared across devices (same
    parameters everywhere), as in the paper.
    """
    t, d = x.shape
    n_g, m_g = _grid(cfg, plan)
    layout = make_layout(cfg.num_experts, n_g, m_g)
    e_pn = layout.experts_per_node
    vpn = layout.virtual_per_node
    k_local = max(1, cfg.top_k // top_g)
    n_mesh, m_mesh = max(plan.n_inter, 1), max(plan.n_intra, 1)
    b_n, b_m = n_g // n_mesh, m_g // m_mesh
    b_mh = vpn // m_mesh
    V2 = b_n * vpn                          # per-device virtual groups, hop 2

    # ---------------- hop 1: route to node -----------------------------------
    def route_inter(xx, token_valid, outer_gid):
        gates, nidx, probs, logits = router_topk(
            xx, params["router_inter"]["w"], top_g, renorm,
            cfg.router_impl)                                           # (t,n)
        valid = (jnp.repeat(token_valid, top_g) if top_g > 1
                 else token_valid)
        return PL.RouteDecision(gates.reshape(-1), nidx.reshape(-1), valid,
                                token_valid, probs, logits, nidx[:, 0],
                                top_g)

    cap1 = capacity(t, top_g, cfg.capacity_factor, n_g)
    spec1 = PL.HopSpec(
        name="inter", axes=plan.ep_inter, n_ranks=n_mesh, num_groups=n_g,
        exchange=_exchange_kind(cfg, n_mesh, innermost=False),
        capacity=cap1, perm=None,           # node ids are already rank-major
        recv_bound_factor=cfg.recv_bound_factor,
        lb_coef=cfg.lb_alpha, loss_groups=n_g,
        wire_integrity=cfg.wire_integrity)

    # ---------------- hop 2: route within node -------------------------------
    def route_intra(x1, valid1, node_row):
        gates, qidx, probs, logits = router_topk(
            x1, params["router_intra"]["w"], k_local, renorm,
            cfg.router_impl)
        q1 = qidx.reshape(-1)                                       # (A2,)
        A2 = q1.shape[0]
        validA = jnp.repeat(valid1, k_local) if k_local > 1 else valid1
        if layout.r > 1:
            rr = jnp.arange(A2) % layout.r
            v_in_node = q1 * layout.r + rr
        else:
            v_in_node = q1
        # per-node virtual groups, node-major (canonical)
        node_of = (jnp.repeat(node_row, k_local) if k_local > 1
                   else node_row)
        v2 = node_of * vpn + v_in_node
        return PL.RouteDecision(gates.reshape(-1), v2, validA, valid1,
                                probs, logits, qidx[:, 0], k_local)

    if cfg.tight_level2_capacity:
        # beyond-paper: the level-1 buffer is ~cap-factor x larger than the
        # tokens it actually carries; sizing level-2 capacity from EXPECTED
        # valid arrivals (t * g / n per node, x cap headroom) instead of the
        # padded buffer removes the capacity compounding that doubles the
        # intra-node All2All payload (EXPERIMENTS.md §Perf-2).
        expected = max(1, math.ceil(t * top_g / n_g))
        cap2 = capacity(expected, k_local, cfg.capacity_factor, vpn)
    else:
        cap2 = capacity(n_mesh * cap1, k_local, cfg.capacity_factor, vpn)
    spec2 = PL.HopSpec(
        name="intra", axes=plan.ep_intra, n_ranks=m_mesh, num_groups=V2,
        exchange=_exchange_kind(cfg, m_mesh, innermost=True),
        capacity=cap2, perm=_rank_major_perm(V2, vpn, b_n, b_mh, m_mesh),
        recv_bound_factor=cfg.recv_bound_factor,
        lb_coef=cfg.lb_beta, loss_groups=e_pn,
        wire_integrity=cfg.wire_integrity)

    wsel, n_groups = _my_expert_weights(params["experts"], layout, plan,
                                        b_n, b_m)
    assert n_groups == spec2.groups_per_rank, (n_groups, spec2)
    return execute_pipeline(
        x, [PL.ExpertHop(route_inter, spec1), PL.ExpertHop(route_intra, spec2)],
        wsel, cfg, act=act, use_kernel=use_kernel, sync=_sync_axes(plan),
        token_valid=token_valid)


# =============================================================================
# Parameter init
# =============================================================================

def init_moe_params(key: jax.Array, cfg: MoEConfig, d_model: int,
                    plan: MeshPlan, *, glu: bool = False,
                    param_dtype=jnp.float32) -> Dict:
    """Init MoE layer params. Expert tensors are stored (n_g, E_pn, d, f)."""
    n_g, m_g = _grid(cfg, plan)
    layout = make_layout(cfg.num_experts, n_g, m_g)
    e_pn = layout.experts_per_node
    f = cfg.d_ff_expert
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(f)
    experts = {
        "w1": (jax.random.normal(k1, (n_g, e_pn, d_model, f)) * scale_in
               ).astype(param_dtype),
        "w2": (jax.random.normal(k2, (n_g, e_pn, f, d_model)) * scale_out
               ).astype(param_dtype),
    }
    if glu:
        experts["w3"] = (jax.random.normal(k3, (n_g, e_pn, d_model, f))
                         * scale_in).astype(param_dtype)
    p: Dict = {"experts": experts}
    if cfg.router == "smile":
        p["router_inter"] = {"w": (jax.random.normal(k4, (d_model, n_g))
                                   * scale_in).astype(param_dtype)}
        p["router_intra"] = {"w": (jax.random.normal(k5, (d_model, e_pn))
                                   * scale_in).astype(param_dtype)}
    else:
        p["router"] = {"w": (jax.random.normal(k4, (d_model, cfg.num_experts))
                             * scale_in).astype(param_dtype)}
    return p


def moe_layer(params: Dict, x: jax.Array, cfg: MoEConfig, plan: MeshPlan,
              *, act: str = "gelu", use_kernel: bool = False,
              token_valid=None) -> Tuple[jax.Array, MoEStats]:
    """Dispatch to the configured routing schedule. ``x``: (t, d) local tokens.

    ``token_valid`` (t,) bool, optional: live-token mask for decode-shaped
    calls (continuous-batching ticks where some slots are dead).  Invalid
    rows route nowhere — zero ragged segments on the wire, excluded from
    LB/z losses — and combine to exactly zero.
    """
    if cfg.router == "smile":
        return smile_moe(params, x, cfg, plan, act=act, renorm=cfg.renorm_gates,
                         top_g=cfg.top_g, use_kernel=use_kernel,
                         token_valid=token_valid)
    return switch_moe(params, x, cfg, plan, act=act, renorm=cfg.renorm_gates,
                      use_kernel=use_kernel, token_valid=token_valid)
