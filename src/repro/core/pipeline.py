"""Composable hop-pipeline IR for MoE routing schedules.

SMILE's core claim is that routing is *compositional*: Switch is ONE flat
dispatch hop over the whole expert grid; SMILE is TWO nested hops over
heterogeneous links (inter-node, then intra-node on the arrived tokens).
This module makes that composition a first-class object instead of two
parallel monoliths:

* :class:`RouteDecision` — what a router decided for one hop: per-assignment
  destination groups, gates and validity, plus the router's probs/logits for
  the load-balancing and z losses.  Produced by a hop's ``route`` callable,
  consumed by the executor.

* :class:`HopSpec` — the *static* schedule of one hop: which mesh axes its
  exchange spans, how many virtual groups it dispatches into, the exchange
  kind (``"local"`` | ``"padded"`` | ``"ragged"``), the capacity / receive
  bound policy, and the canonical→rank-major group relabeling permutation
  that makes every wire format see contiguous per-rank segments.

* :class:`ExpertHop` — one pipeline stage: a ``route`` callable bound to its
  :class:`HopSpec`.

* :func:`execute_pipeline` — the single executor both schedules share.  It
  walks the hop list recursively: route → dispatch (capacity buffer or
  tile-aligned ragged layout, per ``MoEConfig.dispatch_backend``) → exchange
  (identity / fixed-shape All2All / ragged All2All) → inner compute (the
  next hop, or the expert FFN at the innermost hop) → reverse exchange →
  gate-weighted combine; accumulating one :class:`MoEStats` with *per-hop*
  drop fractions along the way.  A backend or wire improvement lands here
  once and every schedule — Switch's flat hop and both SMILE levels —
  inherits it.

**Group relabeling.**  Every hop's virtual groups are relabeled rank-major
(``spec.perm``) *before* dispatch, so that rank ``p``'s groups occupy the
contiguous id range ``[p*gpr, (p+1)*gpr)``.  This collapses what used to be
three hand-maintained fold/transpose dances (switch's mesh-major fold,
SMILE's per-node fold2, the ragged relabels) into one generic
:func:`_fold` / :func:`_unfold` pair and one ragged wire layout.  The
relabel is a pure permutation of group *labels*: per-group contents,
positions and capacity decisions are label-invariant, so outputs are
bit-identical to the node-major formulation (pinned by
``tests/test_pipeline_golden.py``).

**Receive-bound factor** (ROADMAP follow-up, implemented here once for all
hops).  A ragged hop's receive slab is statically sized for worst-case skew
— ``P x R`` rows, the price of zero drops when every rank routes everything
to one place — and the post-hop compute bound (receiver re-compaction, the
recompacted FFN, SMILE's level-2 router) scales with it.
``HopSpec.recv_bound_factor`` bounds the slab at roughly
``factor x expected arrivals`` instead (tile-aligned, never above ``P x
R``): arrivals beyond the bound are clamp-dropped on the receiver, the
reverse hop echoes each receiver's clamped counts back through its own
count exchange so every sender learns exactly which of its rows returned,
and the executor reports the clamp drops in the hop's ``drop_frac``.
``factor=None`` (the default) keeps the bit-identical zero-drop worst-case
bound.  The payoff is a ~``P/factor``-fold smaller post-hop FFN bound —
what a production deployment runs with the LB loss keeping skew near 1.

**Wire integrity** (robustness follow-up, implemented here once for all
hops).  ``HopSpec.wire_integrity`` arms per-segment payload checksums on
every ragged exchange, both directions: the parity-row wire format lives
in :mod:`repro.sharding.comm` (one integrity word per (src, dst, group)
segment, riding the slab as an extra row — fold + length + identity tag);
verification, quarantine and the exact per-(hop, src rank) accounting
(``MoEStats.fault_events`` / ``wire_faults``) live in
:func:`_ragged_forward` / :func:`_ragged_reverse` below.  ``"detect"``
flags and passes payloads through (the A/B observability mode);
``"quarantine"`` zero-fills flagged segments and charges their assignments
to the hop's drop accounting via the echoed reverse — a corrupting peer
costs its own tokens, not the whole step.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.common import faultinject as FI
from repro.core import dispatch as D
from repro.sharding import comm

# number of hop slots in the fixed-shape per-hop drop vector (switch uses 1,
# SMILE 2; the vector is zero-padded so stats trees from different routers
# and dense layers always add)
MAX_HOPS = 2

EXCHANGES = ("local", "padded", "ragged")


# =============================================================================
# Layer stats (accumulated by the executor; one path for every schedule)
# =============================================================================

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MoEStats:
    """Aux outputs of a MoE layer (fp32 scalars / fixed-shape vectors).

    ``drop_frac`` is the summed-over-hops diagnostic every consumer already
    reads; ``hop_drop_frac`` is the per-hop breakdown — slot 0 is the
    outermost hop (switch's flat hop / SMILE level 1), slot 1 SMILE level 2,
    unused slots exactly 0.0 — with one accumulation shape for both routers
    (the executor owns it; the old per-schedule ad-hoc folding is gone).

    Robustness fields (fault-containment PR): ``fault_events`` counts, per
    hop, the count-grid entries the sanitizer rejected plus the wire
    segments the checksum layer flagged (psum'd over the sync axes —
    global totals, summed across layers); ``hop_max_load`` /
    ``hop_load_entropy`` feed the router-collapse watchdog — the global
    max-load fraction (f-vector max) and normalized load entropy (in
    [0, 1], 1 = uniform) per hop, accumulated worst-case across layers
    (max / min respectively; unused hop slots stay at the neutral 0 / 1).

    ``wire_faults`` (wire-integrity PR) localizes checksum verdicts: entry
    ``[hop, s]`` is the global number of (receiver, direction) checks that
    flagged source rank ``s`` (ranks folded mod :data:`WIRE_SRC_BINS`) on
    that hop — the "which rank is corrupting the wire" dashboard row.
    All-zero whenever ``wire_integrity="off"`` or the wire is healthy.
    """
    lb_loss: jax.Array
    z_loss: jax.Array
    # diagnostic: fraction of token-assignments dropped (capacity overflow
    # on padded hops, receive-bound clamping on bounded ragged hops,
    # quarantined/suppressed segments under count faults)
    drop_frac: jax.Array
    hop_drop_frac: jax.Array        # (MAX_HOPS,) per-hop breakdown
    fault_events: jax.Array         # (MAX_HOPS,) sanitizer + wire rejections
    hop_max_load: jax.Array         # (MAX_HOPS,) max f-vector entry
    hop_load_entropy: jax.Array     # (MAX_HOPS,) normalized load entropy
    wire_faults: jax.Array          # (MAX_HOPS, WIRE_SRC_BINS) per-src-rank


# source-rank bins of MoEStats.wire_faults (ranks folded mod this; fixed so
# stats trees from different mesh shapes always add)
WIRE_SRC_BINS = 16

WIRE_POLICIES = ("off", "detect", "quarantine")


def zero_stats() -> MoEStats:
    z = jnp.float32(0.0)
    zv = jnp.zeros((MAX_HOPS,), jnp.float32)
    return MoEStats(z, z, z, zv, zv,
                    zv, jnp.ones((MAX_HOPS,), jnp.float32),
                    jnp.zeros((MAX_HOPS, WIRE_SRC_BINS), jnp.float32))


# =============================================================================
# Routing losses (pure; shared by every hop)
# =============================================================================

def lb_loss_terms(probs: jax.Array, top1: jax.Array, valid: jax.Array,
                  num_groups: int, sync_axes) -> Tuple[jax.Array, jax.Array]:
    """Return globally-averaged (f, P) vectors for one router (paper Eq. 4).

    ``f_i`` — fraction of tokens whose argmax picked group i;
    ``P_i`` — mean router probability mass on group i.
    Both are psum'd over ``sync_axes`` so every device sees global stats.
    """
    v = valid.astype(jnp.float32)
    cnt = comm.psum(v.sum(), sync_axes)
    one = jax.nn.one_hot(top1, num_groups, dtype=jnp.float32) * v[:, None]
    f = comm.psum(one.sum(0), sync_axes) / jnp.maximum(cnt, 1.0)
    p = comm.psum((probs * v[:, None]).sum(0), sync_axes) / jnp.maximum(cnt, 1.0)
    return f, p


def scaled_lb_loss(f: jax.Array, p: jax.Array, coef: float) -> jax.Array:
    """``coef * groups * sum_i f_i P_i`` — min = coef at uniform routing."""
    n = f.shape[0]
    return coef * n * jnp.sum(f * p)


def z_loss(logits: jax.Array, valid: jax.Array, coef: float, sync_axes):
    if coef == 0.0:
        return jnp.float32(0.0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    v = valid.astype(jnp.float32)
    s = comm.psum((jnp.square(lse) * v).sum(), sync_axes)
    cnt = comm.psum(v.sum(), sync_axes)
    return coef * s / jnp.maximum(cnt, 1.0)


# =============================================================================
# Expert FFN flavors (padded / ragged / compact) — Pallas kernels plug in
# via kernels.ops
# =============================================================================

def merge_groups(w: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Expert leaves as (G, d, f) groups: a (b_n, E_local, d, f) leaf is
    merged to G = b_n * E_local, node-major."""
    return {k: v.reshape((-1,) + v.shape[-2:]) if v.ndim == 4 else v
            for k, v in w.items()}


def experts_ffn(w: Dict[str, jax.Array], x: jax.Array, act: str,
                use_kernel: bool = False) -> jax.Array:
    """Apply per-group expert FFN. ``x``: (G, T, d); weights (G, d, f)/(G, f, d),
    or (b_n, E_local, d, f)/(b_n, E_local, f, d) with G = b_n * E_local.

    Four-dimensional leaves stay so: the tokens are viewed as
    (b_n, E_local, T, d) and the einsums batch over both group dimensions,
    so XLA fuses the weights' bf16 cast into the matmuls and the weight
    gradient comes back in the parameter's own shape."""
    if use_kernel:
        from repro.kernels import ops as kops
        w = merge_groups(w)
        return kops.grouped_ffn(x, w["w1"], w.get("w3"), w["w2"], act=act)
    actf = jax.nn.silu if act == "silu" else jax.nn.gelu
    lead = w["w1"].shape[:-2]                     # (G,) or (b_n, E_local)
    xg = x.reshape(lead + x.shape[1:])
    b = "g" if len(lead) == 1 else "ng"           # einsum group dimensions
    h = jnp.einsum(f"{b}td,{b}df->{b}tf", xg, w["w1"].astype(x.dtype))
    h = actf(h)
    if "w3" in w and w["w3"] is not None:
        h = h * jnp.einsum(f"{b}td,{b}df->{b}tf", xg, w["w3"].astype(x.dtype))
    y = jnp.einsum(f"{b}tf,{b}fd->{b}td", h, w["w2"].astype(x.dtype))
    return y.reshape(x.shape)


def experts_ffn_ragged(w: Dict[str, jax.Array], rows: jax.Array,
                       group_starts: jax.Array, act: str, *,
                       block: int, use_kernel: bool = False) -> jax.Array:
    """Expert FFN over the dropless tile-aligned ragged layout.

    ``rows``: (R, d) flat row array from :func:`repro.core.dispatch.
    dispatch_ragged`; ``group_starts``: (G+1,) aligned segment offsets;
    ``block``: the layout's row-tile size.  The non-kernel path runs one
    batched matmul over the row tiles with per-tile weight selection —
    every tile belongs to exactly one group, so this is the jnp shadow of
    the Pallas kernel's scalar-prefetched weight indirection.
    """
    w = merge_groups(w)
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.grouped_ffn_ragged(rows, group_starts, w["w1"],
                                       w.get("w3"), w["w2"], block=block,
                                       act=act)
    R, d = rows.shape
    tile_gid = D.ragged_tile_gids(group_starts, R // block, block)
    xt = rows.reshape(R // block, block, d)
    actf = jax.nn.silu if act == "silu" else jax.nn.gelu
    h = actf(jnp.einsum("tbd,tdf->tbf", xt,
                        jnp.take(w["w1"].astype(rows.dtype), tile_gid, axis=0)))
    if "w3" in w and w["w3"] is not None:
        h = h * jnp.einsum("tbd,tdf->tbf", xt,
                           jnp.take(w["w3"].astype(rows.dtype), tile_gid,
                                    axis=0))
    y = jnp.einsum("tbf,tfd->tbd", h,
                   jnp.take(w["w2"].astype(rows.dtype), tile_gid, axis=0))
    return y.reshape(R, d)


def experts_ffn_compact_rows(w: Dict[str, jax.Array], rows: jax.Array,
                             gid: jax.Array, valid: jax.Array,
                             num_groups: int, act: str,
                             use_kernel: bool = False,
                             sort_impl: str = "argsort") -> jax.Array:
    """Dropless expert compute over *received* rows with per-row group ids.

    ``rows``: (S, d) arrived slab (any layout); ``gid``/``valid``: (S,) local
    group id and real-row flag per slab row.  Compacts the valid rows into
    the tile-aligned ragged layout, runs the FFN over exact segment lengths,
    and scatters results back to the slab layout (invalid rows stay zero) —
    the MXU never touches padding regardless of how the slab arrived.
    """
    ones = jnp.ones((rows.shape[0],), jnp.float32)
    r2, starts, st = D.dispatch_ragged(rows, gid, ones, num_groups, k=1,
                                       valid=valid, use_kernel=use_kernel,
                                       sort_impl=sort_impl)
    out = experts_ffn_ragged(w, r2, starts, act, block=st.cap,
                             use_kernel=use_kernel)
    return D.combine(out, st)


def experts_ffn_compact(w: Dict[str, jax.Array], recv: jax.Array,
                        valid: jax.Array, act: str,
                        use_kernel: bool = False,
                        sort_impl: str = "argsort") -> jax.Array:
    """Dropless expert compute over a *received* capacity buffer.

    When a fixed-shape All2All hop is kept (``ragged_a2a=False``), the
    received ``(G, S, d)`` buffer still carries ``(cf - 1)/cf`` padding rows.
    This compacts the valid rows (``valid``: (G, S) bool) into the ragged
    layout, runs the FFN over exact segment lengths, and scatters results
    back to the fixed slot layout (empty slots stay zero, matching what the
    padded FFN would have produced) — the MegaScale-MoE "no padding into the
    FFN" hot-path fix with the collective left untouched.
    """
    G, S, d = recv.shape
    rgid = jnp.repeat(jnp.arange(G, dtype=jnp.int32), S)
    out = experts_ffn_compact_rows(w, recv.reshape(G * S, d), rgid,
                                   valid.reshape(-1), G, act,
                                   use_kernel=use_kernel,
                                   sort_impl=sort_impl)
    return out.reshape(G, S, d)


# =============================================================================
# The IR
# =============================================================================

@dataclasses.dataclass
class RouteDecision:
    """One router's verdict for one hop, in the executor's vocabulary.

    Per-assignment arrays are flat ``(A = t * k,)``; assignment ``a`` belongs
    to token ``a // k``.  ``group_ids`` are *canonical* virtual-group ids in
    ``[0, spec.num_groups)`` — the executor applies ``spec.perm`` itself, so
    route callables never deal in wire layouts.  ``probs``/``logits``/
    ``top1`` are over the router's own domain (``spec.loss_groups`` wide)
    and feed the LB / z losses; ``token_valid`` masks tokens that are
    padding on arrival slabs (SMILE level 2).
    """
    gates: jax.Array          # (A,) combine weights
    group_ids: jax.Array      # (A,) canonical virtual destination groups
    valid: jax.Array          # (A,) assignment validity
    token_valid: jax.Array    # (t,) token validity (losses)
    probs: jax.Array          # (t, loss_groups)
    logits: jax.Array         # (t, loss_groups)
    top1: jax.Array           # (t,) router argmax (LB loss f-vector)
    k: int                    # assignments per token


@dataclasses.dataclass
class HopSpec:
    """Static schedule of one dispatch hop.

    ``exchange`` picks the wire format:

    * ``"local"``  — the hop's mesh is size 1 *and* it is the innermost hop
      with the dropless backend: no exchange, no slab — the expert FFN runs
      straight over the tile-aligned ragged layout.
    * ``"padded"`` — fixed-shape capacity buffer (``capacity`` rows/group)
      through a regular All2All (identity when ``n_ranks == 1``).  Used by
      the capacity backends everywhere and by dropless when
      ``ragged_a2a=False`` (re-compacted on arrival).
    * ``"ragged"`` — exact tile-aligned segments through
      :func:`repro.sharding.comm.ragged_all_to_all`; ``recv_bound_factor``
      optionally clamps the receive slab (see module docstring).

    ``perm`` (``(num_groups,)`` int32 or None) relabels canonical group ids
    rank-major so rank ``p`` owns ids ``[p*gpr, (p+1)*gpr)``; None means the
    canonical order already is rank-major (identity).

    ``wire_integrity`` arms the parity-row checksum layer on this hop's
    ragged exchanges, both directions (see the module docstring): ``"off"``
    traces the exact production wire, ``"detect"`` verifies and accounts
    but passes payloads through, ``"quarantine"`` additionally drops every
    flagged segment.  Ignored on local/padded exchanges and size-1 meshes
    (nothing crosses a wire).
    """
    name: str                         # "flat" | "inter" | "intra" (display)
    axes: Tuple[str, ...]             # mesh axes the exchange spans
    n_ranks: int                      # P = product of axis sizes
    num_groups: int                   # V: virtual groups dispatched into
    exchange: str                     # "local" | "padded" | "ragged"
    capacity: int = 0                 # rows/group (padded exchange only)
    perm: Optional[jax.Array] = None  # canonical -> rank-major relabel
    recv_bound_factor: Optional[float] = None   # ragged exchange only
    lb_coef: float = 0.0              # LB loss coefficient for this hop
    loss_groups: int = 0              # router prob domain (LB/z losses)
    wire_integrity: str = "off"       # "off" | "detect" | "quarantine"

    def __post_init__(self):
        if self.exchange not in EXCHANGES:
            raise ValueError(f"unknown exchange {self.exchange!r}; "
                             f"expected one of {EXCHANGES}")
        if self.wire_integrity not in WIRE_POLICIES:
            raise ValueError(f"unknown wire_integrity "
                             f"{self.wire_integrity!r}; expected one of "
                             f"{WIRE_POLICIES}")
        if self.num_groups % max(self.n_ranks, 1):
            raise ValueError(f"num_groups {self.num_groups} must fold onto "
                             f"{self.n_ranks} ranks")

    @property
    def groups_per_rank(self) -> int:
        return self.num_groups // max(self.n_ranks, 1)


@dataclasses.dataclass
class ExpertHop:
    """One pipeline stage: a router bound to its hop schedule.

    ``route(x, token_valid, outer_gid) -> RouteDecision`` where ``x`` is the
    (t, d) tokens this hop sees (original tokens for the outermost hop, the
    previous hop's arrival slab otherwise), ``token_valid`` masks real rows,
    and ``outer_gid`` (or None at the outermost hop) is each row's local
    group under the *previous* hop — what SMILE's level-2 router needs to
    keep tokens inside the node they arrived at.
    """
    route: Callable[[jax.Array, jax.Array, Optional[jax.Array]],
                    RouteDecision]
    spec: HopSpec


# =============================================================================
# Generic rank-major fold/unfold (padded exchange)
# =============================================================================

def _fold_a2a(buf: jax.Array, groups: int, mesh_axes, mesh_size: int
              ) -> jax.Array:
    """All2All a (groups, ...) buffer over mesh axes of total size ``s | groups``.

    Logical groups are block-assigned to mesh ranks. After the exchange the
    leading dims are (src_rank, my_local_groups, ...), flattened back to
    (mesh_size * groups//mesh_size, ...) in (src, local-group) order.
    """
    if mesh_size == 1:
        return buf
    b = groups // mesh_size
    rest = buf.shape[1:]
    buf = buf.reshape((mesh_size, b) + rest)
    buf = comm.all_to_all(buf, mesh_axes, split_axis=0, concat_axis=0)
    return buf.reshape((mesh_size * b,) + rest)


def _fold(z: jax.Array, spec: HopSpec) -> jax.Array:
    """Forward exchange of a rank-major capacity buffer.

    ``z``: (V, cap, ...) with groups rank-major -> (gpr, P*cap, ...): each of
    my ``gpr`` local groups holds the ``cap`` arrivals from every source
    rank, source-major — the layout the grouped FFN consumes directly.
    """
    P, gpr = spec.n_ranks, spec.groups_per_rank
    rest = z.shape[1:]
    z = _fold_a2a(z, spec.num_groups, spec.axes, P)         # src-major
    z = z.reshape((P, gpr) + rest)
    z = jnp.moveaxis(z, 1, 0)                               # groups first
    return z.reshape((gpr, P * rest[0]) + rest[1:])


def _unfold(y: jax.Array, spec: HopSpec, cap: int) -> jax.Array:
    """Reverse exchange: (gpr, P*cap, ...) back to the (V, cap, ...)
    rank-major buffer at the origin ranks — the exact mirror of :func:`_fold`."""
    P, gpr = spec.n_ranks, spec.groups_per_rank
    rest = y.shape[2:]
    y = y.reshape((gpr, P, cap) + rest)
    y = jnp.moveaxis(y, 1, 0)                               # dest rank first
    y = y.reshape((spec.num_groups, cap) + rest)
    return _fold_a2a(y, spec.num_groups, spec.axes, P)


# =============================================================================
# Ragged exchange (with the optional receive bound)
# =============================================================================

def recv_bound_rows(factor: float, rows: int, n_ranks: int,
                    groups_per_rank: int, block: int) -> int:
    """Static bounded receive-slab size for a clamped ragged hop.

    ``factor x`` the sender-layout row count (== expected arrivals at
    uniform routing) plus one tile of alignment slack per (source, local
    group) — so ``factor >= 1`` never clamp-drops a perfectly uniform
    routing — rounded up to the row tile and never above the worst-case
    ``P x R`` bound.
    """
    slack = n_ranks * groups_per_rank * block
    b = int(math.ceil(factor * rows)) + slack
    b = ((b + block - 1) // block) * block
    return min(b, n_ranks * rows)


def sanitize_len_grid(len_grid: jax.Array, block: int, src_rows: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Validate an exchanged ``(P, nl)`` count grid; quarantine bad sources.

    The grid arrives over the wire, so the receiver must not trust it: a
    negative entry or a source whose tile-aligned row total exceeds its
    ``src_rows`` staging bound would drive the slab layout math (and the
    fused-emulation compaction gather) out of bounds.  Entries violating
    either invariant mark their *source row* untrustworthy, and the whole
    row is zeroed — segment-granularity quarantine, because a partially
    believed row would shift the group sub-offsets of every later group
    from that source and silently hand tokens to the wrong expert.  The
    quarantined source's rows simply never materialize; the echoed reverse
    hop reports them dropped with exact accounting.

    Returns ``(grid, events, src_bad)``: the sanitized grid, the number of
    *violating* entries (a float32 scalar — the hop's ``fault_events``
    contribution; quarantine collateral, i.e. valid entries zeroed because
    a sibling violated, is intentionally not counted so injected faults
    have exact expected counts), and the (P,) bool per-source quarantine
    mask.  The mask lets the wire-integrity verifier *deduplicate*: a
    source zeroed here necessarily fails its payload checksum too (the
    receiver now believes zero-length segments the sender checksummed at
    full length), and re-flagging it would double-count the one injected
    fault in ``fault_events``/``wire_faults``.  On a healthy grid this is
    the identity with ``events == 0`` and an all-false mask — pure integer
    math, bit-identical outputs (pinned by the golden matrix).

    Known limitation, by construction: an *in-bounds inflated* count — a
    source claiming more rows than it actually staged, within its bound —
    is indistinguishable from a real count at grid level; the sanitizer
    only guarantees no OOB/crash/hang.  That gap is what the wire-integrity
    layer closes: with ``HopSpec.wire_integrity`` on, the per-segment
    parity word's length term exposes the inflation (and its fold/tag terms
    expose payload corruption and segment replay) with exact per-(hop, src
    rank) localization; with it off, the step sentinel still catches the
    downstream loss anomaly globally.
    """
    aligned = ((len_grid + block - 1) // block) * block
    neg = len_grid < 0
    over = jnp.cumsum(jnp.where(neg, 0, aligned), axis=1) > src_rows
    bad = neg | over
    events = bad.sum().astype(jnp.float32)
    src_bad = bad.any(axis=1)
    return jnp.where(src_bad[:, None], 0, len_grid), events, src_bad


@dataclasses.dataclass
class _RaggedHopState:
    """Everything the reverse of one ragged hop needs."""
    recv: jax.Array           # (B, d) received slab
    gid: jax.Array            # (B,) local group per slab row
    valid: jax.Array          # (B,) real-row flag per slab row
    recv_counts: jax.Array    # (P,) aligned rows per source (unclamped)
    send_counts: jax.Array    # (P,) aligned rows sent per destination
    kept: Optional[jax.Array]  # (P,) rows kept per source after the clamp
    rows_out: int             # R: sender layout rows (reverse recv bound)


def _wire_tags(me: jax.Array, P: int, nl: int, incoming: bool) -> jax.Array:
    """(P*nl,) int32 identity tags of a wire's segments, flat-ordered.

    ``tag = (src * P + dst) * nl + g`` — outgoing tags fix ``src = me``,
    incoming tags fix ``dst = me``; a replayed segment carries the wrong
    ``src`` and the tag term of its parity word gives it away.
    """
    other = jnp.repeat(jnp.arange(P, dtype=jnp.int32), nl)
    g = jnp.tile(jnp.arange(nl, dtype=jnp.int32), P)
    src, dst = (other, me) if incoming else (me, other)
    return (src * P + dst) * nl + g


def _ragged_forward(rows: jax.Array, group_starts: jax.Array,
                    seg_lens: jax.Array, spec: HopSpec, block: int,
                    fp: Optional[FI.FaultPlan] = None, level: int = 0
                    ) -> Tuple[_RaggedHopState, jax.Array,
                               Optional[jax.Array]]:
    """Forward ragged All2All of one dispatch hop — zero capacity padding.

    ``rows``: (R, d) *rank-major* ragged layout; ``group_starts``: its
    (V + 1,) aligned offsets; ``seg_lens``: the raw per-group valid counts.
    Exchanges exact tile-aligned segments plus the tiny count grid, and
    rebuilds the received slab's per-row structure from the counts alone —
    no intermediate capacity scatter anywhere.  Identity when the hop's
    mesh is size 1.

    Unclamped, the received slab is sized ``P * R`` — the static worst case
    (every rank routes everything here), which is what guarantees zero
    drops under ANY skew, and what makes every post-hop stage scan
    ``~P/cf x`` more rows than a capacity bound would.  With
    ``spec.recv_bound_factor`` set the slab is :func:`recv_bound_rows`
    instead: sources land at their aligned offsets and whatever falls past
    the bound is clamp-dropped (a tile-aligned *prefix* of the slab
    survives, so surviving segments keep their offsets).  The reverse hop
    (:func:`_ragged_reverse`) echoes the clamped counts back to the
    senders.

    The exchanged count grid is never trusted: :func:`sanitize_len_grid`
    quarantines sources with invalid counts before any layout math (the
    identity, and bit-identical, on healthy grids).  ``fp`` optionally
    injects faults for this ``level`` — grid corruption (``counts`` /
    ``dropseg`` / ``inflate`` / ``dupseg``) before sanitation, wire-slab
    corruption (``bitflip`` / wire-mode ``nanrows`` / ``dupseg``'s region
    replay) onto the received checksummed slab — and because a
    count-targeting plan can legitimately shrink ``rc`` below what the
    senders shipped, it also forces the clamp-style ``kept`` bookkeeping so
    the reverse hop echoes the surviving counts instead of assuming
    everything returns (``fp=None`` keeps the collective-identical
    zero-echo fast path).

    With ``spec.wire_integrity`` armed (and a real wire, ``P > 1``) the
    exchange rides :func:`repro.sharding.comm.checksummed_ragged_all_to_all`
    instead: each source's segment carries ``nl`` parity rows, the receiver
    recomputes every (src, group) integrity word from the payload and
    counts it believes, and a mismatching *source* is flagged —
    ``"quarantine"`` zero-fills its rows, drops their validity (combine
    skips them) and echoes ``kept = 0`` so the origin accounts every lost
    assignment; ``"detect"`` only flags.  Returns ``(state, sanitizer
    events, per-source wire verdicts | None)``.
    """
    P, nl = spec.n_ranks, spec.groups_per_rank
    R = rows.shape[0]
    send_counts = D.ragged_send_counts(group_starts, nl)
    # one count collective per hop: the (P, nl) length grid also determines
    # the aligned per-source segment extents, so the segment exchange skips
    # its own count round trip.  This boundary rides the generic payload
    # all_to_all (which comm cannot dtype-gate), so the count contract is
    # asserted here.
    comm.assert_count_i32(seg_lens, "_ragged_forward(seg_lens)")
    len_grid = comm.all_to_all(seg_lens.reshape(P, nl), spec.axes,
                               split_axis=0, concat_axis=0)
    inject = fp is not None and fp.targets(level)
    if inject and fp.kind == "counts":
        len_grid = FI.corrupt_len_grid(fp, level, len_grid)
    if inject and fp.kind == "dropseg":
        len_grid = FI.drop_segment(fp, level, len_grid)
    if inject and fp.kind == "inflate":
        len_grid = FI.inflate_grid(fp, level, len_grid)
    if inject and fp.kind == "dupseg":
        len_grid = FI.dup_grid(fp, level, len_grid)
    len_grid, events, san_bad = sanitize_len_grid(len_grid, block, R)
    rc = (((len_grid + block - 1) // block) * block).sum(
        axis=1).astype(jnp.int32)
    force_echo = fp is not None and fp.wants_echo
    factor = spec.recv_bound_factor
    clamped = (factor is not None and P > 1
               and recv_bound_rows(factor, R, P, nl, block) < P * R)
    B = recv_bound_rows(factor, R, P, nl, block) if clamped else P * R
    wire = spec.wire_integrity != "off" and P > 1
    if not wire:
        if not clamped:
            # no factor, single-rank hop, or a bound that doesn't reduce the
            # worst case: keep the exact zero-drop path (native-op eligible,
            # no echo exchange) so a non-reducing factor stays bit-identical
            # AND collective-identical to factor=None
            recv, _ = comm.ragged_all_to_all(rows, send_counts, spec.axes,
                                             recv_rows=B, recv_counts=rc)
            gid, valid = D.ragged_recv_layout(len_grid, block, B)
            if inject and fp.kind == "nanrows":
                recv = FI.nan_rows(fp, level, recv, valid)
            # under a count-targeting plan, rc can shrink below what peers
            # shipped: echo the surviving counts (== rc, sum(rc) <= P*R) so
            # senders learn exactly which rows died instead of reading stale
            # slab rows back — the quarantine's drop accounting
            kept = rc if force_echo else None
            return _RaggedHopState(recv, gid, valid, rc, send_counts,
                                   kept, R), events, None
        # bounded slab: segments past B rows are truncated on arrival (the
        # emulations do this natively; allow_truncate keeps the jax-native
        # op off this path, whose paired offset/size contract cannot
        # truncate)
        recv, _ = comm.ragged_all_to_all(rows, send_counts, spec.axes,
                                         recv_rows=B, recv_counts=rc,
                                         allow_truncate=True)
        gid, valid = D.ragged_recv_layout(len_grid, block, B)
        if inject and fp.kind == "nanrows":
            recv = FI.nan_rows(fp, level, recv, valid)
        kept = jnp.clip(B - comm.excl_cumsum(rc), 0, rc)
        return _RaggedHopState(recv, gid, valid, rc, send_counts,
                               kept, R), events, None

    # ---- checksummed wire: parity rows ride the slab ------------------------
    me = comm.axis_index(spec.axes)
    words = comm.segment_parity_words(
        rows, group_starts, seg_lens, _wire_tags(me, P, nl, incoming=False))
    parity = comm.words_to_rows(words, rows.dtype)
    rcw = rc + jnp.int32(nl)
    slab, _ = comm.checksummed_ragged_all_to_all(
        rows, parity, send_counts, spec.axes, recv_rows=B + P * nl,
        recv_counts=rc, nl=nl, allow_truncate=clamped)
    woff = comm.excl_cumsum(rcw)
    if inject and fp.kind == "bitflip":
        slab = FI.flip_wire(fp, level, slab, woff, rc, nl)
    if inject and fp.kind == "nanrows":
        slab = FI.nan_wire(fp, level, slab, woff, rcw)
    if inject and fp.kind == "dupseg":
        slab = FI.copy_wire_region(fp, level, slab, woff, rcw)
    recv, par = comm.split_checksummed_recv(slab, rc, nl, B)
    gid, valid = D.ragged_recv_layout(len_grid, block, B)
    doff = comm.excl_cumsum(rc)
    sseg, swithin, sval = D.ragged_row_membership(
        jnp.concatenate([doff, doff[-1:] + rc[-1:]]), rc, B)
    if clamped:
        kept_wire = jnp.clip((B + P * nl) - woff, 0, rcw)
        full = kept_wire == rcw          # region fully arrived (incl parity)
        data_kept = jnp.minimum(kept_wire, rc)
        # a truncated source's missing rows read clamped garbage off the
        # slab edge: zero them and drop their validity — the plain receive
        # gets this for free (its truncated rows simply never materialize)
        alive = sval & (swithin < jnp.take(data_kept, sseg))
        recv = jnp.where(alive[:, None], recv, 0)
        valid = valid & alive
    else:
        full = jnp.ones((P,), bool)
        data_kept = rc
    aligned = (((len_grid + block - 1) // block) * block).reshape(-1)
    rbounds = jnp.concatenate(
        [comm.excl_cumsum(aligned),
         aligned.sum().reshape(1).astype(jnp.int32)])
    expect = comm.segment_parity_words(
        recv, rbounds, len_grid.reshape(-1),
        _wire_tags(me, P, nl, incoming=True))
    bad_cell = jnp.any(
        comm.int_lane_view(par.reshape(P * nl, -1))
        != comm.stored_words(expect, recv.dtype), axis=-1).reshape(P, nl)
    # source-granular verdict: one corrupt (src, group) cell condemns the
    # whole source segment — a partially believed region would shift every
    # later group's sub-offsets exactly like a half-believed count row.
    # A sanitizer-quarantined source is excluded: its count row was zeroed
    # above, so its parity words trivially mismatch the (now zero-length)
    # segments the receiver believes — re-flagging it here would charge the
    # one injected fault twice in fault_events/wire_faults (and its rows
    # are already zeroed/dropped via the sanitized grid)
    src_bad = bad_cell.any(axis=1) & full & ~san_bad
    if spec.wire_integrity == "quarantine":
        rowbad = jnp.take(src_bad, sseg) & sval
        recv = jnp.where(rowbad[:, None], 0, recv)
        valid = valid & ~rowbad
        kept = jnp.where(src_bad, 0, data_kept)
    else:
        kept = data_kept if (clamped or force_echo) else None
    return (_RaggedHopState(recv, gid, valid, rc, send_counts, kept, R),
            events, src_bad.astype(jnp.float32))


def _ragged_reverse(y_slab: jax.Array, hs: _RaggedHopState, spec: HopSpec
                    ) -> Tuple[jax.Array, Optional[jax.Array],
                               Optional[jax.Array]]:
    """Reverse ragged All2All: route each source's slab segment back to its
    origin rank at the origin offsets.

    Returns ``(back, survived, wire_bad)``: ``back`` (R, d) aligned with
    the sender's original layout rows; ``survived`` (R,) marks the rows
    whose results actually returned — None on the unclamped path
    (everything returns, no extra collective: the mirrored counts are
    already known).  On the clamped path the reverse runs its own tiny
    count exchange, which is exactly the "clamped counts echoed on the
    reverse path": every sender learns how many of its rows each receiver
    kept, reconstructs which layout rows those were (each receiver keeps a
    contiguous *prefix* of each sender's segment), and zero-fills the
    clamp-dropped rows.

    With ``spec.wire_integrity`` armed the returning slab is checksummed
    too (``nl = 1``: one parity row per peer — the reverse wire's segments
    are per-source, not per-group): the origin verifies each returning
    segment's word and, under ``"quarantine"``, zero-fills and un-survives
    rows from flagged peers.  ``wire_bad`` is the (P,) per-peer verdict
    (None with the layer off).  Because quarantine can zero ``kept``
    *mid-slab*, the wire path first compacts the surviving segments to the
    echoed offsets — the off-path's prefix-survival shortcut (send from
    unclamped offsets) no longer holds.
    """
    R = hs.rows_out
    P = spec.n_ranks
    wire = spec.wire_integrity != "off" and P > 1
    if not wire:
        if hs.kept is None:
            back, _ = comm.ragged_all_to_all(y_slab, hs.recv_counts,
                                             spec.axes, recv_rows=R,
                                             seg_rows=R,
                                             recv_counts=hs.send_counts)
            return back, None, None
        # clamped: each surviving forward segment is a prefix of the slab,
        # so sending `kept` rows from the unclamped offsets is
        # self-consistent.  The reverse can never truncate (sum(rb) <=
        # sum(send_counts) <= R), so it stays native-op eligible — only the
        # forward needs allow_truncate
        back_c, rb = comm.ragged_all_to_all(y_slab, hs.kept, spec.axes,
                                            recv_rows=R, seg_rows=R)
        # rb[p] = rows peer p kept of MY segment (the echo). Returning
        # segments arrive compacted at cumsum(rb); remap each to its
        # original offset.
        send_starts = jnp.concatenate(
            [comm.excl_cumsum(hs.send_counts),
             hs.send_counts.sum().reshape(1).astype(jnp.int32)])
        seg, within, ok = D.ragged_row_membership(send_starts, rb, R)
        rboff = comm.excl_cumsum(rb)
        src = jnp.where(ok, jnp.take(rboff, seg) + within, 0)
        back = jnp.where(ok[:, None], jnp.take(back_c, src, axis=0), 0)
        return back, ok, None

    # ---- checksummed reverse wire -------------------------------------------
    me = comm.axis_index(spec.axes)
    if hs.kept is None:
        # mirror-counts path: segments already sit at the believed offsets
        sc, y_send, rb = hs.recv_counts, y_slab, hs.send_counts
    else:
        # compact surviving segments to the echoed cumsum offsets (a
        # quarantined source leaves a hole mid-slab, so the data no longer
        # sits where excl_cumsum(kept) says)
        sc = hs.kept
        doff = comm.excl_cumsum(hs.recv_counts)
        koff = comm.excl_cumsum(sc)
        kb = jnp.concatenate([koff, koff[-1:] + sc[-1:]])
        seg, within, ok = D.ragged_row_membership(kb, sc, y_slab.shape[0])
        idx = jnp.where(ok, jnp.take(doff, seg) + within, 0)
        y_send = jnp.where(ok[:, None], jnp.take(y_slab, idx, axis=0), 0)
        rb = comm.exchange_counts(sc, spec.axes)
    soff = comm.excl_cumsum(sc)
    words = comm.segment_parity_words(
        y_send, jnp.concatenate([soff, soff[-1:] + sc[-1:]]), sc,
        _wire_tags(me, P, 1, incoming=False))
    wire_back, _ = comm.checksummed_ragged_all_to_all(
        y_send, comm.words_to_rows(words, y_send.dtype), sc, spec.axes,
        recv_rows=R + P, recv_counts=rb, nl=1)
    back_c, par = comm.split_checksummed_recv(wire_back, rb, 1, R)
    rboff = comm.excl_cumsum(rb)
    expect = comm.segment_parity_words(
        back_c, jnp.concatenate([rboff, rboff[-1:] + rb[-1:]]), rb,
        _wire_tags(me, P, 1, incoming=True))
    bad = jnp.any(comm.int_lane_view(par.reshape(P, -1))
                  != comm.stored_words(expect, back_c.dtype), axis=-1)
    if hs.kept is None:
        # mirror path: rb == send_counts, arrivals already at origin offsets
        send_starts = jnp.concatenate(
            [comm.excl_cumsum(hs.send_counts),
             hs.send_counts.sum().reshape(1).astype(jnp.int32)])
        seg, _, ok = D.ragged_row_membership(send_starts, rb, R)
        back = back_c
    else:
        send_starts = jnp.concatenate(
            [comm.excl_cumsum(hs.send_counts),
             hs.send_counts.sum().reshape(1).astype(jnp.int32)])
        seg, within, ok = D.ragged_row_membership(send_starts, rb, R)
        src = jnp.where(ok, jnp.take(rboff, seg) + within, 0)
        back = jnp.where(ok[:, None], jnp.take(back_c, src, axis=0), 0)
    if spec.wire_integrity == "quarantine":
        rowbad = jnp.take(bad, seg) & ok
        back = jnp.where(rowbad[:, None], 0, back)
        ok = ok & ~rowbad
        survived = ok
    else:
        survived = None if hs.kept is None else ok
    return back, survived, bad.astype(jnp.float32)


# =============================================================================
# The executor
# =============================================================================

def _occupancy(st: D.CombineState, A: int) -> jax.Array:
    """Per-slot occupancy flags mirroring the token dispatch."""
    return D.dispatch_flags(jnp.ones((A,), jnp.float32), st)


def execute_pipeline(x: jax.Array, hops: Sequence[ExpertHop],
                     wsel: Dict[str, jax.Array], cfg, *, act: str,
                     use_kernel: bool, sync,
                     token_valid: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, MoEStats]:
    """Run a routing schedule expressed as a hop pipeline.

    ``x``: (t, d) local tokens; ``hops``: outermost-first; ``wsel``: this
    device's expert weights, (gpr_innermost, d, f) groups in local order,
    or (b_n, E_local, d, f) with gpr_innermost = b_n * E_local (see
    :func:`repro.core.moe._my_expert_weights`);
    ``cfg``: :class:`repro.common.config.MoEConfig` (dispatch backend, sort
    impl, z coefficient); ``sync``: mesh axes for globally-averaged stats.

    ``token_valid`` (t,) bool masks the *top-level* tokens: invalid rows are
    excluded from every hop's LB/z losses, contribute zero dispatch
    assignments (so ragged hops put zero segments for them on the wire and
    the ``recv_bound_factor`` receive bound sizes itself over live tokens
    only), and combine to exactly zero output.  ``None`` (the default) is
    the all-valid training/prefill path, bit-identical to the pre-serving
    pipeline.  This is the decode-tick contract: a continuous-batching
    engine passes its live-slot mask here so dead slots cost nothing on
    the expert wire.

    Returns ``(y, stats)`` with ``y`` (t, d) gate-weighted combined outputs
    and one :class:`MoEStats` accumulated across all hops (lb and z losses
    summed, ``drop_frac`` summed with the per-hop breakdown preserved).

    **Fault containment.**  ``cfg.fault_plan`` (parsed once here) injects
    deterministic faults at the hop boundaries — count-grid corruption and
    segment suppression inside :func:`_ragged_forward`, NaN rows into every
    exchange flavor's post-dispatch buffer, routing-skew storms onto the
    route decision — while the *always-on* containment machinery
    (:func:`sanitize_len_grid`, the echoed reverse hop, the occupancy-masked
    compact FFNs) keeps every faulted step inside a defined state.  The
    per-hop sanitizer event counts are psum'd into ``stats.fault_events``,
    and the psum'd LB ``f``-vector feeds the router-collapse watchdog
    fields ``hop_max_load`` / ``hop_load_entropy`` at zero extra collective
    cost.  ``fault_plan=None`` is the production path: no injection code
    traces at all, bit-identical to the golden matrix.

    **Wire integrity.**  ``cfg.wire_integrity`` (threaded onto every
    :class:`HopSpec` by the schedule builders) arms per-segment payload
    checksums on both directions of every ragged exchange
    (:func:`_ragged_forward` / :func:`_ragged_reverse`): each flagged
    source adds one event to that hop's ``fault_events`` and one count to
    ``wire_faults[hop, src]`` — exact (hop, source rank) localization —
    and under ``"quarantine"`` the corrupt segment is zero-filled and
    dropped with the same exact accounting the count sanitizer uses, so a
    value-corrupting peer costs its own tokens instead of the whole step.
    """
    if len(hops) > MAX_HOPS:
        raise ValueError(f"pipeline has {len(hops)} hops; MAX_HOPS is "
                         f"{MAX_HOPS} (bump it alongside MoEStats)")
    dropless = cfg.dispatch_backend == "dropless"
    simpl = cfg.sort_impl
    fp = FI.parse_fault_plan(getattr(cfg, "fault_plan", None))
    zero = jnp.float32(0.0)
    lb_terms, z_terms = [], []
    hop_drops = [zero] * MAX_HOPS
    hop_faults = [zero] * MAX_HOPS
    hop_maxload = [zero] * MAX_HOPS
    hop_entropy = [jnp.float32(1.0)] * MAX_HOPS
    hop_wire = [jnp.zeros((WIRE_SRC_BINS,), jnp.float32)] * MAX_HOPS
    wire_used = False

    def run_hop(level: int, x: jax.Array, token_valid: jax.Array,
                outer_gid: Optional[jax.Array]) -> jax.Array:
        # each hop's device ops are named ``hop<level>/<phase>``; hop 1 runs
        # inside hop 0, between its two exchanges
        with jax.named_scope(f"hop{level}"):
            return hop_body(level, x, token_valid, outer_gid)

    def hop_body(level, x, token_valid, outer_gid):
        hop = hops[level]
        spec = hop.spec
        innermost = level == len(hops) - 1
        nanrows_here = (fp is not None and fp.kind == "nanrows"
                        and fp.targets(level))
        with jax.named_scope("route"):
            dec = hop.route(x, token_valid, outer_gid)
            if fp is not None and fp.kind == "skew" and fp.targets(level):
                dec = FI.apply_skew(fp, level, dec, spec.num_groups,
                                    spec.loss_groups)
            A, k = dec.group_ids.shape[0], dec.k
            gid = (dec.group_ids if spec.perm is None
                   else jnp.take(spec.perm, dec.group_ids))

            # ---- losses (one path per hop) ----------------------------------
            f, p = lb_loss_terms(dec.probs, dec.top1, dec.token_valid,
                                 spec.loss_groups, sync)
            lb_terms.append(scaled_lb_loss(f, p, spec.lb_coef))
            z_terms.append(z_loss(dec.logits, dec.token_valid,
                                  cfg.router_z_coef, sync))
            # router-collapse watchdog inputs, from the already-global
            # f-vector: max-load fraction and normalized load entropy
            # (1 = uniform)
            hop_maxload[level] = jnp.max(f)
            if spec.loss_groups > 1:
                fr = f / jnp.maximum(f.sum(), 1e-9)
                ent = -jnp.sum(fr * jnp.log(jnp.maximum(fr, 1e-20)))
                hop_entropy[level] = ent / math.log(spec.loss_groups)

        # ---- dispatch + exchange + inner compute + reverse + combine --------
        if spec.exchange == "local":
            # capacity-free and exchange-free: the expert grid backing this
            # hop is local — FFN straight over exact ragged segment lengths
            with jax.named_scope("dispatch"):
                rows, starts, st = D.dispatch_ragged(
                    x, gid, dec.gates, spec.num_groups, k=k, valid=dec.valid,
                    use_kernel=use_kernel, sort_impl=simpl)
            if nanrows_here:
                rows = FI.nan_rows(fp, level, rows, _occupancy(st, A) > 0)
            with jax.named_scope("expert_ffn"):
                out = experts_ffn_ragged(wsel, rows, starts, act,
                                         block=st.cap, use_kernel=use_kernel)
            with jax.named_scope("combine"):
                return D.combine(out, st)           # nothing CAN drop: 0.0

        if spec.exchange == "ragged":
            with jax.named_scope("dispatch"):
                rows, starts, st = D.dispatch_ragged(
                    x, gid, dec.gates, spec.num_groups, k=k, valid=dec.valid,
                    use_kernel=use_kernel, sort_impl=simpl)
                seg_lens = D.ragged_seg_lens(gid, st.keep, spec.num_groups)
            with jax.named_scope("exchange"):
                hs, ev, wbad = _ragged_forward(rows, starts, seg_lens, spec,
                                               st.cap, fp=fp, level=level)
            if innermost:
                with jax.named_scope("expert_ffn"):
                    y_slab = experts_ffn_compact_rows(
                        wsel, hs.recv, hs.gid, hs.valid,
                        spec.groups_per_rank, act, use_kernel,
                        sort_impl=simpl)
            else:
                y_slab = run_hop(level + 1, hs.recv, hs.valid, hs.gid)
            with jax.named_scope("exchange"):
                back, survived, rbad = _ragged_reverse(y_slab, hs, spec)
            # wire verdicts: every flagged source is one fault event and one
            # per-src-rank localization count (forward + reverse directions)
            for verdict in (wbad, rbad):
                if verdict is not None:
                    nonlocal wire_used
                    wire_used = True
                    ev = ev + verdict.sum()
                    hop_wire[level] = hop_wire[level].at[
                        jnp.arange(spec.n_ranks, dtype=jnp.int32)
                        % WIRE_SRC_BINS].add(verdict)
            hop_faults[level] = ev
            if survived is None:
                # capacity-free end-to-end: exact-constant 0.0, no psum
                with jax.named_scope("combine"):
                    return D.combine(back, st)
            keep = st.keep & jnp.take(survived, jnp.maximum(st.pos, 0))
            dropped = comm.psum((st.keep & ~keep).sum().astype(jnp.float32),
                                sync)
            total = comm.psum(st.keep.sum().astype(jnp.float32), sync)
            hop_drops[level] = dropped / jnp.maximum(total, 1)
            with jax.named_scope("combine"):
                return D.combine(back, dataclasses.replace(st, keep=keep))

        # ---- padded: fixed-shape capacity buffer on the wire ----------------
        hop_backend = "sort" if dropless else cfg.dispatch_backend
        with jax.named_scope("dispatch"):
            buf, st = D.dispatch(x, gid, dec.gates, spec.num_groups,
                                 spec.capacity, k=k, valid=dec.valid,
                                 backend=hop_backend, use_kernel=use_kernel,
                                 sort_impl=simpl)
        with jax.named_scope("exchange"):
            recv = _fold(buf, spec)                 # (gpr, P*cap, d)
        if nanrows_here:
            with jax.named_scope("exchange"):
                occ = _fold(_occupancy(st, A), spec) > 0
            recv = FI.nan_rows(fp, level, recv.reshape(-1, recv.shape[-1]),
                               occ.reshape(-1)).reshape(recv.shape)
        if innermost:
            if dropless:
                # fixed-shape A2A retained; FFN only sees valid rows
                with jax.named_scope("exchange"):
                    rvalid = _fold(_occupancy(st, A), spec) > 0
                with jax.named_scope("expert_ffn"):
                    out = experts_ffn_compact(wsel, recv, rvalid, act,
                                              use_kernel, sort_impl=simpl)
            else:
                with jax.named_scope("expert_ffn"):
                    out = experts_ffn(wsel, recv, act, use_kernel)
        else:
            gpr, S, d = recv.shape
            x1 = recv.reshape(gpr * S, d)
            with jax.named_scope("exchange"):
                valid1 = _fold(_occupancy(st, A), spec).reshape(gpr * S) > 0
            gid1 = jnp.repeat(jnp.arange(gpr, dtype=jnp.int32), S)
            out = run_hop(level + 1, x1, valid1, gid1).reshape(gpr, S, d)
        with jax.named_scope("exchange"):
            back = _unfold(out, spec, spec.capacity)
        dropped = comm.psum((dec.valid & ~st.keep).sum().astype(jnp.float32),
                            sync)
        total = comm.psum(dec.valid.sum().astype(jnp.float32), sync)
        hop_drops[level] = dropped / jnp.maximum(total, 1)
        with jax.named_scope("combine"):
            return D.combine(back, st)

    t = x.shape[0]
    if token_valid is None:
        token_valid = jnp.ones((t,), bool)
    y = run_hop(0, x, token_valid, None)
    hop_vec = jnp.stack(hop_drops)
    # sanitizer events are per-device local counts -> one stacked psum per
    # layer makes them global (f-vector stats are already psum'd upstream)
    fault_vec = comm.psum(jnp.stack(hop_faults), sync)
    # only a wire-armed trace pays the localization psum; the off policy
    # keeps the production collective profile exactly
    wire_vec = (comm.psum(jnp.stack(hop_wire), sync) if wire_used
                else jnp.stack(hop_wire))
    stats = MoEStats(sum(lb_terms[1:], lb_terms[0]),
                     sum(z_terms[1:], z_terms[0]),
                     hop_vec.sum(), hop_vec, fault_vec,
                     jnp.stack(hop_maxload), jnp.stack(hop_entropy),
                     wire_vec)
    return y, stats
