"""Collective-communication abstraction.

Every collective the framework issues goes through these helpers. Passing a
plan whose axes are empty (``single_device_plan()``) turns each helper into the
identity, so the exact same model code doubles as the pure-jnp single-device
oracle used by unit tests and kernel references.

This mirrors the paper's process-group design (Fig. 5): instead of
``inter_node_process_group`` / ``intra_node_process_group`` objects, a named
mesh axis *is* the process group, and ``jax.lax`` collectives over an axis
tuple are the group collectives.

**Wire-integrity format (parity rows).**  The checksummed ragged exchange
(:func:`checksummed_ragged_all_to_all`) makes every ragged wire segment
individually accountable without a second collective: each sender appends,
after the data rows of each destination's segment, ``nl`` *parity rows* —
one per (destination, local-group) sub-segment — so the wire segment for
peer ``p`` is ``send_counts[p]`` data rows followed by ``nl`` parity rows
and the wire counts are simply ``send_counts + nl``.  A parity row is the
segment's int32 integrity word per model lane, stored bitcast into the
payload dtype: ``word[lane] = fold[lane] + len * WIRE_LEN_MULT + tag *
WIRE_TAG_MULT`` (wrapping int32), where ``fold`` is the sum over the
segment's occupied rows of the lanes' bitcast integer views, ``len`` is
the segment's occupied-row count and ``tag`` encodes (src rank, dst rank,
group).  The receiver recomputes the word from the believed counts and
payload (:func:`segment_parity_words`) and compares in the stored domain
(:func:`stored_words` — the low 16 bits for 16-bit payload dtypes): the
fold term catches value corruption, the length term catches in-bounds
count inflation the grid sanitizer provably cannot see, and the tag term
catches replayed/duplicated segments.  Verification, quarantine and event
accounting live in ``core/pipeline``; this module only defines the wire
format and moves the bytes.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

Axes = Union[None, str, Tuple[str, ...]]


def _norm(axes: Axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def psum(x, axes: Axes, axis_index_groups=None):
    axes = _norm(axes)
    if not axes:
        return x
    return lax.psum(x, axes, axis_index_groups=axis_index_groups)


def pmean(x, axes: Axes):
    axes = _norm(axes)
    if not axes:
        return x
    return lax.pmean(x, axes)


def pmax(x, axes: Axes):
    axes = _norm(axes)
    if not axes:
        return x
    return lax.pmax(x, axes)


def all_gather(x, axes: Axes, *, axis: int = 0, tiled: bool = True):
    axes = _norm(axes)
    if not axes:
        return x
    return lax.all_gather(x, axes, axis=axis, tiled=tiled)


def psum_scatter(x, axes: Axes, *, scatter_dimension: int = 0, tiled: bool = True):
    axes = _norm(axes)
    if not axes:
        return x
    return lax.psum_scatter(x, axes, scatter_dimension=scatter_dimension,
                            tiled=tiled)


def all_to_all(x, axes: Axes, *, split_axis: int, concat_axis: int,
               tiled: bool = False):
    """All2All over ``axes``. Identity when the group size is 1.

    With ``tiled=False`` the ``split_axis`` dim must equal the group size and
    is consumed/produced whole: local ``(G, ...)`` -> received ``(G, ...)``
    where the leading index becomes the *source* group rank.
    """
    axes = _norm(axes)
    if not axes:
        return x
    return lax.all_to_all(x, axes, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def axis_index(axes: Axes):
    axes = _norm(axes)
    if not axes:
        return jnp.int32(0)
    return lax.axis_index(axes)


SAVED_COLLECTIVE = "tp_collective"


def name_saved(x):
    """Tag a collective output for the ``remat_save_collectives`` policy:
    under remat, the backward pass replays the forward — including its
    psums/all_gathers, doubling wire traffic. Saving exactly these outputs
    keeps remat's memory win while removing the re-communication."""
    return checkpoint_name(x, SAVED_COLLECTIVE)


def save_collectives_policy():
    return jax.checkpoint_policies.save_only_these_names(SAVED_COLLECTIVE)


def ppermute(x, axes: Axes, perm):
    axes = _norm(axes)
    if not axes:
        return x
    return lax.ppermute(x, axes, perm=perm)


def uniform_cond(pred, true_fn, false_fn, *operands):
    """``lax.cond`` whose predicate the caller guarantees is mesh-uniform.

    A cond whose branches run *different* collective sequences deadlocks
    (or silently mismatches) the moment devices disagree on the predicate:
    some ranks enter the branch's psum, the rest never arrive.  The static
    analyzer (:mod:`repro.analysis.jaxpr_lint`) therefore flags every cond
    with asymmetric branch collectives — EXCEPT conds lowered through this
    wrapper, the one blessed site asserting the uniformity contract: the
    predicate must be computed from collectively reduced values (e.g. a
    psum'd verdict) so every rank takes the same branch and the asymmetry
    is unobservable.  The sentinel's gated optimizer apply
    (``train/sentinel.py``) is the canonical user: its predicate is the
    step verdict, psum'd over every sync axis before the branch.
    """
    return lax.cond(pred, true_fn, false_fn, *operands)


# ------------------------------------------------------------- ragged All2All
def excl_cumsum(c: jax.Array) -> jax.Array:
    """Exclusive int32 cumsum — the segment-offset idiom every ragged
    layout shares (comm, pipeline)."""
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(c).astype(jnp.int32)])[:-1]


def clamped_segment_counts(m: jax.Array, recv_rows: int) -> jax.Array:
    """Paired clamped sizes of a truncating ragged exchange.

    ``m``: the full (P, P) count matrix (``m[s, d]`` = rows source ``s``
    ships to destination ``d`` — every rank holds it after the native
    path's ``all_gather``); ``recv_rows``: the static receive bound every
    destination applies.  Segments land at their *unclamped* source-major
    offsets (the exclusive cumsum down each column) and whatever falls past
    the bound is prefix-truncated, so ``kept[s, d] = clip(recv_rows -
    off[s, d], 0, m[s, d])``.

    Row ``me`` of the result is a rank's clamped SEND sizes, column ``me``
    its clamped RECV sizes: because every rank computes the same matrix,
    sender and receiver agree on every pair — the paired offset/size
    contract ``lax.ragged_all_to_all`` requires, with exactly the
    emulations' truncation semantics.  Pure integer math (unit-tested
    against the emulation oracles in ``tests/distributed/_ragged_a2a.py``).
    """
    off = jnp.cumsum(m, axis=0) - m           # per-column exclusive cumsum
    return jnp.clip(recv_rows - off, 0, m)


def native_truncation_plan(m, me, recv_rows: int):
    """Per-rank arguments of the native truncating ragged exchange.

    From the replicated (P, P) count matrix ``m`` and a rank index ``me``,
    derive the ``(send_sizes, out_off, recv_sizes)`` triple rank ``me``
    hands to ``lax.ragged_all_to_all`` under ``allow_truncate=True``.  All
    three come from the one :func:`clamped_segment_counts` matrix every
    rank computes identically, which is what makes the op's paired
    contract hold across ranks:

    * ``send_sizes`` — row ``me``: my clamped outgoing segment sizes,
      indexed by DESTINATION rank;
    * ``recv_sizes`` — column ``me``: my clamped incoming segment sizes,
      indexed by SOURCE rank, equal pair-for-pair to each sender's
      ``send_sizes[me]`` because both read the same matrix cell;
    * ``out_off`` — where my outgoing segments land in each destination's
      buffer: the *unclamped* source-major offsets (prefix truncation
      keeps them valid — each kept part is a segment prefix), indexed by
      destination like ``send_sizes``.  A fully truncated segment has
      size 0 but an offset past the bound; pin it with its PAIRED send
      size (same destination index space) so ``out_off + send_sizes <=
      recv_rows`` always holds.

    Pure integer math, so the cross-rank pairing is asserted numerically
    in ``tests/distributed/_ragged_a2a.py`` even where the installed jax
    predates the native op.
    """
    kept = clamped_segment_counts(m, recv_rows)
    send_sizes = jnp.take(kept, me, axis=0)
    recv_sizes = jnp.take(kept, me, axis=1)
    out_off = jnp.take(jnp.cumsum(m, axis=0) - m, me, axis=0)
    out_off = jnp.minimum(out_off, recv_rows - send_sizes)
    return send_sizes, out_off, recv_sizes


def _fit_counts(counts: jax.Array, seg_cap: int) -> jax.Array:
    """Clamp per-peer segment counts into the statically valid range.

    Counts arrive over the wire, so the layout math below must not trust
    them: the fused emulation's compaction gather reads ``seg * S +
    within`` — a count beyond the per-segment staging bound ``S`` would
    silently hand back a *different peer's* rows (no crash, ``jnp.take``
    clamps, just wrong-expert data), and a negative count corrupts every
    later peer's cumsum offset.  Semantic validation (and event
    accounting) lives in ``pipeline.sanitize_len_grid``; this is comm's
    own belt-and-braces guarantee that NO count value can make the wire
    primitive read rows it wasn't sent.  Pure integer clip — identity,
    and bit-identical, on healthy counts.
    """
    return jnp.clip(counts, 0, seg_cap)


def assert_count_i32(counts: jax.Array, what: str) -> None:
    """Trace-time dtype gate for count grids at the collective boundary.

    The wire contract is int32 everywhere: silent promotion (x64 mode, a
    stray python-int arithmetic) doubles count-exchange bytes and breaks
    the native ragged-A2A paired offset/size contract.  The static
    analyzer enforces the same rule on traced jaxprs
    (``collective-int-dtype``); this is its dynamic twin for call paths
    the entrypoint grid doesn't reach.
    """
    if counts.dtype != jnp.int32:
        raise TypeError(
            f"{what} must be int32 at the collective boundary, got "
            f"{counts.dtype} (silent x64/promotion?)")


def exchange_counts(send_counts: jax.Array, axes: Axes) -> jax.Array:
    """Tiny int32 All2All: tell every peer how many rows it will receive.

    ``send_counts``: (P,) — entry ``p`` is how many rows this device sends to
    joint rank ``p`` of ``axes``.  Returns (P,) where entry ``p`` is how many
    rows rank ``p`` sends to *this* device.  Identity when the group is 1.
    """
    assert_count_i32(send_counts, "exchange_counts(send_counts)")
    naxes = _norm(axes)
    P = send_counts.shape[0]
    if not naxes or P == 1:
        return send_counts
    return lax.all_to_all(send_counts.reshape(P, 1), naxes, split_axis=0,
                          concat_axis=0).reshape(P)


def _native_ragged_a2a() -> bool:
    """Whether the enclosing ``shard_map``'s mesh is made of accelerator
    devices, where ``lax.ragged_all_to_all`` lowers; XLA:CPU has no
    lowering for it, so CPU meshes (the tests' fake host devices) take the
    fused-slab emulation.  Read from the mesh being traced, so an AOT
    compile against a described TPU topology takes the native op too."""
    dev = jax.sharding.get_abstract_mesh().abstract_device
    return dev is not None and dev.device_kind != "cpu"


def ragged_all_to_all(rows: jax.Array, send_counts: jax.Array, axes: Axes,
                      *, recv_rows: int, seg_rows: Optional[int] = None,
                      recv_counts: Optional[jax.Array] = None,
                      emulation: str = "auto", allow_truncate: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """All2All of *exact* per-peer row segments — no capacity padding on the
    wire (the SMILE bottleneck fix MegaScale-MoE ships in production).

    ``rows``: (R, ...) staging buffer holding, contiguously and in rank order,
    the segment destined for each of the P joint ranks of ``axes``: peer ``p``'s
    segment occupies rows ``[off[p], off[p] + send_counts[p])`` where ``off``
    is the exclusive cumsum of ``send_counts`` (P,).  ``recv_rows`` is the
    static bound of the received layout (callers pass ``P * R``: every source
    can send at most its whole staging buffer).  ``seg_rows`` optionally
    tightens the static bound on any SINGLE per-peer segment (default: all
    of ``rows``) — the reverse of a hop passes the forward layout's row
    count, since no returning segment can exceed what was originally sent;
    without it the emulations would stage ``P x recv_rows`` slabs.
    ``recv_counts`` skips the count exchange when the caller already knows
    the per-source segment lengths (e.g. derived from a counts grid it
    exchanged anyway, or the mirrored counts of a forward hop).

    Returns ``(recv, recv_counts)``: ``recv`` (recv_rows, ...) holds source
    ``p``'s segment at the exclusive cumsum of ``recv_counts`` (source-major),
    zero elsewhere; ``recv_counts`` (P,) is the per-source segment length.
    Calling again with ``send_counts=recv_counts`` and ``recv_rows=R`` routes
    each segment back to its origin at the original offsets — the reverse hop.

    Three wire strategies behind the same contract, picked by ``emulation``:

    * ``"auto"`` on a mesh of accelerator devices — the native
      ``lax.ragged_all_to_all``; exact segment bytes move.
    * ``"auto"`` on a CPU mesh (XLA:CPU cannot run the native op), or
      ``"a2a"`` — the P rotation rounds fused into ONE
      ``lax.all_to_all`` of the ``(P, R)`` staging slab (entry ``p`` is the
      buffer rolled so peer ``p``'s segment starts at row 0), followed by a
      single count-driven compaction gather.  Ships ``P * R`` rows but as
      one fused collective — the fast emulation.
    * ``"ppermute"`` — P-1 explicit rotation rounds: round ``s`` sends each
      rank's segment for peer ``rank+s``, validity carried by the exchanged
      counts.  Same bytes as ``"a2a"`` spread over P-1 neighbor rounds — the
      schedule a ring fabric (or a future Pallas remote-DMA kernel) wants,
      kept selectable and tested; slower under CPU emulation.

    Identity when the group size is 1 (``recv = rows`` zero-padded to
    ``recv_rows``).

    ``allow_truncate=True`` permits a ``recv_rows`` bound SMALLER than the
    worst case: arriving segments whose offsets fall past the bound are
    truncated (rows simply never materialize) — the mechanism behind the
    receive-bound factor of :mod:`repro.core.pipeline`.  Both emulations
    truncate naturally (their compaction indexes past the buffer are
    dropped); the native op's paired offset/size contract cannot express an
    out-of-bounds write, so the native path instead *pre-clamps* both sides
    from one replicated computation: every rank derives the full (P, P)
    count matrix it already all_gathers, applies
    :func:`clamped_segment_counts`, and uses row ``me`` as its send sizes
    and column ``me`` as its receive sizes — sender and receiver agree on
    every pair by construction, and exactly the emulations'
    prefix-truncation semantics move on the wire (asserted against both
    emulation oracles in ``tests/distributed/_ragged_a2a.py``).  Callers
    are responsible for knowing which rows survived — the cumsum of
    ``recv_counts`` clipped to ``recv_rows``.

    The ``REPRO_RAGGED_A2A_EMULATION`` environment variable overrides an
    ``"auto"`` selection (values: ``auto``/``a2a``/``ppermute``) — the
    recoverable escape hatch if the native op misbehaves on a chip:
    forcing an oracle-verified emulation keeps the wire semantics instead
    of falling all the way back to padded capacity hops.
    """
    import os
    if emulation == "auto":
        emulation = os.environ.get("REPRO_RAGGED_A2A_EMULATION", "auto")
    assert_count_i32(send_counts, "ragged_all_to_all(send_counts)")
    if recv_counts is not None:
        assert_count_i32(recv_counts, "ragged_all_to_all(recv_counts)")
    naxes = _norm(axes)
    P = send_counts.shape[0]
    rest = rows.shape[1:]
    if not naxes or P == 1:
        out = jnp.zeros((recv_rows,) + rest, rows.dtype)
        n = min(recv_rows, rows.shape[0])
        out = out.at[:n].set(rows[:n])
        return out, send_counts
    send_off = excl_cumsum(send_counts)
    if emulation == "auto" and _native_ragged_a2a():
        # native path: my segment for peer p lands on p at the offset where
        # p expects MY slice — sum over sources before me of what they send
        # to p, i.e. row ``me`` of the source-exclusive cumsum of the full
        # (src, dst) count matrix (which also supplies recv_counts as
        # column ``me`` — no separate count exchange)
        me = lax.axis_index(naxes)
        m = lax.all_gather(send_counts, naxes, axis=0, tiled=False)  # (P, P)
        if recv_counts is None:
            recv_counts = jnp.take(m, me, axis=1)
        recv_counts = _fit_counts(recv_counts, recv_rows)
        if allow_truncate:
            send_sizes, out_off, recv_sizes = native_truncation_plan(
                m, me, recv_rows)
        else:
            out_off = jnp.take(jnp.cumsum(m, axis=0) - m, me, axis=0)
            send_sizes = send_counts
            recv_sizes = recv_counts
        out = jnp.zeros((recv_rows,) + rest, rows.dtype)
        return lax.ragged_all_to_all(
            rows, out, send_off.astype(jnp.int32),
            send_sizes.astype(jnp.int32), out_off.astype(jnp.int32),
            recv_sizes.astype(jnp.int32),
            axis_name=naxes if len(naxes) > 1 else naxes[0]), recv_counts
    if recv_counts is None:
        recv_counts = exchange_counts(send_counts, naxes)
    R = rows.shape[0]
    S = R if seg_rows is None else min(seg_rows, R)
    recv_counts = _fit_counts(recv_counts, S)
    recv_off = excl_cumsum(recv_counts)
    ar = jnp.arange(S, dtype=jnp.int32)
    bshape = (-1,) + (1,) * len(rest)
    if emulation in ("auto", "a2a"):
        # fused emulation: staging slab (P, S) with peer p's segment rolled
        # to row 0 of entry p; one all_to_all; then a single gather compacts
        # the (src, S)-strided arrivals to the cumsum layout, validity from
        # the exchanged counts (lazy import: layout math lives with the
        # dispatch helpers, and comm must stay importable standalone)
        from repro.core.dispatch import ragged_row_membership
        idx = (ar[None, :] + send_off[:, None]) % R              # (P, S)
        staging = jnp.take(rows, idx.reshape(-1), axis=0
                           ).reshape((P, S) + rest)
        got = lax.all_to_all(staging, naxes, split_axis=0, concat_axis=0)
        coff = jnp.concatenate([recv_off,
                                recv_off[-1:] + recv_counts[-1:]])  # (P+1,)
        seg, within, valid = ragged_row_membership(coff, recv_counts,
                                                   recv_rows)
        src_row = jnp.where(valid, seg * S + within, 0)
        out = jnp.take(got.reshape((P * S,) + rest), src_row, axis=0)
        return jnp.where(valid.reshape(bshape), out, 0), recv_counts
    if emulation != "ppermute":
        raise ValueError(f"unknown emulation {emulation!r}")
    # ppermute rounds: rotation round s pairs every rank i with dst i+s and
    # src i-s (mod P); the slab is the staging buffer rolled so the outgoing
    # segment starts at row 0, and the receiver keeps the first
    # recv_counts[src] rows
    me = lax.axis_index(naxes)
    out = jnp.zeros((recv_rows,) + rest, rows.dtype)
    for s in range(P):
        dst = (me + s) % P
        src = (me - s) % P
        slab = jnp.take(rows, (ar + send_off[dst]) % R, axis=0)  # (S, ...)
        if s:
            slab = lax.ppermute(slab, naxes,
                                perm=[(j, (j + s) % P) for j in range(P)])
        cnt = recv_counts[src]
        idx = jnp.where(ar < cnt, recv_off[src] + ar, recv_rows)  # OOB = drop
        out = out.at[idx].add(
            jnp.where((ar < cnt).reshape(bshape), slab, 0), mode="drop")
    return out, recv_counts


# --------------------------------------------------- wire-integrity (parity)
# Fold multipliers of the per-segment integrity word (module docstring).
# Both odd (units mod 2^32, so distinct lengths/tags map to distinct
# residues) and far apart so a single-row value delta cannot mimic either.
WIRE_LEN_MULT = 1000003
WIRE_TAG_MULT = 777767777


def _lane_int_dtype(dtype) -> jnp.dtype:
    """The same-width integer dtype of a payload lane."""
    return jnp.dtype(f"int{jnp.dtype(dtype).itemsize * 8}")


def int_lane_view(rows: jax.Array) -> jax.Array:
    """Bitcast a float slab to int32 lanes (sign-extending 16-bit dtypes).

    The integrity fold is wrapping int32 arithmetic over this view, so the
    fold of a bf16 slab and of its f32 upcast differ — folds only compare
    against folds of the same payload dtype, which the wire guarantees.
    """
    it = _lane_int_dtype(rows.dtype)
    return lax.bitcast_convert_type(rows, it).astype(jnp.int32)


def words_to_rows(words: jax.Array, dtype) -> jax.Array:
    """Store int32 integrity words as rows of a ``dtype``-typed slab.

    32-bit payloads hold the whole word; 16-bit payloads hold its low half
    (``bitcast_convert_type`` to int16 splits little-endian, index 0 is the
    low half) — 16 bits of fold still make an accidental collision a
    1-in-65536 event per lane, and every lane must collide at once.
    """
    assert_count_i32(words, "words_to_rows(words)")
    it = _lane_int_dtype(dtype)
    if it == jnp.int32:
        return lax.bitcast_convert_type(words, dtype)
    return lax.bitcast_convert_type(
        lax.bitcast_convert_type(words, it)[..., 0], dtype)


def stored_words(words: jax.Array, dtype) -> jax.Array:
    """Project int32 words onto the domain a ``dtype`` slab round-trips.

    Expected words must be compared to received parity rows in this domain
    — comparing the full int32 word against a 16-bit stored half would
    flag every healthy segment.
    """
    return int_lane_view(words_to_rows(words, dtype))


def segment_parity_words(rows: jax.Array, bounds: jax.Array,
                         lens: jax.Array, tags: jax.Array) -> jax.Array:
    """Integrity word of each segment of a concatenated-segments slab.

    ``rows``: (R, d) payload; ``bounds``: (S+1,) ascending segment start
    offsets (segment ``s`` spans ``[bounds[s], bounds[s+1])``, first
    ``lens[s]`` rows occupied); ``tags``: (S,) int32 identity tag folded
    into each word.  Returns (S, d) int32 words.  Pure jnp scatter-add —
    both sides of a wire recompute it from the counts they believe, so a
    disagreement in payload bits, occupancy or identity lands in the word.
    """
    from repro.core.dispatch import ragged_row_membership
    assert_count_i32(lens, "segment_parity_words(lens)")
    assert_count_i32(tags, "segment_parity_words(tags)")
    S = lens.shape[0]
    seg, _, valid = ragged_row_membership(bounds, lens, rows.shape[0])
    contrib = jnp.where(valid[:, None], int_lane_view(rows), 0)
    fold = jnp.zeros((S, rows.shape[1]), jnp.int32).at[
        jnp.where(valid, seg, 0)].add(contrib)
    return fold + (lens * WIRE_LEN_MULT + tags * WIRE_TAG_MULT)[:, None]


def checksummed_ragged_all_to_all(rows: jax.Array, parity: jax.Array,
                                  send_counts: jax.Array, axes: Axes, *,
                                  recv_rows: int, recv_counts: jax.Array,
                                  nl: int, allow_truncate: bool = False
                                  ) -> Tuple[jax.Array, jax.Array]:
    """Ragged All2All with per-segment parity rows riding the same slab.

    ``rows``: (R, d) rank-major staged data (exactly as
    :func:`ragged_all_to_all` takes it); ``parity``: (P*nl, d) parity rows
    in payload dtype, destination-major (rows ``p*nl:(p+1)*nl`` ride at
    the tail of peer ``p``'s segment).  ``recv_counts`` are the believed
    per-source DATA counts; the wire moves ``send_counts + nl`` rows per
    peer and ``recv_rows`` must bound the WIRE layout (data bound plus
    ``P * nl``).  Returns ``(wire_recv, wire_recv_counts)`` — split back
    into payload + parity with :func:`split_checksummed_recv`.

    One gather builds the interleaved wire staging from ``concat([rows,
    parity])``; the exchange itself is one ordinary
    :func:`ragged_all_to_all` of the widened counts — no extra collective,
    no extra count exchange, and the parity rows are subject to exactly
    the same wire hazards as the data they guard (that is the point).
    """
    from repro.core.dispatch import ragged_row_membership
    assert_count_i32(send_counts, "checksummed_ragged_all_to_all(send_counts)")
    assert_count_i32(recv_counts, "checksummed_ragged_all_to_all(recv_counts)")
    P = send_counts.shape[0]
    R = rows.shape[0]
    rest = rows.shape[1:]
    scw = send_counts + jnp.int32(nl)
    woff = excl_cumsum(scw)
    bounds = jnp.concatenate([woff, woff[-1:] + scw[-1:]])
    w_send = R + P * nl
    seg, within, valid = ragged_row_membership(bounds, scw, w_send)
    send_off = excl_cumsum(send_counts)
    sc_seg = jnp.take(send_counts, seg)
    is_data = within < sc_seg
    src = jnp.where(is_data, jnp.take(send_off, seg) + within,
                    R + seg * nl + (within - sc_seg))
    ext = jnp.concatenate([rows, parity.astype(rows.dtype)], axis=0)
    wire = jnp.where(valid.reshape((-1,) + (1,) * len(rest)),
                     jnp.take(ext, jnp.where(valid, src, 0), axis=0), 0)
    return ragged_all_to_all(wire, scw, axes, recv_rows=recv_rows,
                             recv_counts=recv_counts + jnp.int32(nl),
                             allow_truncate=allow_truncate)


def split_checksummed_recv(wire: jax.Array, recv_counts: jax.Array, nl: int,
                           recv_rows: int
                           ) -> Tuple[jax.Array, jax.Array]:
    """Split a checksummed receive back into payload slab + parity rows.

    ``recv_counts``: believed per-source DATA counts (P,); ``recv_rows``:
    the DATA slab bound.  Returns ``(data, parity)`` — ``data``
    (recv_rows, d) laid out exactly as the plain :func:`ragged_all_to_all`
    receive (source ``p`` at the exclusive cumsum of ``recv_counts``, zero
    elsewhere), ``parity`` (P, nl, d) the received parity rows.  Gathers
    clamp at the slab edge, so callers that truncated the wire bound must
    mask out sources whose region did not fully arrive before trusting
    either piece.
    """
    from repro.core.dispatch import ragged_row_membership
    assert_count_i32(recv_counts, "split_checksummed_recv(recv_counts)")
    P = recv_counts.shape[0]
    rest = wire.shape[1:]
    woff = excl_cumsum(recv_counts + jnp.int32(nl))
    doff = excl_cumsum(recv_counts)
    bounds = jnp.concatenate([doff, doff[-1:] + recv_counts[-1:]])
    seg, within, valid = ragged_row_membership(bounds, recv_counts, recv_rows)
    src = jnp.where(valid, jnp.take(woff, seg) + within, 0)
    data = jnp.where(valid.reshape((-1,) + (1,) * len(rest)),
                     jnp.take(wire, src, axis=0), 0)
    pidx = (woff[:, None] + recv_counts[:, None]
            + jnp.arange(nl, dtype=jnp.int32)[None, :])
    parity = jnp.take(wire, pidx.reshape(-1), axis=0
                      ).reshape((P, nl) + rest)
    return data, parity


# ---------------------------------------------------------------- token split
def split_tokens(x, plan_axes: Axes, size: int):
    """Evenly split the leading (token) dim of ``x`` across ``plan_axes``.

    Pads to a multiple of ``size`` when needed; returns ``(local, pad)`` where
    ``pad`` is the number of padding rows appended *globally* (the local shard
    of this device may or may not contain padding — callers mask via the
    returned valid length arithmetic). Used to convert tensor-parallel
    replicated activations into expert-parallel token shards for the MoE block
    (the paper's "each worker owns a slice of the batch").
    """
    axes = _norm(plan_axes)
    t = x.shape[0]
    pad = (-t) % size
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    if not axes:
        return x, pad
    idx = lax.axis_index(axes)
    per = x.shape[0] // size
    local = lax.dynamic_slice_in_dim(x, idx * per, per, axis=0)
    return local, pad


def unsplit_tokens(local, plan_axes: Axes, orig_len: int):
    """Inverse of :func:`split_tokens`: all_gather shards and drop padding."""
    axes = _norm(plan_axes)
    if axes:
        local = lax.all_gather(local, axes, axis=0, tiled=True)
    return local[:orig_len]
