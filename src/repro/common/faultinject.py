"""Deterministic fault-injection harness for the hop pipeline.

**Architecture.**  Production MoE training fails in ways a dense loop never
sees: a corrupted count grid on a dispatch hop, NaN payload rows from a bad
reduction, a peer that silently drops its segment, a routing-collapse storm
that funnels every token to one expert.  The containment machinery for each
of those lives in three layers — count-grid sanitization in
``core/pipeline`` + ``sharding/comm``, drop accounting through the echoed
reverse hop, and the step sentinel in ``train/sentinel`` — and every one of
those paths must be *exercisable*, not just argued.  This module is the
exerciser: a seeded, config-driven :class:`FaultPlan` registered in
``MOE_OPTIONS`` (``MoEConfig.fault_plan``) that the pipeline executor
consults at trace time and injects faults from deterministically, so the
fault matrix in ``tests/distributed/_faults.py`` runs the same fault on the
8-fake-device mesh and the single-device oracle and asserts *exact*
``fault_events`` / ``drop_frac`` accounting.

**Determinism.**  Every injection site is chosen at *trace* time from
``random.Random(seed, level, shape)`` — static Python ints, no jax PRNG —
so a plan is a pure function of its spec string and the (static) shapes it
meets: re-running a faulted step reproduces the identical fault, and the
tests can compute the expected event counts with the ``expected_*`` /
``*_victim`` helpers below instead of re-deriving them by hand.

**Plan spec.**  ``kind[@seed][:hop]`` where ``kind`` is one of

* ``counts``  — overwrite seeded entries of the exchanged ``(P, nl)`` count
  grid with a negative value.  Exercises the sanitizer: each violating
  entry is one ``fault_event``; the corrupted source is quarantined (its
  whole segment dropped with exact ``drop_frac`` accounting via the echoed
  reverse hop).  Inert on padded/local hops (no count grid on the wire).
* ``nanrows`` — overwrite seeded rows of the post-exchange receive slab
  (or the local/padded dispatch buffer) with NaN.  With
  ``wire_integrity="off"`` there is no hop-level detection — containment
  is the step sentinel's non-finite verdict skipping the optimizer
  update.  With the wire-integrity layer on, the injection moves onto the
  received *wire* slab (one seeded source rank's region) and the
  per-segment parity row localizes it to the exact (hop, src rank).
* ``dropseg`` — zero one seeded source rank's row of the count grid at
  every receiver: the peer "sent nothing" (silent segment loss).  A valid
  grid, so zero ``fault_events``; containment is exact drop accounting —
  every assignment from the victim rank drops, ``drop_frac == 1/P`` on an
  otherwise drop-free hop — with the victim's outputs zero-filled.
* ``skew``  — override the hop's route decision so every assignment
  targets one seeded group (router-collapse storm).  Unbounded ragged hops
  absorb it with zero drops; bounded hops clamp and account; the router
  watchdog (``hop_max_load`` / ``hop_load_entropy`` in ``MoEStats``) alarms.
* ``bitflip`` — XOR one bit per lane of one seeded source rank's region of
  the received wire slab (bit 0 on data rows, bit 8 on parity rows, so the
  two deltas can never cancel for segments shorter than 256 rows).
  Structurally invisible (a valid grid, finite floats, plausible
  magnitudes): the count-grid sanitizer *provably cannot* see it.  Only
  the checksum layer detects it; with ``wire_integrity="off"`` the flipped
  payload flows to the loss undetected.
* ``inflate`` — add 1 to one seeded in-bounds entry of the count grid
  before sanitation.  Still a valid grid (zero sanitizer events), but the
  believed segment length now disagrees with the parity word's length
  term, so checksum verification localizes the inflating source exactly.
* ``dupseg``  — replay one seeded source rank's segment as its
  neighbour's: grid row ``w`` is overwritten with row ``v=(w+1)%P`` and
  ``v``'s wire region is copied onto ``w``'s.  Data, length and fold all
  verify — only the parity word's (src, dest, group) *tag* gives the
  replay away, which is exactly what the tag term exists for.

``@seed`` defaults to 0; ``:hop`` defaults to ``-1`` (all hops).
``"none"``/``""`` parse to ``None`` (no injection — the bit-identical
production path).

This module keeps jax out of its import path (``repro.common.config``
validates plans and must stay jax-free); the injectors import ``jax.numpy``
lazily.
"""
from __future__ import annotations

import random
from dataclasses import dataclass
from typing import List, Optional, Tuple

FAULT_KINDS = ("counts", "nanrows", "dropseg", "skew", "bitflip", "inflate",
               "dupseg")

# injected magnitudes (static; chosen so tests can assert exact accounting)
COUNT_POISON = -7          # negative count written by the "counts" kind
N_COUNT_FAULTS = 2         # grid entries poisoned per (device, hop)
N_NAN_ROWS = 3             # slab rows NaN'd per (device, hop)


@dataclass(frozen=True)
class FaultPlan:
    """One parsed fault plan (hashable; lives on the frozen MoEConfig)."""
    kind: str
    seed: int = 0
    hop: int = -1            # -1 = every hop

    def targets(self, level: int) -> bool:
        return self.hop in (-1, level)

    @property
    def wants_echo(self) -> bool:
        """Count-targeting kinds need the echoed reverse hop for exact
        drop accounting (see ``pipeline._ragged_reverse``)."""
        return self.kind in ("counts", "dropseg", "inflate", "dupseg")


def parse_fault_plan(spec: Optional[str]) -> Optional[FaultPlan]:
    """Parse ``kind[@seed][:hop]`` -> :class:`FaultPlan` (or None).

    Raises ``ValueError`` on malformed specs — called by
    ``MoEConfig.with_options`` so a typo'd plan fails at config time, not
    silently mid-run.
    """
    if spec is None:
        return None
    s = spec.strip()
    if s in ("", "none", "off"):
        return None
    hop = -1
    if ":" in s:
        s, hop_s = s.rsplit(":", 1)
        try:
            hop = int(hop_s)
        except ValueError:
            raise ValueError(f"fault plan {spec!r}: hop {hop_s!r} is not an "
                             f"integer") from None
        if hop < -1:
            raise ValueError(f"fault plan {spec!r}: hop must be >= -1")
    seed = 0
    if "@" in s:
        s, seed_s = s.rsplit("@", 1)
        try:
            seed = int(seed_s)
        except ValueError:
            raise ValueError(f"fault plan {spec!r}: seed {seed_s!r} is not "
                             f"an integer") from None
    if s not in FAULT_KINDS:
        raise ValueError(f"fault plan {spec!r}: unknown kind {s!r}; expected "
                         f"one of {FAULT_KINDS}")
    return FaultPlan(s, seed, hop)


def _rng(fp: FaultPlan, level: int, *shape_tag: int) -> random.Random:
    # seeded by the tuple's text: Python 3.11+ seeds only from None, numbers,
    # str and bytes, and a str seed is hashed the same in every process
    return random.Random(repr((fp.seed, fp.kind, level) + shape_tag))


# =============================================================================
# Trace-time site selection (static; shared with the tests' expectations)
# =============================================================================

def count_fault_sites(fp: FaultPlan, level: int, P: int, nl: int
                      ) -> List[Tuple[int, int]]:
    """The (src, group) grid entries the ``counts`` kind poisons."""
    r = _rng(fp, level, P, nl)
    n = min(N_COUNT_FAULTS, P * nl)
    flat = r.sample(range(P * nl), n)
    return [(i // nl, i % nl) for i in sorted(flat)]


def expected_count_events(fp: FaultPlan, level: int, P: int, nl: int) -> int:
    """Sanitizer events one device reports on this hop (== poisoned sites)."""
    return len(count_fault_sites(fp, level, P, nl))


def dropseg_victim(fp: FaultPlan, level: int, P: int) -> int:
    """The source rank whose segments the ``dropseg`` kind suppresses."""
    return _rng(fp, level, P).randrange(P)


def nan_row_sites(fp: FaultPlan, level: int, rows: int) -> List[int]:
    r = _rng(fp, level, rows)
    return sorted(r.sample(range(rows), min(N_NAN_ROWS, rows)))


def expected_nan_rows() -> int:
    return N_NAN_ROWS


def skew_target(fp: FaultPlan, level: int, num_groups: int) -> int:
    return _rng(fp, level, num_groups).randrange(num_groups)


def wire_victim(fp: FaultPlan, level: int, P: int) -> int:
    """The source rank whose received wire region the wire-slab kinds
    (``bitflip``, wire-mode ``nanrows``, ``dupseg``) corrupt."""
    return _rng(fp, level, P).randrange(P)


def inflate_site(fp: FaultPlan, level: int, P: int, nl: int
                 ) -> Tuple[int, int]:
    """The (src, group) count-grid entry the ``inflate`` kind bumps by 1."""
    i = _rng(fp, level, P, nl).randrange(P * nl)
    return (i // nl, i % nl)


def wire_fault_victim(fp: FaultPlan, level: int, P: int, nl: int) -> int:
    """The source rank the checksum layer must localize for ``fp.kind``
    on this hop — shared with the fault-matrix tests' expectations."""
    if fp.kind == "inflate":
        return inflate_site(fp, level, P, nl)[0]
    return wire_victim(fp, level, P)


# =============================================================================
# Injectors (called by the pipeline executor at trace time; lazy jnp)
# =============================================================================

def corrupt_len_grid(fp: FaultPlan, level: int, len_grid):
    """``counts``: poison seeded entries of the exchanged (P, nl) grid."""
    import jax.numpy as jnp
    P, nl = len_grid.shape
    for p, g in count_fault_sites(fp, level, P, nl):
        len_grid = len_grid.at[p, g].set(jnp.int32(COUNT_POISON))
    return len_grid


def drop_segment(fp: FaultPlan, level: int, len_grid):
    """``dropseg``: zero the victim source's whole row of the count grid."""
    P = len_grid.shape[0]
    return len_grid.at[dropseg_victim(fp, level, P)].set(0)


def nan_rows(fp: FaultPlan, level: int, rows, valid=None):
    """``nanrows``: NaN rows of a (R, ...) float slab.

    With ``valid`` (a boolean (R,) occupancy mask) the first
    :data:`N_NAN_ROWS` *occupied* rows are hit — injecting into padding
    would be silently gathered away by ``combine`` and never reach the
    layer output, which is exactly the no-op a fault test must not be.
    Without a mask, seeded static rows are hit.
    """
    import jax.numpy as jnp
    if valid is None:
        idx = jnp.asarray(nan_row_sites(fp, level, rows.shape[0]), jnp.int32)
        return rows.at[idx].set(jnp.nan)
    v = valid.astype(jnp.int32)
    hit = (jnp.cumsum(v) <= N_NAN_ROWS) & (v > 0)
    hit = hit.reshape(hit.shape + (1,) * (rows.ndim - 1))
    return jnp.where(hit, jnp.nan, rows)


def inflate_grid(fp: FaultPlan, level: int, len_grid):
    """``inflate``: bump one seeded entry of the believed (P, nl) grid.

    Unlike ``counts`` the result is still a *valid* grid (non-negative,
    in-bounds at the fault-matrix settings), so the sanitizer reports zero
    events — only the parity word's length term can catch it."""
    p, g = inflate_site(fp, level, *len_grid.shape)
    return len_grid.at[p, g].add(1)


def dup_grid(fp: FaultPlan, level: int, len_grid):
    """``dupseg``: overwrite victim row ``w`` with row ``v=(w+1)%P``."""
    P = len_grid.shape[0]
    w = wire_victim(fp, level, P)
    return len_grid.at[w].set(len_grid[(w + 1) % P])


def _wire_int_view(wire):
    """Bitcast a float wire slab to its same-width integer view."""
    import jax.numpy as jnp
    from jax import lax
    it = jnp.dtype(f"int{wire.dtype.itemsize * 8}")
    return lax.bitcast_convert_type(wire, it)


def flip_wire(fp: FaultPlan, level: int, wire, starts, data_counts, nl: int):
    """``bitflip``: XOR lanes of the victim's received wire region.

    Data rows get bit 0, parity rows bit 8 — asymmetric on purpose: a
    uniform flip of the lowest bit everywhere shifts an L=1 segment's fold
    and its stored parity word by the *same* ±1 and escapes detection.
    With ±1 on data and ±256 on parity the per-lane deltas cannot cancel
    while the segment is shorter than 256 rows."""
    import jax.numpy as jnp
    from jax import lax
    v = wire_victim(fp, level, starts.shape[0])
    iw = _wire_int_view(wire)
    r = jnp.arange(wire.shape[0], dtype=jnp.int32)
    s, c = starts[v], data_counts[v]
    in_data = (r >= s) & (r < s + c)
    in_par = (r >= s + c) & (r < s + c + nl)
    mask = jnp.where(in_data, 1, jnp.where(in_par, 256, 0)).astype(iw.dtype)
    return lax.bitcast_convert_type(iw ^ mask[:, None], wire.dtype)


def nan_wire(fp: FaultPlan, level: int, wire, starts, wire_counts):
    """Wire-mode ``nanrows``: NaN the first rows of the victim's region.

    Row 0 of a region is always either a live data row or the first
    parity row, so at least one NaN'd row is load-bearing and the
    checksum mismatch is guaranteed."""
    import jax.numpy as jnp
    v = wire_victim(fp, level, starts.shape[0])
    r = jnp.arange(wire.shape[0], dtype=jnp.int32)
    n = jnp.minimum(jnp.int32(N_NAN_ROWS), wire_counts[v])
    hit = (r >= starts[v]) & (r < starts[v] + n)
    return jnp.where(hit[:, None], jnp.nan, wire)


def copy_wire_region(fp: FaultPlan, level: int, wire, starts, wire_counts):
    """``dupseg``: replay ``v=(w+1)%P``'s wire region into victim ``w``'s.

    Paired with :func:`dup_grid` (so the two regions have equal believed
    extents); the copied parity row verifies against its own data but
    carries ``v``'s source tag where the receiver expects ``w``'s."""
    import jax.numpy as jnp
    P = starts.shape[0]
    w = wire_victim(fp, level, P)
    v = (w + 1) % P
    r = jnp.arange(wire.shape[0], dtype=jnp.int32)
    off = r - starts[w]
    in_w = (off >= 0) & (off < wire_counts[w])
    src = jnp.where(in_w, starts[v] + off, r)
    return jnp.take(wire, src, axis=0)


def apply_skew(fp: FaultPlan, level: int, dec, num_groups: int,
               loss_groups: int):
    """``skew``: collapse the route decision onto one seeded group.

    Overrides both the dispatch targets (``group_ids``) and the router
    argmax (``top1``) so the LB ``f``-vector — and the router watchdog fed
    from it — sees the storm.  Gates/probs are left untouched (finite), so
    the faulted layer stays oracle-comparable.
    """
    import dataclasses

    import jax.numpy as jnp
    g = skew_target(fp, level, num_groups)
    return dataclasses.replace(
        dec,
        group_ids=jnp.full_like(dec.group_ids, g),
        top1=jnp.full_like(dec.top1, g % max(loss_groups, 1)))
