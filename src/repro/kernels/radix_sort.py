"""Pallas TPU kernel: one-pass counting (radix) sort over small-domain keys.

Every dispatch hop in :mod:`repro.core.dispatch` — the sort backend's
position assignment, the dropless sender layout, and the ragged receiver
re-compaction — reduces to ONE primitive: a *stable* sort of ``A`` int32
group ids drawn from a tiny domain (``num_groups`` experts, or
``ranks x groups_per_rank`` after rank-major relabeling; never more than a
few hundred values).  ``jnp.argsort``/``lax.sort`` lowers that to XLA's
generic comparison sort — O(A log A) compare-and-swap passes that know
nothing about the key domain.  A counting sort is O(A + E): histogram the
keys, exclusive-prefix-sum the histogram, and hand each element
``starts[key] + (#earlier equal keys)`` — its final sorted position,
stability for free because "earlier" is arrival order.

:func:`group_sort_pallas` does this in **one pass over the data**.  The TPU
grid is sequential, so a VMEM scratch accumulator can carry the running
per-key histogram across row tiles:

* tile ``i`` compares its ``bt`` keys against the domain iota
  (``(bt, D)`` one-hot, the TPU-native form of a histogram — no scatter
  hardware needed);
* the *within-tile* exclusive equal-key count is a pairwise compare of the
  tile's keys against themselves under a strictly-lower-triangular mask —
  O(bt) VPU ops per element, no domain factor, no MXU matmul;
* the *cross-tile* count is read off the running histogram scratch with an
  int32 masked reduce — exact for any int32-sized ``A``, unlike an fp32
  pick, which would silently round past ``A = 2^24`` — and the tile then
  bumps the histogram;

Everything is int32 elementwise VPU work: ``bt + 2 * lane_pad(D)`` ops per
element (the exact terms :func:`benchmarks.cost_model.sort_time_report`
charges), so the win over a comparison sort shrinks as the lane-padded
domain widens — the kernel is built for dispatch's small domains, not as a
general sort.
* the per-element local rank (``#earlier equal keys``, over the whole
  array) streams out tile by tile, and the final histogram flushes once on
  the last step.

The wrapper turns ``(local_rank, histogram)`` into the canonical
``(ranks, starts)`` contract with one tiny O(E) cumsum and one O(A)
gather-add — no sort network, no scatter, five A-sized streaming int32
transfers total (kernel: keys in, local ranks out; wrapper: local + keys
in, ranks out) vs the comparison sort's ~log2(A) read+write passes.
Output is
bit-identical to ``jnp.argsort(..., stable=True)`` position arithmetic: a
stable sort of integers is unique, so the radix and argsort paths agree
bit for bit (asserted across the whole dispatch conformance matrix in
``tests/test_dispatch_conformance.py``).

Padding: ``A`` is padded up to a whole number of row tiles with the
sentinel key ``num_keys``, which sorts after every real key and is excluded
from ``starts`` — the pad tail is sliced off before returning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# default row-tile: the within-tile pairwise term costs bt ops/element, so
# keep bt at one lane width; the (bt, D) one-hot and (bt, bt) pair mask
# stay far under VMEM at the largest supported domain (D ~ a few hundred)
BLOCK_ROWS = 128


def _group_sort_kernel(keys_ref, local_ref, hist_ref, count_ref, *,
                       n_tiles: int):
    """One grid step = one (1, 1, bt) tile of keys.

    ``count_ref``: (1, D) int32 VMEM scratch — running per-key histogram of
    every tile BEFORE this one (persists across the sequential grid).
    ``local_ref``: (1, 1, bt) int32 — this tile's per-element count of earlier
    equal keys over the whole array.  ``hist_ref``: (1, D) int32 — final
    histogram, written once on the last step.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)

    bt = local_ref.shape[2]
    D = count_ref.shape[1]
    kt = keys_ref[0]                                          # (1, bt) int32
    keys = kt.reshape(bt, 1)
    dom = jax.lax.broadcasted_iota(jnp.int32, (bt, D), 1)
    onehot = (keys == dom).astype(jnp.int32)                  # (bt, D)

    # within-tile exclusive equal-key count: pairwise compare of the tile's
    # keys against themselves under a strictly-lower-triangular mask (row r
    # counts rows r' < r with the same key) — O(bt) elementwise VPU ops per
    # element, no domain factor, no matmul
    row = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    eq_pair = (keys == kt) & (col < row)                      # (bt, bt)
    within = eq_pair.astype(jnp.int32).sum(axis=1)            # (bt,)

    # cross-tile count: pick this element's key out of the running
    # histogram (masked reduce — no vector gather needed on TPU).  Kept in
    # int32: the running count reaches A, and an fp32 pick would silently
    # round once A exceeds 2^24.
    run_pick = (count_ref[...] * onehot).sum(axis=1)          # (bt,) int32
    local_ref[0] = (within + run_pick).reshape(1, bt)

    count_ref[...] = count_ref[...] + onehot.sum(axis=0, keepdims=True)

    @pl.when(i == n_tiles - 1)
    def _flush():
        hist_ref[...] = count_ref[...]


def group_sort_pallas(keys: jax.Array, num_keys: int, *,
                      block: int = BLOCK_ROWS,
                      interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """Stable counting sort of int32 ``keys`` with domain ``[0, num_keys)``.

    Returns ``(ranks, starts)``:

    * ``ranks`` (A,) int32 — each element's position in the stable sorted
      order (the inverse of ``jnp.argsort(keys, stable=True)``);
    * ``starts`` (num_keys + 1,) int32 — exclusive prefix counts:
      ``starts[d]`` = number of keys ``< d``; ``starts[num_keys] == A``.

    ``ranks[i] = starts[keys[i]] + #{j < i : keys[j] == keys[i]}`` — the
    counting-sort identity, stability by construction.
    """
    if num_keys < 1:
        raise ValueError(f"num_keys must be >= 1, got {num_keys}")
    A = keys.shape[0]
    if A == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.zeros((num_keys + 1,), jnp.int32))
    # the tile is never shrunk below ``block``: Mosaic wants lane-aligned
    # block shapes, so a short input pads up to one full tile of sentinels
    # rather than compiling a ragged block.  Tiles are laid out
    # (n_tiles, 1, bt): a block's last two dims are then the array's own
    # (1, bt), which Mosaic accepts, where a (1, bt) block of an
    # (n_tiles, bt) array breaks the 8-row sublane tiling
    bt = block
    pad = (-A) % bt
    k32 = keys.astype(jnp.int32)
    kp = jnp.concatenate(
        [k32, jnp.full((pad,), num_keys, jnp.int32)]) if pad else k32
    n_tiles = kp.shape[0] // bt
    # histogram domain includes the pad sentinel; lane-align for VMEM
    D = ((num_keys + 1 + 127) // 128) * 128
    local, hist = pl.pallas_call(
        functools.partial(_group_sort_kernel, n_tiles=n_tiles),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((1, 1, bt), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, bt), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, D), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_tiles, 1, bt), jnp.int32),
                   jax.ShapeDtypeStruct((1, D), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, D), jnp.int32)],
        # the running histogram (scratch + revisited hist output) is
        # carried across the tile axis: it must execute sequentially
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(kp.reshape(n_tiles, 1, bt))
    # pad-sentinel counts live at hist[num_keys] and are excluded by
    # construction: starts only prefixes the real domain
    starts = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(hist[0, :num_keys]).astype(jnp.int32)])
    ranks = local.reshape(-1)[:A] + jnp.take(starts, k32)
    return ranks, starts
