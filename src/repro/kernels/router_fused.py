"""Pallas TPU kernel: the fused routing megakernel.

Every hop of the MoE pipeline opens with the same four-stage routing
prologue: router GEMM (``(t, d) @ (d, E)``), softmax, top-k expert
selection, and the dispatch position math (histogram + exclusive prefix
counts over the chosen expert ids).  Unfused, each stage is its own XLA
op with an HBM round trip between them — the logits tensor alone is
written and re-read twice (softmax, then ``lax.top_k``), and the group
sort adds its own streaming passes over the assignment ids.  MegaScale-MoE
(PAPERS.md) reports fusing exactly this dispatch stage as a headline win.

:func:`router_fused_pallas` runs the whole prologue in **one pass over the
token tiles**, everything after the GEMM staying in VMEM:

* tile ``i`` computes its logits block on the MXU
  (``jnp.dot(..., preferred_element_type=f32)`` — bit-identical to the
  unfused fp32 ``einsum``), writes it out once (the z-loss needs it), and
  immediately derives the softmax in-register — max-subtracted, exactly
  the :func:`jax.nn.softmax` primitive sequence, so ``probs`` (the LB-loss
  input) is bit-compatible with the unfused path;
* top-k is ``k`` unrolled max-extraction rounds over the VMEM probs block.
  Ties are broken by an **explicit lowest-expert-index rule** (mask the
  max's candidates against the lane iota and take the minimum index) —
  the same order ``lax.top_k`` guarantees, pinned here so the fused and
  unfused impls can never silently disagree on tied logits (asserted
  bit-for-bit under deliberate bf16 ties in ``tests/test_router_fused.py``);
* the chosen ids feed the radix-sort histogram idiom
  (:mod:`repro.kernels.radix_sort`): a one-hot compare against the domain
  iota bumps a running per-expert int32 histogram carried across the
  sequential grid in VMEM scratch, the within-tile exclusive equal-key
  count is the strictly-lower-triangular pairwise compare, and the final
  histogram flushes once on the last step.

The wrapper turns the per-element local ranks + final histogram into the
canonical ``(ranks, starts)`` contract of :func:`repro.kernels.ops
.group_sort` — each assignment's stable dispatch position
(``ranks[a] = starts[idx[a]] + #earlier-equal``) feeding straight into the
dispatch gather, with no separate sort pass over the ids.  When a hop
relabels groups (rank-major perms, SMILE's virtual-group mapping), the
relabel is a pure label permutation applied downstream of these ids — the
positions here are over the raw expert domain, which is the dispatch
domain whenever group ids coincide with expert ids.

Outputs (``t`` tokens, ``E`` experts, ``A = t*k`` assignments):
``gates (t, k)`` — top-k probabilities, optionally renormalized;
``idx (t, k)`` int32 — chosen expert ids, descending by probability;
``probs (t, E)`` / ``logits (t, E)`` fp32 — the loss inputs, bit-compatible
with the unfused ``router_probs``; ``ranks (A,)`` / ``starts (E + 1,)``
int32 — the counting-sort position contract (per-expert counts are
``starts[1:] - starts[:-1]``).

Padding: ``t`` pads up to whole row tiles; pad rows are masked out of the
histogram (their gates/ids are sliced off before returning), so no
sentinel key is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# row-tile: one lane width of tokens per grid step keeps the (bt*k, bt*k)
# within-tile pair mask and the (bt, E) logits/probs blocks far under VMEM
# at every supported expert count (E <= a few hundred)
BLOCK_ROWS = 128


def _router_fused_kernel(x_ref, w_ref, logits_ref, probs_ref, gates_ref,
                         idx_ref, local_ref, hist_ref, count_ref, *,
                         n_tiles: int, k: int, rows: int):
    """One grid step = one (bt, d) tile of tokens.

    ``count_ref``: (1, D) int32 VMEM scratch — running per-expert histogram
    of every tile BEFORE this one (persists across the sequential grid).
    ``local_ref``: (bt, k) int32 — per-assignment count of earlier equal
    expert ids over the whole array.  ``hist_ref``: (1, D) int32 — final
    histogram, written once on the last step.  ``rows`` = real token count
    (rows past it are padding, masked from the histogram).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)

    bt = x_ref.shape[0]
    E = w_ref.shape[1]
    D = count_ref.shape[1]

    # ---- router GEMM tile (MXU) + in-VMEM softmax ---------------------------
    logits = jnp.dot(x_ref[...].astype(jnp.float32),
                     w_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32)       # (bt, E)
    logits_ref[...] = logits
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)             # == jax.nn.softmax
    probs_ref[...] = probs

    # ---- top-k: k max-extraction rounds, lowest-index tie-break -------------
    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)
    work = probs
    gsel, isel = [], []
    for _ in range(k):
        g = jnp.max(work, axis=-1, keepdims=True)              # (bt, 1)
        # ties: the minimum expert index attaining the max — the order
        # lax.top_k guarantees, pinned explicitly (see module docstring)
        sel = jnp.min(jnp.where(work == g, lane, E), axis=-1, keepdims=True)
        gsel.append(g)
        isel.append(sel)
        work = jnp.where(lane == sel, -jnp.inf, work)
    gates = jnp.concatenate(gsel, axis=1)                      # (bt, k)
    idx = jnp.concatenate(isel, axis=1)                        # (bt, k) int32
    # NOTE: gate renormalization happens in the wrapper epilogue — the
    # k-element sum must associate exactly as the unfused XLA reduce does
    # for bit-compatibility, which an in-kernel reduce cannot guarantee
    gates_ref[...] = gates
    idx_ref[...] = idx

    # ---- one-pass histogram + element-side positions (radix-sort idiom) -----
    # flat assignment order is token-major, slot-minor — exactly the (A,)
    # order the dispatch gather consumes
    A = bt * k
    keys = idx.reshape(A, 1)
    tok = jax.lax.broadcasted_iota(jnp.int32, (bt, k), 0)
    valid = ((tok + i * bt) < rows).reshape(A, 1)              # pad-row mask
    dom = jax.lax.broadcasted_iota(jnp.int32, (A, D), 1)
    onehot = ((keys == dom) & valid).astype(jnp.int32)         # (A, D)

    row = jax.lax.broadcasted_iota(jnp.int32, (A, A), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (A, A), 1)
    eq_pair = (keys == keys.reshape(1, A)) & (col < row) & valid.reshape(1, A)
    within = eq_pair.astype(jnp.int32).sum(axis=1)             # (A,)

    # cross-tile count off the running histogram (int32 masked reduce — an
    # fp32 pick would silently round past A = 2^24)
    run_pick = (count_ref[...] * onehot).sum(axis=1)           # (A,) int32
    local_ref[...] = (within + run_pick).reshape(bt, k)

    count_ref[...] = count_ref[...] + onehot.sum(axis=0, keepdims=True)

    @pl.when(i == n_tiles - 1)
    def _flush():
        hist_ref[...] = count_ref[...]


def router_fused_pallas(x: jax.Array, w: jax.Array, k: int, *,
                        renorm: bool = False, block: int = BLOCK_ROWS,
                        interpret: bool = False):
    """Fused routing prologue over tokens ``x`` (t, d) and router weights
    ``w`` (d, E).

    Returns ``(gates, idx, probs, logits, ranks, starts)`` — see the module
    docstring for shapes and the bit-compatibility contract with the
    unfused ``router_probs`` + ``topk_gates`` + ``ops.group_sort`` chain.
    """
    t, d = x.shape
    E = w.shape[1]
    if not 1 <= k <= E:
        raise ValueError(f"top-k {k} must be in [1, num_experts {E}]")
    if t == 0:
        f32 = jnp.float32
        return (jnp.zeros((0, k), f32), jnp.zeros((0, k), jnp.int32),
                jnp.zeros((0, E), f32), jnp.zeros((0, E), f32),
                jnp.zeros((0,), jnp.int32), jnp.zeros((E + 1,), jnp.int32))
    bt = block
    pad = (-t) % bt
    xp = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)]) if pad else x
    n_tiles = xp.shape[0] // bt
    D = ((E + 127) // 128) * 128                  # lane-aligned domain
    logits, probs, gates, idx, local, hist = pl.pallas_call(
        functools.partial(_router_fused_kernel, n_tiles=n_tiles, k=k, rows=t),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((bt, d), lambda i: (i, 0)),
                  pl.BlockSpec((d, E), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bt, E), lambda i: (i, 0)),
                   pl.BlockSpec((bt, E), lambda i: (i, 0)),
                   pl.BlockSpec((bt, k), lambda i: (i, 0)),
                   pl.BlockSpec((bt, k), lambda i: (i, 0)),
                   pl.BlockSpec((bt, k), lambda i: (i, 0)),
                   pl.BlockSpec((1, D), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_tiles * bt, E), jnp.float32),
                   jax.ShapeDtypeStruct((n_tiles * bt, E), jnp.float32),
                   jax.ShapeDtypeStruct((n_tiles * bt, k), jnp.float32),
                   jax.ShapeDtypeStruct((n_tiles * bt, k), jnp.int32),
                   jax.ShapeDtypeStruct((n_tiles * bt, k), jnp.int32),
                   jax.ShapeDtypeStruct((1, D), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, D), jnp.int32)],
        # the running histogram (scratch + revisited hist output) is
        # carried across the tile axis: it must execute sequentially
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xp, w)
    starts = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(hist[0, :E]).astype(jnp.int32)])
    gates, idx = gates[:t], idx[:t]
    if renorm and k > 1:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    ranks = local[:t].reshape(-1) + jnp.take(starts, idx.reshape(-1))
    return gates, idx, probs[:t], logits[:t], ranks, starts
