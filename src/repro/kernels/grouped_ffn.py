"""Pallas TPU kernels: grouped (per-expert) FFN — dense and ragged.

This is the expert-compute hot spot of the MoE layer — the "FFN Expert" slice
of the paper's Table 3 time breakdown.  Two variants share one tile body
(``act(x @ w1) [* (x @ w3)] @ w2`` with MXU-aligned VMEM tiles, fp32
accumulation, output revisiting over the innermost ``f`` grid axis):

* :func:`grouped_ffn_pallas` — capacity-buffer layout ``(G, T, d)``: every
  group holds the same (padded) number of rows.  Grid ``(G, T/bt, f/bf)``.

* :func:`grouped_ffn_ragged_pallas` — the dropless tile-aligned ragged
  layout from :mod:`repro.core.dispatch`: a flat ``(R, d)`` row array where
  each group's segment starts at a ``block``-aligned offset and holds exactly
  its own tokens (MegaBlocks-style).  Grid ``(R/bt, f/bf)``; the per-tile
  group id (derived from the ragged ``group_starts`` offsets) is
  scalar-prefetched into SMEM, and each step's ``BlockSpec`` index map reads
  it to DMA that group's weight tiles — no capacity padding is ever touched
  by the MXU, and no per-tile weight copy is materialized in HBM (the
  indirection happens in the DMA descriptor, which is exactly what scalar
  prefetch is for).  Alignment-padding rows arrive zeroed by the dispatch
  gather and stay zero through the FFN (``act(0) == 0`` for gelu/silu and
  the GLU product keeps them zero), so the kernel needs no row masks.

Tiling: ``bt=128``/``bf=512`` keeps the working set
``bt*d + 2*d*bf + bf*d + bt*bf + bt*d`` under ~8 MB VMEM at d=8192 and hits
the 128-lane MXU shape on every contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ffn_tile(x, w1, w3, w2, *, act: str):
    """One (bt, d) output tile's contribution for one (d, bf) weight slice."""
    h = jnp.dot(x, w1, preferred_element_type=jnp.float32)
    h = jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)
    if w3 is not None:
        h = h * jnp.dot(x, w3, preferred_element_type=jnp.float32)
    return jnp.dot(h.astype(x.dtype), w2, preferred_element_type=jnp.float32)


def _accumulate(o_ref, contrib, f_id):
    """Init the output tile on the first f step, accumulate afterwards
    (the f axis is innermost, so the tile stays resident in VMEM)."""
    @pl.when(f_id == 0)
    def _init():
        o_ref[0] = contrib.astype(o_ref.dtype)

    @pl.when(f_id != 0)
    def _acc():
        o_ref[0] = (o_ref[0] + contrib).astype(o_ref.dtype)


def _kernel_glu(x_ref, w1_ref, w3_ref, w2_ref, o_ref, *, act: str):
    contrib = _ffn_tile(x_ref[0], w1_ref[0], w3_ref[0], w2_ref[0], act=act)
    _accumulate(o_ref, contrib, pl.program_id(2))


def _kernel_mlp(x_ref, w1_ref, w2_ref, o_ref, *, act: str):
    contrib = _ffn_tile(x_ref[0], w1_ref[0], None, w2_ref[0], act=act)
    _accumulate(o_ref, contrib, pl.program_id(2))


def _kernel_glu_ragged(gid_ref, x_ref, w1_ref, w3_ref, w2_ref, o_ref,
                       *, act: str):
    contrib = _ffn_tile(x_ref[0], w1_ref[0], w3_ref[0], w2_ref[0], act=act)
    _accumulate(o_ref, contrib, pl.program_id(1))


def _kernel_mlp_ragged(gid_ref, x_ref, w1_ref, w2_ref, o_ref, *, act: str):
    contrib = _ffn_tile(x_ref[0], w1_ref[0], None, w2_ref[0], act=act)
    _accumulate(o_ref, contrib, pl.program_id(1))


def _pick_bf(f: int, bf: int, w1, w3, w2):
    """Resolve the f-axis tile: shrink to a divisor of f when possible.

    f % bf != 0 used to silently truncate the tail columns (grid = f // bf).
    Prefer shrinking bf to the largest divisor of f (no data movement); only
    a pathological f with no lane-sized divisor falls back to zero-padding
    the weights (exact: act(0) == 0 for gelu/silu and padded w2 rows are 0,
    but it copies the expert weights every call).
    """
    pad_f = 0
    if f % bf:
        div = max(d_ for d_ in range(1, bf + 1) if f % d_ == 0)
        if div >= min(128, f):
            bf = div
        else:
            pad_f = (-f) % bf
            w1 = jnp.pad(w1, ((0, 0), (0, 0), (0, pad_f)))
            if w3 is not None:
                w3 = jnp.pad(w3, ((0, 0), (0, 0), (0, pad_f)))
            w2 = jnp.pad(w2, ((0, 0), (0, pad_f), (0, 0)))
    return bf, f + pad_f, w1, w3, w2


def grouped_ffn_pallas(x: jax.Array, w1: jax.Array, w3, w2: jax.Array,
                       *, act: str = "gelu", block_t: int = 128,
                       block_f: int = 512, interpret: bool = False
                       ) -> jax.Array:
    """x: (G, T, d); w1/w3: (G, d, f); w2: (G, f, d) -> (G, T, d)."""
    G, T, d = x.shape
    f = w1.shape[-1]
    bt = min(block_t, T)
    bf = min(block_f, f)
    pad_t = (-T) % bt
    if pad_t:
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, 0)))
    Tp = x.shape[1]
    bf, fp, w1, w3, w2 = _pick_bf(f, bf, w1, w3, w2)
    grid = (G, Tp // bt, fp // bf)

    x_spec = pl.BlockSpec((1, bt, d), lambda g, t, j: (g, t, 0))
    w1_spec = pl.BlockSpec((1, d, bf), lambda g, t, j: (g, 0, j))
    w2_spec = pl.BlockSpec((1, bf, d), lambda g, t, j: (g, j, 0))
    o_spec = pl.BlockSpec((1, bt, d), lambda g, t, j: (g, t, 0))

    if w3 is not None:
        kern = functools.partial(_kernel_glu, act=act)
        in_specs = [x_spec, w1_spec, w1_spec, w2_spec]
        args = (x, w1, w3, w2)
    else:
        kern = functools.partial(_kernel_mlp, act=act)
        in_specs = [x_spec, w1_spec, w2_spec]
        args = (x, w1, w2)

    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((G, Tp, d), x.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        # the output block accumulates over the f axis (innermost): that
        # axis is sequential; group and row-tile axes are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out[:, :T]


def grouped_ffn_ragged_pallas(rows: jax.Array, tile_gid: jax.Array,
                              w1: jax.Array, w3, w2: jax.Array,
                              *, act: str = "gelu", block_f: int = 512,
                              interpret: bool = False) -> jax.Array:
    """Ragged grouped FFN over the tile-aligned dropless layout.

    ``rows``: (R, d) flat row array, R a multiple of the row-tile size;
    ``tile_gid``: (R // bt,) int32 group id per row tile (scalar-prefetched;
    see :func:`repro.core.dispatch.ragged_tile_gids`); weights as in
    :func:`grouped_ffn_pallas`.  Returns (R, d).
    """
    R, d = rows.shape
    n_tiles = tile_gid.shape[0]
    assert R % n_tiles == 0, (R, n_tiles)
    bt = R // n_tiles
    f = w1.shape[-1]
    bf = min(block_f, f)
    bf, fp, w1, w3, w2 = _pick_bf(f, bf, w1, w3, w2)
    grid = (n_tiles, fp // bf)

    x3 = rows.reshape(n_tiles, bt, d)
    x_spec = pl.BlockSpec((1, bt, d), lambda i, j, gid: (i, 0, 0))
    w1_spec = pl.BlockSpec((1, d, bf), lambda i, j, gid: (gid[i], 0, j))
    w2_spec = pl.BlockSpec((1, bf, d), lambda i, j, gid: (gid[i], j, 0))
    o_spec = pl.BlockSpec((1, bt, d), lambda i, j, gid: (i, 0, 0))

    if w3 is not None:
        kern = functools.partial(_kernel_glu_ragged, act=act)
        in_specs = [x_spec, w1_spec, w1_spec, w2_spec]
        args = (x3, w1, w3, w2)
    else:
        kern = functools.partial(_kernel_mlp_ragged, act=act)
        in_specs = [x_spec, w1_spec, w2_spec]
        args = (x3, w1, w2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, bt, d), rows.dtype),
        # each row tile's output accumulates over the f axis (innermost):
        # sequential; row tiles are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_gid.astype(jnp.int32), *args)
    return out.reshape(R, d)
