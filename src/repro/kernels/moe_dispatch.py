"""Pallas TPU kernels: fused MoE dispatch gather / combine gather-reduce.

These are the two data movements bracketing expert compute (the "routing"
slice of the paper's Table 3 breakdown).  The sort backend in
:mod:`repro.core.dispatch` reduces both to row gathers with data-dependent
indices.  The index arrays are scalar-prefetched into SMEM; the gathered
array stays in HBM (``memory_space=pl.ANY``) and every grid step issues one
row DMA per selected row into its VMEM output block.  No (A, V) one-hot, no
scatter — every byte moved is a byte the buffer needs.

Row layout.  Mosaic slices a memref only at whole tiles along the
second-to-last dimension (8 rows of 32-bit words; 16-bit dtypes also pack
row pairs), so a single row of a ``(T, d)`` array cannot be a DMA source or
destination, and a ``(1, d)`` block is refused outright.  Both kernels
therefore see rows as ``(N, 1, w)`` arrays of 32-bit words (``w = d *
itemsize / 4``; a bf16 row packs adjacent column pairs into one word):
each row is then a slice of the leading dimension, and every block's last
two dimensions are the array's own ``(1, w)``.  The reshapes to and from
that view are done by XLA around the kernels and are bit-exact.

* :func:`dispatch_gather_pallas` — fill the flat capacity buffer
  ``(R = num_groups*cap, d)``: slot ``i`` copies token row ``src[i]`` from
  ``x``, or zeros when ``src[i] < 0`` (empty slot).  A grid step fills
  ``BLOCK_ROWS`` slots; empty slots issue no DMA and are zeroed in VMEM.

* :func:`combine_gather_pallas` — token ``i`` accumulates its k assignments:
  ``y[i] = sum_j scale[i, j] * rows[src[i, j]]`` with dropped assignments
  (``src < 0``) contributing zero.  Gate weighting and the k-way reduction
  are fused with the gather: a grid step DMAs the ``BLOCK_ROWS x k`` rows
  of its tokens and reduces them over j in order, in fp32.

Both kernels are layout-agnostic row gathers, so they serve the capacity
buffers (``R = num_groups * cap``, slot-major) and the dropless tile-aligned
ragged layout (``R = ragged_rows(...)``, segment-major with ``-1`` alignment
padding) without change — the backends differ only in the ``src`` maps they
prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows (dispatch) or tokens (combine) per grid step: a multiple of the
# 8-row sublane tile, so the output block's leading dim never needs masking
BLOCK_ROWS = 64


def _as_words(a: jax.Array) -> jax.Array:
    """(N, d) float32/bfloat16 -> (N, 1, w) uint32, bit-exact."""
    n, d = a.shape
    if a.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(a, jnp.uint32).reshape(n, 1, d)
    if a.dtype == jnp.bfloat16:
        if d % 2:
            raise ValueError(f"bfloat16 rows need an even width, got {d}")
        return jax.lax.bitcast_convert_type(
            a.reshape(n, d // 2, 2), jnp.uint32).reshape(n, 1, d // 2)
    raise ValueError(f"unsupported dtype {a.dtype}: float32 or bfloat16")


def _from_words(w: jax.Array, dtype) -> jax.Array:
    """Inverse of :func:`_as_words`: (N, 1, w) uint32 -> (N, d) ``dtype``."""
    n, _, nw = w.shape
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(w.reshape(n, nw), jnp.float32)
    return jax.lax.bitcast_convert_type(w.reshape(n, nw),
                                        jnp.bfloat16).reshape(n, 2 * nw)


def _pad_rows(a: jax.Array, block: int, fill) -> jax.Array:
    pad = (-a.shape[0]) % block
    if not pad:
        return a
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                   constant_values=fill)


def _dispatch_kernel(src_ref, x_hbm, o_ref, sem):
    br = o_ref.shape[0]
    base = pl.program_id(0) * br

    def copy(j):
        return pltpu.make_async_copy(x_hbm.at[pl.ds(src_ref[base + j], 1)],
                                     o_ref.at[pl.ds(j, 1)], sem)

    @pl.loop(0, br)
    def _start(j):
        @pl.when(src_ref[base + j] >= 0)
        def _():
            copy(j).start()

    @pl.loop(0, br)
    def _finish(j):
        @pl.when(src_ref[base + j] >= 0)
        def _():
            copy(j).wait()

        @pl.when(src_ref[base + j] < 0)
        def _():
            o_ref[j] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def dispatch_gather_pallas(x: jax.Array, src: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """x: (T, d); src: (R,) int32 source row ids (-1 = empty) -> (R, d)."""
    T, d = x.shape
    R = src.shape[0]
    xw = _as_words(x)
    srcp = _pad_rows(src.astype(jnp.int32), BLOCK_ROWS, -1)
    Rp = srcp.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Rp // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((BLOCK_ROWS, 1, xw.shape[2]),
                               lambda i, src: (i, 0, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        _dispatch_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Rp, 1, xw.shape[2]), jnp.uint32),
        # pure gather: every destination row is written exactly once
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(srcp, xw)
    return _from_words(out[:R], x.dtype)


def _combine_kernel(src_ref, scale_ref, rows_hbm, *refs, k: int,
                    packed: bool):
    """``scale_ref``: (bt, 1, k) fp32 gate weights, already zero for
    dropped assignments.  ``packed``: rows are bf16 pairs (low half = even
    column), accumulated as two fp32 planes; else one fp32 plane."""
    n_out = 2 if packed else 1
    outs, buf, sem = refs[:n_out], refs[n_out], refs[n_out + 1]
    bt = outs[0].shape[0]
    base = pl.program_id(0) * bt

    def copy(r, j):
        # dropped assignments read row 0 and weigh it by zero, exactly as
        # the jnp oracle's clamped gather does
        s = jnp.maximum(src_ref[(base + r) * k + j], 0)
        return pltpu.make_async_copy(rows_hbm.at[pl.ds(s, 1)],
                                     buf.at[j, pl.ds(r, 1)], sem)

    @pl.loop(0, bt)
    def _start(r):
        for j in range(k):
            copy(r, j).start()

    @pl.loop(0, bt)
    def _wait(r):
        for j in range(k):
            copy(r, j).wait()

    # reduce one token row at a time: a (1, 1, nw) value fills one sublane
    # of each vreg, so a whole block's rows as live values would need 8x
    # their bytes and overflow VMEM at the widest registry models
    @pl.loop(0, bt)
    def _reduce(r):
        row = pl.ds(r, 1)
        accs = [None] * n_out
        for j in range(k):
            w = scale_ref[row, :, j:j + 1]                 # (1, 1, 1)
            words = buf[j, row]                            # (1, 1, nw)
            if packed:
                planes = (words << 16, words & jnp.uint32(0xFFFF0000))
            else:
                planes = (words,)
            for p, bits in enumerate(planes):
                contrib = jax.lax.bitcast_convert_type(bits, jnp.float32) * w
                accs[p] = contrib if accs[p] is None else accs[p] + contrib
        for o_ref, acc in zip(outs, accs):
            o_ref[row] = acc


def combine_gather_pallas(rows: jax.Array, src: jax.Array, scale: jax.Array,
                          *, interpret: bool = False) -> jax.Array:
    """rows: (R, d); src/scale: (t, k) -> (t, d) gate-weighted k-reduction,
    accumulated in fp32 in j order and rounded to ``rows.dtype`` once."""
    R, d = rows.shape
    t, k = src.shape
    rw = _as_words(rows)
    nw = rw.shape[2]
    packed = rows.dtype == jnp.bfloat16
    n_out = 2 if packed else 1
    srcp = _pad_rows(src.astype(jnp.int32), BLOCK_ROWS, -1)
    tp = srcp.shape[0]
    w = jnp.where(srcp >= 0,
                  _pad_rows(scale.astype(jnp.float32), BLOCK_ROWS, 0),
                  0.0).reshape(tp, 1, k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tp // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, 1, k), lambda i, src: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((BLOCK_ROWS, 1, nw),
                                lambda i, src: (i, 0, 0))] * n_out,
        scratch_shapes=[pltpu.VMEM((k, BLOCK_ROWS, 1, nw), jnp.uint32),
                        pltpu.SemaphoreType.DMA(())],
    )
    outs = pl.pallas_call(
        functools.partial(_combine_kernel, k=k, packed=packed),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((tp, 1, nw), jnp.float32)] * n_out,
        # every token block reduces its own k rows: blocks are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(srcp.reshape(-1), w, rw)
    acc = jnp.stack([o[:t, 0] for o in outs], axis=-1).reshape(t, d)
    return acc.astype(rows.dtype)
