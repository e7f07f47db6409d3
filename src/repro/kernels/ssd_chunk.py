"""Pallas TPU kernel: Mamba2 SSD intra-chunk block.

The chunked state-space-dual computation is zamba2's compute hot spot. For
one (batch, chunk, head) cell it fuses:

    cs       = cumsum(loga)                       (Q,)
    scores   = C B^T                              (Q, Q)   MXU
    w        = tril(exp(cs_i - cs_j)) * scores
    y_intra  = (w * dt_j) x                       (Q, hd)  MXU
    sB       = (exp(cs_Q - cs) * dt * x)^T B      (hd, ds) MXU
    a_chunk  = exp(cs_Q)

materializing the (Q, Q) decay matrix only in VMEM (the jnp reference builds
a (B, nc, Q, Q, nh) tensor in HBM). The sequential inter-chunk recurrence
(tiny: (hd, ds) state per head) stays in ``lax.scan`` outside the kernel.

The O(Q) terms — ``cs``, ``exp(cs_Q - cs) * dt`` and ``a_chunk`` — are
computed by XLA in the wrapper, which also lays every per-head operand out
head-major with the chunk's Q steps in the last two dims: Mosaic accepts a
block only when its last two dims are tile multiples or the array's own,
and ``cs``/``dt`` arrive in both a row ``(1, Q)`` and a column ``(Q, 1)``
form, so the kernel needs no transpose or cumulative sum.

Working set at Q=128, hd=64, ds=64: 2*Q*hd + 2*Q*ds + Q*Q + hd*ds fp32
~ 200 KB — far under VMEM; both matmul shapes are 128-aligned.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, xt_ref, csr_ref, csc_ref, dtr_ref, tdt_ref, b_ref, c_ref,
            y_ref, sb_ref):
    x = x_ref[0, 0].astype(jnp.float32)                 # (Q, hd)
    xT = xt_ref[0, 0].astype(jnp.float32)               # (hd, Q)
    cs_r = csr_ref[0, 0]                                # (1, Q)
    cs_c = csc_ref[0, 0]                                # (Q, 1)
    dt_r = dtr_ref[0, 0]                                # (1, Q)
    tail_dt = tdt_ref[0, 0]                             # (1, Q)
    B = b_ref[0].astype(jnp.float32)                    # (Q, ds)
    C = c_ref[0].astype(jnp.float32)                    # (Q, ds)
    Q = x.shape[0]

    scores = jax.lax.dot_general(                       # C B^T  (Q, Q)
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    decay = cs_c - cs_r
    mask = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    w = jnp.where(mask, jnp.exp(decay), 0.0) * scores   # (Q, Q)
    y_ref[0, 0] = jnp.dot(w * dt_r, x,
                          preferred_element_type=jnp.float32)   # (Q, hd)
    sb_ref[0, 0] = jnp.dot(xT * tail_dt, B,
                           preferred_element_type=jnp.float32)  # (hd, ds)


def ssd_chunk_pallas(xh, dt, loga, Bc, Cc, *, interpret: bool = False):
    """Intra-chunk SSD terms.

    xh: (B, nc, Q, nh, hd); dt/loga: (B, nc, Q, nh); Bc/Cc: (B, nc, Q, ds).
    Returns (y_intra (B,nc,Q,nh,hd), sB (B,nc,nh,hd,ds), a_chunk (B,nc,nh)).
    """
    B, nc, Q, nh, hd = xh.shape
    ds = Bc.shape[-1]
    G = B * nc
    f32 = jnp.float32
    x = xh.reshape(G, Q, nh, hd).transpose(0, 2, 1, 3)      # (G, nh, Q, hd)
    xT = x.transpose(0, 1, 3, 2)                            # (G, nh, hd, Q)
    cs = jnp.cumsum(loga.astype(f32), axis=2).reshape(G, Q, nh)
    dtf = dt.astype(f32).reshape(G, Q, nh)
    tail_dt = jnp.exp(cs[:, -1:, :] - cs) * dtf

    def row(a):                                             # (G, nh, 1, Q)
        return a.transpose(0, 2, 1)[:, :, None, :]

    per_head = lambda *blk: pl.BlockSpec(blk, lambda g, h: (g, h, 0, 0))
    shared = pl.BlockSpec((1, Q, ds), lambda g, h: (g, 0, 0))
    y, sb = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct((G, nh, Q, hd), f32),
                   jax.ShapeDtypeStruct((G, nh, hd, ds), f32)),
        grid=(G, nh),
        in_specs=[per_head(1, 1, Q, hd), per_head(1, 1, hd, Q),
                  per_head(1, 1, 1, Q), per_head(1, 1, Q, 1),
                  per_head(1, 1, 1, Q), per_head(1, 1, 1, Q),
                  shared, shared],
        out_specs=(per_head(1, 1, Q, hd), per_head(1, 1, hd, ds)),
        # every (chunk, head) cell is independent; the cross-chunk stitch
        # happens in the outer scan, not in this kernel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, xT, row(cs), row(cs).transpose(0, 1, 3, 2), row(dtf), row(tail_dt),
      Bc.reshape(G, Q, ds), Cc.reshape(G, Q, ds))
    return (y.transpose(0, 2, 1, 3).reshape(B, nc, Q, nh, hd),
            sb.reshape(B, nc, nh, hd, ds),
            jnp.exp(cs[:, -1, :]).reshape(B, nc, nh))
