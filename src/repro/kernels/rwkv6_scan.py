"""Pallas TPU kernel: RWKV6 (WKV) recurrence.

The attention-free time-mix recurrence is the rwkv6 arch's compute hot spot
and is inherently sequential in T — the TPU-native formulation keeps the
per-head state matrix ``S (hd, hd)`` resident in VMEM/VREGs and streams the
(r, k, v, w) time series through it in T-steps, materializing nothing of
O(T^2). Grid ``(B, nh)``: heads and batches are independent, so the kernel
parallelizes across them (heads are also the tensor-parallel shard dim).

Layout.  Each step needs ``r, k, w`` as columns ``(hd, 1)`` and ``v`` as a
row ``(1, hd)``, and Mosaic reads a VMEM ref only at whole 8-row tiles.  So
the wrapper lays ``r, k, w`` out time-along-lanes ``(B, nh, hd, T)`` and
``v``/``y`` time-along-rows ``(B, nh, T, hd)``, with T padded to whole
``CHUNK``s (``k = v = r = 0``, ``w = 1``: the padded steps leave the state
unchanged).  The kernel loads one tile-aligned ``CHUNK`` of time at a time
and picks step ``i`` out of it with an exact masked reduction.

For hd=64 the state is 16 KB fp32; the r/k/v/w tiles of a 4k sequence are
4 x 1 MB — comfortably VMEM-resident.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128     # time steps per aligned load: one lane width


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, s_out_ref):
    hd, Tp = r_ref.shape[2], r_ref.shape[3]
    u = u_ref[0].astype(jnp.float32)                 # (hd, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (hd, CHUNK), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, hd), 0)

    def chunk(c, s):
        t0 = pl.multiple_of(c * CHUNK, CHUNK)
        R, K, W = (ref[0, 0, :, pl.ds(t0, CHUNK)].astype(jnp.float32)
                   for ref in (r_ref, k_ref, w_ref))          # (hd, C)
        V = v_ref[0, 0, pl.ds(t0, CHUNK), :].astype(jnp.float32)  # (C, hd)

        def step(i, carry):
            s, Y = carry

            def col(M):                               # step i of (hd, C)
                return jnp.sum(jnp.where(lane == i, M, 0.0), axis=1,
                               keepdims=True)
            r, k, w = col(R), col(K), col(W)          # (hd, 1)
            v = jnp.sum(jnp.where(row == i, V, 0.0), axis=0,
                        keepdims=True)                # (1, hd)
            kv = k * v                                # (hd_k, hd_v)
            y = jnp.sum((s + u * kv) * r, axis=0, keepdims=True)
            return w * s + kv, jnp.where(row == i, y, Y)

        s, Y = jax.lax.fori_loop(0, CHUNK, step,
                                 (s, jnp.zeros((CHUNK, hd), jnp.float32)))
        y_ref[0, 0, pl.ds(t0, CHUNK), :] = Y.astype(y_ref.dtype)
        return s

    s_last = jax.lax.fori_loop(0, Tp // CHUNK, chunk,
                               s0_ref[0, 0].astype(jnp.float32))
    s_out_ref[0, 0] = s_last.astype(s_out_ref.dtype)


def rwkv6_scan_pallas(r: jax.Array, k: jax.Array, v: jax.Array,
                      w: jax.Array, u: jax.Array, s0: jax.Array,
                      *, interpret: bool = False):
    """r/k/v/w: (B, T, nh, hd); u: (nh, hd); s0: (B, nh, hd, hd).

    Returns (y (B, T, nh, hd), s_last (B, nh, hd, hd)).
    """
    B, T, nh, hd = r.shape
    pad = (-T) % CHUNK
    Tp = T + pad

    def padded(a, fill):
        return jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)),
                       constant_values=fill) if pad else a

    # (B, nh, hd, Tp): time along lanes; v stays time along rows
    rT, kT, wT = (padded(a, fill).transpose(0, 2, 3, 1)
                  for a, fill in ((r, 0), (k, 0), (w, 1)))
    vh = padded(v, 0).transpose(0, 2, 1, 3)
    col_spec = pl.BlockSpec((1, 1, hd, Tp), lambda b, h: (b, h, 0, 0))
    row_spec = pl.BlockSpec((1, 1, Tp, hd), lambda b, h: (b, h, 0, 0))
    state_spec = pl.BlockSpec((1, 1, hd, hd), lambda b, h: (b, h, 0, 0))
    y, s_last = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct((B, nh, Tp, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, nh, hd, hd), jnp.float32)),
        grid=(B, nh),
        in_specs=[col_spec, col_spec, row_spec, col_spec,
                  pl.BlockSpec((1, hd, 1), lambda b, h: (h, 0, 0)),
                  state_spec],
        out_specs=(row_spec, state_spec),
        # the time recurrence runs inside one grid step (fori over T);
        # (batch, head) grid steps are independent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(rT, kT, vh, wT, u.reshape(nh, hd, 1), s0)
    return y.transpose(0, 2, 1, 3)[:, :T], s_last
