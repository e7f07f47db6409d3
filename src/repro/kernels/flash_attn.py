"""Pallas TPU kernel: causal flash attention (streaming softmax).

Backbone attention hot spot for the dense/train paths. Grid
``(B, H, Tq/bq)``; each step owns a ``(bq, hd)`` query tile and loops over KV
tiles up to the causal frontier with an online-softmax accumulator held in
VMEM. ``bq = bk = 128`` aligns both MXU contractions ((bq,hd)x(hd,bk) and
(bq,bk)x(bk,hd)); the working set per step is
``bq*hd + 2*bk*hd + bq*bk + bq*hd`` ~ 0.6 MB at hd=128 — far under VMEM,
leaving room for the compiler to double-buffer the KV stream from HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, scale: float):
    qi = pl.program_id(2)
    q = q_ref[0, 0] * scale                         # (bq, hd)
    T = k_ref.shape[1]
    hd = q.shape[-1]

    m = jnp.full((bq,), -1e30, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, hd), jnp.float32)
    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.dslice(j * bk, bk), :]               # (bk, hd)
        v = v_ref[0, 0, pl.dslice(j * bk, bk), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)      # (bq, bk)
        k_pos = j * bk + jax.lax.iota(jnp.int32, bk)
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # causal: only KV tiles at or before this query tile
    n_kv = qi + 1
    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           *, block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """Causal attention. q/k/v: (B, T, H, hd) (same H — GQA pre-expanded).

    Returns (B, T, H, hd).
    """
    B, T, H, hd = q.shape
    bq = min(block_q, T)
    bk = min(block_k, T)
    assert T % bq == 0 and T % bk == 0, (T, bq, bk)
    scale = 1.0 / math.sqrt(hd)
    # layout: (B, H, T, hd) so the head is a grid dim
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, H, T // bq)

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, T, hd), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, hd), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
        # online-softmax state lives in kernel-local accumulators within one
        # grid step; no output or scratch crosses grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
