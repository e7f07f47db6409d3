"""Jitted public wrappers for the Pallas kernels.

On CPU (this offline container) every kernel runs in ``interpret=True`` mode
— the kernel body executes exactly as written, validating the Pallas code
against the :mod:`repro.kernels.ref` oracles; on TPU the same calls compile
to Mosaic.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attn import flash_attention_pallas
from repro.kernels.grouped_ffn import (grouped_ffn_pallas,
                                       grouped_ffn_ragged_pallas)
from repro.kernels.moe_dispatch import (combine_gather_pallas,
                                        dispatch_gather_pallas)
from repro.kernels.radix_sort import group_sort_pallas
from repro.kernels.router_fused import router_fused_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas
from repro.kernels.ssd_chunk import ssd_chunk_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# the two stable group-sort implementations behind MoEConfig.sort_impl
SORT_IMPLS = ("radix", "argsort")
# below this many rows the O(A log A) vs O(A) gap is noise and the
# kernel-launch (or CPU interpret) overhead dominates: route to the
# argsort oracle, exactly as the other wrappers route tiny shapes to ref.
# Module-level so tests can force the kernel on small inputs.
RADIX_MIN_ROWS = 1024


def group_sort(keys, num_keys: int, *, impl: str = "argsort"):
    """Stable sort of small-domain int32 keys — the primitive under every
    dispatch hop's group sort.  Returns ``(ranks, starts)``: each element's
    stable sorted position, and the (num_keys + 1,) exclusive prefix counts
    (``starts[d]`` = #keys < d; ``starts[num_keys]`` = A).

    ``impl="radix"`` runs the one-pass Pallas counting sort
    (:mod:`repro.kernels.radix_sort`; interpret mode off-TPU) for inputs of
    at least ``RADIX_MIN_ROWS`` rows; ``"argsort"`` — and every small input
    — runs the packed single-operand ``lax.sort`` oracle.  Both are exact
    stable integer sorts, so the outputs are bit-identical.
    """
    if impl not in SORT_IMPLS:
        raise ValueError(f"unknown sort_impl {impl!r}; "
                         f"expected one of {SORT_IMPLS}")
    if impl == "radix" and keys.shape[0] >= RADIX_MIN_ROWS:
        return group_sort_pallas(keys, num_keys, interpret=_interpret())
    return ref.group_sort_ref(keys, num_keys)


# the two routing-stage implementations behind MoEConfig.router_impl
ROUTER_IMPLS = ("unfused", "fused")
# below this many tokens the kernel-launch (or CPU interpret) overhead
# dominates the fused win: route to the pure-jnp oracle, exactly as
# group_sort routes tiny inputs to argsort.  Module-level so tests can
# force the kernel on small inputs.
ROUTER_FUSED_MIN_ROWS = 1024
# degenerate expert counts stay on the oracle regardless of token count:
# at E <= 2 the padded kernel GEMM and the unfused mat-vec associate the
# contraction differently (1-ulp logit drift — measured, see
# tests/test_router_fused.py), which would silently break the documented
# bit-compatibility contract (e.g. SMILE inter-node routing on a 2-node
# mesh clears ROUTER_FUSED_MIN_ROWS easily).
ROUTER_FUSED_MIN_EXPERTS = 3


def _router_fused_impl(x, w, k, renorm):
    if (x.shape[0] >= ROUTER_FUSED_MIN_ROWS
            and w.shape[1] >= ROUTER_FUSED_MIN_EXPERTS):
        return router_fused_pallas(x, w, k, renorm=renorm,
                                   interpret=_interpret())
    return ref.router_fused_ref(x, w, k, renorm=renorm)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _router_fused(x, w, k, renorm):
    return _router_fused_impl(x, w, k, renorm)


def _router_fused_fwd(x, w, k, renorm):
    return _router_fused_impl(x, w, k, renorm), (x, w)


def _router_fused_bwd(k, renorm, res, cts):
    # Backward = the VJP of the pure-jnp oracle, which is bit-identical to
    # the kernel forward, so the gradients are exact; the integer outputs
    # (ids, ranks, starts) carry no cotangents.  This also keeps autodiff
    # out of the Pallas body — the histogram/top-k kernel is a forward-only
    # fusion, like the unfused chain's sort it replaces.
    x, w = res
    ct_gates, _ct_idx, ct_probs, ct_logits, _ct_ranks, _ct_starts = cts

    def _float_outs(xx, ww):
        gates, _i, probs, logits, _r, _s = ref.router_fused_ref(
            xx, ww, k, renorm=renorm)
        return gates, probs, logits

    _, vjp = jax.vjp(_float_outs, x, w)
    return vjp((ct_gates, ct_probs, ct_logits))


_router_fused.defvjp(_router_fused_fwd, _router_fused_bwd)


def router_fused(x, w, k, *, renorm: bool = False):
    """Fused routing prologue — router GEMM, softmax, top-k, histogram and
    dispatch positions in one pass (:mod:`repro.kernels.router_fused`;
    interpret mode off-TPU) for inputs of at least ``ROUTER_FUSED_MIN_ROWS``
    tokens and ``ROUTER_FUSED_MIN_EXPERTS`` experts; smaller inputs — and
    degenerate E <= 2 routers, where the padded kernel GEMM drifts 1 ulp
    from the unfused mat-vec — run the bit-identical pure-jnp oracle.  Under
    autodiff the backward pass is the oracle chain's VJP (custom_vjp), so
    the router-weight gradient is exact on both routes.

    Returns ``(gates (t,k), idx (t,k), probs (t,E), logits (t,E),
    ranks (t*k,), starts (E+1,))`` — the loss inputs bit-compatible with
    the unfused ``router_probs``/``topk_gates`` chain, the positions with
    ``group_sort`` over the chosen ids (per-expert counts are
    ``starts[1:] - starts[:-1]``).
    """
    E = w.shape[-1]
    if not 1 <= k <= E:
        raise ValueError(f"top-k k={k} out of range for {E} experts")
    gates, idx, probs, logits, ranks, starts = _router_fused(
        x, w, int(k), bool(renorm))
    # The integer outputs are routing decisions, not differentiable values.
    # Under remat, custom_vjp instantiates their tangents as concrete float0
    # arrays, which blow up in any downstream multiply (e.g. the combine
    # path's group_ids * cap); stop_gradient drops a tangent of any dtype,
    # restoring the symbolic zeros the unfused chain's sort outputs carry.
    stop = jax.lax.stop_gradient
    return gates, stop(idx), probs, logits, stop(ranks), stop(starts)


def _kernel_fwd_oracle_bwd(kernel, oracle):
    """``kernel`` with the VJP of its ``ref`` twin as its backward pass.

    Pallas calls have no transpose rule, and those with scalar-prefetched
    operands have no JVP rule either, so ``jax.grad`` cannot pass through
    them.  The twin computes the same function in jnp, so its VJP is the
    gradient of the kernel's forward; integer operands get float0
    cotangents from ``jax.vjp`` as usual.
    """
    f = jax.custom_vjp(kernel)
    f.defvjp(lambda *args: (kernel(*args), args),
             lambda args, ct: jax.vjp(oracle, *args)[1](ct))
    return f


def grouped_ffn(x, w1, w3, w2, *, act: str = "gelu"):
    """Grouped expert FFN; falls back to the jnp oracle for tiny shapes
    (interpret-mode overhead dominates below one MXU tile).  Differentiable:
    the backward pass is the oracle's VJP."""
    G, T, d = x.shape
    if T < 16 or d % 8:
        return ref.grouped_ffn_ref(x, w1, w3, w2, act=act)
    f = _kernel_fwd_oracle_bwd(
        partial(grouped_ffn_pallas, act=act, interpret=_interpret()),
        partial(ref.grouped_ffn_ref, act=act))
    return f(x, w1.astype(x.dtype),
             None if w3 is None else w3.astype(x.dtype), w2.astype(x.dtype))


def grouped_ffn_ragged(rows, group_starts, w1, w3, w2, *, block: int,
                       act: str = "gelu"):
    """Ragged grouped FFN over the dropless tile-aligned layout.
    rows: (R, d) with R a multiple of ``block``; group_starts: (G+1,)
    aligned segment offsets.  Falls back to the jnp oracle for
    tiny/misaligned shapes."""
    from repro.core.dispatch import ragged_tile_gids
    R, d = rows.shape
    if R == 0 or R < 16 or d % 8 or block < 8:
        return ref.grouped_ffn_ragged_ref(rows, group_starts, w1, w3, w2,
                                          act=act)
    tile_gid = ragged_tile_gids(group_starts, R // block, block)
    return grouped_ffn_ragged_pallas(rows, tile_gid, w1.astype(rows.dtype),
                                     None if w3 is None else w3.astype(rows.dtype),
                                     w2.astype(rows.dtype), act=act,
                                     interpret=_interpret())


def dispatch_gather(x, src):
    """MoE dispatch: gather token rows into the flat capacity buffer.
    Falls back to the jnp oracle for tiny shapes (interpret-mode / grid
    overhead dominates below a few VPU rows)."""
    T, d = x.shape
    R = src.shape[0]
    if T == 0:
        return jnp.zeros((R, d), x.dtype)
    if R < 16 or d % 8:
        return ref.dispatch_gather_ref(x, src)
    f = _kernel_fwd_oracle_bwd(
        partial(dispatch_gather_pallas, interpret=_interpret()),
        ref.dispatch_gather_ref)
    return f(x, src.astype(jnp.int32))


def combine_gather(rows, src, scale):
    """MoE combine: gate-weighted gather-reduce of expert outputs back to
    token order. rows: (R, d); src/scale: (t, k)."""
    t, k = src.shape
    d = rows.shape[-1]
    if rows.shape[0] == 0 or t == 0:
        return jnp.zeros((t, d), rows.dtype)
    if t < 16 or d % 8:
        return ref.combine_gather_ref(rows, src, scale)
    f = _kernel_fwd_oracle_bwd(
        partial(combine_gather_pallas, interpret=_interpret()),
        ref.combine_gather_ref)
    return f(rows, src.astype(jnp.int32), scale.astype(jnp.float32))


def flash_attention(q, k, v):
    """Causal attention with GQA expansion. q: (B,T,H,hd); k/v: (B,T,KV,hd)."""
    H, KV = q.shape[2], k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return flash_attention_pallas(q, k, v, interpret=_interpret())


def rwkv6_scan(r, k, v, w, u, s0):
    return rwkv6_scan_pallas(r, k, v, w, u, s0, interpret=_interpret())


def ssd_chunk(xh, dt, loga, Bc, Cc):
    """Mamba2 SSD intra-chunk terms (see kernels/ssd_chunk.py)."""
    return ssd_chunk_pallas(xh, dt, loga, Bc, Cc, interpret=_interpret())
