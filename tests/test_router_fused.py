"""The fused routing megakernel vs the unfused routing chain.

``repro.kernels.router_fused.router_fused_pallas`` (and its pure-jnp
oracle ``ref.router_fused_ref``) fuse the per-hop routing prologue —
router GEMM, softmax, top-k, histogram and dispatch positions — into one
pass.  The contract is BIT-compatibility with the unfused chain the
executor otherwise runs (``core.moe.router_probs`` + ``topk_gates`` +
``ops.group_sort``):

* property tests over adversarial distributions — including DELIBERATE
  logit ties (duplicated expert columns, all-tied logits) and bf16 inputs,
  where an unpinned tie-break would silently diverge — assert the fused
  expert ids equal the unfused ``lax.top_k`` ids bit for bit, and gates /
  probs / logits / positions likewise;
* the kernel (interpret mode) and the oracle agree on every output across
  awkward token-tile splits;
* the ``ops.router_fused`` wrapper routes small inputs to the oracle and
  large ones to the kernel, both bit-identical.

Degenerate expert counts (E <= 2) are excluded from the property domain:
there the padded kernel GEMM and the unfused mat-vec associate the
contraction differently (1-ulp logit drift — measured, not hypothesized);
the wrapper's ``ROUTER_FUSED_MIN_EXPERTS`` gate pins those widths to the
oracle at any token count (asserted below), and ``ROUTER_FUSED_MIN_ROWS``
keeps tiny inputs on the oracle regardless.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import moe as M
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.router_fused import router_fused_pallas

# named adversarial input families, indexed by a drawn integer
_DISTRIBUTIONS = ("normal", "bf16", "dup_experts", "all_tied", "bf16_dup")


def _make_case(rng, dist: str, t: int, d: int, E: int):
    x = rng.standard_normal((t, d)).astype(np.float32)
    w = rng.standard_normal((d, E)).astype(np.float32)
    if dist in ("dup_experts", "bf16_dup"):
        # duplicated expert columns: EXACT logit ties between expert pairs,
        # the case where an unpinned tie-break order silently diverges
        w[:, 1::2] = w[:, 0::2][:, :E // 2]
    if dist == "all_tied":
        x[:] = 0.0                       # every logit 0: the full-tie storm
    if dist in ("bf16", "bf16_dup"):
        return jnp.asarray(x, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16)
    return jnp.asarray(x), jnp.asarray(w)


def _check_against_unfused(x, w, k, renorm, outs):
    """Assert one impl's 6-tuple against the unfused chain, bit for bit."""
    gates, idx, probs, logits, ranks, starts = outs
    probs_u, logits_u = M.router_probs(x, w)
    gates_u, idx_u = M.topk_gates(probs_u, k, renorm)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_u))
    np.testing.assert_array_equal(np.asarray(gates), np.asarray(gates_u))
    np.testing.assert_array_equal(np.asarray(probs), np.asarray(probs_u))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_u))
    r_u, s_u = ref.group_sort_ref(jnp.asarray(idx_u).reshape(-1), w.shape[1])
    np.testing.assert_array_equal(np.asarray(ranks), np.asarray(r_u))
    np.testing.assert_array_equal(np.asarray(starts), np.asarray(s_u))


@settings(deadline=None, max_examples=25)
@given(t=st.integers(1, 300), d=st.integers(4, 96), e=st.integers(4, 64),
       k=st.integers(1, 4), dist_i=st.integers(0, len(_DISTRIBUTIONS) - 1),
       block_i=st.integers(0, 2), renorm_i=st.integers(0, 1),
       seed=st.integers(0, 2**31 - 1))
def test_router_fused_property(t, d, e, k, dist_i, block_i, renorm_i, seed):
    """Kernel == oracle == unfused chain, bit for bit, on adversarial
    distributions (deliberate ties, bf16) and awkward tile splits."""
    k = min(k, e)
    renorm = bool(renorm_i)
    rng = np.random.default_rng(seed)
    x, w = _make_case(rng, _DISTRIBUTIONS[dist_i], t, d, e)
    block = (8, 32, 128)[block_i]               # incl. many-tile splits
    out_k = router_fused_pallas(x, w, k, renorm=renorm, block=block,
                                interpret=True)
    out_r = ref.router_fused_ref(x, w, k, renorm=renorm)
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _check_against_unfused(x, w, k, renorm, out_k)


def test_router_fused_deliberate_bf16_full_tie():
    """The headline tie case pinned explicitly (not just drawn): bf16
    inputs, every logit identical, k = 3 — the fused ids must be exactly
    the first k expert indices per token (lowest-index tie-break), equal
    to ``lax.top_k``'s order bit for bit."""
    t, d, E, k = 96, 16, 12, 3
    x = jnp.zeros((t, d), jnp.bfloat16)
    w = jnp.asarray(np.random.default_rng(0).standard_normal((d, E)),
                    jnp.bfloat16)
    out = router_fused_pallas(x, w, k, renorm=True, block=32, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out[1]), np.tile(np.arange(k, dtype=np.int32), (t, 1)))
    _check_against_unfused(x, w, k, True, out)
    for a, b in zip(out, ref.router_fused_ref(x, w, k, renorm=True)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("t,e,k", [
    (1, 4, 1),       # single token
    (128, 8, 8),     # k == E: full selection, ids a permutation per token
    (256, 16, 2),    # exact tile multiple
    (257, 16, 2),    # one past a tile boundary
    (48, 130, 4),    # E past one lane width (domain padding)
])
def test_router_fused_edge_shapes(t, e, k):
    rng = np.random.default_rng(t * 31 + e + k)
    x, w = _make_case(rng, "normal", t, 16, e)
    out_k = router_fused_pallas(x, w, k, block=128, interpret=True)
    out_r = ref.router_fused_ref(x, w, k)
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _check_against_unfused(x, w, k, False, out_k)
    if k == e:
        idx = np.sort(np.asarray(out_k[1]), axis=1)
        np.testing.assert_array_equal(idx, np.tile(np.arange(e), (t, 1)))


def test_router_fused_empty_and_invalid():
    x = jnp.zeros((0, 8), jnp.float32)
    w = jnp.zeros((8, 4), jnp.float32)
    gates, idx, probs, logits, ranks, starts = router_fused_pallas(
        x, w, 2, interpret=True)
    assert gates.shape == (0, 2) and probs.shape == (0, 4)
    assert ranks.shape == (0,)
    np.testing.assert_array_equal(np.asarray(starts), np.zeros(5, np.int32))
    for fn in (lambda: router_fused_pallas(jnp.zeros((4, 8)), w, 0,
                                           interpret=True),
               lambda: router_fused_pallas(jnp.zeros((4, 8)), w, 5,
                                           interpret=True),
               lambda: ref.router_fused_ref(jnp.zeros((4, 8)), w, 0),
               lambda: ref.router_fused_ref(jnp.zeros((4, 8)), w, 5)):
        with pytest.raises(ValueError, match="top-k"):
            fn()


def test_ops_wrapper_threshold_switch(monkeypatch):
    """ops.router_fused: the oracle below ROUTER_FUSED_MIN_ROWS, the Pallas
    kernel at/above it (forced via the override) — bit-identical routes."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    small = kops.router_fused(x, w, 2, renorm=True)      # oracle route
    monkeypatch.setattr(kops, "ROUTER_FUSED_MIN_ROWS", 0)
    forced = kops.router_fused(x, w, 2, renorm=True)     # kernel route
    for a, b in zip(small, forced):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ops_wrapper_degenerate_experts_stay_on_oracle(monkeypatch):
    """E <= 2 routes to the oracle even above ROUTER_FUSED_MIN_ROWS (the
    padded kernel GEMM has measured 1-ulp logit drift there — module
    docstring), preserving the bit-compat contract for e.g. SMILE
    inter-node routing on a 2-node mesh.  E = ROUTER_FUSED_MIN_EXPERTS is
    the first kernel-eligible width."""
    monkeypatch.setattr(kops, "ROUTER_FUSED_MIN_ROWS", 0)
    monkeypatch.setattr(kops, "router_fused_pallas",
                        lambda *a, **kw: pytest.fail(
                            "kernel must not run for E <= 2"))
    rng = np.random.default_rng(11)
    for e, k in [(1, 1), (2, 1), (2, 2)]:
        x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((16, e)), jnp.float32)
        out = kops.router_fused(x, w, k, renorm=True)    # oracle route
        _check_against_unfused(x, w, k, True, out)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(
        (16, kops.ROUTER_FUSED_MIN_EXPERTS)), jnp.float32)
    with pytest.raises(pytest.fail.Exception, match="must not run"):
        kops.router_fused(x, w, 2)                       # kernel route


def test_router_fused_gradients_match_unfused(monkeypatch):
    """Router-weight gradients through the fused route (custom_vjp backward
    = the oracle chain's VJP) match the unfused chain — including under
    ``jax.checkpoint``, the combination that (a) has no Pallas autodiff
    rule and (b) materializes float0 tangents on the integer outputs,
    which the combine path's ``group_ids * cap`` multiply then rejects.
    The loss consumes gates/probs/logits AND multiplies the int ids."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((256, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    monkeypatch.setattr(kops, "ROUTER_FUSED_MIN_ROWS", 0)    # kernel route

    def fused_loss(ww):
        gates, idx, probs, logits, _r, _s = kops.router_fused(
            x, ww, 2, renorm=True)
        src = idx.astype(jnp.int32) * 4 + 1      # the float0-tangent trap
        return (gates * (src >= 0)).sum() + (probs * logits).mean()

    def unfused_loss(ww):
        probs, logits = M.router_probs(x, ww)
        gates, idx = M.topk_gates(probs, 2, True)
        src = idx.astype(jnp.int32) * 4 + 1
        return (gates * (src >= 0)).sum() + (probs * logits).mean()

    g_f = jax.grad(fused_loss)(w)
    g_u = jax.grad(unfused_loss)(w)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_u),
                               rtol=1e-6, atol=1e-7)
    g_r = jax.grad(jax.checkpoint(fused_loss))(w)
    np.testing.assert_allclose(np.asarray(g_r), np.asarray(g_f),
                               rtol=1e-6, atol=1e-7)


def test_router_fused_large_jitted():
    """A dispatch-sized jitted call through the wrapper's real kernel path
    (t >= ROUTER_FUSED_MIN_ROWS), against the oracle."""
    rng = np.random.default_rng(3)
    t = max(kops.ROUTER_FUSED_MIN_ROWS, 1024)
    x = jnp.asarray(rng.standard_normal((t, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    fused = jax.jit(lambda a, b: kops.router_fused(a, b, 2, renorm=True))
    out_k = fused(x, w)
    out_r = ref.router_fused_ref(x, w, 2, renorm=True)
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
