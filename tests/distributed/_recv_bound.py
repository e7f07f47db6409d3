"""Subprocess test: the ragged receive-bound factor (HopSpec.recv_bound_factor).

On an 8-fake-device (4 x 2) mesh, asserts the full contract of the bounded
ragged hop implemented once at the pipeline level:

* PRIMITIVE (pipeline._ragged_forward/_ragged_reverse under all-to-one-rank
  skew): the receive slab is statically bounded at ``recv_bound_rows`` (far
  below the worst-case ``P x R``), the receiver's clamped per-source counts
  are echoed back on the reverse path (sender-observed return counts ==
  transpose of receiver-kept counts), returned rows land at their original
  layout offsets with clamp-dropped rows zero-filled, and the survived mask
  matches the echoed counts exactly.

* LAYER (switch + SMILE through the shared executor, zero per-caller code):
  under adversarial all-tokens-to-one-rank routing, every output row is
  either (numerically) identical to the unbounded run's row or exactly
  zero (clamp-dropped), and the reported ``drop_frac`` equals the zero-row
  fraction exactly (k=1: assignments == tokens).

* NO-CLAMP EQUIVALENCE: ``factor`` large enough that nothing clamps is
  BIT-identical to ``factor=None`` — switch and smile, uniform routing —
  with ``drop_frac`` exactly 0.0 (the clamp machinery degenerates to the
  zero-drop path).

Exits non-zero on any mismatch.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.config import MoEConfig
from repro.core import dispatch as D
from repro.core import pipeline as PL
from repro.core.moe import init_moe_params, moe_layer
from repro.sharding.plan import test_plan

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = test_plan(n_inter=4, n_intra=2)
P_ = 8                                     # joint ranks over (data, model)
d = 16


# =============================================================================
# Part 1: primitive-level skew — bounded slab, echoed counts, origin offsets
# =============================================================================

def primitive_skew():
    nl = 2                                 # local groups per rank
    V = P_ * nl
    t_local = 64
    factor = 1.5
    x = jax.random.normal(jax.random.PRNGKey(0), (P_ * t_local, d))

    def f(xx):
        t = xx.shape[0]
        # adversarial: every token targets rank 0 (alternating its 2 groups)
        gid = (jnp.arange(t, dtype=jnp.int32) % nl)
        rows, starts, st = D.dispatch_ragged(xx, gid, jnp.ones((t,)), V, k=1)
        seg_lens = D.ragged_seg_lens(gid, st.keep, V)
        spec = PL.HopSpec(name="t", axes=plan.ep_axes, n_ranks=P_,
                          num_groups=V, exchange="ragged",
                          recv_bound_factor=factor)
        hs, ev, _ = PL._ragged_forward(rows, starts, seg_lens, spec, st.cap)
        # marker transform so reverse provenance is checkable
        y_slab = hs.recv * 2.0
        back, ok, _ = PL._ragged_reverse(y_slab, hs, spec)
        nz = (jnp.abs(back).sum(-1) > 0)
        return (back[None], ok[None], hs.kept[None], hs.recv_counts[None],
                rows[None], nz[None], st.pos[None],
                jnp.int32(hs.recv.shape[0])[None],
                jnp.int32(rows.shape[0])[None], jnp.int32(st.cap)[None],
                ev[None])

    fm = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P(("data", "model"), None),
        out_specs=tuple(P(("data", "model")) for _ in range(11)),
        check_vma=False))
    (back, ok, kept, rc, rows, nz, pos, b_rows, r_rows, blocks, ev) = map(
        np.asarray, fm(x))
    # the sanitizer must treat these (healthy, merely skewed) grids as clean
    assert not ev.any(), ev
    B, R, block = int(b_rows[0]), int(r_rows[0]), int(blocks[0])

    # static slab bound honored, and genuinely below the worst case
    assert B == PL.recv_bound_rows(1.5, R, P_, nl, block), (B, R, block)
    assert B < P_ * R, (B, P_ * R)

    # receiver-side clamp: kept counts are the prefix-clipped rc
    for r in range(P_):
        roff = np.concatenate([[0], np.cumsum(rc[r])])[:-1]
        np.testing.assert_array_equal(kept[r],
                                      np.clip(B - roff, 0, rc[r]))
    # only rank 0 receives anything (all tokens target its groups)
    assert rc[1:].sum() == 0 and kept[1:].sum() == 0
    assert kept[0].sum() == B                      # clamped slab exactly full

    # echo: sender q's surviving-row count toward receiver r == kept[r][q]
    for q in range(P_):
        srv = ok[q]
        # q's layout is rank-major: segment for rank r at send offsets
        sc = np.array([0] * P_)
        # recompute send_counts from the local layout: all rows go to rank 0
        sc[0] = R
        off = np.concatenate([[0], np.cumsum(sc)])[:-1]
        for r in range(P_):
            got_back = srv[off[r]:off[r] + sc[r]].sum()
            assert got_back == kept[r][q], (q, r, got_back, kept[r][q])

    # returned rows at origin offsets: back == 2 * rows where ok, else 0
    for q in range(P_):
        np.testing.assert_allclose(back[q][ok[q]], 2.0 * rows[q][ok[q]],
                                   rtol=0, atol=0)
        assert not np.abs(back[q][~ok[q]]).any()
    print(f"OK primitive skew: slab {B} rows vs worst-case {P_ * R} "
          f"({P_ * R / B:.1f}x smaller), echo verified")


# =============================================================================
# Part 2: full layers under skew — drop accounting through the executor
# =============================================================================

def run_layer(cfg, params, x):
    n_g, m_g = cfg.grid
    espec = P("data", "model", None, None)
    pspecs = {"experts": {"w1": espec, "w2": espec}}
    if cfg.router == "smile":
        pspecs["router_inter"] = {"w": P(None, None)}
        pspecs["router_intra"] = {"w": P(None, None)}
    else:
        pspecs["router"] = {"w": P(None, None)}

    def f(params, x):
        y, st = moe_layer(params, x, cfg, plan, act="gelu")
        return y, st.drop_frac, st.hop_drop_frac

    fsm = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(pspecs, P(("data", "model"), None)),
        out_specs=(P(("data", "model"), None), P(), P()), check_vma=False))
    y, df, hdf = fsm(params, x)
    return np.asarray(y), float(df), np.asarray(hdf)


def layer_skew(router):
    cfg = MoEConfig(num_experts=16, top_k=1, top_g=1, d_ff_expert=32,
                    router=router, grid=(4, 2), dispatch_backend="dropless",
                    ragged_a2a=True)
    params = init_moe_params(jax.random.PRNGKey(0), cfg, d, plan, glu=False)
    # adversarial router: all-positive tokens + a one-column router weight
    # make EVERY token pick expert/node 0 deterministically -> rank 0
    if router == "smile":
        w = params["router_inter"]["w"]
        params["router_inter"]["w"] = jnp.zeros_like(w).at[:, 0].set(8.0)
    else:
        w = params["router"]["w"]
        params["router"]["w"] = jnp.zeros_like(w).at[:, 0].set(8.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (8 * 64, d))) + 0.1

    y_u, df_u, _ = run_layer(cfg, params, x)              # unbounded
    assert df_u == 0.0
    # at these toy sizes the ragged layout carries ~2x tile-alignment
    # headroom (R >> A), so the bound needs a tighter factor on SMILE's
    # 4-rank level-1 hop than on switch's 8-rank flat hop to actually clamp
    factor = 1.5 if router == "switch" else 0.75
    cfg_b = dataclasses.replace(cfg, recv_bound_factor=factor)
    y_b, df_b, hdf_b = run_layer(cfg_b, params, x)

    assert df_b > 0.0, (router, df_b)
    assert np.isclose(df_b, hdf_b.sum()), (df_b, hdf_b)
    # every row: clamp-dropped (exact zero) or the unbounded row
    zero = ~np.abs(y_b).sum(-1).astype(bool)
    np.testing.assert_allclose(y_b[~zero], y_u[~zero], rtol=1e-5, atol=1e-6)
    assert np.abs(y_u[zero]).sum() > 0        # they weren't zero unbounded
    # k=1, top_g=1: dropped assignments == zero-rows, so drop_frac is the
    # exact zero-row fraction (switch: one hop; smile: levels compound but
    # a level-1 drop removes the token from level 2's valid set)
    if router == "switch":
        assert np.isclose(df_b, zero.mean()), (df_b, zero.mean())
    else:
        assert hdf_b[0] > 0.0                 # level 1 clamps under this skew
    print(f"OK layer skew [{router}]: drop_frac {df_b:.3f} "
          f"({int(zero.sum())}/{len(zero)} rows clamp-dropped)")


def layer_noclamp_bitidentical(router):
    cfg = MoEConfig(num_experts=16, top_k=2, top_g=2, d_ff_expert=32,
                    capacity_factor=8.0, router=router, grid=(4, 2),
                    renorm_gates=True, dispatch_backend="dropless",
                    ragged_a2a=True)
    params = init_moe_params(jax.random.PRNGKey(0), cfg, d, plan, glu=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (8 * 32, d))
    y_u, df_u, hdf_u = run_layer(cfg, params, x)
    # factor = P guarantees bound == worst case: the executor must detect
    # the non-reducing bound and take the exact factor=None path (no echo
    # exchange, native-op eligible) — bit-identical by construction
    cfg_b = dataclasses.replace(cfg, recv_bound_factor=float(P_))
    y_b, df_b, hdf_b = run_layer(cfg_b, params, x)
    np.testing.assert_array_equal(y_b, y_u)
    assert df_b == 0.0 and df_u == 0.0
    assert not hdf_b.any() and not hdf_u.any()
    print(f"OK no-clamp bit-identical [{router}]")


primitive_skew()
for router in ("switch", "smile"):
    layer_skew(router)
    layer_noclamp_bitidentical(router)
print("ALL RECV BOUND OK")
