"""MoE core invariants: routing, capacity, dispatch/combine, LB losses.

Includes hypothesis property tests on the dispatch machinery and the
paper's Eq. 4 minimum (loss_lb -> alpha + beta at uniform routing).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.common.config import MoEConfig
from repro.core import moe as M
from repro.core import pipeline as PL
from repro.core.layout import make_layout
from repro.sharding.plan import single_device_plan

PLAN = single_device_plan()


# ---------------------------------------------------------------- layout
def test_layout_exact():
    l = make_layout(256, 16, 16)
    assert l.h == 1 and l.r == 1 and l.shard_intra


def test_layout_replicated():
    l = make_layout(128, 16, 16)
    assert l.r == 2 and l.h == 1 and not l.shard_intra
    assert l.experts_per_node == 8


def test_layout_multi_expert_slot():
    l = make_layout(64, 4, 4)
    assert l.h == 4 and l.r == 1


def test_layout_invalid():
    with pytest.raises(ValueError):
        make_layout(100, 16, 16)   # 100 not divisible by 16


# ------------------------------------------------------- dispatch invariants
@settings(deadline=None, max_examples=25)
@given(t=st.integers(4, 64), groups=st.integers(1, 8),
       cap=st.integers(1, 16), seed=st.integers(0, 2**31 - 1))
def test_positions_under_capacity(t, groups, cap, seed):
    rng = np.random.default_rng(seed)
    gids = jnp.asarray(rng.integers(0, groups, t))
    pos, keep = M.positions_in_group(gids, jnp.ones(t, bool), groups, cap)
    pos, keep, gids = map(np.asarray, (pos, keep, gids))
    # kept slots are unique per group and < capacity
    for g in range(groups):
        sel = keep & (gids == g)
        assert (pos[sel] < cap).all()
        assert len(np.unique(pos[sel])) == sel.sum()
    # arrival-order drop semantics: within a group the first `cap` survive
    for g in range(groups):
        idx = np.where(gids == g)[0]
        assert keep[idx[:cap]].all()
        assert not keep[idx[cap:]].any()


@settings(deadline=None, max_examples=20)
@given(t=st.integers(4, 32), groups=st.integers(1, 4),
       cap=st.integers(4, 8), d=st.integers(4, 16),
       seed=st.integers(0, 2**31 - 1))
def test_scatter_gather_roundtrip(t, groups, cap, d, seed):
    """With ample capacity, combine(dispatch(x)) with gate 1 returns x."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    gids = jnp.asarray(rng.integers(0, groups, t))
    cap = max(cap, t)                                 # no drops
    pos, keep = M.positions_in_group(gids, jnp.ones(t, bool), groups, cap)
    buf = M.dispatch_scatter(x, gids, pos, keep, groups, cap)
    y = M.combine_gather(buf, gids, pos, keep, jnp.ones(t), t, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6)


def test_token_conservation():
    """Every surviving token appears in the buffer exactly once."""
    t, groups, cap, d = 32, 4, 4, 8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    gids = jnp.asarray(rng.integers(0, groups, t))
    pos, keep = M.positions_in_group(gids, jnp.ones(t, bool), groups, cap)
    buf = M.dispatch_scatter(x, gids, pos, keep, groups, cap)
    # sum of buffer equals sum of kept tokens
    kept_sum = np.asarray((x * np.asarray(keep)[:, None]).sum(0))
    np.testing.assert_allclose(np.asarray(buf.sum((0, 1))), kept_sum,
                               rtol=1e-5)


# ------------------------------------------------------------- LB losses
def test_lb_loss_minimum_uniform():
    """Paper: min loss_lb = alpha + beta at uniform routing (Eq. 4)."""
    n = 8
    f = jnp.full((n,), 1.0 / n)
    p = jnp.full((n,), 1.0 / n)
    assert abs(float(M.scaled_lb_loss(f, p, 0.005)) - 0.005) < 1e-7


def test_lb_loss_penalizes_imbalance():
    n = 8
    f = jnp.zeros((n,)).at[0].set(1.0)
    p = jnp.zeros((n,)).at[0].set(1.0)
    skew = float(M.scaled_lb_loss(f, p, 0.005))
    assert skew > 0.005 * (n - 1)


# --------------------------------------------------- full layers (oracle)
@pytest.mark.parametrize("router", ["switch", "smile"])
@pytest.mark.parametrize("grid,E,k,g", [
    ((4, 4), 16, 1, 1),      # one expert per slot, top-1 (the paper)
    ((4, 4), 8, 2, 1),       # replication r=2
    ((4, 4), 32, 8, 4),      # h=2 experts per slot, bi-level top-(4x2)
    ((2, 2), 4, 4, 2),
])
def test_moe_layer_shapes_and_finiteness(router, grid, E, k, g, rng_key):
    cfg = MoEConfig(num_experts=E, top_k=k, top_g=g, d_ff_expert=64,
                    capacity_factor=8.0, router=router, grid=grid,
                    renorm_gates=(k > 1))
    params = M.init_moe_params(rng_key, cfg, 32, PLAN, glu=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 32))
    y, stats = M.moe_layer(params, x, cfg, PLAN, act="silu")
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(stats.drop_frac) < 0.5


@pytest.mark.parametrize("router", ["switch", "smile"])
@pytest.mark.parametrize("grid", [(2, 2), (4, 4)])
def test_unmerged_expert_groups_match_merged(router, grid, rng_key,
                                             monkeypatch):
    """On one device the local expert leaf is (b_n, E_local, d, f) with
    b_n = grid[0] > 1, and the padded FFN batches over both group
    dimensions; outputs and expert-weight gradients equal those of the
    same layer with the leaf merged to (b_n * E_local, d, f)."""
    n, m = grid
    cfg = MoEConfig(num_experts=2 * n * m, top_k=1, d_ff_expert=64,
                    capacity_factor=2.0, router=router, grid=grid)
    params = M.init_moe_params(rng_key, cfg, 32, PLAN, glu=True)
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 32))
    cot = jax.random.normal(jax.random.PRNGKey(2), (96, 32))
    seen = []
    select = M._my_expert_weights

    def run(merge):
        def selected(*a):
            wsel, n_groups = select(*a)
            seen.append(wsel["w1"].ndim)
            return (PL.merge_groups(wsel) if merge else wsel), n_groups

        monkeypatch.setattr(M, "_my_expert_weights", selected)

        def loss(experts):
            y, _ = M.moe_layer(dict(params, experts=experts), x, cfg, PLAN,
                               act="silu")
            return jnp.sum(y * cot), y
        return jax.grad(loss, has_aux=True)(params["experts"])

    g4, y4 = run(merge=False)
    g3, y3 = run(merge=True)
    assert set(seen) == {4}                 # the unmerged leaf was passed on
    np.testing.assert_allclose(y4, y3, rtol=1e-6, atol=1e-6)
    for k in ("w1", "w2", "w3"):
        np.testing.assert_allclose(g4[k], g3[k], rtol=1e-6, atol=1e-6,
                                   err_msg=k)


@pytest.mark.parametrize("router", ["switch", "smile"])
def test_capacity_drops_under_tiny_capacity(router, rng_key):
    cfg = MoEConfig(num_experts=4, top_k=1, d_ff_expert=32,
                    capacity_factor=0.25, router=router, grid=(2, 2))
    params = M.init_moe_params(rng_key, cfg, 16, PLAN)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    y, stats = M.moe_layer(params, x, cfg, PLAN, act="gelu")
    assert float(stats.drop_frac) > 0.0          # must drop something
    # dropped tokens produce zero rows (residual passthrough upstream)
    assert np.isfinite(np.asarray(y)).all()


def test_smile_router_param_reduction():
    """Paper §3.2.1: router params O(mn) -> O(m+n)."""
    d, n, m = 64, 8, 8
    cfg_s = MoEConfig(num_experts=n * m, top_k=1, d_ff_expert=16,
                      router="smile", grid=(n, m))
    cfg_o = MoEConfig(num_experts=n * m, top_k=1, d_ff_expert=16,
                      router="switch", grid=(n, m))
    key = jax.random.PRNGKey(0)
    p_s = M.init_moe_params(key, cfg_s, d, PLAN)
    p_o = M.init_moe_params(key, cfg_o, d, PLAN)
    n_smile = p_s["router_inter"]["w"].size + p_s["router_intra"]["w"].size
    n_switch = p_o["router"]["w"].size
    assert n_smile == d * (n + m)
    assert n_switch == d * n * m
    assert n_smile < n_switch


def test_smile_equals_switch_experts_param_count(rng_key):
    """Expert storage is identical across routers (only routing differs)."""
    cfg_s = MoEConfig(num_experts=16, top_k=1, d_ff_expert=32,
                      router="smile", grid=(4, 4))
    cfg_o = MoEConfig(num_experts=16, top_k=1, d_ff_expert=32,
                      router="switch", grid=(4, 4))
    p_s = M.init_moe_params(rng_key, cfg_s, 32, PLAN)
    p_o = M.init_moe_params(rng_key, cfg_o, 32, PLAN)
    assert p_s["experts"]["w1"].shape == p_o["experts"]["w1"].shape
