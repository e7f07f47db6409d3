"""Pipeline refactor regression suite.

* **Golden equivalence**: the hop-pipeline switch/SMILE layers
  (``repro.core.pipeline.execute_pipeline``) reproduce the pre-refactor
  monolithic implementations BIT for BIT across the full ``dispatch_backend
  x ragged_a2a x sort_impl`` matrix, at ample capacity AND under
  starved-capacity drops.  The fixture (``tests/golden/moe_layer_golden.npz``,
  regenerate with ``tests/golden/gen_golden.py``) was captured from the PR-4
  tree; bit-level float reproducibility only holds within one (platform,
  jax version) pair, so the comparison degrades to tight allclose when the
  recorded environment differs from the running one.

* **Unified stats**: the executor's single accumulation path reports
  per-hop ``drop_frac`` (``MoEStats.hop_drop_frac``) consistently for both
  routers — the old switch/smile stat-shape asymmetry is pinned away.

* **Options registry**: ``MoEConfig.with_options`` validates against
  ``MOE_OPTIONS`` (the same registry the launchers derive their flags
  from), and the deprecated ``configs.with_dispatch_backend`` shim warns
  but still works.
"""
import dataclasses
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import (MOE_DRYRUN_OPTS, MOE_OPTION_FIELDS,
                                 MOE_OPTIONS, MoEConfig)
from repro.core import moe as M
from repro.core import pipeline as PL
from repro.sharding.plan import single_device_plan

PLAN = single_device_plan()
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "moe_layer_golden.npz")

BACKENDS = ("sort", "dense", "dropless")
RAGGED = (True, False)
SORT_IMPLS = ("argsort", "radix")
CASES = {"ample": 8.0, "starved": 1.0}
MATRIX = [(router, case, b, r, s)
          for router in ("switch", "smile") for case in CASES
          for b in BACKENDS for r in RAGGED for s in SORT_IMPLS]


def _layer_cfg(router, backend, ragged, sort_impl, cf):
    return MoEConfig(num_experts=16, top_k=2, top_g=2, d_ff_expert=32,
                     capacity_factor=cf, router=router, grid=(4, 4),
                     renorm_gates=True, dispatch_backend=backend,
                     ragged_a2a=ragged, sort_impl=sort_impl)


@pytest.fixture(scope="module")
def golden():
    return np.load(GOLDEN, allow_pickle=False)


@pytest.fixture(scope="module")
def golden_env(golden):
    ver, platform = (str(v) for v in golden["__meta__"])
    return ver == jax.__version__ and platform == jax.default_backend()


@pytest.fixture(scope="module")
def golden_params(golden):
    """The recorded layer parameters (``p|<router>|<leaf path>``): drawn
    once by ``gen_golden.py``, never redrawn here, since the PRNG's draws
    depend on the jax version's threefry setting."""
    params = {}
    for name in golden.files:
        if name.startswith("p|"):
            _, router, path = name.split("|")
            node = params.setdefault(router, {})
            *parents, leaf = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[leaf] = jnp.asarray(golden[name])
    return params, jnp.asarray(golden["x"])


@pytest.mark.parametrize("router,case,backend,ragged,sort_impl", MATRIX)
def test_golden_equivalence(router, case, backend, ragged, sort_impl,
                            golden, golden_env, golden_params):
    """Every matrix cell of the pipeline-built layer reproduces the
    pre-refactor monolith's output and stats — bit-identically when run in
    the fixture's recorded environment."""
    params, x = golden_params
    cfg = _layer_cfg(router, backend, ragged, sort_impl, CASES[case])
    y, st = M.moe_layer(params[router], x, cfg, PLAN, act="gelu")
    tag = f"{router}|{case}|{backend}|r{int(ragged)}|{sort_impl}"
    y_g, s_g = golden[f"y|{tag}"], golden[f"s|{tag}"]
    s = np.asarray([float(st.lb_loss), float(st.z_loss),
                    float(st.drop_frac)], np.float64)
    if golden_env:
        np.testing.assert_array_equal(np.asarray(y), y_g)
        np.testing.assert_array_equal(s, s_g)
    else:                   # cross-platform: compilation-order float drift
        np.testing.assert_allclose(np.asarray(y), y_g, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(s, s_g, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("router,case,backend,ragged,sort_impl", MATRIX)
def test_golden_equivalence_fused_router(router, case, backend, ragged,
                                         sort_impl, golden, golden_env,
                                         golden_params, monkeypatch):
    """Every golden cell again under ``router_impl="fused"`` (the real
    Pallas megakernel, forced): the fused routing prologue must reproduce
    the recorded pre-refactor outputs under the same per-environment
    policy as the unfused path — bit-identically in the fixture's recorded
    environment, tight allclose elsewhere."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "ROUTER_FUSED_MIN_ROWS", 0)
    params, x = golden_params
    cfg = _layer_cfg(router, backend, ragged, sort_impl, CASES[case]
                     ).with_options(router_impl="fused")
    y, st = M.moe_layer(params[router], x, cfg, PLAN, act="gelu")
    tag = f"{router}|{case}|{backend}|r{int(ragged)}|{sort_impl}"
    y_g, s_g = golden[f"y|{tag}"], golden[f"s|{tag}"]
    s = np.asarray([float(st.lb_loss), float(st.z_loss),
                    float(st.drop_frac)], np.float64)
    if golden_env:
        np.testing.assert_array_equal(np.asarray(y), y_g)
        np.testing.assert_array_equal(s, s_g)
    else:
        np.testing.assert_allclose(np.asarray(y), y_g, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(s, s_g, rtol=1e-5, atol=1e-7)


def test_fused_route_decision_deterministic_across_recompiles(monkeypatch):
    """Two independent jit compilations of the fused routing prologue on
    identical inputs produce bit-identical RouteDecision inputs — gates,
    expert ids, loss probs/logits, and the dispatch positions (the
    histogram scratch carries across grid steps sequentially, so no
    compilation-order freedom may leak into the counts)."""
    from repro.kernels import ops as kops
    monkeypatch.setattr(kops, "ROUTER_FUSED_MIN_ROWS", 0)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((192, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)

    def make_jit():
        # a fresh lambda defeats jax's function-identity jit cache, forcing
        # an independent trace + compile
        return jax.jit(lambda a, b: kops.router_fused(a, b, 2, renorm=True))

    out1 = make_jit()(x, w)
    out2 = make_jit()(x, w)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def make_topk_jit():
        return jax.jit(lambda a, b: M.router_topk(a, b, 2, True, "fused"))

    dec1 = make_topk_jit()(x, w)
    dec2 = make_topk_jit()(x, w)
    for a, b in zip(dec1, dec2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------- unified stats
def test_per_hop_drop_frac_switch(golden_params):
    """Switch is a 1-hop pipeline: slot 0 carries its (only) drop stat,
    slot 1 is exactly zero, and the summed drop_frac equals the vector sum."""
    params, x = golden_params
    cfg = _layer_cfg("switch", "sort", True, "argsort", 1.0)
    _, st = M.moe_layer(params["switch"], x, cfg, PLAN, act="gelu")
    hdf = np.asarray(st.hop_drop_frac)
    assert hdf.shape == (PL.MAX_HOPS,)
    assert float(st.drop_frac) == hdf.sum()
    assert hdf[0] > 0.0 and hdf[1] == 0.0


def test_per_hop_drop_frac_smile(golden_params):
    """SMILE reports each level's drop fraction separately; the scalar is
    their sum (levels compound) — one accumulation path, no ad-hoc fold."""
    params, x = golden_params
    cfg = _layer_cfg("smile", "sort", True, "argsort", 1.0)
    _, st = M.moe_layer(params["smile"], x, cfg, PLAN, act="gelu")
    hdf = np.asarray(st.hop_drop_frac)
    assert float(st.drop_frac) == pytest.approx(hdf.sum(), abs=0)
    assert hdf[0] > 0.0                     # starved level-1 capacity drops
    # capacity-free hops report the EXACT constant 0.0 per hop
    cfg_d = dataclasses.replace(cfg, dispatch_backend="dropless")
    _, st_d = M.moe_layer(params["smile"], x, cfg_d, PLAN, act="gelu")
    assert not np.asarray(st_d.hop_drop_frac).any()
    assert float(st_d.drop_frac) == 0.0


def test_stats_tree_add_shapes():
    """zero_stats() trees add across routers/dense blocks (the transformer
    layer scan requirement)."""
    z = PL.zero_stats()
    assert z.hop_drop_frac.shape == (PL.MAX_HOPS,)
    tot = jax.tree_util.tree_map(lambda a, b: a + b, z, z)
    assert tot.hop_drop_frac.shape == (PL.MAX_HOPS,)


# ------------------------------------------------------ options registry
def test_with_options_validates():
    cfg = MoEConfig(num_experts=8, d_ff_expert=16)
    with pytest.raises(ValueError, match="unknown MoE option"):
        cfg.with_options(nonexistent_knob=1)
    with pytest.raises(ValueError, match="expected one of"):
        cfg.with_options(dispatch_backend="bogus")
    with pytest.raises(ValueError, match="expected a bool"):
        cfg.with_options(ragged_a2a="yes")
    with pytest.raises(ValueError, match="positive"):
        cfg.with_options(dispatch_backend="dropless",
                         recv_bound_factor=-1.0)
    # cross-option constraint: the factor only exists on ragged hops
    with pytest.raises(ValueError, match="recv_bound_factor.*requires"):
        cfg.with_options(recv_bound_factor=2.0)
    with pytest.raises(ValueError, match="recv_bound_factor.*requires"):
        cfg.with_options(dispatch_backend="dropless", ragged_a2a=False,
                         recv_bound_factor=2.0)
    with pytest.raises(ValueError, match="positive"):
        cfg.with_options(dispatch_backend="dropless",
                         recv_bound_factor=True)   # bool is not a factor
    out = cfg.with_options(dispatch_backend="dropless",
                           recv_bound_factor=2.0, sort_impl="radix")
    assert out.dispatch_backend == "dropless"
    assert out.recv_bound_factor == 2.0 and out.sort_impl == "radix"


def test_registry_choices_match_canonical_tuples():
    """The registry's enum choices must track the canonical definitions
    (dispatch.BACKENDS, kernels.ops.SORT_IMPLS) — config.py cannot import
    them (it stays jax-free), so this pin turns silent drift into a
    failure when a new backend/sort impl is added."""
    from repro.core.dispatch import BACKENDS
    from repro.kernels.ops import SORT_IMPLS
    assert set(MOE_OPTION_FIELDS["dispatch_backend"].choices) == set(BACKENDS)
    assert set(MOE_OPTION_FIELDS["sort_impl"].choices) == set(SORT_IMPLS)


def test_registry_covers_config_fields():
    """Every registered option is a real MoEConfig field, and every dryrun
    token — prerequisites included — applies cleanly on its own (the
    dryrun contract: ``--opt recv_bound`` alone must not crash)."""
    fields = {f.name for f in dataclasses.fields(MoEConfig)}
    for opt in MOE_OPTIONS:
        assert opt.field in fields, opt.field
        for req_field, _ in opt.requires:
            assert req_field in fields, (opt.field, req_field)
    base = MoEConfig(num_experts=8, d_ff_expert=16,
                     dispatch_backend="dropless")
    for tok, kw in MOE_DRYRUN_OPTS.items():
        assert set(kw) <= set(MOE_OPTION_FIELDS), tok
        base.with_options(**kw)
        # standalone application from the DEFAULT config too (what dryrun
        # does when the token is the only one passed)
        MoEConfig(num_experts=8, d_ff_expert=16).with_options(**kw)


def test_registry_derives_train_flags():
    """train.py's CLI flags come from the registry — a knob registered
    there parses end-to-end without touching the launcher."""
    import argparse

    from repro.launch.train import add_moe_option_flags, parse_moe_option_flags
    ap = argparse.ArgumentParser()
    add_moe_option_flags(ap)
    args = ap.parse_args(["--dispatch-backend", "dropless",
                          "--ragged-a2a", "on", "--sort-impl", "radix",
                          "--recv-bound-factor", "1.5"])
    opts = parse_moe_option_flags(args)
    assert opts == {"dispatch_backend": "dropless", "ragged_a2a": True,
                    "sort_impl": "radix", "recv_bound_factor": 1.5}
    MoEConfig(num_experts=8, d_ff_expert=16).with_options(**opts)
    # empty flags -> no overrides
    assert parse_moe_option_flags(ap.parse_args([])) == {}


def test_with_dispatch_backend_shim_warns():
    """The deprecated entry point still works — with a DeprecationWarning —
    and lands on exactly what with_options produces."""
    from repro.configs import get_reduced, with_dispatch_backend, with_options
    cfg = get_reduced("smile-3.7b")
    with pytest.warns(DeprecationWarning, match="with_options"):
        old = with_dispatch_backend(cfg, "dropless", ragged_a2a=False,
                                    sort_impl="radix")
    new = with_options(cfg, dispatch_backend="dropless", ragged_a2a=False,
                       sort_impl="radix")
    assert old == new
    # still validates through the registry
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError):
            with_dispatch_backend(cfg, "bogus")
    # dense archs: no-op, but arguments still validated
    dense = get_reduced("qwen1.5-0.5b")
    with pytest.warns(DeprecationWarning):
        assert with_dispatch_backend(dense, "sort") == dense


def test_recv_bound_rows_properties():
    """Static bound: tile-aligned, monotone in factor, never above the
    worst case, and >= expected arrivals + alignment slack at factor 1."""
    R, P, nl, block = 1024, 8, 4, 64
    worst = P * R
    prev = 0
    for f in (0.5, 1.0, 2.0, 4.0, 16.0):
        b = PL.recv_bound_rows(f, R, P, nl, block)
        assert b % block == 0
        assert b <= worst
        assert b >= prev
        prev = b
    assert PL.recv_bound_rows(1.0, R, P, nl, block) >= R + P * nl * block
    assert PL.recv_bound_rows(100.0, R, P, nl, block) == worst
