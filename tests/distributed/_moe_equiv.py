"""Subprocess test: distributed MoE layer == single-device oracle.

Covers both routers x {exact grid, h>1 slots, replication r>1, bi-level
top-(g x k_local)} on an 8-fake-device (4 x 2) mesh.

Dropless cases run BOTH wire strategies — ragged All2All (exact tile-aligned
segments over comm.ragged_all_to_all, the default) and the padded capacity
hop (ragged_a2a=False) — and assert, on non-overflowing inputs (cf=16):

* each matches the single-device oracle within the shared thresholds;
* they match each other (the ragged exchange is a pure wire-format change);
* the ragged run reports drop_frac == 0.0 exactly — no capacity buffer
  exists anywhere, at either SMILE level, so nothing can drop.

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
from repro.core.moe import init_moe_params, moe_layer
from repro.sharding.plan import single_device_plan, test_plan

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = test_plan(n_inter=4, n_intra=2)
oracle = single_device_plan()
d = 32

CASES = [((4, 2), 8, 1, 1, "sort"), ((4, 4), 16, 2, 1, "sort"),
         ((4, 4), 8, 4, 2, "sort"), ((4, 8), 8, 2, 2, "sort"),
         ((8, 4), 32, 1, 1, "sort"),
         # dropless on a real mesh: ragged A2A hops by default, padded
         # capacity hops + on-arrival re-compaction as the A/B variant
         ((4, 4), 16, 2, 1, "dropless"), ((4, 4), 8, 4, 2, "dropless"),
         ((4, 2), 8, 1, 1, "dropless"), ((4, 8), 8, 2, 2, "dropless")]


def run_dist(cfg, params, x):
    n_g, m_g = cfg.grid
    e_pn = cfg.num_experts // n_g
    shard_intra = (cfg.num_experts % (n_g * m_g) == 0) and (e_pn % 2 == 0)
    espec = P("data", "model" if shard_intra else None, None, None)
    pspecs = {"experts": {"w1": espec, "w2": espec}}
    if cfg.router == "smile":
        pspecs["router_inter"] = {"w": P(None, None)}
        pspecs["router_intra"] = {"w": P(None, None)}
    else:
        pspecs["router"] = {"w": P(None, None)}

    def f(params, x):
        y, st = moe_layer(params, x, cfg, plan, act="gelu")
        return y, st.lb_loss, st.drop_frac

    fsm = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(pspecs, P(("data", "model"), None)),
        out_specs=(P(("data", "model"), None), P(), P()), check_vma=False))
    return fsm(params, x)


for router in ["switch", "smile"]:
    for grid, E, k, g, backend in CASES:
        cfg = MoEConfig(num_experts=E, top_k=k, top_g=g, d_ff_expert=64,
                        capacity_factor=16.0, router=router, grid=grid,
                        renorm_gates=(k > 1), dispatch_backend=backend)
        params = init_moe_params(jax.random.PRNGKey(0), cfg, d, plan,
                                 glu=False)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, d))
        y_ref, st_ref = moe_layer(params, x, cfg, oracle, act="gelu")

        y_dist, lb_dist, df_dist = run_dist(cfg, params, x)
        np.testing.assert_allclose(np.asarray(y_dist), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(float(lb_dist), float(st_ref.lb_loss),
                                   rtol=1e-4)
        if backend == "dropless":
            # ragged A2A: capacity-free end-to-end -> exact-zero drop stat
            # on the mesh (both SMILE levels) and on the oracle
            assert float(df_dist) == 0.0, (router, grid, float(df_dist))
            assert float(st_ref.drop_frac) == 0.0
            # padded-hop variant agrees with the ragged exchange (and the
            # oracle) on non-overflowing inputs
            cfg_p = dataclasses.replace(cfg, ragged_a2a=False)
            y_pad, _, df_pad = run_dist(cfg_p, params, x)
            np.testing.assert_allclose(np.asarray(y_pad), np.asarray(y_ref),
                                       rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(np.asarray(y_dist),
                                       np.asarray(y_pad),
                                       rtol=2e-4, atol=2e-5)
            assert float(df_pad) == 0.0, (router, grid, float(df_pad))
            # bounded receive slab at a non-clamping factor: BIT-identical
            # to the unbounded ragged run, still exactly zero drops
            # (skew-adversarial clamping is covered in _recv_bound.py)
            cfg_b = dataclasses.replace(cfg, recv_bound_factor=8.0)
            y_bnd, _, df_bnd = run_dist(cfg_b, params, x)
            np.testing.assert_array_equal(np.asarray(y_bnd),
                                          np.asarray(y_dist))
            assert float(df_bnd) == 0.0, (router, grid, float(df_bnd))
        print(f"OK {router} grid={grid} E={E} k={k} g={g} [{backend}]")
print("ALL MOE EQUIV OK")
