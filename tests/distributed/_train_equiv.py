"""Subprocess test: one distributed LAMB train step == single-device oracle.

Validates the manual-collective gradient assembly (partition loss + per-leaf
psum over replicated axes) across all six architecture families on a
(2 x 2) fake-device mesh. Asserts loss, grad-norm and updated-parameter
agreement. Exits non-zero on mismatch.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.config import TrainConfig
from repro.configs import get_reduced
from repro.data.pipeline import make_batch
from repro.models.transformer import init_model
from repro.optim import make_optimizer, make_schedule
from repro.sharding.plan import single_device_plan, test_plan
from repro.train.step import build_train_step

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = test_plan(n_inter=2, n_intra=2)
oracle = single_device_plan()

ARCHS = ["smile-3.7b", "switch-3.7b", "qwen3-moe-30b-a3b", "llama3-405b",
         "rwkv6-1.6b", "zamba2-2.7b", "deepseek-v3-671b", "musicgen-large"]

# The rwkv6 KNOWN_BAD waiver is gone: the "distributed" divergence was not a
# sharding bug at all — the per-head group norm's eps=1e-5 amplified
# shape-dependent last-ulp compilation differences by ~316x wherever the
# near-empty WKV state made var ~ 0 (reproducible with NO mesh, purely by
# batch slicing).  Fixed by the head-size-scaled GN_EPS in models/rwkv6.py;
# all eight archs now assert the same thresholds.

for name in ARCHS:
    cfg = get_reduced(name).replace(remat=False)
    tcfg = TrainConfig(global_batch_size=8, seq_len=32, optimizer="lamb",
                       lr=1e-3, warmup_steps=2, grad_clip=1.0)
    params = init_model(jax.random.PRNGKey(0), cfg, oracle)
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, 8, 32, 0, 0).items()}
    opt = make_optimizer("lamb")
    sched = make_schedule("cosine", 1e-3, 2, 100)

    step1, _ = build_train_step(cfg, tcfg, oracle, opt, sched, params, batch)
    p_in = jax.tree.map(jnp.copy, params)
    p_ref, _, m_ref = step1(p_in, opt.init(params), batch, jnp.int32(1))

    step2, _ = build_train_step(cfg, tcfg, plan, opt, sched, params, batch,
                                mesh=mesh)
    p_dist, _, m_dist = step2(params, opt.init(params), batch, jnp.int32(1))

    dl = abs(float(m_ref["loss"]) - float(m_dist["loss"]))
    dg = abs(float(m_ref["grad_norm"]) - float(m_dist["grad_norm"]))
    rel_g = dg / max(float(m_ref["grad_norm"]), 1e-6)
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        p_ref, p_dist)
    maxerr = max(jax.tree.leaves(errs))
    print(f"{name:20s} dloss={dl:.2e} dgnorm_rel={rel_g:.2e} "
          f"dparam={maxerr:.2e}")
    assert dl < 2e-2, (name, dl)
    assert rel_g < 6e-2, (name, rel_g)
    assert maxerr < 5e-3, (name, maxerr)
# the launcher's own loop: with a mesh, train() creates parameters and
# optimizer state already sharded by the step's specs, and its run matches
# the same run on one device
from repro.launch.train import train  # noqa: E402
from repro.sharding.plan import plan_from_mesh  # noqa: E402
from repro.sharding.specs import param_specs  # noqa: E402

cfg = get_reduced("smile-3.7b")
kw = dict(steps=2, batch=8, seq=32, log_every=1)
p_one, h_one = train(cfg, **kw)
p_mesh, h_mesh = train(cfg, mesh=mesh, **kw)
for leaf, spec in zip(jax.tree.leaves(p_mesh),
                      jax.tree.leaves(param_specs(p_mesh, cfg,
                                                  plan_from_mesh(mesh)),
                                      is_leaf=lambda x: isinstance(x, P))):
    want = jax.sharding.NamedSharding(mesh, spec)
    assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (leaf.sharding,
                                                             spec)
dl = abs(h_one[0]["loss"] - h_mesh[0]["loss"])
maxerr = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))), p_one,
    jax.device_get(p_mesh))))
print(f"{'train() on mesh':20s} dloss={dl:.2e} dparam={maxerr:.2e}")
assert dl < 2e-2 and maxerr < 5e-3, (dl, maxerr)
print("ALL TRAIN EQUIV OK")
