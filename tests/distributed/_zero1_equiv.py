"""Subprocess test: distributed ZeRO-1 LAMB step == standard LAMB oracle."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import TrainConfig
from repro.configs import get_reduced
from repro.data.pipeline import make_batch
from repro.models.transformer import init_model
from repro.optim import make_optimizer, make_schedule
from repro.sharding.plan import single_device_plan, test_plan
from repro.train.step import build_train_step, zero1_state

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = test_plan(2, 2)
oracle = single_device_plan()

for name in ["llama3-405b", "qwen3-moe-30b-a3b", "deepseek-v3-671b"]:
    cfg = get_reduced(name).replace(remat=False)
    tcfg = TrainConfig(global_batch_size=8, seq_len=32, optimizer="lamb",
                       lr=1e-3, warmup_steps=2, grad_clip=1.0)
    params = init_model(jax.random.PRNGKey(0), cfg, oracle)
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, 8, 32, 0, 0).items()}
    opt = make_optimizer("lamb")
    sched = make_schedule("cosine", 1e-3, 2, 100)

    step_ref, _ = build_train_step(cfg, tcfg, oracle, opt, sched, params,
                                   batch)
    p_ref, _, m_ref = step_ref(jax.tree.map(jnp.copy, params),
                               opt.init(params), batch, jnp.int32(1))

    step_z, _ = build_train_step(cfg, tcfg, plan, opt, sched, params, batch,
                                 mesh=mesh, zero1=True)
    ostate = zero1_state(params, cfg, plan)
    p_z, _, m_z = step_z(params, ostate, batch, jnp.int32(1))

    dl = abs(float(m_ref["loss"]) - float(m_z["loss"]))
    rel_g = abs(float(m_ref["grad_norm"]) - float(m_z["grad_norm"])) / \
        max(float(m_ref["grad_norm"]), 1e-6)
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        p_ref, p_z)
    maxerr = max(jax.tree.leaves(errs))
    print(f"{name:20s} dloss={dl:.2e} dgnorm_rel={rel_g:.2e} "
          f"dparam={maxerr:.2e}")
    assert dl < 2e-2 and rel_g < 6e-2 and maxerr < 5e-3, name

# --------------- sentinel under ZeRO-1 (ROADMAP follow-up, retired) ----------
# The split zero1_reduce_and_clip/zero1_apply lets sentinel.gated_update
# gate the owned-chunk apply: a healthy sentinel step is bit-identical to
# the plain ZeRO-1 step; a NaN-poisoned step leaves params and the SHARDED
# optimizer state (moment chunks + step clock) bit-unchanged.
from repro.train import sentinel as SEN

cfg = get_reduced("llama3-405b").replace(remat=False)
tcfg = TrainConfig(global_batch_size=8, seq_len=32, optimizer="lamb",
                   lr=1e-3, warmup_steps=2, grad_clip=1.0, sentinel=True)
params = init_model(jax.random.PRNGKey(0), cfg, oracle)
batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, 8, 32, 0, 0).items()}
opt = make_optimizer("lamb")
sched = make_schedule("cosine", 1e-3, 2, 100)
fresh = lambda t: jax.tree.map(lambda x: jnp.array(np.asarray(x)), t)
teq = lambda a, b: all(bool((np.asarray(x) == np.asarray(y)).all())
                       for x, y in zip(jax.tree.leaves(a),
                                       jax.tree.leaves(b)))

step_z, _ = build_train_step(cfg, tcfg, plan, opt, sched, params, batch,
                             mesh=mesh, zero1=True)
step_s, _ = build_train_step(cfg, tcfg, plan, opt, sched, params, batch,
                             mesh=mesh, zero1=True, sentinel=True)
ostate = zero1_state(params, cfg, plan)
p0 = jax.tree.map(np.asarray, params)
o0 = jax.tree.map(np.asarray, ostate)
sent = SEN.init_sentinel_state()

p_z, o_z, _ = step_z(fresh(p0), fresh(o0), batch, jnp.int32(1))
p_s, o_s, m_s, sent1 = step_s(fresh(p0), fresh(o0), batch, jnp.int32(1),
                              sent)
assert float(m_s["skip"]) == 0.0
assert teq(p_z, p_s) and teq(o_z, o_s)
print("OK zero1 sentinel healthy step bit-identical to plain zero1")

# poison the params with NaN -> NaN loss + NaN grads survive the
# reduce-scatter; the verdict is global; the gated apply never runs
def poison(x):
    x = np.asarray(x).copy()
    if np.issubdtype(x.dtype, np.floating):
        x[...] = np.nan
    return x

def beq(a, b):           # bitwise tree equality (NaN == NaN by bit pattern)
    ok = True
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x = np.ascontiguousarray(np.asarray(x))
        y = np.ascontiguousarray(np.asarray(y))
        ok = ok and x.shape == y.shape and x.dtype == y.dtype and bool(
            (x.view(np.uint8) == y.view(np.uint8)).all())
    return ok

pb0 = jax.tree.map(poison, p0)
p_b, o_b, m_b, sent2 = step_s(fresh(pb0), fresh(o0), batch, jnp.int32(1),
                              sent)
assert not np.isfinite(float(m_b["loss"]))
assert float(m_b["skip"]) == 1.0
assert beq(p_b, pb0), "poisoned step must leave params bit-unchanged"
assert beq(o_b, o0), "poisoned step must leave sharded opt state unchanged"
assert float(np.asarray(o_b.step)) == 0.0       # step clock did not advance
assert float(sent2.nonfinite) == 1.0 and float(sent2.skipped) == 1.0
print("OK zero1 sentinel poisoned step skipped, sharded state bit-unchanged")
print("ZERO1 EQUIV OK")
