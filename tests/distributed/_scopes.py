"""Subprocess test: the collectives of the SMILE train step on a (2 x 2)
mesh sit under the scopes that name them.

Every all-to-all is the expert exchange (``moe/.../exchange``), and the
per-leaf gradient sums are the all-reduces under ``grad_sync``: their
operands are the local gradients of exactly the leaves that are replicated
over some mesh axis.  The dropless path (ragged exchange) is checked too.
Exits non-zero on a mismatch.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import collections
import re
import sys
ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench.scopes import scope_path
from repro.common.config import TrainConfig
from repro.configs import get_reduced, with_options
from repro.models.transformer import init_model
from repro.optim import make_optimizer, make_schedule
from repro.sharding.plan import plan_from_mesh
from repro.sharding.specs import shard_axes
from repro.train.step import build_train_step

INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$')
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def opcode(body):
    """The first word after a space to open an operand list."""
    return re.search(r" ([a-z][a-z0-9-]*)\(", body).group(1)


def operand_shapes(body):
    """``(dtype, dims)`` of each array in an instruction's result shape."""
    head = body[:body.index(" " + opcode(body) + "(")]
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", head)]


mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = plan_from_mesh(mesh)
opt = make_optimizer("lamb")
sched = make_schedule("cosine", 1e-3, 2, 100)
tcfg = TrainConfig(global_batch_size=8, seq_len=32, optimizer="lamb",
                   lr=1e-3, warmup_steps=2, grad_clip=1.0)
batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32)
         for k in ("tokens", "labels")}

for label, cfg in (("smile", get_reduced("smile-3.7b")),
                   ("smile dropless", with_options(get_reduced("smile-3.7b"),
                                                   dispatch_backend="dropless"))):
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg, plan))
    step, pspec = build_train_step(cfg, tcfg, plan, opt, sched, params, batch,
                                   mesh=mesh)
    text = step.lower(params, jax.eval_shape(opt.init, params), batch,
                      jnp.int32(1)).compile().as_text()
    instrs = [m.groups() for m in map(INSTR.match, text.splitlines()) if m]

    def path(body):
        m = OP_NAME.search(body)
        return scope_path(m.group(1)) if m else []

    a2a = [(n, b) for n, b in instrs
           if opcode(b) in ("all-to-all", "ragged-all-to-all")]
    assert a2a, f"{label}: no all-to-all in the compiled step"
    for n, b in a2a:
        p = path(b)
        assert p[:1] == ["moe"] and p[-1] == "exchange", (label, n, p)

    # the local gradient of every leaf replicated over some mesh axis
    want = collections.Counter()

    def local(leaf, spec, axes):
        if axes:
            want[tuple(NamedSharding(mesh, spec).shard_shape(leaf.shape))] += 1

    jax.tree.map(local, params, pspec, shard_axes(pspec, plan))
    # XLA combines independent all-reduces over the same devices into one,
    # named after one of them: the loss's scalar sums ride along
    got = collections.Counter()
    for n, b in instrs:
        if opcode(b) in ("all-reduce", "all-reduce-start") and \
                path(b)[:1] == ["grad_sync"]:
            got.update(dims for _, dims in operand_shapes(b) if dims)
    assert got == want, (label, sorted(got.items()), sorted(want.items()))
    print(f"OK {label}: {len(a2a)} all-to-alls under moe/.../exchange; "
          f"{sum(want.values())} gradient sums under grad_sync")
print("SCOPES OK")
