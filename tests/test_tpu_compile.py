"""Compile every Pallas kernel for a TPU v5e chip, with no chip attached.

The TPU compiler is installed with jax, and compiles for a described
``v5e:2x2`` topology while the CPU stays the backend.  Interpret mode runs
kernels the chip's compiler refuses (blocks off the (8, 128) tiling, too
much VMEM), so these compiles guard every main-path kernel at the widths
smile-3.7b gives it on one chip: d_model 768, d_ff_expert 3072, 128
experts, top-1, capacity 2.0, 4096 tokens per step.  The combine gather,
whose VMEM grows with top-k and d_model, is also compiled at the top-8
widths of qwen3-moe-30b-a3b (d_model 2048) and deepseek-v3-671b (7168).

The reduced SMILE train step is compiled for the 2x2 host too, to pin the
named scopes the benchmark splits device time by: every scope must reach
the optimized program, and LAMB's update must lie under ``optimizer``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under several test workers only the worker given this file may try.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from bench import scopes as S
from repro.kernels.flash_attn import flash_attention_pallas
from repro.kernels.grouped_ffn import (grouped_ffn_pallas,
                                       grouped_ffn_ragged_pallas)
from repro.kernels.moe_dispatch import (combine_gather_pallas,
                                        dispatch_gather_pallas)
from repro.kernels.radix_sort import group_sort_pallas
from repro.kernels.router_fused import router_fused_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas
from repro.kernels.ssd_chunk import ssd_chunk_pallas

D, F, E, TOKENS = 768, 3072, 128, 4096
CAP = 2 * TOKENS // E            # capacity-2.0 rows per expert
ROWS = E * CAP                   # flat dispatch buffer
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# name -> (kernel, [(shape, dtype), ...]); shapes only, no topology here
KERNELS = {
    "grouped_ffn": (
        lambda x, w1, w2: grouped_ffn_pallas(x, w1, None, w2),
        [((E, CAP, D), BF16), ((E, D, F), BF16), ((E, F, D), BF16)]),
    "grouped_ffn_ragged": (
        lambda r, g, w1, w2: grouped_ffn_ragged_pallas(r, g, w1, None, w2),
        [((ROWS, D), BF16), ((ROWS // 128,), I32), ((E, D, F), BF16),
         ((E, F, D), BF16)]),
    "dispatch_gather": (
        dispatch_gather_pallas,
        [((TOKENS, D), BF16), ((ROWS,), I32)]),
    "combine_gather": (
        combine_gather_pallas,
        [((ROWS, D), BF16), ((TOKENS, 1), I32), ((TOKENS, 1), F32)]),
    "combine_gather_top8_d2048": (
        combine_gather_pallas,
        [((8 * TOKENS, 2048), BF16), ((TOKENS, 8), I32), ((TOKENS, 8), F32)]),
    "combine_gather_top8_d7168": (
        combine_gather_pallas,
        [((8 * TOKENS, 7168), BF16), ((TOKENS, 8), I32), ((TOKENS, 8), F32)]),
    "group_sort": (
        lambda keys: group_sort_pallas(keys, E + 1),
        [((TOKENS,), I32)]),
    "router_fused": (
        lambda x, w: router_fused_pallas(x, w, 1),
        [((TOKENS, D), F32), ((D, E), F32)]),
    "flash_attention": (
        flash_attention_pallas,
        [((16, 256, 12, 64), BF16)] * 3),
    # off the SMILE path: the rwkv6 and zamba2 blocks' kernels
    "rwkv6_scan": (
        rwkv6_scan_pallas,
        [((2, 512, 32, 64), BF16)] * 4
        + [((32, 64), F32), ((2, 32, 64, 64), F32)]),
    "ssd_chunk": (
        ssd_chunk_pallas,
        [((2, 4, 128, 8, 64), F32), ((2, 4, 128, 8), F32),
         ((2, 4, 128, 8), F32), ((2, 4, 128, 64), F32),
         ((2, 4, 128, 64), F32)]),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    kernel, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# =============================================================================
# The train step's named scopes, as the compiled program keeps them
# =============================================================================

# ``  ROOT %fusion.52 = f32[...] fusion(...), ..., metadata={op_name="..."``
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = (.*)$')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


@pytest.fixture(scope="module")
def smile_step(topo, no_persistent_cache):
    """The reduced SMILE train step (LAMB) compiled for the described 2x2
    host: each instruction's text, by name, and the entry's output
    operands in order (parameters, then LAMB's m, v and step, then the
    metrics)."""
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.common.config import TrainConfig
    from repro.configs import get_reduced
    from repro.models.transformer import init_model
    from repro.optim import make_optimizer, make_schedule
    from repro.sharding.plan import plan_from_mesh
    from repro.sharding.specs import batch_specs
    from repro.train.step import build_train_step, opt_state_specs

    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(2, 2),
                             ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = plan_from_mesh(mesh)
    cfg = get_reduced("smile-3.7b")
    tcfg = TrainConfig(global_batch_size=8, seq_len=32, optimizer="lamb",
                       lr=1e-3, warmup_steps=2, grad_clip=1.0)
    opt = make_optimizer("lamb")
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg,
                                               plan))
    batch = {k: jax.ShapeDtypeStruct((8, 32), I32) for k in ("tokens", "labels")}
    step, pspec = build_train_step(cfg, tcfg, plan, opt,
                                   make_schedule("cosine", 1e-3, 2, 100),
                                   params, batch, mesh=mesh)

    def like(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                              sharding=NamedSharding(mesh, s)),
            tree, specs, is_leaf=lambda x: isinstance(x, P))

    args = (like(params, pspec),
            like(jax.eval_shape(opt.init, params), opt_state_specs(pspec, plan)),
            like(batch, batch_specs(batch, plan)),
            like(jax.ShapeDtypeStruct((), I32), P()))
    text = step.lower(*args).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    instrs = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            instrs[m.group(1)] = m.group(2)
    root = re.search(r"ROOT %\S+ = .*? tuple\(([^)]*)\)", entry).group(1)
    outputs = [re.sub(r"/\*.*?\*/", "", o).strip().lstrip("%")
               for o in root.split(",")]
    n_state = 3 * len(jax.tree.leaves(params)) + 1
    return instrs, outputs, n_state


def test_train_step_keeps_every_scope(smile_step):
    instrs, _, _ = smile_step
    seen = set()
    for body in instrs.values():
        m = _OP_NAME.search(body)
        if m:
            seen.update(S.scope_path(m.group(1)))
    want = S.LAYERS + S.PHASES + ("hop0", "hop1")
    missing = [s for s in want if s not in seen]
    assert not missing, f"scopes missing from the compiled step: {missing}"


def test_lamb_update_lies_under_optimizer(smile_step):
    """Each new parameter and LAMB state leaf the step returns is made by
    an op under ``optimizer`` (through copies, bitcasts and tuple
    elements, which carry no scope of their own)."""
    instrs, outputs, n_state = smile_step
    assert len(outputs) > n_state
    moves = ("copy", "copy-start", "copy-done", "bitcast", "get-tuple-element")
    for name in outputs[:n_state]:
        body = instrs[name]
        # the opcode is the first word after a space to open an operand list
        while re.search(r" ([a-z][a-z0-9-]*)\(", body).group(1) in moves:
            name = re.search(r"\(%([^\s,)]+)", body).group(1)
            body = instrs[name]
        m = _OP_NAME.search(body)
        assert m and S.scope_of(m.group(1))[0] == "optimizer", (name, body[:300])
