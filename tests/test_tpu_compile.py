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
the optimized program, and LAMB's update must lie under ``optimizer``.  The
one-chip SMILE and Switch steps are compiled at published widths to pin
that the expert weights' bf16 casts and LAMB's clip scale stay fused.

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
_MOVES = ("copy", "copy-start", "copy-done", "bitcast", "get-tuple-element")


def _parse(text):
    """Each instruction's text after ``=``, by name, over every computation;
    and the entry's output operands in order."""
    entry = text[text.index("\nENTRY "):]
    instrs = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            instrs[m.group(1)] = m.group(2)
    root = re.search(r"ROOT %\S+ = .*? tuple\(([^)]*)\)", entry).group(1)
    outputs = [re.sub(r"/\*.*?\*/", "", o).strip().lstrip("%")
               for o in root.split(",")]
    return instrs, outputs


def _opcode(body):
    """The opcode: the first word after a space to open an operand list."""
    return re.search(r" ([a-z][a-z0-9-]*)\(", body).group(1)


def _producer(instrs, name):
    """The instruction that makes ``name``'s value, through copies,
    bitcasts and tuple elements (which carry no scope of their own)."""
    body = instrs[name]
    while _opcode(body) in _MOVES:
        name = re.search(r"\(%([^\s,)]+)", body).group(1)
        body = instrs[name]
    return name


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
    instrs, outputs = _parse(step.lower(*args).compile().as_text())
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
    for name in outputs[:n_state]:
        name = _producer(instrs, name)
        body = instrs[name]
        m = _OP_NAME.search(body)
        assert m and S.scope_of(m.group(1))[0] == "optimizer", (name, body[:300])


# =============================================================================
# One chip: the expert weights' LAMB update and bf16 casts stay fused
# =============================================================================

_SHAPE = re.compile(r"\b(f32|bf16)\[([\d,]+)\]")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")


def _computation_of(text):
    """``{instruction name: name of the computation that holds it}``."""
    out, comp = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = comp
    return out


@pytest.fixture(scope="module", params=["smile-3.7b", "switch-3.7b"])
def one_chip_step(request, one_chip, no_persistent_cache):
    """The benchmark's one-chip train step (2 layers, logical grid (2, 2),
    LAMB) at published widths with a small batch, compiled for one
    described v5e chip: instructions by name, the entry's outputs, the
    number of state outputs, each instruction's computation, and the
    element count of an expert weight leaf."""
    import dataclasses
    import math

    from repro.common.config import TrainConfig
    from repro.configs import get_config
    from repro.models.transformer import init_model
    from repro.optim import make_optimizer, make_schedule
    from repro.sharding.plan import single_device_plan
    from repro.train.step import build_train_step

    cfg = get_config(request.param)
    cfg = cfg.replace(num_layers=2, moe=dataclasses.replace(cfg.moe,
                                                            grid=(2, 2)))
    plan = single_device_plan()
    tcfg = TrainConfig(global_batch_size=2, seq_len=128, optimizer="lamb",
                       lr=3e-4, warmup_steps=1, grad_clip=1.0)
    opt = make_optimizer("lamb")
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg,
                                               plan))
    batch = {k: jax.ShapeDtypeStruct((2, 128), I32) for k in ("tokens", "labels")}
    step, _ = build_train_step(cfg, tcfg, plan, opt,
                               make_schedule("cosine", 3e-4, 1, 100),
                               params, batch)
    like = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), t)
    args = (like(params), like(jax.eval_shape(opt.init, params)),
            like(batch), jax.ShapeDtypeStruct((), I32, sharding=one_chip))
    text = step.lower(*args).compile().as_text()
    instrs, outputs = _parse(text)
    experts = [x for path, x in jax.tree_util.tree_flatten_with_path(params)[0]
               if "experts" in jax.tree_util.keystr(path)]
    assert experts and experts[0].shape[1:3] == (2, E // 2), experts[0].shape
    return (instrs, outputs, 3 * len(jax.tree.leaves(params)) + 1,
            _computation_of(text), math.prod(experts[0].shape))


def test_one_chip_expert_weights_stay_fused(one_chip_step):
    """With two inter-node groups on the chip, the expert leaves keep their
    (b_n, E_local) group dimensions through the expert FFN.  Then LAMB
    reads the bf16 weight gradient with the clip scale fused in: no fusion
    under ``optimizer`` writes an f32 expert-sized tensor other than LAMB's
    m, v and new parameters.  And the matmuls cast the f32 weights
    themselves: no expert weight is cast to bf16 on its own."""
    import math

    instrs, outputs, n_state, comp_of, n_expert = one_chip_step
    fused = {c for b in instrs.values() if _opcode(b) == "fusion"
             for c in re.findall(r"calls=%([^\s,]+)", b)}
    top = {n: b for n, b in instrs.items() if comp_of[n] not in fused}

    def expert_sized(body, dtype):
        head = body[:body.index(" " + _opcode(body) + "(")]
        return any(dt == dtype and math.prod(map(int, dims.split(",")))
                   == n_expert for dt, dims in _SHAPE.findall(head))

    def scope(body):
        m = _OP_NAME.search(body)
        return S.scope_of(m.group(1) if m else None)

    state = {_producer(instrs, o) for o in outputs[:n_state]}
    writes = [n for n, b in top.items() if _opcode(b) == "fusion"
              and expert_sized(b, "f32") and (scope(b) or ("",))[0] == "optimizer"]
    assert len(set(writes) & state) >= 4, writes   # LAMB's passes, w1 and w2
    spills = sorted(set(writes) - state)
    assert not spills, [(n, top[n][:200]) for n in spills]

    def cast_only(body):
        if _opcode(body) == "convert":
            return True
        if _opcode(body) != "fusion":
            return False
        called = re.search(r"calls=%([^\s,]+)", body).group(1)
        ops = {_opcode(b) for n, b in instrs.items() if comp_of[n] == called}
        return "convert" in ops and ops <= {"parameter", "convert", "bitcast",
                                            "copy"}

    casts = [n for n, b in top.items() if expert_sized(b, "bf16")
             and cast_only(b)]
    assert not casts, [(n, top[n][:200]) for n in casts]
