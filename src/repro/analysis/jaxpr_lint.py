"""Trace-time SPMD/collective invariant checks over closed jaxprs.

The pass walks a jaxpr (recursing into every sub-jaxpr: ``cond`` branches,
``while``/``scan`` bodies, ``pjit``/``shard_map``/``custom_vjp`` calls),
collects every collective equation with its axis names, operand types and
source provenance, and applies four rules — see the package docstring
(:mod:`repro.analysis`) for the rationale of each:

* ``cond-collective-mismatch`` — all branches of a ``lax.cond`` must run
  the same collective sequence, unless the cond was lowered through
  :func:`repro.sharding.comm.uniform_cond` (mesh-uniform predicate).
* ``unknown-axis-name`` — collective axis names must exist on the mesh.
* ``collective-int-dtype`` — integer collective operands must be int32.
* ``collective-outside-comm`` — collectives may only be introduced by
  ``sharding/comm.py``-lowered code.

Entrypoint tracing (:func:`iter_entrypoints` / :func:`run`) needs the
8-fake-device mesh, so the full pass runs from ``python -m
repro.launch.analyze`` (which forces the device count before importing
jax); :func:`lint_jaxpr` itself is mesh-free and is what the seeded-bad
fixtures in ``tests/test_analysis.py`` drive in-process.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as jcore

from repro.analysis import Finding

# Primitive names of cross-device collectives.  pmean lowers to psum;
# psum_scatter lowers to reduce_scatter.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_to_all",
    "all_gather", "reduce_scatter", "psum_scatter", "ragged_all_to_all",
    "pgather",
})

# The one module allowed to introduce collective primitives.
COMM_SUFFIX = "repro/sharding/comm.py"

_JAXPR_TYPES = (jcore.Jaxpr, jcore.ClosedJaxpr)


def _as_jaxpr(v) -> Optional[jcore.Jaxpr]:
    if isinstance(v, jcore.ClosedJaxpr):
        return v.jaxpr
    if isinstance(v, jcore.Jaxpr):
        return v
    return None


def _sub_jaxprs(params: dict) -> Iterator[Tuple[str, jcore.Jaxpr]]:
    """Yield (param_key, jaxpr) for every sub-jaxpr in an eqn's params."""
    for key, v in params.items():
        j = _as_jaxpr(v)
        if j is not None:
            yield key, j
        elif isinstance(v, (list, tuple)):
            for item in v:
                j = _as_jaxpr(item)
                if j is not None:
                    yield key, j


def user_frame(eqn: jcore.JaxprEqn) -> Tuple[Optional[str], Optional[int]]:
    """Innermost non-jax stack frame of an equation (file, line)."""
    tb = eqn.source_info.traceback if eqn.source_info else None
    if tb is None:
        return None, None
    for fr in tb.frames:
        fn = fr.file_name
        if "site-packages" in fn or fn.startswith("<") or "/jax/" in fn:
            continue
        return fn, fr.line_num
    return None, None


def _axes_of(eqn: jcore.JaxprEqn) -> Tuple[str, ...]:
    """Normalized axis-name tuple of a collective equation."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name"))
    if axes is None:
        return ()
    if isinstance(axes, (str, int)):
        return (str(axes),)
    return tuple(str(a) for a in axes)


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One collective equation: what, over which axes, on what, and where."""

    prim: str
    axes: Tuple[str, ...]
    in_types: Tuple[str, ...]      # "f32[64,32]"-style operand types
    path: str                      # jaxpr nesting path, e.g. "/shard_map/cond"
    file: Optional[str]
    line: Optional[int]

    @property
    def signature(self) -> Tuple[str, Tuple[str, ...], Tuple[str, ...]]:
        """Congruence key: primitive + axis names + operand types, in order."""
        return (self.prim, self.axes, self.in_types)


def _site(eqn: jcore.JaxprEqn, path: str) -> CollectiveSite:
    f, ln = user_frame(eqn)
    types = tuple(str(v.aval) for v in eqn.invars
                  if isinstance(v, jcore.Var) or hasattr(v, "aval"))
    return CollectiveSite(eqn.primitive.name, _axes_of(eqn), types, path,
                          f, ln)


def collect_collectives(jaxpr: jcore.Jaxpr, path: str = ""
                        ) -> List[CollectiveSite]:
    """All collective sites in ``jaxpr``, recursing into sub-jaxprs."""
    sites: List[CollectiveSite] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            sites.append(_site(eqn, path))
        for key, sub in _sub_jaxprs(eqn.params):
            sites.extend(collect_collectives(sub, f"{path}/{name}"))
    return sites


# =============================================================================
# Rules
# =============================================================================

def check_cond_congruence(jaxpr: jcore.Jaxpr, entry: str = "",
                          path: str = "") -> List[Finding]:
    """Every ``cond`` branch pair must run identical collective sequences.

    Waived for conds whose innermost user frame lives in ``comm.py`` —
    i.e. conds lowered through :func:`repro.sharding.comm.uniform_cond`,
    whose contract is a mesh-uniform predicate (every device takes the
    same branch, so asymmetric collectives cannot diverge the mesh).
    """
    findings: List[Finding] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "cond" and "branches" in eqn.params:
            seqs = [tuple(s.signature for s in collect_collectives(b))
                    for b in (_as_jaxpr(br) for br in eqn.params["branches"])]
            if len(set(seqs)) > 1:
                f, ln = user_frame(eqn)
                if not (f and f.endswith(COMM_SUFFIX)):
                    desc = " vs ".join(
                        "[" + ", ".join(f"{p} over {a}" for p, a, _ in s) + "]"
                        for s in seqs)
                    findings.append(Finding(
                        "jaxpr", "cond-collective-mismatch",
                        f"{entry}: cond at {path or '/'} runs different "
                        f"collective sequences per branch ({desc}); either "
                        f"make the branches congruent or route the cond "
                        f"through comm.uniform_cond after proving the "
                        f"predicate mesh-uniform", f, ln))
        for key, sub in _sub_jaxprs(eqn.params):
            findings.extend(
                check_cond_congruence(sub, entry, f"{path}/{name}"))
    return findings


def check_axis_names(sites: Sequence[CollectiveSite],
                     mesh_axes: Sequence[str], entry: str = ""
                     ) -> List[Finding]:
    """Collective axis names must all exist on the mesh."""
    known = set(mesh_axes)
    findings = []
    for s in sites:
        unknown = [a for a in s.axes if a not in known]
        if unknown:
            findings.append(Finding(
                "jaxpr", "unknown-axis-name",
                f"{entry}: {s.prim} at {s.path or '/'} names mesh axes "
                f"{unknown} not in the mesh spec {sorted(known)}",
                s.file, s.line))
    return findings


def check_count_dtypes(sites: Sequence[CollectiveSite], entry: str = ""
                       ) -> List[Finding]:
    """Integer operands of collectives (count grids) must be int32."""
    findings = []
    for s in sites:
        bad = [t for t in s.in_types
               if t.startswith(("int", "uint")) and not t.startswith(
                   ("int32", "uint32", "int8", "int16", "uint8", "uint16"))]
        if bad:
            findings.append(Finding(
                "jaxpr", "collective-int-dtype",
                f"{entry}: {s.prim} at {s.path or '/'} moves non-int32 "
                f"integer operand(s) {bad} across the wire — count grids "
                f"must be exactly int32 at every collective boundary "
                f"(silent x64 promotion doubles exchange bytes and breaks "
                f"the native ragged-A2A offset contract)",
                s.file, s.line))
    return findings


def check_provenance(sites: Sequence[CollectiveSite], entry: str = ""
                     ) -> List[Finding]:
    """Collectives may only be introduced by comm.py-lowered code."""
    findings = []
    for s in sites:
        if s.file is None:
            continue               # no traceback (synthetic jaxpr): skip
        if not s.file.endswith(COMM_SUFFIX):
            findings.append(Finding(
                "jaxpr", "collective-outside-comm",
                f"{entry}: {s.prim} at {s.path or '/'} is introduced "
                f"outside sharding/comm.py — all collectives must go "
                f"through the comm helpers (single-device oracle identity, "
                f"remat save-policy tagging, and this analyzer's waivers "
                f"all key off that provenance)", s.file, s.line))
    return findings


def lint_jaxpr(closed: jcore.ClosedJaxpr, *, mesh_axes: Sequence[str],
               entry: str = "", provenance: bool = True) -> List[Finding]:
    """Run all jaxpr rules over one traced entrypoint."""
    jaxpr = closed.jaxpr
    sites = collect_collectives(jaxpr)
    findings = check_cond_congruence(jaxpr, entry)
    findings += check_axis_names(sites, mesh_axes, entry)
    findings += check_count_dtypes(sites, entry)
    if provenance:
        findings += check_provenance(sites, entry)
    return findings


# =============================================================================
# Entrypoint grid: both routers x all backends x ragged/padded wire, plus
# the train step with the sentinel on and off.  Shapes derive from the
# paper configs in repro.configs, scaled onto the 8-device test mesh.
# =============================================================================

MESH_SHAPE = (4, 2)
MESH_AXES = ("data", "model")


def _moe_cases():
    import dataclasses as dc

    from repro.configs import get_reduced

    for router, arch in (("switch", "switch-3.7b"), ("smile", "smile-3.7b")):
        base = get_reduced(arch).moe
        base = dc.replace(base, num_experts=8, d_ff_expert=64,
                          grid=MESH_SHAPE, capacity_factor=2.0)
        for backend, ragged in (("sort", True), ("dense", True),
                                ("dropless", True), ("dropless", False)):
            cfg = base.with_options(dispatch_backend=backend,
                                    ragged_a2a=ragged)
            name = f"moe/{router}/{backend}"
            if backend == "dropless":
                name += "/ragged" if ragged else "/padded"
            yield name, cfg
        # the fused routing megakernel feeding the same dropless/ragged hop:
        # the route decision moves through the kernel (or its oracle below
        # the row threshold) but every collective it feeds must stay
        # congruent with the unfused chain
        yield (f"moe/{router}/dropless/fused",
               base.with_options(dispatch_backend="dropless", ragged_a2a=True,
                                 router_impl="fused"))
        # wire-integrity policies ride the ragged hops only: the parity
        # rows and per-segment verdicts must obey every collective rule
        # (int32 words, comm.py provenance, no divergent conds)
        for pol in ("detect", "quarantine"):
            yield (f"moe/{router}/dropless/ragged/wire-{pol}",
                   base.with_options(dispatch_backend="dropless",
                                     ragged_a2a=True, wire_integrity=pol))


def _trace_moe(cfg, mesh, plan):
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.moe import init_moe_params, moe_layer

    d, t = 32, 64
    params = init_moe_params(jax.random.PRNGKey(0), cfg, d, plan)
    x = jnp.zeros((t, d), jnp.float32)
    espec = P("data", "model", None, None)
    pspecs = {"experts": {k: espec for k in params["experts"]}}
    for k in params:
        if k.startswith("router"):
            pspecs[k] = {"w": P(None, None)}

    def f(p, xx):
        y, st = moe_layer(p, xx, cfg, plan, act="gelu")
        return y, st.lb_loss, st.drop_frac

    fsm = jax.shard_map(f, mesh=mesh,
                        in_specs=(pspecs, P(("data", "model"), None)),
                        out_specs=(P(("data", "model"), None), P(), P()),
                        check_vma=False)
    return jax.make_jaxpr(fsm)(params, x)


def _trace_train(sentinel: bool, mesh, plan):
    import jax.numpy as jnp

    from repro.common.config import TrainConfig
    from repro.configs import get_reduced
    from repro.data.pipeline import make_batch
    from repro.models.transformer import init_model
    from repro.optim import make_optimizer, make_schedule
    from repro.sharding.plan import single_device_plan
    from repro.train.sentinel import init_sentinel_state
    from repro.train.step import build_train_step

    cfg = get_reduced("smile-3.7b").replace(remat=False)
    tcfg = TrainConfig(global_batch_size=8, seq_len=32, optimizer="lamb",
                       lr=1e-3, warmup_steps=2, sentinel=sentinel)
    params = init_model(jax.random.PRNGKey(0), cfg, single_device_plan())
    batch = {k: jnp.asarray(v)
             for k, v in make_batch(cfg, 8, 32, 0, 0).items()}
    opt = make_optimizer("lamb")
    sched = make_schedule("cosine", 1e-3, 2, 100)
    step, _ = build_train_step(cfg, tcfg, plan, opt, sched, params, batch,
                               mesh=mesh, sentinel=sentinel)
    args = (params, opt.init(params), batch, jnp.int32(1))
    if sentinel:
        args += (init_sentinel_state(),)
    return jax.make_jaxpr(lambda *a: step(*a))(*args)


def _trace_serve(mesh, plan):
    """Serving entrypoints: the fused batched decode tick (paged KV scatter/
    gather + the masked MoE decode hop) and one bucketed prefill chunk.
    Unregistered entrypoints are invisible to ``./ci.sh --static`` — these
    are the jitted callables ``repro.serve.engine.Engine`` drives."""
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.models.transformer import init_model
    from repro.serve import kvcache as KVC
    from repro.serve.engine import (build_paged_decode_step,
                                    build_paged_prefill)

    cfg = get_reduced("qwen3-moe-30b-a3b")     # MoE arch -> decode hop traced
    params = init_model(jax.random.PRNGKey(0), cfg, plan)
    page, pool_pages, n_slots, mp = 4, 16, 4, 4
    caches = KVC.init_paged_caches(cfg, pool_pages, page, plan)
    table = jnp.zeros((n_slots, mp), jnp.int32)

    decode = build_paged_decode_step(cfg, plan, params, caches, mesh)
    dargs = (params, jnp.zeros((n_slots,), jnp.int32), caches, table,
             jnp.zeros((n_slots,), jnp.int32),
             jnp.ones((n_slots,), bool))
    yield "serve/decode_tick", jax.make_jaxpr(lambda *a: decode(*a))(*dargs)

    prefill = build_paged_prefill(cfg, plan, params, caches, mesh)
    pargs = (params, jnp.zeros((1, 8), jnp.int32), caches, table[:1],
             jnp.int32(0), jnp.int32(8))
    yield ("serve/prefill_chunk",
           jax.make_jaxpr(lambda *a: prefill(*a))(*pargs))


def iter_entrypoints() -> Iterator[Tuple[str, jcore.ClosedJaxpr]]:
    """Trace the registered entrypoint grid on the 8-fake-device mesh."""
    from repro.sharding.plan import test_plan

    if len(jax.devices()) < 8:
        raise RuntimeError(
            "jaxpr_lint needs >= 8 devices to trace the entrypoint grid; "
            "run via `python -m repro.launch.analyze`, which forces "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
            "importing jax")
    auto = (jax.sharding.AxisType.Auto,) * len(MESH_AXES)
    mesh = jax.make_mesh(MESH_SHAPE, MESH_AXES, axis_types=auto)
    plan = test_plan(*MESH_SHAPE)
    for name, cfg in _moe_cases():
        yield name, _trace_moe(cfg, mesh, plan)
    train_mesh = jax.make_mesh((2, 2), MESH_AXES, axis_types=auto)
    train_plan = test_plan(2, 2)
    for sentinel in (False, True):
        name = f"train_step/{'sentinel' if sentinel else 'plain'}"
        yield name, _trace_train(sentinel, train_mesh, train_plan)
    yield from _trace_serve(train_mesh, train_plan)


def run(log=None) -> List[Finding]:
    """Trace and lint every registered entrypoint; return all findings."""
    findings: List[Finding] = []
    for name, closed in iter_entrypoints():
        got = lint_jaxpr(closed, mesh_axes=MESH_AXES, entry=name)
        if log:
            n = len(collect_collectives(closed.jaxpr))
            log(f"  jaxpr: {name}: {n} collective sites, "
                f"{len(got)} finding(s)")
        findings += got
    return findings
