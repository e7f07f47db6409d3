"""Static checker over the Pallas kernels in ``repro.kernels``.

Each kernel wrapper is traced (never executed) at representative static
shapes; the resulting ``pallas_call`` equations expose the grid, every
``BlockMapping`` (block shape + index-map jaxpr + operand shape) and the
compiler params, which is everything the four rules need:

* ``vmem-budget`` — per-grid-step VMEM footprint, estimated as 2x the sum
  of block bytes (Mosaic double-buffers every pipelined block) plus
  scratch bytes, against a configurable budget (default 16 MiB — one
  TPUv4/v5 core's VMEM).  Each buffer's last dim is rounded up to 128
  lanes and its second-to-last up to the dtype's sublane count, as Mosaic
  tiles them; a second-to-last dim of 1 is tiled ``(1, 128)`` and stays 1.
  Values the kernel body keeps live are not counted: a ``(n, 1, w)`` value
  takes one sublane of each vreg, 8x its bytes, so reduce such rows one
  at a time (the combine gather does).
* ``tile-alignment`` — the trailing block dim must be the full array dim
  or a multiple of 128 (lanes); the second-to-last must be the full dim or
  a multiple of the dtype's sublane count (fp32: 8, bf16: 16, int8/fp8:
  32).  A block dim of 1 is no exception: Mosaic refuses a ``(1, d)`` block
  of a ``(T, d)`` array, though interpret mode runs it.  Lay such rows out
  as ``(T, 1, d)``, where the block's last two dims are the array's own.
* ``index-map-oob`` — index maps that depend only on grid indices are
  evaluated over the (corner-sampled) grid; a returned block index outside
  the padded operand bounds reads/writes out of bounds.  Maps that read
  scalar-prefetch operands (e.g. the ragged FFN's ``gid[i]``) are runtime
  contracts — validated dynamically by their callers — and are skipped.
* ``grid-race`` / ``missing-dimension-semantics`` — an output whose index
  map is constant along a grid axis is *revisited* across that axis (its
  block stays resident while the axis advances: the radix sort's running
  histogram, the combine gather's accumulator, the grouped FFN's f-axis
  accumulation).  Revisiting is only sound when that axis is sequential,
  so it must be declared ``"arbitrary"`` in ``dimension_semantics``; a
  ``"parallel"`` marking there is a data race on a real TPU (interpret
  mode runs sequentially and hides it).  Every kernel must declare
  ``dimension_semantics`` explicitly — VMEM scratch persists across the
  whole grid, so implicit semantics make carried state an accident.
"""
from __future__ import annotations

import itertools
import math
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.analysis import Finding
from repro.analysis.jaxpr_lint import _sub_jaxprs

DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024      # one core's VMEM
_SUBLANE = {8: 4, 4: 8, 2: 16, 1: 32}       # itemsize -> sublane multiple
_MAX_FULL_GRID = 4096                       # full enumeration cap for probes


def _pallas_eqns(jaxpr: jcore.Jaxpr) -> Iterator[jcore.JaxprEqn]:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for _key, sub in _sub_jaxprs(eqn.params):
            yield from _pallas_eqns(sub)


def _src_of(eqn: jcore.JaxprEqn) -> Tuple[Optional[str], Optional[int]]:
    """file:line of the kernel body from its jaxpr's debug info."""
    info = str(getattr(eqn.params["jaxpr"].debug_info, "func_src_info", ""))
    # format: "<kernel_name> at <file>:<line>"
    if " at " in info:
        loc = info.rsplit(" at ", 1)[1]
        if ":" in loc:
            f, _, ln = loc.rpartition(":")
            if ln.isdigit():
                return f, int(ln)
    return None, None


def _block_dims(bm) -> Tuple[int, ...]:
    """Block extent per dim: ``Blocked(n)`` -> n, squeezed dims -> 1."""
    return tuple(int(getattr(b, "block_size", 1) or 1)
                 for b in bm.block_shape)


def _tiled_bytes(dims: Sequence[int], dtype) -> int:
    """Bytes of a VMEM buffer of shape ``dims`` once Mosaic tiles its last
    two dims: lanes up to 128, sublanes up to the dtype's count (a unit
    second-to-last dim keeps its ``(1, 128)`` tile)."""
    itemsize = jnp.dtype(dtype).itemsize
    if not dims:
        return itemsize
    dims = list(dims)
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) >= 2 and dims[-2] > 1:
        sub = _SUBLANE.get(itemsize, 8)
        dims[-2] = -(-dims[-2] // sub) * sub
    return math.prod(dims) * itemsize


def _in_vmem(ref_aval) -> bool:
    """Blocks with no explicit memory space are pipelined into VMEM."""
    space = getattr(ref_aval, "memory_space", None)
    return space is None or str(getattr(space, "value", space)) == "vmem"


def _is_output(bm) -> bool:
    return str(getattr(bm, "origin", "")).startswith("output")


def _index_map_args(bm, grid_len: int):
    """(extra_avals, uses_extra): prefetch operands of the index map."""
    invars = bm.index_map_jaxpr.jaxpr.invars
    extra = invars[grid_len:]
    used = set()
    for eqn in bm.index_map_jaxpr.jaxpr.eqns:
        used.update(v for v in eqn.invars if isinstance(v, jcore.Var))
    used.update(v for v in bm.index_map_jaxpr.jaxpr.outvars
                if isinstance(v, jcore.Var))
    return extra, any(v in used for v in extra)


def _eval_index_map(bm, point: Sequence[int], extra) -> Optional[Tuple[int, ...]]:
    args = [jnp.int32(i) for i in point]
    for v in extra:
        aval = v.aval
        try:
            args.append(jnp.zeros(aval.shape, aval.dtype))
        except Exception:
            return None
    try:
        out = jcore.jaxpr_as_fun(bm.index_map_jaxpr)(*args)
    except Exception:
        return None
    return tuple(int(x) for x in out)


def _probe_points(grid: Sequence[int]) -> List[Tuple[int, ...]]:
    if math.prod(grid) <= _MAX_FULL_GRID:
        return list(itertools.product(*(range(g) for g in grid)))
    corners = [sorted({0, 1, g - 1}) for g in grid]
    return list(itertools.product(*corners))


def lint_pallas_call(eqn: jcore.JaxprEqn, *, name: str,
                     vmem_budget: int = DEFAULT_VMEM_BUDGET) -> List[Finding]:
    """Apply all kernel rules to one traced ``pallas_call`` equation."""
    findings: List[Finding] = []
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    # operands left in HBM (memory_space=pl.ANY) are not windowed into
    # VMEM: the kernel DMAs from them itself, so no block rule applies
    bms = [bm for bm in gm.block_mappings
           if _in_vmem(bm.transformed_block_aval)]
    src_file, src_line = _src_of(eqn)

    def add(rule: str, msg: str):
        findings.append(Finding("pallas", rule, f"{name}: {msg}",
                                src_file, src_line))

    # ---- VMEM footprint ----------------------------------------------------
    block_bytes = 0
    for bm in bms:
        block_bytes += _tiled_bytes(_block_dims(bm), bm.array_aval.dtype)
    body: jcore.Jaxpr = eqn.params["jaxpr"]
    n_scratch = gm.num_scratch_operands
    scratch_bytes = 0
    for v in (body.invars[len(body.invars) - n_scratch:] if n_scratch else ()):
        aval = v.aval
        if _in_vmem(aval):            # not SMEM scalars or DMA semaphores
            scratch_bytes += _tiled_bytes(aval.shape, aval.dtype)
    est = 2 * block_bytes + scratch_bytes
    if est > vmem_budget:
        add("vmem-budget",
            f"estimated per-grid-step VMEM {est / 2**20:.1f} MiB "
            f"(2 x {block_bytes / 2**20:.1f} MiB blocks "
            f"+ {scratch_bytes / 2**20:.1f} MiB scratch) exceeds the "
            f"{vmem_budget / 2**20:.0f} MiB budget")

    # ---- tile alignment ----------------------------------------------------
    for bm in bms:
        dims = _block_dims(bm)
        arr = bm.array_aval.shape
        if not dims:
            continue
        itemsize = bm.array_aval.dtype.itemsize
        sub = _SUBLANE.get(itemsize, 8)
        b_last, a_last = dims[-1], arr[-1]
        if not (b_last == a_last or b_last % 128 == 0):
            add("tile-alignment",
                f"{bm.origin}: trailing block dim {b_last} (array dim "
                f"{a_last}) is neither the full dim nor a multiple of 128 "
                f"lanes")
        if len(dims) >= 2:
            b2, a2 = dims[-2], arr[-2]
            if not (b2 == a2 or b2 % sub == 0):
                add("tile-alignment",
                    f"{bm.origin}: second-to-last block dim {b2} (array "
                    f"dim {a2}) is not a multiple of the {sub}-row "
                    f"sublane tile for itemsize {itemsize}")

    # ---- index-map OOB + output revisit detection --------------------------
    points = _probe_points(grid)
    revisited_axes: dict = {}
    for bm in bms:
        dims = _block_dims(bm)
        arr = bm.array_aval.shape
        extra, uses_extra = _index_map_args(bm, len(grid))
        if uses_extra:
            continue            # data-dependent map: a runtime contract
        results = {}
        oob_hit = None
        for pt in points:
            out = _eval_index_map(bm, pt, extra)
            if out is None:
                break
            results[pt] = out
            if oob_hit is None and len(out) == len(dims):
                for d, (idx, b, a) in enumerate(zip(out, dims, arr)):
                    nblocks = max(1, -(-a // b))
                    if idx < 0 or idx >= nblocks:
                        oob_hit = (pt, d, idx, nblocks)
                        break
        if oob_hit:
            pt, d, idx, nblocks = oob_hit
            add("index-map-oob",
                f"{bm.origin}: index map returns block index {idx} on "
                f"dim {d} at grid point {pt}, outside the padded operand "
                f"bound of {nblocks} block(s)")
        if _is_output(bm) and results and len(results) == len(points):
            for a, g in enumerate(grid):
                if g <= 1:
                    continue
                def drop(pt):      # grid point with axis a removed
                    return pt[:a] + pt[a + 1:]
                groups: dict = {}
                for pt, out in results.items():
                    groups.setdefault(drop(pt), set()).add(out)
                if all(len(v) == 1 for v in groups.values()):
                    revisited_axes.setdefault(a, []).append(str(bm.origin))

    # ---- dimension_semantics: presence + revisited axes sequential ---------
    cp = eqn.params.get("compiler_params") or {}
    sem = getattr(cp.get("mosaic_tpu"), "dimension_semantics", None)
    if sem is None:
        detail = ""
        if revisited_axes:
            ax = sorted(revisited_axes)
            detail = (f" — and grid axis(es) {ax} revisit outputs "
                      f"{sorted(set(sum(revisited_axes.values(), [])))}, "
                      f"which is a data race unless those axes are "
                      f"declared \"arbitrary\"")
        if n_scratch and not revisited_axes:
            detail = (" — and the kernel carries VMEM scratch across the "
                      "grid, which implicit semantics make an accident")
        add("missing-dimension-semantics",
            f"pallas_call has no explicit dimension_semantics for its "
            f"{len(grid)}-axis grid{detail}")
    else:
        sem = tuple(sem)
        if len(sem) != len(grid):
            add("missing-dimension-semantics",
                f"dimension_semantics {sem} has {len(sem)} entries for a "
                f"{len(grid)}-axis grid")
        else:
            for a, outs in sorted(revisited_axes.items()):
                if sem[a] != "arbitrary":
                    add("grid-race",
                        f"grid axis {a} is marked {sem[a]!r} but outputs "
                        f"{sorted(set(outs))} are revisited across it "
                        f"(index map constant in axis {a}): carried "
                        f"VMEM state across a parallel axis is a data "
                        f"race — declare the axis \"arbitrary\"")
    return findings


def lint_pallas_jaxpr(closed: jcore.ClosedJaxpr, *, name: str,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET
                      ) -> List[Finding]:
    """Lint every pallas_call reachable from a traced wrapper call."""
    findings: List[Finding] = []
    n = 0
    for eqn in _pallas_eqns(closed.jaxpr):
        n += 1
        findings.extend(lint_pallas_call(eqn, name=name,
                                         vmem_budget=vmem_budget))
    if n == 0:
        findings.append(Finding(
            "pallas", "no-pallas-call",
            f"{name}: traced wrapper contains no pallas_call equation "
            f"(registry case is stale?)"))
    return findings


# =============================================================================
# Kernel registry: every kernel in repro.kernels at representative shapes.
# Shapes mirror what the dispatch/attention paths actually feed them (lane-
# sized domains, 128-row tiles) while staying small enough to trace fast.
# =============================================================================

def kernel_cases() -> Iterator[Tuple[str, Callable[[], jcore.ClosedJaxpr]]]:
    from repro.kernels.flash_attn import flash_attention_pallas
    from repro.kernels.grouped_ffn import (grouped_ffn_pallas,
                                           grouped_ffn_ragged_pallas)
    from repro.kernels.moe_dispatch import (combine_gather_pallas,
                                            dispatch_gather_pallas)
    from repro.kernels.radix_sort import group_sort_pallas
    from repro.kernels.router_fused import router_fused_pallas
    from repro.kernels.rwkv6_scan import rwkv6_scan_pallas
    from repro.kernels.ssd_chunk import ssd_chunk_pallas

    f32, i32 = jnp.float32, jnp.int32

    yield "group_sort", lambda: jax.make_jaxpr(
        lambda keys: group_sort_pallas(keys, 64))(
            jnp.zeros((1024,), i32))
    # routing megakernel: token-tiled sequential grid carrying the expert
    # histogram in VMEM scratch and revisiting the histogram output on the
    # last step — the grid-race + scratch rules both bite here
    yield "router_fused", lambda: jax.make_jaxpr(
        lambda x, w: router_fused_pallas(x, w, 2))(
            jnp.zeros((1024, 64), f32), jnp.zeros((64, 16), f32))
    # f = 1024 with bf = 512 keeps the innermost f axis at 2 grid steps so
    # the output-revisit detector exercises the accumulation axis
    yield "grouped_ffn", lambda: jax.make_jaxpr(
        lambda x, w1, w2: grouped_ffn_pallas(x, w1, None, w2))(
            jnp.zeros((4, 256, 256), f32), jnp.zeros((4, 256, 1024), f32),
            jnp.zeros((4, 1024, 256), f32))
    yield "grouped_ffn_ragged", lambda: jax.make_jaxpr(
        lambda r, g, w1, w2: grouped_ffn_ragged_pallas(r, g, w1, None, w2))(
            jnp.zeros((1024, 256), f32), jnp.zeros((8,), i32),
            jnp.zeros((4, 256, 1024), f32), jnp.zeros((4, 1024, 256), f32))
    yield "dispatch_gather", lambda: jax.make_jaxpr(
        lambda x, src: dispatch_gather_pallas(x, src))(
            jnp.zeros((256, 256), f32), jnp.zeros((512,), i32))
    yield "combine_gather", lambda: jax.make_jaxpr(
        lambda rows, src, sc: combine_gather_pallas(rows, src, sc))(
            jnp.zeros((512, 256), f32), jnp.zeros((256, 2), i32),
            jnp.zeros((256, 2), f32))
    yield "flash_attention", lambda: jax.make_jaxpr(
        lambda q, k, v: flash_attention_pallas(q, k, v))(
            *(jnp.zeros((2, 256, 4, 64), f32),) * 3)
    yield "rwkv6_scan", lambda: jax.make_jaxpr(
        lambda r, k, v, w, u, s0: rwkv6_scan_pallas(r, k, v, w, u, s0))(
            *(jnp.zeros((2, 128, 4, 64), f32),) * 4,
            jnp.zeros((4, 64), f32), jnp.zeros((2, 4, 64, 64), f32))
    yield "ssd_chunk", lambda: jax.make_jaxpr(
        lambda xh, dt, loga, Bc, Cc: ssd_chunk_pallas(xh, dt, loga, Bc, Cc))(
            jnp.zeros((2, 2, 128, 4, 64), f32),
            jnp.zeros((2, 2, 128, 4), f32), jnp.zeros((2, 2, 128, 4), f32),
            jnp.zeros((2, 2, 128, 64), f32), jnp.zeros((2, 2, 128, 64), f32))


def run(vmem_budget: int = DEFAULT_VMEM_BUDGET, log=None) -> List[Finding]:
    """Trace and lint every registered kernel; return all findings."""
    findings: List[Finding] = []
    for name, build in kernel_cases():
        got = lint_pallas_jaxpr(build(), name=name, vmem_budget=vmem_budget)
        if log:
            log(f"  pallas: {name}: {len(got)} finding(s)")
        findings += got
    return findings
