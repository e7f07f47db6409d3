"""Device time of the train step's named layers, from a profiler trace.

The program names its layers with ``jax.named_scope``: ``embed``,
``attention``, ``ffn``, ``moe``, ``lm_head``, ``grad_sync`` and
``optimizer``, and inside ``moe`` each hop (``hop0``, ``hop1``) and its
phases ``route``, ``dispatch``, ``exchange``, ``expert_ffn``, ``combine``.
A scope reaches the compiled program only as the ``op_name`` metadata of
the HLO instructions it encloses; a device trace names each op by its
instruction name (``fusion.52``).  :func:`op_scopes` maps one to the other
from the text of the executable that ran, :func:`scope_of` reads an
``op_name``, and :func:`scope_ms` sums the device time of the ops a
predicate picks, per whole step, as ``trace.collective_ms`` does.

Run as a module it reads the split of one traced stretch of a cell on the
chip; that is not part of a benchmark run::

    python3 -m bench.scopes --workload <cell> [<cell> ...] --seed <n>

It prints one JSON line per cell, per device: milliseconds per step of
each layer, of ops with no layer (``unattributed``), of each MoE phase per
hop, and the step module's own time beside them; and writes them all to
``chiprun_out/scopes.json``.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

from bench import trace as T

LAYERS = ("embed", "attention", "ffn", "moe", "lm_head", "grad_sync",
          "optimizer")
PHASES = ("route", "dispatch", "exchange", "expert_ffn", "combine")
UNATTRIBUTED = "unattributed"
_HOP = re.compile(r"^hop\d+$")
# transforms that wrap a name-stack component: ``transpose(jvp(moe))``
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap|checkpoint|remat|shard_map)"
                      r"\((.*)\)$")
# ``  ROOT %fusion.52 = f32[...] fusion(...), ..., metadata={op_name="..."``
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?'
                    r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` over every computation of an HLO
    module's text (loop bodies included); instructions without an
    ``op_name`` are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def scope_path(op_name: str) -> List[str]:
    """The program's own scope names in ``op_name``, outermost first, with
    transform wrappers stripped and every other component left out."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        if part in LAYERS or part in PHASES or _HOP.match(part):
            out.append(part)
    return out


def scope_of(op_name: Optional[str]) -> Optional[Tuple[str, Optional[str]]]:
    """``(layer, phase)`` of an op: the outermost layer scope and the
    innermost phase inside it (None where it has none); None where the op
    has no layer scope."""
    path = scope_path(op_name or "")
    layers = [i for i, p in enumerate(path) if p in LAYERS]
    if not layers:
        return None
    phases = [p for p in path[layers[0]:] if p in PHASES]
    return path[layers[0]], (phases[-1] if phases else None)


def hop_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost hop scope of an op (``hop1``), or None."""
    hops = [p for p in scope_path(op_name or "") if _HOP.match(p)]
    return hops[-1] if hops else None


def self_times(ops, window: Tuple[float, float]) -> List[Tuple[str, float]]:
    """``(name, seconds)`` of each op event inside ``window``, less the
    events nested inside it, so that each stretch of device time is counted
    once, for the innermost op that ran then."""
    evs = sorted(T.clip(ops, *window), key=lambda e: (e[1], -e[2]))
    out, stack = [], []           # stack: [name, start, end, covered]

    def close(top):
        out.append((top[0], (top[2] - top[1]) - top[3]))

    for name, a, b in evs:
        while stack and a >= stack[-1][2]:
            close(stack.pop())
        if stack:
            b = min(b, stack[-1][2])
            stack[-1][3] += b - a
        stack.append([name, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


def scope_ms(tr, op_scopes: Dict[str, str],
             pred: Callable[[str, Optional[str]], bool]) -> Optional[float]:
    """Milliseconds per whole step of the ops for which ``pred(event name,
    op_name)`` holds (``op_name`` None where the op is not in
    ``op_scopes``), each device over its own whole steps, mean over the
    devices; None where no step was traced."""
    per_dev = []
    for d in tr.devices:
        if not d.steps:
            continue
        s = sum(t for name, t in self_times(d.ops, d.window)
                if pred(name, op_scopes.get(T.short_name(name))))
        per_dev.append(s / d.steps)
    if not per_dev:
        return None
    return 1e3 * sum(per_dev) / len(per_dev)


def in_layers(*layers: str) -> Callable[[str, Optional[str]], bool]:
    """Predicate: the op lies under one of ``layers``."""
    def pred(name, op_name):
        s = scope_of(op_name)
        return s is not None and s[0] in layers
    return pred


def grad_allreduce(name: str, op_name: Optional[str]) -> bool:
    """Predicate: an all-reduce under ``grad_sync``, a gradient sum."""
    s = scope_of(op_name)
    return (T.opcode(name).startswith("all-reduce")
            and s is not None and s[0] == "grad_sync")


def split_ms(tr, op_scopes: Dict[str, str]) -> List[dict]:
    """Per device with whole steps: milliseconds per step of each layer and
    of ``unattributed``, with the ops that take most of each; of each MoE
    phase per hop (``hop0/route``), and of the ops under no phase inside
    ``moe`` (``hop0/other``); and of the step module events."""
    out = []
    for i, d in enumerate(tr.devices):
        if not d.steps:
            continue
        layers = dict.fromkeys(LAYERS + (UNATTRIBUTED,), 0.0)
        moe: Dict[str, float] = {}
        ops: Dict[Tuple[str, str], float] = {}
        grad_sums = 0.0
        for name, t in self_times(d.ops, d.window):
            short = T.short_name(name)
            op_name = op_scopes.get(short)
            s = scope_of(op_name)
            layer = s[0] if s else UNATTRIBUTED
            layers[layer] += t
            grad_sums += t if grad_allreduce(name, op_name) else 0.0
            ops[layer, short] = ops.get((layer, short), 0.0) + t
            if layer == "moe":
                key = f"{hop_of(op_name) or 'nohop'}/{s[1] or 'other'}"
                moe[key] = moe.get(key, 0.0) + t
        ms = lambda v: 1e3 * v / d.steps
        top = {k: [] for k in layers}
        for (layer, short), t in sorted(ops.items(), key=lambda kv: -kv[1]):
            if len(top[layer]) < 4:
                top[layer].append([short, ms(t)])
        out.append({
            "device": i, "steps": d.steps,
            "step_module_ms": ms(sum(b - a for _, a, b in d.step_events)),
            "busy_ms": ms(T.busy_s(d, d.window)),
            "layers_ms": {k: ms(v) for k, v in layers.items()},
            "moe_phases_ms": {k: ms(v) for k, v in sorted(moe.items())},
            "top_ops_ms": top,
            "grad_allreduce_ms": ms(grad_sums),
            "all_reduce_ms": ms(T.op_seconds(d, d.window, ("all-reduce",))),
        })
    return out


# =============================================================================
# A traced stretch of a cell on the chip
# =============================================================================

def measure(name: str, seed: int, log) -> dict:
    """Run cell ``name``'s program, trace ``trace_steps`` of its steps as a
    benchmark run does, and split the trace by scope."""
    import shutil
    import tempfile
    import time

    import jax

    from bench import harness as H
    cell = H.load_cell(name)
    prog = H.Program(cell, seed, jax.devices()[:cell.chips])
    prog.run(steps=H.CHECK_STEPS)
    tdir = tempfile.mkdtemp(prefix="bench_scopes_")
    steps = cell.workload["trace_steps"]
    prog.run(steps=H.TRACE_AFTER + steps + 1, tracer=H.Tracer(tdir, steps))
    module = H._module_name(prog)
    t = time.perf_counter()
    scopes = H._op_scopes(prog)
    compile_s = time.perf_counter() - t
    prog.close()
    tr = T.load(tdir)
    shutil.rmtree(tdir, ignore_errors=True)
    T.trim_to_steps(tr, module)
    log(f"[scopes] {name}: module {module}, {len(scopes)} instructions with "
        f"an op_name, compile for op_scopes {compile_s:.3f} s")
    return {"workload": name, "seed": seed, "module": module,
            "compile_s": compile_s, "devices": split_ms(tr, scopes)}


def main() -> None:
    import argparse
    import json
    import sys

    from bench import harness as H
    sys.path.insert(0, str(H.ROOT / "src"))
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    H.enable_compile_cache(metadata=True)
    log = lambda s: print(s, file=sys.stderr, flush=True)
    lines = []
    for w in args.workload:
        lines.append(measure(w, args.seed, log))
        print(json.dumps(lines[-1]), flush=True)
    out = H.ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "scopes.json").write_text(json.dumps(lines, indent=1))


if __name__ == "__main__":
    main()
