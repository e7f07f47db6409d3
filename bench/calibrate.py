#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds <a> <b> ... \
        [--faults half_batch no_exchange] [--control]

For each seed, in one process: the program's three set-up steps against the
float32 reference (the lower reading); on the first ``UPPER_SEEDS`` seeds
also the reference computed with float8 forward matmuls against it (the
control) and each planted fault (bench/faults.py): the upper readings.
Prints one JSON line per seed and writes them all to
``chiprun_out/calib_<cell>.json``.  Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
UPPER_SEEDS = 3          # seeds (the first ones) the control and faults run on


def summary(prog: dict, ref: dict) -> dict:
    """The compared numbers, with each per-leaf number's worst leaf beside
    them."""
    from bench import harness as H
    out = dict(H.gaps(prog, ref))
    for name, per_leaf in H.leaf_gaps(prog, ref).items():
        worst = max(per_leaf, key=per_leaf.get)
        out[f"{name}_worst"] = [worst, per_leaf[worst]]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    import contextlib
    import jax
    from bench import faults as FT
    from bench import harness as H
    H.enable_compile_cache()
    cell = H.load_cell(args.workload)
    devices = jax.devices()[:cell.chips]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    upper = args.seeds[:UPPER_SEEDS]
    lines = {s: {"seed": s} for s in args.seeds}
    refs = {}
    for s in upper:                      # references kept on the host
        t = time.perf_counter()
        refs[s] = H.run_reference(cell, s, devices[0], keep_grads=True)
        lines[s]["reference_s"] = time.perf_counter() - t
        if args.control:
            t = time.perf_counter()
            ctl = H.run_reference(cell, s, devices[0], precision="control",
                                  keep_grads=True)
            lines[s]["control_s"] = time.perf_counter() - t
            lines[s]["control"] = summary(ctl, refs[s])
            del ctl

    def readings(tag, plant, seeds):
        with plant():
            prog = H.Program(cell, seeds[0], devices)
            for s in seeds:
                prog.reset(s)
                got = prog.check_steps(keep_grads=True)
                prog.free_state()
                ref = refs.get(s) or H.run_reference(cell, s, devices[0],
                                                     keep_grads=True)
                lines[s][tag] = summary(got, ref)
                lines[s][tag + "_losses"] = got["losses"]
                lines[s]["reference_losses"] = ref["losses"]
                del got, ref
            prog.close()

    readings("program", contextlib.nullcontext, args.seeds)
    for f in args.faults:
        readings(f, FT.FAULTS[f], upper)
    result = [lines[s] for s in args.seeds]
    for line in result:
        print(json.dumps(line), flush=True)
    (out_dir / f"calib_{args.workload}.json").write_text(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
