"""Static analyzer CLI: ``python -m repro.launch.analyze``.

Runs the three :mod:`repro.analysis` passes (jaxpr SPMD invariants, Pallas
kernel lint, AST repo lint), prints one line per finding with file:line
provenance, and exits nonzero if anything was flagged.  Wired into
``./ci.sh --static``.

The jaxpr pass traces the entrypoint grid through ``shard_map`` on a
(4, 2) mesh, so this module pins the CPU platform and forces 8 fake CPU
devices via ``XLA_FLAGS`` *before* jax is imported — run it as a
subprocess (as ci.sh and the tests do), not inside a process that already
initialized jax.  It only traces, so it never needs (or takes) a chip.
"""
from __future__ import annotations

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_FLAG = "--xla_force_host_platform_device_count=8"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import argparse


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.analyze",
        description="static SPMD/collective invariant checker + Pallas lint")
    ap.add_argument("--pass", dest="passes", default="all",
                    choices=("all", "jaxpr", "pallas", "repo"),
                    help="which analysis pass to run (default: all)")
    ap.add_argument("--vmem-budget-mib", type=float, default=16.0,
                    help="per-grid-step VMEM budget for pallas_lint (MiB)")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="suppress progress lines; print findings only")
    args = ap.parse_args(argv)

    log = (lambda _msg: None) if args.quiet else (lambda msg: print(msg, flush=True))

    from repro.analysis import format_findings

    findings = []
    if args.passes in ("all", "repo"):
        log("[analyze] repo lint (AST)...")
        from repro.analysis import repo_lint
        findings += repo_lint.run(log=log)
    if args.passes in ("all", "pallas"):
        log("[analyze] pallas lint (tracing kernel registry)...")
        from repro.analysis import pallas_lint
        budget = int(args.vmem_budget_mib * 1024 * 1024)
        findings += pallas_lint.run(vmem_budget=budget, log=log)
    if args.passes in ("all", "jaxpr"):
        log("[analyze] jaxpr lint (tracing entrypoint grid)...")
        from repro.analysis import jaxpr_lint
        findings += jaxpr_lint.run(log=log)

    print(format_findings(findings), flush=True)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
