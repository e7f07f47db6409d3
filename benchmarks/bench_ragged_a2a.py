"""Ragged vs capacity-padded All2All dispatch hops (EXPERIMENTS.md §Perf-4).

Times the full meshed switch MoE layer forward — router, dispatch, BOTH
All2All hops, expert FFN, combine — on an 8-fake-device mesh for three wire
strategies:

* ``sort@cf``          — capacity buffer on the wire AND into the FFN;
* ``dropless_pad@cf``  — capacity buffer on the wire, ragged re-compaction
  before the FFN (the pre-ragged dropless path, ``ragged_a2a=False``);
* ``ragged``           — exact tile-aligned segments on the wire via
  ``comm.ragged_all_to_all`` (no capacity factor: there is no capacity);
* ``ragged_rb@f``      — ragged wire PLUS the receive-bound factor
  (``MoEConfig.recv_bound_factor``, the hop-pipeline knob): the receive
  slab — and with it the post-hop re-compaction sort and recompacted FFN
  bound — shrinks from the worst-case ``P x R`` rows to
  ``~f x expected``, trading bounded clamp drops under extreme skew
  (``drop_frac`` is measured and reported; 0.0 at this benchmark's
  near-uniform routing) for the P-fold smaller compute bound.

Alongside wall time it reports per-hop WIRE BYTES two ways: *measured* from
the live routing (the actual per-destination segment counts the exchange
ships, aggregated over ranks, headers included) and *modeled* from
``benchmarks.cost_model.hop_wire_report`` — the measured-vs-modeled check
that keeps the cost model honest.

Honest caveat, recorded in the JSON: on this CPU container the ragged
exchange runs through the fused-slab emulation (jax < 0.4.38 has no
``lax.ragged_all_to_all``), whose equal-split collective ships the full
``P x R`` statically-bounded staging slab where real fabric moves only the
valid segments, and the worst-case receive bound inflates the recompacted
FFN the same way.  Wall-clock here therefore UNDERSTATES the ragged path;
wire bytes are the portable number (exact, from live counts), and
``modeled_step_ratio_*`` applies them to the Table-3-calibrated cost model.

Multi-device emulation needs its own XLA_FLAGS before jax initializes, so
``main()``/``run_smoke()`` re-exec this module as a ``--child`` subprocess.

Writes ``BENCH_ragged_a2a.json`` (skipped in ``--smoke``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

D_MODEL = 128
D_FF = 256
ITERS = 10
WARMUP = 2
CFS = (1.25, 1.5, 2.0)
RB_FACTORS = (1.5, 2.0)       # recv_bound_factor cells (ragged wire)
# (local tokens per device, experts, k) on the 8-rank mesh — production-ish
# local shapes (high tokens-per-expert, the regime the dropless sweep
# documents as the win case)
SWEEP = [(2048, 8, 2), (4096, 8, 1)]
SMOKE_SWEEP = [(128, 8, 1)]


# =============================================================================
# child: runs under 8 fake devices
# =============================================================================

def _child(smoke: bool) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from benchmarks import cost_model
    from benchmarks.bench_dispatch import _time_interleaved
    from repro.common.config import MoEConfig
    from repro.core import dispatch as D
    from repro.core.moe import capacity, init_moe_params, moe_layer, \
        router_probs, topk_gates
    from repro.sharding.plan import plan_from_mesh

    P_ = 8
    mesh = jax.make_mesh((P_,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    plan = plan_from_mesh(mesh)
    assert plan.ep == P_
    bpe = 4                                    # fp32 on the CPU emulation
    sweep = SMOKE_SWEEP if smoke else SWEEP
    cfs = (1.25,) if smoke else CFS            # smoke: one cf, one compile each
    iters, warmup = (2, 1) if smoke else (ITERS, WARMUP)
    results = []

    for T_local, E, k in sweep:
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(jax.random.PRNGKey(1), (P_ * T_local, D_MODEL))

        def layer_fn(cfg):
            """One compiled (y, drop_frac) layer; the timing wrapper takes
            y, ``drop`` reads the stat off the SAME compiled function."""
            params = init_moe_params(key, cfg, D_MODEL, plan, glu=False)
            pspecs = {"experts": {"w1": P("data", None, None, None),
                                  "w2": P("data", None, None, None)},
                      "router": {"w": P(None, None)}}

            def f(p, xx):
                y, st = moe_layer(p, xx, cfg, plan, act="gelu")
                return y, st.drop_frac

            fsm = jax.jit(jax.shard_map(f, mesh=mesh,
                                        in_specs=(pspecs, P("data", None)),
                                        out_specs=(P("data", None), P()),
                                        check_vma=False))
            timed_fn = lambda xx: fsm(params, xx)[0]
            drop = lambda xx: float(fsm(params, xx)[1])
            return timed_fn, params, drop

        fns = {}
        cfg_r = MoEConfig(num_experts=E, top_k=k, d_ff_expert=D_FF,
                          router="switch", grid=(P_, 1),
                          renorm_gates=(k > 1), dispatch_backend="dropless")
        fns["ragged"], params_r, _ = layer_fn(cfg_r)
        rbs = RB_FACTORS[:1] if smoke else RB_FACTORS
        rb_drops = {}
        for rb in rbs:
            fns[f"ragged_rb{rb}"], _, rb_drops[rb] = layer_fn(
                dataclasses.replace(cfg_r, recv_bound_factor=rb))
        for cf in cfs:
            fns[f"sort@cf{cf}"], _, _ = layer_fn(dataclasses.replace(
                cfg_r, dispatch_backend="sort", capacity_factor=cf))
            fns[f"dropless_pad@cf{cf}"], _, _ = layer_fn(dataclasses.replace(
                cfg_r, ragged_a2a=False, capacity_factor=cf))
        timed = _time_interleaved(fns, (x,), iters=iters, warmup=warmup)

        # ---- measured wire bytes of the forward hop ------------------------
        # ragged: the actual per-destination aligned segment counts each rank
        # ships (grid (8,1): groups are already rank-major, one per rank)
        V = E  # h = E // P_ ... V = virtual_total = P_ * (E // P_)
        rw = params_r["router"]["w"]

        def counts_fn(xx):
            probs, _ = router_probs(xx, rw)
            _, eidx = topk_gates(probs, k, k > 1)
            n_local_g = V // P_
            _, starts, st = D.dispatch_ragged(xx, eidx.reshape(-1),
                                              jnp.ones((xx.shape[0] * k,),
                                                       jnp.float32),
                                              V, k=k)
            return D.ragged_send_counts(starts, n_local_g)[None], \
                jnp.int32(st.cap)[None]

        cm = jax.jit(jax.shard_map(counts_fn, mesh=mesh,
                                   in_specs=P("data", None),
                                   out_specs=(P("data"), P("data")),
                                   check_vma=False))
        counts, blks = cm(x)
        counts = np.asarray(counts)                     # (P, P) [src, dst]
        block = int(np.asarray(blks)[0])
        off_diag_rows = int(counts.sum() - np.trace(counts))
        header = P_ * (P_ + V) * cost_model.BYTES_INT32
        ragged_measured = off_diag_rows * D_MODEL * bpe + header

        cap_rows = {cf: V * capacity(T_local, k, cf, V) for cf in cfs}
        padded_measured = {
            cf: int(P_ * cap_rows[cf] * (P_ - 1) / P_) * D_MODEL * bpe
            for cf in cfs}

        row = {"T_local": T_local, "E": E, "k": k, "block": block,
               "ragged_ms": timed["ragged"],
               "ragged_wire_bytes_measured": ragged_measured}

        # ---- wire-integrity parity overhead (EXPERIMENTS.md §Robust-2) -----
        # wire_integrity != off appends nl parity rows per (src, dst)
        # segment on the forward hop and 1 per peer on the reverse hop —
        # counted off-diagonal like the measured data bytes above.  The
        # overhead is routing-independent (a constant per-peer tax), so it
        # shrinks as tokens/device grow; acceptance bound is <= 5% here.
        nl_parity = V // P_
        parity_rows = P_ * (P_ - 1) * (nl_parity + 1)   # fwd + reverse
        data_rows_2hop = 2 * off_diag_rows              # fwd + reverse (echo)
        row["wire_parity_rows"] = parity_rows
        row["wire_parity_bytes"] = parity_rows * D_MODEL * bpe
        row["wire_integrity_overhead_frac"] = (
            parity_rows * D_MODEL * bpe
            / (data_rows_2hop * D_MODEL * bpe + 2 * header))

        # ---- bounded receive slab (recv_bound_factor) ----------------------
        # the payoff is a STATIC bound: every post-hop stage (re-compaction
        # sort, recompacted FFN) scans `slab_rows` instead of P x R
        from repro.core.dispatch import ragged_rows
        from repro.core.pipeline import recv_bound_rows
        R_layout = ragged_rows(T_local * k, V, block)
        nl_g = V // P_
        row["ffn_bound_rows_unbounded"] = P_ * R_layout
        for rb in rbs:
            bnd = recv_bound_rows(rb, R_layout, P_, nl_g, block)
            row[f"ragged_rb{rb}_ms"] = timed[f"ragged_rb{rb}"]
            row[f"ffn_bound_rows_rb{rb}"] = bnd
            row[f"ffn_bound_shrink_rb{rb}"] = P_ * R_layout / bnd
            # measured drop_frac of the bounded-slab cell (honesty check:
            # the clamp must not bite at this near-uniform routing) — read
            # off the already-compiled timing function, zero extra compiles
            row[f"drop_frac_rb{rb}"] = rb_drops[rb](x)
            row[f"cpu_emulated_rb{rb}_speedup"] = (timed["ragged"]
                                                   / timed[f"ragged_rb{rb}"])
        for cf in cfs:
            model = cost_model.hop_wire_report(
                T_local, k, cf, V, block, D_MODEL, P_, bytes_per_elem=bpe)
            row[f"sort_cf{cf}_ms"] = timed[f"sort@cf{cf}"]
            row[f"dropless_pad_cf{cf}_ms"] = timed[f"dropless_pad@cf{cf}"]
            row[f"padded_wire_bytes_measured_cf{cf}"] = padded_measured[cf]
            # modeled numbers are per-device; measured aggregate over ranks
            row[f"padded_wire_bytes_modeled_cf{cf}"] = int(
                model["padded_bytes"] * P_)
            row[f"ragged_wire_bytes_modeled_cf{cf}"] = int(
                model["ragged_bytes"] * P_)
            row[f"wire_reduction_cf{cf}"] = (padded_measured[cf]
                                             / ragged_measured)
            row[f"cpu_emulated_step_ratio_cf{cf}"] = (
                timed[f"dropless_pad@cf{cf}"] / timed["ragged"])
            # modeled hop round trip on real fabric (exact segments on the
            # wire — what lax.ragged_all_to_all / a remote-DMA kernel ships),
            # on both hardware profiles of the calibrated cost model
            for hw in (cost_model.V5E, cost_model.P4D):
                t = cost_model.hop_time_report(
                    T_local, k, cf, V, block, D_MODEL, D_FF, P_, hw,
                    bytes_per_elem=2)
                row[f"modeled_step_ratio_cf{cf}_{hw.name}"] = t["ratio"]
        results.append(row)

    rb_cols = RB_FACTORS[:1] if smoke else RB_FACTORS
    hdr = ("T_local,E,k,block,ragged_ms,"
           + ",".join(f"rb{rb}_ms,rb{rb}_ffn_shrink,rb{rb}_drop"
                      for rb in rb_cols) + ","
           + ",".join(f"sort_cf{cf}_ms,dropless_pad_cf{cf}_ms,"
                      f"wire_red_cf{cf},cpu_emu_ratio_cf{cf},"
                      f"v5e_model_ratio_cf{cf}" for cf in cfs))
    print(hdr)
    for r in results:
        print(f"{r['T_local']},{r['E']},{r['k']},{r['block']},"
              f"{r['ragged_ms']:.2f}," +
              ",".join(f"{r[f'ragged_rb{rb}_ms']:.2f},"
                       f"{r[f'ffn_bound_shrink_rb{rb}']:.2f}x,"
                       f"{r[f'drop_frac_rb{rb}']:.4f}"
                       for rb in rb_cols) + "," +
              ",".join(f"{r[f'sort_cf{cf}_ms']:.2f},"
                       f"{r[f'dropless_pad_cf{cf}_ms']:.2f},"
                       f"{r[f'wire_reduction_cf{cf}']:.2f}x,"
                       f"{r[f'cpu_emulated_step_ratio_cf{cf}']:.2f}x,"
                       f"{r[f'modeled_step_ratio_cf{cf}_tpu-v5e']:.2f}x"
                       for cf in cfs))
    if smoke:
        print("SMOKE OK")
        return
    payload = {
        "bench": "ragged_vs_padded_a2a",
        "d_model": D_MODEL, "d_ff": D_FF, "iters": ITERS, "ranks": P_,
        "capacity_factors": list(CFS),
        "recv_bound_factors": list(RB_FACTORS),
        "jax_backend": jax.default_backend(),
        "native_ragged_all_to_all": hasattr(jax.lax, "ragged_all_to_all"),
        "wire_integrity_note": (
            "wire_parity_rows / wire_integrity_overhead_frac quantify the "
            "wire_integrity=detect|quarantine parity-row tax (one extra "
            "row per (rank, group) segment each direction, no extra "
            "collective) against the measured two-hop ragged wire bytes; "
            "see repro.sharding.comm.checksummed_ragged_all_to_all."),
        "caveat": ("CPU container, jax without lax.ragged_all_to_all: the "
                   "ragged exchange runs the fused-slab emulation, whose "
                   "equal-split collective ships the full P x R staging "
                   "bound instead of exact segments (a P-fold byte blowup "
                   "the native op does not have), and the worst-case "
                   "receive bound inflates the recompacted FFN the same "
                   "way.  cpu_emulated_step_ratio therefore UNDERSTATES "
                   "the ragged path; wire bytes (measured from live "
                   "segment counts) are the portable number, and "
                   "modeled_step_ratio_* applies them to the Table-3-"
                   "calibrated congestion model, where the ragged hop is "
                   "parity-or-better at every cf >= 1.25."),
        "results": results,
    }
    out_path = os.path.join(ROOT, "BENCH_ragged_a2a.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out_path}")


# =============================================================================
# parent: re-exec with multi-device XLA_FLAGS
# =============================================================================

def _spawn(extra) -> None:
    env = dict(os.environ)
    # a CPU emulation of an 8-rank mesh: fake host devices, never a chip,
    # so the child runs even where its parent already holds the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT, os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--child"] + extra, cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=1800)
    sys.stdout.write(p.stdout)
    if p.returncode != 0:
        sys.stderr.write(p.stderr[-3000:])
        raise RuntimeError(f"bench_ragged_a2a child failed ({p.returncode})")


def run_smoke() -> None:
    """One jitted ragged-exchange round trip (both wire formats) on the fake
    multi-device mesh — the CI smoke half; writes no artifacts."""
    _spawn(["--smoke"])


def main() -> None:
    _spawn([])


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child("--smoke" in sys.argv)
    else:
        if "--smoke" in sys.argv:
            run_smoke()
        else:
            main()
