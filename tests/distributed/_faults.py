"""Subprocess test: the fault-injection containment matrix.

Every fault class of ``repro.common.faultinject`` x {switch, smile} on the
8-fake-device (4 x 2) mesh, dropless + ragged hops (the wire where count
grids actually travel).  For each cell the layer must end in a DEFINED
state with EXACT accounting — no crash, no hang, no wrong-expert output:

* ``counts``  — sanitizer quarantines the poisoned sources: global
  ``fault_events[hop] == n_devices * expected_count_events(...)`` exactly,
  the quarantined segments are dropped (``drop_frac > 0``) and the output
  stays finite.
* ``dropseg`` — a valid-but-silent grid: ZERO fault events, and the drop
  accounting is exact — ``hop_drop_frac[hop] == 1/P`` of that hop's ranks
  (every assignment from the victim rank, nothing else).
* ``nanrows`` — NO hop-level detection at ``wire_integrity=off`` by design
  (payloads are not checksummed): NaN reaches the layer output, zero
  events, zero drops — containment is the step sentinel's job
  (tests/test_sentinel.py).
* ``skew``   — routing collapse onto one group: the unbounded ragged hops
  absorb it with exactly zero drops while the router watchdog fields alarm
  (``hop_max_load == 1``, ``hop_load_entropy ~ 0``).
* inert plan (``counts`` aimed at a hop that doesn't exist) — the forced
  echo-reverse path on healthy counts is BIT-identical to ``fault_plan=
  None``, which itself is the golden-pinned production path.

Wire-integrity matrix (``wire_integrity = detect | quarantine``, the
per-segment parity rows of ``comm.checksummed_ragged_all_to_all``):

* healthy runs at EVERY policy are bit-identical to the production path —
  the parity rows ride the slab and are stripped before compute;
* ``nanrows``/``bitflip``/``inflate``/``dupseg`` under ``quarantine`` are
  each localized to the exact (hop, source rank): ``fault_events[hop] ==
  n_devices`` (one flagged source per receiver), ``wire_faults[hop,
  victim] == n_devices``, ``hop_drop_frac[hop] == 1/P`` (exactly the
  victim's segment at every receiver, nothing else), and the output stays
  finite — no sentinel burn;
* ``detect`` counts and localizes the same events but passes payloads
  through with exactly zero drops (the A/B policy);
* ``off`` is provably blind to ``inflate``/``dupseg``: the PR-6 sanitizer
  accepts the corrupted-but-structurally-valid grid with zero events.

Exits non-zero on any violation.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common import faultinject as FI
from repro.common.config import MoEConfig
from repro.core.moe import init_moe_params, moe_layer
from repro.sharding.plan import test_plan

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = test_plan(n_inter=4, n_intra=2)
NDEV = 8
d = 32

# hop wire parameters on this mesh for grid=(4,4), E=16 (see core/moe.py):
# switch: one flat hop over both axes; smile: inter over "data", intra
# over "model" with V2 = 4 local virtual groups
HOPS = {"switch": {0: (8, 2)},              # level -> (P, groups_per_rank)
        "smile": {0: (4, 1), 1: (2, 2)}}


def base_cfg(router):
    return MoEConfig(num_experts=16, top_k=2, top_g=2, d_ff_expert=64,
                     capacity_factor=16.0, router=router, grid=(4, 4),
                     renorm_gates=True, dispatch_backend="dropless",
                     ragged_a2a=True)


def run_dist(cfg, params, x):
    espec = P("data", "model", None, None)
    pspecs = {"experts": {"w1": espec, "w2": espec}}
    if cfg.router == "smile":
        pspecs["router_inter"] = {"w": P(None, None)}
        pspecs["router_intra"] = {"w": P(None, None)}
    else:
        pspecs["router"] = {"w": P(None, None)}

    def f(params, x):
        y, st = moe_layer(params, x, cfg, plan, act="gelu")
        return (y, st.drop_frac, st.hop_drop_frac, st.fault_events,
                st.hop_max_load, st.hop_load_entropy, st.wire_faults)

    fsm = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(pspecs, P(("data", "model"), None)),
        out_specs=(P(("data", "model"), None),) + (P(),) * 6, check_vma=False))
    return fsm(params, x)


for router in ("switch", "smile"):
    cfg = base_cfg(router)
    params = init_moe_params(jax.random.PRNGKey(0), cfg, d, plan, glu=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, d))
    y0, df0, hdf0, ev0, ml0, le0, wf0 = run_dist(cfg, params, x)
    assert float(df0) == 0.0 and not np.asarray(ev0).any()
    assert not np.asarray(wf0).any()
    assert not np.isnan(np.asarray(y0)).any()

    # ---- inert plan: echo-reverse machinery on healthy counts is the
    # identity, bit for bit (and zero events / zero drops)
    y_i, df_i, _, ev_i, _, _, _ = run_dist(
        cfg.with_options(fault_plan="counts@0:7"), params, x)
    np.testing.assert_array_equal(np.asarray(y_i), np.asarray(y0))
    assert float(df_i) == 0.0 and not np.asarray(ev_i).any()
    print(f"OK {router} inert-echo bit-identical")

    # ---- counts: exact sanitizer event accounting, finite output ---------
    fp = FI.parse_fault_plan("counts")
    y, df, hdf, ev, _, _, _ = run_dist(cfg.with_options(fault_plan="counts"),
                                    params, x)
    expect = np.zeros(2, np.float32)
    for lvl, (Pn, nl) in HOPS[router].items():
        expect[lvl] = NDEV * FI.expected_count_events(fp, lvl, Pn, nl)
    np.testing.assert_array_equal(np.asarray(ev), expect)
    assert not np.isnan(np.asarray(y)).any()
    assert float(df) > 0.0                 # quarantined segments dropped
    print(f"OK {router} counts events={np.asarray(ev)} drop={float(df):.3f}")

    # ---- dropseg: zero events, EXACT 1/P drop on the victim's hop --------
    for lvl, (Pn, nl) in HOPS[router].items():
        y, df, hdf, ev, _, _, _ = run_dist(
            cfg.with_options(fault_plan=f"dropseg:{lvl}"), params, x)
        assert not np.asarray(ev).any(), (router, lvl, np.asarray(ev))
        hdf = np.asarray(hdf)
        assert hdf[lvl] == np.float32(1.0 / Pn), (router, lvl, hdf, Pn)
        other = [h for i, h in enumerate(hdf) if i != lvl]
        assert not np.asarray(other).any(), (router, lvl, hdf)
        assert not np.isnan(np.asarray(y)).any()
        print(f"OK {router} dropseg:{lvl} drop={hdf[lvl]:.4f} == 1/{Pn}")

    # ---- nanrows: undetectable at hop level BY DESIGN — NaN must reach
    # the output (sentinel territory), with zero events / zero drops
    y, df, _, ev, _, _, _ = run_dist(cfg.with_options(fault_plan="nanrows"),
                                  params, x)
    assert np.isnan(np.asarray(y)).any()
    assert not np.asarray(ev).any() and float(df) == 0.0
    print(f"OK {router} nanrows propagates to sentinel")

    # ---- skew: storm absorbed with zero drops; watchdog alarms -----------
    y, df, _, ev, ml, le, _ = run_dist(cfg.with_options(fault_plan="skew"),
                                    params, x)
    assert float(df) == 0.0 and not np.asarray(ev).any()
    assert not np.isnan(np.asarray(y)).any()
    ml, le = np.asarray(ml), np.asarray(le)
    for lvl in HOPS[router]:
        assert ml[lvl] == 1.0, (router, lvl, ml)
        assert le[lvl] < 0.05, (router, lvl, le)
    print(f"OK {router} skew absorbed, watchdog max_load={ml} entropy={le}")

    # ================= wire-integrity matrix (parity-row checksums) =======
    # ---- healthy wire at every policy is bit-identical to production -----
    for pol in ("detect", "quarantine"):
        y, df, hdf, ev, _, _, wf = run_dist(
            cfg.with_options(wire_integrity=pol), params, x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
        assert float(df) == 0.0 and not np.asarray(ev).any()
        assert not np.asarray(wf).any(), (router, pol, np.asarray(wf))
        print(f"OK {router} healthy {pol} bit-identical, zero events")

    # ---- quarantine: every wire fault class localized to the exact
    # (hop, src rank) with exact event / drop / per-rank accounting --------
    for kind in ("nanrows", "bitflip", "inflate", "dupseg"):
        for lvl, (Pn, nl) in HOPS[router].items():
            fp = FI.parse_fault_plan(f"{kind}:{lvl}")
            victim = FI.wire_fault_victim(fp, lvl, Pn, nl)
            y, df, hdf, ev, _, _, wf = run_dist(
                cfg.with_options(wire_integrity="quarantine",
                                 fault_plan=f"{kind}:{lvl}"), params, x)
            ev, hdf, wf = map(np.asarray, (ev, hdf, wf))
            # one flagged source per receiver, on the faulted hop only
            expect_ev = np.zeros(2, np.float32)
            expect_ev[lvl] = NDEV
            np.testing.assert_array_equal(ev, expect_ev)
            # localized to the EXACT source rank at every receiver
            expect_wf = np.zeros_like(wf)
            expect_wf[lvl, victim] = NDEV
            np.testing.assert_array_equal(wf, expect_wf)
            # exactly the victim's segment dropped everywhere: 1/P
            assert hdf[lvl] == np.float32(1.0 / Pn), (router, kind, lvl, hdf)
            other = [h for i, h in enumerate(hdf) if i != lvl]
            assert not np.asarray(other).any(), (router, kind, lvl, hdf)
            # degraded-mode continue: finite output, nothing for the
            # sentinel to burn the step over
            assert not np.isnan(np.asarray(y)).any(), (router, kind, lvl)
            print(f"OK {router} quarantine {kind}:{lvl} -> "
                  f"(hop {lvl}, rank {victim}) drop=1/{Pn}")

    # ---- counts x quarantine: the sanitizer and the checksum verifier must
    # not DOUBLE-count the same injected fault — a source quarantined by
    # sanitize_len_grid trivially fails its wire parity too (the receiver
    # now believes zero-length segments the sender checksummed full-length),
    # so fault_events must equal the sanitizer's exact entry count alone and
    # wire_faults must stay zero (the PR-8 known-edge, fixed + pinned here)
    fp = FI.parse_fault_plan("counts")
    y, df, hdf, ev, _, _, wf = run_dist(
        cfg.with_options(wire_integrity="quarantine", fault_plan="counts"),
        params, x)
    expect = np.zeros(2, np.float32)
    for lvl, (Pn, nl) in HOPS[router].items():
        expect[lvl] = NDEV * FI.expected_count_events(fp, lvl, Pn, nl)
    np.testing.assert_array_equal(np.asarray(ev), expect)
    assert not np.asarray(wf).any(), (router, np.asarray(wf))
    assert float(df) > 0.0                 # quarantined segments dropped
    assert not np.isnan(np.asarray(y)).any()
    print(f"OK {router} counts x quarantine deduplicated: "
          f"events={np.asarray(ev)} wire_faults all zero")

    # ---- detect: same events + localization, payloads pass through -------
    fp = FI.parse_fault_plan("bitflip:0")
    Pn, nl = HOPS[router][0]
    victim = FI.wire_fault_victim(fp, 0, Pn, nl)
    y, df, hdf, ev, _, _, wf = run_dist(
        cfg.with_options(wire_integrity="detect", fault_plan="bitflip:0"),
        params, x)
    ev, wf = np.asarray(ev), np.asarray(wf)
    assert ev[0] == NDEV and ev[1] == 0.0, (router, ev)
    assert wf[0, victim] == NDEV and wf.sum() == NDEV, (router, wf)
    assert float(df) == 0.0 and not np.asarray(hdf).any()   # A/B: no drops
    y = np.asarray(y)
    assert not np.array_equal(y, np.asarray(y0))    # corruption passes ...
    assert not np.isnan(y).any()                    # ... but stays finite
    print(f"OK {router} detect bitflip counted at (0, rank {victim}), "
          f"payload passed through")

    # ---- off: the sanitizer alone is provably blind to in-bounds grid
    # corruption — structurally valid, zero events, zero drops -------------
    for kind in ("inflate", "dupseg"):
        y, df, hdf, ev, _, _, wf = run_dist(
            cfg.with_options(fault_plan=f"{kind}:0"), params, x)
        assert not np.asarray(ev).any() and not np.asarray(wf).any()
        # inflate is FULLY silent; dupseg's misattributed rows may fail the
        # echo (a drop, never a detection) — blindness is about events
        if kind == "inflate":
            assert float(df) == 0.0, (router, kind, float(df))
        assert not np.isnan(np.asarray(y)).any(), (router, kind)
        print(f"OK {router} off {kind} zero events (sanitizer blind spot)")

print("ALL FAULT CONTAINMENT OK")
