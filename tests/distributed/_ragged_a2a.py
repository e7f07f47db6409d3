"""Subprocess test: comm.ragged_all_to_all == numpy segment-exchange oracle.

Edge-case matrix on an 8-fake-device (4 x 2) mesh, joint-axes (8-rank) and
single-axis (4-rank per model column) exchanges:

* balanced random counts;
* zero rows to some ranks (including a rank that sends nothing at all);
* ALL rows to one rank (the worst-case skew the static bound must absorb);
* reverse exchange (send_counts = forward recv_counts) restores every
  original segment at its original offset;
* truncation (``allow_truncate=True`` with a ``recv_rows`` bound below the
  worst case): both emulations prefix-truncate at the unclamped offsets
  against a numpy truncation oracle, and ``comm.clamped_segment_counts``
  — the paired clamped sizes the native ``lax.ragged_all_to_all`` path
  uses — reproduces exactly the kept-row matrix the emulations realize,
  and every rank's full ``comm.native_truncation_plan`` argument triple
  satisfies the op's cross-rank paired contract (sender ``s``'s
  ``send_sizes[d]`` == receiver ``d``'s ``recv_sizes[s]``; live segments
  at the unclamped offsets; ``out_off + send_sizes <= bound``).  The
  emulations are the semantic oracle: the installed jax predates the
  native op, so these checks are what keep the native path honest.

Exits non-zero on any mismatch.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.sharding import comm

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
R, d = 24, 5
rng = np.random.default_rng(0)


def oracle(rows, counts):
    """numpy reference: rows (P, R, d) per-rank staging, counts (P, P)
    [src, dst] -> (recv (P, P*R, d), recv_counts (P, P))."""
    P_, = {rows.shape[0], counts.shape[0], counts.shape[1]}
    recv = np.zeros((P_, P_ * R, d), rows.dtype)
    rc = counts.T.copy()                       # [dst, src]
    for dst in range(P_):
        off = 0
        for src in range(P_):
            s0 = counts[src, :dst].sum()
            n = counts[src, dst]
            recv[dst, off:off + n] = rows[src, s0:s0 + n]
            off += n
    return recv, rc


def run_exchange(rows, counts, axes, p, emulation="auto"):
    """Run the exchange under shard_map; rows (P, R, d), counts (P, p)."""
    def f(r, c):
        out, rc = comm.ragged_all_to_all(r[0], c[0], axes, recv_rows=p * R,
                                         emulation=emulation)
        return out[None], rc[None]

    fsm = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(("data", "model")), P(("data", "model"))),
        out_specs=(P(("data", "model")), P(("data", "model"))),
        check_vma=False))
    return fsm(jnp.asarray(rows), jnp.asarray(counts))


def check_joint(counts, label, emulation="auto"):
    """Joint (4x2 = 8-rank) exchange vs oracle + reverse round trip."""
    Pn = 8
    rows = np.zeros((Pn, R, d), np.float32)
    for src in range(Pn):
        n = counts[src].sum()
        assert n <= R, (label, n)
        # distinctive payload: encodes (src, position) so any misrouting
        # or mis-offset shows up as a value mismatch, not just a count one
        rows[src, :n] = (src * 1000
                         + np.arange(n)[:, None] * 10
                         + np.arange(d)[None, :])
    got, got_rc = run_exchange(rows, counts, ("data", "model"), Pn,
                               emulation)
    want, want_rc = oracle(rows, counts)
    np.testing.assert_array_equal(np.asarray(got_rc), want_rc, err_msg=label)
    np.testing.assert_array_equal(np.asarray(got), want, err_msg=label)

    # reverse hop: exchanging back with send_counts = recv_counts must land
    # every segment at its origin offsets (zero elsewhere)
    def rev(r, c):
        fwd, rc = comm.ragged_all_to_all(r[0], c[0], ("data", "model"),
                                         recv_rows=Pn * R,
                                         emulation=emulation)
        back, back_c = comm.ragged_all_to_all(fwd, rc, ("data", "model"),
                                              recv_rows=R,
                                              emulation=emulation)
        return back[None], back_c[None]

    fsm = jax.jit(jax.shard_map(
        rev, mesh=mesh, in_specs=(P(("data", "model")), P(("data", "model"))),
        out_specs=(P(("data", "model")), P(("data", "model"))),
        check_vma=False))
    back, back_c = fsm(jnp.asarray(rows), jnp.asarray(counts))
    np.testing.assert_array_equal(np.asarray(back_c), counts, err_msg=label)
    masked = rows.copy()
    for src in range(Pn):
        masked[src, counts[src].sum():] = 0.0  # staging slack returns as 0
    np.testing.assert_array_equal(np.asarray(back), masked, err_msg=label)
    print(f"OK joint {label} [{emulation}]")


# both emulation strategies must agree with the oracle: the fused
# all_to_all slab (the fast default under jax<0.4.38) and the explicit
# ppermute rotation rounds (the ring-fabric schedule)
for emu in ["a2a", "ppermute"]:
    # ---- balanced random counts ---------------------------------------------
    c = rng.integers(0, R // 8, (8, 8)).astype(np.int32)
    check_joint(c, "balanced", emu)

    # ---- zero rows to some ranks (one rank sends nothing, one starves) -----
    c = rng.integers(0, R // 8, (8, 8)).astype(np.int32)
    c[:, 3] = 0          # nobody sends to rank 3
    c[5, :] = 0          # rank 5 sends nothing
    check_joint(c, "zero-to-some", emu)

    # ---- ALL rows to one rank (worst-case skew; fills the static bound) ----
    c = np.zeros((8, 8), np.int32)
    c[:, 2] = R          # every rank ships its whole staging buffer to rank 2
    check_joint(c, "all-to-one", emu)

# ---- truncation: bounded recv_rows prefix-truncates at unclamped offsets ---
def trunc_oracle(rows, counts, bound):
    """numpy truncation reference: segments land at their UNCLAMPED
    source-major offsets; rows past ``bound`` never materialize.  Returns
    ``(recv (P, bound, d), kept (P, P) [dst, src])``."""
    P_ = rows.shape[0]
    recv = np.zeros((P_, bound, d), rows.dtype)
    kept = np.zeros((P_, P_), np.int32)
    for dst in range(P_):
        off = 0
        for src in range(P_):
            s0 = counts[src, :dst].sum()
            n = counts[src, dst]
            nk = max(0, min(n, bound - off))
            recv[dst, off:off + nk] = rows[src, s0:s0 + nk]
            kept[dst, src] = nk
            off += n
    return recv, kept


def check_truncated(counts, bound, label, emulation):
    Pn = 8
    rows = np.zeros((Pn, R, d), np.float32)
    for src in range(Pn):
        n = counts[src].sum()
        rows[src, :n] = (src * 1000 + np.arange(n)[:, None] * 10
                         + np.arange(d)[None, :])

    def f(r, c):
        out, rc = comm.ragged_all_to_all(r[0], c[0], ("data", "model"),
                                         recv_rows=bound, emulation=emulation,
                                         allow_truncate=True)
        return out[None], rc[None]

    fsm = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P(("data", "model")), P(("data", "model"))),
        out_specs=(P(("data", "model")), P(("data", "model"))),
        check_vma=False))
    got, _ = fsm(jnp.asarray(rows), jnp.asarray(counts))
    want, kept = trunc_oracle(rows, counts, bound)
    np.testing.assert_array_equal(np.asarray(got), want, err_msg=label)
    # the paired clamped sizes the native lax.ragged_all_to_all path uses
    # must describe EXACTLY this truncation: kept[s, d] with row me a
    # rank's clamped send sizes and column me its clamped recv sizes
    kept_helper = np.asarray(
        comm.clamped_segment_counts(jnp.asarray(counts), bound))
    np.testing.assert_array_equal(kept_helper, kept.T, err_msg=label)
    # the full per-rank argument triples of the native path: every rank's
    # plan must satisfy lax.ragged_all_to_all's cross-rank paired contract
    # (sender s's send_sizes[d] == receiver d's recv_sizes[s]) and stay in
    # bounds — exercised numerically because no CI jax has the native op
    plans = [tuple(np.asarray(a) for a in
                   comm.native_truncation_plan(jnp.asarray(counts), r, bound))
             for r in range(Pn)]
    for s in range(Pn):
        send_sizes, out_off, recv_sizes = plans[s]
        np.testing.assert_array_equal(send_sizes, kept[:, s], err_msg=label)
        np.testing.assert_array_equal(recv_sizes, kept[s], err_msg=label)
        for dst in range(Pn):
            assert send_sizes[dst] == plans[dst][2][s], (label, s, dst)
            assert 0 <= out_off[dst], (label, s, dst)
            assert out_off[dst] + send_sizes[dst] <= bound, (label, s, dst)
            if send_sizes[dst]:     # live segments land at unclamped offsets
                assert out_off[dst] == counts[:s, dst].sum(), (label, s, dst)
    print(f"OK truncated {label} [{emulation}]")


for emu in ["a2a", "ppermute"]:
    c = rng.integers(0, R // 8, (8, 8)).astype(np.int32)
    check_truncated(c, 8, "balanced-tight", emu)      # bound below arrivals
    c = np.zeros((8, 8), np.int32)
    c[:, 2] = R                                       # rank 2 overflows hard
    check_truncated(c, 40, "all-to-one-trunc", emu)
    c = rng.integers(0, R // 8, (8, 8)).astype(np.int32)
    check_truncated(c, 8 * R, "bound-no-op", emu)     # bound == worst case

# ---- single-axis exchange: 4 ranks over "data", per model column -----------
# model column is part of the joint sharding but NOT of the exchange: the
# two columns run independent 4-rank exchanges.
Pn = 4
counts = rng.integers(0, R // 4, (8, Pn)).astype(np.int32)
rows = np.zeros((8, R, d), np.float32)
for dev in range(8):
    n = counts[dev].sum()
    rows[dev, :n] = (dev * 1000 + np.arange(n)[:, None] * 10
                     + np.arange(d)[None, :])
got, got_rc = run_exchange(rows, counts, ("data",), Pn)
# oracle per model column: device (i, j) has joint rank i*2+j, data rank i
for col in range(2):
    devs = [i * 2 + col for i in range(Pn)]
    want, want_rc = oracle(rows[devs][:, :R], counts[devs])
    np.testing.assert_array_equal(np.asarray(got_rc)[devs], want_rc)
    np.testing.assert_array_equal(np.asarray(got)[devs], want)
print("OK single-axis")

print("ALL RAGGED A2A OK")
