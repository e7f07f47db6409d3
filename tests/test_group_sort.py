"""The group-sort primitive under every dispatch hop.

``repro.kernels.ops.group_sort`` (and its two implementations — the
one-pass Pallas counting sort ``group_sort_pallas`` and the packed-argsort
oracle ``ref.group_sort_ref``) must be a *stable* sort: property tests
assert permutation validity, stability (equal keys preserve arrival
order), bit-identical agreement with ``jnp.argsort(..., stable=True)``,
and exact prefix counts, across adversarial key distributions —
all-one-group, empty groups, A == 0, E == 1, non-power-of-two A, and
pathological tile boundaries.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.radix_sort import group_sort_pallas

# named adversarial key distributions, indexed by a drawn integer
_DISTRIBUTIONS = ("uniform", "one_group", "two_ends", "sorted", "reversed",
                  "skewed")


def _make_keys(rng, dist: str, A: int, D: int) -> np.ndarray:
    if dist == "uniform":
        return rng.integers(0, D, A)
    if dist == "one_group":                     # all keys equal: pure stability
        return np.full(A, int(rng.integers(0, D)))
    if dist == "two_ends":                      # empty groups in the middle
        return np.where(rng.uniform(size=A) < 0.5, 0, D - 1)
    if dist == "sorted":
        return np.sort(rng.integers(0, D, A))
    if dist == "reversed":
        return np.sort(rng.integers(0, D, A))[::-1].copy()
    # "skewed": one hot group plus a sprinkle everywhere
    hot = int(rng.integers(0, D))
    keys = rng.integers(0, D, A)
    keys[rng.uniform(size=A) < 0.8] = hot
    return keys


def _check_group_sort(keys: np.ndarray, D: int, ranks, starts):
    """Assert the full (ranks, starts) contract against numpy oracles."""
    A = keys.shape[0]
    ranks = np.asarray(ranks)
    starts = np.asarray(starts)
    # permutation validity
    assert sorted(ranks.tolist()) == list(range(A))
    # stability + bit-identical agreement with the stable argsort: a stable
    # integer sort is unique, so the rank array is fully determined
    order = np.argsort(keys, kind="stable")
    want = np.empty(A, np.int64)
    want[order] = np.arange(A)
    np.testing.assert_array_equal(ranks, want)
    # equal keys preserve arrival order (implied by the above, asserted
    # directly so a future contract change can't silently weaken it)
    for d in np.unique(keys):
        np.testing.assert_array_equal(np.sort(ranks[keys == d]),
                                      ranks[keys == d])
    # exclusive prefix counts over the whole domain
    np.testing.assert_array_equal(
        starts, np.searchsorted(keys[order], np.arange(D + 1)))


@settings(deadline=None, max_examples=25)
@given(a=st.integers(0, 500), d=st.integers(1, 12),
       dist_i=st.integers(0, len(_DISTRIBUTIONS) - 1),
       block_i=st.integers(0, 2), seed=st.integers(0, 2**31 - 1))
def test_group_sort_property(a, d, dist_i, block_i, seed):
    """Pallas counting sort == argsort oracle == numpy stable argsort,
    bit for bit, on adversarial distributions and awkward tile splits."""
    rng = np.random.default_rng(seed)
    keys = _make_keys(rng, _DISTRIBUTIONS[dist_i], a, d)
    kj = jnp.asarray(keys, jnp.int32)
    block = (8, 32, 256)[block_i]               # incl. many-tile splits
    r_p, s_p = group_sort_pallas(kj, d, block=block, interpret=True)
    r_r, s_r = ref.group_sort_ref(kj, d)
    _check_group_sort(keys, d, r_p, s_p)
    np.testing.assert_array_equal(np.asarray(r_p), np.asarray(r_r))
    np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))


@pytest.mark.parametrize("a,d", [
    (0, 5),          # empty input
    (7, 1),          # single-group domain (E == 1)
    (1, 1),          # single element, single group
    (333, 4),        # non-power-of-two A spanning several tiles
    (256, 3),        # exact tile multiple
    (257, 3),        # one past a tile boundary
])
def test_group_sort_edge_shapes(a, d):
    rng = np.random.default_rng(a * 31 + d)
    keys = rng.integers(0, d, a)
    kj = jnp.asarray(keys, jnp.int32)
    for impl_out in (group_sort_pallas(kj, d, block=128, interpret=True),
                     ref.group_sort_ref(kj, d)):
        _check_group_sort(keys, d, *impl_out)


def test_group_sort_empty_groups():
    """Groups with zero keys must still get well-formed prefix entries."""
    keys = jnp.asarray([5, 5, 0, 5, 0], jnp.int32)        # groups 1-4, 6+ empty
    for ranks, starts in (group_sort_pallas(keys, 8, block=8, interpret=True),
                          ref.group_sort_ref(keys, 8)):
        _check_group_sort(np.asarray(keys), 8, ranks, starts)
        np.testing.assert_array_equal(np.asarray(starts),
                                      [0, 2, 2, 2, 2, 2, 5, 5, 5])


def test_ops_wrapper_impl_switch(monkeypatch):
    """ops.group_sort: "argsort" -> oracle; "radix" -> the Pallas kernel at
    or above RADIX_MIN_ROWS, oracle fallback below; unknown impl raises;
    both routes bit-identical."""
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 9, 64), jnp.int32)
    with pytest.raises(ValueError, match="unknown sort_impl"):
        kops.group_sort(keys, 9, impl="quantum")
    r_a, s_a = kops.group_sort(keys, 9, impl="argsort")
    # below the threshold radix falls back to the oracle
    r_f, s_f = kops.group_sort(keys, 9, impl="radix")
    np.testing.assert_array_equal(np.asarray(r_a), np.asarray(r_f))
    # force the kernel on the same small input: still bit-identical
    monkeypatch.setattr(kops, "RADIX_MIN_ROWS", 0)
    r_k, s_k = kops.group_sort(keys, 9, impl="radix")
    np.testing.assert_array_equal(np.asarray(r_a), np.asarray(r_k))
    np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_k))


def test_group_sort_rejects_empty_domain():
    keys = jnp.zeros((4,), jnp.int32)
    for fn in (lambda: ref.group_sort_ref(keys, 0),
               lambda: group_sort_pallas(keys, 0, interpret=True)):
        with pytest.raises(ValueError, match="num_keys"):
            fn()


def test_group_sort_large_jitted():
    """A dispatch-sized jitted cell through the real kernel path of the ops
    wrapper (A >= RADIX_MIN_ROWS), against the oracle."""
    rng = np.random.default_rng(3)
    A, D = max(kops.RADIX_MIN_ROWS, 1024), 65
    keys = jnp.asarray(rng.integers(0, D, A), jnp.int32)
    radix = jax.jit(lambda k: kops.group_sort(k, D, impl="radix"))
    r_k, s_k = radix(keys)
    r_a, s_a = ref.group_sort_ref(keys, D)
    np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_a))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_a))
