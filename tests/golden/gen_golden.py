"""Regenerate the MoE-layer golden fixture (``moe_layer_golden.npz``).

The fixture pins the *exact* (bit-level) outputs of the switch/SMILE layers
across the full ``dispatch_backend x ragged_a2a x sort_impl`` conformance
matrix, plus a low-capacity case that exercises the drop path.  It was first
captured from the pre-pipeline monolithic ``switch_moe``/``smile_moe``
implementations (PR 4 tree), so the pipeline refactor's golden-equivalence
test (``tests/test_pipeline_golden.py``) proves the rewrite is a pure
refactor: bit-identical outputs on every cell.

Bit-level float reproducibility only holds within one (platform, jax
version) pair — both are recorded in the fixture and the test falls back to
tight allclose when they differ from the running environment.

The layer parameters are stored in the fixture too (``p|<router>|<leaf>``),
so the test never redraws them: ``PRNGKey(0)`` draws different numbers
under ``jax_threefry_partitionable=True`` (jax's default since 0.5), and the
recorded outputs were produced under the old ``False`` setting, which
:func:`golden_params` pins.

    PYTHONPATH=src python tests/golden/gen_golden.py            # everything
    PYTHONPATH=src python tests/golden/gen_golden.py --params   # add params,
                                                   # keep recorded outputs
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import MoEConfig
from repro.core import moe as M
from repro.sharding.plan import single_device_plan

PLAN = single_device_plan()
BACKENDS = ("sort", "dense", "dropless")
RAGGED = (True, False)
SORT_IMPLS = ("argsort", "radix")

# the conformance-suite layer shape (ample capacity, nothing drops) plus a
# starved-capacity variant that pins the drop bookkeeping bit-exactly
CASES = {
    "ample": dict(capacity_factor=8.0),
    "starved": dict(capacity_factor=1.0),
}


def layer_cfg(router, backend, ragged, sort_impl, capacity_factor):
    return MoEConfig(num_experts=16, top_k=2, top_g=2, d_ff_expert=32,
                     capacity_factor=capacity_factor, router=router,
                     grid=(4, 4), renorm_gates=True,
                     dispatch_backend=backend, ragged_a2a=ragged,
                     sort_impl=sort_impl)


def golden_params():
    """{router: layer params} drawn from ``PRNGKey(0)`` exactly as the
    recorded outputs were: with the non-partitionable threefry."""
    params = {}
    with jax.threefry_partitionable(False):
        for router in ("switch", "smile"):
            cfg0 = layer_cfg(router, "dense", True, "argsort", 8.0)
            params[router] = M.init_moe_params(jax.random.PRNGKey(0), cfg0,
                                               32, PLAN, glu=False)
    return params


def param_arrays(params) -> dict:
    """Flatten {router: params} to the fixture's ``p|router|leaf`` keys."""
    out = {}
    for router, tree in params.items():
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            name = "/".join(str(k.key) for k in path)
            out[f"p|{router}|{name}"] = np.asarray(leaf)
    return out


def add_params(path):
    """Store the parameters in an existing fixture, leaving its recorded
    inputs, outputs and environment as they are."""
    with np.load(path, allow_pickle=False) as old:
        out = {k: old[k] for k in old.files if not k.startswith("p|")}
    out.update(param_arrays(golden_params()))
    np.savez_compressed(path, **out)
    print(f"wrote {path} (parameters added to the recorded fixture)")


def main(out_path):
    with jax.threefry_partitionable(False):
        x = jax.random.normal(jax.random.PRNGKey(1), (48, 32))
    out = {"x": np.asarray(x)}
    meta = {"jax_version": jax.__version__,
            "platform": jax.default_backend()}
    params = golden_params()
    out.update(param_arrays(params))
    for router in ("switch", "smile"):
        for case, kw in CASES.items():
            for backend in BACKENDS:
                for ragged in RAGGED:
                    for simpl in SORT_IMPLS:
                        cfg = layer_cfg(router, backend, ragged, simpl, **kw)
                        y, st = M.moe_layer(params[router], x, cfg, PLAN,
                                            act="gelu")
                        tag = f"{router}|{case}|{backend}|r{int(ragged)}|{simpl}"
                        out[f"y|{tag}"] = np.asarray(y)
                        out[f"s|{tag}"] = np.asarray(
                            [float(st.lb_loss), float(st.z_loss),
                             float(st.drop_frac)], np.float64)
    np.savez_compressed(out_path, __meta__=np.asarray(
        [meta["jax_version"], meta["platform"]]), **out)
    print(f"wrote {out_path} ({len(out) - 1} arrays, "
          f"jax {meta['jax_version']} on {meta['platform']})")


if __name__ == "__main__":
    # optional path argument: write elsewhere (e.g. to diff a regeneration
    # against the checked-in fixture without clobbering it)
    args = [a for a in sys.argv[1:] if a != "--params"]
    target = args[0] if args else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "moe_layer_golden.npz")
    if "--params" in sys.argv[1:]:
        add_params(target)
    else:
        main(target)
