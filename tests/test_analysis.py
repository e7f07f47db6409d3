"""Static analyzer: seeded-bad fixtures must be flagged, live tree clean.

Each fixture seeds exactly one hazard class from the analyzer's rule set
and asserts the matching rule (and only it) fires; the final test runs the
full CLI against the live codebase in a subprocess (it needs 8 fake
devices, which the unit-test process must not have) and asserts exit 0.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import Finding, format_findings, jaxpr_lint, pallas_lint, repo_lint
from repro.sharding import comm

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


# ---------------------------------------------------------------- jaxpr pass
def test_cond_one_sided_psum_flagged():
    """A cond whose true branch psums and whose false branch doesn't."""
    def f(x, flag):
        return lax.cond(flag,
                        lambda v: lax.psum(v, "data"),
                        lambda v: v, x)

    closed = jax.make_jaxpr(f, axis_env=[("data", 8)])(
        jnp.ones((4,)), jnp.bool_(True))
    got = jaxpr_lint.check_cond_congruence(closed.jaxpr, entry="fixture")
    assert len(got) == 1 and got[0].rule == "cond-collective-mismatch"
    assert "psum over ('data',)" in got[0].message


def test_uniform_cond_waives_congruence():
    """The same asymmetry through comm.uniform_cond is intentionally waived."""
    def f(x, flag):
        return comm.uniform_cond(flag,
                                 lambda v: lax.psum(v, "data"),
                                 lambda v: v, x)

    closed = jax.make_jaxpr(f, axis_env=[("data", 8)])(
        jnp.ones((4,)), jnp.bool_(True))
    assert jaxpr_lint.check_cond_congruence(closed.jaxpr) == []


def test_unknown_axis_and_int_dtype_rules():
    def f(c):
        return lax.psum(c, "data")

    closed = jax.make_jaxpr(f, axis_env=[("data", 8)])(
        jnp.ones((4,), jnp.int64) if jax.config.jax_enable_x64
        else jnp.arange(4, dtype=jnp.int32))
    sites = jaxpr_lint.collect_collectives(closed.jaxpr)
    assert len(sites) == 1
    # axis rule: the traced axis name is missing from a disjoint mesh spec
    got = jaxpr_lint.check_axis_names(sites, mesh_axes=("model",))
    assert len(got) == 1 and got[0].rule == "unknown-axis-name"
    # dtype rule fires on a synthetic site with an int64 operand
    bad = jaxpr_lint.CollectiveSite(
        prim="all_to_all", axes=("data",), in_types=("int64[8]",),
        path="/shard_map", file=None, line=None)
    got = jaxpr_lint.check_count_dtypes([bad])
    assert len(got) == 1 and got[0].rule == "collective-int-dtype"
    assert jaxpr_lint.check_count_dtypes(sites) == []


# --------------------------------------------------------------- pallas pass
def _trace_pallas(fn, *args):
    closed = jax.make_jaxpr(fn)(*args)
    eqns = list(pallas_lint._pallas_eqns(closed.jaxpr))
    assert len(eqns) == 1
    return eqns[0]


def test_oversized_vmem_block_flagged():
    """One 16 MiB f32 block in + out: 2x double-buffered = 64 MiB >> 16."""
    def f(x):
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(
            k, grid=(2,),
            in_specs=[pl.BlockSpec((1, 2048, 2048), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, 2048, 2048), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((2, 2048, 2048), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=True)(x)

    eqn = _trace_pallas(f, jnp.zeros((2, 2048, 2048), jnp.float32))
    got = pallas_lint.lint_pallas_call(eqn, name="fixture")
    assert [g.rule for g in got] == ["vmem-budget"]
    # a budget large enough clears it
    assert pallas_lint.lint_pallas_call(eqn, name="fixture",
                                        vmem_budget=1 << 30) == []


def test_vmem_budget_counts_lane_padding():
    """An 8-wide f32 block fills 8 of 128 lanes: 512 KiB of data, double
    buffered in and out, occupies 8 MiB of VMEM once tiled."""
    def f(x):
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(
            k, grid=(2,),
            in_specs=[pl.BlockSpec((1, 4096, 8), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, 4096, 8), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((2, 4096, 8), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=True)(x)

    eqn = _trace_pallas(f, jnp.zeros((2, 4096, 8), jnp.float32))
    got = pallas_lint.lint_pallas_call(eqn, name="fixture",
                                       vmem_budget=4 << 20)
    assert [g.rule for g in got] == ["vmem-budget"]
    assert "8.0 MiB" in got[0].message
    assert pallas_lint.lint_pallas_call(eqn, name="fixture",
                                        vmem_budget=9 << 20) == []


def test_scratch_across_parallel_axis_flagged():
    """Accumulating output revisited across an axis marked parallel."""
    def f(x):
        def k(x_ref, o_ref):
            o_ref[...] = o_ref[...] + x_ref[...]
        return pl.pallas_call(
            k, grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=True)(x)

    eqn = _trace_pallas(f, jnp.zeros((32, 128), jnp.float32))
    got = pallas_lint.lint_pallas_call(eqn, name="fixture")
    assert [g.rule for g in got] == ["grid-race"]
    assert "axis 0" in got[0].message


def test_missing_semantics_and_oob_flagged():
    def f(x):
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(
            k, grid=(4,),
            # off-by-one: block i+1 walks past the final block of x
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i + 1, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
            interpret=True)(x)

    eqn = _trace_pallas(f, jnp.zeros((32, 128), jnp.float32))
    rules = {g.rule for g in pallas_lint.lint_pallas_call(eqn, name="fixture")}
    assert rules == {"index-map-oob", "missing-dimension-semantics"}


@pytest.mark.parametrize("rows,width", [(256, 768), (8, 128)])
def test_single_row_block_flagged(rows, width):
    """A (1, d) block of a (T, d) array (a row gather) or a (1, bt) block of
    an (n, bt) array (a key tile): interpret mode runs both, Mosaic refuses
    them.  The same rows laid out (T, 1, d) are clean."""
    def copy(block, index, shape):
        def f(x):
            def k(x_ref, o_ref):
                o_ref[...] = x_ref[...]
            spec = pl.BlockSpec(block, index)
            return pl.pallas_call(
                k, grid=(rows,), in_specs=[spec], out_specs=spec,
                out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel",)),
                interpret=True)(x)
        return _trace_pallas(f, jnp.zeros(shape, jnp.float32))

    eqn = copy((1, width), lambda i: (i, 0), (rows, width))
    got = pallas_lint.lint_pallas_call(eqn, name="fixture")
    assert [g.rule for g in got] == ["tile-alignment"] * 2
    assert "second-to-last block dim 1" in got[0].message
    eqn = copy((1, 1, width), lambda i: (i, 0, 0), (rows, 1, width))
    assert pallas_lint.lint_pallas_call(eqn, name="fixture") == []


# ----------------------------------------------------------------- repo pass
def test_unregistered_config_knob_flagged(tmp_path):
    src = open(os.path.join(SRC, "repro", "common", "config.py")).read()
    anchor = "    num_experts:"
    assert anchor in src
    seeded = src.replace(
        anchor, "    totally_unregistered_knob: int = 0\n" + anchor, 1)
    p = tmp_path / "config.py"
    p.write_text(seeded)
    got = repo_lint.check_config_registry(str(p))
    assert len(got) == 1 and got[0].rule == "unregistered-config-knob"
    assert "totally_unregistered_knob" in got[0].message
    # the pristine file is clean (the live tree's own guarantee)
    clean = tmp_path / "clean_config.py"
    clean.write_text(src)
    assert repo_lint.check_config_registry(str(clean)) == []


def test_rogue_all_to_all_flagged(tmp_path):
    p = tmp_path / "rogue.py"
    p.write_text(
        "from jax import lax\n\n"
        "def leak(x):\n"
        "    return lax.all_to_all(x, 'data', split_axis=0, concat_axis=0)\n")
    got = repo_lint.check_collective_callsites([str(p)])
    assert len(got) == 1 and got[0].rule == "rogue-collective"
    assert got[0].line == 4
    # the same call inside a file named sharding/comm.py is allowed
    d = tmp_path / "sharding"
    d.mkdir()
    (d / "comm.py").write_text(p.read_text())
    assert repo_lint.check_collective_callsites([str(d / "comm.py")]) == []


def test_kernel_twin_rule(tmp_path):
    (tmp_path / "ops.py").write_text("from k import good_pallas\n")
    (tmp_path / "ref.py").write_text("def good_ref(x):\n    return x\n")
    (tmp_path / "k.py").write_text(
        "def good_pallas(x):\n    return x\n\n"
        "def orphan_pallas(x):\n    return x\n")
    got = repo_lint.check_kernel_twins(str(tmp_path))
    rules = sorted(g.rule for g in got)
    assert rules == ["kernel-missing-ref", "kernel-missing-wrapper"]
    assert all("orphan_pallas" in g.message for g in got)


# ------------------------------------- dynamic twin of the int32 boundary rule
def test_comm_count_boundary_dtype_assert():
    good = jnp.zeros((4,), jnp.int32)
    assert comm.exchange_counts(good, None) is good
    with pytest.raises(TypeError, match="int32 at the collective boundary"):
        comm.exchange_counts(good.astype(jnp.int16), None)
    with pytest.raises(TypeError, match="int32 at the collective boundary"):
        comm.ragged_all_to_all(jnp.zeros((8, 4)), good.astype(jnp.float32),
                               None, recv_rows=8)


# ------------------------------------------------------------- driver + live
def test_finding_format():
    f = Finding("pallas", "vmem-budget", "too big", "a/b.py", 7)
    assert f.format() == "[pallas] vmem-budget: too big (a/b.py:7)"
    assert format_findings([]) == "no findings"
    assert format_findings([f]).endswith("1 finding(s)")


def test_cli_exit_code_plumbing(monkeypatch):
    from repro.launch import analyze
    monkeypatch.setattr(repo_lint, "run", lambda log=None: [])
    assert analyze.main(["--pass", "repo", "-q"]) == 0
    monkeypatch.setattr(
        repo_lint, "run",
        lambda log=None: [Finding("repo", "rogue-collective", "seeded")])
    assert analyze.main(["--pass", "repo", "-q"]) == 1


def test_live_codebase_passes_clean():
    """The full analyzer over the real tree: all passes, exit 0."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-m", "repro.launch.analyze", "-q"],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, (
        f"analyzer flagged the live tree:\nSTDOUT:\n{p.stdout[-3000:]}\n"
        f"STDERR:\n{p.stderr[-3000:]}")
    assert "no findings" in p.stdout
