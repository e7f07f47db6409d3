"""``correct`` on CPU at a small size: the reference agrees with the
program where the program computes in float32, and the control and every
fault that a cell can have make ``correct`` come out false."""
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import faults as FT
from bench import harness as H
from bench.tests.tiny import tiny_cell

SEED = 2**31 + 77            # larger than 32 signed bits hold


def _float32_program(monkeypatch):
    """The program with its activations in float32."""
    import repro.models.transformer as TT
    embed = TT.embed_inputs
    monkeypatch.setattr(TT, "embed_inputs",
                        lambda *a, **k: embed(*a, **dict(k, dtype=jnp.float32)))


def _readings(cell, seed):
    prog = H.Program(cell, seed, jax.devices()[:1])
    try:
        return prog.check_steps()
    finally:
        prog.close()


@pytest.mark.parametrize("name", ["smile-3.7b.mlm512", "switch-3.7b.mlm512"])
def test_reference_matches_program_in_float32(name, monkeypatch):
    """With its activations in float32 (the configuration runs them in
    bfloat16), the program's step is the reference's to float32 rounding:
    routing, capacity drops, losses, gradient clipping and LAMB included."""
    _float32_program(monkeypatch)
    cell = tiny_cell(name)
    got = _readings(cell, SEED)
    ref = H.run_reference(cell, SEED, jax.devices()[0])
    g = H.gaps(got, ref)
    assert g["loss_gap"] < 1e-6 and g["grad_gap"] < 1e-4 and g["change_gap"] < 1e-4, g


@pytest.mark.parametrize("name", ["smile-3.7b.mlm512", "switch-3.7b.mlm512"])
def test_control_is_not_correct(name):
    """The reference in float8 (the control) in the program's place fails
    the cell's limits."""
    cell = tiny_cell(name)
    ref = H.run_reference(cell, SEED, jax.devices()[0])
    ctl = H.run_reference(cell, SEED, jax.devices()[0], precision="control")
    g = H.gaps(ctl, ref)
    assert not H.judge(g, cell.workload["limits"]), g


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_fault_under_the_timed_path_is_not_correct(fault):
    """A whole run, past the look for a chip, with the step broken."""
    cell = tiny_cell("smile-3.7b.mlm512")
    out = H.run_cell(cell.name, SEED, 0.5, False, t_start=time.perf_counter(),
                     require_accelerator=False, cell=cell, plant=FT.FAULTS[fault],
                     log=lambda s: None)
    assert out["correct"] is False, out["checks"]
    assert list(out)[-1] == "checks"


def test_mesh_faults_are_not_correct():
    """On four virtual devices, for the four-chip cell with SMILE's routing
    and with Switch's: the program in float32 is the reference's step, and
    the exchange between chips left out, the gradient sync left out, and
    half the batch left out each make a run incorrect."""
    script = Path(__file__).with_name("mesh_faults.py")
    root = Path(H.__file__).resolve().parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    r = subprocess.run([sys.executable, str(script)], env=env, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH FAULTS CAUGHT" in r.stdout, r.stdout[-3000:]


def test_a_next_token_cell_runs_from_new_files_alone(tmp_path, monkeypatch):
    """A next-token (``clm``) cell is a new traffic file and a new cell file:
    a whole run of it, past the look for a chip, comes back ``correct`` with
    no benchmark file edited.  A plumbing check: the configuration is the
    MLM encoder's, program and reference alike bidirectional."""
    import json
    import shutil
    base = tmp_path / "bench"
    shutil.copytree(H.BENCH, base, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    traffic = {"kind": "clm", "batch": 16, "seq": 512, "zipf_a": 1.3, "ngram": 8,
               "doc_len_median": 256, "doc_len_sigma": 1.0}
    (base / "traffic" / "clm16x512.json").write_text(json.dumps(traffic))
    cell = dict(H.load_json("workloads", "smile-3.7b.mlm512"), traffic="clm16x512")
    (base / "workloads" / "smile-3.7b.clm512.json").write_text(json.dumps(cell))
    assert all(p.read_bytes() == b for p, b in before.items())
    cell = tiny_cell("smile-3.7b.clm512", base)
    assert cell.traffic["kind"] == "clm"
    _float32_program(monkeypatch)
    out = H.run_cell(cell.name, SEED, 0.5, False, t_start=time.perf_counter(),
                     require_accelerator=False, cell=cell, log=lambda s: None)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["loss_gap"]["value"] < 1e-5, out["checks"]


def test_scope_map_is_that_of_the_step_that_ran():
    """The traced run's ``op_scopes`` come from the executable the window
    drove (lowered for the batch's own sharding), and name every layer the
    readers read."""
    import numpy as np
    from bench import scopes as S
    cell = tiny_cell("switch-3.7b.mlm512")
    prog = H.Program(cell, SEED, jax.devices()[:1])
    try:
        prog.check_steps()
        got = H._op_scopes(prog)
        ran = prog.step_fn.lower(prog.params, prog.opt_state,
                                 prog.put(prog.loader.get()), np.int32(1))
        assert got == S.op_scopes(ran.compile().as_text())
    finally:
        prog.close()
    layers = {(S.scope_of(op) or (None,))[0] for op in got.values()}
    assert {"optimizer", "moe", "attention", "embed", "lm_head"} <= layers
