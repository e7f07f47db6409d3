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
    import repro.models.transformer as TT
    embed = TT.embed_inputs
    monkeypatch.setattr(TT, "embed_inputs",
                        lambda *a, **k: embed(*a, **dict(k, dtype=jnp.float32)))
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
    """On four virtual devices: the exchange between chips left out, the
    gradient sync left out, and half the batch left out, each make the
    four-chip cell's run incorrect."""
    script = Path(__file__).with_name("mesh_faults.py")
    root = Path(H.__file__).resolve().parent.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    r = subprocess.run([sys.executable, str(script)], env=env, capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH FAULTS CAUGHT" in r.stdout, r.stdout[-3000:]
