"""Multi-device coverage via subprocesses (8 fake CPU devices each).

The unit-test process itself must keep ONE device (Pallas interpret-mode
kernels and smoke tests rely on it), so every shard_map test runs in a
subprocess with its own XLA_FLAGS.
"""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)


def _run(script: str, timeout: int = 900):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(HERE, "distributed",
                                                     script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    if p.returncode != 0:
        raise AssertionError(
            f"{script} failed:\nSTDOUT:\n{p.stdout[-3000:]}\n"
            f"STDERR:\n{p.stderr[-3000:]}")
    return p.stdout


def test_ragged_all_to_all_oracle():
    out = _run("_ragged_a2a.py")
    assert "ALL RAGGED A2A OK" in out


def test_moe_layer_equivalence():
    out = _run("_moe_equiv.py")
    assert "ALL MOE EQUIV OK" in out


def test_recv_bound_factor():
    out = _run("_recv_bound.py")
    assert "ALL RECV BOUND OK" in out


def test_train_step_equivalence():
    out = _run("_train_equiv.py", timeout=1800)
    assert "ALL TRAIN EQUIV OK" in out


def test_decode_equivalence():
    out = _run("_decode_equiv.py", timeout=1800)
    assert "ALL DECODE EQUIV OK" in out


def test_zero1_equivalence():
    out = _run("_zero1_equiv.py", timeout=1800)
    assert "ZERO1 EQUIV OK" in out


def test_fault_containment():
    out = _run("_faults.py", timeout=1800)
    assert "ALL FAULT CONTAINMENT OK" in out


def test_collectives_sit_under_their_scopes():
    out = _run("_scopes.py")
    assert "SCOPES OK" in out
