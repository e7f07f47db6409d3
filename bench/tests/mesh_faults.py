"""The four-chip cell at a small size on four virtual CPU devices, with
SMILE's routing and with Switch's: with its activations in float32 the
program's step is the reference's, and with each fault a four-chip cell can
have planted a run comes out not ``correct``.  Started by test_correct.py
in a process of its own (the device count is fixed when JAX starts)."""
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

from bench import faults as FT
from bench import harness as H
from bench.tests.tiny import tiny_cell

SEED = 2**31 + 5


def cells() -> list:
    smile = tiny_cell("smile-3.7b.mlm512.4chip")
    switch = tiny_cell("switch-3.7b.mlm512").config
    return [smile, dataclasses.replace(smile, name="switch-3.7b.4chip",
                                       config=switch)]


def float32_gaps(cell) -> dict:
    import repro.models.transformer as TT
    embed = TT.embed_inputs
    TT.embed_inputs = lambda *a, **k: embed(*a, **dict(k, dtype=jnp.float32))
    try:
        prog = H.Program(cell, SEED, jax.devices()[:cell.chips])
        try:
            got = prog.check_steps()
        finally:
            prog.close()
    finally:
        TT.embed_inputs = embed
    return H.gaps(got, H.run_reference(cell, SEED, jax.devices()[0]))


def main() -> int:
    for cell in cells():
        name = cell.name
        g = float32_gaps(cell)
        print(name, "float32", g, flush=True)
        if not (g["loss_gap"] < 1e-5 and g["grad_gap"] < 1e-4
                and g["change_gap"] < 1e-4):
            print(f"{name}: the float32 program differs from the reference")
            return 1
        for fault in ("no_exchange", "no_grad_sync", "half_batch"):
            out = H.run_cell(cell.name, SEED, 0.5, False, t_start=time.perf_counter(),
                             require_accelerator=False, cell=cell,
                             plant=FT.FAULTS[fault], log=lambda s: None)
            print(name, fault, out["checks"], flush=True)
            if out["correct"] is not False:
                print(f"{name} {fault}: correct came out {out['correct']}")
                return 1
    print("MESH FAULTS CAUGHT")
    return 0


if __name__ == "__main__":
    sys.exit(main())
