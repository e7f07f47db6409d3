"""Run the four-chip cell at a small size on four virtual CPU devices with
each fault a four-chip cell can have planted, and require ``correct``
false.  Started by test_correct.py in a process of its own (the device
count is fixed when JAX starts)."""
import sys
import time

from bench import faults as FT
from bench import harness as H
from bench.tests.tiny import tiny_cell


def main() -> int:
    cell = tiny_cell("smile-3.7b.mlm512.4chip")
    for fault in ("no_exchange", "no_grad_sync", "half_batch"):
        out = H.run_cell(cell.name, 2**31 + 5, 0.5, False, t_start=time.perf_counter(),
                         require_accelerator=False, cell=cell,
                         plant=FT.FAULTS[fault], log=lambda s: None)
        print(fault, out["checks"], flush=True)
        if out["correct"] is not False:
            print(f"{fault}: correct came out {out['correct']}")
            return 1
    print("MESH FAULTS CAUGHT")
    return 0


if __name__ == "__main__":
    sys.exit(main())
