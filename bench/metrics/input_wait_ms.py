"""Host time the loop spent taking a batch from the loader and copying it
to the device, mean per window step (the benchmark's own timer)."""
NAME = "input_wait_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "data pipeline"
MOVES = "tokens_per_s"


def read(run):
    return run.input_wait_ms
