"""Share of the traced steps in which no op ran on the device, mean over
the chips: 1 - (union of op intervals / the device's window of whole
steps)."""
from bench import trace as T

NAME = "device_idle"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "tokens_per_s"


def read(run):
    busy = T.busy_share(run.trace)
    return None if busy is None else 100.0 * (1.0 - busy)
