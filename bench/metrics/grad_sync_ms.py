"""Device time of every all-reduce, per step, mean over the chips: the
gradient sync over the replicated axes, and with it the tensor-parallel
activation sums and the loss and norm reductions, which the trace does not
tell apart.  Nothing to read where no such op ran (one chip)."""
from bench import trace as T

NAME = "grad_sync_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "all-reduces: gradient sync and tensor-parallel sums"
MOVES = "tokens_per_s"
KINDS = ("all-reduce",)


def read(run):
    return T.collective_ms(run.trace, KINDS)
