"""Device time of the expert exchange: all-to-all family collectives
(all-to-all, ragged-all-to-all, collective-permute), per step, mean over
the chips.  Nothing to read where no such op ran (one chip)."""
from bench import trace as T

NAME = "exchange_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "expert exchange"
MOVES = "tokens_per_s"
KINDS = ("all-to-all", "ragged-all-to-all", "collective-permute")


def read(run):
    return T.collective_ms(run.trace, KINDS)
