"""Device time of the ``attention`` scopes per step, mean over the chips:
ln1, the QKV and output projections, the scores and their softmax,
forward, backward and recompute, in every block.  Nothing to read where
the run did not map the step's ops to their scopes."""
from bench import scopes as S

NAME = "attention_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "attention"
MOVES = "tokens_per_s"


def read(run):
    if run.op_scopes is None:
        return None
    return S.scope_ms(run.trace, run.op_scopes, S.in_layers("attention"))
