"""Device time of the ``embed`` and ``lm_head`` scopes per step, mean over
the chips: the token embedding, the final norm, the head and the
cross-entropy with its sums.  Nothing to read where the run did not map
the step's ops to their scopes."""
from bench import scopes as S

NAME = "lm_head_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "embedding and LM head"
MOVES = "tokens_per_s"


def read(run):
    if run.op_scopes is None:
        return None
    return S.scope_ms(run.trace, run.op_scopes, S.in_layers("embed", "lm_head"))
