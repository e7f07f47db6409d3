"""Device time of the ``moe`` scope per step, mean over the chips: the MoE
sublayer, forward, backward and recompute (ln2, routing, dispatch, the
expert exchange, the expert FFN, combine; ``models/transformer.py``
``moe_block`` over ``core/pipeline.py``).  Nothing to read where the run
did not map the step's ops to their scopes."""
from bench import scopes as S

NAME = "moe_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "MoE layer"
MOVES = "tokens_per_s"


def read(run):
    if run.op_scopes is None:
        return None
    return S.scope_ms(run.trace, run.op_scopes, S.in_layers("moe"))
