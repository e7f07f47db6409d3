"""Device time of the ``optimizer`` scope per step, mean over the chips:
the global-norm clip and the LAMB update with its trust-ratio sums
(``train/step.py`` ``train_step_fn``).  Nothing to read where the run did
not map the step's ops to their scopes."""
from bench import scopes as S

NAME = "optimizer_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "optimizer"
MOVES = "tokens_per_s"


def read(run):
    if run.op_scopes is None:
        return None
    return S.scope_ms(run.trace, run.op_scopes, S.in_layers("optimizer"))
