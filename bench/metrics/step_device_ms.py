"""Device time of one train step: the duration of the jitted step
program's module, found by its name in the trace, mean over each chip's
whole traced steps and over the chips."""
NAME = "step_device_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "train step"
MOVES = "tokens_per_s"


def read(run):
    per_dev = [sum(b - a for _, a, b in d.step_events) / d.steps
               for d in run.trace.devices if d.steps]
    if not per_dev:
        return None
    return 1e3 * sum(per_dev) / len(per_dev)
