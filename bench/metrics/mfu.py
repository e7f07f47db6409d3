"""Model FLOP/s utilization: the forward and backward matmul FLOPs the
model needs per token (bench/flops.py) times the tokens per second of the
traced steps (whole step modules over each chip's window of them, from the
device trace), over the chips' bf16 peak (bench/peaks.json)."""
NAME = "mfu"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "train step"
MOVES = "tokens_per_s"


def read(run):
    if run.peak is None or not run.traced_tokens_per_s:
        return None
    return (100.0 * run.flops_per_token * run.traced_tokens_per_s
            / (run.chips * run.peak["bf16_flops"]))
