"""A benchmark cell at a size a CPU test run can hold: the cell's own
configuration and traffic with every width and count cut down."""
import copy

from bench import harness as H


def tiny_cell(name: str, base=H.BENCH) -> H.Cell:
    c = H.load_cell(name, base)
    cfg = copy.deepcopy(c.config)
    cfg["model"].update(d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
                        vocab_size=512)
    cfg["moe"].update(num_experts=8, d_ff_expert=128)
    workload = dict(c.workload, trace_steps=3)
    traffic = dict(c.traffic, batch=4 * c.chips, seq=32)
    return H.Cell(name, workload, cfg, traffic)
