"""Model FLOPs of a training step, counted from the configuration's shapes.

What the model requires per token, forward and backward (three times the
forward): the attention projections and scores, the dense FFNs, one expert
FFN per token (top-1, no capacity padding), the routers, and the LM head.
Recomputation under remat, padding rows and elementwise work are not
counted, so the share of the peak this gives is a model FLOP/s utilization.
"""
from __future__ import annotations


def forward_flops_per_token(cfg: dict, seq: int) -> int:
    m, moe = cfg["model"], cfg["moe"]
    d, H = m["d_model"], m["num_heads"]
    hd = d // H
    L = m["num_layers"]
    n_moe = L // moe["every_n_layers"]
    n_dense = L - n_moe
    attn = 2 * 4 * d * H * hd + 2 * 2 * seq * H * hd
    dense_ffn = 2 * 2 * d * m["d_ff"]
    expert_ffn = moe["top_k"] * 2 * 2 * d * moe["d_ff_expert"]
    if moe["router"] == "smile":
        router = 2 * d * (moe["grid"][0] + moe["num_experts"] // moe["grid"][0])
    else:
        router = 2 * d * moe["num_experts"]
    head = 2 * d * m["vocab_size"]
    return L * attn + n_dense * dense_ffn + n_moe * (expert_ffn + router) + head


def train_flops_per_token(cfg: dict, seq: int) -> int:
    return 3 * forward_flops_per_token(cfg, seq)
