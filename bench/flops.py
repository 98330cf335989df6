"""Model FLOPs from a configuration's shapes: the operations that the
forward and backward passes require, not what a program computes (no
recomputation, no capacity padding, no masked-out attention, no vocabulary
padding).  Training counts 6 per matmul parameter touched per token (2 for
the forward pass, 4 for the backward), plus causal attention."""
from __future__ import annotations


def matmul_params_per_token(mc: dict) -> int:
    """Matmul parameters one token passes through: the attention
    projections, the feed-forward (for a mixture of experts its router and
    its ``num_experts_per_tok`` experts), and the LM head.  The embedding is
    a lookup."""
    d = mc["hidden_size"]
    nh, nkv = mc["num_attention_heads"], mc["num_key_value_heads"]
    hd = d // nh
    ff = mc["intermediate_size"]
    attn = 2 * d * nh * hd + 2 * d * nkv * hd
    if mc.get("num_local_experts", 0):
        mlp = d * mc["num_local_experts"] + mc["num_experts_per_tok"] * 3 * d * ff
    else:
        mlp = 3 * d * ff
    return mc["num_hidden_layers"] * (attn + mlp) + d * mc["vocab_size"]


def attention_flops_per_token(mc: dict, seq: int) -> int:
    """Causal attention of a ``seq``-long sequence, forward and backward,
    averaged over its tokens: (seq + 1) / 2 keys per query, 4 * heads *
    head_dim per key forward (scores and values), times 3."""
    d, nh = mc["hidden_size"], mc["num_attention_heads"]
    hd = d // nh
    return mc["num_hidden_layers"] * 6 * nh * hd * (seq + 1)


def train_flops_per_token(mc: dict, seq: int) -> int:
    return 6 * matmul_params_per_token(mc) + attention_flops_per_token(mc, seq)
