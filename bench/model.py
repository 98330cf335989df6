"""A configuration file (Hugging Face ``config.json`` keys, as run) as the
program's ``ModelConfig``.

The file names the program's architecture id under ``arch``.  Keys the
program models are mapped onto its fields; keys it does not model must hold
the value that the program's arithmetic implies (a multiplier of 1, untied
embeddings), so that the file states what is run."""
from __future__ import annotations

import dataclasses
import math

# Hugging Face key -> ModelConfig field
FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
}
# keys the program has no switch for, and the value its arithmetic implies
FIXED = {
    "tie_word_embeddings": False,
    "hidden_act": "silu",
    "attention_bias": False,
    "mlp_bias": False,
    "embedding_multiplier": 1.0,
    "residual_multiplier": 1.0,
    "logits_scaling": 1.0,
}


def is_moe(mc: dict) -> bool:
    return mc.get("num_local_experts", 0) > 0


def head_dim(mc: dict) -> int:
    return mc["hidden_size"] // mc["num_attention_heads"]


def padded_vocab(mc: dict) -> int:
    m = mc["vocab_pad_multiple"]
    return -(-mc["vocab_size"] // m) * m


def check_fixed(mc: dict) -> None:
    for k, v in FIXED.items():
        if k in mc and mc[k] != v:
            raise ValueError(f"{mc['name']}: {k}={mc[k]!r} is not what the "
                             f"program runs ({v!r})")
    if "attention_multiplier" in mc and not math.isclose(
            mc["attention_multiplier"], head_dim(mc) ** -0.5):
        raise ValueError(f"{mc['name']}: the program scales attention by "
                         f"1/sqrt(head_dim)")


def program_config(mc: dict):
    """The program's ``ModelConfig`` for configuration file ``mc``."""
    from repro.configs import get_config
    from repro.configs.base import VOCAB_PAD_TO

    check_fixed(mc)
    if mc["vocab_pad_multiple"] != VOCAB_PAD_TO:
        raise ValueError("the program pads the vocabulary to a multiple of "
                         f"{VOCAB_PAD_TO}")
    kw = {f: mc[k] for k, f in FIELDS.items() if k in mc}
    kw["head_dim"] = head_dim(mc)
    kw["tie_embeddings"] = False
    if is_moe(mc):
        kw["moe_d_ff"] = kw["d_ff"] = mc["intermediate_size"]
        kw["router_aux_loss"] = mc["router_aux_loss_coef"]
    else:
        kw["d_ff"] = mc["intermediate_size"]
    cfg = dataclasses.replace(get_config(mc["arch"]), **kw)
    if cfg.vocab_padded != padded_vocab(mc):
        raise ValueError("padded vocabulary differs")
    return cfg
