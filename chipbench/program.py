"""The program's model for the decoders of ``chipbench/reference.py``:
dense GQA and token-choice MoE, SwiGLU feed-forwards, with or without
qk-norm.  A configuration names this file under ``"program"``."""
from __future__ import annotations

from typing import Any, Dict


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (silu) feed-forwards are driven")
    experts = c.get("num_local_experts", 0)
    return ModelConfig(
        name=c["name"], family="moe" if experts else "dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        vocab=c["vocab_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        qk_norm=c["qk_norm"], rope_base=float(c["rope_theta"]),
        d_ff=c["intermediate_size"], mlp_type="swiglu",
        n_experts=experts, experts_top_k=c.get("num_experts_per_tok", 0),
        capacity_factor=c.get("moe_capacity_factor", 1.25),
        tie_embeddings=c["tie_word_embeddings"],
        norm_eps=float(c["rms_norm_eps"]), dtype=c["compute_dtype"])
