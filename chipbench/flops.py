"""Model FLOPs of a train step, counted from a configuration's shapes.

Only what the forward and backward passes require: every matmul once
forward and twice backward (3x), causal attention over the positions a
query may see, the logits against the tied embedding, and for a MoE layer
the router and the ``k`` experts each token is routed to.  Not counted:
rematerialisation (the program recomputes each layer in the backward
pass), capacity padding and dropped tokens of the expert buffers, the
dense dispatch, norms, softmax and the optimizer's elementwise work.

Why not ``compiled.cost_analysis()``: XLA counts the body of a
``lax.scan`` once, not once per trip, so the program's layer scan and
chunked loss are undercounted (2.27e12 for qwen3-1.7b-l4's step against
about 1.34e13 from shapes, in an AOT compile for a described v5e); and it
counts recomputation and padding, which are not model work.
"""
from __future__ import annotations

from typing import Any, Dict


def forward_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward FLOPs per token of a decoder at ``seq_len`` (2 per MAC)."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    H, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    ff, V = c["intermediate_size"], c["vocab_size"]
    proj = d * hd * (H + 2 * Hkv) + H * hd * d
    # Causal: query i attends to i + 1 keys, (S + 1) / 2 on average; QK^T
    # and PV each take H * hd MACs per key.
    attn = 2 * H * hd * (seq_len + 1) / 2
    E, k = c.get("num_local_experts", 0), c.get("num_experts_per_tok", 0)
    ffn = 3 * d * ff * (k if E else 1) + (d * E if E else 0)
    head = d * V
    return 2.0 * (L * (proj + attn + ffn) + head)


def train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward plus backward (twice the forward's matmuls)."""
    return 3.0 * forward_flops_per_token(c, seq_len)
