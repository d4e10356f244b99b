"""Model FLOPs of a dense decoder, from its configuration file.

The arithmetic of ``repro.launch.roofline.model_flops`` (2 N per token
for the weights, plus 4 * kv_len * heads * head_dim per token per layer
for QK^T and PV), copied here so the yardstick stays fixed.  N counts the
weights that take part in a matrix product: every layer's projections
and MLP, and the output head; the input embedding is a lookup and is
not counted.
"""
from __future__ import annotations


def matmul_params(model: dict) -> int:
    d, ff, v = (model["hidden_size"], model["intermediate_size"],
                model["vocab_size"])
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return model["num_hidden_layers"] * per_layer + d * v


def decode_flops(model: dict, tokens: int, kv_tokens: int) -> float:
    """FLOPs of decode steps that made ``tokens`` tokens, whose lanes
    attended ``kv_tokens`` positions in all (summed over lanes and steps)."""
    h, hd = model["num_attention_heads"], model["head_dim"]
    attn = 4.0 * kv_tokens * h * hd * model["num_hidden_layers"]
    return 2.0 * matmul_params(model) * tokens + attn
