"""What a step must move, from shapes: the functions behind every roofline
share. Kept with the benchmark so that no PR that claims a gain can change
how its gain is counted.

A decode microstep on one chip reads this chip's layer weights once, this
chip's share of the output head once, and the live keys and values of the
rows in the step; for the small row counts of serving it is bound by memory,
not by the matrix units (2 · parameters · rows operations against parameters
· bytes of traffic: ~8 operations a byte at 4 rows, against the v5e's ~240).
"""

from __future__ import annotations

MATMUL_BYTES = {"int8": 1, "bf16": 2, "f32": 4}


def head_dim(model: dict) -> int:
    return model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"]
    )


def layer_params(model: dict) -> dict:
    """Parameters of one decoder layer: ``{"matmul": n, "other": n}``."""
    H, I, D = model["hidden_size"], model["intermediate_size"], head_dim(model)
    q, kv = model["num_attention_heads"] * D, model["num_key_value_heads"] * D
    matmul = H * q + 2 * H * kv + q * H + 3 * H * I
    return {"matmul": matmul, "other": 2 * H + q + 2 * kv,
            "out_channels": q + 2 * kv + H + 2 * I + H}


def layer_weight_bytes(model: dict, weight_dtype: str) -> int:
    p = layer_params(model)
    b = p["matmul"] * MATMUL_BYTES[weight_dtype] + p["other"] * 2
    if weight_dtype == "int8":
        b += p["out_channels"] * 2  # one bf16 scale per output channel
    return b


def head_bytes(model: dict, table_bytes: int = 2) -> int:
    """The untied output head (the embedding is a gather of a few rows)."""
    return model["hidden_size"] * model["vocab_size"] * table_bytes


def kv_bytes_per_token_layer(model: dict, kv_bytes: int = 2) -> int:
    return 2 * model["num_key_value_heads"] * head_dim(model) * kv_bytes


def decode_step_bytes(model: dict, weight_dtype: str, stages: int,
                      live_tokens: float, kv_bytes: int = 2) -> float:
    """Bytes one chip must read for one decode microstep: its layers, its
    share of the head, and the live KV (``live_tokens`` = the sum of the
    context lengths of the rows in the step) of its layers."""
    layers = model["num_hidden_layers"] / stages
    return (
        layers * layer_weight_bytes(model, weight_dtype)
        + head_bytes(model) / stages
        + layers * live_tokens * kv_bytes_per_token_layer(model, kv_bytes)
    )
