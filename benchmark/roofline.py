"""What a step must move, from shapes: what every block's byte count shares.
Kept with the benchmark so that no PR that claims a gain can change how its
gain is counted. The count of ONE block's decode microstep — its layers'
weights, which of them a step touched — is the block's
(``blocks/<model_type>.py::decode_step_bytes``); these take the block's
``dims(model)``.
"""

from __future__ import annotations

MATMUL_BYTES = {"int8": 1, "bf16": 2, "f32": 4}


def head_bytes(dims: dict, table_bytes: int = 2) -> int:
    """The output head, one hidden x vocabulary table read once a step (tied
    or not; the embedding lookup is a gather of a few rows)."""
    return dims["hidden"] * dims["vocab"] * table_bytes


def kv_bytes_per_token_layer(dims: dict, kv_bytes: int = 2) -> int:
    return 2 * dims["kv_heads"] * dims["head_dim"] * kv_bytes


def decode_step_bytes(dims: dict, layer_bytes: float, stages: int,
                      live_tokens: float, kv_bytes: int = 2) -> float:
    """One chip's reads for one decode microstep, given what ONE layer's
    weights cost it (the block's count): its layers, its share of the head,
    and the live KV (``live_tokens`` = the sum of the context lengths of the
    rows in the step) of its layers."""
    layers = dims["layers"] / stages
    return (
        layers * layer_bytes
        + head_bytes(dims) / stages
        + layers * live_tokens * kv_bytes_per_token_layer(dims, kv_bytes)
    )
