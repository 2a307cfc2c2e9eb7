"""A second block that differs from ``blocks/qwen2.py`` in every part of the
seam — the program's own GPT-2: LayerNorm with bias, a learned position
table, one fused and biased qkv projection, a GELU (tanh form) MLP with
biases, an output head tied to the embedding, no rotary. It proves the seam
wide enough; it is kept at tiny widths for the benchmark's tests, has no
configuration under ``configs/`` and no cell. Published keys are HF GPT-2's
(``n_embd``, ``n_head``, ``n_layer``, ``n_positions``, ``n_inner``).

Leaf names are the program's (``models/gpt2.py::init_layer_params``); every
bias and the position table are drawn away from zero, so that a program that
dropped one is seen.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import roofline
from benchmark.reference import dequant
from benchmark.weights import Leaf

# ------------------------------------------------------------------- shapes


def dims(model: dict) -> dict:
    H, N = int(model["n_embd"]), int(model["n_head"])
    return {"layers": int(model["n_layer"]), "hidden": H,
            "vocab": int(model["vocab_size"]), "kv_heads": N,
            "head_dim": H // N}


def inner(model: dict) -> int:
    return int(model.get("n_inner") or 4 * model["n_embd"])


# ------------------------------------------------------------------ weights

BIAS_STD = 0.1
GAIN_STD = 0.1


def fan_in(x):
    return x * x.shape[-2] ** -0.5


def row_scaled(x):
    """A table of vectors of about unit length: token and position rows weigh
    the same in their sum, and tied logits have about unit variance."""
    return x * x.shape[-1] ** -0.5


def gain(x):
    return 1.0 + GAIN_STD * x


def bias(x):
    return BIAS_STD * x


def layer_leaves(model: dict) -> tuple:
    H, I = int(model["n_embd"]), inner(model)
    return (
        Leaf("ln1_w", (H,), gain), Leaf("ln1_b", (H,), bias),
        Leaf("w_qkv", (H, 3 * H), fan_in, matmul=True),
        Leaf("b_qkv", (3 * H,), bias),
        Leaf("w_proj", (H, H), fan_in, matmul=True),
        Leaf("b_proj", (H,), bias),
        Leaf("ln2_w", (H,), gain), Leaf("ln2_b", (H,), bias),
        Leaf("w_fc", (H, I), fan_in, matmul=True), Leaf("b_fc", (I,), bias),
        Leaf("w_out", (I, H), fan_in, matmul=True), Leaf("b_out", (H,), bias),
    )


def tables(model: dict) -> tuple:
    V, H, P = int(model["vocab_size"]), int(model["n_embd"]), int(model["n_positions"])
    return (
        Leaf("embed", (V, H), row_scaled, vocab_axis=0),
        Leaf("pos_embed", (P, H), row_scaled),
        Leaf("final_norm", (H,), gain),
        Leaf("final_norm_bias", (H,), bias),
    )


# ---------------------------------------------------------------- reference

# Measured on the CPU at the tiny widths of tests/data/tiny_gpt2.json (H 128,
# 4 layers, vocabulary 512; bf16 serving, Pallas in interpret mode), through
# harness.run_cell, 39 scored positions a run (PR 26): sound runs over 12
# seeds (and one with int8 weights, one on a ring of four) read a mean margin
# of at most 0.00019 and a worst of 0.0072; the program served without its
# position table reads a mean of 0.27-0.50 and a worst of 1.5-2.4 (3 seeds),
# without its qkv bias a mean of 0.040-0.18 and a worst of 0.52-0.87
# (test_benchmark.py::test_a_second_block_runs_through_the_harness holds
# both). A dropped b_fc or b_out reads a mean of 0.003 on two seeds of three
# and is NOT told apart at this size. A test's thresholds at a toy size: no
# cell is judged by them.
DELTA_MEAN = 0.004
DELTA_MAX = 0.1


def layer_static(model: dict) -> dict:
    return dict(heads=int(model["n_head"]),
                eps=float(model.get("layer_norm_epsilon", 1e-5)))


def head_static(model: dict) -> dict:
    return dict(eps=float(model.get("layer_norm_epsilon", 1e-5)))


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def layer_forward(h, p, *, heads, eps):
    """One GPT-2 block over a whole sequence h: [S, H], float32."""
    with jax.default_matmul_precision("highest"):
        p = {k: dequant(v) for k, v in p.items()}
        S, H = h.shape
        x = layer_norm(h, p["ln1_w"], p["ln1_b"], eps)
        q, k, v = jnp.split(x @ p["w_qkv"] + p["b_qkv"], 3, axis=-1)
        q, k, v = (t.reshape(S, heads, -1) for t in (q, k, v))
        scores = jnp.einsum("snd,tnd->nst", q, k) / math.sqrt(H // heads)
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, axis=-1), v)
        h = h + attn.reshape(S, H) @ p["w_proj"] + p["b_proj"]
        x = layer_norm(h, p["ln2_w"], p["ln2_b"], eps)
        return h + gelu_new(x @ p["w_fc"] + p["b_fc"]) @ p["w_out"] + p["b_out"]


def embed(tables: dict, ids, *, eps=None):
    """Token row + position row, ids at positions 0..S-1."""
    pos = tables["pos_embed"][: ids.shape[0]]
    return (tables["embed"][ids].astype(jnp.float32)
            + pos.astype(jnp.float32))


def logits(h, tables: dict, *, eps):
    x = layer_norm(h, tables["final_norm"].astype(jnp.float32),
                   tables["final_norm_bias"].astype(jnp.float32), eps)
    return x @ tables["embed"].astype(jnp.float32).T  # the tied head


# -------------------------------------------------------------------- bytes


def layer_weight_bytes(model: dict, weight_dtype: str) -> int:
    H, I = int(model["n_embd"]), inner(model)
    matmul = 3 * H * H + H * H + 2 * H * I
    other = 4 * H + 3 * H + H + I + H  # norms' gains and biases; four biases
    b = matmul * roofline.MATMUL_BYTES[weight_dtype] + other * 2
    if weight_dtype == "int8":
        b += (3 * H + H + I + H) * 2  # one bf16 scale per output channel
    return b


def decode_step_bytes(model: dict, weight_dtype: str, stages: int,
                      live_tokens: float, rec=None, kv_bytes: int = 2) -> float:
    """Layers, the tied head (the embedding table read once as a matmul) and
    the live KV; the position table is a gather of a few rows."""
    return roofline.decode_step_bytes(
        dims(model), layer_weight_bytes(model, weight_dtype), stages,
        live_tokens, kv_bytes,
    )
