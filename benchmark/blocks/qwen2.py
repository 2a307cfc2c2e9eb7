"""The Qwen2 block (the llama block with q/k/v biases): its weights, its plain
reference and its bytes. Found by ``model_type: "qwen2"``.

**Weights.** Twelve leaves a layer, in the fixed order of ``LEAF_ORDER`` (the
order is part of the seed: leaf ``i`` of layer ``l`` is drawn from
``fold_in(fold_in(root, l), i)``, ``weights.py``):

- the seven matmul weights: normal, scaled by fan-in ** -0.5; quantised
  under ``weight_dtype: int8``;
- q/k/v biases: normal × 0.1 — NOT zero, or a dropped bias would go unseen;
- norm gains: 1 + normal × 0.1;
- tables: embedding normal; final norm a gain; untied output head normal ×
  hidden ** -0.5 (logits of about unit variance).

**Reference.** Qwen2 as its authors describe it, in straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision: RMSNorm, rotary
embedding at base θ (rotate-half form), grouped-query causal attention with
q/k/v bias, SwiGLU, untied output head. No cache, no kernel, no batching: one
sequence, every position at once. It reads nothing from the program under
test; an int8 weight comes as its ``(q, scale)`` pair.

**Bytes.** A decode microstep on one chip reads this chip's layer weights
once, this chip's share of the output head once, and the live keys and values
of the rows in the step; for the small row counts of serving it is bound by
memory, not by the matrix units (2 · parameters · rows operations against
parameters · bytes of traffic: ~8 operations a byte at 4 rows, against the
v5e's ~240). Every layer reads the same weights whatever the tokens, so the
run's records are not consulted.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import roofline
from benchmark.reference import dequant, round_kv
from benchmark.weights import Leaf

# ------------------------------------------------------------------- shapes


def head_dim(model: dict) -> int:
    return model.get("head_dim") or (
        model["hidden_size"] // model["num_attention_heads"]
    )


def dims(model: dict) -> dict:
    """What the shared code needs of the published keys."""
    return {
        "layers": int(model["num_hidden_layers"]),
        "hidden": int(model["hidden_size"]),
        "vocab": int(model["vocab_size"]),
        "kv_heads": int(model["num_key_value_heads"]),
        "head_dim": int(head_dim(model)),
    }


# ------------------------------------------------------------------ weights

MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LEAF_ORDER = (
    "input_norm", "wq", "wk", "wv", "wo", "post_norm",
    "w_gate", "w_up", "w_down", "bq", "bk", "bv",
)
BIAS_STD = 0.1
GAIN_STD = 0.1


def fan_in(x):
    return x * x.shape[-2] ** -0.5


def gain(x):
    return 1.0 + GAIN_STD * x


def bias(x):
    return BIAS_STD * x


def plain(x):
    return x


def leaf_shapes(model: dict) -> dict:
    H, I = model["hidden_size"], model["intermediate_size"]
    D = head_dim(model)
    Nh, Nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return {
        "input_norm": (H,), "post_norm": (H,),
        "wq": (H, Nh * D), "wk": (H, Nkv * D), "wv": (H, Nkv * D),
        "wo": (Nh * D, H),
        "w_gate": (H, I), "w_up": (H, I), "w_down": (I, H),
        "bq": (Nh * D,), "bk": (Nkv * D,), "bv": (Nkv * D,),
    }


def layer_leaves(model: dict) -> tuple:
    """The leaves of one layer, in the order they are drawn."""
    shapes = leaf_shapes(model)
    out = []
    for name in LEAF_ORDER:
        if name in MATMUL_LEAVES:
            out.append(Leaf(name, shapes[name], fan_in, matmul=True))
        elif name.endswith("_norm"):
            out.append(Leaf(name, shapes[name], gain))
        else:
            out.append(Leaf(name, shapes[name], bias))
    return tuple(out)


def tables(model: dict) -> tuple:
    """The model's tables, in the order they are drawn; ``vocab_axis`` is the
    dimension a ring splits among its chips."""
    V, H = model["vocab_size"], model["hidden_size"]
    return (
        Leaf("embed", (V, H), plain, vocab_axis=0),
        Leaf("final_norm", (H,), gain),
        Leaf("lm_head", (H, V), fan_in, vocab_axis=1),
    )


# ---------------------------------------------------------------- reference

# Logits of the seeded model have about unit variance over a vocabulary of
# 152k, so the best and second-best logit lie ~0.2 apart and bf16 serving
# legitimately flips between them at 2-5% of positions. Measured on the chip
# (PERF.md section 2): bf16 activations, int8 or bf16 weights, read a mean
# margin of 0.0003-0.0008 and a worst of 0.03-0.08 over 22 runs; an fp8 arena
# under a bf16 label reads a mean of 0.0019; a dropped bias or a wrong rotary
# base reads a mean above 0.1. An int8 arena (0.00045) errs by less than bf16
# arithmetic does and cannot be told apart from tokens alone.
DELTA_MEAN = 0.0015
DELTA_MAX = 0.25


def layer_static(model: dict) -> dict:
    """The keywords of ``layer_forward`` the published keys fix."""
    return dict(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
    )


def head_static(model: dict) -> dict:
    """The keywords of ``embed`` and ``logits``."""
    return dict(eps=float(model["rms_norm_eps"]))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotary(x, theta):
    """x: [S, N, D] at positions 0..S-1."""
    S, _, D = x.shape
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(
    jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "kv_round")
)
def layer_forward(h, p, *, heads, kv_heads, eps, theta, kv_round=None):
    """One decoder layer over a whole sequence h: [S, H], float32."""
    with jax.default_matmul_precision("highest"):
        p = {k: dequant(v) for k, v in p.items()}
        S, _ = h.shape
        x = rms_norm(h, p["input_norm"], eps)
        q = (x @ p["wq"] + p["bq"]).reshape(S, heads, -1)
        k = (x @ p["wk"] + p["bk"]).reshape(S, kv_heads, -1)
        v = (x @ p["wv"] + p["bv"]).reshape(S, kv_heads, -1)
        D = q.shape[-1]
        q, k = rotary(q, theta), rotary(k, theta)
        k, v = round_kv(k, kv_round), round_kv(v, kv_round)
        group = heads // kv_heads
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("snd,tnd->nst", q, k) / math.sqrt(D)
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, axis=-1), v)
        h = h + attn.reshape(S, -1) @ p["wo"]
        x = rms_norm(h, p["post_norm"], eps)
        mlp = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
        return h + mlp


def embed(tables: dict, ids, *, eps=None):
    """Hidden states [S, H] that enter layer 0, for ids at positions 0..S-1."""
    return tables["embed"][ids].astype(jnp.float32)


def logits(h, tables: dict, *, eps):
    """h: [T, H] final hidden states → [T, V]. Traced inside the comparison's
    jitted call, under its ``highest`` precision."""
    x = rms_norm(h, tables["final_norm"].astype(jnp.float32), eps)
    return x @ tables["lm_head"].astype(jnp.float32)


# -------------------------------------------------------------------- bytes


def layer_params(model: dict) -> dict:
    """Parameters of one decoder layer: ``{"matmul": n, "other": n}``."""
    H, I, D = model["hidden_size"], model["intermediate_size"], head_dim(model)
    q, kv = model["num_attention_heads"] * D, model["num_key_value_heads"] * D
    matmul = H * q + 2 * H * kv + q * H + 3 * H * I
    return {"matmul": matmul, "other": 2 * H + q + 2 * kv,
            "out_channels": q + 2 * kv + H + 2 * I + H}


def layer_weight_bytes(model: dict, weight_dtype: str) -> int:
    p = layer_params(model)
    b = p["matmul"] * roofline.MATMUL_BYTES[weight_dtype] + p["other"] * 2
    if weight_dtype == "int8":
        b += p["out_channels"] * 2  # one bf16 scale per output channel
    return b


def decode_step_bytes(model: dict, weight_dtype: str, stages: int,
                      live_tokens: float, rec=None, kv_bytes: int = 2) -> float:
    """Bytes one chip must read for one decode microstep: its layers, its
    share of the head (untied; the embedding is a gather of a few rows), and
    the live KV (``live_tokens`` = the sum of the context lengths of the rows
    in the step) of its layers. ``rec``, the run's records, is unused: a
    dense block reads the same weights whatever the step held."""
    return roofline.decode_step_bytes(
        dims(model), layer_weight_bytes(model, weight_dtype), stages,
        live_tokens, kv_bytes,
    )
