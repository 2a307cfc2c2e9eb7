"""The plain reference, and the comparison that decides ``correct``.

Qwen2 (the llama block with q/k/v biases) as its authors describe it, in
straightforward ``jax.numpy``, float32, matmuls at ``highest`` precision:
RMSNorm, rotary embedding at base θ (rotate-half form), grouped-query causal
attention with q/k/v bias, SwiGLU, untied output head. No cache, no kernel,
no batching: one sequence, every position at once. It reads nothing from the
program under test; weights come in as plain arrays (an int8 weight as its
``(q, scale)`` pair, dequantised here as ``q · scale``), one layer at a time.

Departure from the published description: a sequence is padded at its END to
a multiple of ``PAD_TO`` so that a handful of shapes compile; under a causal
mask the padding cannot reach an earlier position.

The comparison. The server returns token ids, not logits, so the served
sequence is scored teacher-forced: the reference runs once over prompt +
served tokens, and at every output position t the *margin*

    m_t = max(ref_logits_t) − ref_logits_t[served_t]          (≥ 0)

says how far below the reference's best the served token lies. A rounding
flip between near-ties gives a small margin and does not compound. A run is
correct when the mean margin is at most ``DELTA_MEAN`` and no margin exceeds
``DELTA_MAX``.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

PAD_TO = 256

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


def _dequant(leaf) -> jnp.ndarray:
    """A weight as float32: plain arrays are cast, ``(q, scale)`` pairs are
    multiplied out per output channel."""
    if isinstance(leaf, tuple):
        q, scale = leaf
        return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None, :]
    return leaf.astype(jnp.float32)


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


def _round_kv(x, kv_round):
    """Keys or values as a cache of lower precision would hold them. Only the
    benchmark's tests pass ``kv_round``: they show that serving from such a
    cache under a bf16 label fails the comparison."""
    if kv_round is None:
        return x
    if kv_round in ("int8", "int4"):  # symmetric, one scale per head
        qmax = 127.0 if kv_round == "int8" else 7.0
        scale = jnp.max(jnp.abs(x), axis=(0, 2), keepdims=True) / qmax
        return jnp.round(x / scale) * scale
    return x.astype(kv_round).astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "kv_round")
)
def layer_forward(h, p, *, heads, kv_heads, eps, theta, kv_round=None):
    """One decoder layer over a whole sequence h: [S, H], float32."""
    with jax.default_matmul_precision("highest"):
        p = {k: _dequant(v) for k, v in p.items()}
        S, _ = h.shape
        x = rms_norm(h, p["input_norm"], eps)
        q = (x @ p["wq"] + p["bq"]).reshape(S, heads, -1)
        k = (x @ p["wk"] + p["bk"]).reshape(S, kv_heads, -1)
        v = (x @ p["wv"] + p["bv"]).reshape(S, kv_heads, -1)
        D = q.shape[-1]
        q, k = rotary(q, theta), rotary(k, theta)
        k, v = _round_kv(k, kv_round), _round_kv(v, kv_round)
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


@functools.partial(jax.jit, static_argnames=("eps",))
def margins_from_hidden(h, final_norm, lm_head, served, *, eps):
    """h: [T, H] hidden states at the positions that predict ``served``."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, final_norm.astype(jnp.float32), eps)
        logits = x @ lm_head.astype(jnp.float32)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(logits, axis=-1)


def _as_ref_layer(layer: dict) -> dict:
    """Containers to plain data: any ``(q, scale)`` named tuple becomes a
    plain tuple, so this module needs no type of the program's."""
    return {
        k: (tuple(v) if isinstance(v, tuple) else v) for k, v in layer.items()
    }


def hidden_states(model: dict, get_layer, embed, sequences: list,
                  **overrides) -> list:
    """Final hidden states [S_padded, H] of each id sequence. ``get_layer(l)``
    gives layer l's leaves on the device; only one layer is resident at a
    time. Layers are the outer loop, so each is fetched once for all
    sequences. ``overrides`` (tests only) replace a keyword of
    ``layer_forward``, e.g. a wrong ``theta``."""
    kw = dict(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
    )
    kw.update(overrides)
    hidden = []
    for ids in sequences:
        ids = np.asarray(ids, np.int32)
        padded = jnp.asarray(np.pad(ids, (0, -len(ids) % PAD_TO)))
        hidden.append(embed[padded].astype(jnp.float32))
    for l in range(int(model["num_hidden_layers"])):
        p = _as_ref_layer(get_layer(l))
        hidden = [layer_forward(h, p, **kw) for h in hidden]
        del p
    return hidden


def score(model: dict, get_layer, tables: dict, samples: list) -> dict:
    """Teacher-forced margins of ``samples`` — ``(prompt_ids, served_ids)``
    pairs — under the reference."""
    eps = float(model["rms_norm_eps"])
    seqs = [(len(p), len(s)) for p, s in samples]
    hidden = hidden_states(
        model, get_layer, tables["embed"],
        [np.concatenate([p, s]) for p, s in samples],
    )
    margins, agree = [], []
    for (n_prompt, n_out), h, (_, served) in zip(seqs, hidden, samples):
        rows = h[n_prompt - 1 : n_prompt - 1 + n_out]
        m, best = margins_from_hidden(
            rows, tables["final_norm"], tables["lm_head"],
            jnp.asarray(np.asarray(served, np.int32)), eps=eps,
        )
        margins.append(np.asarray(m))
        agree.append(np.asarray(best) == np.asarray(served))
    m = np.concatenate(margins)
    a = np.concatenate(agree)
    return {
        "positions": int(m.size),
        "samples": len(samples),
        "margin_mean": float(m.mean()),
        "margin_max": float(m.max()),
        "margin_p99": float(np.percentile(m, 99)),
        "argmax_share": float(a.mean()),
    }


def verdict(scored: dict) -> bool:
    return (
        scored["positions"] > 0
        and scored["margin_mean"] <= DELTA_MEAN
        and scored["margin_max"] <= DELTA_MAX
    )
