"""The ``deepseek_v3`` block (GigaChat3.1-702B-A36B publishes this
``model_type``): latent attention (MLA), leading dense layers, then expert
layers routed by sigmoid scores in groups beside a shared expert — with ONE
chip's share of the routed experts and of the vocabulary. Its weights, its
plain reference and its bytes. Found by ``model_type: "deepseek_v3"``.

**What a later builder must know** (``benchmark/README.md`` predates this
block and is not edited):

- *Two kinds of layer* (``layer_kinds``): the first ``first_k_dense_replace``
  are ``dense`` (a SiLU-gated MLP of ``intermediate_size``), the rest ``moe``.
  The tree is ``params["layers"] = {"dense": {...}, "moe": {...}}``.
- *The share.* The configuration's ``n_routed_experts`` is how many routed
  experts are HELD here; ``n_routed_experts_total`` (top level, default the
  same) is how many the router scores and ``ep_rank`` which run of ids this
  chip holds (``rank · held …``). Router, bias, groups and normalisation are
  over ALL; only the held experts' terms are summed, the shared expert is
  computed in full, and what the absent experts would add is left out — in
  the program and here alike. ``vocab_size`` is the slice held (rows
  ``0 … vocab_size - 1``); logits, sampling and the traffic's ids are over it.
- *Leaves are the program's* (``models/deepseek_v3.py``): ``kv_b_proj`` is kept
  as its two per-head factors, each a plain ``[in, out]`` matmul leaf —
  ``w_uk [Nh·nope, kv_lora]`` (head ``h`` = rows ``h·nope …``: ``k_nope[h] =
  c_kv W_uk[h]ᵀ``) and ``w_uv [Nh·v, kv_lora]`` (head ``h`` = rows
  ``h·v …``: ``v[h] = c_kv W_uv[h]ᵀ``), the nope rows and the value rows of
  ``kv_b_proj`` as published — a head's rotated columns of ``wq_b`` /
  ``wkv_a`` are stored DE-INTERLEAVED, so rotation is rotate-half, and
  ``wkv_a`` is ``[H, arena_entry_dim]``: ``[c_kv | k_pe]`` columns, then ZERO
  columns up to whole 128-lane tiles (drawn zero here, read as bytes).
  On the published layout that is ``transformers``' ``rope_interleave: true``
  (``tests/test_deepseek_v3_vs_hf.py`` holds the converter to it).
- The generator draws every non-matmul leaf in the activation dtype, so the
  correction bias's values are bf16-representable; both sides add them in
  float32.

**Weights** (rules as ``blocks/olmoe.py``: matmuls normal × fan-in ** -0.5,
gains 1 + 0.1 n, never 1; ``router_bias`` 0.01 n, never 0 — a dropped one must
not go unseen: at 0.01 three tokens of five still choose other experts without
it. NOT 0.1: on sigmoid scores that makes the busiest of 256 experts ten times
the mean and leaves some idle, where the published bias exists to EVEN the
load (it is the balancing term of ``noaux_tc``); and the share of the pairs
that falls on the 16 experts held here then moves by ±28% a layer from seed to
seed, and with it the decode step by over 1% — measured, PR 34: the 95th
percentile gap of six seeds spread 0.9-1.5% at 0.1). ``wq_a`` and ``wkv_a``
are drawn at TWICE the fan-in scale: at the plain scale ``c_q`` and ``c_kv``
come out with unit variance, their RMSNorms are the identity but for the
gains, and a program that dropped one would go unseen. ``w_uk`` / ``w_uv`` are
scaled by ``kv_lora`` ** -0.5 (the fan-in of ``kv_b_proj``), ``we_down`` by
ONE expert's fan-in. ``wq_b`` stays at the plain fan-in scale ON PURPOSE: the
seeded scores then have a deviation of 2.8 after the published softmax scale
(192^-0.5 · m² = 0.144 on a ``q·k`` of variance 128 + 64·4: ``k_pe`` is the 2×
of ``wkv_a``, un-normed), attention sharp enough to pass every rounding of a
layer's input on magnified. That makes the floor high (the bf16 program's
hidden state is 5.5-8.5% off the reference's after 9 layers, its next token
the reference's best at 80-85% of positions: ``calibrate_deepseek_v3.py
chain``) — and it is what lets the margins READ the arena's precision: an fp8
latent entry reads 5 times the sound mean. Drawn at (8 × fan-in) ** -0.5
(scores of unit variance, as the other blocks' have) the floor falls to a
quarter (mean 0.0027-0.0049, the reference's best at 95%) but an fp8 latent
entry reads 1.4-1.7 times it and no limit has room on both sides (read on the
chip, PR 34; PERF.md section 6).

**Reference.** DeepSeek-V3 as published (HF ``modeling_deepseek_v3.py``),
straightforward ``jax.numpy``, float32, matmuls at ``highest``, one sequence,
every position at once, no cache: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``
→ heads of ``[nope | rope]``; ``[c_kv | k_pe] = x W_kva``, ``c_kv =
RMSNorm(c_kv)``; keys and values DECOMPRESSED per head (``k = [c_kv W_uk[h]ᵀ
| RoPE(k_pe)]``, ``v = c_kv W_uv[h]ᵀ``); causal softmax of ``q kᵀ`` ×
``(nope + rope)^-0.5 · m²``, ``m = 0.1 · mscale_all_dim · ln(factor) + 1``;
YaRN frequencies as ``transformers``' ``_compute_yarn_parameters``; the router
``noaux_tc`` transcribed directly. Departures, all deliberate: attention is
decompressed where the program absorbs (so the two share no arithmetic); the
expert sum is in its DENSE form over the held experts (every held expert for
every position, the unchosen multiplied by zero), sharing no routing, tiles
or kernel with the program; the kept set of the router is built from sorted
thresholds, which keeps more than k on an exact tie (measure zero).

**Bytes** (``decode_step_bytes``): per decode microstep one chip reads every
layer's attention weights (five projections, two absorbed factors), norms,
the dense layers' MLPs, and of each expert layer the router, the shared
expert and the routed experts the step READ (the program's counter,
``experts_read_per_layer``: the mean over ALL layers, the dense ones reading
none, so × ``dims["layers"]`` is a step's expert bytes); the head slice; and
the live latents at what the arena holds per token and layer
(``arena_bytes_per_token_layer``: 1,280 — 576 values padded to 640 lanes of
bf16), counted ONCE, not as K and V.
"""

from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from benchmark import roofline
from benchmark.reference import dequant
from benchmark.weights import Leaf

# ------------------------------------------------------------------- shapes


def total_experts(model: dict) -> int:
    return int(model.get("n_routed_experts_total", model["n_routed_experts"]))


def held_experts(model: dict) -> tuple:
    """``(first id, count)`` of the routed experts held here."""
    held = int(model["n_routed_experts"])
    return int(model.get("ep_rank", 0)) * held, held


def arena_entry_dim(model: dict) -> int:
    """Lanes of one token's arena entry: ``[c_kv | k_pe]`` padded to 128."""
    return -(-(model["kv_lora_rank"] + model["qk_rope_head_dim"]) // 128) * 128


def arena_bytes_per_token_layer(model: dict, kv_bytes: int = 2) -> int:
    return arena_entry_dim(model) * kv_bytes


def dims(model: dict) -> dict:
    """What the shared code needs. ``kv_heads`` and ``head_dim`` are the
    PUBLISHED view (the decompressed keys: 64 heads of nope + rope), as the
    program's ``ModelConfig`` gives them — NOT what the cache holds, which
    is one latent entry a token and layer (``arena_bytes_per_token_layer``).
    So the shared ``roofline.kv_bytes_per_token_layer`` is wrong for this
    block by a factor of 38, and ``decode_step_bytes`` below does not use
    it."""
    return {
        "layers": int(model["num_hidden_layers"]),
        "hidden": int(model["hidden_size"]),
        "vocab": int(model["vocab_size"]),
        "kv_heads": int(model.get("num_key_value_heads",
                                  model["num_attention_heads"])),
        "head_dim": int(model["qk_nope_head_dim"] + model["qk_rope_head_dim"]),
    }


def layer_kinds(model: dict) -> tuple:
    k = int(model.get("first_k_dense_replace", 0))
    return ("dense",) * k + ("moe",) * (int(model["num_hidden_layers"]) - k)


# ------------------------------------------------------------------ weights

ATTN_ORDER = (
    "input_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
    "w_uk", "w_uv", "wo", "post_norm",
)
LEAF_ORDER = {
    "dense": ATTN_ORDER + ("w_gate", "w_up", "w_down"),
    "moe": ATTN_ORDER + (
        "router", "router_bias", "we_gate", "we_up", "we_down",
        "ws_gate", "ws_up", "ws_down",
    ),
}
GAIN_STD = 0.1
BIAS_STD = 0.01
DOWN_SCALE = 2.0


def fan_in(x):
    return x * x.shape[-2] ** -0.5


def down_fan_in(x):
    """``wq_a``, ``wkv_a``: off the scale at which the norm that follows is
    the identity."""
    return DOWN_SCALE * fan_in(x)


def gain(x):
    return 1.0 + GAIN_STD * x


def small(x):
    return BIAS_STD * x


def plain(x):
    return x


def zero_past(rule, columns: int):
    """``rule``, then zero columns from ``columns`` on (``wkv_a``'s pad)."""
    def padded(x):
        return jnp.where(jnp.arange(x.shape[-1]) < columns, rule(x), 0.0)
    return padded


def scaled(fan: int):
    def rule(x):
        return x * fan ** -0.5
    return rule


def leaf_shapes(model: dict) -> dict:
    H, Nh = model["hidden_size"], model["num_attention_heads"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    I, F = model["intermediate_size"], model["moe_intermediate_size"]
    Fs = F * int(model.get("n_shared_experts", 1))
    E, (_, held) = total_experts(model), held_experts(model)
    return {
        "input_norm": (H,), "post_norm": (H,), "q_a_norm": (rq,),
        "kv_a_norm": (rkv,),
        "wq_a": (H, rq), "wq_b": (rq, Nh * (dn + dr)),
        "wkv_a": (H, arena_entry_dim(model)),
        "w_uk": (Nh * dn, rkv), "w_uv": (Nh * dv, rkv), "wo": (Nh * dv, H),
        "w_gate": (H, I), "w_up": (H, I), "w_down": (I, H),
        "router": (H, E), "router_bias": (E,),
        "we_gate": (H, held * F), "we_up": (H, held * F),
        "we_down": (held * F, H),
        "ws_gate": (H, Fs), "ws_up": (H, Fs), "ws_down": (Fs, H),
    }


def layer_leaves(model: dict) -> dict:
    """``{kind: leaves}``, each kind's in the order they are drawn."""
    shapes = leaf_shapes(model)
    rkv, F = model["kv_lora_rank"], model["moe_intermediate_size"]
    rules = {  # both absorbed factors have kv_b_proj's fan-in, kv_lora
        "wq_a": down_fan_in,
        "wkv_a": zero_past(down_fan_in, rkv + model["qk_rope_head_dim"]),
        "w_uk": scaled(rkv), "w_uv": scaled(rkv), "we_down": scaled(F),
    }
    out = {}
    for kind, order in LEAF_ORDER.items():
        leaves = []
        for name in order:
            if name.endswith("_norm"):
                leaves.append(Leaf(name, shapes[name], gain))
            elif name == "router":
                leaves.append(Leaf(name, shapes[name], fan_in))
            elif name == "router_bias":
                leaves.append(Leaf(name, shapes[name], small))
            else:
                leaves.append(Leaf(name, shapes[name],
                                   rules.get(name, fan_in), matmul=True))
        out[kind] = tuple(leaves)
    return out


def tables(model: dict) -> tuple:
    V, H = model["vocab_size"], model["hidden_size"]
    return (
        Leaf("embed", (V, H), plain, vocab_axis=0),
        Leaf("final_norm", (H,), gain),
        Leaf("lm_head", (H, V), fan_in, vocab_axis=1),
    )


# ---------------------------------------------------------------- reference

# Read on the chip, PR 34 (PERF.md section 6 has the runs): whole runs of
# gigachat31_702b_a36b.stream, 8 scored requests a run, 1,100-2,100 output
# positions. Logits of the seeded model have unit variance over the 16,032 ids
# of the slice. ``DELTA_MEAN`` lies between the two readings it must lie
# between, with room on both sides:
# - the LARGEST this program gives (bf16 activations and arena, int8 weights;
#   12 seeds): mean margin 0.0147-0.0221 (mean 0.0185), worst 0.51-1.08,
#   served token = reference argmax at 78.5-83.2% of positions. Far over the
#   other blocks' floor and no fault: the same layers chained over one prompt
#   read a mean of 0.00001 with float32 activations, both latent kernels stand
#   0.18% from a float32 attention over their operands, and a prefill alone
#   reads what a served run reads (calibrate_deepseek_v3.py chain, kernels) — it is the seeded
#   scores' width (the docstring's "Weights") passing bf16's rounding on;
# - the SMALLEST the nearest precision below gives (calibrate_deepseek_v3.py,
#   the same cell, same counts of positions): an fp8 latent entry under the
#   bf16 label (what the arena holds, rounded to e4m3's three mantissa bits)
#   reads 0.1055 / 0.1026 (0.090-0.103 while the bias was drawn at 0.1 n) — not
#   correct, 5.5 / 7 times its seed's sound run (0.0191 / 0.0147).
# What the limit CANNOT tell at 8 requests (read while the bias was drawn at
# 0.1 n): fp8 expert matmuls 0.0207 / 0.0229 beside sound runs of 0.0170 /
# 0.0162-0.0182 at their seeds (+20-35%: 0.4 held experts a layer and token),
# a bf16 router 0.0174 / 0.0166 beside the same — inside the seed-to-seed
# spread; the guard of both is the
# tier-1 logits test (tests/test_deepseek_v3.py: a lower precision fails at
# 2e-4) until the server has a logits tap (PERF.md section 7, e).
# ``DELTA_MAX`` guards against gross errors only, as in blocks/olmoe.py (an fp8
# latent reads 0.99-1.17, about the sound worst): a token drawn blind reads
# ~4; a dropped correction bias, shared expert or kind reads far over the
# toy's limits at tiny widths (benchmark/tests/test_deepseek_v3_block.py).
DELTA_MEAN = 0.04
DELTA_MAX = 2.0


def yarn_inv_freq(dim: int, theta: float, rope_scaling) -> np.ndarray:
    """``transformers``' ``_compute_yarn_parameters``, transcribed; plain
    frequencies without ``rope_scaling``. The cos/sin factor ``mscale /
    mscale_all_dim`` is returned beside them."""
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not rope_scaling:
        return 1.0 / pos_freqs, 1.0
    rs = dict(rope_scaling)
    kind = rs.get("rope_type", rs.get("type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling of type {kind!r} has no reference")
    factor = float(rs["factor"])
    orig = int(rs["original_max_position_embeddings"])
    beta_fast, beta_slow = rs.get("beta_fast") or 32, rs.get("beta_slow") or 1

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if rs.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1 - extrapolation) + (
        1.0 / pos_freqs) * extrapolation
    ms, ms_all = rs.get("mscale"), rs.get("mscale_all_dim")
    if ms and ms_all:
        attn = mscale(factor, ms) / mscale(factor, ms_all)
    else:
        attn = mscale(factor, 1.0)
    return inv, float(attn)


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(model: dict) -> float:
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    rs = model.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        m = mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
        scale *= m * m
    return scale


def _freeze(rs):
    return None if not rs else tuple(sorted(rs.items()))


def layer_static(model: dict) -> dict:
    """The keywords of ``layer_forward`` the published keys fix (alike for
    both kinds; ``kind=`` itself is added by the shared code)."""
    first, held = held_experts(model)
    return dict(
        heads=int(model["num_attention_heads"]),
        nope=int(model["qk_nope_head_dim"]),
        rope=int(model["qk_rope_head_dim"]),
        kv_lora=int(model["kv_lora_rank"]),
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
        rope_scaling=_freeze(model.get("rope_scaling")),
        scale=softmax_scale(model),
        experts=total_experts(model), first_held=first, held=held,
        top_k=int(model["num_experts_per_tok"]),
        n_group=int(model.get("n_group", 1)),
        topk_group=int(model.get("topk_group", 1)),
        routed_scale=float(model.get("routed_scaling_factor", 1.0)),
    )


def head_static(model: dict) -> dict:
    return dict(eps=float(model["rms_norm_eps"]))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotary(x, theta, rope_scaling):
    """x: [S, N, D] at positions 0..S-1, rotate-half."""
    S, _, D = x.shape
    inv, factor = yarn_inv_freq(D, theta, dict(rope_scaling or ()))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :] * factor
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :] * factor
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def router_weights(x, router, bias, *, top_k, n_group, topk_group,
                   routed_scale, use_bias=True):
    """``[S, E]``: an expert's weight where the router keeps it, else 0 —
    ``noaux_tc``: ``s = sigmoid(x W_r)``; the choice on ``s + bias``: a
    group's score is the sum of its two largest, the ``topk_group`` best
    groups stay and the rest are set to 0.0 (as ``transformers`` masks
    them), the ``top_k`` largest are kept; weights are the UNbiased ``s``
    there, over their sum (+1e-20), times ``routed_scale``."""
    S, E = x.shape[0], router.shape[-1]
    s = jax.nn.sigmoid(x @ router)
    choice = s + bias if use_bias else s
    grouped = choice.reshape(S, n_group, E // n_group)
    top2 = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)  # [S, n_group]
    g_kth = jnp.sort(top2, axis=-1)[:, n_group - topk_group]
    keep_group = top2 >= g_kth[:, None]
    choice = jnp.where(keep_group[:, :, None], grouped, 0.0).reshape(S, E)
    kth = jnp.sort(choice, axis=-1)[:, E - top_k]
    kept = jnp.where(choice >= kth[:, None], s, 0.0)
    return kept / (kept.sum(-1, keepdims=True) + 1e-20) * routed_scale


def gated_mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


@functools.partial(
    jax.jit,
    static_argnames=(
        "kind", "heads", "nope", "rope", "kv_lora", "eps", "theta",
        "rope_scaling", "scale", "experts", "first_held", "held", "top_k",
        "n_group", "topk_group", "routed_scale", "kv_round", "router_dtype",
        "use_bias", "use_shared",
    ),
)
def layer_forward(h, p, *, kind, heads, nope, rope, kv_lora, eps, theta,
                  rope_scaling, scale, experts, first_held, held, top_k,
                  n_group, topk_group, routed_scale, kv_round=None,
                  router_dtype=None, use_bias=True, use_shared=True):
    """One layer of ``kind`` over a whole sequence h: [S, H], float32.
    ``kv_round`` (the latent entry as a cache of lower precision would hold
    it), ``router_dtype``, ``use_bias=False`` and ``use_shared=False`` are
    the tests' wrong models."""
    with jax.default_matmul_precision("highest"):
        p = {k: dequant(v) for k, v in p.items()}
        S, H = h.shape
        x = rms_norm(h, p["input_norm"], eps)
        c_q = rms_norm(x @ p["wq_a"], p["q_a_norm"], eps)
        q = (c_q @ p["wq_b"]).reshape(S, heads, nope + rope)
        kv_a = x @ p["wkv_a"]
        c_kv = rms_norm(kv_a[:, :kv_lora], p["kv_a_norm"], eps)
        k_pe = rotary(kv_a[:, None, kv_lora:kv_lora + rope], theta,
                      rope_scaling)
        if kv_round is not None:
            c_kv = c_kv.astype(kv_round).astype(jnp.float32)
            k_pe = k_pe.astype(kv_round).astype(jnp.float32)
        w_uk = p["w_uk"].reshape(heads, nope, kv_lora)
        w_uv = p["w_uv"].reshape(heads, -1, kv_lora)
        k_nope = jnp.einsum("sc,hdc->shd", c_kv, w_uk)
        v = jnp.einsum("sc,hvc->shv", c_kv, w_uv)
        q_pe = rotary(q[..., nope:], theta, rope_scaling)
        qf = jnp.concatenate([q[..., :nope], q_pe], -1)
        kf = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (S, heads, rope))], -1)
        scores = jnp.einsum("snd,tnd->nst", qf, kf) * scale
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("nst,tnv->snv", jax.nn.softmax(scores, axis=-1), v)
        h = h + attn.reshape(S, -1) @ p["wo"]
        x = rms_norm(h, p["post_norm"], eps)
        if kind == "dense":
            return h + gated_mlp(x, p["w_gate"], p["w_up"], p["w_down"])
        xr, wr = x, p["router"]
        if router_dtype is not None:
            xr = xr.astype(router_dtype).astype(jnp.float32)
            wr = wr.astype(router_dtype).astype(jnp.float32)
        kept = router_weights(
            xr, wr, p["router_bias"], top_k=top_k, n_group=n_group,
            topk_group=topk_group, routed_scale=routed_scale,
            use_bias=use_bias,
        )[:, first_held:first_held + held]  # the held experts' weights
        F = p["we_gate"].shape[-1] // held
        act = jax.nn.silu(x @ p["we_gate"]) * (x @ p["we_up"])  # [S, held·F]
        act = (act.reshape(S, held, F) * kept[:, :, None]).reshape(S, -1)
        y = act @ p["we_down"]
        if use_shared:
            y = y + gated_mlp(x, p["ws_gate"], p["ws_up"], p["ws_down"])
        return h + y


def embed(tables: dict, ids, *, eps=None):
    return tables["embed"][ids].astype(jnp.float32)


def logits(h, tables: dict, *, eps):
    x = rms_norm(h, tables["final_norm"].astype(jnp.float32), eps)
    return x @ tables["lm_head"].astype(jnp.float32)


# -------------------------------------------------------------------- bytes


def _matmul_bytes(shape: tuple, weight_dtype: str) -> int:
    """A matmul leaf and, under int8, its one bf16 scale per output channel."""
    b = shape[0] * shape[1] * roofline.MATMUL_BYTES[weight_dtype]
    return b + (shape[1] * 2 if weight_dtype == "int8" else 0)


def attention_bytes(model: dict, weight_dtype: str) -> int:
    """What every layer reads for its attention: five projections, the two
    absorbed factors, four norm gains."""
    sh = leaf_shapes(model)
    b = sum(_matmul_bytes(sh[n], weight_dtype)
            for n in ("wq_a", "wq_b", "wkv_a", "w_uk", "w_uv", "wo"))
    return b + 2 * sum(sh[n][0] for n in (
        "input_norm", "post_norm", "q_a_norm", "kv_a_norm"))


def dense_mlp_bytes(model: dict, weight_dtype: str) -> int:
    sh = leaf_shapes(model)
    return sum(_matmul_bytes(sh[n], weight_dtype)
               for n in ("w_gate", "w_up", "w_down"))


def moe_fixed_bytes(model: dict, weight_dtype: str) -> int:
    """What an expert layer reads whatever it routes: the bf16 router, its
    bias, the shared expert, and ``we_down``'s one scale per channel."""
    sh = leaf_shapes(model)
    b = sum(_matmul_bytes(sh[n], weight_dtype)
            for n in ("ws_gate", "ws_up", "ws_down"))
    b += (sh["router"][0] * sh["router"][1] + sh["router_bias"][0]) * 2
    return b + (sh["we_down"][1] * 2 if weight_dtype == "int8" else 0)


def expert_bytes(model: dict, weight_dtype: str) -> int:
    """One routed expert of one layer: its three matrices, and under int8
    the scales of its gate and up columns."""
    H, F = model["hidden_size"], model["moe_intermediate_size"]
    b = 3 * H * F * roofline.MATMUL_BYTES[weight_dtype]
    return b + (2 * F * 2 if weight_dtype == "int8" else 0)


def experts_read_per_layer(rec, lo=None, hi=None):
    """Mean distinct HELD experts read per layer per decode microstep, over
    ALL of the chip's layers (a dense layer reads none), from the step
    records in ``[lo, hi]`` (default: the traced slice, else the window) —
    so that × ``dims["layers"]`` × ``expert_bytes`` is a step's expert
    bytes. None where the records carry no such counter."""
    if lo is None:
        lo, hi = rec.get("traced") or rec["window"]
    read = steps = layers = 0
    for st in rec.get("steps", ()):
        if not lo <= st["t"] <= hi or not st.get("expert_steps"):
            continue
        read += sum(st["experts_read"])
        steps += st["expert_steps"]
        layers = len(st["experts_read"])
    return read / (steps * layers) if steps else None


def decode_step_bytes(model: dict, weight_dtype: str, stages: int,
                      live_tokens: float, rec=None, kv_bytes: int = 2) -> float:
    """Bytes one chip must read for one decode microstep (the docstring's
    "Bytes"). ``stages`` must be 1: a ring over this model is not run."""
    n = experts_read_per_layer(rec) if rec is not None else None
    if n is None:
        raise ValueError(
            "the records carry no experts_read counter: the bytes of a "
            "decode step of a model with experts cannot be counted"
        )
    if stages != 1:
        raise ValueError("deepseek_v3 bytes are counted for one stage")
    kinds = layer_kinds(model)
    L, n_moe = len(kinds), kinds.count("moe")
    d = dims(model)
    return (
        L * attention_bytes(model, weight_dtype)
        + (L - n_moe) * dense_mlp_bytes(model, weight_dtype)
        + n_moe * moe_fixed_bytes(model, weight_dtype)
        + n * L * expert_bytes(model, weight_dtype)
        + roofline.head_bytes(d)
        + L * live_tokens * arena_bytes_per_token_layer(model, kv_bytes)
    )


def prefill_attn_flops(model: dict, query_tokens: int, key_tokens: int) -> int:
    """Operations of the latent prefill attention of ONE layer as the
    program runs it (absorbed: every head scores ``arena_entry_dim`` lanes
    and sums ``kv_lora_rank``), for ``query_tokens`` queries over
    ``key_tokens`` attended keys in all — for the compute-roofline share of
    ``paged_prefill`` reported in PERF.md section 5."""
    per_pair = 2 * (arena_entry_dim(model) + model["kv_lora_rank"])
    return model["num_attention_heads"] * per_pair * query_tokens * key_tokens
