"""The OLMoE block (the llama block with a q/k RMSNorm and sparse experts in
place of the dense MLP): its weights, its plain reference and its bytes.
Found by ``model_type: "olmoe"``.

**Weights.** Twelve leaves a layer, in the fixed order of ``LEAF_ORDER``
(names and shapes are the program's, ``models/llama.init_layer_params``):

- the four attention matmuls (``wq`` and ``wk`` at TWICE the fan-in scale:
  at the plain scale q and k come out with unit variance, the q/k norm is
  the identity but for its gains, and a program that dropped it would go
  unseen; at twice, its scores without the norm are four times too sharp),
  and the layer's experts as ONE block-sparse
  MLP of width E·F — ``we_gate``, ``we_up`` ``[H, E·F]`` and ``we_down``
  ``[E·F, H]``, expert ``e`` being columns (rows) ``e·F … (e+1)·F``: normal,
  scaled by fan-in ** -0.5 (``we_down`` by an EXPERT's fan-in F, not the
  leaf's E·F rows: a token contracts over the F rows of each chosen expert);
  quantised under ``weight_dtype: int8`` (one scale per output channel, so
  ``we_down``'s scale spans its experts);
- ``router`` ``[H, E]``: normal × fan-in ** -0.5 (logits of about unit
  variance: the top 8 of 64 hold ~0.35 of the mass), never quantised;
- norm gains ``input_norm``, ``post_norm``, ``q_norm``, ``k_norm``: 1 +
  normal × 0.1 — not 1, or a dropped norm would go unseen;
- tables: embedding normal; final norm a gain; untied head normal ×
  hidden ** -0.5.

**Reference.** OLMoE as published (``modeling_olmoe.py``), in straightforward
``jax.numpy``, float32, matmuls at ``highest``: RMSNorm; q, k, v projections;
an RMSNorm over the WHOLE projected width of q and of k, then the heads are
split and rotated (rotate-half, base θ); causal softmax attention; the router
``softmax_float32(x Wr)`` over all experts, the ``top_k`` largest ``p_e`` kept
AS THEY ARE (``norm_topk_prob: false``; renormalised when the file says
true); ``h += Σ_e p_e · (silu(x Wg_e) ⊙ (x Wu_e)) Wd_e``. Departures, both
deliberate: the expert sum is computed in its DENSE form — all E experts for
every position, the unchosen multiplied by zero — so that it shares no
routing, grouping or kernel with the program; and the kept set is "every
``p_e`` at least the k-th largest", which keeps more than k on an exact tie
(measure zero in float32). No cache, no batching: one sequence, every
position at once.

**Bytes.** A decode microstep reads the attention weights, router and norms
of every layer whole, the output head once, the live KV — and of the experts
only those the step's rows chose: the mean number of distinct experts read
per layer per decode microstep comes from the program's counter in the run's
records (``StepRecord.experts_read``), not from an expectation.
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

MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "we_gate", "we_up", "we_down")
LEAF_ORDER = (
    "input_norm", "wq", "wk", "wv", "wo", "post_norm",
    "router", "we_gate", "we_up", "we_down", "q_norm", "k_norm",
)
GAIN_STD = 0.1


def fan_in(x):
    return x * x.shape[-2] ** -0.5


QK_SCALE = 2.0


def qk_fan_in(x):
    """``wq``, ``wk``: off the scale at which the q/k norm is the identity."""
    return QK_SCALE * fan_in(x)


def gain(x):
    return 1.0 + GAIN_STD * x


def plain(x):
    return x


def expert_fan_in(experts: int):
    """``we_down [E·F, H]``: scaled by ONE expert's fan-in F."""
    def rule(x):
        return x * (x.shape[-2] // experts) ** -0.5
    return rule


def leaf_shapes(model: dict) -> dict:
    H, F, E = (model["hidden_size"], model["intermediate_size"],
               model["num_experts"])
    D = head_dim(model)
    Nh, Nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return {
        "input_norm": (H,), "post_norm": (H,),
        "wq": (H, Nh * D), "wk": (H, Nkv * D), "wv": (H, Nkv * D),
        "wo": (Nh * D, H),
        "router": (H, E),
        "we_gate": (H, E * F), "we_up": (H, E * F), "we_down": (E * F, H),
        "q_norm": (Nh * D,), "k_norm": (Nkv * D,),
    }


def layer_leaves(model: dict) -> tuple:
    """The leaves of one layer, in the order they are drawn."""
    shapes = leaf_shapes(model)
    out = []
    for name in LEAF_ORDER:
        if name == "we_down":
            out.append(Leaf(name, shapes[name],
                            expert_fan_in(int(model["num_experts"])),
                            matmul=True))
        elif name in MATMUL_LEAVES:
            rule = qk_fan_in if name in ("wq", "wk") else fan_in
            out.append(Leaf(name, shapes[name], rule, matmul=True))
        elif name == "router":
            out.append(Leaf(name, shapes[name], fan_in))
        else:
            out.append(Leaf(name, shapes[name], gain))
    return tuple(out)


def tables(model: dict) -> tuple:
    V, H = model["vocab_size"], model["hidden_size"]
    return (
        Leaf("embed", (V, H), plain, vocab_axis=0),
        Leaf("final_norm", (H,), gain),
        Leaf("lm_head", (H, V), fan_in, vocab_axis=1),
    )


# ---------------------------------------------------------------- reference

# Read on the chip, PR 27 (PERF.md section 6). Logits of the seeded model have
# about unit variance over a vocabulary of 50,304; bf16 serving flips between
# the best and second best at 2-4% of positions. All readings are whole runs
# of olmoe_1b_7b.backlog: 8 scored requests, 780-1,290 output positions.
# ``DELTA_MEAN`` lies between the two readings it must lie between:
# - the LARGEST this program gives (bf16 activations and arena, int8 weights;
#   22 seeds): mean margin 0.00015-0.00045 (mean 0.00029, sd 0.00008, which
#   is what 1,000 positions leave of a per-position sd of 0.0025), worst
#   0.020-0.066, served token = reference argmax at 96.1-98.8% of positions;
# - the SMALLEST the nearest precision below gives, at the same position
#   counts (benchmark/tests/calibrate_olmoe.py, three seeds whose sound runs
#   read 0.00031 / 0.00045 / 0.00024): fp8 expert matmuls (rows, codes and
#   gated activation at e4m3's three mantissa bits, float32 accumulation)
#   0.00082 / 0.00083 / 0.00058 — not correct. An fp8 arena under the bf16
#   label reads 0.00057, 0.00063, 0.00106, 0.00133 (210-394 positions a run:
#   its decode step is three times slower here; pooled 0.00093 over 1,392) —
#   not correct, and the arena's type check says so too.
# What the limit CANNOT tell at 8 requests: a bf16 router (logits multiplied
# out in bfloat16) reads 0.00034 / 0.00054 / 0.00035 at those three seeds —
# +0.00003 to +0.0001 over its seed's sound run, inside the seed-to-seed sd.
# Telling that shift by margins needs ~40 times the positions (~330 scored
# requests; a window finishes 13). The guard of the router's precision is the
# tier-1 logits test (tests/test_olmoe.py: a bf16 router fails at 2e-5) until
# the server has a logits tap (PERF.md section 7). An int8 arena reads
# 0.00038 and cannot be told apart either (as on Qwen2; the type check does).
# The worst margin separates none of them (fp8: 0.035-0.100); its limit
# guards against gross errors only: a zero router reads a mean of 0.16 and a
# dropped q/k norm 0.57 at tiny widths (benchmark/tests/test_olmoe_block.py).
DELTA_MEAN = 0.00055
DELTA_MAX = 0.25


def layer_static(model: dict) -> dict:
    """The keywords of ``layer_forward`` the published keys fix."""
    return dict(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
        experts=int(model["num_experts"]),
        top_k=int(model["num_experts_per_tok"]),
        renorm=bool(model.get("norm_topk_prob", False)),
    )


def head_static(model: dict) -> dict:
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


def router_weights(x, router, top_k: int, renorm: bool):
    """``[S, E]``: the router's probability where an expert is kept, else 0."""
    p = jax.nn.softmax(x @ router, axis=-1)
    kth = jnp.sort(p, axis=-1)[:, p.shape[-1] - top_k]
    kept = jnp.where(p >= kth[:, None], p, 0.0)
    if renorm:
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return kept


@functools.partial(
    jax.jit,
    static_argnames=("heads", "kv_heads", "eps", "theta", "experts", "top_k",
                     "renorm", "kv_round", "qk_norm", "router_dtype"),
)
def layer_forward(h, p, *, heads, kv_heads, eps, theta, experts, top_k,
                  renorm=False, kv_round=None, qk_norm=True,
                  router_dtype=None):
    """One decoder layer over a whole sequence h: [S, H], float32.
    ``qk_norm=False``, another ``top_k``, ``renorm`` and ``router_dtype`` (the
    router's inputs rounded to a lower precision) are the tests' wrong
    models."""
    with jax.default_matmul_precision("highest"):
        p = {k: dequant(v) for k, v in p.items()}
        S, H = h.shape
        x = rms_norm(h, p["input_norm"], eps)
        q, k, v = x @ p["wq"], x @ p["wk"], x @ p["wv"]
        if qk_norm:
            q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
        q = q.reshape(S, heads, -1)
        k = k.reshape(S, kv_heads, -1)
        v = v.reshape(S, kv_heads, -1)
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
        xr, wr = x, p["router"]
        if router_dtype is not None:
            xr = xr.astype(router_dtype).astype(jnp.float32)
            wr = wr.astype(router_dtype).astype(jnp.float32)
        kept = router_weights(xr, wr, top_k, renorm)  # [S, E]
        F = p["we_gate"].shape[-1] // experts
        act = jax.nn.silu(x @ p["we_gate"]) * (x @ p["we_up"])  # [S, E·F]
        act = (act.reshape(S, experts, F) * kept[:, :, None]).reshape(S, -1)
        return h + act @ p["we_down"]


def embed(tables: dict, ids, *, eps=None):
    return tables["embed"][ids].astype(jnp.float32)


def logits(h, tables: dict, *, eps):
    x = rms_norm(h, tables["final_norm"].astype(jnp.float32), eps)
    return x @ tables["lm_head"].astype(jnp.float32)


# -------------------------------------------------------------------- bytes


def dense_layer_bytes(model: dict, weight_dtype: str) -> int:
    """What every decode microstep reads of one layer whatever it routes: the
    attention matmuls (and their scales), the router and the four norms in
    bf16, and ``we_down``'s one scale per output channel."""
    H, D = model["hidden_size"], head_dim(model)
    q, kv = model["num_attention_heads"] * D, model["num_key_value_heads"] * D
    b = (H * q + 2 * H * kv + q * H) * roofline.MATMUL_BYTES[weight_dtype]
    b += (H * model["num_experts"] + 2 * H + q + kv) * 2
    if weight_dtype == "int8":
        b += (q + 2 * kv + H) * 2 + H * 2
    return b


def expert_bytes(model: dict, weight_dtype: str) -> int:
    """One expert of one layer: its three matrices, and under int8 the scales
    of its gate and up columns."""
    H, F = model["hidden_size"], model["intermediate_size"]
    b = 3 * H * F * roofline.MATMUL_BYTES[weight_dtype]
    if weight_dtype == "int8":
        b += 2 * F * 2
    return b


def experts_read_per_layer(rec, lo=None, hi=None):
    """Mean distinct experts read per layer per decode microstep, from the
    step records in ``[lo, hi]`` (default: the traced slice, else the
    window); None where the records carry no such counter."""
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
    """Bytes one chip must read for one decode microstep: its layers'
    attention weights, routers and norms whole, of the experts the mean
    number the run's decode microsteps really read per layer (each distinct
    expert once), its share of the head, and the live KV."""
    n = experts_read_per_layer(rec) if rec is not None else None
    if n is None:
        raise ValueError(
            "the records carry no experts_read counter: the bytes of a "
            "decode step of a model with experts cannot be counted"
        )
    layer = dense_layer_bytes(model, weight_dtype) + n * expert_bytes(
        model, weight_dtype
    )
    return roofline.decode_step_bytes(
        dims(model), layer, stages, live_tokens, kv_bytes
    )
