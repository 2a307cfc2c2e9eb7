"""The Ouro block (ByteDance's LoopLM family; ``model_type: "ouro"``): a LOOPED
stack of sandwich-norm layers — its weights, its plain reference and its
bytes. Found by ``model_type: "ouro"``.

**The model** (ISSUE 60; the released ``modeling_ouro.py``; what the published
configuration leaves to that module is the configuration file's ``assumed``).
``N(x; g)`` is RMSNorm at ``rms_norm_eps`` with gain ``g``; L =
``num_hidden_layers``, T = ``total_ut_steps``, theta = ``early_exit_threshold``:

```
layer_l(h):   a = Attn_l(N(h; g1a_l));  h = h + N(a; g1b_l)    # a norm on the branch's INPUT and OUTPUT
              m = MLP_l(N(h; g2a_l));   h = h + N(m; g2b_l)
model(ids):   h = E[ids]
              for t in 0..T-1:                                  # the SAME L layers' weights in every pass
                  for l in 0..L-1:  h = layer_l(h)              # pass t attends pass t's keys
                  h = N(h; g_final);  s_t = h                   # closes the pass: the NORMED state enters pass t+1
                  g_t = sigmoid(s_t . w_gate + b_gate)
              p_t = g_t prod_{u<t} (1 - g_u)  for t < T-1;   p_{T-1} = prod_{u<T-1} (1 - g_u)
              exit = first t with sum_{u<=t} p_u >= theta, else T-1
              logits = s_exit W_head                            # no norm here: s_exit is closed already
```

``Attn``: ``num_attention_heads`` query and ``num_key_value_heads`` key/value
heads of ``head_dim``, NO bias, rotary over the whole head at ``rope_theta``
(rotate-half), causal softmax at ``head_dim ** -0.5``, full attention in every
layer. ``MLP``: ``W_down(silu(x W_gate) * x W_up)``. Untied tables. Every pass
runs for every token whatever the gate says.

**Weights.** Eleven leaves a layer in the fixed order of ``LEAF_ORDER`` (leaf
``i`` of layer ``l`` from ``fold_in(fold_in(root, l), i)``, ``weights.py``):
the seven matmul weights, normal scaled by fan-in ** -0.5 (quantised under
``weight_dtype: int8``); the two INPUT norms' gains 1 + 0.1 n — never 1, or a
norm too many would go unseen (an RMSNorm of an RMSNorm at gain 1 is the first
again); the two OUTPUT norms' gains ``OUT_GAIN`` (1 + 0.1 n) = 0.25 (1 + 0.1
n): at gains of 1 every branch adds a unit-RMS update to a state that a pass
starts at unit RMS, and the seeded stack, the SAME random layers applied four
times, amplifies bf16's rounding like an iterated random map — a sound bf16
program then reads a mean margin of 0.094 against this float32 reference,
served token = reference argmax at 58% (my chip run, PR 60: seed 2147483659;
48 layers x 4 passes at hidden 512 on the CPU read the same, 0.145 and 54%,
where 48 x 1 reads 0.0015 and 12 x 4 0.0016), and no lower precision could
be told from it. At 0.25 a pass's 96 branch outputs are still 86% of its
closed state's variance (1 + 96 x 0.0625 = 7) — the layers, not the carried
state, are the model — and a sound run reads what a one-pass model reads.
The layers are drawn ONCE: ``dims()["layers"]`` is L whatever T.
Tables: embedding normal; final norm a gain; untied head normal x hidden **
-0.5; the gate's ``[H]`` vector normal x hidden ** -0.5 (``s . w`` of about
unit variance: the sigmoid far from saturated) and its bias 0.1 n — never zero.

**Reference.** The equations above in plain ``jax.numpy``, float32, matmuls
at ``highest``: ``layer_forward`` over a whole sequence (no cache: a pass
attends its own keys by construction), ``close_pass`` = the final norm
(``reference.hidden_states`` walks T passes and hands ``logits`` every pass's
closed state ``[T, rows, H]``), the gate and the head in ``logits``.

**Bytes.** A decode microstep reads the layers' weights T times (a pass a
read: 192 layer calls of 103 MB at the published sizes), the head once, and
the live keys and values of T passes (slot ``t * L + l`` of the program's
arena: 1.5 MiB a token).
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
    """What the shared code needs of the published keys. ``layers`` is L, NOT
    T x L: the weights are drawn once."""
    return {
        "layers": int(model["num_hidden_layers"]),
        "hidden": int(model["hidden_size"]),
        "vocab": int(model["vocab_size"]),
        "kv_heads": int(model["num_key_value_heads"]),
        "head_dim": int(head_dim(model)),
    }


def passes(model: dict) -> int:
    """T: the published key, as it is (``blocks.passes`` refuses T < 1)."""
    return int(model["total_ut_steps"])


# ------------------------------------------------------------------ weights

MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORM_LEAVES = ("input_norm", "attn_out_norm", "post_norm", "mlp_out_norm")
# the program's names (``models/llama.py``), in the order a layer uses them:
# input_layernorm, q/k/v/o, input_layernorm_2, post_attention_layernorm,
# gate/up/down, post_attention_layernorm_2 of the released OuroDecoderLayer
LEAF_ORDER = (
    "input_norm", "wq", "wk", "wv", "wo", "attn_out_norm",
    "post_norm", "w_gate", "w_up", "w_down", "mlp_out_norm",
)
OUT_NORM_LEAVES = ("attn_out_norm", "mlp_out_norm")
GAIN_STD = 0.1
OUT_GAIN = 0.25  # (the module docstring's "Weights" says why not 1)
BIAS_STD = 0.1


def fan_in(x):
    return x * x.shape[-2] ** -0.5


def gain(x):
    return 1.0 + GAIN_STD * x


def out_gain(x):
    return OUT_GAIN * (1.0 + GAIN_STD * x)


def plain(x):
    return x


def gate_in(x):
    return x * x.shape[-1] ** -0.5


def gate_bias(x):
    return BIAS_STD * x


def leaf_shapes(model: dict) -> dict:
    H, I = model["hidden_size"], model["intermediate_size"]
    D = head_dim(model)
    Nh, Nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return dict(
        {name: (H,) for name in NORM_LEAVES},
        wq=(H, Nh * D), wk=(H, Nkv * D), wv=(H, Nkv * D), wo=(Nh * D, H),
        w_gate=(H, I), w_up=(H, I), w_down=(I, H),
    )


def layer_leaves(model: dict) -> tuple:
    """The leaves of one layer, in the order they are drawn: four norms, seven
    matmuls, no bias."""
    shapes = leaf_shapes(model)
    return tuple(
        Leaf(name, shapes[name], fan_in, matmul=True)
        if name in MATMUL_LEAVES else Leaf(
            name, shapes[name], out_gain if name in OUT_NORM_LEAVES else gain)
        for name in LEAF_ORDER
    )


def tables(model: dict) -> tuple:
    """The model's tables, in the order they are drawn: the llama family's
    three, then the exit gate's vector and bias (read by ``close_pass``'s
    caller never: by ``logits`` here, by the stage's close in the program)."""
    V, H = model["vocab_size"], model["hidden_size"]
    return (
        Leaf("embed", (V, H), plain, vocab_axis=0),
        Leaf("final_norm", (H,), gain),
        Leaf("lm_head", (H, V), fan_in, vocab_axis=1),
        Leaf("exit_gate", (H,), gate_in),
        Leaf("exit_bias", (1,), gate_bias),
    )


# ---------------------------------------------------------------- reference

# How ``correct`` is decided (benchmark/README.md): the mean and the worst
# margin of the served tokens under this reference, over every request a run
# scores (three replies of 512 tokens; five or six where a run finishes
# them). Logits of the seeded model have about unit variance over a
# vocabulary of 49k. Read on the chip (my chip runs, PR 60; PERF.md section 2
# has every reading; ``tests/calibrate_ouro.py`` makes the controls):
#   sound bf16, fifteen runs at fifteen seeds: mean 0.00098 .. 0.0025, worst
#   0.079 .. 0.141, served token = reference argmax at 90.8 .. 96.7% (192
#   bf16 layer calls a token: 2.5 to 7 times Qwen2.5-7B's mean over 28);
#   another model, each far outside: every pass in pass 0's slots mean 2.03,
#   T - 1 passes 0.728, no norm between passes 2.58, an output norm dropped
#   3.49; the head norming again 0.0170 .. 0.0179 (worst 0.29 .. 0.32);
#   a precision below the label: int8 weights 0.0097 .. 0.0099 (4.2 and 5.0
#   times their seeds' sound runs; worst 0.24 .. 0.34), an fp8 arena 0.0138
#   .. 0.0145 (6.0 and 7.3 times; worst 0.28 .. 0.31).
# DELTA_MEAN lies 2.0 times over the largest sound run and 1.9 times under
# the smallest lower-precision one. ONE request of a lower-precision run can
# read 0.0047 .. 0.0052: the limit is for a run's three or more. DELTA_MAX is
# the family's 0.25: the largest sound worst is 0.141; the lower-precision
# runs' worsts straddle it (0.24 .. 0.34) and they fail by the mean. The head
# norming once more can be told on the chip (8 times the sound mean) and NOT
# on the benchmark's toy (tests/test_ouro_block.py says why): the CPU test
# that holds it on logits is tests/test_ouro.py::
# test_each_wrong_model_fails_the_tolerance[the_head_norms_again].
DELTA_MEAN = 0.005
DELTA_MAX = 0.25


def layer_static(model: dict) -> dict:
    """The keywords of ``layer_forward`` the published keys fix."""
    return dict(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
    )


def head_static(model: dict) -> dict:
    """The keywords of ``embed``, ``close_pass`` and ``logits``."""
    return dict(
        eps=float(model["rms_norm_eps"]),
        exit_threshold=float(model["early_exit_threshold"]),
    )


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotary(x, theta):
    """x: [S, N, D] at positions 0..S-1, over the whole head, rotate-half."""
    S, _, D = x.shape
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(
    jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "kv_round",
                              "drop")
)
def layer_forward(h, p, *, heads, kv_heads, eps, theta, kv_round=None,
                  drop=None):
    """One decoder layer over a whole sequence h: [S, H], float32: a norm on
    each branch's input AND on its output, before the residual add. ``drop``
    (tests only) names an output norm to leave out."""
    with jax.default_matmul_precision("highest"):
        p = {k: dequant(v) for k, v in p.items()}
        S, _ = h.shape
        x = rms_norm(h, p["input_norm"], eps)
        q = (x @ p["wq"]).reshape(S, heads, -1)
        k = (x @ p["wk"]).reshape(S, kv_heads, -1)
        v = (x @ p["wv"]).reshape(S, kv_heads, -1)
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
        attn = attn.reshape(S, -1) @ p["wo"]
        if drop != "attn_out_norm":
            attn = rms_norm(attn, p["attn_out_norm"], eps)
        h = h + attn
        x = rms_norm(h, p["post_norm"], eps)
        mlp = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
        if drop != "mlp_out_norm":
            mlp = rms_norm(mlp, p["mlp_out_norm"], eps)
        return h + mlp


def embed(tables: dict, ids, **_head_static):
    """Hidden states [S, H] that enter layer 0 of pass 0."""
    return tables["embed"][ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("step", "eps", "exit_threshold"))
def close_pass(h, tables: dict, *, step, eps, exit_threshold):
    """What closes pass ``step`` over a whole sequence h: [S, H]: the final
    norm, the same after every pass; its result enters the next pass."""
    return rms_norm(h, tables["final_norm"].astype(jnp.float32), eps)


def exit_pass(h, tables: dict, exit_threshold: float):
    """h: [T, rows, H] closed states -> [rows] the pass each position's logits
    are taken from: the first at which the running sum of ``p`` reaches the
    threshold, else the last."""
    T = h.shape[0]
    g = jax.nn.sigmoid(
        h @ tables["exit_gate"].astype(jnp.float32)
        + tables["exit_bias"].astype(jnp.float32))  # [T, rows]
    stayed = jnp.cumprod(1.0 - g, axis=0)
    before = jnp.concatenate([jnp.ones_like(g[:1]), stayed[:-1]])
    p = jnp.concatenate([(g * before)[:-1], before[-1:]])
    reached = jnp.cumsum(p, axis=0) >= exit_threshold
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0), T - 1)


def logits(h, tables: dict, *, eps, exit_threshold, head_norm=False):
    """h: [T, rows, H], every pass's closed state at the scored rows -> [rows,
    V]: the gate chooses a pass a position and the head reads it AS IT IS —
    closed already. ``head_norm`` (tests only): the head norming once more."""
    at = exit_pass(h, tables, exit_threshold)
    chosen = jnp.take_along_axis(h, at[None, :, None], axis=0)[0]
    if head_norm:
        chosen = rms_norm(chosen, tables["final_norm"].astype(jnp.float32), eps)
    return chosen @ tables["lm_head"].astype(jnp.float32)


# -------------------------------------------------------------------- bytes


def layer_params(model: dict) -> dict:
    """Parameters of one decoder layer: ``{"matmul": n, "other": n}``."""
    H, I, D = model["hidden_size"], model["intermediate_size"], head_dim(model)
    q, kv = model["num_attention_heads"] * D, model["num_key_value_heads"] * D
    matmul = H * q + 2 * H * kv + q * H + 3 * H * I
    return {"matmul": matmul, "other": 4 * H,
            "out_channels": q + 2 * kv + H + 2 * I + H}


def layer_weight_bytes(model: dict, weight_dtype: str) -> int:
    p = layer_params(model)
    b = p["matmul"] * roofline.MATMUL_BYTES[weight_dtype] + p["other"] * 2
    if weight_dtype == "int8":
        b += p["out_channels"] * 2  # one bf16 scale per output channel
    return b


def decode_step_bytes(model: dict, weight_dtype: str, stages: int,
                      live_tokens: float, rec=None, kv_bytes: int = 2) -> float:
    """Bytes one chip must read for one decode microstep. A looped block
    counts its passes itself: the layers T times, the live K/V of T passes
    (each pass keeps keys and values of its own), the head once. The gate's
    ``[H]`` vector and the norms' gains are in ``other`` or too small to
    count. ``rec`` is unused: every step reads the same weights."""
    T = passes(model)
    return roofline.decode_step_bytes(
        dims(model), T * layer_weight_bytes(model, weight_dtype), stages,
        T * live_tokens, kv_bytes,
    )


def attn_kv_bytes(model: dict, rec, lo=None, hi=None, kv_bytes: int = 2):
    """Bytes of keys and values a decode microstep's attention MUST read
    (``layer_metrics/attn_kv_hbm_pct.py``): the live tokens of the rows in the
    step x one token-and-layer entry x L layers x T passes. None where no
    decode step falls inside ``[lo, hi]``."""
    from benchmark.layer_metrics.decode_hbm_pct import live_tokens_per_slot

    if lo is None:
        lo, hi = rec.get("traced") or rec["window"]
    live = live_tokens_per_slot(rec, lo, hi)
    if live is None:
        return None
    d = dims(model)
    return (passes(model) * d["layers"] * live
            * roofline.kv_bytes_per_token_layer(d, kv_bytes))
