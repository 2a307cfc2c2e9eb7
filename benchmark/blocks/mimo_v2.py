"""The ``mimo_v2`` block (MiMo-V2.5's language model publishes this
``model_type``): WINDOW and FULL attention in one stack, each kind of layer
with its own KV state, a sink logit in window layers, keys of 192 and values
of 128, partial rotary, and one chip's share of 256 sigmoid-routed experts
with no shared expert. Its weights, its plain reference and its bytes. Found
by ``model_type: "mimo_v2"``.

**What a later builder must know** (``benchmark/README.md`` predates this
block and is not edited):

- *Layers of up to four kinds* (``layer_kinds``): layer ``l`` is
  ``("moe" if moe_layer_freq[l] else "dense") + ("_swa" if
  hybrid_layer_pattern[l] else "_full")``; the tree is ``params["layers"] =
  {kind: {...}}``, one stack per kind in layer order. The kinds ALTERNATE
  down the model; the program runs a stage's layers as runs of one kind in
  model order (``models/mimo_v2.stage_runs``).
- *The share* is ``blocks/deepseek_v3.py``'s: ``n_routed_experts`` HELD of
  ``n_routed_experts_total`` (router, bias and normalisation over ALL; only
  the held experts' terms are summed), ``vocab_size`` the slice held.
- *Leaves are the program's* (``models/mimo_v2.py``): ``wqkv [H, Hq·192 +
  Hkv·192 + Hkv·128]`` — the published fused projection, q heads then k heads
  then v heads, ``Hkv`` 4 in full layers and 8 in window layers; ``sink
  [Hq]`` in the kinds whose attention has one; ``wo [Hq·128, H]``.
- *What the arenas hold* (``arena_bytes_per_token_layer``): a key is stored
  padded from 192 to 256 lanes (two whole tiles), a value as its 128: a full
  layer 4 x (256 + 128) x 2 B = 3,072 B a token, a window layer 6,144 B — of
  the at most ``sliding_window`` tokens a query reaches.

**Weights** (rules as ``blocks/deepseek_v3.py``: matmuls normal × fan-in **
-0.5, gains 1 + 0.1 n, never 1; ``router_bias`` 0.01 n, never 0; ``we_down``
by ONE expert's fan-in). ``sink`` is drawn ``4 + n``: a learned sink takes a
real share of a window's mass — at 4 it is ``e^4 = 55`` beside the ~210 that
128 keys of unit-variance scores sum to, a fifth of the denominator — so a
program that dropped it reads far off; drawn around 0 it would be a
hundredth and go unseen. Every non-matmul leaf is drawn in the activation
dtype, so its values are bf16-representable; both sides use them in float32.

**Reference.** The equations of ISSUE 39 in straightforward ``jax.numpy``,
float32, matmuls at ``highest``, one sequence, every position at once, no
cache: ``qkv = RMSNorm(h) W_qkv`` split by heads; rotary by halves on the
first ``int(192 x 0.334) = 64`` dims of q and k at the kind's base (``rope_theta``
full, ``swa_rope_theta`` window); ``v x attention_value_scale``; scores ``q·k
/ sqrt(192)`` under a causal mask, a window layer keeping ``i - window < j <=
i``; where the kind has a sink, the scalar ``s_h`` joins the row's logits
before the softmax and its column is dropped; ``o_proj``; then SwiGLU or the
router ``noaux_tc`` over ONE group (``s = sigmoid(x W_r)``, the ``top_k``
largest of ``s + bias``, weights ``s`` there over their sum) with the expert
sum in its DENSE form over the held experts. Departures, all deliberate:
attention is BLOCKED over queries (``Q_BLOCK`` rows of scores at a time, so
that 6.7 k positions fit) and shares no kernel, walk or arena with the
program; the kept set of the router is built from a sorted threshold, which
keeps more than k on an exact tie (measure zero).

**Bytes** (``decode_step_bytes``): per decode microstep one chip reads every
layer's fused qkv, ``wo`` and norms (a window layer's sink too), layer 0's
dense MLP, of each expert layer the router and the routed experts the step
READ (the program's counter, ``experts_read_per_layer``); the head slice; and
the live keys and values PER KIND (``attn_kv_bytes``): a full layer every
context token at 3,072 B, a window layer at most ``sliding_window`` tokens a
row at 6,144 B — what a sound program must read, so a kernel that walked
behind the window would read LOWER on its roofline share, never over 100.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import roofline, samples
from benchmark.reference import dequant
from benchmark.weights import Leaf

# ------------------------------------------------------------------- shapes

ATTN_KINDS = ("full", "swa")


def attn_of(kind: str) -> str:
    return kind.rsplit("_", 1)[1]


def total_experts(model: dict) -> int:
    return int(model.get("n_routed_experts_total", model["n_routed_experts"]))


def held_experts(model: dict) -> tuple:
    """``(first id, count)`` of the routed experts held here."""
    held = int(model["n_routed_experts"])
    return int(model.get("ep_rank", 0)) * held, held


def kv_heads(model: dict, attn: str) -> int:
    if attn == "swa":
        return int(model.get("swa_num_key_value_heads")
                   or model["num_key_value_heads"])
    return int(model["num_key_value_heads"])


def has_sink(model: dict, attn: str) -> bool:
    key = ("add_swa_attention_sink_bias" if attn == "swa"
           else "add_full_attention_sink_bias")
    return bool(model.get(key, False))


def rope_dims(model: dict) -> int:
    return int(model["head_dim"] * float(model.get("partial_rotary_factor", 1.0)))


def key_lanes(model: dict) -> int:
    """Lanes of one stored key: ``head_dim`` padded to whole 128-lane tiles."""
    return -(-int(model["head_dim"]) // 128) * 128


def arena_bytes_per_token_layer(model: dict, attn: str = "full",
                                kv_bytes: int = 2) -> int:
    """What ONE token of ONE layer of attention kind ``attn`` holds in that
    kind's arena: its key/value heads x (padded key + value)."""
    return kv_heads(model, attn) * (
        key_lanes(model) + int(model["v_head_dim"])) * kv_bytes


def layer_kinds(model: dict) -> tuple:
    L = int(model["num_hidden_layers"])
    attn = list(model["hybrid_layer_pattern"])[:L]
    ffn = model["moe_layer_freq"]
    ffn = (list(ffn)[:L] if isinstance(ffn, (list, tuple))
           else [int(l % int(ffn) == 0) for l in range(L)])
    return tuple(
        ("moe" if m else "dense") + ("_swa" if a else "_full")
        for a, m in zip(attn, ffn)
    )


def attn_layers(model: dict) -> dict:
    """``{"full": n, "swa": n}``: this chip's layers of each attention kind."""
    kinds = layer_kinds(model)
    return {a: sum(attn_of(k) == a for k in kinds) for a in ATTN_KINDS}


def dims(model: dict) -> dict:
    """What the shared code needs. ``kv_heads`` / ``head_dim`` are the FULL
    layers' published view; the shared ``roofline.kv_bytes_per_token_layer``
    is wrong for this block (two kinds of layer, a padded key, a narrower
    value) and ``decode_step_bytes`` below does not use it."""
    return {
        "layers": int(model["num_hidden_layers"]),
        "hidden": int(model["hidden_size"]),
        "vocab": int(model["vocab_size"]),
        "kv_heads": int(model["num_key_value_heads"]),
        "head_dim": int(model["head_dim"]),
    }


# ------------------------------------------------------------------ weights

GAIN_STD = 0.1
BIAS_STD = 0.01
SINK_MEAN = 4.0


def fan_in(x):
    return x * x.shape[-2] ** -0.5


def gain(x):
    return 1.0 + GAIN_STD * x


def small(x):
    return BIAS_STD * x


def sink_rule(x):
    return SINK_MEAN + x


def plain(x):
    return x


def scaled(fan: int):
    def rule(x):
        return x * fan ** -0.5
    return rule


def leaf_shapes(model: dict, kind: str) -> dict:
    H, Hq = model["hidden_size"], model["num_attention_heads"]
    Dk, Dv = model["head_dim"], model["v_head_dim"]
    Hkv = kv_heads(model, attn_of(kind))
    I, F = model["intermediate_size"], model["moe_intermediate_size"]
    E, (_, held) = total_experts(model), held_experts(model)
    return {
        "input_norm": (H,), "post_norm": (H,), "sink": (Hq,),
        "wqkv": (H, Hq * Dk + Hkv * Dk + Hkv * Dv), "wo": (Hq * Dv, H),
        "w_gate": (H, I), "w_up": (H, I), "w_down": (I, H),
        "router": (H, E), "router_bias": (E,),
        "we_gate": (H, held * F), "we_up": (H, held * F),
        "we_down": (held * F, H),
    }


def leaf_order(model: dict, kind: str) -> tuple:
    attn = ("input_norm", "wqkv") + (
        ("sink",) if has_sink(model, attn_of(kind)) else ()
    ) + ("wo", "post_norm")
    if kind.startswith("dense"):
        return attn + ("w_gate", "w_up", "w_down")
    return attn + ("router", "router_bias", "we_gate", "we_up", "we_down")


def layer_leaves(model: dict) -> dict:
    """``{kind: leaves}``, each kind's in the order they are drawn."""
    F = model["moe_intermediate_size"]
    out = {}
    for kind in dict.fromkeys(layer_kinds(model)):
        shapes, leaves = leaf_shapes(model, kind), []
        for name in leaf_order(model, kind):
            if name.endswith("_norm"):
                leaves.append(Leaf(name, shapes[name], gain))
            elif name == "sink":
                leaves.append(Leaf(name, shapes[name], sink_rule))
            elif name == "router":
                leaves.append(Leaf(name, shapes[name], fan_in))
            elif name == "router_bias":
                leaves.append(Leaf(name, shapes[name], small))
            else:
                rule = scaled(F) if name == "we_down" else fan_in
                leaves.append(Leaf(name, shapes[name], rule, matmul=True))
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

# Read on the chip, PR 39 (PERF.md section 6 has the runs): whole runs of
# mimo_v25.reason, the finished requests of a run (3 untraced, 6-7 traced)
# scored over their 6,144 output positions each; logits of the seeded model
# have about unit variance over the 19,072 ids of the slice. A seed's
# reading is the same to the last digit in every run of it. ``DELTA_MEAN``
# lies between the two readings it must lie between:
# - the LARGEST this program gives (bf16 activations and arenas, int8
#   weights) over its seeds: mean margin 0.00078-0.00105 over 18 seeds
#   (0.000782, 0.000819, 0.000834, 0.000836, 0.000843, 0.000868, 0.000874,
#   0.000882, 0.000895, 0.000897, 0.000897, 0.000904, 0.000940, 0.000959,
#   0.000960, 0.000965, 0.000969, 0.001045: mean 0.000898, deviation
#   0.000067), worst 0.24-0.57, served token = reference argmax at
#   96.9-97.3% of positions;
# - the SMALLEST the nearest precision below gives
#   (benchmark/tests/calibrate_mimo_v2.py, the same cell, same counts of
#   positions, through the harness). ``fp8_kv`` — every key and value rounded
#   to fp8 e4m3's three mantissa bits before it enters its arena, under the
#   bf16 label — reads 0.001129 / 0.001352 / 0.001456 / 0.001499 / 0.001524
#   at five seeds (1.29-1.86 times its seed's sound run: 0.000843 ->
#   0.001129, 0.001045 -> 0.001352, 0.000782 -> 0.001456, 0.000819 ->
#   0.001499, 0.000834 -> 0.001524), served token = argmax at 95.8-96.0%.
#   Under this PR's first limit, 0.0012, the first of the five came out
#   CORRECT through the harness; so the limit came down to where all five
#   are not, 7% over the largest sound reading (3.3 deviations over the
#   sound mean) and 0.8% under the smallest fp8 one. ``int4_weights`` — the
#   engine's matmul weights rounded to int4's 15 levels under the int8 label,
#   the cheat that would pay where a step is bound by the weights it reads —
#   is the control with room: PERF.md section 2 has its readings.
# The room under the fp8 readings is thin, as on OLMoE: four key/value heads
# and a window of 128 keys leave an fp8 cache little to spoil; what keeps an
# fp8 arena out beside the margin is the arena's type check (the label) and
# the tier-1 logits test (tests/test_mimo_v2.py: an fp8 cache, a bf16
# router, a dropped sink or window fail at 3e-4), which also sees what the
# limit CANNOT tell. ``DELTA_MAX`` guards against gross errors only, as in
# the other blocks (an fp8 cache reads 0.26-0.40, inside the sound worst; a
# token drawn blind reads ~4).
DELTA_MEAN = 0.00112
DELTA_MAX = 2.0

#: query rows of scores the reference holds at a time
Q_BLOCK = 512


def layer_static(model: dict) -> dict:
    """Per kind: the keywords of ``layer_forward`` the published keys fix."""
    first, held = held_experts(model)
    common = dict(
        heads=int(model["num_attention_heads"]),
        head_dim=int(model["head_dim"]), v_dim=int(model["v_head_dim"]),
        rope=rope_dims(model),
        value_scale=float(model.get("attention_value_scale") or 1.0),
        eps=float(model.get("layernorm_epsilon", 1e-5)),
        experts=total_experts(model), first_held=first, held=held,
        top_k=int(model["num_experts_per_tok"]),
    )
    out = {}
    for kind in dict.fromkeys(layer_kinds(model)):
        attn = attn_of(kind)
        out[kind] = dict(
            common, kv_heads=kv_heads(model, attn),
            theta=float(model["swa_rope_theta"] if attn == "swa"
                        else model["rope_theta"]),
            window=int(model["sliding_window"]) if attn == "swa" else 0,
            use_sink=has_sink(model, attn),
        )
    return out


def head_static(model: dict) -> dict:
    return dict(eps=float(model.get("layernorm_epsilon", 1e-5)))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rotary(x, theta, rope):
    """x: [S, N, D] at positions 0..S-1: the first ``rope`` dims rotated by
    halves, the rest as they are."""
    S = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    r, half = x[..., :rope], rope // 2
    rot = jnp.concatenate([-r[..., half:], r[..., :half]], -1)
    return jnp.concatenate([r * cos + rot * sin, x[..., rope:]], -1)


def attention(q, k, v, scale, window, sink):
    """q [S, Hq, Dk], k [S, Hkv, Dk], v [S, Hkv, Dv] → [S, Hq, Dv]: causal
    (windowed) softmax attention, ``Q_BLOCK`` query rows at a time. A window
    layer's block of queries is scored against the BAND of keys it can reach
    (the block's own and the ``window`` before its first: the mask inside the
    band is the same ``i - window < j <= i``), a full layer's against every
    key. ``sink`` [Hq] or None: a logit a row, in the softmax and out of the
    sum."""
    S, Hq, _ = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    block = next(b for b in (Q_BLOCK, 256, S) if b <= S and S % b == 0)
    band = block + window if window and block + window < S else S
    lead = band - block if band < S else 0
    if lead:  # keys before position 0: never kept (j < 0)
        k = jnp.concatenate([jnp.zeros((lead, *k.shape[1:]), k.dtype), k])
        v = jnp.concatenate([jnp.zeros((lead, *v.shape[1:]), v.dtype), v])

    def rows(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block, axis=0)
        # the band's first key position: i0 - lead (full layers: 0)
        k0 = i0 - lead if lead else 0
        kb = jax.lax.dynamic_slice_in_dim(k, i0 if lead else 0, band, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(v, i0 if lead else 0, band, axis=0)
        i = (i0 + jnp.arange(block))[:, None]
        j = (k0 + jnp.arange(band))[None, :]
        keep = (j <= i) & (j >= 0)
        if window:
            keep &= j > i - window
        qg = qb.reshape(block, Hkv, G, -1)
        s = jnp.einsum("skgd,tkd->kgst", qg, kb) * scale
        s = jnp.where(keep[None, None], s, -jnp.inf)
        if sink is not None:
            s = jnp.concatenate([s, jnp.broadcast_to(
                sink.reshape(Hkv, G, 1, 1), (Hkv, G, block, 1))], -1)
        p = jax.nn.softmax(s, axis=-1)[..., :band]
        return jnp.einsum("kgst,tkv->skgv", p, vb).reshape(block, Hq, -1)

    out = jax.lax.map(rows, jnp.arange(0, S, block))
    return out.reshape(S, Hq, v.shape[-1])


def router_weights(x, router, bias, *, top_k, use_bias=True):
    """``[S, E]``: an expert's weight where the router keeps it, else 0 —
    ``noaux_tc`` over one group: ``s = sigmoid(x W_r)``, the ``top_k``
    largest of ``s + bias`` are kept; weights are the UNbiased ``s`` there
    over their sum (+1e-20)."""
    E = router.shape[-1]
    s = jax.nn.sigmoid(x @ router)
    choice = s + bias if use_bias else s
    kth = jnp.sort(choice, axis=-1)[:, E - top_k]
    kept = jnp.where(choice >= kth[:, None], s, 0.0)
    return kept / (kept.sum(-1, keepdims=True) + 1e-20)


def gated_mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def by_rows(fn, *xs):
    """``fn`` (work that treats every position alike) over the rows of
    ``xs``, ``Q_BLOCK`` positions at a time where they divide a long
    sequence: the intermediates of 7 k positions stay small, and the chip's
    compiler takes ~1 s over a float32 product inside the loop where it takes
    ~8 s over the same product at the top of a program (read with the no-chip
    compiler for the described v5e, PR 39: a run's 360 s were short of it)."""
    S = xs[0].shape[0]
    if S <= Q_BLOCK or S % Q_BLOCK:
        return fn(*xs)
    out = jax.lax.map(
        lambda b: fn(*b),
        tuple(x.reshape(S // Q_BLOCK, Q_BLOCK, *x.shape[1:]) for x in xs),
    )
    return out.reshape(S, *out.shape[2:])


#: sequences longer than this are padded to whole multiples of it
S_PAD = 1024


def layer_forward(h, p, **kw):
    """One layer over a whole sequence h: [S, H], float32 (``_layer_forward``
    has the keywords). A long sequence is padded to whole ``S_PAD``s first
    (causal: the pad changes no real position) so that every scored request
    of a cell — prompts of 16-512 before replies of 6,144 — is ONE shape:
    each kind's layer compiles once a run (~13 s a kind at 7,168 positions;
    read on the chip, PR 39: a second length cost 35 s of a run's 360)."""
    S = h.shape[0]
    pad = -S % S_PAD if S > S_PAD else 0
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, h.shape[1]), h.dtype)])
    return _layer_forward(h, p, **kw)[:S]


@functools.partial(
    jax.jit,
    static_argnames=(
        "kind", "heads", "kv_heads", "head_dim", "v_dim", "rope", "theta",
        "window", "use_sink", "value_scale", "eps", "experts", "first_held",
        "held", "top_k", "kv_round", "router_dtype", "use_bias",
    ),
)
def _layer_forward(h, p, *, kind, heads, kv_heads, head_dim, v_dim, rope,
                   theta, window, use_sink, value_scale, eps, experts,
                   first_held, held, top_k, kv_round=None, router_dtype=None,
                   use_bias=True):
    """One layer of ``kind`` over a whole sequence h: [S, H], float32.
    ``kv_round`` (keys and values as a cache of lower precision would hold
    them), ``router_dtype`` and ``use_bias=False`` are the tests' wrong
    models; so are overrides of ``window``, ``use_sink``, ``theta`` and
    ``value_scale``."""
    with jax.default_matmul_precision("highest"):
        p = {k: dequant(v) for k, v in p.items()}
        S, H = h.shape
        qkv = by_rows(
            lambda hb: rms_norm(hb, p["input_norm"], eps) @ p["wqkv"], h)
        nq, nk = heads * head_dim, kv_heads * head_dim
        q = rotary(qkv[:, :nq].reshape(S, heads, head_dim), theta, rope)
        k = rotary(qkv[:, nq:nq + nk].reshape(S, kv_heads, head_dim), theta,
                   rope)
        v = qkv[:, nq + nk:].reshape(S, kv_heads, v_dim) * value_scale
        if kv_round is not None:
            k = k.astype(kv_round).astype(jnp.float32)
            v = v.astype(kv_round).astype(jnp.float32)
        sink = p["sink"] if use_sink and "sink" in p else None
        o = attention(q, k, v, head_dim ** -0.5, window, sink)

        def rest(hb, ob):  # what follows attention, a position at a time
            hb = hb + ob @ p["wo"]
            x = rms_norm(hb, p["post_norm"], eps)
            if kind.startswith("dense"):
                return hb + gated_mlp(x, p["w_gate"], p["w_up"], p["w_down"])
            xr, wr = x, p["router"]
            if router_dtype is not None:
                xr = xr.astype(router_dtype).astype(jnp.float32)
                wr = wr.astype(router_dtype).astype(jnp.float32)
            kept = router_weights(
                xr, wr, p["router_bias"], top_k=top_k, use_bias=use_bias,
            )[:, first_held:first_held + held]  # the held experts' weights
            F = p["we_gate"].shape[-1] // held
            act = jax.nn.silu(x @ p["we_gate"]) * (x @ p["we_up"])  # [B, held·F]
            act = (act.reshape(-1, held, F) * kept[:, :, None]).reshape(
                act.shape)
            return hb + act @ p["we_down"]

        return by_rows(rest, h, o.reshape(S, -1))


def embed(tables: dict, ids, *, eps=None):
    return tables["embed"][ids].astype(jnp.float32)


def logits(h, tables: dict, *, eps):
    gain = tables["final_norm"].astype(jnp.float32)
    head = tables["lm_head"].astype(jnp.float32)
    return by_rows(lambda hb: rms_norm(hb, gain, eps) @ head, h)


# -------------------------------------------------------------------- bytes


def _matmul_bytes(shape: tuple, weight_dtype: str) -> int:
    """A matmul leaf and, under int8, its one bf16 scale per output channel."""
    b = shape[0] * shape[1] * roofline.MATMUL_BYTES[weight_dtype]
    return b + (shape[1] * 2 if weight_dtype == "int8" else 0)


def attention_bytes(model: dict, kind: str, weight_dtype: str) -> int:
    """What a layer of ``kind`` reads for its attention: the fused qkv,
    ``wo``, two norm gains, the sink where it has one."""
    sh = leaf_shapes(model, kind)
    b = sum(_matmul_bytes(sh[n], weight_dtype) for n in ("wqkv", "wo"))
    b += 2 * (sh["input_norm"][0] + sh["post_norm"][0])
    return b + (2 * sh["sink"][0] if has_sink(model, attn_of(kind)) else 0)


def dense_mlp_bytes(model: dict, weight_dtype: str) -> int:
    sh = leaf_shapes(model, "dense_full")
    return sum(_matmul_bytes(sh[n], weight_dtype)
               for n in ("w_gate", "w_up", "w_down"))


def moe_fixed_bytes(model: dict, weight_dtype: str) -> int:
    """What an expert layer reads whatever it routes: the bf16 router, its
    bias, and ``we_down``'s one scale per channel."""
    sh = leaf_shapes(model, "moe_full")
    b = (sh["router"][0] * sh["router"][1] + sh["router_bias"][0]) * 2
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
    records in ``[lo, hi]`` (default: the traced slice, else the window).
    None where the records carry no such counter."""
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


def kv_tokens_read(rec, window: int, lo=None, hi=None):
    """``(full, windowed)``: per decode step in ``[lo, hi]``, the mean over
    the steps of the rows' context lengths summed — whole, and cut to
    ``window`` a row (what a window layer's query reaches) — from the
    records' requests. None where no step falls inside."""
    if lo is None:
        lo, hi = rec.get("traced") or rec["window"]
    steps = samples.steps_in_window(rec, lo, hi)
    if not steps:
        return None
    full = cut = 0.0
    for st in steps:
        t = st["t"]
        for r in rec["requests"]:
            started = r["server_started_at"]
            if started is None or started > t:
                continue
            if r["finished"] is not None and r["finished"] < t:
                continue
            n = r["prompt_len"] + sum(1 for s in r["stamps"] if s <= t)
            full += n
            cut += min(n, window)
    return full / len(steps) / rec["chips"], cut / len(steps) / rec["chips"]


def attn_kv_bytes(model: dict, rec, lo=None, hi=None, kv_bytes: int = 2):
    """Bytes of keys and values a decode microstep's attention MUST read:
    each kind's live tokens x its entry bytes x its layers."""
    got = kv_tokens_read(rec, int(model["sliding_window"]), lo, hi)
    if got is None:
        return None
    layers = attn_layers(model)
    return sum(
        layers[a] * n * arena_bytes_per_token_layer(model, a, kv_bytes)
        for a, n in zip(ATTN_KINDS, got)
    )


def decode_step_bytes(model: dict, weight_dtype: str, stages: int,
                      live_tokens: float, rec=None, kv_bytes: int = 2) -> float:
    """Bytes one chip must read for one decode microstep (the docstring's
    "Bytes"). ``stages`` must be 1. ``live_tokens`` is the shared reader's
    count of the full layers' tokens; the window layers' is read from the
    records."""
    n = experts_read_per_layer(rec) if rec is not None else None
    if n is None:
        raise ValueError(
            "the records carry no experts_read counter: the bytes of a "
            "decode step of a model with experts cannot be counted"
        )
    if stages != 1:
        raise ValueError("mimo_v2 bytes are counted for one stage")
    kinds = layer_kinds(model)
    L = len(kinds)
    window = int(model["sliding_window"])
    got = kv_tokens_read(rec, window)
    tokens = {"full": live_tokens,
              "swa": min(live_tokens, window) if got is None else got[1]}
    layers = attn_layers(model)
    return (
        sum(attention_bytes(model, k, weight_dtype) for k in kinds)
        + sum(k.startswith("dense") for k in kinds)
        * dense_mlp_bytes(model, weight_dtype)
        + sum(k.startswith("moe") for k in kinds)
        * moe_fixed_bytes(model, weight_dtype)
        + n * L * expert_bytes(model, weight_dtype)
        + roofline.head_bytes(dims(model))
        + sum(layers[a] * tokens[a]
              * arena_bytes_per_token_layer(model, a, kv_bytes)
              for a in ATTN_KINDS)
    )
