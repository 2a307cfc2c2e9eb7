"""The ``solar_open2`` block (Solar-Open2-250B publishes this ``model_type``):
KDA linear-attention mixers and, every fourth layer, gated softmax attention
WITHOUT positions in one stack — every layer TWO sub-blocks, ``h ← h +
mixer(RMSNorm(h))`` then ``h ← h + MoE(RMSNorm(h))`` — with a recurrent MATRIX
state of fixed size a request, a K/V arena that only the attention layers
write, and one chip's share of 320 sigmoid-routed experts beside a shared one.
Its weights, its plain reference and its bytes. Found by ``model_type:
"solar_open2"``.

**What a later builder must know** (``benchmark/README.md`` "A block"):

- *Layers of two kinds* (``layer_kinds``): layer ``l`` is ``gqa`` where ``l`` is
  in the published ``gqa_layers`` (the entries under ``num_hidden_layers``) and
  ``kda`` otherwise. The tree is ``params["layers"] = {kind: {...}}``, one stack
  per kind in layer order; the program runs a stage's layers as runs of one
  kind in model order.
- *The share* is ``blocks/deepseek_v3.py``'s: ``n_routed_experts`` HELD of
  ``n_routed_experts_total`` (router, bias and normalisation over ALL; only the
  held experts' terms are summed), ``ep_rank`` which run of ids, ``vocab_size``
  the slice held.
- *Leaves are the program's* (``models/solar_open2.py``). A KDA mixer: ``wq``,
  ``wk``, ``wv [H, heads·128]``, the decay's low-rank pair ``w_a_down [H, 128]``
  / ``w_a_up [128, heads·128]``, the output gate's ``w_g_down`` / ``w_g_up``
  likewise, ``w_beta [H, heads]`` (float: a column a head), ``conv_w [K,
  3·heads·128]`` over ``[q | k | v]`` (tap ``k`` meets the input ``K-1-k`` back;
  NO conv bias), ``A_log [heads]``, ``dt_bias [heads·128]``, ``gate_norm [128]``
  (ONE gain, every head's), ``wo``. Attention: ``wq [H, 64·128]``, ``wk``, ``wv
  [H, 8·128]``, ``w_gate [H, 64·128]`` (the output gate, a value a channel),
  ``wo``. Every layer's MLP: ``blocks/deepseek_v3.py``'s expert leaves.
- *What a request holds beside the arena* (``state_bytes_per_row_layer``): per
  KDA layer the float32 state ``[64, 128, 128]`` (4 MiB) and the conv's last
  3 inputs (``3 x 24,576`` float32, 288 KiB): fixed, whatever the context. The
  arena holds the attention layers only: 8 heads x (128 + 128) x 2 B = 4 KB a
  token and layer.

**Weights** (rules as ``blocks/nemotron_h.py``: matmuls normal × fan-in **
-0.5, gains 1 + 0.1 n, ``router_bias`` 0.01 n, ``we_down`` by ONE expert's
fan-in, the router's columns ``antithetic`` and the experts' down projections
``centred`` — PERF.md section 6, PR 43: a held share otherwise moves the step
from seed to seed) and, so that the mechanism is visible: the conv's taps 0.5
n; ``w_beta`` at the fan-in scale (``β = 2 · sigmoid`` spreads around 1, over
AND under: a ``β`` not doubled is seen); both low-rank pairs at the fan-in
scale (the gate spreads around 1/2); ``dt_bias`` and ``A_log`` by that block's
rules for Mamba-2 — ``A_log = log U(1, 16)`` a head, ``dt_bias`` a channel the
inverse softplus of a log-uniform step — over FOUR decades, [1e-5, 0.1], where
ISSUE 53 wrote that block's [0.001, 0.1] "so that half-lives run from a few
tokens to thousands and 4,096 steps really multiply through the state": with
``a = (x̂ W_a↓) W_a↑`` of unit variance INSIDE the softplus (``E e^a`` = 1.65) and
``A`` up to 16 the two-decade range gives a median half-life of 6 tokens and
none over 420, the state forgets what it was told before a rounding of it can
add up, and a bf16 state read as a sound run on the chip (0.00354 at one seed
where sound runs read 0.0031-0.0034: PERF.md section 2, PR 53). As drawn, ``g =
−exp(A_log) · softplus(a + dt_bias)`` a step spans −5e-6 (a slow channel at a
quiet token) to below −30 (over 12.8 M draws: the 0.1% and 99.9% points −5e-6
and −5.8, the largest −35, where ``exp(g)`` is 1e-15 — NO lower bound, which is
what the program's chunk form must live with), and a channel's half-life ``ln 2
/ E|g|`` is under 1.5 tokens for a tenth of the channels, 60 at the median,
over 1,024 for 19%, over 4,096 for 5%, the longest ~35,000: inside every head
the decays spread over four decades. No q/k gains (PERF.md section 6, PR 49:
peaked softmaxes part from a float32 reference layer after layer).

**Reference.** The equations of ISSUE 53 in straightforward ``jax.numpy``,
float32, matmuls at ``highest``, one sequence, no cache, no kernel. A KDA
mixer is the SEQUENTIAL recurrence — a ``lax.scan`` over positions carrying the
``[heads, 128, 128]`` state, one position at a time, independent of the
program's chunkwise form: ``S' = Diag(α) S``, ``u = v − S'ᵀ k``, ``S = S' + β k
uᵀ``, ``o = Sᵀ q``; ``L2norm(x) = x / sqrt(Σ x² + 1e-6)``; the conv written as
``K`` shifted sums from a zero history; the output norm over each HEAD's
channels with the one gain, then the sigmoid gate a channel. Attention: causal
softmax over 64 query heads sharing 8 key/value heads, NO rotary embedding,
the output times ``sigmoid(x̂ W_gate)`` before ``W_o``, in blocks of ``Q_BLOCK``
queries; the router ``noaux_tc`` over one group transcribed directly; the
expert sum in its DENSE form over the held experts, ``Q_BLOCK`` positions at a
time. A long sequence is padded to whole ``S_PAD``s (causal: a pad changes no
real position) so that the chip's compiler meets ONE shape a kind.

**Bytes** (``decode_step_bytes``): per decode microstep one chip reads every
KDA mixer's matmul leaves and small leaves and, per LIVE row, reads AND writes
its state and conv tail (``state_bytes_per_row_layer``); each attention
layer's five matmul leaves and the live keys and values at 4 KB a token; of
every layer the bf16 router, the shared expert and the routed experts the
step READ (the program's counter); the head slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import roofline, samples
from benchmark.reference import dequant
from benchmark.weights import Leaf

# ------------------------------------------------------------------- shapes


def layer_kinds(model: dict) -> tuple:
    gqa = {int(l) for l in model["gqa_layers"]}
    return tuple(
        "gqa" if l in gqa else "kda"
        for l in range(int(model["num_hidden_layers"]))
    )


def kind_layers(model: dict) -> dict:
    kinds = layer_kinds(model)
    return {k: kinds.count(k) for k in ("kda", "gqa")}


def total_experts(model: dict) -> int:
    return int(model.get("n_routed_experts_total", model["n_routed_experts"]))


def held_experts(model: dict) -> tuple:
    """``(first id, count)`` of the routed experts held here."""
    held = int(model["n_routed_experts"])
    return int(model.get("ep_rank", 0)) * held, held


def kda_dims(model: dict) -> dict:
    lin = model["linear_attn_config"]
    nh, hd = int(lin["num_heads"]), int(lin["head_dim"])
    return {
        "heads": nh, "head_dim": hd, "inner": nh * hd, "rank": hd,
        "kernel": int(lin.get("short_conv_kernel_size", 4)),
    }


def state_bytes_per_row_layer(model: dict, moved: bool = True) -> int:
    """Bytes of ONE request's recurrent state in ONE KDA layer (the float32
    ``[heads, 128, 128]`` state and the conv's tail over ``[q | k | v]``);
    with ``moved`` what a decode step moves of it: each read AND written."""
    d = kda_dims(model)
    held = 4 * (
        d["inner"] * d["head_dim"] + (d["kernel"] - 1) * 3 * d["inner"]
    )
    return 2 * held if moved else held


def arena_bytes_per_token_layer(model: dict, kv_bytes: int = 2) -> int:
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * kv_bytes


def dims(model: dict) -> dict:
    """What the shared code needs. ``kv_heads`` / ``head_dim`` are the
    attention layers'; ``layers`` counts every layer, so the shared
    ``roofline.kv_bytes_per_token_layer`` x layers is wrong for this block (3
    layers of 12 keep keys) and ``decode_step_bytes`` does not use it."""
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
CONV_STD = 0.5


def fan_in(x):
    return x * x.shape[-2] ** -0.5


def gain(x):
    return 1.0 + GAIN_STD * x


def small(x):
    return BIAS_STD * x


def conv_rule(x):
    return CONV_STD * x


def plain(x):
    return x


def scaled(fan: int):
    def rule(x):
        return x * fan ** -0.5
    return rule


def antithetic(held: int):
    """The router ``[H, E]``, every column of length 1: inside each rank's
    share of ``held`` columns the second half are the first half's NEGATIVES
    (``blocks/nemotron_h.py`` says what that keeps still)."""
    def rule(x):
        H, E = x.shape
        share = held if E % held == 0 else 1
        n = share // 2
        w = x.reshape(H, E // share, share)
        a = w[:, :, :n]
        w = jnp.concatenate([a, -a, w[:, :, 2 * n:]], axis=-1).reshape(H, E)
        return w * jax.lax.rsqrt(jnp.sum(w * w, axis=0, keepdims=True))
    return rule


def centred(rule, blocks: int = 1):
    """A down projection ``[blocks · F, out]`` whose columns sum to zero over
    each block's ``F`` rows: what ``rule`` draws less each column's mean."""
    def centred_rule(x):
        w = rule(x)
        w = w.reshape(blocks, w.shape[0] // blocks, w.shape[1])
        return (w - w.mean(axis=1, keepdims=True)).reshape(-1, w.shape[2])
    return centred_rule


def uniform01(x):
    """A standard-normal sample through its distribution function."""
    return 0.5 * (1.0 + jax.lax.erf(x * 2.0 ** -0.5))


def a_log_rule(x):
    return jnp.log(1.0 + 15.0 * uniform01(x))


def dt_bias_rule(lo: float, hi: float):
    def rule(x):
        dt = jnp.exp(jnp.log(lo) + uniform01(x) * (jnp.log(hi) - jnp.log(lo)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus(rule) == dt
    return rule


def leaf_shapes(model: dict) -> dict:
    """Every leaf a layer can have, by name (``wq``, ``wk``, ``wv`` and ``wo``
    are each kind's own: ``kind_shapes``)."""
    H, F = model["hidden_size"], model["moe_intermediate_size"]
    Fs = F * int(model.get("n_shared_experts", 1))
    E, (_, held) = total_experts(model), held_experts(model)
    d = kda_dims(model)
    return {
        "input_norm": (H,), "post_norm": (H,),
        "router": (H, E), "router_bias": (E,),
        "we_gate": (H, held * F), "we_up": (H, held * F),
        "we_down": (held * F, H),
        "ws_gate": (H, Fs), "ws_up": (H, Fs), "ws_down": (Fs, H),
        "w_a_down": (H, d["rank"]), "w_a_up": (d["rank"], d["inner"]),
        "w_g_down": (H, d["rank"]), "w_g_up": (d["rank"], d["inner"]),
        "w_beta": (H, d["heads"]),
        "conv_w": (d["kernel"], 3 * d["inner"]), "A_log": (d["heads"],),
        "dt_bias": (d["inner"],), "gate_norm": (d["head_dim"],),
    }


def kind_shapes(model: dict, kind: str) -> dict:
    """``leaf_shapes`` with the four projections of ``kind``'s mixer."""
    H = model["hidden_size"]
    sh = leaf_shapes(model)
    if kind == "kda":
        D = kda_dims(model)["inner"]
        sh.update(wq=(H, D), wk=(H, D), wv=(H, D), wo=(D, H))
    else:
        Hq, Hkv, D = (model["num_attention_heads"],
                      model["num_key_value_heads"], model["head_dim"])
        sh.update(wq=(H, Hq * D), wk=(H, Hkv * D), wv=(H, Hkv * D),
                  w_gate=(H, Hq * D), wo=(Hq * D, H))
    return sh


#: a kind's mixer leaves and every layer's MLP leaves, each in the order drawn
MIXER_ORDER = {
    "kda": ("input_norm", "wq", "wk", "wv", "w_a_down", "w_a_up", "w_g_down",
            "w_g_up", "w_beta", "conv_w", "A_log", "dt_bias", "gate_norm",
            "wo"),
    "gqa": ("input_norm", "wq", "wk", "wv", "w_gate", "wo"),
}
MLP_ORDER = ("post_norm", "router", "router_bias", "we_gate", "we_up",
             "we_down", "ws_gate", "ws_up", "ws_down")
MATMUL = (
    "wq", "wk", "wv", "wo", "w_gate", "w_a_down", "w_a_up", "w_g_down",
    "w_g_up", "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down",
)


def layer_leaves(model: dict) -> dict:
    """``{kind: leaves}``, each kind's in the order they are drawn: the
    mixer's, then the MLP's."""
    _, held = held_experts(model)
    rules = {
        "input_norm": gain, "post_norm": gain, "gate_norm": gain,
        "router": antithetic(held), "router_bias": small,
        "conv_w": conv_rule, "A_log": a_log_rule,
        "dt_bias": dt_bias_rule(1e-5, 0.1), "w_beta": fan_in,
    }
    down = {  # positive-mean activations in: no constant vector out
        "ws_down": centred(fan_in),
        "we_down": centred(scaled(model["moe_intermediate_size"]), held),
    }
    out = {}
    for kind in dict.fromkeys(layer_kinds(model)):
        shapes = kind_shapes(model, kind)
        out[kind] = tuple(
            Leaf(name, shapes[name], down.get(name, fan_in), matmul=True)
            if name in MATMUL else Leaf(name, shapes[name], rules[name])
            for name in MIXER_ORDER[kind] + MLP_ORDER
        )
    return out


def tables(model: dict) -> tuple:
    V, H = model["vocab_size"], model["hidden_size"]
    return (
        Leaf("embed", (V, H), plain, vocab_axis=0),
        Leaf("final_norm", (H,), gain),
        Leaf("lm_head", (H, V), fan_in, vocab_axis=1),
    )


# ---------------------------------------------------------------- reference

# Read on the chip, PR 53 (PERF.md sections 2 and 6 have the runs): whole runs
# of solar_open2_250b.cot, the finished requests of a run scored over their
# 4,096 output positions each (16,384 positions an untraced run), through the
# harness. ``DELTA_MEAN`` lies between the two readings it must lie between:
# - the LARGEST this program gives (bf16 activations and arena, int8 weights, a
#   float32 state): 0.005196-0.005883 over the six seeds of two sets of six (a
#   seed reads the same to the last digit in both sets), 0.005505 and 0.005923
#   traced at a seventh and an eighth (24,576 positions each), the served
#   token the reference's argmax at 88.4-89.6%. A LOW
#   floor beside the other share-holding blocks' 0.015-0.05: the held eighth
#   of the experts carries an eighth of the routed pairs at scale 1, so a kept
#   expert flipped by a bf16-rounded input moves a token by little;
# - the SMALLEST the nearest precision below gives
#   (benchmark/tests/calibrate_solar_open2.py): a bf16 recurrent state
#   0.008055 / 0.008891 / 0.008760 / 0.008184 at four seeds, 1.42-1.64 times
#   its seed's sound run (the highest-reading sound seed's among them); the
#   other models far over: a write strength not doubled (``beta_01``) 0.678, no
#   correction (``no_delta``) 1.818, the attention layers' gate dropped
#   (``no_gate``) 0.1115; on the axis that would PAY, int4 weights under the
#   int8 label, 1.113. Each reads ``"correct": false`` under these limits.
# So 0.0068: 14.8% over the largest sound reading, 15.6% under the smallest
# control. (Under ISSUE 53's literal draw of ``dt_bias`` — the docstring's
# "Weights" — sound runs read 0.0031-0.0034 and a bf16 state 0.00354: unseen.)
# ``DELTA_MAX`` guards against gross errors only, as in the other blocks: a
# sound run's worst position reads 0.27-0.41 (0.55 under the first draw), a
# bf16 state's 0.37-0.43 (it is the MEAN that refuses it), the wrong models'
# 1.87-5.2.
DELTA_MEAN = 0.0068
DELTA_MAX = 1.5

#: positions of position-wise work (and query rows of scores) held at a time
Q_BLOCK = 512
#: sequences longer than this are padded to whole multiples of it
S_PAD = 1024
#: what ``L2norm`` adds under its root
L2_EPS = 1e-6


def layer_static(model: dict) -> dict:
    """Per kind: the keywords of ``layer_forward`` the published keys fix."""
    first, held = held_experts(model)
    d = kda_dims(model)
    eps = float(model["rms_norm_eps"])
    moe = dict(
        eps=eps, experts=total_experts(model), first_held=first, held=held,
        top_k=int(model["num_experts_per_tok"]),
        routed_scale=float(model.get("routed_scaling_factor", 1.0)),
    )
    return {
        "kda": dict(
            moe, heads=d["heads"], head_dim=d["head_dim"],
            beta_scale=2.0 if model.get("kda_allow_neg_eigval") else 1.0,
        ),
        "gqa": dict(
            moe, heads=int(model["num_attention_heads"]),
            kv_heads=int(model["num_key_value_heads"]),
            head_dim=int(model["head_dim"]),
            use_gate=bool(model.get("use_gqa_gate", False)),
        ),
    }


def head_static(model: dict) -> dict:
    return dict(eps=float(model["rms_norm_eps"]))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def by_rows(fn, *xs):
    """``fn`` (work that treats every position alike) over the rows of
    ``xs``, ``Q_BLOCK`` positions at a time where they divide a long
    sequence (``blocks/mimo_v2.py`` says what it saves the chip's compiler)."""
    S = xs[0].shape[0]
    if S <= Q_BLOCK or S % Q_BLOCK:
        return fn(*xs)
    out = jax.lax.map(
        lambda b: fn(*b),
        tuple(x.reshape(S // Q_BLOCK, Q_BLOCK, *x.shape[1:]) for x in xs),
    )
    return out.reshape(S, *out.shape[2:])


def attention(q, k, v, scale):
    """q [S, Hq, D], k, v [S, Hkv, D] → [S, Hq, D]: causal softmax attention,
    ``Q_BLOCK`` query rows at a time against every key. No position term."""
    S, Hq, _ = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    block = next(b for b in (Q_BLOCK, 256, S) if b <= S and S % b == 0)

    def rows(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block, axis=0)
        i = (i0 + jnp.arange(block))[:, None]
        keep = jnp.arange(S)[None, :] <= i
        s = jnp.einsum(
            "skgd,tkd->kgst", qb.reshape(block, Hkv, G, -1), k) * scale
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgst,tkv->skgv", p, v).reshape(block, Hq, -1)

    out = jax.lax.map(rows, jnp.arange(0, S, block))
    return out.reshape(S, Hq, v.shape[-1])


def router_weights(x, router, bias, *, top_k, routed_scale, use_bias=True):
    """``[S, E]``: an expert's weight where the router keeps it, else 0 —
    ``noaux_tc`` over one group: ``s = sigmoid(x W_r)``, the ``top_k`` largest
    of ``s + bias`` are kept; weights are the UNbiased ``s`` there over their
    sum (+1e-20), times ``routed_scale``."""
    E = router.shape[-1]
    s = jax.nn.sigmoid(x @ router)
    choice = s + bias if use_bias else s
    kth = jnp.sort(choice, axis=-1)[:, E - top_k]
    kept = jnp.where(choice >= kth[:, None], s, 0.0)
    return kept / (kept.sum(-1, keepdims=True) + 1e-20) * routed_scale


def gated_mlp(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def causal_conv(x, w):
    """x [S, C] from a zero history, ``w [K, C]``, no bias: ``y_t = Σ_k w[k]
    x[t - (K-1) + k]``."""
    K, S = w.shape[0], x.shape[0]
    xin = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    y = 0.0
    for k in range(K):
        y = y + xin[k:k + S] * w[k]
    return y


def delta_recurrence(q, k, v, g, beta, state_round=None, use_delta=True):
    """The SEQUENTIAL KDA recurrence from a zero state: q, k, g [S, nh, dk], v
    [S, nh, dv], beta [S, nh] → o [S, nh, dv]. One position a step of a
    ``lax.scan``. ``state_round``: the state as a narrower type would hold it
    (``lax.reduce_precision``: the chip's compiler drops a cast there and
    back); ``use_delta=False``: ``u = v``, gated linear attention without the
    correction (wrong models of the tests and the calibration)."""
    nh, dk = q.shape[1:]
    dv = v.shape[-1]

    def step(s, t):
        qt, kt, vt, gt, bt = t
        s = s * jnp.exp(gt)[:, :, None]  # S' = Diag(α) S
        u = vt - jnp.einsum("hkv,hk->hv", s, kt) if use_delta else vt
        s = s + (bt[:, None] * kt)[:, :, None] * u[:, None, :]
        if state_round is not None:
            s = jax.lax.reduce_precision(s, *state_round)
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    _, o = jax.lax.scan(
        step, jnp.zeros((nh, dk, dv), jnp.float32), (q, k, v, g, beta)
    )
    return o


def layer_forward(h, p, **kw):
    """One layer over a whole sequence h: [S, H], float32 (``_layer_forward``
    has the keywords). A long sequence is padded to whole ``S_PAD``s first
    (causal: the pad changes no real position) so that every scored request
    of a cell is ONE shape: each kind's layer compiles once a run."""
    S = h.shape[0]
    pad = -S % S_PAD if S > S_PAD else 0
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, h.shape[1]), h.dtype)])
    return _layer_forward(h, p, **kw)[:S]


@functools.partial(
    jax.jit,
    static_argnames=(
        "kind", "eps", "heads", "kv_heads", "head_dim", "beta_scale",
        "use_gate", "experts", "first_held", "held", "top_k", "routed_scale",
        "state_round", "use_delta", "head_decay", "head_norm", "use_l2",
        "use_dt_bias", "router_dtype", "use_bias", "use_shared",
    ),
)
def _layer_forward(h, p, *, kind, eps, heads, head_dim, kv_heads=0,
                   beta_scale=1.0, use_gate=True, experts=0, first_held=0,
                   held=0, top_k=0, routed_scale=1.0, state_round=None,
                   use_delta=True, head_decay=False, head_norm=True,
                   use_l2=True, use_dt_bias=True, router_dtype=None,
                   use_bias=True, use_shared=True):
    """One layer of ``kind`` over a whole sequence h: [S, H], float32. The
    wrong models of the tests and the calibration: ``state_round`` (a narrower
    state: ``(exponent bits, mantissa bits)``), ``use_delta=False`` (no
    correction), ``beta_scale`` 1 (``β`` not doubled), ``head_decay`` (the mean
    of ``g`` over a head's channels: a scalar decay a head), ``head_norm=False``
    (the output norm over ALL channels in one group), ``use_l2=False`` (``q``
    and ``k`` not normalised), ``use_dt_bias=False``, ``use_gate=False`` (either
    kind's output gate dropped), ``router_dtype``, ``use_bias=False`` (the
    router's), ``use_shared=False``."""
    with jax.default_matmul_precision("highest"):
        p = {k: dequant(v) for k, v in p.items()}
        S, H = h.shape
        if kind == "kda":
            nh, hd = heads, head_dim
            D = nh * hd

            def proj(hb):
                x = rms_norm(hb, p["input_norm"], eps)
                return jnp.concatenate(
                    [x @ p["wq"], x @ p["wk"], x @ p["wv"],
                     (x @ p["w_a_down"]) @ p["w_a_up"],
                     (x @ p["w_g_down"]) @ p["w_g_up"], x @ p["w_beta"]],
                    axis=-1)

            got = by_rows(proj, h)
            qkv = jax.nn.silu(causal_conv(got[:, :3 * D], p["conv_w"]))
            q = qkv[:, :D].reshape(S, nh, hd)
            k = qkv[:, D:2 * D].reshape(S, nh, hd)
            v = qkv[:, 2 * D:].reshape(S, nh, hd)
            if use_l2:
                q, k = l2norm(q), l2norm(k)
            q = q * hd ** -0.5
            a = got[:, 3 * D:4 * D]
            if use_dt_bias:
                a = a + p["dt_bias"]
            g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(a).reshape(
                S, nh, hd)
            if head_decay:
                g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
            z = got[:, 4 * D:5 * D]
            beta = beta_scale * jax.nn.sigmoid(got[:, 5 * D:])
            o = delta_recurrence(q, k, v, g, beta, state_round, use_delta)

            def out(hb, ob, zb):
                if head_norm:
                    y = rms_norm(ob, p["gate_norm"], eps).reshape(-1, D)
                else:
                    y = rms_norm(
                        ob.reshape(-1, D), jnp.tile(p["gate_norm"], nh), eps)
                if use_gate:
                    y = y * jax.nn.sigmoid(zb)
                return hb + y @ p["wo"]

            h = by_rows(out, h, o, z)
        else:
            nq, nk = heads * head_dim, kv_heads * head_dim

            def qkv(hb):
                x = rms_norm(hb, p["input_norm"], eps)
                return jnp.concatenate(
                    [x @ p["wq"], x @ p["wk"], x @ p["wv"], x @ p["w_gate"]],
                    axis=-1)

            got = by_rows(qkv, h)
            o = attention(
                got[:, :nq].reshape(S, heads, head_dim),
                got[:, nq:nq + nk].reshape(S, kv_heads, head_dim),
                got[:, nq + nk:nq + 2 * nk].reshape(S, kv_heads, head_dim),
                head_dim ** -0.5,
            ).reshape(S, nq)

            def out(hb, ob, gb):
                if use_gate:
                    ob = ob * jax.nn.sigmoid(gb)
                return hb + ob @ p["wo"]

            h = by_rows(out, h, o, got[:, nq + 2 * nk:])
        F = p["we_gate"].shape[-1] // held

        def ffn(hb):
            x = rms_norm(hb, p["post_norm"], eps)
            xr, wr = x, p["router"]
            if router_dtype is not None:
                xr = xr.astype(router_dtype).astype(jnp.float32)
                wr = wr.astype(router_dtype).astype(jnp.float32)
            kept = router_weights(
                xr, wr, p["router_bias"], top_k=top_k,
                routed_scale=routed_scale, use_bias=use_bias,
            )[:, first_held:first_held + held]  # the held experts' weights
            act = jax.nn.silu(x @ p["we_gate"]) * (x @ p["we_up"])
            act = (act.reshape(-1, held, F) * kept[:, :, None]).reshape(
                x.shape[0], -1)
            y = act @ p["we_down"]
            if use_shared:
                y = y + gated_mlp(x, p["ws_gate"], p["ws_up"], p["ws_down"])
            return hb + y

        return by_rows(ffn, h)


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


def _count(shape: tuple) -> int:
    return functools.reduce(lambda a, c: a * c, shape, 1)


def mixer_fixed_bytes(model: dict, weight_dtype: str, kind: str) -> int:
    """What a mixer reads whatever the rows and the context: its matmul
    leaves and, in bf16, everything else of it."""
    sh = kind_shapes(model, kind)
    return sum(
        _matmul_bytes(sh[name], weight_dtype) if name in MATMUL
        else 2 * _count(sh[name])
        for name in MIXER_ORDER[kind]
    )


def moe_fixed_bytes(model: dict, weight_dtype: str) -> int:
    """What a layer's MLP reads whatever it routes: the bf16 router, its
    bias, the norm, the shared expert, and ``we_down``'s one scale per
    channel."""
    sh = leaf_shapes(model)
    b = sum(_matmul_bytes(sh[n], weight_dtype)
            for n in ("ws_gate", "ws_up", "ws_down"))
    b += (sh["router"][0] * sh["router"][1] + sh["router_bias"][0]
          + sh["post_norm"][0]) * 2
    return b + (sh["we_down"][1] * 2 if weight_dtype == "int8" else 0)


def expert_bytes(model: dict, weight_dtype: str) -> int:
    """One routed expert of one layer: its three matrices, and under int8
    the scales of its gate and up columns."""
    H, F = model["hidden_size"], model["moe_intermediate_size"]
    b = 3 * H * F * roofline.MATMUL_BYTES[weight_dtype]
    return b + (2 * F * 2 if weight_dtype == "int8" else 0)


def held_bytes(model: dict, weight_dtype: str) -> int:
    """Everything the chip HOLDS of the model (``configs/solar_open2_250b.json``
    adds it up in words): every layer's mixer, fixed MLP part and held
    experts, and both vocabulary tables in bf16."""
    layers = kind_layers(model)
    _, held = held_experts(model)
    d = dims(model)
    return (
        sum(n * mixer_fixed_bytes(model, weight_dtype, k)
            for k, n in layers.items())
        + d["layers"] * (moe_fixed_bytes(model, weight_dtype)
                         + held * expert_bytes(model, weight_dtype))
        + 2 * d["vocab"] * d["hidden"] * 2
    )


def experts_read_per_layer(rec, lo=None, hi=None):
    """Mean distinct HELD experts read per layer per decode microstep, over
    ALL of the chip's layers, from the step records
    in ``[lo, hi]`` (default: the traced slice, else the window) — so that ×
    ``dims["layers"]`` × ``expert_bytes`` is a step's expert bytes. None where
    the records carry no such counter."""
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


def _per_step(rec, lo, hi, value):
    """The mean over the decode steps in ``[lo, hi]`` (default: the traced
    slice, else the window) and per chip of ``value(request, t)`` summed over
    the requests in flight at the step's time ``t``. None where no step falls
    inside."""
    if lo is None:
        lo, hi = rec.get("traced") or rec["window"]
    steps = samples.steps_in_window(rec, lo, hi)
    if not steps:
        return None
    total = 0.0
    for st in steps:
        t = st["t"]
        for r in rec["requests"]:
            started = r["server_started_at"]
            if started is None or started > t:
                continue
            if r["finished"] is not None and r["finished"] < t:
                continue
            total += value(r, t)
    return total / len(steps) / rec["chips"]


def live_rows(rec, lo=None, hi=None):
    """Mean requests in flight per decode step and chip, from the records'
    requests (each holds one row's recurrent state)."""
    return _per_step(rec, lo, hi, lambda r, t: 1)


def context_tokens(rec, lo=None, hi=None):
    """Per decode step and chip, the rows' context lengths summed, from the
    records' requests."""
    return _per_step(
        rec, lo, hi,
        lambda r, t: r["prompt_len"] + sum(1 for s in r["stamps"] if s <= t),
    )


def kda_state_bytes(model: dict, rec, lo=None, hi=None):
    """Bytes of recurrent state a decode microstep MUST move: live rows x KDA
    layers x the state and conv tail, read and written."""
    rows = live_rows(rec, lo, hi)
    if rows is None:
        return None
    return rows * kind_layers(model)["kda"] * state_bytes_per_row_layer(model)


def attn_kv_bytes(model: dict, rec, lo=None, hi=None, kv_bytes: int = 2):
    """Bytes of keys and values a decode microstep's attention MUST read: the
    live tokens x 4 KB x the ATTENTION layers (three of twelve)."""
    n = context_tokens(rec, lo, hi)
    if n is None:
        return None
    return (kind_layers(model)["gqa"] * n
            * arena_bytes_per_token_layer(model, kv_bytes))


def decode_step_bytes(model: dict, weight_dtype: str, stages: int,
                      live_tokens: float, rec=None, kv_bytes: int = 2) -> float:
    """Bytes one chip must move for one decode microstep (the docstring's
    "Bytes"). ``stages`` must be 1."""
    n = experts_read_per_layer(rec) if rec is not None else None
    if n is None:
        raise ValueError(
            "the records carry no experts_read counter: the bytes of a "
            "decode step of a model with experts cannot be counted"
        )
    if stages != 1:
        raise ValueError("solar_open2 bytes are counted for one stage")
    layers = kind_layers(model)
    L = layers["kda"] + layers["gqa"]
    rows = live_rows(rec)
    return (
        layers["kda"] * (
            mixer_fixed_bytes(model, weight_dtype, "kda")
            + (1.0 if rows is None else rows)
            * state_bytes_per_row_layer(model)
        )
        + layers["gqa"] * (
            mixer_fixed_bytes(model, weight_dtype, "gqa")
            + live_tokens * arena_bytes_per_token_layer(model, kv_bytes)
        )
        + L * moe_fixed_bytes(model, weight_dtype)
        + n * L * expert_bytes(model, weight_dtype)
        + roofline.head_bytes(dims(model))
    )
