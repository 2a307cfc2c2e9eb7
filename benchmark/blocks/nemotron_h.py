"""The ``nemotron_h`` block (Nemotron-3-Super-120B-A12B publishes this
``model_type``): Mamba-2 mixers, LatentMoE feed-forwards and a few attention
layers in one stack — every layer ONE sub-block, ``h ← h + f(RMSNorm(h))`` —
with a recurrent state of fixed size a request, experts in a 1,024-wide
latent space (not gated, ``relu2``), and one chip's share of 512 sigmoid-routed
experts beside a shared one. Its weights, its plain reference and its bytes.
Found by ``model_type: "nemotron_h"``.

**What a later builder must know** (``benchmark/README.md`` "A block"):

- *Layers of three kinds* (``layer_kinds``): character ``l`` of
  ``hybrid_override_pattern`` names layer ``l`` — ``M`` → ``mamba``, ``E`` →
  ``moe``, ``*`` → ``attn``; the tree is ``params["layers"] = {kind: {...}}``,
  one stack per kind in layer order; the program runs a stage's layers as runs
  of one kind in model order (``models/nemotron_h.stage_runs``).
- *The share* is ``blocks/deepseek_v3.py``'s: ``n_routed_experts`` HELD of
  ``n_routed_experts_total`` (router, bias and normalisation over ALL; only the
  held experts' terms are summed), ``vocab_size`` the slice held.
- *Leaves are the program's* (``models/nemotron_h.py``): a mixer's ``w_in [H,
  d_inner + conv_dim + heads]`` (``[z | xBC | dt]`` columns), ``conv_w [K,
  conv_dim]`` (tap ``k`` meets the input ``K-1-k`` back), ``conv_b``,
  ``dt_bias``, ``A_log``, ``D [heads]``, ``gate_norm [d_inner]``, ``w_out``; an
  expert layer's ``router [H, E]``, ``router_bias``, ``w_lat_down [H, 1024]``,
  ``w_lat_up``, ``we_up [1024, held·F]``, ``we_down [held·F, 1024]`` (NO gate
  matrix), ``ws_up [H, 5376]``, ``ws_down``; attention's ``wq`` .. ``wo``.
- *What a request holds beside the arena* (``state_bytes_per_row_layer``): per
  mixer layer the float32 state ``[128, 64, 128]`` (4 MiB) and the conv's last
  3 inputs (``3 x 10,240`` float32): fixed, whatever the context. The arena
  holds the attention layers only: 2 heads x (128 + 128) x 2 B = 1 KB a token
  and layer.

**Weights** (rules as ``blocks/deepseek_v3.py``: matmuls normal × fan-in **
-0.5, gains 1 + 0.1 n, ``router_bias`` 0.01 n, ``we_down`` by ONE expert's
fan-in) and, so that the state-space mechanism is visible: ``dt_bias`` the
inverse softplus of a LOG-UNIFORM ``dt`` in [``time_step_min``,
``time_step_max``] (the published initialisation; the uniform sample is the
normal one through its distribution function), ``A_log = log U(1, 16)`` (a
head forgets in 0.6 to 1,000 steps), ``D = 1 + 0.1 n``, the conv's taps 0.5 n
and its bias 0.5 n — none ever zero, or a dropped one would go unseen. Every
non-matmul leaf is drawn in the activation dtype, so its values are
bf16-representable; both sides use them in float32.

*So that one seed's run costs what another's does* (PERF.md section 6, PR 43's
second round: a step reads the ~5.5 held experts a token meets in each expert
layer, ~9 us each, and the plain draw moved that count by +-3.5% from seed to
seed — the 50th percentile gap by 26 us, the same for a seed in every run of
it). A trained router is held even by its correction bias; a drawn one is
not: ``relu2`` and ``silu`` have positive means, so every down projection put
a CONSTANT vector into the residual (13% of the router's input by the last
expert layer), the router's columns met it with random offsets, and whether
the 128 held columns' offsets came out above or below the other 384's was
the seed's luck. Two rules take that out, neither moves a leaf's scale:
``w_out``, ``ws_down`` and each expert's ``we_down`` are drawn with columns
that SUM TO ZERO over their rows (``centred``: the constant part of the
router's input falls to 2%), and the router's columns come in ANTITHETIC pairs
inside each rank's share, each of length 1 (``antithetic``: column ``e +
held/2`` is minus column ``e``, so whatever direction is left favours no rank
to first order, and no expert is kept more often for its column's length).
A token's count of held experts keeps the spread an even router gives it.

**Reference.** The equations of ISSUE 43 in straightforward ``jax.numpy``,
float32, matmuls at ``highest``, one sequence, no cache, no kernel. A mixer is
the SEQUENTIAL recurrence — a ``lax.scan`` over positions carrying the state,
one position at a time, independent of the program's block form: ``[z | xBC |
dt] = x̂ W_in``; ``xBC = silu(causal depthwise conv + bias)`` written as ``K``
shifted sums from a zero history; ``dt = softplus(dt + dt_bias)`` (no clamp:
``time_step_limit`` is absent); ``S ← exp(dt A) S + dt (x ⊗ B)``, ``y = S C + D
x``; the gated norm gate FIRST, ``RMSNorm_per_group(y · silu(z)) · gain``;
``W_out``. A LatentMoE is the router ``noaux_tc`` over ONE group (``s =
sigmoid(x̂ W_r)``, the ``top_k`` largest of ``s + bias``, weights ``s`` there
over their sum, times ``routed_scaling_factor``), ``u = x̂ W_↓``, then a plain
LOOP over the held experts ``Σ_e w_e relu(u W1_e)² W2_e``, ``W_↑``, beside the
shared ``relu(x̂ Ws1)² Ws2``. Attention: causal softmax over 32 query heads
sharing 2 key/value heads, NO rotary embedding. Departures, all deliberate:
attention is BLOCKED over queries and position-wise work runs ``Q_BLOCK``
positions at a time (``by_rows``); a long sequence is padded to whole
``S_PAD``s (causal: a pad changes no real position) so that the chip's
compiler meets ONE shape a kind; the kept set of the router is built from a
sorted threshold, which keeps more than k on an exact tie (measure zero).

**Bytes** (``decode_step_bytes``): per decode microstep one chip reads every
mixer's ``w_in`` / ``w_out`` and small leaves and, per LIVE row, reads AND
writes its state and conv tail (``state_bytes_per_row_layer``); of each expert
layer the bf16 router, both latent projections, the shared expert and the
routed experts the step READ (the program's counter); each attention layer's
four projections and the live keys and values at 1 KB a token; the head slice.
``scan_flops`` / ``scan_bytes``: what the block-form prefill scan of one mixer
layer must compute and move for a chunk (PERF.md section 7 (b') has its use).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import roofline, samples
from benchmark.reference import dequant
from benchmark.weights import Leaf

# ------------------------------------------------------------------- shapes

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def layer_kinds(model: dict) -> tuple:
    L = int(model["num_hidden_layers"])
    return tuple(KINDS[c] for c in str(model["hybrid_override_pattern"])[:L])


def kind_layers(model: dict) -> dict:
    kinds = layer_kinds(model)
    return {k: kinds.count(k) for k in ("mamba", "moe", "attn")}


def total_experts(model: dict) -> int:
    return int(model.get("n_routed_experts_total", model["n_routed_experts"]))


def held_experts(model: dict) -> tuple:
    """``(first id, count)`` of the routed experts held here."""
    held = int(model["n_routed_experts"])
    return int(model.get("ep_rank", 0)) * held, held


def ssm_dims(model: dict) -> dict:
    nh, hd = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    g, ds = int(model["n_groups"]), int(model["ssm_state_size"])
    return {
        "heads": nh, "head_dim": hd, "groups": g, "state": ds,
        "inner": nh * hd, "conv_dim": nh * hd + 2 * g * ds,
        "kernel": int(model.get("conv_kernel", 4)),
        "chunk": int(model.get("chunk_size", 128)),
    }


def state_bytes_per_row_layer(model: dict, moved: bool = True) -> int:
    """Bytes of ONE request's recurrent state in ONE mixer layer (float32
    state and conv tail); with ``moved`` what a decode step moves of it: each
    read AND written."""
    d = ssm_dims(model)
    held = 4 * (d["inner"] * d["state"] + (d["kernel"] - 1) * d["conv_dim"])
    return 2 * held if moved else held


def arena_bytes_per_token_layer(model: dict, kv_bytes: int = 2) -> int:
    return 2 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * kv_bytes


def dims(model: dict) -> dict:
    """What the shared code needs. ``kv_heads`` / ``head_dim`` are the
    attention layers'; ``layers`` counts every layer, so the shared
    ``roofline.kv_bytes_per_token_layer`` x layers is wrong for this block
    (2 layers of 17 keep keys) and ``decode_step_bytes`` does not use it."""
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
    """The router ``[H, E]``, every column of length 1 (``fan_in`` gives that
    in the mean only): inside each rank's share of ``held`` columns the second
    half are the first half's NEGATIVES (the module docstring says what that
    keeps still). An odd share's last column stays its own."""
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
    H = model["hidden_size"]
    Hq, Hkv, D = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    d = ssm_dims(model)
    E, (_, held) = total_experts(model), held_experts(model)
    F, Hl = model["moe_intermediate_size"], model["moe_latent_size"]
    Fs = model["moe_shared_expert_intermediate_size"]
    return {
        "norm": (H,),
        "wq": (H, Hq * D), "wk": (H, Hkv * D), "wv": (H, Hkv * D),
        "wo": (Hq * D, H),
        "w_in": (H, d["inner"] + d["conv_dim"] + d["heads"]),
        "conv_w": (d["kernel"], d["conv_dim"]), "conv_b": (d["conv_dim"],),
        "dt_bias": (d["heads"],), "A_log": (d["heads"],), "D": (d["heads"],),
        "gate_norm": (d["inner"],), "w_out": (d["inner"], H),
        "router": (H, E), "router_bias": (E,),
        "w_lat_down": (H, Hl), "w_lat_up": (Hl, H),
        "we_up": (Hl, held * F), "we_down": (held * F, Hl),
        "ws_up": (H, Fs), "ws_down": (Fs, H),
    }


ORDER = {
    "mamba": ("norm", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "gate_norm", "w_out"),
    "moe": ("norm", "router", "router_bias", "w_lat_down", "w_lat_up",
            "we_up", "we_down", "ws_up", "ws_down"),
    "attn": ("norm", "wq", "wk", "wv", "wo"),
}


def layer_leaves(model: dict) -> dict:
    """``{kind: leaves}``, each kind's in the order they are drawn."""
    shapes = leaf_shapes(model)
    _, held = held_experts(model)
    rules = {
        "norm": gain, "gate_norm": gain, "D": gain,
        "router": antithetic(held),
        "router_bias": small, "conv_w": conv_rule, "conv_b": conv_rule,
        "A_log": a_log_rule,
        "dt_bias": dt_bias_rule(float(model.get("time_step_min", 0.001)),
                                float(model.get("time_step_max", 0.1))),
    }
    down = {  # positive-mean activations in: no constant vector out
        "w_out": centred(fan_in), "ws_down": centred(fan_in),
        "we_down": centred(scaled(model["moe_intermediate_size"]), held),
    }
    out = {}
    for kind in dict.fromkeys(layer_kinds(model)):
        leaves = []
        for name in ORDER[kind]:
            if name in rules:
                leaves.append(Leaf(name, shapes[name], rules[name]))
            else:
                leaves.append(Leaf(name, shapes[name], down.get(name, fan_in),
                                   matmul=True))
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

# Read on the chip, PR 43 (PERF.md sections 2 and 6 have the runs): whole runs
# of nemotron3_super_120b_a12b.think, the finished requests of a run scored
# over their 4,096 output positions each (16-25 k positions a run), under the
# draw as it stands since the PR's second round (the docstring's antithetic
# router and centred down projections; the first round's plain draw read
# 0.0407-0.0484 sound and 0.0577-0.0834 with a bf16 state, limit 0.053).
# ``DELTA_MEAN`` lies between the two readings it must lie between. The
# LARGEST this program gives (bf16 activations, int8 weights, a float32
# state): 0.0486-0.0522 over 18 seeds, the served token the reference's
# argmax at 73-74% — a HIGH floor and no fault: the router keeps 22 of 512
# experts at a scale of 5.0, so a bf16-rounded input flips a kept expert now
# and then and each flip moves that token's hidden state by a whole expert's
# term (a mixer layer alone reads 0.006 of its output, an expert layer
# 0.05-0.07, all of it in a few tokens). The SMALLEST the nearest precision
# below gives through the harness (benchmark/tests/calibrate_nemotron_h.py): a
# bf16 recurrent state 0.0622 / 0.0670 / 0.0894 at three seeds (1.26-1.84
# times its seed's sound run; the highest-reading sound seed's among them); a
# dropped conv bias 2.15, a dropped skip term 1.68; on the axis that would
# PAY, int4 weights under the int8 label, 0.96 (the first round's draw). So
# 0.058: 11% over the largest sound reading (7 deviations over the sound mean
# of 0.0501), 7% under the smallest control. ``DELTA_MAX`` guards against
# gross errors only, as in the other blocks: a sound run's worst position
# reads 1.45-2.22 (a token drawn blind ~4), the dropped terms' 5.4-6.1 (a bf16
# state's 1.5-2.5: it is the MEAN that refuses it).
DELTA_MEAN = 0.058
DELTA_MAX = 3.0

#: positions of position-wise work (and query rows of scores) held at a time
Q_BLOCK = 512
#: sequences longer than this are padded to whole multiples of it
S_PAD = 1024


def layer_static(model: dict) -> dict:
    """Per kind: the keywords of ``layer_forward`` the published keys fix."""
    first, held = held_experts(model)
    d = ssm_dims(model)
    eps = float(model.get("layer_norm_epsilon", model.get("norm_eps", 1e-5)))
    return {
        "mamba": dict(
            eps=eps, ssm_heads=d["heads"], ssm_head_dim=d["head_dim"],
            groups=d["groups"], state=d["state"],
        ),
        "moe": dict(
            eps=eps, experts=total_experts(model), first_held=first,
            held=held, top_k=int(model["num_experts_per_tok"]),
            route_scale=float(model.get("routed_scaling_factor") or 1.0),
        ),
        "attn": dict(
            eps=eps, heads=int(model["num_attention_heads"]),
            kv_heads=int(model["num_key_value_heads"]),
            head_dim=int(model["head_dim"]),
        ),
    }


def head_static(model: dict) -> dict:
    return dict(
        eps=float(model.get("layer_norm_epsilon", model.get("norm_eps", 1e-5)))
    )


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


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
    ``Q_BLOCK`` query rows at a time against every key."""
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


def router_weights(x, router, bias, *, top_k, scale, use_bias=True):
    """``[S, E]``: an expert's weight where the router keeps it, else 0 —
    ``noaux_tc`` over one group: ``s = sigmoid(x W_r)``, the ``top_k`` largest
    of ``s + bias`` are kept; weights are the UNbiased ``s`` there over their
    sum (+1e-20), times ``scale``."""
    E = router.shape[-1]
    s = jax.nn.sigmoid(x @ router)
    choice = s + bias if use_bias else s
    kth = jnp.sort(choice, axis=-1)[:, E - top_k]
    kept = jnp.where(choice >= kth[:, None], s, 0.0)
    return kept / (kept.sum(-1, keepdims=True) + 1e-20) * scale


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def causal_conv(x, w, b):
    """x [S, C] from a zero history, ``w [K, C]``: ``y_t = b + Σ_k w[k]
    x[t - (K-1) + k]``."""
    K, S = w.shape[0], x.shape[0]
    xin = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    y = b
    for k in range(K):
        y = y + xin[k:k + S] * w[k]
    return y


def recurrence(x, dt, A, Bm, Cm, D, state_round=None):
    """The SEQUENTIAL state-space recurrence from a zero state: x [S, nh, hd],
    dt [S, nh], A, D [nh], Bm, Cm [S, g, ds] → y [S, nh, hd]. One position a
    step of a ``lax.scan``. ``state_round``: the state as a narrower type
    would hold it (a wrong model of the tests and the calibration)."""
    S, nh, hd = x.shape
    g, ds = Bm.shape[1], Bm.shape[2]
    r = nh // g

    def step(s, t):
        xt, dtt, bt, ct = t
        dA = jnp.exp(dtt * A).reshape(g, r, 1, 1)
        xdt = (xt * dtt[:, None]).reshape(g, r, hd)
        s = s * dA + xdt[..., None] * bt[:, None, None, :]
        if state_round is not None:
            s = s.astype(state_round).astype(jnp.float32)
        y = jnp.sum(s * ct[:, None, None, :], axis=-1)  # [g, r, hd]
        return s, y.reshape(nh, hd)

    _, y = jax.lax.scan(
        step, jnp.zeros((g, r, hd, ds), jnp.float32), (x, dt, Bm, Cm)
    )
    return y + D[None, :, None] * x


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
        "kind", "eps", "ssm_heads", "ssm_head_dim", "groups", "state",
        "experts", "first_held", "held", "top_k", "route_scale", "heads",
        "kv_heads", "head_dim", "state_round", "router_dtype", "use_bias",
        "use_conv_bias", "use_skip", "gate_first",
    ),
)
def _layer_forward(h, p, *, kind, eps, ssm_heads=0, ssm_head_dim=0, groups=1,
                   state=0, experts=0, first_held=0, held=0, top_k=0,
                   route_scale=1.0, heads=0, kv_heads=0, head_dim=0,
                   state_round=None, router_dtype=None, use_bias=True,
                   use_conv_bias=True, use_skip=True, gate_first=True):
    """One layer of ``kind`` over a whole sequence h: [S, H], float32.
    ``state_round``, ``router_dtype``, ``use_bias=False`` (the router's),
    ``use_conv_bias=False``, ``use_skip=False`` (``D``) and ``gate_first=False``
    are the tests' wrong models."""
    with jax.default_matmul_precision("highest"):
        p = {k: dequant(v) for k, v in p.items()}
        S, H = h.shape
        if kind == "attn":
            def qkv(hb):
                x = rms_norm(hb, p["norm"], eps)
                return jnp.concatenate(
                    [x @ p["wq"], x @ p["wk"], x @ p["wv"]], axis=-1)

            got = by_rows(qkv, h)
            nq, nk = heads * head_dim, kv_heads * head_dim
            o = attention(
                got[:, :nq].reshape(S, heads, head_dim),
                got[:, nq:nq + nk].reshape(S, kv_heads, head_dim),
                got[:, nq + nk:].reshape(S, kv_heads, head_dim),
                head_dim ** -0.5,
            )
            return by_rows(lambda hb, ob: hb + ob @ p["wo"], h,
                           o.reshape(S, -1))
        if kind == "moe":
            F = p["we_up"].shape[-1] // held
            Hl = p["we_up"].shape[0]
            w1 = p["we_up"].reshape(Hl, held, F)
            w2 = p["we_down"].reshape(held, F, Hl)

            def ffn(hb):
                x = rms_norm(hb, p["norm"], eps)
                xr, wr = x, p["router"]
                if router_dtype is not None:
                    xr = xr.astype(router_dtype).astype(jnp.float32)
                    wr = wr.astype(router_dtype).astype(jnp.float32)
                kept = router_weights(
                    xr, wr, p["router_bias"], top_k=top_k, scale=route_scale,
                    use_bias=use_bias,
                )[:, first_held:first_held + held]  # the held experts' weights
                u = x @ p["w_lat_down"]

                def one(e, acc):  # a plain loop over the held experts
                    y = relu2(u @ w1[:, e]) @ w2[e]
                    return acc + kept[:, e][:, None] * y

                r = jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))
                shared = relu2(x @ p["ws_up"]) @ p["ws_down"]
                return hb + r @ p["w_lat_up"] + shared

            return by_rows(ffn, h)
        # a Mamba-2 mixer
        nh, hd, g, ds = ssm_heads, ssm_head_dim, groups, state
        di = nh * hd
        cd = di + 2 * g * ds
        zxd = by_rows(lambda hb: rms_norm(hb, p["norm"], eps) @ p["w_in"], h)
        z, xbc, dt = zxd[:, :di], zxd[:, di:di + cd], zxd[:, di + cd:]
        bias = p["conv_b"] if use_conv_bias else jnp.zeros_like(p["conv_b"])
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], bias))
        dt = jax.nn.softplus(dt + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        D = p["D"] if use_skip else jnp.zeros_like(p["D"])
        y = recurrence(
            xbc[:, :di].reshape(S, nh, hd), dt, A,
            xbc[:, di:di + g * ds].reshape(S, g, ds),
            xbc[:, di + g * ds:].reshape(S, g, ds), D, state_round,
        ).reshape(S, di)

        def out(hb, yb, zb):
            if gate_first:
                v = (yb * jax.nn.silu(zb)).reshape(-1, g, di // g)
                v = v * jax.lax.rsqrt(
                    jnp.mean(v * v, axis=-1, keepdims=True) + eps)
                v = v.reshape(-1, di) * p["gate_norm"]
            else:
                v = yb.reshape(-1, g, di // g)
                v = v * jax.lax.rsqrt(
                    jnp.mean(v * v, axis=-1, keepdims=True) + eps)
                v = v.reshape(-1, di) * p["gate_norm"] * jax.nn.silu(zb)
            return hb + v @ p["w_out"]

        return by_rows(out, h, y, z)


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


def mamba_fixed_bytes(model: dict, weight_dtype: str) -> int:
    """What a mixer reads whatever the rows: both projections, the conv, the
    per-head vectors and the two gains (bf16)."""
    sh = leaf_shapes(model)
    b = sum(_matmul_bytes(sh[n], weight_dtype) for n in ("w_in", "w_out"))
    small_leaves = ("norm", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                    "gate_norm")
    return b + 2 * sum(
        functools.reduce(lambda a, c: a * c, sh[n], 1) for n in small_leaves)


def moe_fixed_bytes(model: dict, weight_dtype: str) -> int:
    """What an expert layer reads whatever it routes: the bf16 router and its
    bias, both latent projections, the shared expert, the norm, and
    ``we_down``'s one scale per channel."""
    sh = leaf_shapes(model)
    b = (sh["router"][0] * sh["router"][1] + sh["router_bias"][0]
         + sh["norm"][0]) * 2
    b += sum(_matmul_bytes(sh[n], weight_dtype)
             for n in ("w_lat_down", "w_lat_up", "ws_up", "ws_down"))
    return b + (sh["we_down"][1] * 2 if weight_dtype == "int8" else 0)


def attention_bytes(model: dict, weight_dtype: str) -> int:
    sh = leaf_shapes(model)
    return sum(_matmul_bytes(sh[n], weight_dtype)
               for n in ("wq", "wk", "wv", "wo")) + 2 * sh["norm"][0]


def expert_bytes(model: dict, weight_dtype: str) -> int:
    """One routed expert of one layer: its TWO matrices in the latent space,
    and under int8 the scales of its up columns."""
    Hl, F = model["moe_latent_size"], model["moe_intermediate_size"]
    b = 2 * Hl * F * roofline.MATMUL_BYTES[weight_dtype]
    return b + (F * 2 if weight_dtype == "int8" else 0)


def experts_read_per_layer(rec, lo=None, hi=None):
    """Mean distinct HELD experts read per layer per decode microstep, over
    ALL of the chip's layers (a mixer or an attention layer reads none), from
    the step records in ``[lo, hi]`` (default: the traced slice, else the
    window). None where the records carry no such counter."""
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


def live_rows(rec, lo=None, hi=None):
    """Mean requests in flight per decode step in ``[lo, hi]`` and chip, from
    the records' requests (each holds one row's recurrent state). None where
    no step falls inside."""
    if lo is None:
        lo, hi = rec.get("traced") or rec["window"]
    steps = samples.steps_in_window(rec, lo, hi)
    if not steps:
        return None
    rows = 0
    for st in steps:
        t = st["t"]
        for r in rec["requests"]:
            started = r["server_started_at"]
            if started is None or started > t:
                continue
            if r["finished"] is not None and r["finished"] < t:
                continue
            rows += 1
    return rows / len(steps) / rec["chips"]


def ssm_state_bytes(model: dict, rec, lo=None, hi=None):
    """Bytes of recurrent state a decode microstep MUST move: live rows x
    mixer layers x the state and conv tail, read and written."""
    rows = live_rows(rec, lo, hi)
    if rows is None:
        return None
    return rows * kind_layers(model)["mamba"] * state_bytes_per_row_layer(model)


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
        raise ValueError("nemotron_h bytes are counted for one stage")
    layers = kind_layers(model)
    L = sum(layers.values())
    rows = live_rows(rec)
    return (
        layers["mamba"] * (
            mamba_fixed_bytes(model, weight_dtype)
            + (1.0 if rows is None else rows)
            * state_bytes_per_row_layer(model)
        )
        + layers["moe"] * moe_fixed_bytes(model, weight_dtype)
        + n * L * expert_bytes(model, weight_dtype)
        + layers["attn"] * (
            attention_bytes(model, weight_dtype)
            + live_tokens * arena_bytes_per_token_layer(model, kv_bytes)
        )
        + roofline.head_bytes(dims(model))
    )


def scan_flops(model: dict, positions: int) -> float:
    """Floating-point operations of ONE mixer layer's block-form scan over
    ``positions`` positions of one row (multiply-add = 2): inside a block the
    scores ``C·B`` (a group), the masked product with ``x`` (a head), the
    state a block adds and the read-out of the state entering it."""
    d = ssm_dims(model)
    Q = d["chunk"]
    blocks = -(-positions // Q)
    per_block = 2 * Q * Q * d["state"] * d["groups"]  # C·B scores
    per_block += 2 * Q * Q * d["head_dim"] * d["heads"]  # (scores ⊙ decay) x
    per_block += 2 * 2 * Q * d["head_dim"] * d["state"] * d["heads"]
    return float(blocks * per_block)


def scan_bytes(model: dict, positions: int, act_bytes: int = 4) -> float:
    """Bytes ONE mixer layer's scan must move for ``positions`` positions of
    one row: ``x``, ``B``, ``C``, ``dt`` in and ``y`` out (float32 as the
    program holds them), and the row's state read and written once."""
    d = ssm_dims(model)
    per_pos = (2 * d["inner"] + 2 * d["groups"] * d["state"] + d["heads"])
    return float(positions * per_pos * act_bytes
                 + 2 * 4 * d["inner"] * d["state"])
