"""The Keye-VL-2.0 language block (the Qwen3-MoE shape — GQA with a per-head
q/k RMSNorm, softmax-routed experts in every layer — with a learned
sparse-attention indexer that chooses which keys a query attends): its
weights, its plain reference and its bytes. Found by ``model_type:
"KeyeVL2"``, the published string letter for letter.

**The layer** (``h_t`` at position ``t``; the configuration file's ``assumed``
says which of these the published config does not state):

```
x   = rmsnorm(h; g_in)
q   = x Wq [Nh x D]      k = x Wk [Nkv x D]      v = x Wv [Nkv x D]
q_j = rmsnorm_D(q_j; g_q)   k_j = rmsnorm_D(k_j; g_k)        per head
q, k = rotary(., t; theta, rotate_half over all D dims)

qI  = x WqI [Hi x Di]     kI = layernorm_Di(x WkI; gamma, beta) [Di]     wI = x WwI [Hi]
qI, kI = rotary(., t; theta, over all Di dims)
I[t, s] = sum_j wI[t, j] · Hi^-1/2 · Di^-1/2 · relu(qI[t, j] · kI[s])         s <= t
S_t     = the topk positions s <= t with the largest I[t, s] (all of them
          while t + 1 <= topk; a tie goes to the lower position)

a_j = softmax over s in S_t of (q_j · k_{j // G, s}) / sqrt(D);  o_j = sum a_j[s] v_{j // G, s}
h   = h + concat_j(o_j) Wo
y   = rmsnorm(h; g_post);  p = softmax(y Wr) in float32;  E_t = top-k of p;  w_e = p_e / sum_{E_t} p
h   = h + sum_{e in E_t} w_e · (silu(y Wg_e) * (y Wu_e)) Wd_e
```

**Weights.** Seventeen leaves a layer, in the fixed order of ``LEAF_ORDER``
(names and shapes are the program's, ``models/llama.init_layer_params``). The
matmuls are normal x fan-in ** -0.5 (``we_down`` by an EXPERT's fan-in) and
quantised under ``weight_dtype: int8``; the router and the index heads' weight
``w_idx`` (16 columns) stay in bf16. ``wq`` and ``wk`` are drawn at TWICE the
fan-in scale (``qk_fan_in``, as OLMoE's: at the plain scale the per-head q/k
norm is the identity but for its gains and a program that dropped it would go
unseen; at twice, its scores without the norm are four times too sharp). What
makes ``correct`` SEE the selection: ISSUE 49 expected that the q/k norm gains
would have to be raised until a head's softmax is peaked. Read on the chip
(12 layers, one reply, positions past a context of 2,700; PERF.md section 6,
PR 49), they need not be and must not be: at ``QK_GAIN`` 1 — a head's logits
at a deviation of about 1, the plain ``1 + 0.1 n`` — a program that keeps the
most recent 2,048 keys reads a mean margin of 0.42 and one that selects nothing
0.099 where the sound program reads 0.0065; at 1.4 the SOUND program reads
0.042 (every layer's softmax sharpens what bf16 rounding the one before it
left: served token = reference argmax at 72%), and at 2 twelve layers in bf16
part from the float32 reference altogether (0.58 from the first position; two
layers read 0.0014). Norm gains 1 + 0.1 n, the index key's LayerNorm bias
0.1 n: never 1 or 0, or a dropped one goes unseen.

**Reference.** The equations above in straightforward ``jax.numpy``, float32,
matmuls at ``highest``; no cache, no kernel, one sequence, every position at
once — scores, selection and attention ``Q_BLOCK`` query rows at a time, and
what treats every position alike ``by_rows``, so that 9 k positions fit beside
one resident float32 layer (2.5 GB). The expert sum is the DENSE form (every
expert for every position, the unchosen multiplied by zero): it shares no
routing, grouping or kernel with the program. The selection keeps exactly
``topk`` keys: above the ``topk``-th largest score, and of the keys that tie
with it the earliest.

**Bytes.** A decode microstep reads every layer's attention and indexer
weights, router and norms whole, the output head once, of the experts those
its rows chose (the program's counter), the index keys of the rows' live
tokens (``Di`` x 2 B a token and layer) once a row's context is longer than
``topk`` — and of K and V only the CHOSEN tokens', ``min(context, topk)`` a row
and layer: a step that read every live token would take longer over the same
count and read lower, never higher.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import roofline, samples
from benchmark.reference import dequant
from benchmark.weights import Leaf

# ------------------------------------------------------------------- shapes


def indexer(model: dict) -> dict:
    sa = model["sa_config"]
    return {"heads": int(sa["indexer_num_heads"]),
            "dim": int(sa["indexer_head_dim"]), "topk": int(sa["topk"])}


def dims(model: dict) -> dict:
    """What the shared code needs of the published keys."""
    return {
        "layers": int(model["num_hidden_layers"]),
        "hidden": int(model["hidden_size"]),
        "vocab": int(model["vocab_size"]),
        "kv_heads": int(model["num_key_value_heads"]),
        "head_dim": int(model["head_dim"]),
    }


# ------------------------------------------------------------------ weights

MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "we_gate", "we_up", "we_down",
                 "wq_idx", "wk_idx")
LEAF_ORDER = (
    "input_norm", "wq", "wk", "wv", "wo", "post_norm",
    "router", "we_gate", "we_up", "we_down", "q_norm", "k_norm",
    "wq_idx", "wk_idx", "w_idx", "k_idx_norm", "k_idx_bias",
)
GAIN_STD = 0.1
QK_GAIN = 1.0
QK_SCALE = 2.0


def fan_in(x):
    return x * x.shape[-2] ** -0.5


def qk_fan_in(x):
    """``wq``, ``wk``: off the scale at which the q/k norm is the identity."""
    return QK_SCALE * fan_in(x)


def gain(x):
    return 1.0 + GAIN_STD * x


def qk_gain(x):
    """``q_norm``, ``k_norm``: a head's logits at a deviation of about
    ``QK_GAIN ** 2`` (the docstring's "Weights" says why it is 1)."""
    return QK_GAIN * (1.0 + GAIN_STD * x)


def bias(x):
    return GAIN_STD * x


def plain(x):
    return x


def expert_fan_in(experts: int):
    """``we_down [E·F, H]``: scaled by ONE expert's fan-in F."""
    def rule(x):
        return x * (x.shape[-2] // experts) ** -0.5
    return rule


def leaf_shapes(model: dict) -> dict:
    H, F, E = (model["hidden_size"], model["moe_intermediate_size"],
               model["num_experts"])
    D = model["head_dim"]
    Nh, Nkv = model["num_attention_heads"], model["num_key_value_heads"]
    ix = indexer(model)
    return {
        "input_norm": (H,), "post_norm": (H,),
        "wq": (H, Nh * D), "wk": (H, Nkv * D), "wv": (H, Nkv * D),
        "wo": (Nh * D, H),
        "router": (H, E),
        "we_gate": (H, E * F), "we_up": (H, E * F), "we_down": (E * F, H),
        "q_norm": (D,), "k_norm": (D,),
        "wq_idx": (H, ix["heads"] * ix["dim"]), "wk_idx": (H, ix["dim"]),
        "w_idx": (H, ix["heads"]),
        "k_idx_norm": (ix["dim"],), "k_idx_bias": (ix["dim"],),
    }


def layer_leaves(model: dict) -> tuple:
    """The leaves of one layer, in the order they are drawn."""
    shapes = leaf_shapes(model)
    rules = {
        "we_down": expert_fan_in(int(model["num_experts"])),
        "router": fan_in, "w_idx": fan_in, "wq": qk_fan_in, "wk": qk_fan_in,
        "q_norm": qk_gain, "k_norm": qk_gain, "k_idx_bias": bias,
    }
    return tuple(
        Leaf(name, shapes[name],
             rules.get(name, fan_in if name in MATMUL_LEAVES else gain),
             matmul=name in MATMUL_LEAVES)
        for name in LEAF_ORDER
    )


def tables(model: dict) -> tuple:
    V, H = model["vocab_size"], model["hidden_size"]
    return (
        Leaf("embed", (V, H), plain, vocab_axis=0),
        Leaf("final_norm", (H,), gain),
        Leaf("lm_head", (H, V), fan_in, vocab_axis=1),
    )


# ---------------------------------------------------------------- reference

# Read on the chip, PR 49 (PERF.md sections 2 and 6 have the runs): whole runs
# of keye_vl2_30b_a3b.longgen, the ONE request that finishes in ramp and window
# (three in a traced run) scored over its 8,192 output positions; logits over
# the 37,984 ids of the slice.
# - The LARGEST this program gives (bf16 activations and arenas, int8 weights)
#   over 14 seeds: mean margin 0.00126, 0.00273, 0.00285, 0.00310, 0.00315,
#   0.00413, 0.00437, 0.00484, 0.00495, 0.00525, 0.00552, 0.00556, 0.00568,
#   0.00662 (mean 0.0042, deviation 0.0014: the SEED moves it five-fold — how
#   many keys lie at the edge of a query's top-2,048, where the program's bf16
#   products and the reference's float32 choose differently), worst position
#   0.28-0.66, served token = reference argmax at 90.9-97.6% of positions.
# - The two controls ISSUE 49 asked for, through the harness
#   (benchmark/tests/calibrate_keye_vl2.py, seed 2147483659 whose sound run
#   reads 0.00568): ``recent`` (the most recent 2,048 keys in place of the
#   indexer's choice) **1.069** (worst 5.33, argmax 32.9%); ``all`` (no
#   selection: the unselected kernels) **0.136** (worst 1.77, argmax 56.2%,
#   three requests: its step is 2.3 ms) — 119 and 15 times the limit, each
#   `"correct": false` by the mean AND by the worst position.
# - The nearest precision below the bf16 the configuration states, ``fp8_kv``
#   (keys and values rounded to e4m3's three mantissa bits before they enter
#   their arenas): 0.00398 / 0.00881 / 0.01033 at three seeds whose sound runs
#   read 0.00273 / 0.00568 / 0.00310 (1.46, 1.55 and 3.3 times). **The limit
#   CANNOT stand between these and the sound runs**: the seeds' own spread
#   (0.0013-0.0066) is wider than what an fp8 KV state adds, so it refuses fp8
#   at one seed of three (0.01033) and passes it at the lowest (0.00398,
#   `"correct": true`) — PERF.md section 7 (z); what keeps an fp8 arena out is
#   the arena's type check and the tier-1 logits tests, as on OLMoE and Jamba.
#   The control of that precision WITH room, on the axis that would pay (a
#   step reads 0.7 GB of weights): ``int4_weights`` under the int8 label reads
#   **0.383** (worst 2.39, argmax 36.0%) where its seed's sound run reads
#   0.00273 — `"correct": false`, 43 times the limit.
#   ``bf16_scores`` (the index scores rounded to bfloat16 after they are
#   summed) reads 0.00476 where its seed's sound run reads 0.00568: inside the
#   noise of ONE changed rounding, as expected of a choice among near-ties.
# PR 49 set 0.009 from those: 36% over the largest reading of ONE request, the
# cycle's first. A run scores every request the program finishes — one at
# PR 49's speed, two since PR 50, three since PR 51, a fourth from ~16 s a
# reply — and about one sound run in eight read over 0.009 (0.0090116 at PR
# 51, 0.009705 at PR 50; PERF.md sections 2 and 7).
#
# Read again on the chip, PR 59 (benchmark/tests/calibrate_keye_vl2.py, which
# prints a request's own margins; ``sound``, ``--seconds 92``: the cycle's
# first FIVE requests finish and are scored, 40,960 positions a run; PR 58's
# program; twelve seeds of the builder's own, chiprun_out/cal59):
# - mean margin over the first five: 0.004782, 0.005820, 0.005893, 0.006108,
#   0.006141, 0.006190, 0.006437, 0.006454, 0.006865, 0.007157, 0.007918,
#   0.008318 (mean 0.0065, deviation 0.0010);
# - over the first k, the largest of the twelve at k = 1 .. 5: 0.007938,
#   0.007824, 0.007873, 0.008054, 0.008318 (the smallest 0.002438 .. 0.004782):
#   whatever count a run finishes, the LARGEST sound reading is 0.008318;
# - ONE request's own mean 0.000019-0.010830 (the sixty: mean 0.0065; the
#   largest at each place in the cycle 0.007938, 0.009791, 0.009273, 0.010336,
#   0.010830; PR 50 read a 512-token prompt's at 0.0121): it follows the seed
#   as much as the request, and rises through a reply;
# - worst position of a run 0.486-0.767.
# The controls that must fail, through the harness at 50 s at two of those
# seeds, 2147059003 / 3000059801, whose sound runs read 0.006870 / 0.007753
# over their first three (mean margin of the run; worst position):
#   ``all`` **0.190960 / 0.165619** (2.30 / 2.42; FOUR requests: its step is
#   2.1 ms; the first request alone 0.131625 / 0.104832, the smallest reading
#   of a control at any count);
#   ``int4_weights`` **0.380700 / 0.457209** (2.55 / 2.43; three requests);
#   ``recent`` **1.086459 / 1.150422** (5.32 / 5.56; three requests)
# — each `"correct": false` by the mean and by the worst position.
# The limit: 0.02. Lower reading 0.008318 (the largest sound one at any count
# of requests), upper reading 0.104832 (the smallest control's, 12.6 times the
# lower). 0.02 is 2.4 times the lower (14 deviations over the first-five
# mean), 2.06 times PR 50's 0.009705 and 1.65 times the largest single request
# ever read (0.0121: a run that finished only such a request still passes
# with more than the 25% of room ISSUE 59 asks for); ``all`` fails 8.3 and 9.5
# times over as a run reads it, and 5.2 times by its first request alone: the
# largest round limit under which every reading of ``all`` fails five times
# over, as ISSUE 59 asks (the records expected ~0.016 from ``all`` at 0.136;
# it reads 0.166-0.191 over four requests, and the room goes above the sound
# readings: fresh seeds read higher than a dozen did). What it cannot do is
# what 0.009 could not do either: an fp8 KV state (0.00398-0.01033 above) lies
# inside the seeds' own band — the arena's type check and the tier-1 logits
# tests refuse that, not this limit. ``DELTA_MAX`` 1.5 stays: 1.96 times the
# largest sound worst position of the sixty requests (0.767), under every
# control run's worst.
DELTA_MEAN = 0.02
DELTA_MAX = 1.5

#: query rows of scores the reference holds at a time
Q_BLOCK = 512
#: sequences longer than this are padded to whole multiples of it
S_PAD = 1024


def layer_static(model: dict) -> dict:
    """The keywords of ``layer_forward`` the published keys fix."""
    ix = indexer(model)
    return dict(
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
        experts=int(model["num_experts"]),
        top_k=int(model["num_experts_per_tok"]),
        renorm=bool(model.get("norm_topk_prob", False)),
        index_heads=ix["heads"], topk=ix["topk"],
    )


def head_static(model: dict) -> dict:
    return dict(eps=float(model["rms_norm_eps"]))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain + bias


def rotary(x, theta):
    """x: [S, N, D] at positions 0..S-1, rotated by halves over all D."""
    S, _, D = x.shape
    inv_freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def by_rows(fn, *xs):
    """``fn`` (work that treats every position alike) over the rows of
    ``xs``, ``Q_BLOCK`` positions at a time where they divide a long
    sequence (``blocks/mimo_v2.by_rows`` says what it saves the compiler)."""
    S = xs[0].shape[0]
    if S <= Q_BLOCK or S % Q_BLOCK:
        return fn(*xs)
    out = jax.lax.map(
        lambda b: fn(*b),
        tuple(x.reshape(S // Q_BLOCK, Q_BLOCK, *x.shape[1:]) for x in xs),
    )
    return out.reshape(S, *out.shape[2:])


def keep_topk(score, topk: int):
    """``[rows, S]`` bool: the ``topk`` largest of each row's finite scores —
    above the ``topk``-th largest, and of those that tie with it the earliest
    positions, as many as are left; a row with no more than ``topk`` finite
    scores keeps them all."""
    K = min(topk, score.shape[-1])
    kth = jax.lax.top_k(score, K)[0][:, -1:]
    above = score > kth
    tie = (score == kth) & (score > -jnp.inf)
    left = K - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= left))


def selected_attention(q, k, v, qi, ki, wi, *, topk, select, score_dtype):
    """q [S, Nh, D], k / v [S, Nkv, D]; index queries qi [S, Hi, Di], the index
    key ki [S, Di], head weights wi [S, Hi] → [S, Nh, D]: per query the causal
    softmax over the keys it SELECTED, ``Q_BLOCK`` query rows at a time.
    ``select``: ``"indexer"`` (the model), ``"recent"`` (the most recent
    ``topk`` keys) or ``"all"`` (no selection) — the last two are the
    controls. ``score_dtype``: the index products rounded to a lower
    precision (another reading beside the thresholds)."""
    S, Nh, D = q.shape
    Nkv = k.shape[1]
    G = Nh // Nkv
    block = next(b for b in (Q_BLOCK, 256, S) if b <= S and S % b == 0)
    if score_dtype is not None:
        qi, ki = (jax.lax.reduce_precision(
            a, *{"bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}[score_dtype]
        ) for a in (qi, ki))

    def rows(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block, axis=0)
        i = (i0 + jnp.arange(block))[:, None]
        j = jnp.arange(S)[None, :]
        keep = j <= i
        if select == "recent":
            keep &= j > i - topk
        elif select == "indexer":
            qib = jax.lax.dynamic_slice_in_dim(qi, i0, block, axis=0)
            wib = jax.lax.dynamic_slice_in_dim(wi, i0, block, axis=0)
            s = jax.nn.relu(jnp.einsum("shd,td->sht", qib, ki))
            score = jnp.einsum("sh,sht->st", wib, s)
            keep = keep_topk(jnp.where(keep, score, -jnp.inf), topk)
        qg = qb.reshape(block, Nkv, G, D)
        s = jnp.einsum("skgd,tkd->kgst", qg, k) / math.sqrt(D)
        s = jnp.where(keep[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgst,tkd->skgd", p, v).reshape(block, Nh, D)

    out = jax.lax.map(rows, jnp.arange(0, S, block))
    return out.reshape(S, Nh, D)


def router_weights(x, router, top_k: int, renorm: bool):
    """``[S, E]``: the router's probability where an expert is kept, else 0."""
    p = jax.nn.softmax(x @ router, axis=-1)
    kth = jnp.sort(p, axis=-1)[:, p.shape[-1] - top_k]
    kept = jnp.where(p >= kth[:, None], p, 0.0)
    if renorm:
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return kept


def layer_forward(h, p, **kw):
    """One layer over a whole sequence h: [S, H], float32 (``_layer_forward``
    has the keywords). A long sequence is padded to whole ``S_PAD``s first
    (causal: the pad changes no real position) so that every scored request
    of a cell is ONE shape and the layer compiles once a run."""
    S = h.shape[0]
    pad = -S % S_PAD if S > S_PAD else 0
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, h.shape[1]), h.dtype)])
    return _layer_forward(h, p, **kw)[:S]


@functools.partial(
    jax.jit,
    static_argnames=(
        "heads", "kv_heads", "eps", "theta", "experts", "top_k", "renorm",
        "index_heads", "topk", "select", "score_dtype", "kv_round",
        "index_bias", "index_weights", "router_dtype",
    ),
)
def _layer_forward(h, p, *, heads, kv_heads, eps, theta, experts, top_k,
                   renorm, index_heads, topk, select="indexer",
                   score_dtype=None, kv_round=None, index_bias=True,
                   index_weights=True, router_dtype=None):
    """``select`` / ``score_dtype`` as ``selected_attention``; ``kv_round``
    (keys and values as a cache of lower precision would hold them),
    ``index_bias=False`` (the index key's LayerNorm bias dropped),
    ``index_weights=False`` (every index head weighted alike),
    ``router_dtype`` and an overridden ``theta`` are the tests' wrong models."""
    with jax.default_matmul_precision("highest"):
        p = {k: dequant(v) for k, v in p.items()}
        S, H = h.shape
        x = by_rows(lambda hb: rms_norm(hb, p["input_norm"], eps), h)
        q, k, v = (
            by_rows(lambda xb, w=p[n]: xb @ w, x) for n in ("wq", "wk", "wv")
        )
        q = rms_norm(q.reshape(S, heads, -1), p["q_norm"], eps)
        k = rms_norm(k.reshape(S, kv_heads, -1), p["k_norm"], eps)
        v = v.reshape(S, kv_heads, -1)
        q, k = rotary(q, theta), rotary(k, theta)
        if kv_round is not None:
            mant = {"float8_e4m3fn": (4, 3), "bfloat16": (8, 7)}[kv_round]
            k = jax.lax.reduce_precision(k, *mant)
            v = jax.lax.reduce_precision(v, *mant)
        qi = by_rows(lambda xb: xb @ p["wq_idx"], x).reshape(S, index_heads, -1)
        Di = qi.shape[-1]
        ki = layer_norm(
            by_rows(lambda xb: xb @ p["wk_idx"], x), p["k_idx_norm"],
            p["k_idx_bias"] if index_bias else 0.0, eps,
        )
        qi, ki = rotary(qi, theta), rotary(ki[:, None, :], theta)[:, 0]
        wi = (by_rows(lambda xb: xb @ p["w_idx"], x) if index_weights
              else jnp.ones((S, index_heads)))
        wi = wi * (index_heads * Di) ** -0.5
        o = selected_attention(
            q, k, v, qi, ki, wi, topk=topk, select=select,
            score_dtype=score_dtype,
        )

        def rest(hb, ob):  # what follows attention, a position at a time
            hb = hb + ob @ p["wo"]
            y = rms_norm(hb, p["post_norm"], eps)
            yr, wr = y, p["router"]
            if router_dtype is not None:
                yr = yr.astype(router_dtype).astype(jnp.float32)
                wr = wr.astype(router_dtype).astype(jnp.float32)
            kept = router_weights(yr, wr, top_k, renorm)  # [B, E]
            F = p["we_gate"].shape[-1] // experts
            act = jax.nn.silu(y @ p["we_gate"]) * (y @ p["we_up"])
            act = (act.reshape(-1, experts, F) * kept[:, :, None]).reshape(
                act.shape)
            return hb + act @ p["we_down"]

        return by_rows(rest, h, o.reshape(S, -1))


def embed(tables: dict, ids, *, eps=None):
    return tables["embed"][ids].astype(jnp.float32)


def logits(h, tables: dict, *, eps):
    gain_ = tables["final_norm"].astype(jnp.float32)
    head = tables["lm_head"].astype(jnp.float32)
    return by_rows(lambda hb: rms_norm(hb, gain_, eps) @ head, h)


# -------------------------------------------------------------------- bytes


def _matmul_bytes(shape: tuple, weight_dtype: str) -> int:
    """A matmul leaf and, under int8, its one bf16 scale per output channel."""
    b = shape[0] * shape[1] * roofline.MATMUL_BYTES[weight_dtype]
    return b + (shape[1] * 2 if weight_dtype == "int8" else 0)


def indexer_weight_bytes(model: dict, weight_dtype: str) -> int:
    """One layer's indexer: its two quantised projections, the index heads'
    weight and the index key's LayerNorm in bf16."""
    s = leaf_shapes(model)
    return (
        _matmul_bytes(s["wq_idx"], weight_dtype)
        + _matmul_bytes(s["wk_idx"], weight_dtype)
        + (s["w_idx"][0] * s["w_idx"][1] + 2 * s["k_idx_norm"][0]) * 2
    )


def dense_layer_bytes(model: dict, weight_dtype: str) -> int:
    """What every decode microstep reads of one layer whatever it routes and
    selects: the attention matmuls, the indexer's weights, the router and the
    norms in bf16, and ``we_down``'s one scale per output channel."""
    s = leaf_shapes(model)
    b = sum(_matmul_bytes(s[n], weight_dtype) for n in ("wq", "wk", "wv", "wo"))
    b += indexer_weight_bytes(model, weight_dtype)
    b += (s["router"][0] * s["router"][1] + 2 * s["input_norm"][0]
          + 2 * s["q_norm"][0]) * 2
    if weight_dtype == "int8":
        b += s["we_down"][1] * 2
    return b


def expert_bytes(model: dict, weight_dtype: str) -> int:
    """One expert of one layer: its three matrices, and under int8 the scales
    of its gate and up columns."""
    H, F = model["hidden_size"], model["moe_intermediate_size"]
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


def tokens_per_step(rec, topk: int, lo=None, hi=None):
    """``(live, chosen, scored)`` per decode step in ``[lo, hi]``, the mean
    over the steps of the rows' context lengths summed: whole; cut to
    ``topk`` a row (the tokens whose K/V a selecting step reads); and whole
    again where some row of the step is past ``topk`` (the index keys a step
    scores: none while the selection is everything) — from the records'
    requests. None where no step falls inside."""
    if lo is None:
        lo, hi = rec.get("traced") or rec["window"]
    steps = samples.steps_in_window(rec, lo, hi)
    if not steps:
        return None
    live = chosen = scored = 0.0
    for st in steps:
        t = st["t"]
        ctx = []
        for r in rec["requests"]:
            started = r["server_started_at"]
            if started is None or started > t:
                continue
            if r["finished"] is not None and r["finished"] < t:
                continue
            ctx.append(r["prompt_len"] + sum(1 for s in r["stamps"] if s <= t))
        live += sum(ctx)
        chosen += sum(min(n, topk) for n in ctx)
        scored += sum(ctx) if ctx and max(ctx) > topk else 0
    n = len(steps) * rec["chips"]
    return live / n, chosen / n, scored / n


def attn_kv_bytes(model: dict, rec, lo=None, hi=None, kv_bytes: int = 2):
    """Bytes of keys and values a decode microstep's attention MUST read: the
    tokens its rows chose, ``min(context, topk)`` a row, x what the arena holds
    of one token and layer x this chip's layers."""
    got = tokens_per_step(rec, indexer(model)["topk"], lo, hi)
    if got is None:
        return None
    d = dims(model)
    return d["layers"] * got[1] * roofline.kv_bytes_per_token_layer(d, kv_bytes)


def index_bytes(model: dict, weight_dtype: str, rec, lo=None, hi=None,
                kv_bytes: int = 2):
    """Bytes a decode microstep's indexer MUST read: every layer's indexer
    weights, and the index keys of the live tokens it scores (``Di`` x 2 B a
    token and layer)."""
    got = tokens_per_step(rec, indexer(model)["topk"], lo, hi)
    if got is None:
        return None
    return dims(model)["layers"] * (
        indexer_weight_bytes(model, weight_dtype)
        + got[2] * indexer(model)["dim"] * kv_bytes
    )


def decode_step_bytes(model: dict, weight_dtype: str, stages: int,
                      live_tokens: float, rec=None, kv_bytes: int = 2) -> float:
    """Bytes one chip must read for one decode microstep (the docstring's
    "Bytes"): a SELECTING step's — the chosen tokens' K/V, not the live
    tokens', plus the index keys it scored. ``live_tokens`` is the shared
    reader's count of the live context; what of it was chosen and scored is
    read from the records (without them: all of it, cut to ``topk`` once)."""
    n = experts_read_per_layer(rec) if rec is not None else None
    if n is None:
        raise ValueError(
            "the records carry no experts_read counter: the bytes of a "
            "decode step of a model with experts cannot be counted"
        )
    d, ix = dims(model), indexer(model)
    got = tokens_per_step(rec, ix["topk"]) if rec.get("requests") else None
    chosen, scored = (
        (min(live_tokens, ix["topk"]),
         live_tokens if live_tokens > ix["topk"] else 0.0)
        if got is None else got[1:]
    )
    layers = d["layers"] / stages
    return (
        layers * (dense_layer_bytes(model, weight_dtype)
                  + n * expert_bytes(model, weight_dtype))
        + roofline.head_bytes(d) / stages
        + layers * chosen * roofline.kv_bytes_per_token_layer(d, kv_bytes)
        + layers * scored * ix["dim"] * kv_bytes
    )
