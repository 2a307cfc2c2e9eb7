"""The ``jamba`` block (AI21-Jamba2-3B publishes this ``model_type``): Mamba-1
mixers and a few attention layers in one stack — every layer TWO sub-blocks,
``h ← h + mixer(RMSNorm_in(h))`` then ``h ← h + MLP(RMSNorm_ff(h))`` — with a
recurrent state of fixed size a request whose decay differs per channel AND
per state value, ONE key/value head under 20 query heads, a dense gated MLP in
every layer and a TIED head. Its weights, its plain reference and its bytes.
Found by ``model_type: "jamba"``.

**What a later builder must know** (``benchmark/README.md`` "A block"):

- *Layers of two kinds* (``layer_kinds``): layer ``l`` is ``attn`` where ``l %
  attn_layer_period == attn_layer_offset`` and ``mamba`` otherwise (the
  published model code's ``layers_block_type``; at the published keys layers 7
  and 21 of 28). A kind's layer is its mixer AND the MLP; the tree is
  ``params["layers"] = {kind: {...}}``, one stack per kind in layer order; the
  program runs a stage's layers as runs of one kind in model order.
- ``num_experts`` is 1: EVERY layer's feed-forward is the dense gated MLP;
  ``expert_layer_period`` / ``expert_layer_offset`` / ``num_experts_per_tok``
  are kept and not read.
- *Leaves are the program's* (``models/jamba.py``): every layer's ``norm``,
  ``post_norm``, ``w_gate``, ``w_up``, ``w_down``; attention's ``wq`` .. ``wo``;
  a mixer's ``w_in [H, 2 d_inner]`` (``[x | z]`` columns), ``conv_w [K,
  d_inner]`` (tap ``k`` meets the input ``K-1-k`` back), ``conv_b``, ``w_x
  [d_inner, dt_rank + 2 state]`` (``[δ | B | C]`` columns), the THREE norms'
  gains ``dt_norm``, ``b_norm``, ``c_norm``, ``w_dt [dt_rank, d_inner]``,
  ``dt_bias``, ``A_log [d_inner, state]``, ``D [d_inner]``, ``w_out``. NO
  ``lm_head``: the head is ``embed`` (``tie_word_embeddings``).
- *What a request holds beside the arena* (``state_bytes_per_row_layer``): per
  mixer layer the float32 state ``[16, 5120]`` (327,680 B) and the conv's last
  3 inputs (``3 x 5,120`` float32, 61,440 B): 389,120 B, fixed whatever the
  context. The arena holds the attention layers only: 1 head x (128 + 128) x
  2 B = 512 B a token and layer.

**Weights** (rules as ``blocks/nemotron_h.py``: matmuls normal × fan-in **
-0.5, gains 1 + 0.1 n) and, so that the mechanism is visible: ``A_log[c, n] =
log(n + 1)`` AS PUBLISHED (the S4D-real initialisation of the model code: a
channel's 16 state values forget at 16 different rates, the slowest in ~1 /
dt steps), ``dt_bias`` the inverse softplus of a LOG-UNIFORM ``dt`` in [0.001,
0.1] (the Mamba initialisation; the uniform sample is the normal one through
its distribution function), the three norms' gains and ``D`` at 1 + 0.1 n,
the conv's taps 0.5 n and its bias 0.5 n — none ever zero, so a dropped norm,
skip, bias or tap moves the margins. ``w_out`` and ``w_down`` are drawn with
columns that SUM TO ZERO over their rows (``centred``: silu has a positive
mean, and a constant vector in the residual is what no trained model has).
The TIED table is normal × hidden ** -0.5, so that the logits have about unit
variance (an embedding of length ~1 enters a residual that grows past it).
Every non-matmul leaf is drawn in the activation dtype, so its values are
bf16-representable; both sides use them in float32.

**Reference.** The equations of ISSUE 45 in straightforward ``jax.numpy``,
float32, matmuls at ``highest``, one sequence, no cache, no kernel. A mixer is
the recurrence POSITION BY POSITION — a ``lax.scan`` over positions carrying
``S [d_inner, state]`` from zero: ``[x | z] = ĥ W_in``; ``x = silu(causal
depthwise conv + bias)`` written as ``K`` shifted sums from a zero history;
``[δ | B | C] = x W_x``, each through its RMSNorm; ``dt = softplus(δ W_dt +
b_dt)``; ``S ← exp(dt ⊗ A) S + (dt x) ⊗ B``, ``y = S C + D x``; ``y · silu(z)``;
``W_out``. Attention: causal softmax over 20 query heads sharing ONE key/value
head at ``1/√128``, NO rotary embedding. The MLP ``(silu(ĥ W_g) ⊙ ĥ W_u)
W_d``. Logits against the embedding table. Departures, all deliberate:
attention is BLOCKED over queries and position-wise work runs ``Q_BLOCK``
positions at a time (``by_rows``); a long sequence is padded to whole
``S_PAD``s (causal: a pad changes no real position) so that the chip's
compiler meets ONE shape a kind.

**Bytes** (``decode_step_bytes``): per decode microstep one chip reads every
layer's MLP and norms, every mixer's projections and small leaves and, per
LIVE row, reads AND writes its state and conv tail
(``state_bytes_per_row_layer``); each attention layer's four projections and
the live keys and values at 512 B a token; the tied table once, as the head.
``scan_bytes`` / ``scan_flops``: what a ONE-PASS scan in time of one mixer
layer must move and compute for a chunk (``prefill_scan_hbm_pct`` reads the
first).
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
    period = int(model["attn_layer_period"])
    offset = int(model["attn_layer_offset"])
    return tuple(
        "attn" if l % period == offset else "mamba"
        for l in range(int(model["num_hidden_layers"]))
    )


def kind_layers(model: dict) -> dict:
    kinds = layer_kinds(model)
    return {k: kinds.count(k) for k in ("mamba", "attn")}


def head_dim(model: dict) -> int:
    return int(model.get("head_dim") or
               model["hidden_size"] // model["num_attention_heads"])


def ssm_dims(model: dict) -> dict:
    H = int(model["hidden_size"])
    rank = model["mamba_dt_rank"]
    return {
        "inner": int(model["mamba_expand"]) * H,
        "state": int(model["mamba_d_state"]),
        "kernel": int(model.get("mamba_d_conv", 4)),
        "rank": -(-H // 16) if rank == "auto" else int(rank),
    }


def state_bytes_per_row_layer(model: dict, moved: bool = True) -> int:
    """Bytes of ONE request's recurrent state in ONE mixer layer (float32
    state and conv tail); with ``moved`` what a decode step moves of it: each
    read AND written."""
    d = ssm_dims(model)
    held = 4 * d["inner"] * (d["state"] + d["kernel"] - 1)
    return 2 * held if moved else held


def arena_bytes_per_token_layer(model: dict, kv_bytes: int = 2) -> int:
    return 2 * int(model["num_key_value_heads"]) * head_dim(model) * kv_bytes


def dims(model: dict) -> dict:
    """What the shared code needs. ``kv_heads`` / ``head_dim`` are the
    attention layers'; ``layers`` counts every layer, so the shared
    ``roofline.kv_bytes_per_token_layer`` x layers is wrong for this block
    (2 layers of 28 keep keys) and ``decode_step_bytes`` does not use it."""
    return {
        "layers": int(model["num_hidden_layers"]),
        "hidden": int(model["hidden_size"]),
        "vocab": int(model["vocab_size"]),
        "kv_heads": int(model["num_key_value_heads"]),
        "head_dim": head_dim(model),
    }


# ------------------------------------------------------------------ weights

GAIN_STD = 0.1
CONV_STD = 0.5
DT_MIN, DT_MAX = 0.001, 0.1


def fan_in(x):
    return x * x.shape[-2] ** -0.5


def gain(x):
    return 1.0 + GAIN_STD * x


def conv_rule(x):
    return CONV_STD * x


def hidden_scaled(x):
    """The TIED table ``[V, H]``: logits of about unit variance."""
    return x * x.shape[-1] ** -0.5


def centred(x):
    """A down projection ``[F, out]`` whose columns sum to zero over its
    rows: what ``fan_in`` draws less each column's mean."""
    w = fan_in(x)
    return w - w.mean(axis=0, keepdims=True)


def uniform01(x):
    """A standard-normal sample through its distribution function."""
    return 0.5 * (1.0 + jax.lax.erf(x * 2.0 ** -0.5))


def a_log_rule(x):
    """``A_log[c, n] = log(n + 1)``, as published: the sample is not used."""
    n = jnp.arange(1, x.shape[-1] + 1, dtype=jnp.float32)
    return jnp.broadcast_to(jnp.log(n), x.shape)


def dt_bias_rule(x):
    lo, hi = jnp.log(DT_MIN), jnp.log(DT_MAX)
    dt = jnp.exp(lo + uniform01(x) * (hi - lo))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus(rule) == dt


def leaf_shapes(model: dict) -> dict:
    H, F = model["hidden_size"], model["intermediate_size"]
    Hq, Hkv, D = (model["num_attention_heads"], model["num_key_value_heads"],
                  head_dim(model))
    d = ssm_dims(model)
    di, ds, R, K = d["inner"], d["state"], d["rank"], d["kernel"]
    return {
        "norm": (H,), "post_norm": (H,),
        "w_gate": (H, F), "w_up": (H, F), "w_down": (F, H),
        "wq": (H, Hq * D), "wk": (H, Hkv * D), "wv": (H, Hkv * D),
        "wo": (Hq * D, H),
        "w_in": (H, 2 * di), "conv_w": (K, di), "conv_b": (di,),
        "w_x": (di, R + 2 * ds),
        "dt_norm": (R,), "b_norm": (ds,), "c_norm": (ds,),
        "w_dt": (R, di), "dt_bias": (di,), "A_log": (di, ds), "D": (di,),
        "w_out": (di, H),
    }


MLP = ("post_norm", "w_gate", "w_up", "w_down")
ORDER = {
    "mamba": ("norm", "w_in", "conv_w", "conv_b", "w_x", "dt_norm", "b_norm",
              "c_norm", "w_dt", "dt_bias", "A_log", "D", "w_out") + MLP,
    "attn": ("norm", "wq", "wk", "wv", "wo") + MLP,
}
MATMULS = ("w_gate", "w_up", "w_down", "wq", "wk", "wv", "wo", "w_in", "w_x",
           "w_dt", "w_out")


def layer_leaves(model: dict) -> dict:
    """``{kind: leaves}``, each kind's in the order they are drawn."""
    shapes = leaf_shapes(model)
    rules = {
        "norm": gain, "post_norm": gain, "dt_norm": gain, "b_norm": gain,
        "c_norm": gain, "D": gain, "conv_w": conv_rule, "conv_b": conv_rule,
        "A_log": a_log_rule, "dt_bias": dt_bias_rule,
        # silu has a positive mean: no constant vector into the residual
        "w_out": centred, "w_down": centred,
    }
    return {
        kind: tuple(
            Leaf(name, shapes[name], rules.get(name, fan_in),
                 matmul=name in MATMULS)
            for name in ORDER[kind]
        )
        for kind in dict.fromkeys(layer_kinds(model))
    }


def tables(model: dict) -> tuple:
    """No ``lm_head``: the head is the embedding table."""
    V, H = model["vocab_size"], model["hidden_size"]
    return (
        Leaf("embed", (V, H), hidden_scaled, vocab_axis=0),
        Leaf("final_norm", (H,), gain),
    )


# ---------------------------------------------------------------- reference

# Read on the chip, PR 45 (PERF.md sections 2 and 6 have the runs): whole runs
# of jamba2_3b.agent, 8 finished requests of a run scored over their 512
# output positions each (4,096 positions a run). ``DELTA_MEAN`` lies between
# the two readings it must lie between. The LARGEST this program gives (bf16
# activations and weights as stated, a float32 state): 0.0038-0.0061 over
# 16 seeds, the served token the reference's argmax at 87-89% (a
# vocabulary of 65,536 at unit variance: the top two logits lie ~0.2 apart
# and bf16 activations flip near-ties). The SMALLEST the nearest precision
# below the one the configuration states gives, through the harness
# (benchmark/tests/calibrate_jamba.py): INT8 WEIGHTS under the bf16 label
# 0.0668 (12 times a sound run; argmax 63%) — the axis that would PAY, a step
# is bound by the weights it reads. Dropped terms: the ``dt`` norm 1.53, the
# ``B`` norm 1.90, the ``C`` norm 1.80, the ``D`` skip 4.21. So 0.012: twice
# the largest sound reading, a fifth of the smallest control that the
# margins can see. **What they cannot see: a bf16 STATE** reads 0.0061 /
# 0.0063 / 0.0062 at three seeds, 1.13-1.16 times its seed's sound run
# (0.0053 / 0.0055 / 0.0054) and inside the band the seeds span: with ``A = -(n + 1)`` and ``dt`` in
# [0.001, 0.1] a state value remembers ~1 / (dt (n + 1)) positions, a dozen
# at the median, and its rounding adds a few tenths of a percent to a ``y``
# that the next matmul rounds to bf16 anyway; the tier-1 logits test
# (tests/test_jamba.py, float32 on both sides) is where a narrower state
# fails (PERF.md section 7). ``DELTA_MAX`` guards against gross errors only,
# as in the other blocks: a sound run's worst position reads 0.16-0.29, int8
# weights' 0.75, the dropped terms' 5.4-7.9.
DELTA_MEAN = 0.012
DELTA_MAX = 2.0

#: positions of position-wise work (and query rows of scores) held at a time
Q_BLOCK = 512
#: sequences longer than this are padded to whole multiples of it
S_PAD = 1024


def layer_static(model: dict) -> dict:
    """Per kind: the keywords of ``layer_forward`` the published keys fix."""
    d = ssm_dims(model)
    eps = float(model.get("rms_norm_eps", 1e-6))
    return {
        "mamba": dict(eps=eps, inner=d["inner"], state=d["state"],
                      rank=d["rank"]),
        "attn": dict(
            eps=eps, heads=int(model["num_attention_heads"]),
            kv_heads=int(model["num_key_value_heads"]),
            head_dim=head_dim(model),
        ),
    }


def head_static(model: dict) -> dict:
    return dict(eps=float(model.get("rms_norm_eps", 1e-6)))


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
    """The recurrence position by position from a zero state: ``x``, ``dt [S,
    d_inner]``, ``A [d_inner, state]``, ``Bm``, ``Cm [S, state]``, ``D
    [d_inner]`` → ``y [S, d_inner]``. One position a step of a ``lax.scan``.
    ``state_round``: the state as a narrower type would hold it (a wrong
    model of the tests and the calibration)."""
    def step(s, t):
        xt, dtt, bt, ct = t
        s = jnp.exp(dtt[:, None] * A) * s + (dtt * xt)[:, None] * bt[None, :]
        if state_round is not None:
            s = s.astype(state_round).astype(jnp.float32)
        return s, s @ ct

    _, y = jax.lax.scan(step, jnp.zeros(A.shape, jnp.float32), (x, dt, Bm, Cm))
    return y + D[None, :] * x


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
        "kind", "eps", "inner", "state", "rank", "heads", "kv_heads",
        "head_dim", "state_round", "use_conv_bias", "use_skip", "use_dt_norm",
        "use_b_norm", "use_c_norm", "use_dt_bias", "use_gate",
    ),
)
def _layer_forward(h, p, *, kind, eps, inner=0, state=0, rank=0, heads=0,
                   kv_heads=0, head_dim=0, state_round=None,
                   use_conv_bias=True, use_skip=True, use_dt_norm=True,
                   use_b_norm=True, use_c_norm=True, use_dt_bias=True,
                   use_gate=True):
    """One layer of ``kind`` over a whole sequence h: [S, H], float32: its
    mixer, then the MLP. ``state_round`` and the ``use_*=False`` keywords (a
    dropped conv bias, ``D`` skip, ``δ`` / ``B`` / ``C`` norm, ``dt`` bias,
    gate) are the tests' wrong models."""
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
            h = by_rows(lambda hb, ob: hb + ob @ p["wo"], h, o.reshape(S, -1))
        else:  # a Mamba-1 mixer
            di, ds, R = inner, state, rank
            xz = by_rows(lambda hb: rms_norm(hb, p["norm"], eps) @ p["w_in"], h)
            x, z = xz[:, :di], xz[:, di:]
            bias = p["conv_b"] if use_conv_bias else jnp.zeros_like(p["conv_b"])
            x = jax.nn.silu(causal_conv(x, p["conv_w"], bias))

            def selection(xb):
                dbc = xb @ p["w_x"]
                delta, Bm, Cm = dbc[:, :R], dbc[:, R:R + ds], dbc[:, R + ds:]
                if use_dt_norm:
                    delta = rms_norm(delta, p["dt_norm"], eps)
                if use_b_norm:
                    Bm = rms_norm(Bm, p["b_norm"], eps)
                if use_c_norm:
                    Cm = rms_norm(Cm, p["c_norm"], eps)
                dt = delta @ p["w_dt"]
                if use_dt_bias:
                    dt = dt + p["dt_bias"]
                return jnp.concatenate(
                    [jax.nn.softplus(dt), Bm, Cm], axis=-1)

            sel = by_rows(selection, x)
            D = p["D"] if use_skip else jnp.zeros_like(p["D"])
            y = recurrence(
                x, sel[:, :di], -jnp.exp(p["A_log"]), sel[:, di:di + ds],
                sel[:, di + ds:], D, state_round,
            )
            if use_gate:
                y = y * jax.nn.silu(z)
            h = by_rows(lambda hb, yb: hb + yb @ p["w_out"], h, y)

        def mlp(hb):
            x = rms_norm(hb, p["post_norm"], eps)
            return hb + (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p[
                "w_down"]

        return by_rows(mlp, h)


def embed(tables: dict, ids, *, eps=None):
    return tables["embed"][ids].astype(jnp.float32)


def logits(h, tables: dict, *, eps):
    """Against the embedding table: the head is tied."""
    gain = tables["final_norm"].astype(jnp.float32)
    table = tables["embed"].astype(jnp.float32)
    return by_rows(lambda hb: rms_norm(hb, gain, eps) @ table.T, h)


# -------------------------------------------------------------------- bytes


def _size(shape: tuple) -> int:
    return functools.reduce(lambda a, c: a * c, shape, 1)


def _matmul_bytes(shape: tuple, weight_dtype: str) -> int:
    """A matmul leaf and, under int8, its one bf16 scale per output channel."""
    b = _size(shape) * roofline.MATMUL_BYTES[weight_dtype]
    return b + (shape[1] * 2 if weight_dtype == "int8" else 0)


def layer_fixed_bytes(model: dict, kind: str, weight_dtype: str) -> int:
    """What a layer of ``kind`` reads whatever the rows: its matmuls in
    ``weight_dtype``, every other leaf in bf16 (``A_log`` the largest:
    [5120, 16])."""
    sh = leaf_shapes(model)
    return sum(
        _matmul_bytes(sh[n], weight_dtype) if n in MATMULS else 2 * _size(sh[n])
        for n in ORDER[kind]
    )


def layer_params(model: dict, kind: str) -> int:
    sh = leaf_shapes(model)
    return sum(_size(sh[n]) for n in ORDER[kind])


def total_params(model: dict) -> int:
    """Parameters of the whole model; the tied table counted once."""
    layers = kind_layers(model)
    return (
        sum(n * layer_params(model, kind) for kind, n in layers.items())
        + model["vocab_size"] * model["hidden_size"] + model["hidden_size"]
    )


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
    "Bytes"). ``stages`` must be 1. Without records ONE live row."""
    if stages != 1:
        raise ValueError("jamba bytes are counted for one stage")
    layers = kind_layers(model)
    rows = live_rows(rec) if rec is not None else None
    return (
        layers["mamba"] * (
            layer_fixed_bytes(model, "mamba", weight_dtype)
            + (1.0 if rows is None else rows)
            * state_bytes_per_row_layer(model)
        )
        + layers["attn"] * (
            layer_fixed_bytes(model, "attn", weight_dtype)
            + live_tokens * arena_bytes_per_token_layer(model, kv_bytes)
        )
        + roofline.head_bytes(dims(model))  # the tied table, as the head
    )


def scan_flops(model: dict, positions: int) -> float:
    """Floating-point operations of ONE mixer layer's scan in time over
    ``positions`` positions of one row, per channel and state value: the
    decay's product and exponential, the update (two products and a sum) and
    the read-out (a product and a sum): 7, an exponential counted as one."""
    d = ssm_dims(model)
    return float(positions * d["inner"] * d["state"] * 7)


def scan_bytes(model: dict, positions: int, act_bytes: int = 4) -> float:
    """Bytes ONE mixer layer's scan must move for ``positions`` positions of
    one row in ONE pass: ``x``, ``dt``, ``z`` in and ``y`` out over the
    channels, ``B`` and ``C`` over the state values (float32 as the program
    holds them), and the row's state read and written once. Nothing of shape
    ``[positions, d_inner, state]`` is among them."""
    d = ssm_dims(model)
    per_pos = 4 * d["inner"] + 2 * d["state"]
    return float(positions * per_pos * act_bytes
                 + 2 * 4 * d["inner"] * d["state"])
