"""``solar_open2`` on the CPU at tiny widths (``tiny_solar_open2``: two periods
of four layers, ``GKKKGKKK`` — gated GQA without positions, then three KDA
mixers — every MLP 8 sigmoid-routed experts beside a shared one; 4 heads of a
16 x 16 state): the program's LOGITS over the whole forward against the plain
float32 reference of ``benchmark/blocks/solar_open2.py`` (the recurrence
position by position); the controls that must FAIL that tolerance; the
chunkwise WY form against the time scan of the step across one, two and more
chunks, from a carried state, at gates from −1e-3 to −60 a step and ``β`` near
0 and near 2; pads and dead rows leaving the state and the conv's tail bit for
bit; the decode step over a slot's live rows in XLA and on the interpreted
kernel; the eight shares of the experts adding up to the uncut reference's
layer; what the configuration reads and refuses, by name. The engine and the
server: ``tests/test_solar_open2_serve.py``."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import solar_open2 as so
from llm_sharding_tpu.models.config import (
    SOLAR_KEYS_NOT_READ, ModelConfig, tiny_solar_open2, tiny_solar_open2_keys,
)
from llm_sharding_tpu.models.stack import zero_recurrent
from llm_sharding_tpu.ops import kda

KEYS = tiny_solar_open2_keys()
CFG = tiny_solar_open2()
# float32 on both sides, matmuls at ``highest``: the two differ by the order
# of their sums only (the chunkwise form against the position-by-position
# scan; ~2e-5 read here over logits of ~3); the other models' 3e-4. A bf16
# state, a bf16 router, a dropped correction and a ``β`` not doubled each read
# far over it (``test_a_wrong_model_fails_the_tolerance``)
TOL = 3e-4
# the chunkwise form against the time scan, relative to the largest value:
# both are float32 sums of the same products in another order — 1e-6 read over
# chunks of up to 192 positions at ``β`` in (0, 1), 8e-6 with ``β`` near 2
# (``I − β k kᵀ`` then reflects: rounding is carried, not damped) — and 1e-4
# leaves room for the chip's own sums without passing a dropped term
CHUNK_TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    p = so.init_params(CFG, jax.random.key(3), jnp.float32)
    k = jax.random.key(4)
    for kind, stack in p["layers"].items():  # gains off one
        for i, name in enumerate(sorted(stack)):
            if name.endswith("norm"):
                stack[name] = stack[name] + 0.2 * jax.random.normal(
                    jax.random.fold_in(k, i), stack[name].shape)
    return p


def reference_logits(params, ids, keys=KEYS, **overrides):
    """The benchmark's plain reference over one sequence."""
    from benchmark import blocks, reference, weights

    block = blocks.load("solar_open2")
    kinds = blocks.kinds(block, keys)
    tables = {k: params[k] for k in ("embed", "final_norm", "lm_head")}
    hidden = reference.hidden_states(
        block, keys, lambda l: weights.take_layer(params["layers"], kinds, l),
        tables, [ids], **overrides,
    )[0][:len(ids)]
    return np.asarray(block.logits(hidden, tables, **block.head_static(keys)))


def system_logits(params, ids, cfg=CFG, backend="xla"):
    with jax.default_matmul_precision("highest"):
        logits, rec = so.forward_full(
            cfg, params, jnp.asarray([ids]), backend
        )
    return np.asarray(logits[0]), rec


# 70 positions: more than one chunk of 64, so the carried state is crossed
IDS = np.random.default_rng(5).integers(0, 250, size=70).astype(np.int32)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_logits_match_the_plain_reference(params, backend):
    got, rec = system_logits(params, IDS, backend=backend)
    want = reference_logits(params, IDS)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL
    assert rec["kda"].shape == (6, 1, 4, 16, 16)
    assert rec["conv"].shape == (6, 1, 3, 192)


@pytest.mark.parametrize("wrong", [
    {"state_round": (8, 7)},
    {"router_dtype": jnp.bfloat16},
    {"use_delta": False},
    {"beta_scale": 1.0},
    {"head_decay": True},
    {"head_norm": False},
    {"use_dt_bias": False},
    {"use_gate": False},
    {"use_l2": False},
    {"use_bias": False},
    {"use_shared": False},
], ids=lambda w: next(iter(w)))
def test_a_wrong_model_fails_the_tolerance(params, wrong):
    """A bf16 state, a bf16 router, a dropped correction (``u = v``), a ``β``
    not doubled, a decay a head, a norm over all channels and the rest each
    read far over the tolerance."""
    got, _ = system_logits(params, IDS)
    gap = np.abs(got - reference_logits(params, IDS, **wrong)).max()
    # (``not <=``: a delta rule over keys that are not normalised diverges,
    # and a reference that reads NaN is no match either)
    assert not gap <= 4 * TOL


@pytest.mark.parametrize("kind,i", [("gqa", 1), ("kda", 2)])
def test_one_layer_against_its_reference(params, kind, i):
    """ONE layer of each kind — the mixer, then the expert MLP — against the
    block's reference of that layer; a dropped output gate is another model."""
    from benchmark import blocks
    from llm_sharding_tpu.models.deepseek_v3 import mlp_sub_block
    from llm_sharding_tpu.ops.attention import cached_attention

    block = blocks.load("solar_open2")
    p = jax.tree.map(lambda a: a[i], params["layers"][kind])
    h = jax.random.normal(jax.random.key(8), (1, 40, 64))
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        if kind == "gqa":
            assert p["w_gate"].shape == (64, 4 * 16) and "rope" not in p
            got, _ = so.gqa_block(CFG, p, h, lambda q, k, v: (
                cached_attention(q, k, v, pos, pos, 16 ** -0.5), None))
        else:
            zero = zero_recurrent(CFG, 1, 1)
            got, _, _ = so.kda_block(
                CFG, p, h, zero["kda"][0], zero["conv"][0],
                jnp.ones((1, 40), bool))
        got, _ = mlp_sub_block(CFG, p, got, None, "xla")
    static = dict(block.layer_static(KEYS)[kind], kind=kind)
    want = block.layer_forward(h[0], p, **static)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-4
    wrong = block.layer_forward(h[0], p, **dict(static, use_gate=False))
    assert np.abs(np.asarray(got[0]) - np.asarray(wrong)).max() > 1e-2


def test_attention_carries_no_position(params):
    """NoPE: with the causal mask the only order there is, the LAST position's
    output is the same whatever order the earlier tokens came in."""
    from llm_sharding_tpu.ops.attention import cached_attention

    p = jax.tree.map(lambda a: a[0], params["layers"]["gqa"])
    h = jax.random.normal(jax.random.key(9), (1, 12, 64))
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    attend = lambda q, k, v: (
        cached_attention(q, k, v, pos, pos, 16 ** -0.5), None)
    perm = jnp.concatenate([jnp.arange(11)[::-1], jnp.array([11])])
    a, _ = so.gqa_block(CFG, p, h, attend)
    b, _ = so.gqa_block(CFG, p, h[:, perm], attend)
    assert np.abs(np.asarray(a[0, -1] - b[0, -1])).max() < 1e-5


# ---- the recurrence: the chunkwise form, the time scan, the step ----------

def _operands(seed, B, S, nh=4, dk=16, dv=16, gate=None, beta=(0.0, 2.0)):
    """``gate``: every step's ``g`` (a float), else ``g = −softplus`` spread
    over four decades; ``beta`` uniform in the given range."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    g = -jnp.exp(3.0 * jax.random.normal(ks[3], (B, S, nh, dk)) - 3.0)
    return dict(
        q=unit(jax.random.normal(ks[0], (B, S, nh, dk))) * dk ** -0.5,
        k=unit(jax.random.normal(ks[1], (B, S, nh, dk))),
        v=jax.random.normal(ks[2], (B, S, nh, dv)),
        g=g if gate is None else jnp.full_like(g, gate),
        beta=jax.random.uniform(
            ks[4], (B, S, nh), minval=beta[0], maxval=beta[1]),
        s0=jax.random.normal(ks[5], (B, nh, dk, dv)),
    )


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


ORDER = ("q", "k", "v", "g", "beta")


@pytest.mark.parametrize("chunks", [
    (1,), (64,), (100,), (64, 64), (16, 64, 5, 192),
], ids=str)
def test_the_chunk_form_is_the_time_scan_of_the_step(chunks):
    """Lengths that are and are not multiples of 64, from a NON-ZERO carried
    state: the stored state is the carry from chunk to chunk."""
    S = sum(chunks)
    m = _operands(len(chunks) + S, 2, S)
    want_o, want_s = kda.kda_scan(m["s0"], *(m[n] for n in ORDER))
    s, os_, at = m["s0"], [], 0
    for c in chunks:
        o, s = jax.jit(kda.kda_chunk)(
            s, *(m[n][:, at:at + c] for n in ORDER))
        os_.append(o)
        at += c
    assert _rel(jnp.concatenate(os_, 1), want_o) < CHUNK_TOL
    assert _rel(s, want_s) < CHUNK_TOL


@pytest.mark.parametrize("beta", [(0.0, 0.02), (1.98, 2.0)], ids=str)
@pytest.mark.parametrize("gate", [-1e-3, -0.3, -5.0, -60.0])
def test_no_gate_and_no_write_strength_breaks_the_chunk_form(gate, beta):
    """The gate has NO lower bound: ``g = −60`` at every one of 130 positions
    is −3,840 a chunk, which no ``exp(−G)`` could hold — every exponent the
    form takes is <= 0, and an underflow is the true value. ``β`` near 2
    reflects the state along the key (an eigenvalue near −1) and near 0
    writes nothing."""
    m = _operands(11, 2, 130, gate=gate, beta=beta)
    want_o, want_s = kda.kda_scan(m["s0"], *(m[n] for n in ORDER))
    o, s = jax.jit(kda.kda_chunk)(m["s0"], *(m[n] for n in ORDER))
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))
    assert _rel(o, want_o) < CHUNK_TOL and _rel(s, want_s) < CHUNK_TOL


def test_every_exponent_of_the_chunk_form_is_at_most_zero(monkeypatch):
    """Each ``exp`` of ``kda_chunk`` (all of them outside its chunk-to-chunk
    scan) is fed a value that was clamped at 0 or is a difference the
    cumulative gate makes <= 0 — checked on the values, with the gate at −60."""
    m = _operands(12, 1, 64, gate=-60.0)
    seen, real_exp = [], jnp.exp

    def spy(x):
        seen.append(float(jnp.max(x)))
        return real_exp(x)

    monkeypatch.setattr(kda.jnp, "exp", spy)
    kda.kda_chunk(m["s0"], *(m[n] for n in ORDER))
    assert len(seen) >= 6 and max(seen) <= 0.0


def test_the_step_is_the_equations():
    """``kda_step`` against the four lines written out with numpy, a head
    at a time."""
    m = _operands(2, 1, 1)
    o, s = kda.kda_step(m["s0"], *(m[n][:, 0] for n in ORDER))
    for h in range(4):
        S0 = np.asarray(m["s0"][0, h], np.float64)
        q, k, v, g = (np.asarray(m[n][0, 0, h], np.float64)
                      for n in ("q", "k", "v", "g"))
        beta = float(m["beta"][0, 0, h])
        Sd = np.exp(g)[:, None] * S0
        u = v - Sd.T @ k
        S1 = Sd + beta * np.outer(k, u)
        assert np.abs(np.asarray(s[0, h]) - S1).max() < 1e-5
        assert np.abs(np.asarray(o[0, h]) - S1.T @ q).max() < 1e-5


@pytest.mark.parametrize("n_real", [0, 1, 63, 70])
def test_a_right_padded_chunk_leaves_the_state_of_its_last_real_token(
        params, n_real):
    """Through the mixer itself (``kda_block``): the state AND the conv's
    tail after a chunk of 80 whose first ``n_real`` positions are real are
    those after ``n_real`` single steps — bit for bit the old ones at 0."""
    p = jax.tree.map(lambda a: a[1], params["layers"]["kda"])
    k = jax.random.split(jax.random.key(n_real), 3)
    h = jax.random.normal(k[0], (2, 80, CFG.hidden_size))
    s0 = jax.random.normal(k[1], (2, 4, 16, 16))
    c0 = jax.random.normal(k[2], (2, 3, 192))
    live = jnp.broadcast_to(jnp.arange(80)[None] < n_real, (2, 80))
    _, s, c = so.kda_block(CFG, p, h, s0, c0, live)
    want_s, want_c = s0, c0
    one = jnp.ones((2, 1), bool)
    for t in range(n_real):
        _, want_s, want_c = so.kda_block(
            CFG, p, h[:, t:t + 1], want_s, want_c, one)
    if n_real == 0:
        assert bool(jnp.all(s == s0)) and bool(jnp.all(c == c0))
    assert _rel(s, want_s) < CHUNK_TOL
    assert np.abs(np.asarray(c - want_c)).max() < 1e-6


def test_a_dead_row_keeps_its_state_bit_for_bit(params):
    """Two rows, one with no real position: its state and tail come back
    as they went in while the other's advance — in a chunk and in a step."""
    p = jax.tree.map(lambda a: a[0], params["layers"]["kda"])
    k = jax.random.split(jax.random.key(21), 3)
    s0 = jax.random.normal(k[1], (2, 4, 16, 16))
    c0 = jax.random.normal(k[2], (2, 3, 192))
    for S in (64, 1):
        h = jax.random.normal(k[0], (2, S, CFG.hidden_size))
        live = jnp.stack([jnp.ones((S,), bool), jnp.zeros((S,), bool)])
        _, s, c = so.kda_block(CFG, p, h, s0, c0, live)
        assert bool(jnp.all(s[1] == s0[1])) and bool(jnp.all(c[1] == c0[1]))
        assert not bool(jnp.all(s[0] == s0[0]))


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("shape", [(8, 128, 128), (4, 16, 16)], ids=str)
def test_the_decode_step_advances_the_live_rows_where_the_state_lies(
        backend, shape):
    """``kda_step_rows`` over a slot's rows 2..5 of layer 1 of a carried
    array, two of four live: the live rows' state and read-out are
    ``kda_step``'s, a dead row's read-out is zero and its state — like every
    row outside the slot and every other layer — bit for bit what it was.
    At 8 heads of 128 x 128 the interpreted path is the KERNEL's; the tiny
    shape is one Mosaic cannot tile, which runs the loop."""
    nh, dk, dv = shape
    m = _operands(31, 4, 1, nh, dk, dv)
    s_all = jax.random.normal(jax.random.key(32), (3, 8, nh, dk, dv))
    s_all = s_all.at[1, 2:6].set(m["s0"])
    order, n_live = jnp.array([2, 0, 1, 3]), jnp.int32(2)
    args = tuple(m[n][:, 0] for n in ORDER)
    want_o, want_s = kda.kda_step(m["s0"], *args)
    o, got = kda.kda_step_rows(
        s_all, (jnp.int32(1), jnp.int32(2)), order, n_live, *args,
        backend=backend,
    )
    for b, live in enumerate((True, False, True, False)):
        if live:
            assert np.abs(np.asarray(got[1, 2 + b] - want_s[b])).max() < 1e-5
            assert np.abs(np.asarray(o[b] - want_o[b])).max() < 1e-5
        else:
            assert bool(jnp.all(got[1, 2 + b] == s_all[1, 2 + b]))
            assert bool(jnp.all(o[b] == 0))
    keep = jnp.ones((3, 8), bool).at[1, 2].set(False).at[1, 4].set(False)
    assert bool(jnp.all(jnp.where(
        keep[:, :, None, None, None], got == s_all, True)))
    # no live row at all: nothing moves
    o, got = kda.kda_step_rows(
        s_all, (jnp.int32(1), jnp.int32(2)), order, jnp.int32(0), *args,
        backend=backend,
    )
    assert bool(jnp.all(got == s_all)) and bool(jnp.all(o == 0))


def test_which_shapes_the_kernel_takes():
    from llm_sharding_tpu.ops import ssm

    assert kda.kernel_eligible(64, 128, 128) and kda.head_tile(64, 128, 128) == 16
    assert kda.kernel_eligible(8, 128, 128) and kda.head_tile(8, 128, 128) == 8
    assert not kda.kernel_eligible(4, 16, 16)
    assert not kda.kernel_eligible(4, 128, 128)  # heads not whole sublane tiles
    # the ONE question a server asks, whatever the family
    assert ssm.rows_backend("xla", CFG) == "xla"
    assert ssm.rows_backend("interpret", CFG) == "interpret"
    assert ssm.scan_path("interpret", CFG) == "block"


def test_the_conv_takes_no_bias_without_building_one():
    """``conv_step`` / ``conv_chunk`` with ``b=None`` (KDA's) are the biased
    ones at a zero bias, and the Mamba families' calls are unchanged."""
    from llm_sharding_tpu.ops import ssm

    k = jax.random.split(jax.random.key(50), 3)
    tail = jax.random.normal(k[0], (2, 3, 24))
    x = jax.random.normal(k[1], (2, 10, 24))
    w = jax.random.normal(k[2], (4, 24))
    zero = jnp.zeros((24,))
    n = jnp.array([10, 4], jnp.int32)
    for got, want in (
        (ssm.conv_step(tail, x[:, 0], w), ssm.conv_step(tail, x[:, 0], w, zero)),
        (ssm.conv_chunk(tail, x, n, w), ssm.conv_chunk(tail, x, n, w, zero)),
    ):
        assert all(bool(jnp.all(a == b)) for a, b in zip(got, want))


# ---- the share of the experts ----------------------------------------------

@pytest.mark.parametrize("kind", ["kda", "gqa"])
def test_the_eight_shares_add_up_to_the_uncut_references_layer(params, kind):
    """At the tiny size, 8 experts held ONE a rank over 8 ranks: with the
    mixer, the shared expert and the residual counted ONCE, the ranks' routed
    parts add up to the plain reference's layer that holds all 8 — router,
    bias and normalisation over ALL the experts whatever a rank holds."""
    from benchmark import blocks
    from llm_sharding_tpu.models.deepseek_v3 import mlp_sub_block
    from llm_sharding_tpu.ops.attention import cached_attention

    block = blocks.load("solar_open2")
    p = jax.tree.map(lambda a: a[0], params["layers"][kind])
    h = jax.random.normal(jax.random.key(40), (1, 9, CFG.hidden_size))
    pos = jnp.arange(9, dtype=jnp.int32)[None]
    F = CFG.moe_intermediate_size
    with jax.default_matmul_precision("highest"):
        if kind == "gqa":
            mixed, _ = so.gqa_block(CFG, p, h, lambda q, k, v: (
                cached_attention(q, k, v, pos, pos, 16 ** -0.5), None))
        else:
            zero = zero_recurrent(CFG, 1, 1)
            mixed, _, _ = so.kda_block(
                CFG, p, h, zero["kda"][0], zero["conv"][0],
                jnp.ones((1, 9), bool))
        no_routed = dict(p, we_down=jnp.zeros_like(p["we_down"]))
        base, _ = mlp_sub_block(CFG, no_routed, mixed, None, "xla")
        parts = []
        for rank in range(8):
            cfg = tiny_solar_open2(
                n_routed_experts=1, n_routed_experts_total=8, ep_rank=rank)
            assert cfg.held_experts_ == (rank, 1) and cfg.num_experts == 8
            cols = slice(rank * F, (rank + 1) * F)
            share = dict(
                p, we_gate=p["we_gate"][:, cols], we_up=p["we_up"][:, cols],
                we_down=p["we_down"][cols],
            )
            got, stats = mlp_sub_block(cfg, share, mixed, None, "xla")
            parts.append(got - base)
            # the counter is over ALL the experts of the layer, on every rank
            assert int(stats.expert_tokens.sum()) == 9 * CFG.num_experts_per_tok
            assert stats.expert_tokens.shape == (8,)
    want = block.layer_forward(
        h[0], p, **dict(block.layer_static(KEYS)[kind], kind=kind))
    assert np.abs(np.asarray(sum(parts))).max() > 0.05
    assert np.abs(np.asarray((base + sum(parts))[0] - want)).max() < 1e-4


# ---- the configuration ------------------------------------------------------

def test_what_the_configuration_reads():
    assert CFG.model_type == "solar_open2" and CFG.recurrent
    assert not CFG.latent_kv and not CFG.windowed and not CFG.sparse_attn
    assert CFG.layer_pattern == "GKKKGKKK"
    assert CFG.layer_kinds == ("gqa", "kda", "kda", "kda") * 2
    assert CFG.recurrent_shapes == {"kda": (4, 16, 16), "conv": (3, 192)}
    assert CFG.recurrent_row_bytes == 4 * (4 * 16 * 16 + 3 * 192)
    assert CFG.conv_dim == 192 and CFG.kda_beta_scale == 2.0 and CFG.attn_gate
    assert (CFG.cache_heads, CFG.cache_k_dim, CFG.cache_v_dim) == (2, 16, 16)
    assert (CFG.n_group, CFG.topk_group, CFG.norm_topk_prob) == (1, 1, True)
    assert ModelConfig.from_json(CFG.to_json()) == CFG
    assert tiny_solar_open2(kda_allow_neg_eigval=False).kda_beta_scale == 1.0
    # the published widths: the third recurrent shape, 4,489,216 B a row and
    # layer, 4 KB a token and attention layer
    big = tiny_solar_open2(
        hidden_size=4096, num_attention_heads=64, num_key_value_heads=8,
        head_dim=128, num_hidden_layers=12, gqa_layers=[0, 4, 8],
        linear_attn_config=dict(
            short_conv_kernel_size=4, head_dim=128, num_heads=64,
            num_kv_heads=None),
    )
    assert big.recurrent_shapes == {"kda": (64, 128, 128), "conv": (3, 24576)}
    assert big.recurrent_row_bytes == 4_194_304 + 294_912
    assert big.layer_pattern == "GKKKGKKKGKKK"
    assert 2 * big.cache_heads * big.cache_k_dim * 2 == 4096
    # every key of the published config is read, refused or named as not read
    assert set(SOLAR_KEYS_NOT_READ) == {
        "gqa_interval", "rope_theta", "partial_rotary_factor",
        "intermediate_size"}


@pytest.mark.parametrize("key,value,match", [
    ("kda_use_full_proj", True, "kda_use_full_proj"),
    ("use_rope", True, "use_rope"),
    ("first_k_dense_replace", 1, "first_k_dense_replace"),
    ("linear_attn_config", dict(
        short_conv_kernel_size=4, head_dim=16, num_heads=4, num_kv_heads=2),
     "num_kv_heads"),
    ("linear_attn_config", dict(head_dim=16), "lacks 'num_heads'"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("scoring_func", "softmax", "scoring_func"),
    ("n_group", 2, "n_group"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("gqa_layers", [0, 9], "gqa_layers"),
    ("gqa_layers", list(range(8)), "no KDA layer"),
    ("gqa_layers", None, "lacks 'gqa_layers'"),
    ("n_routed_experts_total", 12, "must divide"),
    ("ep_rank", 1, "rank"),
])
def test_what_is_not_done_is_refused_by_name(key, value, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(tiny_solar_open2_keys(**{key: value}))


def test_the_paths_that_are_not_built_are_refused_by_name(params):
    with pytest.raises(NotImplementedError, match="dense KV cache"):
        so.forward_layers(CFG, params["layers"], None, None, None)
    with pytest.raises(NotImplementedError, match="tensor / context"):
        so.forward_layers_paged(
            CFG, params["layers"], None, None, None, None, None, None, None,
            tp_axis="tp")
    with pytest.raises(NotImplementedError, match="quantized"):
        so.forward_layers_paged(
            CFG, params["layers"], jnp.zeros((1, 1, 64)), (None, {}), None,
            None, None, None, None, k_scale=jnp.zeros(()))
    from llm_sharding_tpu.utils import convert, shard_store

    with pytest.raises(NotImplementedError, match="solar_open2"):
        convert.params_from_hf(CFG, {})
    with pytest.raises(NotImplementedError, match="solar_open2"):
        shard_store.save_shards_streaming(CFG, {}, "/nonexistent/never/made")


def test_the_new_leaves_quantise_and_the_small_ones_stay(params):
    from llm_sharding_tpu.ops.quant import QTensor, quantize_layer_params

    q = quantize_layer_params(params["layers"])
    for name in ("wq", "wk", "wv", "wo", "w_a_down", "w_a_up", "w_g_down",
                 "w_g_up", "we_gate", "ws_down"):
        assert isinstance(q["kda"][name], QTensor), name
    for name in ("w_beta", "conv_w", "A_log", "dt_bias", "gate_norm",
                 "router", "router_bias", "input_norm", "post_norm"):
        assert not isinstance(q["kda"][name], QTensor), name
    for name in ("wq", "wk", "wv", "wo", "w_gate", "we_up"):
        assert isinstance(q["gqa"][name], QTensor), name
    # ... and the quantised model's logits stand near the float one's
    got, _ = system_logits(dict(params, layers=q), IDS)
    want, _ = system_logits(params, IDS)
    # (tiny widths: a fan-in of 64 rounds coarsely and a flipped expert moves
    # a token by a whole term)
    assert np.isfinite(got).all() and 1e-4 < np.abs(got - want).mean() < 0.5


def test_zero_recurrent_lays_the_third_shape_out():
    rec = zero_recurrent(CFG, 4, 3)
    assert rec["kda"].shape == (4, 3, 4, 16, 16)
    assert rec["conv"].shape == (4, 3, 3, 192)
    assert rec["kda"].dtype == jnp.float32
