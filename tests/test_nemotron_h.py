"""``nemotron_h`` on the CPU at tiny widths (three Mamba-2 mixers — 8 heads of
16, a state of 16, 2 groups, blocks of 8 positions — two LatentMoE layers of
8 experts in a latent space of 32, one attention layer): the program's LOGITS
over the whole forward against the plain float32 reference of
``benchmark/blocks/nemotron_h.py`` (the SEQUENTIAL recurrence); the controls
that must FAIL that tolerance; the block form against the sequential
recurrence across one, two and more chunks; a right-padded chunk leaving the
state of its last real token; the shares of the experts adding up to the
uncut layer; what the configuration reads and refuses, by name. The engine
and the server: ``tests/test_nemotron_h_serve.py``."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import nemotron_h
from llm_sharding_tpu.models.config import (
    ModelConfig, nemotron3_super_120b_a12b, nemotron3_super_keys,
    tiny_nemotron_h, tiny_nemotron_h_keys,
)
from llm_sharding_tpu.ops import ssm

KEYS = tiny_nemotron_h_keys()
CFG = tiny_nemotron_h()
# float32 on both sides, matmuls at ``highest``: the two differ by the order
# of their sums only (2-3e-6 read here over logits of ~4); a bf16 state reads
# 2e-2, a bf16 router flips an expert (1e-1), a dropped conv bias 1
TOL = 5e-5


@pytest.fixture(scope="module")
def params():
    p = nemotron_h.init_params(CFG, jax.random.key(3), jnp.float32)
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

    block = blocks.load("nemotron_h")
    kinds = blocks.kinds(block, keys)
    tables = {k: params[k] for k in ("embed", "final_norm", "lm_head")}

    def forward(h, p, **kw):  # an override goes to the kind that takes it
        return block.layer_forward(h, p, **kw)

    hidden = reference.hidden_states(
        block, keys, lambda l: weights.take_layer(params["layers"], kinds, l),
        tables, [ids], **overrides,
    )[0][:len(ids)]
    return np.asarray(block.logits(hidden, tables, **block.head_static(keys)))


def system_logits(params, ids, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        logits, rec = nemotron_h.forward_full(cfg, params, jnp.asarray([ids]))
    return np.asarray(logits[0]), rec


IDS = np.random.default_rng(5).integers(0, 250, size=43).astype(np.int32)


def test_logits_match_the_plain_reference(params):
    got, _ = system_logits(params, IDS)
    want = reference_logits(params, IDS)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("wrong", [
    {"state_round": jnp.bfloat16},
    {"router_dtype": jnp.bfloat16},
    {"use_conv_bias": False},
    {"use_skip": False},
    {"gate_first": False},
    {"use_bias": False},
])
def test_a_wrong_model_fails_the_tolerance(params, wrong):
    got, _ = system_logits(params, IDS)
    assert np.abs(got - reference_logits(params, IDS, **wrong)).max() > 4 * TOL


def _mixer_inputs(seed, B, S):
    k = jax.random.split(jax.random.key(seed), 8)
    nh, hd, g, ds = 8, 4, 2, 16
    return dict(
        x=jax.random.normal(k[0], (B, S, nh, hd)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, S, nh)) - 1.0),
        A=-jnp.exp(jax.random.normal(k[2], (nh,))),
        Bm=jax.random.normal(k[3], (B, S, g, ds)),
        Cm=jax.random.normal(k[4], (B, S, g, ds)),
        D=jax.random.normal(k[5], (nh,)),
        s0=jax.random.normal(k[6], (B, nh, hd, ds)),
    )


def _sequential(m, dt=None, upto=None):
    dt = m["dt"] if dt is None else dt
    s, ys = m["s0"], []
    for t in range(m["x"].shape[1] if upto is None else upto):
        y, s = ssm.ssm_step(s, m["x"][:, t], dt[:, t], m["A"], m["Bm"][:, t],
                            m["Cm"][:, t], m["D"])
        ys.append(y)
    return jnp.stack(ys, 1), s


@pytest.mark.parametrize("chunks", [(8,), (16,), (16, 16), (16, 16, 16, 5)])
def test_the_block_form_is_the_sequential_recurrence(chunks):
    """Across one block, one chunk, two chunks and more (the last ragged):
    the stored state is the carry from chunk to chunk."""
    S = sum(chunks)
    m = _mixer_inputs(len(chunks), 2, S)
    want_y, want_s = _sequential(m)
    s, ys, at = m["s0"], [], 0
    for n in chunks:
        sl = slice(at, at + n)
        y, s = ssm.ssm_chunk(s, m["x"][:, sl], m["dt"][:, sl], m["A"],
                             m["Bm"][:, sl], m["Cm"][:, sl], m["D"], block=8)
        ys.append(y)
        at += n
    assert np.abs(jnp.concatenate(ys, 1) - want_y).max() < 2e-5
    assert np.abs(s - want_s).max() < 2e-5


@pytest.mark.parametrize("n_real", [0, 1, 2, 7, 8, 13, 16])
def test_a_right_padded_chunk_leaves_the_state_of_its_last_real_token(
        params, n_real):
    """Through the mixer itself (``mamba_block``): the state AND the conv's
    tail after a chunk of 16 whose first ``n_real`` positions are real are
    those after ``n_real`` single steps — exactly the old ones at 0."""
    p = jax.tree.map(lambda a: a[0], params["layers"]["mamba"])
    k = jax.random.split(jax.random.key(n_real), 3)
    B, S = 2, 16
    h = jax.random.normal(k[0], (B, S, CFG.hidden_size))
    zero = nemotron_h.zero_recurrent(CFG, 1, B)
    s0 = jax.random.normal(k[1], zero["ssm"][0].shape)
    c0 = jax.random.normal(k[2], zero["conv"][0].shape)
    live = jnp.broadcast_to(jnp.arange(S)[None] < n_real, (B, S))
    with jax.default_matmul_precision("highest"):
        _, s_pad, c_pad = nemotron_h.mamba_block(CFG, p, h, s0, c0, live)
        s, c = s0, c0
        one = jnp.ones((B, 1), bool)
        for t in range(n_real):
            _, s, c = nemotron_h.mamba_block(CFG, p, h[:, t:t + 1], s, c, one)
    if n_real == 0:
        assert np.array_equal(s_pad, s0) and np.array_equal(c_pad, c0)
    assert np.abs(s_pad - s).max() < 1e-5
    assert np.abs(c_pad - c).max() < 1e-6


def test_a_dead_row_of_a_decode_step_keeps_its_state(params):
    p = jax.tree.map(lambda a: a[0], params["layers"]["mamba"])
    k = jax.random.split(jax.random.key(0), 3)
    h = jax.random.normal(k[0], (2, 1, CFG.hidden_size))
    zero = nemotron_h.zero_recurrent(CFG, 1, 2)
    s0 = jax.random.normal(k[1], zero["ssm"][0].shape)
    c0 = jax.random.normal(k[2], zero["conv"][0].shape)
    live = jnp.asarray([[True], [False]])
    _, s, c = nemotron_h.mamba_block(CFG, p, h, s0, c0, live)
    assert np.array_equal(s[1], s0[1]) and np.array_equal(c[1], c0[1])
    assert np.abs(s[0] - s0[0]).max() > 1e-3
    assert np.abs(c[0, -1] - c0[0, -1]).max() > 1e-3


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("alive", [
    (True, False, True), (False, False, False), (True, True, True),
    (False, True, False)])
def test_a_decode_step_moves_the_live_rows_state_where_it_lies(
        params, alive, backend):
    """``mamba_decode_rows`` (what the paged decode step runs: the LIVE rows
    of the slot advanced inside the carried array — a loop over them in XLA,
    ONE kernel call whose grid is the live rows under ``interpret``) against
    ``mamba_block`` over the slot's rows: the same hidden state and the same
    new state for a live row, bit for bit the old state for a dead one — and
    for every row and layer of the carried array outside the slot. With no
    live row the kernel's grid is one step that puts a block back as it
    came."""
    p = jax.tree.map(lambda a: a[1], params["layers"]["mamba"])
    k = jax.random.split(jax.random.key(11), 3)
    B, rows, row0, layer = len(alive), 6, 2, 1
    h = jax.random.normal(k[0], (B, 1, CFG.hidden_size))
    zero = nemotron_h.zero_recurrent(CFG, 3, rows)
    s_all = jax.random.normal(k[1], zero["ssm"].shape)
    c0 = jax.random.normal(k[2], zero["conv"][0, :B].shape)
    live = jnp.asarray(alive)[:, None]
    slot = s_all[layer, row0:row0 + B]
    h_want, s_want, c_want = nemotron_h.mamba_block(CFG, p, h, slot, c0, live)
    h_got, s_got, c_got = jax.jit(
        lambda s_all, at: nemotron_h.mamba_decode_rows(
            CFG, p, h, s_all, at, c0, live, backend)
    )(s_all, (jnp.int32(layer), jnp.int32(row0)))
    assert np.abs(c_got - c_want).max() < 1e-6
    for b, on in enumerate(alive):
        got = s_got[layer, row0 + b]
        if on:
            assert np.abs(got - s_want[b]).max() < 1e-6
            assert np.abs(h_got[b] - h_want[b]).max() < 1e-5
        else:  # its read-out is ZERO: the mixer adds nothing to the row
            assert np.array_equal(got, slot[b])
            assert np.array_equal(h_got[b], h[b])
    outside = np.ones(s_all.shape[:2], bool)
    outside[layer, row0:row0 + B] = False
    assert np.array_equal(np.asarray(s_got)[outside], np.asarray(s_all)[outside])


@pytest.mark.parametrize("alive", [(False, True), (True, True)])
def test_the_state_kernel_at_the_published_head_shape(alive):
    """``ssm_step_rows`` at the shape the chip runs (128 heads of 64, a state
    of 128, 8 groups: blocks of two groups' 32 heads, 1 MiB, four a row),
    the kernel emulated against the loop over ``ssm_step``: ``y`` (zero for a
    row that is not live), the live rows' new state, and every other row of
    the carried array bit for bit."""
    nh, hd, ds, g, B = 128, 64, 128, 8, len(alive)
    assert ssm.head_tile(nh, g, hd, ds) == 32
    assert ssm.kernel_eligible(nh, g, hd, ds)
    k = jax.random.split(jax.random.key(44), 8)
    s_all = jax.random.normal(k[0], (2, B + 1, nh, hd, ds))
    x = jax.random.normal(k[1], (B, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(k[2], (B, nh)))
    A = -jnp.exp(jax.random.normal(k[3], (nh,)))
    Bm, Cm = (jax.random.normal(k[i], (B, g, ds)) for i in (4, 5))
    D = jax.random.normal(k[6], (nh,))
    on = jnp.asarray(alive)
    at = (jnp.int32(1), jnp.int32(1))

    def run(backend):
        return jax.jit(lambda s_all: ssm.ssm_step_rows(
            s_all, at, jnp.argsort(~on), jnp.sum(on.astype(jnp.int32)), x,
            jnp.where(on[:, None], dt, 0.0), A, Bm, Cm, D, backend=backend,
        ))(s_all)

    (y_want, s_want), (y_got, s_got) = run("xla"), run("interpret")
    assert np.abs(y_got - y_want).max() < 2e-4 * np.abs(y_want).max()
    assert np.abs(s_got - s_want).max() < 1e-5
    dead = np.ones(s_all.shape[:2], bool)
    dead[1, 1:] = np.logical_not(alive)
    assert np.array_equal(np.asarray(s_got)[dead], np.asarray(s_all)[dead])
    assert np.array_equal(y_got[~np.asarray(alive)], y_want[~np.asarray(alive)])
    assert np.abs(s_got[1, 1:][np.asarray(alive)] - s_all[1, 1:][np.asarray(alive)]).max() > 1e-3


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(params):
    """Four chips each hold 2 of the 8 experts: their routed terms add up to
    the uncut layer's, the shared expert (which every chip computes) counted
    ONCE."""
    full = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    h = jax.random.normal(jax.random.key(9), (1, 12, CFG.hidden_size))
    F = CFG.moe_intermediate_size
    with jax.default_matmul_precision("highest"):
        want, stats = nemotron_h.moe_block(CFG, full, h, backend="xla")
        x = nemotron_h.rms_norm(h, full["norm"], CFG.rms_norm_eps)
        shared = nemotron_h.relu2_mlp(x, full["ws_up"], full["ws_down"])
        total = jnp.zeros_like(h)
        pairs = 0
        for rank in range(4):
            cfg = ModelConfig.from_hf_config(dict(
                KEYS, n_routed_experts=2, n_routed_experts_total=8,
                ep_rank=rank))
            sl = slice(rank * 2 * F, (rank + 1) * 2 * F)
            mine = dict(full, we_up=full["we_up"][:, sl],
                        we_down=full["we_down"][sl])
            got, st = nemotron_h.moe_block(cfg, mine, h, backend="xla")
            total = total + (got - h - shared)
            assert int(st.experts_read) <= 2
            pairs += int(st.expert_tokens[rank * 2:rank * 2 + 2].sum())
            assert np.array_equal(st.expert_tokens, stats.expert_tokens)
    assert np.abs(total + h + shared - want).max() < 2e-5
    assert pairs == 12 * CFG.num_experts_per_tok


def test_the_published_keys_are_read():
    cfg = nemotron3_super_120b_a12b()
    assert cfg.model_type == "nemotron_h" and cfg.recurrent
    assert len(cfg.layer_pattern) == 88
    kinds = cfg.layer_kinds
    assert (kinds.count("mamba"), kinds.count("moe"), kinds.count("attn")) == (
        40, 40, 8)
    assert kinds[:17] == tuple(
        {"M": "mamba", "E": "moe", "*": "attn"}[c] for c in "MEMEMEM*EMEMEMEM*")
    assert (cfg.ssm_inner, cfg.conv_dim, cfg.ssm_chunk) == (8192, 10240, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (512, 22)
    assert (cfg.moe_latent_size, cfg.moe_intermediate_size) == (1024, 2688)
    assert cfg.moe_shared_intermediate_size == 5376
    assert cfg.routed_scaling_factor == 5.0 and cfg.rms_norm_eps == 1e-5
    assert (cfg.cache_heads, cfg.cache_k_dim, cfg.cache_v_dim) == (2, 128, 128)
    # one request, one mixer layer: the float32 state and the conv's tail
    assert cfg.recurrent_row_bytes == 4 * (128 * 64 * 128 + 3 * 10240)
    # a chip's share, as the last two configurations have it
    cut = nemotron3_super_120b_a12b(
        num_hidden_layers=17, n_routed_experts=128,
        n_routed_experts_total=512, ep_rank=2)
    assert cut.held_experts_ == (256, 128) and cut.num_experts == 512
    assert cut.layer_pattern == "MEMEMEM*EMEMEMEM*"
    # kept and NOT read: another value changes nothing
    assert nemotron3_super_120b_a12b(
        rope_theta=1e6, partial_rotary_factor=0.5, use_mamba_kernels=False,
        moe_shared_expert_overlap=True, rescale_prenorm_residual=False,
        time_step_floor=1.0, num_logits_to_keep=7) == cfg
    assert not tiny_nemotron_h(hybrid_override_pattern="E*E*E*").recurrent


@pytest.mark.parametrize("key, value", [
    ("mlp_hidden_act", "silu"), ("mamba_hidden_act", "gelu"),
    ("attention_bias", True), ("mlp_bias", True), ("use_bias", True),
    ("mamba_proj_bias", True), ("use_conv_bias", False),
    ("norm_topk_prob", False), ("tie_word_embeddings", True),
    ("n_group", 2), ("topk_group", 2), ("n_shared_experts", 2),
    ("residual_in_fp32", True), ("sliding_window", 128),
    ("num_nextn_predict_layers", 1), ("time_step_limit", [0.0, 1.0]),
    ("expand", 3), ("n_groups", 3), ("norm_eps", 1e-6),
    ("hybrid_override_pattern", "MEM-EM"), ("hybrid_override_pattern", "MEM"),
    ("ep_rank", 1), ("n_routed_experts_total", 12),
])
def test_what_the_configuration_refuses_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        tiny_nemotron_h(**{key: value})


@pytest.mark.parametrize("key", [
    "hybrid_override_pattern", "mamba_num_heads", "ssm_state_size",
    "moe_latent_size", "moe_shared_expert_intermediate_size", "head_dim",
])
def test_a_missing_key_is_named(key):
    keys = tiny_nemotron_h_keys()
    del keys[key]
    with pytest.raises(ValueError, match=key):
        ModelConfig.from_hf_config(keys)


def test_layer_kinds_runs_and_state_slots(params):
    runs = nemotron_h.stage_runs(CFG, params["layers"])
    assert [(r.kind, r.stack_first, r.count, r.slot_first) for r in runs] == [
        ("mamba", 0, 1, 0), ("moe", 0, 1, 3), ("mamba", 1, 1, 1),
        ("attn", 0, 1, 5), ("moe", 1, 1, 4), ("mamba", 2, 1, 2),
    ]
    assert nemotron_h.kind_layer_counts(CFG, params["layers"], axis=0) == {
        "mamba": 3, "moe": 2, "attn": 1}
    # a stage that holds other kinds than the model's first layers
    bad = dict(params["layers"], moe=jax.tree.map(
        lambda a: a[:1], params["layers"]["moe"]))
    with pytest.raises(NotImplementedError, match="same sequence"):
        nemotron_h.stage_runs(CFG, bad)
    with pytest.raises(NotImplementedError, match="dense KV cache"):
        nemotron_h.forward_layers(CFG, params["layers"], None, None, None)


def test_the_references_padding_of_a_long_sequence_changes_no_position(
        params, monkeypatch):
    from benchmark import blocks

    block = blocks.load("nemotron_h")
    want = reference_logits(params, IDS)
    monkeypatch.setattr(block, "S_PAD", 16)
    monkeypatch.setattr(block, "Q_BLOCK", 8)
    assert np.abs(reference_logits(params, IDS) - want).max() < 1e-5


def test_the_published_preset_counts_120_billion_parameters():
    """The layers written down from the row's config add up to the published
    size: 40 x 2,873 M + 40 x 109.6 M + 8 x 35.7 M + 1.07 B."""
    from benchmark import blocks

    block = blocks.load("nemotron_h")
    keys = nemotron3_super_keys()
    shapes = block.leaf_shapes(keys)
    count = lambda names: sum(int(np.prod(shapes[n])) for n in names)
    per = {k: count(block.ORDER[k]) for k in block.ORDER}
    kinds = block.layer_kinds(keys)
    total = sum(per[k] for k in kinds) + 2 * 131072 * 4096 + 4096
    assert abs(total / 1e9 - 120.7) < 0.2
    assert abs(per["mamba"] / 1e6 - 109.6) < 0.1
    assert abs(per["attn"] / 1e6 - 35.7) < 0.1
    assert block.expert_bytes(keys, "int8") == 2 * 1024 * 2688 + 2 * 2688
