"""``mimo_v2`` on the CPU at tiny widths (a dense full-attention layer, two
expert window layers — window 8, a sink, 2 key/value heads — and an expert
full-attention layer with 1; keys of 24 with rotary on the first 8, values of
16): the program's LOGITS — prefill in chunks, then decode through BOTH paged
arenas with the window layers' blocks behind the window handed back, kernels
interpreted — against the plain float32 reference of
``benchmark/blocks/mimo_v2.py`` at contexts of many windows; the controls
that must FAIL that tolerance; the shares of the experts adding up to the
uncut layer; what the configuration refuses, by name. The engine and the
server: ``tests/test_mimo_v2_serve.py``."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import mimo_v2
from llm_sharding_tpu.models.cache import POS_SENTINEL, init_cache
from llm_sharding_tpu.models.config import (
    ModelConfig, tiny_mimo_v2, tiny_mimo_v2_keys,
)

KEYS = tiny_mimo_v2_keys()
CFG = tiny_mimo_v2()
BS, T = 4, 24  # arena block size, table width: 96 columns
TOL = 3e-4


@pytest.fixture(scope="module")
def params():
    p = mimo_v2.init_params(CFG, jax.random.key(3), jnp.float32)
    # gains off one, a sink that takes a real share of a window's mass
    k = jax.random.key(4)
    for kind, stack in p["layers"].items():
        for i, name in enumerate(sorted(stack)):
            if name.endswith("_norm"):
                stack[name] = stack[name] + 0.2 * jax.random.normal(
                    jax.random.fold_in(k, i), stack[name].shape)
        if "sink" in stack:
            stack["sink"] = stack["sink"] + 2.0
    return p


def reference_logits(params, ids, keys=KEYS, **overrides):
    """The benchmark's plain reference over one sequence."""
    from benchmark import blocks, reference, weights

    block = blocks.load("mimo_v2")
    kinds = blocks.kinds(block, keys)
    tables = {k: params[k] for k in ("embed", "final_norm", "lm_head")}
    hidden = reference.hidden_states(
        block, keys, lambda l: weights.take_layer(params["layers"], kinds, l),
        tables, [ids], **overrides,
    )[0][:len(ids)]
    return np.asarray(block.logits(hidden, tables, **block.head_static(keys)))


def paged_logits(cfg, params, ids, chunks, backend, round_to=None):
    """Prefill ``ids`` chunk by chunk (``chunks``: the chunk edges), then
    decode the rest token by token, through ``forward_layers_paged`` over the
    two arenas. Before every call the window table lets go of the blocks
    wholly behind the window, as the server does."""
    dtype = jnp.float32
    p = params if round_to is None else jax.tree.map(
        lambda a: a.astype(round_to).astype(a.dtype), params)
    n = {"full": 2, "swa": 2}
    arenas = {
        a: (jnp.zeros((n[a], T + 1, cfg.kv_heads_of(a), BS, cfg.cache_k_dim), dtype),
            jnp.zeros((n[a], T + 1, cfg.kv_heads_of(a), BS, cfg.cache_v_dim), dtype))
        for a in ("full", "swa")
    }
    table = np.arange(1, T + 1, dtype=np.int32)[None]  # one row
    kv_pos = jnp.full((1, T * BS), POS_SENTINEL, jnp.int32)
    outs, held = [], []

    @functools.partial(jax.jit, static_argnames=("prefill",))
    def step(k, v, tables, kv_pos, tokens, pos, prefill):
        with jax.default_matmul_precision("highest"):
            h = mimo_v2.embed(p, tokens)
            h, k, v, _, _, stats = mimo_v2.forward_layers_paged(
                cfg, p["layers"], h, k, v, tables, pos, kv_pos, pos,
                backend=backend, prefill=prefill,
            )
            return mimo_v2.final_logits(cfg, p, h)[0], k, v, stats

    def run(tokens, cols, prefill):
        nonlocal arenas, kv_pos
        pos = jnp.asarray(cols, jnp.int32)[None]
        kv_pos = kv_pos.at[0, pos[0]].set(pos[0])
        swa = table.copy()
        swa[0, : max(cols[0] - cfg.sliding_window + 1, 0) // BS] = 0
        held.append(int((swa[0, : -(-(cols[-1] + 1) // BS)] != 0).sum()))
        k = (arenas["full"][0], arenas["swa"][0])
        v = (arenas["full"][1], arenas["swa"][1])
        logits, k, v, stats = step(
            k, v, (jnp.asarray(table), jnp.asarray(swa)), kv_pos,
            jnp.asarray(tokens, jnp.int32)[None], pos, prefill)
        if round_to is not None:
            k = tuple(a.astype(round_to).astype(dtype) for a in k)
            v = tuple(a.astype(round_to).astype(dtype) for a in v)
        arenas = {"full": (k[0], v[0]), "swa": (k[1], v[1])}
        outs.append(np.asarray(logits, np.float32))
        return stats

    edges = [0, *chunks]
    for a, b in zip(edges[:-1], edges[1:]):
        run(ids[a:b], list(range(a, b)), True)
    for t in range(edges[-1], len(ids)):
        stats = run(ids[t:t + 1], [t], False)
    return np.concatenate(outs), stats, held


IDS = (np.arange(70) * 37 + 11) % 250  # nine windows of 8


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_paged_logits_match_the_plain_reference(params, backend):
    """Prefill in chunks of 16 (not whole blocks of the last: 16, 32, 45),
    then 25 decode steps, over contexts of up to nine windows and across
    block and chunk edges, both arenas, the window layers' old blocks gone —
    against the reference's full forward, tight enough that bf16 fails."""
    want = reference_logits(params, IDS)
    got, stats, held = paged_logits(CFG, params, IDS, [16, 32, 45], backend)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    # a decoding row's window layers hold the window's blocks and no more
    assert max(held[3:]) <= -(-CFG.sliding_window // BS) + 1
    # the layer slots, kind after kind: the dense layer routes nothing
    assert stats.experts_read.shape == (4,) and int(stats.experts_read[0]) == 0
    assert int(stats.expert_tokens[0].sum()) == 0
    assert int(stats.expert_tokens[1].sum()) == CFG.num_experts_per_tok
    if backend == "interpret":
        low, _, _ = paged_logits(
            CFG, params, IDS, [16, 32, 45], backend, jnp.bfloat16)
        assert np.abs(low - want).max() > 10 * TOL  # a lower precision fails


def test_the_monolith_matches_the_reference(params):
    want = reference_logits(params, IDS)
    cache = init_cache(CFG, 1, 80, dtype=jnp.float32)
    assert cache.k.shape[-2:] == (2, 128) and cache.v.shape[-1] == 16
    with jax.default_matmul_precision("highest"):
        logits, _ = mimo_v2.forward(
            CFG, params, jnp.asarray(IDS[None]), cache, jnp.arange(70)[None])
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("wrong", [
    {"window": 0},  # the window ignored: a window layer reads everything
    {"use_sink": False},  # the sink dropped
    {"theta": 1e7},  # the full layers' rope base used in window layers
    {"value_scale": 1.0},  # v unscaled
    {"router_dtype": jnp.bfloat16},  # a bf16 router
    {"use_bias": False},  # the router's correction bias dropped
    {"kv_round": jnp.float8_e4m3fn},  # keys and values as an fp8 cache holds them
])
def test_a_wrong_model_fails_the_tolerance(params, wrong):
    """The controls: each of these is a model the program could have been,
    and each reads far outside the tolerance the sound program meets."""
    want = reference_logits(params, IDS)
    off = reference_logits(params, IDS, **wrong)
    assert np.abs(off - want).max() > 10 * TOL


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(params):
    """4 ranks holding 2 of 8 experts each: the expert layers' outputs of
    the shares add up to the uncut layer's (no shared expert to count
    once), in the program and in the reference."""
    from benchmark import blocks

    block = blocks.load("mimo_v2")
    h = jax.random.normal(jax.random.key(9), (1, 12, CFG.hidden_size))
    pos = jnp.arange(12)[None]
    p = jax.tree.map(lambda a: a[0], params["layers"]["moe_full"])
    rope = mimo_v2._rope_tables(CFG, pos)["full"]

    def attend(q, k, v):
        return jnp.zeros((*q.shape[:3], CFG.v_head_dim), q.dtype), None

    def moe_out(cfg, p):
        with jax.default_matmul_precision("highest"):
            out, _, _ = mimo_v2.layer_block(cfg, p, "full", h, *rope, attend)
        return np.asarray(out - h)  # attention adds nothing here

    whole = moe_out(CFG, p)
    F = CFG.moe_intermediate_size
    parts = []
    for rank in range(4):
        cfg = tiny_mimo_v2(n_routed_experts=2, n_routed_experts_total=8,
                           ep_rank=rank)
        assert cfg.held_experts_ == (2 * rank, 2) and cfg.num_experts == 8
        sl = slice(2 * rank * F, 2 * (rank + 1) * F)
        share = dict(p, we_gate=p["we_gate"][:, sl], we_up=p["we_up"][:, sl],
                     we_down=p["we_down"][sl])
        parts.append(moe_out(cfg, share))
        # the reference's share of the same layer
        keys = tiny_mimo_v2_keys(n_routed_experts=2, n_routed_experts_total=8,
                                 ep_rank=rank)
        kw = dict(block.layer_static(keys)["moe_full"], kind="moe_full")
        ref = np.asarray(block.layer_forward(h[0], share, **kw))
        full_kw = dict(block.layer_static(KEYS)["moe_full"], kind="moe_full")
        if rank == 0:
            ref_whole = np.asarray(block.layer_forward(h[0], p, **full_kw))
            ref_parts = []
        ref_parts.append(ref)
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    # the reference: each share's layer output holds the SAME attention and
    # residual; the expert terms add up
    base = np.asarray(block.layer_forward(
        h[0], dict(p, we_down=jnp.zeros_like(p["we_down"])), **full_kw))
    np.testing.assert_allclose(
        sum(r - base for r in ref_parts), ref_whole - base, atol=2e-5)


def test_what_the_configuration_refuses_by_name():
    for wrong, word in [
        ({"n_group": 2}, "n_group"), ({"n_shared_experts": 1}, "n_shared"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
        ({"swa_head_dim": 32}, "swa_head_dim"),
        ({"n_routed_experts": 3, "n_routed_experts_total": 8}, "held"),
    ]:
        with pytest.raises(ValueError, match=word):
            tiny_mimo_v2(**wrong)
    with pytest.raises(ValueError, match="lacks 'hybrid_layer_pattern'"):
        ModelConfig.from_hf_config({
            k: v for k, v in KEYS.items() if k != "hybrid_layer_pattern"})
    # a qwen2 / gemma sliding-window checkpoint is still refused by its words
    from llm_sharding_tpu.models.config import tiny_qwen2

    with pytest.raises(ValueError, match="sliding-window attention is not"):
        tiny_qwen2(use_sliding_window=True)
    with pytest.raises(ValueError, match="gemma-2"):
        ModelConfig.from_hf_config(dict(
            model_type="gemma", vocab_size=256, hidden_size=64,
            intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=1, head_dim=16,
            sliding_window=4096))


def test_layer_kinds_runs_and_arena_slots():
    """The first twelve layers of the published pattern run as five runs in
    model order; a layer's arena slot is its order among its attention kind."""
    cfg = tiny_mimo_v2(
        num_hidden_layers=12,
        hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0],
        moe_layer_freq=[0] + [1] * 11,
    )
    assert cfg.layer_kinds[:6] == (
        "dense_full", "moe_swa", "moe_swa", "moe_swa", "moe_swa", "moe_full")
    layers = mimo_v2.init_params(cfg, jax.random.key(0), jnp.float32)["layers"]
    runs = mimo_v2.stage_runs(cfg, layers)
    assert [(r.kind, r.stack_first, r.count, r.attn, r.arena_first)
            for r in runs] == [
        ("dense_full", 0, 1, "full", 0), ("moe_swa", 0, 4, "swa", 0),
        ("moe_full", 0, 1, "full", 1), ("moe_swa", 4, 5, "swa", 4),
        ("moe_full", 1, 1, "full", 2),
    ]
    # slots kind after kind: dense_full 0, moe_swa 1-9, moe_full 10-11
    assert [r.slot_first for r in runs] == [0, 1, 10, 5, 11]


def test_the_references_banded_window_attention_is_the_plain_one():
    """The reference scores a window layer's block of queries against the
    band of keys it can reach; over 768 positions (three blocks) that equals
    the softmax over every key under the same mask, sink and all."""
    from benchmark import blocks

    block = blocks.load("mimo_v2")
    S, Hq, Hkv, D, Dv, W = 768, 4, 2, 8, 4, 8
    ks = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(ks[0], (S, Hq, D))
    k = jax.random.normal(ks[1], (S, Hkv, D))
    v = jax.random.normal(ks[2], (S, Hkv, Dv))
    sink = jax.random.normal(ks[3], (Hq,))
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    for window, sk in ((W, sink), (W, None), (0, sink)):
        got = np.asarray(block.attention(q, k, v, 0.3, window, sk))
        keep = (j <= i) & ((j > i - window) if window else True)
        s = np.einsum("shd,thd->hst", np.asarray(q),
                      np.repeat(np.asarray(k), Hq // Hkv, 1)) * 0.3
        s = np.where(keep[None], s, -np.inf)
        if sk is not None:
            s = np.concatenate(
                [s, np.broadcast_to(np.asarray(sk)[:, None, None], (Hq, S, 1))], -1)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = (p / p.sum(-1, keepdims=True))[..., :S]
        want = np.einsum("hst,thv->shv", p, np.repeat(np.asarray(v), Hq // Hkv, 1))
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_references_padding_of_a_long_sequence_changes_no_position(params):
    """``layer_forward`` pads a long sequence to whole ``S_PAD``s so that a
    cell's scored requests are one shape; under a causal mask the pad moves
    no real position (a window layer with its sink and its experts)."""
    from benchmark import blocks

    block = blocks.load("mimo_v2")
    S = block.S_PAD + 76  # padded to 2 x S_PAD; the plain call is not
    h = jax.random.normal(jax.random.key(5), (S, CFG.hidden_size))
    p = jax.tree.map(lambda a: a[1], params["layers"]["moe_swa"])
    kw = dict(block.layer_static(KEYS)["moe_swa"], kind="moe_swa")
    got = block.layer_forward(h, p, **kw)
    assert got.shape == h.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(block._layer_forward(h, p, **kw)),
        atol=2e-5)
    # the head, row block by row block, is the plain product
    tables = {k: params[k] for k in ("final_norm", "lm_head")}
    rows = h[: 2 * block.Q_BLOCK]
    with jax.default_matmul_precision("highest"):
        want = block.rms_norm(rows, tables["final_norm"], 1e-5) @ tables["lm_head"]
    np.testing.assert_allclose(
        np.asarray(block.logits(rows, tables, eps=1e-5)), np.asarray(want),
        atol=2e-5)
