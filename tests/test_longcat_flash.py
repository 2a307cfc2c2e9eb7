"""``longcat_flash`` on the CPU at tiny widths (3 double layers: 6 latent
attentions of 4 heads, both latent scales on; 8 real + 4 zero-compute experts,
3 a token, scale 6): the program's LOGITS — the monolith's full forward, then
prefill (one-shot and in chunks) and decode through the TWO-slots-a-layer
latent arena with the kernels interpreted — against the plain float32
reference of ``benchmark/blocks/longcat_flash.py``, with each wrong model the
tolerance must fail; the absorbed attention with its two scales against the
decompressed equations; the biased softmax router; experts without weights in
both regimes of ``ops/moe.expert_mlp``; the shares adding up to the uncut
layer; what is refused, by name. The engine and the server:
``tests/test_longcat_flash_serve.py``."""

import dataclasses
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import deepseek_v3 as deepseek, longcat_flash as lf
from llm_sharding_tpu.models.cache import POS_SENTINEL, init_cache
from llm_sharding_tpu.models.config import (
    ModelConfig, tiny_deepseek_v3_keys, tiny_longcat_flash,
    tiny_longcat_flash_keys,
)
from llm_sharding_tpu.ops import moe
from llm_sharding_tpu.ops.flash_attention import attention_step
from llm_sharding_tpu.ops.rope import rope_cos_sin

KEYS = tiny_longcat_flash_keys()
CFG = tiny_longcat_flash()
BS, T = 8, 8  # arena block size, table width: a window of 64 columns
# float32 against float32 at ``highest``: what is left is the order of the
# sums (absorbed against decompressed, tiles against a loop): 1e-5 read; a
# bf16 router alone reads 3e-2, a dropped zero-compute term 3.7
TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return lf.init_params(CFG, jax.random.key(3), jnp.float32)


def block():
    from benchmark import blocks

    return blocks.load("longcat_flash")


def reference_logits(params, ids, keys=KEYS, **overrides):
    """The benchmark's plain reference over one sequence."""
    from benchmark import reference, weights

    tables = {k: params[k] for k in ("embed", "final_norm", "lm_head")}
    hidden = reference.hidden_states(
        block(), keys, lambda l: weights.take_layer(params["layers"], None, l),
        tables, [ids], **overrides,
    )[0][:len(ids)]
    return np.asarray(
        block().logits(hidden, tables, **block().head_static(keys)))


def paged_logits(cfg, params, ids, chunks, backend, round_to=None):
    """Prefill ``ids`` up to ``chunks[-1]`` in chunks that end at ``chunks``,
    then decode the rest token by token, all through
    ``forward_layers_paged`` over an arena of TWO slots a layer."""
    L = cfg.num_hidden_layers
    dtype = jnp.float32
    p = params if round_to is None else jax.tree.map(
        lambda a: a.astype(round_to).astype(a.dtype), params)
    k = jnp.zeros((2 * L, T + 1, 1, BS, cfg.cache_k_dim), dtype)
    v = jnp.zeros((2 * L, T + 1, 1, BS, 0), dtype)
    table = jnp.arange(1, T + 1, dtype=jnp.int32)[None]  # one row
    kv_pos = jnp.full((1, T * BS), POS_SENTINEL, jnp.int32)
    outs = []

    @functools.partial(jax.jit, static_argnames=("prefill",))
    def step(k, v, kv_pos, tokens, pos, prefill):
        with jax.default_matmul_precision("highest"):
            h = lf.embed(p, tokens)
            h, k, v, _, _, stats = lf.forward_layers_paged(
                cfg, p["layers"], h, k, v, table, pos, kv_pos, pos,
                backend=backend, prefill=prefill,
            )
            return lf.final_logits(cfg, p, h)[0], k, v, stats

    def run(tokens, cols, prefill):
        nonlocal k, v, kv_pos
        pos = jnp.asarray(cols, jnp.int32)[None]
        kv_pos = kv_pos.at[0, pos[0]].set(pos[0])
        logits, k, v, stats = step(
            k, v, kv_pos, jnp.asarray(tokens, jnp.int32)[None], pos, prefill)
        if round_to is not None:
            k = k.astype(round_to).astype(dtype)
        outs.append(np.asarray(logits, np.float32))
        return stats

    start = 0
    for end in chunks:
        run(ids[start:end], range(start, end), True)
        start = end
    for t in range(start, len(ids)):
        stats = run(ids[t:t + 1], [t], False)
    return np.concatenate(outs), stats, k


IDS = (np.arange(30) * 37 + 11) % 250


@pytest.mark.parametrize("backend", ["interpret", "xla"])
@pytest.mark.parametrize("chunks", [(16,), (8, 16)],
                         ids=["one_shot", "chunked"])
def test_paged_logits_match_the_plain_reference(params, backend, chunks):
    """Prefill — one shot, and two chunks of whole blocks — then decode
    through the arena, against the reference's ONE full forward."""
    want = reference_logits(params, IDS)
    got, stats, k = paged_logits(CFG, params, IDS, chunks, backend)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    # fourteen... here six slots: every one written, each its own values
    assert k.shape[0] == 2 * CFG.num_hidden_layers
    written = np.asarray(jnp.abs(k[:, 1:]).sum((1, 2, 3, 4)))
    assert (written > 0).all() and len(set(written.round(3))) == len(written)
    # the counters: E + Z wide, every pick of the last step's one row
    E, Z, kk = CFG.num_experts, CFG.zero_experts, CFG.num_experts_per_tok
    assert stats.expert_tokens.shape == (3, E + Z)
    assert [int(n) for n in stats.expert_tokens.sum(1)] == [kk] * 3
    real = np.asarray(stats.expert_tokens[:, :E])
    np.testing.assert_array_equal(
        np.asarray(stats.experts_read), (real > 0).sum(1))


def test_a_lower_precision_and_each_wrong_model_fail_the_tolerance(params):
    want = reference_logits(params, IDS)
    low, _, _ = paged_logits(CFG, params, IDS, (16,), "interpret", jnp.bfloat16)
    assert np.abs(low - want).max() > 10 * TOL
    for wrong in (
        dict(router_dtype=jnp.bfloat16),  # a bf16 router flips near ties
        dict(use_zero=False),  # the zero-compute term dropped
        dict(moe_late=True),  # the experts fed the SECOND norm: no shortcut
        dict(s_q=1.0, s_kv=1.0),  # the two latent scales ignored
        dict(renorm=True),  # the kept weights renormalised
        dict(use_bias=False),  # the correction bias dropped
    ):
        off = reference_logits(params, IDS, **wrong)
        assert np.abs(off - want).max() > 10 * TOL, wrong


def test_the_monolith_matches_the_reference(params):
    want = reference_logits(params, IDS)
    cache = init_cache(CFG, 1, 32, dtype=jnp.float32)
    # two cache layer slots a layer, one latent entry each, no values
    assert cache.k.shape == (6, 1, 32, 1, 128) and cache.v.shape[-1] == 0
    with jax.default_matmul_precision("highest"):
        logits, new = lf.forward(
            CFG, params, jnp.asarray(IDS[None]), cache, jnp.arange(30)[None])
        full = lf.forward_full(CFG, params, jnp.asarray(IDS[None]))
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(full[0]), want, atol=TOL, rtol=TOL)
    assert int(new.length) == 30


def test_a_masked_layer_changes_nothing_and_routes_nowhere(params):
    h = lf.embed(params, jnp.asarray(IDS[None]))
    cache = init_cache(CFG, 1, 32, dtype=jnp.float32)
    pos = jnp.arange(30)[None]
    mask = jnp.asarray([True, False, True])
    out, new, stats = lf.forward_layers(
        CFG, params["layers"], h, cache, pos, layer_mask=mask)
    cut = jax.tree.map(lambda a: a[jnp.asarray([0, 2])], params["layers"])
    want, _, _ = lf.forward_layers(
        dataclasses.replace(CFG, num_hidden_layers=2), cut, h,
        init_cache(CFG, 1, 32, 2, dtype=jnp.float32), pos)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    assert not np.asarray(new.k[2:4]).any()  # the masked layer's two slots
    assert np.asarray(new.k[4:6]).any()
    assert int(stats.expert_tokens[1].sum()) == 0
    assert int(stats.experts_read[1]) == 0


def test_absorbed_attention_with_its_scales_equals_the_decompressed_equations(
        params):
    """``mla_attention`` (absorbed, the scales folded into the norms' gains)
    against the reference's decompressed half; the entry the cache is handed
    holds the SCALED latent and the UNscaled rotated ``k_pe``."""
    S = 19
    h = jax.random.normal(jax.random.key(9), (1, S, CFG.hidden_size))
    p = lf.sub_layer(jax.tree.map(lambda a: a[1], params["layers"]), 1)
    pos = jnp.arange(S)[None]
    cos, sin = rope_cos_sin(pos, CFG, dtype=jnp.float32)
    seen = {}

    def attend(q_full, entry):
        seen["entry"] = entry
        k_r = entry  # [1, S, 1, Dk]: the whole sequence, no cache
        return attention_step(
            q_full, k_r, k_r[..., :CFG.kv_lora_rank], pos, pos, 0,
            deepseek.softmax_scale(CFG),
        ), None

    st = block().layer_static(KEYS)
    attn = {k: st[k] for k in ("heads", "nope", "rope", "kv_lora", "eps",
                               "theta", "scale", "s_q", "s_kv")}
    names = ("input_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
             "w_uk", "w_uv", "wo")
    with jax.default_matmul_precision("highest"):
        got, _ = deepseek.mla_attention(
            CFG, p, h, cos, sin, attend, q_scale=CFG.mla_q_scale,
            kv_scale=CFG.mla_kv_scale)
        entry = np.asarray(seen["entry"][0, :, 0])
        want = block().attention_half(h[0], {n: p[n] for n in names}, **attn)
        plain, _ = deepseek.mla_attention(CFG, p, h, cos, sin, attend)
        unscaled = block().attention_half(
            h[0], {n: p[n] for n in names}, **dict(attn, s_q=1.0, s_kv=1.0))
    np.testing.assert_allclose(got[0], want, atol=1e-4, rtol=1e-4)
    # ... and at scales of 1 the same function is deepseek_v3's attention
    np.testing.assert_allclose(plain[0], unscaled, atol=1e-4, rtol=1e-4)
    assert np.abs(np.asarray(got[0] - plain[0])).max() > 0.05
    assert abs(st["s_q"] - (64 / 24) ** 0.5) < 1e-6
    assert abs(st["s_kv"] - 2 ** 0.5) < 1e-6
    # the entry: [s_kv · N(c) | RoPE(k_pe) | zeros]
    r, dr = CFG.kv_lora_rank, CFG.qk_rope_head_dim
    x = np.asarray(h[0]) / np.sqrt(
        (np.asarray(h[0]) ** 2).mean(-1, keepdims=True) + CFG.rms_norm_eps)
    kv_a = (x * np.asarray(p["input_norm"])) @ np.asarray(p["wkv_a"])
    c = kv_a[:, :r]
    c = c / np.sqrt((c ** 2).mean(-1, keepdims=True) + CFG.rms_norm_eps)
    np.testing.assert_allclose(
        entry[:, :r], c * np.asarray(p["kv_a_norm"]) * 2 ** 0.5,
        atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(  # position 0 is not rotated, and not scaled
        entry[0, r:r + dr], kv_a[0, r:r + dr], atol=1e-5, rtol=1e-5)
    assert not entry[:, r + dr:].any()


def test_a_bias_moves_the_choice_and_never_a_weight():
    rng = np.random.default_rng(1)
    N, H, E, K, scale = 40, 16, 24, 4, 6.0
    x = rng.normal(size=(N, H)).astype(np.float32)
    w = rng.normal(size=(H, E)).astype(np.float32)
    bias = (rng.normal(size=(E,)) * 0.05).astype(np.float32)
    plain_w, plain_ids = moe.route(jnp.asarray(x), jnp.asarray(w), K)
    got_w, got_ids = moe.route(
        jnp.asarray(x), jnp.asarray(w), K, bias=jnp.asarray(bias), scale=scale)
    moved = 0
    for n in range(N):
        z = x[n].astype(np.float64) @ w
        p = np.exp(z - z.max())
        p /= p.sum()
        ids = np.argsort(-(p + bias))[:K]
        assert sorted(ids) == sorted(np.asarray(got_ids[n]))
        order = np.argsort(np.asarray(got_ids[n]))
        np.testing.assert_allclose(  # the UNbiased p, not renormalised, x 6
            np.asarray(got_w[n])[order], p[np.sort(ids)] * scale, rtol=1e-5)
        moved += sorted(ids) != sorted(np.asarray(plain_ids[n]))
    assert moved > 5  # the bias is not nothing
    # a zero bias is the plain router's choice and weights
    zero_w, zero_ids = moe.route(
        jnp.asarray(x), jnp.asarray(w), K, bias=jnp.zeros((E,)))
    np.testing.assert_array_equal(zero_ids, plain_ids)
    np.testing.assert_allclose(zero_w, plain_w, rtol=1e-6)


def _experts(seed, N, E, F, H, L=2):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (N, H), jnp.float32)
    wg = jax.random.normal(ks[1], (L, H, E * F), jnp.float32) * H ** -0.5
    wu = jax.random.normal(ks[2], (L, H, E * F), jnp.float32) * H ** -0.5
    wd = jax.random.normal(ks[3], (L, E * F, H), jnp.float32) * F ** -0.5
    return x, wg, wu, wd


@pytest.mark.parametrize("rows", [4, 100], ids=["decode", "grouped"])
@pytest.mark.parametrize("held", [None, (4, 4)], ids=["all_held", "a_share"])
def test_experts_without_weights_in_both_regimes(rows, held):
    """``expert_mlp(zero_from=)``, the kernel emulated against XLA and both
    against the dense form: a token whose picks are ALL zero-compute reads no
    expert and gets ``(Σ w) · x``; a dead row and a pad get zeros and are
    not counted; ``expert_tokens`` is ``[E + Z]`` wide."""
    E, Z, K, F, H = 8, 4, 3, 32, 64
    first, count = held or (0, E)
    x, wg, wu, wd = _experts(2, rows, count, F, H)
    rng = np.random.default_rng(rows)
    ids = np.stack([rng.permutation(E + Z)[:K] for _ in range(rows)])
    ids[0] = [E, E + 1, E + 3]  # every pick a zero-compute expert
    ids[1] = [first, E + 2, first + 1]  # held real experts and one zero
    weights = rng.uniform(0.1, 1.0, size=(rows, K)).astype(np.float32)
    live = np.ones((rows,), bool)
    live[2] = False  # a dead row / a pad position
    layer = jnp.asarray(1, jnp.int32)
    outs = {}
    for backend in ("interpret", "xla"):
        outs[backend] = moe.expert_mlp(
            x, jnp.asarray(weights), jnp.asarray(ids, jnp.int32), wg, wu, wd,
            E + Z, live=jnp.asarray(live), layer=layer, backend=backend,
            held=held, zero_from=E,
        )
    y, stats = outs["interpret"]
    np.testing.assert_allclose(y, outs["xla"][0], atol=1e-4, rtol=1e-4)
    # the dense form over the held real experts + the zero-compute term
    hp = jax.lax.Precision.HIGHEST
    want = np.zeros((rows, H), np.float32)
    for n in range(rows):
        if not live[n]:
            continue
        for kk in range(K):
            e = ids[n, kk]
            if e >= E:
                want[n] += weights[n, kk] * np.asarray(x[n])
            elif first <= e < first + count:
                c = slice((e - first) * F, (e - first + 1) * F)
                g = jnp.dot(x[n], wg[1][:, c], precision=hp)
                u = jnp.dot(x[n], wu[1][:, c], precision=hp)
                want[n] += weights[n, kk] * np.asarray(
                    jnp.dot(jax.nn.silu(g) * u, wd[1][c], precision=hp))
    np.testing.assert_allclose(y, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(
        y[0], weights[0].sum() * np.asarray(x[0]), rtol=1e-5)
    assert not np.asarray(y[2]).any()
    counts = np.zeros((E + Z,), int)
    for n in range(rows):
        if live[n]:
            np.add.at(counts, ids[n], 1)
    np.testing.assert_array_equal(stats.expert_tokens, counts)
    assert counts[E:].sum() > 0
    assert int(stats.experts_read) == int(
        (counts[first:first + count] > 0).sum())
    # one row whose picks are all zero-compute: no tile, nothing read
    y1, st1 = moe.expert_mlp(
        x[:1], jnp.asarray(weights[:1]), jnp.asarray(ids[:1], jnp.int32),
        wg, wu, wd, E + Z, layer=layer, backend="interpret", held=held,
        zero_from=E,
    )
    assert int(st1.experts_read) == 0
    np.testing.assert_allclose(
        y1[0], weights[0].sum() * np.asarray(x[0]), rtol=1e-5)


def one_layer(**kw):
    return (tiny_longcat_flash(num_layers=1, **kw),
            tiny_longcat_flash_keys(num_layers=1, **kw))


@pytest.mark.parametrize("positions", [12, 48])  # both regimes of the tiles
def test_the_shares_and_the_zero_term_once_add_up_to_the_uncut_layer(positions):
    """The tie between a share and the model: the shares' ROUTED parts plus
    the zero-compute term ONCE are the uncut reference's ``m``, and the layer
    built from that sum is the uncut layer — in the program and in the
    reference."""
    full_cfg, full_keys = one_layer()
    full = lf.init_params(full_cfg, jax.random.key(8), jnp.float32)
    F, E, n = full_cfg.moe_intermediate_size, 8, 4
    ids = (np.arange(positions) * 13 + 5) % 250
    pos = jnp.arange(positions)[None]

    def program(cfg, p):
        h = lf.embed(p, jnp.asarray(ids[None]))
        cache = init_cache(cfg, 1, positions, dtype=jnp.float32)
        with jax.default_matmul_precision("highest"):
            out, _, stats = lf.forward_layers(cfg, p["layers"], h, cache, pos)
        return np.asarray(out[0]), stats

    def share(rank):
        lo, hi = rank * (E // n) * F, (rank + 1) * (E // n) * F
        lay = dict(full["layers"])
        lay["we_gate"] = lay["we_gate"][..., lo:hi]
        lay["we_up"] = lay["we_up"][..., lo:hi]
        lay["we_down"] = lay["we_down"][:, lo:hi]
        kw = dict(n_routed_experts=E // n, n_routed_experts_total=E,
                  ep_rank=rank)
        return one_layer(**kw), dict(full, layers=lay)

    whole, stats = program(full_cfg, full)
    # what every chip computes alike: the layer with the expert path off,
    # and the zero-compute term alone (a share that holds NO real expert's
    # pairs: every real weight set to zero)
    alike, _ = program(
        dataclasses.replace(full_cfg, routed_scaling_factor=0.0), full)
    dead = jax.tree.map(jnp.zeros_like, {
        k: full["layers"][k] for k in ("we_gate", "we_up", "we_down")})
    zero_only, _ = program(full_cfg, dict(full, layers={**full["layers"], **dead}))
    zero_term = zero_only - alike
    assert np.abs(zero_term).max() > 0.05  # experts without weights add
    routed, held_pairs = [], 0
    for rank in range(n):
        (cfg, keys), p = share(rank)
        out, st = program(cfg, p)
        routed.append(out - alike - zero_term)
        # the counters: every pair routed, over all E + Z, on every share
        np.testing.assert_array_equal(st.expert_tokens, stats.expert_tokens)
        lo = rank * (E // n)
        held_pairs += int(st.expert_tokens[0, lo:lo + E // n].sum())
        # and the reference is given the same share
        ref = reference_logits(p, ids, keys)
        got = lf.final_logits(cfg, p, jnp.asarray(out))
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(
        alike + zero_term + sum(routed), whole, atol=1e-4, rtol=1e-4)
    zero_pairs = int(stats.expert_tokens[0, E:].sum())
    assert zero_pairs > 0
    assert held_pairs + zero_pairs == positions * full_cfg.num_experts_per_tok
    assert np.abs(sum(routed)).max() > 0.05  # the routed part is not nothing
    # the uncut REFERENCE is the uncut program
    np.testing.assert_allclose(
        lf.final_logits(full_cfg, full, jnp.asarray(whole)),
        reference_logits(full, ids, full_keys), atol=TOL, rtol=TOL)


def test_the_keys_are_the_familys_own_and_what_is_not_done_is_refused(params):
    from llm_sharding_tpu.runtime.engine import PipelineEngine
    from llm_sharding_tpu.utils import convert

    assert CFG.model_type == "longcat_flash" and CFG.layer_kinds == ()
    assert (CFG.num_hidden_layers, CFG.arena_slots) == (3, 2)
    assert (CFG.num_experts, CFG.zero_experts, CFG.router_experts) == (8, 4, 12)
    assert CFG.intermediate_size == 96 and CFG.moe_intermediate_size == 32
    assert abs(CFG.mla_q_scale - (64 / 24) ** 0.5) < 1e-12
    assert abs(CFG.mla_kv_scale - 2 ** 0.5) < 1e-12
    off = tiny_longcat_flash(mla_scale_q_lora=False, mla_scale_kv_lora=False)
    assert (off.mla_q_scale, off.mla_kv_scale) == (1.0, 1.0)
    assert ModelConfig.from_json(CFG.to_json()) == CFG
    # every other family: one slot a layer, no zero-compute experts
    other = ModelConfig.from_hf_config(tiny_deepseek_v3_keys())
    assert (other.arena_slots, other.zero_experts) == (1, 0)
    assert other.router_experts == other.num_experts
    for key, bad in (
        ("zero_expert_type", "copy"), ("attention_method", "MHA"),
        ("norm_topk_prob", True), ("router_bias", True),
        ("tie_word_embeddings", True), ("rope_interleave", False),
    ):
        with pytest.raises(ValueError, match=key):
            ModelConfig.from_hf_config(dict(KEYS, **{key: bad}))
    with pytest.raises(ValueError, match="q_lora_rank"):
        ModelConfig.from_hf_config(dict(KEYS, q_lora_rank=None))
    with pytest.raises(ValueError, match="rope_scaling"):
        ModelConfig.from_hf_config(dict(KEYS, rope_scaling={
            "rope_type": "yarn", "factor": 4.0}))
    with pytest.raises(ValueError, match="must divide"):
        ModelConfig.from_hf_config(dict(KEYS, n_routed_experts=3,
                                        n_routed_experts_total=8))
    with pytest.raises(ValueError, match="moe_topk"):
        ModelConfig.from_hf_config(dict(KEYS, moe_topk=13))
    # deepseek_v3's refusal of a softmax router points at this family
    with pytest.raises(ValueError, match="longcat_flash"):
        ModelConfig.from_hf_config(
            dict(tiny_deepseek_v3_keys(), scoring_func="softmax"))
    with pytest.raises(NotImplementedError, match="longcat_flash"):
        convert._refuse_unmapped(CFG)
    with pytest.raises(NotImplementedError, match="longcat_flash"):
        lf.forward_layers(CFG, params["layers"], None, None, None,
                          tp_axis="tensor")
    with pytest.raises(NotImplementedError, match="quantized"):
        lf.forward_layers_paged(
            CFG, params["layers"], None, None, None, None, None, None, None,
            k_scale=jnp.zeros(()))
    with pytest.raises(NotImplementedError, match="sparse experts"):
        PipelineEngine(CFG, params, num_stages=1, tensor_parallel=2,
                       devices=jax.devices()[:2])
