"""``ouro`` (a looped stack: the same sandwich-norm layers run T times a token,
the final norm closing every pass, K/V of its own for every pass, an exit gate
over the passes) on the CPU at tiny widths: the program's LOGITS — the
monolith's full forward, prefill then decode through the dense cache, prefill
in chunks then decode through the paged arena of ``T · L`` slots — against the
equations of ISSUE 60 WRITTEN OUT BY HAND below (plain ``jnp``, float32, no
cache: a whole sequence at once), with each wrong model the tolerance must
fail; the gate's choice a position at three thresholds; one pass without the
output norms being the plain llama model; what the keys refuse; int8. The
engine and the server: ``tests/test_ouro_serve.py``."""

import dataclasses
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama, stack
from llm_sharding_tpu.models.cache import POS_SENTINEL, init_cache
from llm_sharding_tpu.models.config import (
    ModelConfig, tiny_ouro, tiny_ouro_keys,
)

CFG = tiny_ouro()  # 3 layers, T = 3, hidden 64, 4 heads of 16, vocabulary 256
BS, NB = 8, 8  # arena block size, table width: a window of 64 columns
# float32 against float32 at ``highest``: what is left is the order of the
# sums (a cache's attention against a whole sequence's, a scan against a
# loop). 3e-6 read over nine layer calls; the mildest wrong model below (one
# output norm dropped in one layer of three) reads 0.3
TOL = 2e-4
NORMS = ("input_norm", "attn_out_norm", "post_norm", "mlp_out_norm")
IDS = (np.arange(30) * 37 + 11) % 250


def seeded_params(cfg, seed=3):
    """``llama.init_params`` with every norm's gain drawn as 1 + 0.1 n: at a
    gain of exactly 1 an RMSNorm of an RMSNorm is the first one again, and a
    norm too many or too few would go unseen."""
    p = llama.init_params(cfg, jax.random.key(seed), jnp.float32)
    key = jax.random.key(seed + 100)
    lay = dict(p["layers"])
    for i, name in enumerate(n for n in NORMS if n in lay):
        lay[name] = 1.0 + 0.1 * jax.random.normal(
            jax.random.fold_in(key, i), lay[name].shape, jnp.float32)
    p["layers"] = lay
    p["final_norm"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.fold_in(key, 9), p["final_norm"].shape, jnp.float32)
    return p


@pytest.fixture(scope="module")
def params():
    return seeded_params(CFG)


# ------------------------------------------------ the equations, by hand

def _norm(x, g, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):  # [S, N, D] at positions 0..S-1, rotate-half
    S, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + rot * sin


def hand_layer(cfg, p, h, *, drop=None):
    """``a = Attn(N(h; g1a)); h += N(a; g1b); m = MLP(N(h; g2a)); h += N(m;
    g2b)`` over one sequence [S, H]; ``drop`` names an output norm to leave
    out (a wrong model)."""
    S, D, N = h.shape[0], cfg.head_dim_, cfg.num_attention_heads
    x = _norm(h, p["input_norm"])
    q = _rope((x @ p["wq"]).reshape(S, N, D), cfg.rope_theta)
    k = _rope((x @ p["wk"]).reshape(S, N, D), cfg.rope_theta)
    v = (x @ p["wv"]).reshape(S, N, D)
    s = jnp.einsum("snd,tnd->nst", q, k) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    a = jnp.einsum("nst,tnd->snd", jax.nn.softmax(s, -1), v)
    a = a.reshape(S, N * D) @ p["wo"]
    if "attn_out_norm" in p and drop != "attn_out_norm":
        a = _norm(a, p["attn_out_norm"])
    h = h + a
    x = _norm(h, p["post_norm"])
    m = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    if "mlp_out_norm" in p and drop != "mlp_out_norm":
        m = _norm(m, p["mlp_out_norm"])
    return h + m


def hand_model(cfg, params, ids, *, passes=None, close="every",
               head_norm=False, drop=None):
    """``(logits [S, V], exit pass [S], closed states [T, S, H])`` of one
    sequence. The wrong models: ``passes`` other than the configuration's,
    ``close="last"`` (no norm between passes), ``head_norm`` (the head norming
    once more), ``drop`` (an output norm of layer 0 left out)."""
    with jax.default_matmul_precision("highest"):
        T = cfg.passes if passes is None else passes
        L = cfg.num_hidden_layers
        h = params["embed"][jnp.asarray(ids)]
        closed = []
        for t in range(T):
            for l in range(L):
                p = jax.tree.map(lambda a: a[l], params["layers"])
                h = hand_layer(cfg, p, h, drop=drop if l == 0 else None)
            if close == "every" or t == T - 1:
                h = _norm(h, params["final_norm"])
            closed.append(h)
        s = jnp.stack(closed)  # [T, S, H]
        if "exit_gate" in params:
            g = jax.nn.sigmoid(s @ params["exit_gate"] + params["exit_bias"][0])
        else:  # one pass: nothing to choose among
            g = jnp.zeros(s.shape[:2])
        stay, cum = jnp.ones(len(ids)), jnp.zeros(len(ids))
        at = jnp.full((len(ids),), T - 1)
        found = jnp.zeros((len(ids),), bool)
        for t in range(T):
            p_t = stay if t == T - 1 else g[t] * stay
            cum, stay = cum + p_t, stay * (1.0 - g[t])
            hit = (cum >= cfg.exit_threshold) & ~found
            at, found = jnp.where(hit, t, at), found | hit
        chosen = jnp.take_along_axis(s, at[None, :, None], 0)[0]
        if head_norm:
            chosen = _norm(chosen, params["final_norm"])
        return chosen @ params["lm_head"], at, s


# --------------------------------------------------------------- the program

def full_forward(cfg, params, ids):
    ids = jnp.asarray(ids, jnp.int32)[None]
    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        cache = init_cache(cfg, 1, ids.shape[1], dtype=jnp.float32)
        logits, _ = llama.forward(cfg, params, ids, cache, pos)
    return np.asarray(logits[0])


def dense_logits(cfg, params, ids, split):
    """Prefill ``ids[:split]`` then decode the rest through the dense cache."""
    step = jax.jit(functools.partial(llama.forward, cfg))
    cache = init_cache(cfg, 1, len(ids), dtype=jnp.float32)
    outs = []
    with jax.default_matmul_precision("highest"):
        for lo, hi in [(0, split)] + [(t, t + 1) for t in range(split, len(ids))]:
            logits, cache = step(
                params, jnp.asarray(ids[lo:hi], jnp.int32)[None], cache,
                jnp.arange(lo, hi, dtype=jnp.int32)[None])
            outs.append(np.asarray(logits[0]))
    return np.concatenate(outs), cache


def paged_logits(cfg, params, ids, chunks, backend, slot_offset=True):
    """Prefill ``ids`` in chunks that end at ``chunks``, then decode the rest
    token by token, through ``forward_layers_paged`` over an arena of ``T ·
    L`` slots. ``slot_offset=False``: every pass reads and writes pass 0's
    slots (a wrong cache)."""
    slots = cfg.arena_slots * cfg.num_hidden_layers
    D = cfg.head_dim_
    k = jnp.zeros((slots, NB + 1, cfg.num_key_value_heads, BS, D), jnp.float32)
    v = jnp.zeros_like(k)
    table = jnp.arange(1, NB + 1, dtype=jnp.int32)[None]
    kv_pos = jnp.full((1, NB * BS), POS_SENTINEL, jnp.int32)
    close = stack.close_tables(cfg, params)
    outs = []

    @functools.partial(jax.jit, static_argnames=("prefill",))
    def step(k, v, kv_pos, tokens, pos, prefill):
        with jax.default_matmul_precision("highest"):
            h = llama.embed(params, tokens)
            h, k, v, _, _, at = llama.forward_layers_paged(
                cfg, params["layers"], h, k, v, table, pos, kv_pos, pos,
                backend=backend, prefill=prefill, close=close,
            )
            return llama.final_logits(cfg, params, h)[0], k, v, at

    def run(tokens, cols, prefill):
        nonlocal k, v, kv_pos
        pos = jnp.asarray(cols, jnp.int32)[None]
        kv_pos = kv_pos.at[0, pos[0]].set(pos[0])
        logits, k, v, at = step(
            k, v, kv_pos, jnp.asarray(tokens, jnp.int32)[None], pos, prefill)
        outs.append(np.asarray(logits))
        return at

    with pytest.MonkeyPatch.context() as mp:
        if not slot_offset:
            mp.setattr(stack, "_slot", lambda i, first_layer: i)
        start = 0
        for end in chunks:
            run(ids[start:end], range(start, end), True)
            start = end
        for t in range(start, len(ids)):
            run(ids[t:t + 1], [t], False)
    return np.concatenate(outs), k


# ------------------------------------------------------------------- tests

@pytest.mark.parametrize("T", [1, 3, 4])
def test_the_full_forward_is_the_equations_written_out(T):
    """(a) T passes over the same three layers, the final norm after every
    one, the head without a norm of its own."""
    cfg = tiny_ouro(total_ut_steps=T)
    p = seeded_params(cfg)
    assert ("exit_gate" in p) == (T > 1)
    want, at, _ = hand_model(cfg, p, IDS)
    np.testing.assert_allclose(full_forward(cfg, p, IDS), want,
                               atol=TOL, rtol=TOL)
    assert (np.asarray(at) == T - 1).all()  # threshold 1: the last pass


def test_prefill_then_decode_through_the_dense_cache(params):
    """(b) ... is the full forward, logits not tokens; the cache has T · L
    rows and every one holds values of its own."""
    want, _, _ = hand_model(CFG, params, IDS)
    got, cache = dense_logits(CFG, params, IDS, 16)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    assert cache.k.shape[0] == CFG.passes * CFG.num_hidden_layers == 9
    written = np.asarray(jnp.abs(cache.k).sum((1, 2, 3, 4)))
    assert (written > 0).all() and len(set(written.round(3))) == 9


@pytest.mark.parametrize("backend", ["interpret", "xla"])
@pytest.mark.parametrize("chunks", [(16,), (8, 16)],
                         ids=["one_shot", "chunked"])
def test_chunks_then_decode_through_the_paged_arena(params, backend, chunks):
    """(b) ... over an arena of nine slots, kernels interpreted and not."""
    want, _, _ = hand_model(CFG, params, IDS)
    got, k = paged_logits(CFG, params, IDS, chunks, backend)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    written = np.asarray(jnp.abs(k[:, 1:]).sum((1, 2, 3, 4)))
    assert k.shape[0] == 9 and (written > 0).all()
    assert len(set(written.round(3))) == 9  # pass t's keys are pass t's own


def test_a_cache_whose_passes_share_slots_fails(params):
    """(c) Every pass reading and writing pass 0's slots: the prefill's later
    passes overwrite the earlier ones' keys, and a decode step's first pass
    attends the LAST pass's."""
    want, _, _ = hand_model(CFG, params, IDS)
    got, k = paged_logits(CFG, params, IDS, (16,), "xla", slot_offset=False)
    assert not np.asarray(jnp.abs(k[3:, 1:])).any()  # slots 3.. never written
    assert np.abs(got[16:] - np.asarray(want)[16:]).max() > 100 * TOL


@pytest.mark.parametrize("wrong", [
    dict(passes=2), dict(close="last"), dict(head_norm=True),
    dict(drop="attn_out_norm"), dict(drop="mlp_out_norm"),
], ids=["a_pass_fewer", "no_norm_between_passes", "the_head_norms_again",
        "no_attn_out_norm", "no_mlp_out_norm"])
def test_each_wrong_model_fails_the_tolerance(params, wrong):
    """(d) The final norm runs exactly T times a token and the head adds none:
    T - 1 passes, a norm after the last pass only, a norm more in the head and
    a dropped output norm (in ONE layer of three) each read far outside."""
    got = full_forward(CFG, params, IDS)
    other, _, _ = hand_model(CFG, params, IDS, **wrong)
    assert np.abs(got - np.asarray(other)).max() > 100 * TOL


@pytest.mark.parametrize("theta", [1.0, 0.5, 1e-6])
def test_the_gate_chooses_the_pass_the_head_reads(params, theta):
    """(e) At 1 the last pass (a saturated gate aside), at a tiny threshold
    pass 0, at 0.5 a mix: the exit pass a position is the hand-written one,
    and the logits are that pass's closed state through the head."""
    cfg = dataclasses.replace(CFG, exit_threshold=theta)
    want, at, closed = hand_model(cfg, params, IDS)
    at = np.asarray(at)
    ids = jnp.asarray(IDS, jnp.int32)[None]
    pos = jnp.arange(len(IDS), dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        h, _, got_at = llama.forward_layers(
            cfg, params["layers"], llama.embed(params, ids),
            init_cache(cfg, 1, len(IDS), dtype=jnp.float32), pos,
            close=stack.close_tables(cfg, params))
    np.testing.assert_array_equal(np.asarray(got_at[0]), at)
    np.testing.assert_allclose(
        np.asarray(h[0]), np.asarray(closed)[at, np.arange(len(IDS))],
        atol=TOL, rtol=TOL)
    np.testing.assert_allclose(full_forward(cfg, params, IDS), want,
                               atol=TOL, rtol=TOL)
    if theta == 1.0:
        assert (at == cfg.passes - 1).all()
    elif theta == 0.5:
        assert len(set(at)) > 1  # some positions leave early, some do not
    else:
        assert (at == 0).all()
    # the decode path keeps the same choice: the served rows' passes
    got, _ = dense_logits(cfg, params, IDS, 16)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_one_pass_without_output_norms_is_the_plain_llama_model():
    """(f) ... of the same keys (no bias), to the last digit: the loop and the
    two extra norms are all the family adds."""
    ouro = dataclasses.replace(tiny_ouro(total_ut_steps=1), out_norms=False)
    plain = ModelConfig.from_hf_config(dict(
        {k: v for k, v in tiny_ouro_keys().items()
         if k not in ("total_ut_steps", "early_exit_threshold", "layer_types")},
        model_type="llama"))
    assert ouro == plain and not plain.attention_bias
    p = seeded_params(plain)
    assert "attn_out_norm" not in p["layers"] and "exit_gate" not in p
    np.testing.assert_array_equal(
        full_forward(ouro, p, IDS), full_forward(plain, p, IDS))
    want, _, _ = hand_model(plain, p, IDS)  # one pass, closed once
    np.testing.assert_allclose(full_forward(plain, p, IDS), want,
                               atol=TOL, rtol=TOL)


def test_the_keys_are_the_familys_own_and_what_is_not_done_is_refused():
    """(g) ``from_hf_config`` takes the published keys as they are; the
    refusals name what they refuse; the new fields survive JSON."""
    assert (CFG.model_type, CFG.passes, CFG.exit_threshold, CFG.out_norms,
            CFG.attention_bias, CFG.arena_slots) == (
        "llama", 3, 1.0, True, False, 3)
    assert ModelConfig.from_json(CFG.to_json()) == CFG
    assert tiny_ouro(total_ut_steps=1).arena_slots == 1
    for bad, match in [
        (dict(total_ut_steps=0), "total_ut_steps 0"),
        (dict(early_exit_threshold=0.0), "early_exit_threshold"),
        (dict(early_exit_threshold=1.5), "early_exit_threshold"),
        (dict(use_sliding_window=True), "sliding-window"),
        (dict(sliding_window=4096), "sliding-window"),
        (dict(layer_types=["full_attention", "sliding_attention",
                           "full_attention"]), "sliding_attention"),
    ]:
        with pytest.raises(ValueError, match=match):
            tiny_ouro(**bad)
    keys = tiny_ouro_keys()
    del keys["total_ut_steps"]
    with pytest.raises(ValueError, match="lacks 'total_ut_steps'"):
        ModelConfig.from_hf_config(keys)
    # a checkpoint of the family is refused by name, never read as llama's
    from llm_sharding_tpu.utils.convert import _refuse_unmapped

    with pytest.raises(NotImplementedError, match="'ouro'"):
        _refuse_unmapped(CFG)
    # a stage function that is not handed what closes a pass says so
    with pytest.raises(ValueError, match="what closes a pass"):
        llama.forward_layers(
            CFG, seeded_params(CFG)["layers"],
            jnp.zeros((1, 4, CFG.hidden_size)),
            init_cache(CFG, 1, 4, dtype=jnp.float32),
            jnp.arange(4, dtype=jnp.int32)[None])


def test_int8_quantises_the_seven_matmuls_and_leaves_the_four_norms(params):
    """(h)"""
    from llm_sharding_tpu.ops.quant import QTensor, quantize_params

    q = quantize_params(params)
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert isinstance(q["layers"][name], QTensor), name
    for name in NORMS:
        assert not isinstance(q["layers"][name], QTensor), name
    for name in ("final_norm", "exit_gate", "exit_bias"):
        assert not isinstance(q[name], QTensor), name
    # the int8 model's full forward is near the float one, and not it
    got, want = full_forward(CFG, q, IDS), full_forward(CFG, params, IDS)
    err = np.abs(got - want).max()
    assert TOL < err < 0.5
