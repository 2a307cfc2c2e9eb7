"""``deepseek_v3`` on the CPU at tiny widths (1 dense + 2 expert layers,
``v_head_dim`` != ``qk_nope_head_dim``, YaRN on): the program's LOGITS —
prefill, then decode through the latent paged arena with the kernels
interpreted — against the plain float32 reference of
``benchmark/blocks/deepseek_v3.py``; absorbed attention against decompressed
attention on the same latents; the shares of the experts adding up to the
uncut layer; the router against a direct transcription; what is refused, by
name; and the lowered step programs of the one-kind models, unchanged. The
engine, the server and the shard store: ``tests/test_deepseek_v3_serve.py``."""

import dataclasses
import functools
import hashlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import deepseek_v3 as deepseek, llama
from llm_sharding_tpu.models.cache import POS_SENTINEL, init_cache
from llm_sharding_tpu.models.config import (
    ModelConfig, tiny_deepseek_v3, tiny_deepseek_v3_keys, tiny_olmoe,
    tiny_qwen2,
)
from llm_sharding_tpu.ops import moe
from llm_sharding_tpu.ops.paged_attention import (
    paged_attention, paged_prefill, write_block_kv,
)
from llm_sharding_tpu.runtime.engine import PipelineEngine

KEYS = tiny_deepseek_v3_keys()
CFG = tiny_deepseek_v3()
BS, T = 8, 8  # arena block size, table width: a window of 64 columns


@pytest.fixture(scope="module")
def params():
    p = deepseek.init_params(CFG, jax.random.key(3), jnp.float32)
    # gains off one and a real bias, so a dropped one shows
    k = jax.random.key(4)
    for kind, stack in p["layers"].items():
        for i, name in enumerate(sorted(stack)):
            if name.endswith("_norm"):
                stack[name] = stack[name] + 0.2 * jax.random.normal(
                    jax.random.fold_in(k, i), stack[name].shape)
    return p


def reference_logits(params, ids, keys=KEYS, **overrides):
    """The benchmark's plain reference over one sequence."""
    from benchmark import blocks, reference, weights

    block = blocks.load("deepseek_v3")
    kinds = blocks.kinds(block, keys)
    tables = {k: params[k] for k in ("embed", "final_norm", "lm_head")}
    hidden = reference.hidden_states(
        block, keys, lambda l: weights.take_layer(params["layers"], kinds, l),
        tables, [ids], **overrides,
    )[0][:len(ids)]
    return np.asarray(block.logits(hidden, tables, **block.head_static(keys)))


def paged_logits(cfg, params, ids, n_prefill, backend, round_to=None):
    """Prefill ``ids[:n_prefill]`` as one chunk, then decode the rest token
    by token, all through ``forward_layers_paged`` over a latent arena.
    ``round_to``: weights and the arena's entries rounded through a lower
    precision (this CPU multiplies no bf16; the rounding is what counts)."""
    L = cfg.num_hidden_layers
    dtype = jnp.float32
    p = params if round_to is None else jax.tree.map(
        lambda a: a.astype(round_to).astype(a.dtype), params)
    k = jnp.zeros((L, T + 1, 1, BS, cfg.cache_k_dim), dtype)
    v = jnp.zeros((L, T + 1, 1, BS, 0), dtype)
    table = jnp.arange(1, T + 1, dtype=jnp.int32)[None]  # one row
    kv_pos = jnp.full((1, T * BS), POS_SENTINEL, jnp.int32)
    outs = []

    @functools.partial(jax.jit, static_argnames=("prefill",))
    def step(k, v, kv_pos, tokens, pos, prefill):  # two compiles, not eleven
        with jax.default_matmul_precision("highest"):
            h = deepseek.embed(p, tokens)
            h, k, v, _, _, stats = deepseek.forward_layers_paged(
                cfg, p["layers"], h, k, v, table, pos, kv_pos, pos,
                backend=backend, prefill=prefill,
            )
            return deepseek.final_logits(cfg, p, h)[0], k, v, stats

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

    run(ids[:n_prefill], range(n_prefill), True)
    for t in range(n_prefill, len(ids)):
        stats = run(ids[t:t + 1], [t], False)
    return np.concatenate(outs), stats


IDS = (np.arange(30) * 37 + 11) % 250


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_paged_logits_match_the_plain_reference(params, backend):
    """(a) prefill, then decode through the latent arena, against the plain
    float32 reference — and tight enough that bf16 fails."""
    want = reference_logits(params, IDS)
    got, stats = paged_logits(CFG, params, IDS, 20, backend)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # the layer slots: a dense layer reads and routes nothing
    assert stats.experts_read.shape == (3,) and int(stats.experts_read[0]) == 0
    assert int(stats.expert_tokens[0].sum()) == 0
    assert int(stats.expert_tokens[1].sum()) == CFG.num_experts_per_tok
    if backend == "interpret":
        low, _ = paged_logits(CFG, params, IDS, 20, backend, jnp.bfloat16)
        assert np.abs(low - want).max() > 10 * 2e-4  # a lower precision fails


def test_the_monolith_matches_the_reference_and_the_served_tokens(params):
    want = reference_logits(params, IDS)
    cache = init_cache(CFG, 1, 32, dtype=jnp.float32)
    assert cache.k.shape[-2:] == (1, 128) and cache.v.shape[-1] == 0
    with jax.default_matmul_precision("highest"):
        logits, _ = deepseek.forward(
            CFG, params, jnp.asarray(IDS[None]), cache, jnp.arange(30)[None])
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=2e-4, rtol=2e-4)


def test_absorbed_attention_equals_decompressed_attention_on_the_same_latents():
    """(b) ``[q_lat | q_pe] · [c_kv | k_pe]`` with values a slice of the keys,
    through both paged kernels, == softmax over per-head decompressed k/v."""
    Nh, dn, dr, dv, r = 4, 16, 8, 24, 32
    S, Dk = 19, 128
    ks = jax.random.split(jax.random.key(0), 6)
    c_kv = jax.random.normal(ks[0], (S, r))
    k_pe = jax.random.normal(ks[1], (S, dr))
    q_nope = jax.random.normal(ks[2], (1, S, Nh, dn))
    q_pe = jax.random.normal(ks[3], (1, S, Nh, dr))
    w_uk = jax.random.normal(ks[4], (Nh * dn, r)) * r ** -0.5
    w_uv = jax.random.normal(ks[5], (Nh * dv, r)) * r ** -0.5
    scale = 0.3
    with jax.default_matmul_precision("highest"):
        # decompressed, as published
        k_nope = jnp.einsum("sc,hdc->shd", c_kv, w_uk.reshape(Nh, dn, r))
        vals = jnp.einsum("sc,hvc->shv", c_kv, w_uv.reshape(Nh, dv, r))
        kf = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, None], (S, Nh, dr))], -1)
        qf = jnp.concatenate([q_nope[0], q_pe[0]], -1)
        sc = jnp.einsum("snd,tnd->nst", qf, kf) * scale
        sc = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], sc, -jnp.inf)
        want = jnp.einsum("nst,tnv->snv", jax.nn.softmax(sc, -1), vals)
        # absorbed, over a paged latent arena
        entry = jnp.zeros((1, S, 1, Dk)).at[0, :, 0, :r].set(c_kv)
        entry = entry.at[0, :, 0, r:r + dr].set(k_pe)
        q_full = jnp.zeros((1, S, Nh, Dk)).at[..., :r].set(
            deepseek.absorb_q(q_nope, w_uk))
        q_full = q_full.at[..., r:r + dr].set(q_pe)
        table = jnp.arange(1, 5, dtype=jnp.int32)[None]
        cols = jnp.arange(S, dtype=jnp.int32)[None]
        kv_pos = jnp.full((1, 32), POS_SENTINEL, jnp.int32).at[0, :S].set(cols[0])
        k_arena, v_arena = write_block_kv(
            jnp.zeros((2, 5, 1, 8, Dk)), jnp.zeros((2, 5, 1, 8, 0)), 1,
            table, cols, entry, None,
        )
        assert v_arena.shape[-1] == 0  # holds nothing, is not written
        for backend in ("interpret", "xla"):
            o_lat = paged_prefill(
                q_full, k_arena, v_arena, 1, table, cols, kv_pos, scale,
                backend=backend, latent_v=r,
            )
            got = deepseek.absorb_o(o_lat, w_uv)[0]
            np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
            # decode: the last position alone
            o_last = paged_attention(
                q_full[:, -1:], k_arena, v_arena, 1, table, cols[:, -1:],
                kv_pos, scale, backend=backend, latent_v=r,
            )
            got = deepseek.absorb_o(o_last, w_uv)[0, 0]
            np.testing.assert_allclose(got, want[-1], atol=1e-4, rtol=1e-4)


def one_moe_layer(**kw):
    cfg = tiny_deepseek_v3(num_hidden_layers=1, first_k_dense_replace=0, **kw)
    return cfg, tiny_deepseek_v3_keys(
        num_hidden_layers=1, first_k_dense_replace=0, **kw)


@pytest.mark.parametrize("positions", [12, 48])  # both regimes of the tiles
def test_the_shares_add_up_to_the_uncut_layer(positions):
    """(c) the parts all shares give, with what every chip computes alike
    (attention, the shared expert) counted once, equal the uncut layer — in
    the program and in the reference."""
    full_cfg, full_keys = one_moe_layer()
    full = deepseek.init_params(full_cfg, jax.random.key(8), jnp.float32)
    F, E, n = full_cfg.moe_intermediate_size, 8, 4
    ids = (np.arange(positions) * 13 + 5) % 250
    pos = jnp.arange(positions)[None]

    def program(cfg, p):
        h = deepseek.embed(p, jnp.asarray(ids[None]))
        cache = init_cache(cfg, 1, positions, dtype=jnp.float32)
        with jax.default_matmul_precision("highest"):
            out, _, stats = deepseek.forward_layers(
                cfg, p["layers"], h, cache, pos)
        return np.asarray(out[0]), stats

    def share(rank):
        lo, hi = rank * (E // n) * F, (rank + 1) * (E // n) * F
        moe_l = dict(full["layers"]["moe"])
        moe_l["we_gate"] = moe_l["we_gate"][..., lo:hi]
        moe_l["we_up"] = moe_l["we_up"][..., lo:hi]
        moe_l["we_down"] = moe_l["we_down"][:, lo:hi]
        kw = dict(n_routed_experts=E // n, n_routed_experts_total=E,
                  ep_rank=rank)
        return one_moe_layer(**kw), dict(full, layers={"moe": moe_l})

    whole, stats = program(full_cfg, full)
    # what every chip computes alike: the layer with the routed part off
    alike, _ = program(
        dataclasses.replace(full_cfg, routed_scaling_factor=0.0), full)
    parts, held_pairs = [], 0
    for rank in range(n):
        (cfg, keys), p = share(rank)
        out, st = program(cfg, p)
        parts.append(out - alike)
        # the counters: every pair routed, over all experts, on every share
        np.testing.assert_array_equal(st.expert_tokens, stats.expert_tokens)
        lo = rank * (E // n)
        held_pairs += int(st.expert_tokens[0, lo:lo + E // n].sum())
        # and the reference is given the same share
        ref = reference_logits(p, ids, keys)
        h_last = deepseek.final_logits(cfg, p, jnp.asarray(out))
        np.testing.assert_allclose(h_last, ref, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(alike + sum(parts), whole, atol=1e-4, rtol=1e-4)
    assert held_pairs == positions * full_cfg.num_experts_per_tok
    assert np.abs(sum(parts)).max() > 0.05  # the routed part is not nothing


def test_the_router_matches_a_direct_transcription():
    """(d) groups, the bias used for the choice only, normalisation over all
    chosen, the scale — and the masked groups' 0.0 as transformers fills."""
    rng = np.random.default_rng(1)
    N, H, E, G, KG, K, scale = 40, 16, 32, 8, 3, 4, 2.5
    x = rng.normal(size=(N, H)).astype(np.float32)
    w = (rng.normal(size=(H, E)) * 0.5).astype(np.float32)
    bias = (rng.normal(size=(E,)) * 0.3 - 0.4).astype(np.float32)  # some < 0
    got_w, got_ids = moe.route_noaux_tc(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias), K, G, KG, scale)
    for n in range(N):
        s = 1.0 / (1.0 + np.exp(-(x[n].astype(np.float64) @ w)))
        choice = s + bias
        groups = choice.reshape(G, E // G)
        score = np.sort(groups, axis=-1)[:, -2:].sum(-1)
        kept = np.argsort(-score)[:KG]
        masked = np.zeros_like(choice)  # the other groups read 0.0
        for g in kept:
            masked[g * (E // G):(g + 1) * (E // G)] = groups[g]
        ids = np.argsort(-masked)[:K]
        weights = s[ids] / (s[ids].sum() + 1e-20) * scale
        order = np.argsort(np.asarray(got_ids[n]))
        assert sorted(ids) == list(np.asarray(got_ids[n])[order])
        np.testing.assert_allclose(
            np.asarray(got_w[n])[order], weights[np.argsort(ids)], rtol=1e-5)
    assert abs(float(got_w.sum(-1).mean()) - scale) < 1e-4


def test_what_is_not_done_is_refused_by_name(params):
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        ModelConfig.from_hf_config(dict(KEYS, num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="scoring_func"):
        ModelConfig.from_hf_config(dict(KEYS, scoring_func="softmax"))
    with pytest.raises(ValueError, match="q_lora_rank"):
        ModelConfig.from_hf_config(dict(KEYS, q_lora_rank=None))
    with pytest.raises(ValueError, match="rope_interleave"):
        ModelConfig.from_hf_config(dict(KEYS, rope_interleave=False))
    with pytest.raises(ValueError, match="must divide"):
        ModelConfig.from_hf_config(dict(KEYS, n_routed_experts=3,
                                        n_routed_experts_total=8))
    with pytest.raises(NotImplementedError, match="sparse experts"):
        PipelineEngine(CFG, params, num_stages=1, tensor_parallel=2,
                       devices=jax.devices()[:2])
    eng = PipelineEngine(CFG, params, num_stages=1, cache_dtype=jnp.float32,
                         devices=jax.devices()[:1])
    paged = dict(capacity=64, batch_per_slot=2, kv_block_size=8, kv_blocks=33)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        eng.serve(kv_dtype="int8", **paged)
    with pytest.raises(NotImplementedError, match="speculate over a latent"):
        eng.serve(speculate=2, **paged)
    with pytest.raises(NotImplementedError, match="sparse experts|latent"):
        eng.serve(cp=2, **paged)


# ---- (f) the per-kind tree changes no program of a one-kind model ---------
# sha256[:16] of the lowered (StableHLO) text of the three step programs of
# tiny_qwen2 and tiny_olmoe on a ring of two, kernels interpreted, as the
# PARENT of PR 34 lowered them (recorded there with this very test, under
# tests/conftest.py: the text depends on its settings): the
# guard that the accepted configurations' programs are what they were. A PR
# that changes a one-kind program on purpose re-records them and says so:
# PR 36 re-recorded both ``serve_prefill_chunk`` (the prefill kernel's grid is
# its live cells, the program returns the walk's counters); PR 40 re-recorded
# the three of ``olmoe`` (the expert kernel's grid is its live tiles, the
# combines select them); PR 42 re-recorded both ``serve_prefill_chunk`` again
# (a chunk writes its K/V as whole-block tiles); PR 46 re-recorded both
# ``serve_chunk`` (a decode step's fresh K/V lands through the write kernel
# ``paged_kv_write`` before ``paged_decode``; ``serve_prefill_chunk`` and
# ``serve_admit`` kept their texts); PR 54 re-recorded both ``serve_chunk``
# again (``paged_decode`` is one invocation that copies a cell's blocks by
# hand and walks in its body; the other two kept their texts); PR 61
# re-recorded both ``serve_chunk`` once more (``paged_decode`` stores the
# step's fresh K/V itself, the arenas aliased over its outputs: no
# ``paged_kv_write`` before it; the other two kept their texts); PR 63
# re-recorded the three of ``olmoe`` (the decode regime of the expert product
# is one kernel that fetches by hand — at these toy sizes a chunk of 2 x 16
# positions and an admission's bucket are decode calls too; the three of
# ``qwen2`` kept their texts); ``qwen2``'s ``serve_admit`` is still the
# parent of PR 34's.
GOLDEN = {
    ("qwen2", "serve_admit"): "41a2afe52004928f",
    ("qwen2", "serve_chunk"): "294ec14a73fc8447",
    ("qwen2", "serve_prefill_chunk"): "1f5a149bea8b1099",
    ("olmoe", "serve_admit"): "79bb81ccc37f93d9",
    ("olmoe", "serve_chunk"): "7e03515730e0369c",
    ("olmoe", "serve_prefill_chunk"): "5640e68523781c60",
}


@pytest.mark.parametrize("family", ["qwen2", "olmoe"])
def test_a_one_kind_models_step_programs_are_unchanged(family, monkeypatch):
    from llm_sharding_tpu.parallel import serve as serve_ops

    cfg = {"qwen2": tiny_qwen2, "olmoe": tiny_olmoe}[family]()
    assert cfg.layer_kinds == ()
    p = llama.init_params(cfg, jax.random.key(1), dtype=jnp.float32)
    eng = PipelineEngine(cfg, p, num_stages=2, cache_dtype=jnp.float32,
                         devices=jax.devices()[:2])
    assert "wo" in eng.stage_layers  # today's tree: leaves, not kinds
    texts = {}
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    for prog in ("serve_chunk", "serve_prefill_chunk", "serve_admit"):
        orig = getattr(serve_ops, prog)

        def call(*a, _o=orig, _n=prog, **kw):
            if _n not in texts:
                texts[_n] = _o.lower(*a, **kw).as_text()
            return _o(*a, **kw)

        monkeypatch.setattr(serve_ops, prog, call)
    srv = eng.serve(capacity=64, batch_per_slot=2, kv_block_size=8,
                    kv_blocks=65, prefill_chunk=16)
    rng = np.random.default_rng(0)
    for n in (5, 20):
        srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 4)
    srv.run_until_idle()
    srv.close()
    got = {(family, k): hashlib.sha256(v.encode()).hexdigest()[:16]
           for k, v in texts.items()}
    assert got == {k: v for k, v in GOLDEN.items() if k[0] == family}
