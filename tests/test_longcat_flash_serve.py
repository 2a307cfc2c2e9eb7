"""``longcat_flash`` through the engine and the server on the CPU at tiny widths
(``tests/test_longcat_flash.py`` holds the model and its ops to the plain
reference): ``PipelineEngine.serve()`` over an arena TWO layer slots a layer
wide — chunked admission in whole chunks, rows freed and reused, the radix
cache, its host tier, snapshots, ``extract`` / ``adopt`` and the disaggregated
hand-off all carrying six slots for three layers as they carry three for
``deepseek_v3`` — a ring of two stages, a server without ``prefill_chunk`` (the
one-shot dense window) and one without pages, the shard store, the step
programs' words, the counters, and every refusal by name."""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.obs.metrics import REGISTRY
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate

from test_longcat_flash import CFG, params  # noqa: F401  (the fixture)

PAGED = dict(capacity=128, batch_per_slot=2, kv_block_size=8, kv_blocks=129)


def engine(params, stages=1, cfg=CFG):
    return PipelineEngine(cfg, params, num_stages=stages,
                          cache_dtype=jnp.float32,
                          devices=jax.devices()[:stages])


def oracle(params, prompt, n, cfg=CFG):
    res = generate(cfg, params, prompt, n, cache_dtype=jnp.float32)
    return list(res.tokens[0, len(prompt):int(res.lengths[0])])


def serve_and_check(eng, params, cfg=CFG, **kw):
    """Serve three prompts (shorter than a chunk, longer than one, and a
    repeat's seed for the prefix cache) and hold the tokens to the monolith's."""
    kw.setdefault("prefill_chunk", 16)
    srv = eng.serve(prefix_cache="hbm", **PAGED, **kw)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 250, size=n).astype(np.int32)
               for n in (5, 20, 37)]
    reqs = [srv.submit(p, 6) for p in prompts]
    srv.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert list(r.tokens) == oracle(params, p, 6, cfg)
    return srv, prompts, reqs


def test_serving_over_an_arena_two_slots_a_layer_wide(
        params, monkeypatch, tmp_path):
    """The normal serve path, kernels interpreted: every prompt admits chunk
    by chunk (no dense window of six slots is built: ``serve_admit`` is never
    dispatched), rows are freed and reused, a repeated prompt hits the radix
    cache over latent blocks of six slots, a snapshot restores and continues;
    the arena gauges read one ENTRY's bytes over six slots."""
    from llm_sharding_tpu.parallel import serve as serve_ops
    from llm_sharding_tpu.runtime.server import (
        PipelineServer, load_snapshot, save_snapshot,
    )

    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    admits = []
    orig = serve_ops.serve_admit
    monkeypatch.setattr(
        serve_ops, "serve_admit",
        lambda *a, **kw: admits.append(1) or orig(*a, **kw))
    eng = engine(params)
    srv, prompts, reqs = serve_and_check(eng, params)
    assert srv.attn_impl == "interpret" and not admits
    assert srv._bucket(5) == 16  # whole chunks, whatever the length
    # [S, 2 · Lp, NB, one latent head, BS, 128 lanes]; no values
    assert srv.state.k.shape == (1, 6, 129, 1, 8, 128)
    assert srv.state.v.shape[-1] == 0
    assert REGISTRY.get("server_kv_entry_bytes").value == 128 * 4  # f32 here
    assert srv.arena_bytes_device == 6 * 129 * 8 * 128 * 4
    # three requests over one slot of two rows: a row was reused
    assert len(reqs) > PAGED["batch_per_slot"]
    hits = REGISTRY.get("server_prefix_cache_hit_tokens_total")
    before = sum(c.value for _, c in hits.series())
    again = srv.submit(prompts[2], 6)
    srv.run_until_idle()
    assert list(again.tokens) == list(reqs[2].tokens)
    assert sum(c.value for _, c in hits.series()) > before
    srv._alloc.check(), srv._radix.check()
    # snapshot mid-stream, restore, and the stream continues token-exact
    long = srv.submit(prompts[1], 12)
    for _ in range(4):
        srv.step()
    save_snapshot(srv.snapshot(), str(tmp_path / "snap"))
    srv.close()
    back = PipelineServer.restore(eng, load_snapshot(str(tmp_path / "snap")))
    assert back.state.k.shape[1] == 6
    revived = next(r for r in back._rows + list(back._queue)
                   if r is not None and r.id == long.id)
    back.run_until_idle()
    assert list(revived.tokens) == oracle(params, prompts[1], 12)
    back.close()


def test_the_counters_know_the_experts_without_weights(params, monkeypatch):
    """``StepRecord.expert_tokens`` is ``[E + Z]`` wide; pairs on zero-compute
    experts are its slice past the real ones and ``server_moe_zero_pairs_
    total``; held + elsewhere + zero = every pair routed; pads and dead rows
    count nothing."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    routed = REGISTRY.get("server_moe_pairs_routed_total")
    held = REGISTRY.get("server_moe_pairs_held_total")
    zero = REGISTRY.get("server_moe_zero_pairs_total")
    r0, h0, z0 = routed.value, held.value, zero.value
    srv = engine(params).serve(prefill_chunk=16, **PAGED)
    prompt = np.arange(3, 23, dtype=np.int32)  # 20 tokens: 19 prefilled
    req = srv.submit(prompt, 4)
    srv.run_until_idle()
    recs = srv.stepline_snapshot(64)
    srv.close()
    assert all(len(r["expert_tokens"]) == CFG.router_experts
               for r in recs if r.get("expert_tokens"))
    E, k, L = CFG.num_experts, CFG.num_experts_per_tok, CFG.num_hidden_layers
    # 19 prompt positions through the chunks, then one decode step a token
    # but the last (whose hidden state nothing reads)
    pairs = (len(prompt) - 1 + len(req.tokens)) * k * L
    total = np.sum([r["expert_tokens"] for r in recs
                    if r.get("expert_tokens")], axis=0)
    assert routed.value - r0 == total.sum()
    assert total.sum() in (pairs, pairs - k * L)
    assert zero.value - z0 == total[E:].sum() > 0
    assert held.value - h0 == total[:E].sum()  # every real expert held here
    read = [r["experts_read"] for r in recs if r.get("expert_steps")]
    assert read and max(max(x) for x in read) <= k


def test_without_a_prefill_chunk_a_prompt_admits_through_the_dense_window(
        params, monkeypatch):
    """The one-shot path: a dense window of six slots, cut into the arena's
    blocks — and a server without pages (the dense state, six slots)."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    eng = engine(params)
    srv, _, _ = serve_and_check(eng, params, prefill_chunk=None)
    assert srv._bucket(5) == 8
    srv.close()
    dense = eng.serve(capacity=64, batch_per_slot=2)
    assert dense.state.k.shape[:2] == (1, 6)
    prompt = np.arange(7, 18, dtype=np.int32)
    assert dense.result(dense.submit(prompt, 5)) == oracle(params, prompt, 5)
    dense.close()


def test_extract_and_adopt_move_a_request_between_two_slot_arenas(
        params, monkeypatch):
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    servers = [
        PipelineEngine(CFG, params, num_stages=1, cache_dtype=jnp.float32,
                       devices=jax.devices()[i:i + 1]).serve(
            prefix_cache="hbm", **dict(PAGED, kv_blocks=65))
        for i in (0, 1)
    ]
    prompt = np.random.default_rng(5).integers(0, 250, size=9).astype(np.int32)
    req = servers[0].submit(prompt, 12)
    for _ in range(5):
        servers[0].step()
    assert req.tokens and not req.done
    servers[1].adopt(servers[0].extract(req), req)
    assert servers[1].result(req) == oracle(params, prompt, 12)
    for srv in servers:
        srv.close()


def test_the_host_tier_and_the_disaggregated_hand_off_carry_six_slots(
        params, monkeypatch):
    from llm_sharding_tpu.runtime.disagg import DisaggServer

    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    paged = dict(capacity=128, kv_block_size=8, kv_blocks=65,
                 prefill_chunk=16)
    prompt = np.random.default_rng(1).integers(0, 250, size=40).astype(np.int32)
    want = oracle(params, prompt, 6)
    srv = engine(params).serve(batch_per_slot=2, prefix_cache="host",
                               host_pool_blocks=32, **paged)
    assert srv.result(srv.submit(prompt, 6)) == want
    srv._radix.demote_all()
    assert srv.result(srv.submit(prompt, 6)) == want
    assert srv.prefix_cache_stats()["host_hit_tokens"] == 32
    srv._alloc.check(), srv._radix.check()
    srv.close()

    ok = REGISTRY.get("server_disagg_handoffs_total")
    before = dict(ok.series()).get(("ok",))
    before = before.value if before else 0
    dis = DisaggServer(
        CFG, params, data_parallel=2, num_stages=1,
        devices=jax.devices()[:2], cache_dtype=jnp.float32,
        prefix_cache="hbm", roles=["prefill", "decode"], **paged)
    req = dis.submit(prompt, 6)
    dis.run_until_idle()
    assert list(req.tokens) == want
    assert dict(ok.series())[("ok",)].value == before + 1
    dis.close()


def test_a_ring_of_two_stages_pads_a_layer_and_its_two_slots(params):
    """Three layers over two stages: stage 1 holds one layer and a padding
    slot — two masked arena slots that are written nothing."""
    eng = engine(params, stages=2)
    assert eng.stage_layers["wo_0"].shape[:2] == (2, 2)
    np.testing.assert_array_equal(
        np.asarray(eng.layer_masks), [[True, True], [True, False]])
    srv, _, _ = serve_and_check(eng, params)
    assert srv.state.k.shape[:2] == (2, 4)
    k = np.asarray(srv.state.k)
    assert k[0, :, 1:].any() and k[1, :2, 1:].any()
    assert not k[1, 2:, 1:].any()  # the masked layer's two slots
    srv.close()


def test_the_shard_store_and_int8_keep_every_matmul_a_plain_leaf(
        params, tmp_path):
    from llm_sharding_tpu.ops.quant import QTensor, quantize_params
    from llm_sharding_tpu.utils import shard_store

    q = quantize_params(params)
    lay = q["layers"]
    for i in (0, 1):
        for name in ("wq_a", "wq_b", "wkv_a", "w_uk", "w_uv", "wo", "w_gate",
                     "w_up", "w_down"):
            assert isinstance(lay[f"{name}_{i}"], QTensor), name
        for name in ("input_norm", "q_a_norm", "kv_a_norm", "post_norm"):
            assert not isinstance(lay[f"{name}_{i}"], QTensor)
    for name in ("we_gate", "we_up", "we_down"):
        assert isinstance(lay[name], QTensor)
    for name in ("router", "router_bias"):
        assert not isinstance(lay[name], QTensor)
    shard_store.save_shards(CFG, q, str(tmp_path))
    cfg, back = shard_store.load_full(str(tmp_path), dtype=jnp.float32)
    assert cfg == CFG
    same = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
        q["layers"], back["layers"])
    assert all(jax.tree.leaves(same))
    # the int8 model serves what the int8 monolith generates
    eng = PipelineEngine(CFG, back, num_stages=1, cache_dtype=jnp.float32,
                         devices=jax.devices()[:1])
    srv = eng.serve(prefill_chunk=16, **PAGED)
    prompt = np.arange(40, 61, dtype=np.int32)
    got = srv.result(srv.submit(prompt, 5))
    srv.close()
    assert got == oracle(back, prompt, 5)
    # a CHECKPOINT of the family is refused before anything is written
    with pytest.raises(NotImplementedError, match="longcat_flash"):
        shard_store.save_shards_streaming(
            CFG, lambda name: None, str(tmp_path / "no"))
    assert not (tmp_path / "no").exists() or not any(
        (tmp_path / "no").iterdir())


def test_the_step_programs_name_the_zero_compute_term(params, monkeypatch):
    """The decode and the chunk program carry ``zero_expert`` — the word PR 57
    added to ``obs.stepline.SCOPES`` — beside latent attention's words (twice
    a layer), ``mlp`` AND ``router`` / ``moe`` (a layer has dense MLPs and
    experts), and no word of another family's."""
    from llm_sharding_tpu.obs.stepline import SCOPES
    from llm_sharding_tpu.parallel import serve as serve_ops

    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    texts = {}
    for name in ("serve_chunk", "serve_prefill_chunk"):
        orig = getattr(serve_ops, name)

        def call(*a, _o=orig, _n=name, **kw):
            if _n not in texts:
                texts[_n] = _o.lower(*a, **kw).as_text(debug_info=True)
            return _o(*a, **kw)

        monkeypatch.setattr(serve_ops, name, call)
    srv = engine(params).serve(prefill_chunk=16, **PAGED)
    srv.submit(np.arange(5, 25, dtype=np.int32), 3)
    srv.run_until_idle()
    srv.close()
    assert sorted(texts) == ["serve_chunk", "serve_prefill_chunk"]
    assert "zero_expert" in SCOPES
    for name, text in texts.items():
        paths = set(re.findall(r'loc\("([^"]+)"', text))
        found = {w for w in SCOPES
                 if any(re.search(rf"(^|/){w}(/|$)", p) for p in paths)}
        assert {"zero_expert", "norm", "qkv", "rope", "absorb", "kv_write",
                "attn", "o_proj", "mlp", "router", "moe", "state"} <= found
        assert not found & {"ssm", "ssm_proj", "ssm_x", "moe_latent", "kda",
                            "kda_proj", "conv", "indexer", "select",
                            "kv_take", "kv_put"}
        # both kernels of a decode step run twice a layer, on slots 2l, 2l+1
        if name == "serve_chunk":
            assert text.count("paged_decode") >= 2


def test_what_two_slots_a_layer_cannot_do_is_refused_by_name(params):
    eng = engine(params)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        eng.serve(kv_dtype="int8", **PAGED)
    with pytest.raises(NotImplementedError, match="speculate over a latent"):
        eng.serve(speculate=2, **PAGED)
    with pytest.raises(NotImplementedError, match="sparse experts|latent"):
        eng.serve(cp=2, **PAGED)
    with pytest.raises(NotImplementedError, match="sparse experts"):
        PipelineEngine(CFG, params, num_stages=1, tensor_parallel=2,
                       devices=jax.devices()[:2])
