"""``deepseek_v3`` through the engine and the server on the CPU at tiny widths
(``tests/test_deepseek_v3.py`` holds the model and its ops to the plain
reference; a file of its own so that the two run on two workers): the
per-kind tree through ``PipelineEngine.serve()`` with the kernels interpreted,
the latent arena under the prefix cache, its host tier and snapshots,
``extract`` / ``adopt`` and the disaggregated hand-off, a ring of two stages,
and the shard store."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate

from paged_arena import tiles_then_rows
from test_deepseek_v3 import CFG, params  # noqa: F401  (the fixture)


def serve_and_check(eng, params, cfg=CFG, **kw):
    """Serve three prompts (one-shot, chunked, and a repeat that hits the
    prefix cache) and hold the tokens to the monolith's."""
    srv = eng.serve(
        capacity=128, batch_per_slot=2, kv_block_size=8, kv_blocks=129,
        prefill_chunk=16, prefix_cache="hbm", **kw,
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 250, size=n).astype(np.int32)
               for n in (5, 20, 37)]
    reqs = [srv.submit(p, 6) for p in prompts]
    srv.run_until_idle()
    for p, r in zip(prompts, reqs):
        res = generate(cfg, params, p, 6, cache_dtype=jnp.float32)
        assert list(r.tokens) == list(res.tokens[0, len(p):int(res.lengths[0])])
    return srv, prompts, reqs


def test_serving_through_the_engine_latent_arena_prefix_cache_and_snapshot(
        params, monkeypatch, tmp_path):
    """(g) the normal serve path: per-kind tree through the engine, kernels
    interpreted, the arena one latent entry a token; a repeated prompt hits
    the radix cache over latent blocks; a snapshot restores and continues."""
    from llm_sharding_tpu.obs.metrics import REGISTRY
    from llm_sharding_tpu.runtime.server import (
        PipelineServer, load_snapshot, save_snapshot,
    )

    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    eng = PipelineEngine(CFG, params, num_stages=1, cache_dtype=jnp.float32,
                         devices=jax.devices()[:1])
    srv, prompts, reqs = serve_and_check(eng, params)
    assert srv.attn_impl == "interpret"
    assert srv.state.k.shape[-3:] == (1, 8, 128)  # one latent head, padded
    assert srv.state.v.shape[-1] == 0 and srv.state.v.dtype == srv.state.k.dtype
    assert REGISTRY.get("server_kv_entry_bytes").value == 128 * 4  # f32 here
    hits = REGISTRY.get("server_prefix_cache_hit_tokens_total")
    before = sum(c.value for _, c in hits.series())
    again = srv.submit(prompts[2], 6)
    srv.run_until_idle()
    assert list(again.tokens) == list(reqs[2].tokens)
    assert sum(c.value for _, c in hits.series()) > before
    rec = srv.stepline_snapshot(1)[-1]
    assert len(rec["expert_tokens"]) == CFG.num_experts
    routed = REGISTRY.get("server_moe_pairs_routed_total").value
    assert routed > 0
    assert REGISTRY.get("server_moe_pairs_held_total").value == routed
    # snapshot mid-stream, restore, and the stream continues token-exact
    long = srv.submit(prompts[1], 12)
    for _ in range(4):
        srv.step()
    save_snapshot(srv.snapshot(), str(tmp_path / "snap"))
    srv.close()
    back = PipelineServer.restore(eng, load_snapshot(str(tmp_path / "snap")))
    revived = next(r for r in back._rows + list(back._queue)
                   if r is not None and r.id == long.id)
    back.run_until_idle()
    res = generate(CFG, params, prompts[1], 12, cache_dtype=jnp.float32)
    assert list(revived.tokens) == list(
        res.tokens[0, len(prompts[1]):int(res.lengths[0])])
    back.close()


def test_a_latent_chunk_writes_tiles_and_serves_what_the_rows_serve(
        params, monkeypatch):
    """The chunk write over the latent arena (one head of ``Dk`` lanes, no
    values): chunked admissions — cold, and over a radix hit — write whole
    blocks, and the tokens (the monolith's) are those of the row-wise
    write, kernels interpreted."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    eng = PipelineEngine(CFG, params, num_stages=1, cache_dtype=jnp.float32,
                         devices=jax.devices()[:1])

    def run():
        srv, prompts, reqs = serve_and_check(eng, params)
        again = srv.submit(
            np.concatenate([prompts[2][:32], prompts[1]]), 6)  # a hit, chunked
        srv.run_until_idle()
        srv.close()
        return [list(r.tokens) for r in (*reqs, again)]

    tiles_then_rows(run)


def test_extract_and_adopt_move_a_request_between_latent_arenas(
        params, monkeypatch):
    """A request extracted mid-decode from one server and adopted by another
    (the migration primitive of the replica router and of the disaggregated
    hand-off) finishes token-exact over latent arenas on both sides."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    paged = dict(capacity=128, batch_per_slot=2, kv_block_size=8,
                 kv_blocks=65, prefix_cache="hbm")
    servers = [
        PipelineEngine(CFG, params, num_stages=1, cache_dtype=jnp.float32,
                       devices=jax.devices()[i:i + 1]).serve(**paged)
        for i in (0, 1)
    ]
    prompt = np.random.default_rng(5).integers(0, 250, size=9).astype(np.int32)
    req = servers[0].submit(prompt, 12)
    for _ in range(5):
        servers[0].step()
    assert req.tokens and not req.done
    servers[1].adopt(servers[0].extract(req), req)
    res = generate(CFG, params, prompt, 12, cache_dtype=jnp.float32)
    assert servers[1].result(req) == list(
        res.tokens[0, len(prompt):int(res.lengths[0])])
    for srv in servers:
        srv.close()


def test_the_host_tier_and_the_disaggregated_hand_off_carry_latent_blocks(
        params, monkeypatch):
    """Latent blocks (a key entry, a zero-wide value) demoted to the host
    tier come back byte for byte, and a prefill replica hands its blocks to
    a decode replica that prefills nothing again."""
    from llm_sharding_tpu.obs.metrics import REGISTRY
    from llm_sharding_tpu.runtime.disagg import DisaggServer

    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    paged = dict(capacity=128, kv_block_size=8, kv_blocks=65,
                 prefill_chunk=16)
    prompt = np.random.default_rng(1).integers(0, 250, size=40).astype(np.int32)
    res = generate(CFG, params, prompt, 6, cache_dtype=jnp.float32)
    want = list(res.tokens[0, len(prompt):int(res.lengths[0])])

    eng = PipelineEngine(CFG, params, num_stages=1, cache_dtype=jnp.float32,
                         devices=jax.devices()[:1])
    srv = eng.serve(batch_per_slot=2, prefix_cache="host",
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


def test_a_ring_of_two_stages_pads_each_kind_and_serves_the_same_tokens(
        params):
    """Stage 0 holds the dense layer and one expert layer, stage 1 one expert
    layer and a padding slot where stage 0 has its dense layer."""
    eng = PipelineEngine(CFG, params, num_stages=2, cache_dtype=jnp.float32,
                         devices=jax.devices()[:2])
    assert {k: v["wo"].shape[:2] for k, v in eng.stage_layers.items()} == {
        "dense": (2, 1), "moe": (2, 1)}
    np.testing.assert_array_equal(
        np.asarray(eng.layer_masks), [[True, True], [False, True]])
    srv, _, _ = serve_and_check(eng, params)
    srv.close()


def test_the_shard_store_keeps_one_block_a_layer_and_stacks_by_kind(
        params, tmp_path):
    from llm_sharding_tpu.ops.quant import QTensor, quantize_params
    from llm_sharding_tpu.utils import shard_store

    q = quantize_params(params)
    assert isinstance(q["layers"]["moe"]["w_uk"], QTensor)
    assert not isinstance(q["layers"]["moe"]["router"], QTensor)
    shard_store.save_shards(CFG, q, str(tmp_path))
    cfg, back = shard_store.load_full(str(tmp_path), dtype=jnp.float32)
    assert cfg == CFG and set(back["layers"]) == {"dense", "moe"}
    same = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
        q["layers"], back["layers"])
    assert all(jax.tree.leaves(same))
    with pytest.raises(NotImplementedError, match="several kinds"):
        shard_store.load_stage(str(tmp_path), 0, 2, pad_to=3)
