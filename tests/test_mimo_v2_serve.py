"""``mimo_v2`` through the engine and the server on the CPU at tiny widths
(``tests/test_mimo_v2.py`` holds the model and its kernels to the plain
reference): a KV state per kind of attention layer through
``PipelineEngine.serve()`` with the kernels interpreted — replies many
windows long, the window layers' blocks going back to their pool while the
row decodes, the bound on what a row may hold under a seeded run of
admissions, decodes and ends — and what a window layer breaks: made to work
(``extract`` / ``adopt``, a ring of alike stages) or refused by name
(a prefix-cache hit is not offered; snapshots, prefix handles, the embeddings
entry, the hand-off's block moves, speculation, cp, a quantized arena)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import mimo_v2
from llm_sharding_tpu.models.config import tiny_mimo_v2
from llm_sharding_tpu.obs.metrics import REGISTRY
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate

from paged_arena import tiles_then_rows
from test_mimo_v2 import CFG, params  # noqa: F401  (the fixture)

PAGED = dict(capacity=128, batch_per_slot=2, kv_block_size=4, kv_blocks=80,
             prefill_chunk=16)


def engine(params, cfg=CFG, **kw):
    kw.setdefault("num_stages", 1)
    n = kw["num_stages"]
    return PipelineEngine(cfg, params, cache_dtype=jnp.float32,
                          devices=jax.devices()[:n], **kw)


def oracle(cfg, params, prompt, n):
    res = generate(cfg, params, prompt, n, cache_dtype=jnp.float32)
    return list(res.tokens[0, len(prompt):int(res.lengths[0])])


def check_pools(srv):
    """The allocator's invariant: a row's window layers hold at most their
    share, the pools' books balance."""
    srv._alloc.check()
    srv._alloc_swa.check()
    held = [len(h) for h in srv._row_swa]
    assert max(held) <= srv._swa_quota
    assert sum(held) == srv._alloc_swa.in_use
    for row, h in enumerate(srv._row_swa):
        assert sorted(h.values()) == sorted(
            int(b) for b in srv._tables_swa[row] if b)
    return held


def align_window_tables(srv):
    """Put the window tables' host mirror on a 64-byte boundary, where the
    CPU backend's device_put ALIASES a numpy array: the push must hand over
    a copy, or a dispatch in flight reads the next slide of the window
    (without this the luck of the allocator decides: the tier-1 run failed
    here one time in two)."""
    n = srv._tables_swa.size * 4
    raw = np.zeros(n + 64, np.uint8)
    off = -raw.ctypes.data % 64
    srv._tables_swa = raw[off:off + n].view(np.int32).reshape(
        srv._tables_swa.shape)


def test_serving_through_the_engine_with_a_kv_state_per_kind(
        params, monkeypatch):
    """The normal serve path, kernels interpreted: prompts under, at and
    over a chunk, replies of five windows; tokens are the monolith's; the
    window layers' pool gives blocks back while rows decode and never holds
    more than the window's share; a repeated prompt is NOT offered a hit."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    srv = engine(params).serve(prefix_cache="hbm", **PAGED)
    align_window_tables(srv)
    assert srv.attn_impl == "interpret" and srv.windowed
    assert srv.prefix_cache == "off" and srv._radix is None
    # an arena per kind: its layers, its pool, its heads; keys padded, values not
    assert srv.state.k.shape[1:] == (2, 80, 1, 4, 128)
    assert srv.state.v.shape[1:] == (2, 80, 1, 4, 16)
    assert srv.state.k_swa.shape[1:] == (2, 2 * 7 + 1, 2, 4, 128)
    assert srv.state.v_swa.shape[1:] == (2, 2 * 7 + 1, 2, 4, 16)
    assert srv._swa_quota == (8 + 16) // 4 + 1
    entry = REGISTRY.get("server_kv_kind_entry_bytes")
    assert {v[0]: c.value for v, c in entry.series()} == {
        "full": 1 * (128 + 16) * 4, "swa": 2 * (128 + 16) * 4}
    freed = REGISTRY.get("server_kv_window_blocks_freed_total")
    before = freed.value
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 250, size=n).astype(np.int32)
               for n in (5, 16, 37)]
    reqs = [srv.submit(p, 40) for p in prompts]
    most = 0
    while srv.step():
        most = max(most, max(check_pools(srv)))
    srv.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert list(r.tokens) == oracle(CFG, params, p, 40)
    assert freed.value - before >= 3 * (40 // 4 - 1)  # a block every 4 steps
    # decoding rows held the window's blocks, not the reply's
    assert 3 <= most <= srv._swa_quota
    assert srv._alloc.in_use == 0 and srv._alloc_swa.in_use == 0
    recs = [r for r in srv.stepline_snapshot(400) if r.get("kv_kinds")]
    assert recs and all(
        r["kv_kinds"]["swa"]["decode_blocks_live"]
        <= r["kv_kinds"]["full"]["decode_blocks_live"] for r in recs)
    assert sum(r["kv_kinds"]["swa"]["blocks_freed"] for r in recs) > 0
    assert max(r["kv_kinds"]["swa"]["blocks_in_use"] for r in recs) <= 2 * 7
    assert recs[-1]["kv_kinds"]["full"]["blocks_total"] == 79
    # the same prompt again: no hit is offered, the reply is the same
    hits = REGISTRY.get("server_prefix_cache_hit_tokens_total")
    h0 = sum(c.value for _, c in hits.series())
    again = srv.submit(prompts[2], 40)
    srv.run_until_idle()
    assert list(again.tokens) == list(reqs[2].tokens)
    assert sum(c.value for _, c in hits.series()) == h0
    text = REGISTRY.render() if hasattr(REGISTRY, "render") else ""
    for name in ("server_kv_kind_blocks_in_use", "server_kv_kind_blocks_total"):
        fam = REGISTRY.get(name)
        assert {v[0] for v, _ in fam.series()} == {"full", "swa"}, text[:0]
    srv.close()


@pytest.mark.parametrize("aligned", [False, True])
def test_the_xla_path_commits_the_same_tokens(params, aligned):
    srv = engine(params).serve(paged_attn="xla", **PAGED)
    if aligned:
        align_window_tables(srv)
    prompt = np.random.default_rng(3).integers(0, 250, size=21).astype(np.int32)
    req = srv.submit(prompt, 30)
    srv.run_until_idle()
    assert list(req.tokens) == oracle(CFG, params, prompt, 30)
    srv.close()


@pytest.mark.parametrize("attn", ["xla", "interpret"])
def test_a_chunk_writes_each_kinds_arena_as_tiles(params, monkeypatch, attn):
    """The chunk write over a KV state per kind (keys wider than values; a
    window layer's table maps the blocks behind the window and a short
    row's pad blocks to block 0, where their tiles land): prompts of one,
    two and three chunks write whole blocks into both kinds' arenas, and
    the tokens — the monolith's — are those of the row-wise write."""
    if attn == "interpret":
        monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    eng = engine(params)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 250, size=n).astype(np.int32)
               for n in (7, 30, 41)]

    def run():
        srv = eng.serve(paged_attn="auto" if attn == "interpret" else "xla",
                        **PAGED)
        assert srv.attn_impl == attn
        reqs = [srv.submit(p, 12) for p in prompts]
        srv.run_until_idle()
        check_pools(srv)
        srv.close()
        return [list(r.tokens) for r in reqs]

    assert tiles_then_rows(run) == [oracle(CFG, params, p, 12) for p in prompts]


def test_the_bound_on_a_window_layers_blocks_holds_under_a_seeded_run(params):
    """A seeded run of admissions, decodes and ends (finished, cancelled):
    the window pool is every row's share and the trash block — worked out,
    not an option — a row never holds more than its share, the pool never
    runs dry, a request waits in the queue while the rows are taken,
    everything drains."""
    srv = engine(params).serve(paged_attn="xla", **PAGED)
    assert srv._alloc_swa.num_blocks == 2 * srv._swa_quota + 1
    rng = np.random.default_rng(7)
    live, done = [], []
    waited = False
    for step in range(260):
        if step % 9 == 0 and len(live) < 5:
            n = int(rng.integers(3, 45))
            live.append(srv.submit(
                rng.integers(0, 250, size=n).astype(np.int32),
                int(rng.integers(4, 50))))
        if step % 37 == 36 and live:
            srv.cancel(live[int(rng.integers(len(live)))])
        srv.step()
        check_pools(srv)
        waited |= bool(srv._queue)
        done += [r for r in live if r.done]
        live = [r for r in live if not r.done]
    srv.run_until_idle()
    check_pools(srv)
    assert waited and len(done) > 8
    assert srv._alloc.in_use == 0 and srv._alloc_swa.in_use == 0
    srv.close()


def test_extract_and_adopt_move_a_request_between_windowed_servers(
        params, monkeypatch):
    """Migration re-prefills the resumed prompt through the chunked path, so
    it needs no block of the source: it works over a KV state per kind."""
    servers = [
        PipelineEngine(CFG, params, num_stages=1, cache_dtype=jnp.float32,
                       devices=jax.devices()[i:i + 1]).serve(
                           paged_attn="xla", **PAGED)
        for i in (0, 1)
    ]
    prompt = np.random.default_rng(5).integers(0, 250, size=9).astype(np.int32)
    req = servers[0].submit(prompt, 30)
    for _ in range(14):
        servers[0].step()
    assert req.tokens and not req.done
    servers[1].adopt(servers[0].extract(req), req)
    assert servers[0]._alloc_swa.in_use == 0
    assert servers[1].result(req) == oracle(CFG, params, prompt, 30)
    for srv in servers:
        srv.close()


def test_a_ring_of_alike_stages_serves_and_an_unlike_one_is_refused(params):
    """Two stages that each hold [window, full] expert layers serve through
    the ring; the default tiny model's stages differ and are refused."""
    cfg = tiny_mimo_v2(hybrid_layer_pattern=[1, 0, 1, 0],
                       moe_layer_freq=[1, 1, 1, 1])
    p = mimo_v2.init_params(cfg, jax.random.key(2), jnp.float32)
    srv = engine(p, cfg, num_stages=2).serve(paged_attn="xla", **PAGED)
    assert srv.state.k_swa.shape[:2] == (2, 1) and srv.state.k.shape[:2] == (2, 1)
    prompt = np.random.default_rng(6).integers(0, 250, size=19).astype(np.int32)
    req = srv.submit(prompt, 24)
    srv.run_until_idle()
    assert list(req.tokens) == oracle(cfg, p, prompt, 24)
    srv.close()
    with pytest.raises((ValueError, NotImplementedError), match="same sequence"):
        engine(params, num_stages=2).serve(paged_attn="xla", **PAGED)


@pytest.mark.parametrize("kw, word", [
    ({"prefill_chunk": None}, "chunk by chunk"),
    ({"kv_block_size": None, "kv_blocks": None}, "paged arena per kind"),
    ({"kv_dtype": "int8"}, "quantized arena per kind"),
    ({"speculate": 2, "prefill_chunk": None}, "chunk by chunk"),
    ({"snapshot_every_s": 1.0, "snapshot_path": "/tmp/x"}, "snapshots of"),
])
def test_what_a_window_layer_breaks_is_refused_at_construction(params, kw, word):
    with pytest.raises((ValueError, NotImplementedError), match=word):
        engine(params).serve(**dict(PAGED, paged_attn="xla", **kw))


@pytest.mark.parametrize("rows, chunk, bs", [(2, 16, 4), (3, 8, 4), (1, 32, 8)])
def test_the_window_pool_is_every_rows_share_and_no_option(params, rows, chunk, bs):
    """Admission gives a row exactly ``ceil((window + chunk) / BS) + 1``
    window blocks, so the pool is rows x that + the trash block: worked out
    from what the server is given, whatever the capacity."""
    srv = engine(params).serve(paged_attn="xla", **dict(
        PAGED, batch_per_slot=rows, prefill_chunk=chunk, kv_block_size=bs,
        kv_blocks=160))
    quota = -(-(CFG.sliding_window + chunk) // bs) + 1
    assert srv._swa_quota == quota
    assert srv._alloc_swa.num_blocks == rows * quota + 1
    assert srv.state.k_swa.shape[2] == rows * quota + 1
    srv.close()


def test_a_windowed_model_prefills_in_whole_chunks_with_one_program(params):
    """Every prompt admits chunk by chunk, so a prompt under a chunk is
    padded to one: ONE ``serve_prefill_chunk`` shape whatever the length."""
    from llm_sharding_tpu.obs import metrics

    srv = engine(params).serve(paged_attn="xla", **dict(PAGED, capacity=96))
    assert [srv._bucket(n) for n in (1, 5, 16, 17, 37)] == [16, 16, 16, 32, 64]
    rng = np.random.default_rng(11)
    reqs = [srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 6)
            for n in (3, 9, 16, 21)]
    srv.run_until_idle()
    for r in reqs:
        assert list(r.tokens) == oracle(CFG, params, np.asarray(r.prompt), 6)
    chunks = {key for prog, key in metrics._SHAPE_KEYS_SEEN
              if prog == "serve_prefill_chunk" and key[2] == 96}
    assert {key[3] for key in chunks} == {16}
    srv.close()


def test_what_a_window_layer_breaks_is_refused_on_a_live_server(params):
    srv = engine(params).serve(paged_attn="xla", **PAGED)
    with pytest.raises(NotImplementedError, match="snapshot of a windowed"):
        srv.snapshot()
    with pytest.raises(NotImplementedError, match="prefill_prefix over a"):
        srv.prefill_prefix(np.arange(8))
    with pytest.raises(NotImplementedError, match="submit_embedding over a"):
        srv.submit_embedding(np.zeros((4, CFG.hidden_size), np.float32), 4)
    # the hand-off, the host tier and the disk tier move blocks by id
    with pytest.raises(NotImplementedError, match="moving KV blocks"):
        srv._read_arena_blocks([1, 2])
    with pytest.raises(NotImplementedError, match="moving KV blocks"):
        srv._write_arena_blocks([1], None, None)
    srv.close()
    # a model with no window layer takes no window pool
    from llm_sharding_tpu.models import llama
    from llm_sharding_tpu.models.config import tiny_llama

    cfg = tiny_llama()
    p = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    plain = engine(p, cfg).serve(**dict(PAGED, paged_attn="xla"))
    assert not plain.windowed and plain.state.k_swa is None
    assert plain.state.tables_swa is None and plain._bucket(5) == 8
    plain.close()
    # cp: refused by the model's own words before a mesh is built
    with pytest.raises(NotImplementedError):
        engine(params).serve(**dict(PAGED, paged_attn="xla", cp=2))


def test_the_shard_store_and_the_converter_carry_the_kinds(params, tmp_path):
    """A block file a layer whatever its kind, int8 through the fused qkv
    leaf (router, bias and sink stay); a checkpoint in the published
    fused-qkv layout converts to the same tree; the store serves."""
    from llm_sharding_tpu.ops.quant import QTensor, quantize_params
    from llm_sharding_tpu.utils import shard_store
    from llm_sharding_tpu.utils.convert import params_from_hf

    q = quantize_params(params)
    assert isinstance(q["layers"]["moe_swa"]["wqkv"], QTensor)
    assert not isinstance(q["layers"]["moe_swa"]["router"], QTensor)
    assert not isinstance(q["layers"]["moe_swa"]["sink"], QTensor)
    shard_store.save_shards(CFG, q, str(tmp_path / "q"))
    cfg, back = shard_store.load_full(str(tmp_path / "q"), dtype=jnp.float32)
    assert cfg == CFG
    assert set(back["layers"]) == {"dense_full", "moe_swa", "moe_full"}
    same = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
        q["layers"], back["layers"])
    assert all(jax.tree.leaves(same))
    # a checkpoint as published: torch Linear [out, in], experts one by one
    hf = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "model.norm.weight": np.asarray(params["final_norm"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T}
    seen = {}
    F = CFG.moe_intermediate_size
    for i, kind in enumerate(CFG.layer_kinds):
        j = seen.get(kind, 0)
        seen[kind] = j + 1
        p = jax.tree.map(lambda a: np.asarray(a[j]), params["layers"][kind])
        pre = f"model.layers.{i}."
        hf[pre + "input_layernorm.weight"] = p["input_norm"]
        hf[pre + "post_attention_layernorm.weight"] = p["post_norm"]
        hf[pre + "self_attn.qkv_proj.weight"] = p["wqkv"].T
        hf[pre + "self_attn.o_proj.weight"] = p["wo"].T
        if "sink" in p:
            hf[pre + "self_attn.attention_sink_bias"] = p["sink"]
        if kind.startswith("dense"):
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                hf[pre + f"mlp.{theirs}.weight"] = p[ours].T
            continue
        hf[pre + "mlp.gate.weight"] = p["router"].T
        hf[pre + "mlp.gate.e_score_correction_bias"] = p["router_bias"]
        for e in range(CFG.num_experts):
            sl = slice(e * F, (e + 1) * F)
            hf[pre + f"mlp.experts.{e}.gate_proj.weight"] = p["we_gate"][:, sl].T
            hf[pre + f"mlp.experts.{e}.up_proj.weight"] = p["we_up"][:, sl].T
            hf[pre + f"mlp.experts.{e}.down_proj.weight"] = p["we_down"][sl].T
    got = params_from_hf(CFG, hf, dtype=jnp.float32)
    same = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)), params, got)
    assert all(jax.tree.leaves(same))
    # the store through the engine's own loader, then the server
    shard_store.save_shards(CFG, params, str(tmp_path / "f"))
    eng = PipelineEngine.from_shards(
        str(tmp_path / "f"), num_stages=1, devices=jax.devices()[:1],
        dtype=jnp.float32, cache_dtype=jnp.float32)
    srv = eng.serve(paged_attn="xla", **PAGED)
    prompt = np.random.default_rng(8).integers(0, 250, size=11).astype(np.int32)
    req = srv.submit(prompt, 20)
    srv.run_until_idle()
    assert list(req.tokens) == oracle(CFG, params, prompt, 20)
    srv.close()


def test_the_cli_serves_a_windowed_store(params, tmp_path, capsys, monkeypatch):
    """``python -m llm_sharding_tpu serve`` takes a ``mimo_v2`` store through
    the normal path with the flags of any paged server (the window layers'
    pool is worked out); without ``--prefill-chunk`` the server says what it
    needs."""
    import io

    from llm_sharding_tpu import cli
    from llm_sharding_tpu.utils import shard_store

    class Tok:
        def __call__(self, text):
            return {"input_ids": [ord(c) % 200 + 1 for c in text]}

        def decode(self, ids, skip_special_tokens=True):
            return " ".join(str(int(i)) for i in ids)

    monkeypatch.setattr(
        PipelineEngine, "_require_tokenizer", lambda self: Tok())
    store = str(tmp_path / "store")
    shard_store.save_shards(CFG, params, store)
    argv = ["serve", store, "--max-new", "20", "--stages", "1", "--capacity",
            "128", "--dtype", "f32", "--batch-per-slot", "2",
            "--kv-block-size", "4", "--kv-blocks", "80"]
    text = "a question of some words"
    monkeypatch.setattr("sys.stdin", io.StringIO(text + "\n"))
    assert cli.main(argv + ["--prefill-chunk", "16"]) == 0
    out = capsys.readouterr()
    assert '"requests_completed": 1' in out.err
    prompt = np.asarray(Tok()(text)["input_ids"], np.int32)
    want = " ".join(str(t) for t in oracle(CFG, params, prompt, 20))
    assert want in out.out
    monkeypatch.setattr("sys.stdin", io.StringIO(text + "\n"))
    with pytest.raises(ValueError, match="chunk by chunk"):
        cli.main(argv)
