"""``KeyeVL2`` through the engine and the server on the CPU at tiny widths
(``tests/test_keye_vl2.py`` holds the layer and its operations to the plain
reference): an index arena beside K and V through ``PipelineEngine.serve()`` —
prefill in chunks, then decode through the arenas, a query attending the
``topk`` 16 keys its indexer chose — against the reference's FULL forward in
LOGITS, at prompts under and over ``topk`` and a chunk, four rows at different
contexts in one step; a block freed and reused (a stale index key is never
scored); a ring of two stages; the counters, the gauges and the ``/metrics``
rows; the words of the step programs; and what the index arena is not carried
through, each refused by name through the ONE helper the windowed and the
recurrent models' refusals go through."""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.obs import metrics
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.server import PipelineServer

from test_keye_vl2 import BLOCK, CFG, MODEL, params, ref_logits  # noqa: F401

PAGED = dict(capacity=256, batch_per_slot=4, kv_block_size=4, kv_blocks=257,
             prefill_chunk=16)


def engine(params, cfg=CFG, **kw):
    kw.setdefault("num_stages", 1)
    n = kw["num_stages"]
    return PipelineEngine(cfg, params, cache_dtype=jnp.float32,
                          devices=jax.devices()[:n], **kw)


def served_logit_gaps(params, req):
    """LOGITS, not tokens: teacher-forced, the reference's best logit minus
    its logit of the served token at every output position of the WHOLE
    sequence (0 where the served token is the reference's argmax)."""
    ids = np.concatenate([np.asarray(req.prompt), np.asarray(req.tokens)])
    logits = ref_logits(params, ids.astype(np.int32))
    n = len(req.prompt)
    rows = logits[n - 1:n - 1 + len(req.tokens)]
    served = np.asarray(req.tokens)
    return rows.max(-1) - rows[np.arange(len(served)), served]


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_prefill_then_decode_through_the_arenas_is_the_references_forward(
        params, backend, monkeypatch):
    """The normal serve path on both backends: FOUR rows of unlike contexts in
    one slot (prompts of 5, 12, 37 and 70 tokens: under ``topk``, under a
    chunk, over both), replies of 24 tokens decoded through the arenas — the
    selection bites in every row's decode and inside the long prompts'
    chunks: every served token's reference logit is the reference's best."""
    if backend == "interpret":
        monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    srv = engine(params).serve(
        prefix_cache="hbm", **({"paged_attn": "xla"} if backend == "xla" else {}),
        **PAGED)
    assert srv.attn_impl == backend and srv.sparse
    assert not srv.windowed and not srv.recurrent
    # a hit's suffix would admit through the dense window: switched off
    assert srv.prefix_cache == "off" and srv._radix is None
    # K and V at two key/value heads of 16; ONE index key a token beside
    # them, padded to a whole 128-lane tile, in the same blocks
    assert srv.state.k.shape == (1, 2, 257, 2, 4, 16)
    assert srv.state.idx.shape == (1, 2, 257, 1, 4, 128)
    assert srv.state.k_swa is None and srv.state.recurrent is None
    rng = np.random.default_rng(3)
    reqs = [srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 24)
            for n in (5, 12, 37, 70)]
    srv.run_until_idle()
    assert srv._alloc.in_use == 0  # index blocks are freed with their K/V
    srv.close()
    for r in reqs:
        assert len(r.tokens) == 24
        assert served_logit_gaps(params, r).max() < 3e-4
    # every prompt admitted chunk by chunk, in whole chunks: ONE program
    assert [srv._bucket(n) for n in (1, 5, 16, 17, 37)] == [16, 16, 16, 32, 64]


def test_a_freed_block_reused_by_another_request_scores_no_stale_key(params):
    """A pool of ONE row's worth of blocks: the second request decodes in the
    very blocks the first filled with index keys (and the trash block holds
    what parked rows wrote), and reads what a fresh server gives it."""
    kw = dict(PAGED, capacity=128, batch_per_slot=1, kv_blocks=33)
    rng = np.random.default_rng(8)
    first, second = (rng.integers(0, 250, size=n).astype(np.int32)
                     for n in (60, 9))
    srv = engine(params).serve(paged_attn="xla", **kw)
    a = srv.submit(first, 30)
    srv.run_until_idle()
    held = np.abs(np.asarray(srv.state.idx, np.float32)).sum(axis=(0, 1, 3, 4, 5))
    assert (held[1:] > 0).sum() >= 22  # the first request's keys stay behind
    b = srv.submit(second, 40)
    srv.run_until_idle()
    srv.close()
    assert len(a.tokens) == 30 and len(b.tokens) == 40
    assert served_logit_gaps(params, b).max() < 3e-4


def test_a_ring_of_two_stages_carries_the_index_arena(params):
    srv = engine(params, num_stages=2).serve(paged_attn="xla", **PAGED)
    assert srv.state.idx.shape[:3] == (2, 1, 257)
    prompt = np.random.default_rng(6).integers(0, 250, size=19).astype(np.int32)
    req = srv.submit(prompt, 16)
    srv.run_until_idle()
    srv.close()
    assert served_logit_gaps(params, req).max() < 3e-4


@pytest.mark.parametrize("kw, word", [
    ({"prefill_chunk": None}, "chunk by chunk"),
    ({"kv_block_size": None, "kv_blocks": None},
     "paged arena with its index keys beside K and V"),
    ({"kv_dtype": "int8"}, "kv_dtype='int8' over a token-selecting model"),
    ({"speculate": 2}, "serve_verify writes no index keys"),
    ({"snapshot_every_s": 1.0, "snapshot_path": "/tmp/x"},
     "snapshots of a token-selecting model"),
    # (its experts refuse cp first, by name too; without them the kind's own
    # refusal, "cp / tp over a token-selecting model", is the one that fires)
    ({"cp": 2}, "cp over a model with sparse experts"),
])
def test_what_the_index_arena_breaks_is_refused_at_construction(
        params, kw, word):
    with pytest.raises((ValueError, NotImplementedError), match=word):
        engine(params).serve(**dict(PAGED, paged_attn="xla", **kw))


def test_tensor_parallelism_is_refused(params):
    with pytest.raises((ValueError, NotImplementedError)):
        PipelineEngine(
            CFG, params, cache_dtype=jnp.float32, num_stages=1,
            tensor_parallel=2, devices=jax.devices()[:2],
        ).serve(**dict(PAGED, paged_attn="xla"))


@pytest.mark.parametrize("what", [
    "snapshot", "restore", "prefill_prefix", "submit_embedding", "read",
    "write",
])
def test_what_the_index_arena_breaks_is_refused_on_a_live_server(params, what):
    eng = engine(params)
    srv = eng.serve(paged_attn="xla", **PAGED)
    calls = {
        "snapshot": (srv.snapshot, "snapshot of a token-selecting model"),
        "prefill_prefix": (lambda: srv.prefill_prefix(np.arange(8)),
                           "prefill_prefix over a token-selecting"),
        "submit_embedding": (
            lambda: srv.submit_embedding(
                np.zeros((4, CFG.hidden_size), np.float32), 4),
            "submit_embedding over a token-selecting"),
        # the hand-off, the host tier and the disk tier move blocks by id
        "read": (lambda: srv._read_arena_blocks([1, 2]), "moving KV blocks"),
        "write": (lambda: srv._write_arena_blocks([1], None, None),
                  "moving KV blocks"),
    }
    try:
        if what == "restore":
            from llm_sharding_tpu.runtime import server as server_mod

            with pytest.raises(NotImplementedError,
                               match="restore into a token-selecting"):
                server_mod.refuse_kind_state(
                    CFG, "restore into", server_mod._SNAPSHOT_WHY)
            with pytest.raises(Exception):
                PipelineServer.restore(eng, {"format": 99})
        else:
            call, word = calls[what]
            with pytest.raises(NotImplementedError, match=word) as err:
                call()
            assert "index arena" in str(err.value)
    finally:
        srv.close()


def test_the_refusals_name_the_model_through_the_one_helper():
    from llm_sharding_tpu.runtime.server import (
        kind_state_name, refuse_kind_state,
    )

    assert kind_state_name(CFG) == "a token-selecting model (llama)"
    with pytest.raises(NotImplementedError,
                       match="a token-selecting model .llama.: the index arena"):
        refuse_kind_state(CFG, "x of", ("w", "r"))
    with pytest.raises(NotImplementedError, match="llama.: one reason"):
        refuse_kind_state(CFG, "x of", "one reason")


def test_the_step_programs_name_the_indexer_and_the_selection(
        params, monkeypatch):
    """The decode and the chunk program carry ``indexer`` and ``select`` — the
    words PR 49 added to ``obs.stepline.SCOPES`` — beside the words of the
    llama block; ``serve_admit`` is never dispatched."""
    from llm_sharding_tpu.obs.stepline import SCOPES
    from llm_sharding_tpu.parallel import serve as serve_ops

    texts = {}
    for name in ("serve_chunk", "serve_prefill_chunk", "serve_admit"):
        orig = getattr(serve_ops, name)

        def call(*a, _o=orig, _n=name, **kw):
            if _n not in texts:
                texts[_n] = _o.lower(*a, **kw).as_text(debug_info=True)
            return _o(*a, **kw)

        monkeypatch.setattr(serve_ops, name, call)
    srv = engine(params).serve(paged_attn="xla", **PAGED)
    srv.submit(np.arange(5, 25, dtype=np.int32), 3)
    srv.run_until_idle()
    srv.close()
    assert sorted(texts) == ["serve_chunk", "serve_prefill_chunk"]
    words = {"indexer", "select"}
    assert words <= set(SCOPES)
    for text in texts.values():
        paths = set(re.findall(r'loc\("([^"]+)"', text))
        found = {w for w in SCOPES
                 if any(re.search(rf"(^|/){w}(/|$)", p) for p in paths)}
        assert words | {"attn", "qkv", "rope", "o_proj", "kv_write", "norm",
                        "router", "moe", "state"} <= found


def test_the_counters_the_gauges_and_the_metrics_rows(params):
    """What a decode step scored, kept, found live and walked (host
    arithmetic at dispatch, summed over rows and layers), and the index arena
    under a kind name of its own beside K/V's."""
    from llm_sharding_tpu.runtime.server import _update_load_gauges

    base = {c: c.value for c in (metrics.SPARSE_TOKENS_SCORED,
                                 metrics.SPARSE_TOKENS_READ,
                                 metrics.SPARSE_TOKENS_LIVE,
                                 metrics.SPARSE_TOKENS_WALKED)}
    srv = engine(params).serve(paged_attn="xla", **dict(PAGED, batch_per_slot=1))
    item = 4  # a float32 arena here
    assert metrics.KV_KIND_ENTRY_BYTES.labels(kind="kv").value == 2 * 2 * 16 * item
    assert metrics.KV_KIND_ENTRY_BYTES.labels(kind="index").value == 128 * item
    # the arena's bytes count the index keys: blocks x layers x 4 tokens
    assert srv.arena_bytes_device == 257 * 2 * 4 * (2 * 2 * 16 + 128) * item
    prompt = np.random.default_rng(2).integers(0, 250, size=9).astype(np.int32)
    req = srv.submit(prompt, 12)
    for _ in range(4):
        srv.step()
    _update_load_gauges()
    used = metrics.KV_KIND_BLOCKS_IN_USE.labels(kind="index").value
    assert used == metrics.KV_KIND_BLOCKS_IN_USE.labels(kind="kv").value > 0
    assert metrics.KV_KIND_BLOCKS_TOTAL.labels(kind="index").value >= 256
    srv.run_until_idle()
    recs = srv.stepline.snapshot()
    text = metrics.REGISTRY.prometheus_text()
    srv.close()
    assert len(req.tokens) == 12
    scored, read, live, walked = (c.value - base[c] for c in base)
    # 11 decode dispatches at contexts 9..19 after the injected last prompt
    # token's (the host's length mirror), 2 layers: everything is live, the
    # steps past a context of 16 score it all and KEEP 16 of it; the
    # attention streams the blocks of all of it on every dispatch (the
    # selection is a mask over the decode kernel's walk)
    steps = [r["sparse_tokens"] for r in recs if "sparse_tokens" in r]
    assert sum(s["live"] for s in steps) == live > 0
    assert sum(s["read"] for s in steps) == read
    assert sum(s["scored"] for s in steps) == scored
    assert 0 < read < live and 0 < scored < live
    assert all(s["read"] <= 16 * 2 * max(1, s["live"] // 18) for s in steps)
    assert all(s["walked"] == s["live"] for s in steps) and walked == live
    assert all(set(s) == {"scored", "read", "live", "walked"} for s in steps)
    for family in ("server_sparse_tokens_scored_total",
                   "server_sparse_tokens_read_total",
                   "server_sparse_tokens_live_total",
                   "server_sparse_tokens_walked_total",
                   'server_kv_kind_entry_bytes{kind="index"}',
                   'server_kv_kind_blocks_in_use{kind="index"}'):
        assert family in text


@pytest.mark.parametrize("scores", ["as they are", "rounded until they tie"])
def test_the_search_serves_the_ids_the_sort_served(params, monkeypatch, scores):
    """``select_mask`` as it is (PR 51: the ``topk``-th score by a search)
    and the sort-based body it had (``test_keye_vl2.sorted_mask``, built from
    ``select_tokens``) serve the same ids, id for id: two rows in one slot,
    one crossing ``topk`` inside its prompt's chunks and one in its reply —
    on the indexer's scores, and on the same scores rounded to whole numbers,
    where many columns tie across the ``topk``-th place and the lower column
    has to win on both sides."""
    from llm_sharding_tpu.ops import paged_attention as pa
    from test_keye_vl2 import sorted_mask

    def coarse(s):
        return s if scores == "as they are" else jnp.round(s)

    rng = np.random.default_rng(51)
    prompts = [rng.integers(0, 250, size=n).astype(np.int32) for n in (5, 37)]
    served = {}
    for name, mask in (("search", pa.select_mask), ("sort", sorted_mask)):
        monkeypatch.setattr(
            pa, "select_mask", lambda s, topk, _m=mask: _m(coarse(s), topk))
        jax.clear_caches()  # the step programs trace the selection they find
        try:
            srv = engine(params).serve(paged_attn="xla", **PAGED)
            reqs = [srv.submit(p, 40) for p in prompts]
            srv.run_until_idle()
            srv.close()
        finally:
            monkeypatch.undo()
            jax.clear_caches()
        served[name] = [[int(t) for t in r.tokens] for r in reqs]
    assert [len(t) for t in served["search"]] == [40, 40]
    assert served["search"] == served["sort"]


def test_the_kernel_search_serves_the_ids_the_xla_search_serves(
        params, monkeypatch):
    """Under ``PAGED_FORCE_KERNEL=interpret`` a slot's search runs as the
    Pallas call (``select_topk``, PR 58) — a decode step's four rows and a
    chunk's sixteen queries both — and the server says so
    (``server_select_backend``); the same server with the search held to its
    XLA form (every other kernel still emulated) serves the same ids, id for
    id: two rows, one crossing ``topk`` inside its prompt's chunks and one
    in its reply."""
    from llm_sharding_tpu.ops import paged_attention as pa
    from llm_sharding_tpu.runtime.server import _update_load_gauges

    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    rng = np.random.default_rng(58)
    prompts = [rng.integers(0, 250, size=n).astype(np.int32) for n in (5, 37)]
    served, searched = {}, {}
    kernel = pa.select_topk_tpu
    for form in ("interpret", "xla"):
        shapes = searched[form] = []
        monkeypatch.setattr(
            pa, "select_topk_tpu",
            lambda s, k, _seen=shapes, **kw: (
                _seen.append(s.shape) or kernel(s, k, **kw)))
        if form == "xla":
            monkeypatch.setattr(pa, "select_path", lambda batch, width: "xla")
        jax.clear_caches()  # the step programs trace the search they find
        try:
            srv = engine(params).serve(**PAGED)
            assert srv.attn_impl == "interpret"
            assert srv.select_backend == form
            reqs = [srv.submit(p, 14) for p in prompts]
            srv.run_until_idle()
            _update_load_gauges()
            text = metrics.REGISTRY.prometheus_text()
            srv.close()
        finally:
            jax.clear_caches()
        for b in metrics.SELECT_BACKENDS:
            assert f'server_select_backend{{backend="{b}"}} {int(b == form)}' in text
        served[form] = [[int(t) for t in r.tokens] for r in reqs]
    # a decode step's slot of four rows and a chunk's sixteen queries, over
    # the window's 256 columns
    assert set(searched["interpret"]) == {(4, 256), (16, 256)}
    assert searched["xla"] == []
    assert [len(t) for t in served["interpret"]] == [14, 14]
    assert served["interpret"] == served["xla"]
