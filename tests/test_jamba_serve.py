"""``jamba`` through the engine and the server on the CPU at tiny widths
(``tests/test_jamba.py`` holds the model and its operations to the plain
reference): a Mamba-1 recurrent state beside the paged arena through
``PipelineEngine.serve()`` — prefill in chunks, then decode through the arena
and the recurrent rows, against the reference's FULL forward in LOGITS, at
prompts shorter than, equal to and longer than a chunk, two requests live in
one slot; a row reused after a finished request starting from zero; a ring of
alike stages; the counters and the ``/metrics`` rows; the words of the step
programs; and what a recurrent state breaks, each refused by name through
the ONE helper ``nemotron_h``'s refusals go through (a prefix-cache hit is
not offered; snapshots and ``restore``, prefix handles, the embeddings entry,
the hand-off's block moves, speculation, tp and cp, a quantized arena, a
non-paged server, a ring of unlike stages) — with no branch on the model's
name in ``runtime/server.py`` or ``parallel/serve.py``."""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import jamba
from llm_sharding_tpu.models.config import tiny_jamba
from llm_sharding_tpu.obs import metrics
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.server import PipelineServer

from test_jamba import CFG, KEYS, params, reference_logits  # noqa: F401

PAGED = dict(capacity=128, batch_per_slot=2, kv_block_size=4, kv_blocks=80,
             prefill_chunk=16)


def engine(params, cfg=CFG, **kw):
    kw.setdefault("num_stages", 1)
    n = kw["num_stages"]
    return PipelineEngine(cfg, params, cache_dtype=jnp.float32,
                          devices=jax.devices()[:n], **kw)


def served_logit_gaps(params, req, keys=KEYS):
    """LOGITS, not tokens: teacher-forced, the reference's best logit minus
    its logit of the served token at every output position of the WHOLE
    sequence (0 where the served token is the reference's argmax)."""
    ids = np.concatenate([np.asarray(req.prompt), np.asarray(req.tokens)])
    logits = reference_logits(params, ids.astype(np.int32), keys=keys)
    n = len(req.prompt)
    rows = logits[n - 1:n - 1 + len(req.tokens)]
    served = np.asarray(req.tokens)
    return rows.max(-1) - rows[np.arange(len(served)), served]


def test_prefill_then_decode_through_the_state_is_the_references_forward(
        params, monkeypatch):
    """The normal serve path, kernels interpreted (both paged kernels at ONE
    key/value head and the scan kernel): prompts under, at and over a chunk
    (1, 2 and 3 chunks), two rows of unlike lengths sharing a slot, replies of
    24 tokens decoded through the state: every served token's reference logit
    is the reference's best over the WHOLE sequence within 3e-4."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    srv = engine(params).serve(prefix_cache="hbm", **PAGED)
    assert srv.attn_impl == "interpret" and srv.recurrent and not srv.windowed
    assert srv.recurrent_backend == srv.recurrent_scan_path == "interpret"
    # a hit cannot slice a recurrent state: accepted and switched off
    assert srv.prefix_cache == "off" and srv._radix is None
    # the arena holds the ONE attention layer at ONE key/value head; the
    # state five mixers, by row, shaped by the configuration alone
    assert srv.state.k.shape == (1, 1, 80, 1, 4, 16)
    assert srv.state.recurrent["ssm"].shape == (1, 5, 2, 4, 8, 16)
    assert srv.state.recurrent["conv"].shape == (1, 5, 2, 3, 128)
    assert srv.state.recurrent["ssm"].dtype == jnp.float32
    assert srv.state.k_swa is None
    rng = np.random.default_rng(3)
    reqs = [srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 24)
            for n in (5, 37, 16, 19)]
    srv.run_until_idle()
    for r in reqs:
        assert len(r.tokens) == 24
        assert served_logit_gaps(params, r).max() < 3e-4
    # every prompt admitted chunk by chunk, in whole chunks: ONE program
    assert [srv._bucket(n) for n in (1, 5, 16, 17, 37)] == [16, 16, 16, 32, 64]
    chunks = {key for prog, key in metrics._SHAPE_KEYS_SEEN
              if prog == "serve_prefill_chunk" and key[2] == 128}
    assert {key[3] for key in chunks} == {16}
    srv.close()


def test_the_xla_path_serves_the_same_logits(params):
    srv = engine(params).serve(paged_attn="xla", **PAGED)
    assert srv.recurrent_backend == srv.recurrent_scan_path == "xla"
    rng = np.random.default_rng(4)
    reqs = [srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 12)
            for n in (16, 33)]
    srv.run_until_idle()
    srv.close()
    for r in reqs:
        assert served_logit_gaps(params, r).max() < 3e-4


def test_the_fused_and_the_split_mixer_step_serve_the_same_tokens(
        params, monkeypatch):
    """A server whose kernels are interpreted takes the FUSED decode step of
    a mixer (one call a layer between the projections, the conv's tail in
    its pass), the XLA server the SPLIT one: the same prompts — a slot of two
    rows of unlike lengths, a row joining later — reply with the same tokens,
    and ``server_recurrent_mixer_step`` is one-hot for each while it lives."""
    from llm_sharding_tpu.runtime.server import _update_load_gauges

    def gauge():
        _update_load_gauges()
        return {p: metrics.RECURRENT_MIXER_STEP.labels(path=p).value
                for p in metrics.RECURRENT_MIXER_STEPS}

    def serve(want, **kw):
        before = gauge()
        srv = engine(params).serve(**kw, **PAGED)
        assert srv.recurrent_mixer_step == want
        now = gauge()
        assert {p: now[p] - before[p] for p in now} == {
            p: float(p == want) for p in now}
        assert f'server_recurrent_mixer_step{{path="{want}"}}' in (
            metrics.REGISTRY.prometheus_text())
        rng = np.random.default_rng(6)
        reqs = [srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 20)
                for n in (7, 21)]
        for _ in range(6):
            srv.step()
        reqs.append(srv.submit(rng.integers(0, 250, size=18).astype(np.int32), 20))
        srv.run_until_idle()
        srv.close()
        assert gauge() == before
        return [list(r.tokens) for r in reqs]

    split = serve("split", paged_attn="xla")
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    fused = serve("fused")
    assert all(len(t) == 20 for t in fused)
    assert fused == split


def test_a_reused_row_starts_from_zero(params):
    """One row: the second request decodes in the row the first left its
    state in, and reads what a fresh server gives it."""
    kw = dict(PAGED, batch_per_slot=1)
    rng = np.random.default_rng(8)
    first, second = (rng.integers(0, 250, size=n).astype(np.int32)
                     for n in (21, 9))
    srv = engine(params).serve(paged_attn="xla", **kw)
    a = srv.submit(first, 12)
    srv.run_until_idle()
    left = np.asarray(srv.state.recurrent["ssm"])
    assert np.abs(left).max() > 1e-3  # the finished request's state stays
    b = srv.submit(second, 12)
    srv.run_until_idle()
    srv.close()
    fresh = engine(params).serve(paged_attn="xla", **kw)
    c = fresh.submit(second, 12)
    fresh.run_until_idle()
    fresh.close()
    assert list(b.tokens) == list(c.tokens) and len(a.tokens) == 12
    assert served_logit_gaps(params, b).max() < 3e-4


def test_a_ring_of_alike_stages_carries_the_state(params):
    """Two stages of one period (``M*MM``) each: a state and an arena a
    stage."""
    kw = dict(num_hidden_layers=8, attn_layer_offset=1)
    cfg = tiny_jamba(**kw)
    assert cfg.layer_pattern == "M*MMM*MM"
    p = jamba.init_params(cfg, jax.random.key(5), jnp.float32)
    srv = engine(p, cfg, num_stages=2).serve(paged_attn="xla", **PAGED)
    assert srv.state.recurrent["ssm"].shape[:3] == (2, 3, 4)
    assert srv.state.k.shape[:2] == (2, 1)
    prompt = np.random.default_rng(6).integers(0, 250, size=19).astype(np.int32)
    req = srv.submit(prompt, 16)
    srv.run_until_idle()
    srv.close()
    assert served_logit_gaps(p, req, keys=dict(KEYS, **kw)).max() < 3e-4


def test_a_ring_of_unlike_stages_is_refused(params):
    with pytest.raises((ValueError, NotImplementedError), match="same sequence"):
        engine(params, num_stages=2).serve(paged_attn="xla", **PAGED)


@pytest.mark.parametrize("kw, word", [
    ({"prefill_chunk": None}, "chunk by chunk"),
    ({"kv_block_size": None, "kv_blocks": None},
     "paged arena beside its recurrent state"),
    ({"kv_dtype": "int8"}, "quantized arena beside a recurrent state"),
    ({"speculate": 2}, "roll the state back"),
    ({"snapshot_every_s": 1.0, "snapshot_path": "/tmp/x"}, "snapshots of"),
    ({"cp": 2}, "cp / tp over a recurrent-state model .jamba."),
])
def test_what_a_recurrent_state_breaks_is_refused_at_construction(
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
def test_what_a_recurrent_state_breaks_is_refused_on_a_live_server(
        params, what):
    eng = engine(params)
    srv = eng.serve(paged_attn="xla", **PAGED)
    calls = {
        "snapshot": (srv.snapshot, "snapshot of a recurrent-state model"),
        "prefill_prefix": (lambda: srv.prefill_prefix(np.arange(8)),
                           "prefill_prefix over a recurrent-state"),
        "submit_embedding": (
            lambda: srv.submit_embedding(
                np.zeros((4, CFG.hidden_size), np.float32), 4),
            "submit_embedding over a recurrent-state"),
        # the hand-off, the host tier and the disk tier move blocks by id
        "read": (lambda: srv._read_arena_blocks([1, 2]), "moving KV blocks"),
        "write": (lambda: srv._write_arena_blocks([1], None, None),
                  "moving KV blocks"),
    }
    try:
        if what == "restore":
            # a snapshot of such a server cannot exist; one of another model
            # is refused by the model's name before anything is read
            from llm_sharding_tpu.runtime import server as server_mod

            with pytest.raises(NotImplementedError,
                               match="restore into a recurrent-state"):
                server_mod.refuse_kind_state(
                    CFG, "restore into", server_mod._SNAPSHOT_WHY)
            with pytest.raises(Exception):
                PipelineServer.restore(eng, {"format": 99})
        else:
            call, word = calls[what]
            with pytest.raises(NotImplementedError, match=word):
                call()
    finally:
        srv.close()


def test_the_refusals_name_the_model_through_the_one_helper():
    from llm_sharding_tpu.runtime.server import (
        kind_state_name, refuse_kind_state,
    )

    assert kind_state_name(CFG) == "a recurrent-state model (jamba)"
    with pytest.raises(NotImplementedError,
                       match="a recurrent-state model .jamba.: r"):
        refuse_kind_state(CFG, "x of", ("w", "r"))


def test_the_server_and_the_step_programs_do_not_name_the_model():
    """A model with a recurrent state is ONE code path: the state's shape
    comes from the configuration (``cfg.recurrent_shapes``)."""
    import inspect

    from llm_sharding_tpu.parallel import serve as serve_ops
    from llm_sharding_tpu.runtime import server as server_mod

    for mod in (serve_ops, server_mod):
        text = inspect.getsource(mod)
        assert not re.search(r"model_type\s*[!=]=\s*[\"']jamba", text)
        assert "mamba_num_heads" not in text and "mamba_d_inner" not in text


def test_the_step_programs_name_the_mixers_and_the_path_to_dt(
        params, monkeypatch):
    """The decode and the chunk program carry ``ssm_x`` — the word PR 45
    added to ``obs.stepline.SCOPES`` — beside the words of the layers they
    share with other models; ``serve_admit`` is never dispatched; on the XLA
    path the decode step's state update and the chunk's scan are loops under
    ``ssm``."""
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
    words = {"ssm_proj", "conv", "ssm", "ssm_x"}
    assert words <= set(SCOPES)
    for text in texts.values():
        paths = set(re.findall(r'loc\("([^"]+)"', text))
        found = {w for w in SCOPES
                 if any(re.search(rf"(^|/){w}(/|$)", p) for p in paths)}
        assert words | {"mlp", "attn", "qkv", "o_proj", "kv_write", "norm",
                        "state"} <= found
        # no rotary embedding, no experts
        assert not found & {"absorb", "rope", "router", "moe", "moe_latent"}
        assert any(re.search(r"(^|/)ssm/while", p) for p in paths)


def test_the_counters_and_the_metrics_rows(params, monkeypatch):
    """Rows holding a state and their bytes, the positions through the scan,
    real and padded (host arithmetic at dispatch), and the two one-hot gauges
    that name the paths."""
    from llm_sharding_tpu.models.config import jamba2_3b
    from llm_sharding_tpu.ops import ssm
    from llm_sharding_tpu.runtime.server import _update_load_gauges

    def gauges():
        _update_load_gauges()
        return (
            {b: metrics.RECURRENT_BACKEND.labels(backend=b).value
             for b in metrics.RECURRENT_BACKENDS},
            {p: metrics.RECURRENT_SCAN_PATH.labels(path=p).value
             for p in metrics.RECURRENT_SCAN_PATHS},
        )

    before = gauges()
    real0 = metrics.PREFILL_SCAN_POSITIONS.labels(kind="real").value
    pad0 = metrics.PREFILL_SCAN_POSITIONS.labels(kind="pad").value
    srv = engine(params).serve(paged_attn="xla", **PAGED)
    assert metrics.RECURRENT_ROW_BYTES.value == 5 * CFG.recurrent_row_bytes
    assert CFG.recurrent_row_bytes == 4 * (4 * 128 + 3 * 128)
    after = gauges()
    for was, now in zip(before, after):
        assert {k: now[k] - was[k] for k in now} == {
            k: float(k == "xla") for k in now}
    rng = np.random.default_rng(2)
    reqs = [srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 6)
            for n in (5, 12, 21)]
    srv.run_until_idle()
    recs = srv.stepline.snapshot()
    text = metrics.REGISTRY.prometheus_text()
    srv.close()
    assert gauges() == before
    assert all(len(r.tokens) == 6 for r in reqs)
    # prompts admit by bucket: 5 and 12 share one chunk of 16 x 2 rows, 21
    # takes two; each row's LAST token enters as a decode step: 4 + 11 + 20
    real = metrics.PREFILL_SCAN_POSITIONS.labels(kind="real").value - real0
    pad = metrics.PREFILL_SCAN_POSITIONS.labels(kind="pad").value - pad0
    assert (real, pad) == (35, 3 * 2 * 16 - 35)
    scanned = [r["scan_positions"] for r in recs if "scan_positions" in r]
    assert sum(s["real"] for s in scanned) == 35
    rows = [r["recurrent_rows"] for r in recs if "recurrent_rows" in r]
    assert rows and max(rows) == 2 and min(rows) >= 1
    for family in ("server_recurrent_rows_in_use", "server_recurrent_row_bytes",
                   'server_recurrent_backend{backend="xla"}',
                   'server_recurrent_scan_path{path="xla"}',
                   'server_prefill_scan_positions_total{kind="real"}'):
        assert family in text
    # on the chip the tiny mixer's 16 channels a sublane are no whole lane
    # tile; the published widths' 640 are — for the decode step and the scan
    monkeypatch.setattr(ssm.jax, "default_backend", lambda: "tpu")
    assert ssm.rows_backend("kernel", CFG) == "xla"
    assert ssm.rows_backend("kernel", jamba2_3b()) == "kernel"
    assert ssm.scan_path("kernel", jamba2_3b()) == "kernel"
    assert ssm.scan_path("interpret", CFG) == "interpret"
    assert ssm.mixer_step_path("kernel", jamba2_3b()) == "fused"
    assert ssm.mixer_step_path("kernel", CFG) == "split"
    assert 26 * jamba2_3b().recurrent_row_bytes == 26 * 389_120


def test_mamba_2_scans_in_block_form():
    from llm_sharding_tpu.models.config import tiny_nemotron_h
    from llm_sharding_tpu.ops import ssm

    assert ssm.scan_path("xla", tiny_nemotron_h()) == "block"


def test_the_shard_store_and_the_converter_carry_the_kinds(params, tmp_path):
    """The store keeps one block a layer whatever its kind and NO ``lm_head``
    (the head is tied), from the tree and, streaming, from the published
    tensor names (``utils/convert.JAMBA_NAMES``, the one table both read)."""
    import os

    from llm_sharding_tpu.utils import shard_store
    from test_jamba_vs_hf import hf_names

    for sub, save in (
        ("tree", lambda out: shard_store.save_shards(CFG, params, out)),
        ("names", lambda out: shard_store.save_shards_streaming(
            CFG, hf_names(CFG, params), out, dtype=jnp.float32)),
    ):
        out = str(tmp_path / sub)
        save(out)
        files = sorted(os.listdir(out))
        assert "lm_head.npz" not in files
        assert sum(f.startswith("block_") for f in files) == 6
        stage = shard_store.load_stage(
            out, 0, CFG.num_hidden_layers, dtype=np.float32)
        assert "lm_head" not in stage
        for kind, stack in params["layers"].items():
            for name, a in stack.items():
                assert np.array_equal(stage["layers"][kind][name], a), (
                    sub, kind, name)
        assert np.array_equal(stage["embed"], params["embed"])
