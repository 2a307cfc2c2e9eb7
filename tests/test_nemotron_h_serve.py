"""``nemotron_h`` through the engine and the server on the CPU at tiny widths
(``tests/test_nemotron_h.py`` holds the model and its operations to the plain
reference): a recurrent state beside the paged arena through
``PipelineEngine.serve()`` — prefill in chunks, then decode through the state,
against the reference's FULL forward; two rows of unlike prompt lengths in one
slot; a row reused after a finished request starting from zero; the counters;
and what a recurrent state breaks, each refused by name through the ONE
helper a windowed model's refusals go through (a prefix-cache hit is not
offered; snapshots and ``restore``, prefix handles, the embeddings entry, the
hand-off's block moves, speculation, tp and cp, a quantized arena, a
non-paged server, a ring of unlike stages)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import nemotron_h
from llm_sharding_tpu.models.config import tiny_nemotron_h
from llm_sharding_tpu.obs import metrics
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.server import PipelineServer

from test_nemotron_h import CFG, KEYS, params, reference_logits  # noqa: F401

PAGED = dict(capacity=128, batch_per_slot=2, kv_block_size=4, kv_blocks=80,
             prefill_chunk=16)


def engine(params, cfg=CFG, **kw):
    kw.setdefault("num_stages", 1)
    n = kw["num_stages"]
    return PipelineEngine(cfg, params, cache_dtype=jnp.float32,
                          devices=jax.devices()[:n], **kw)


def margins(params, req):
    """Teacher-forced: the reference's best logit minus its logit of the
    served token, at every output position, over the whole sequence."""
    ids = np.concatenate([np.asarray(req.prompt), np.asarray(req.tokens)])
    logits = reference_logits(params, ids.astype(np.int32))
    n = len(req.prompt)
    rows = logits[n - 1:n - 1 + len(req.tokens)]
    served = np.asarray(req.tokens)
    return rows.max(-1) - rows[np.arange(len(served)), served]


def test_prefill_then_decode_through_the_state_is_the_references_forward(
        params, monkeypatch):
    """The normal serve path, kernels interpreted: prompts under, at and over
    a chunk (1, 2 and 3 chunks), two rows of unlike lengths sharing a slot,
    replies of 24 tokens decoded through the state: every served token is the
    reference's argmax over the WHOLE sequence (float32: margin under 1e-4)."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    seen_before = set(metrics._SHAPE_KEYS_SEEN)  # other files' of this worker
    srv = engine(params).serve(prefix_cache="hbm", **PAGED)
    assert srv.attn_impl == "interpret" and srv.recurrent and not srv.windowed
    # a hit cannot slice a recurrent state: accepted and switched off
    assert srv.prefix_cache == "off" and srv._radix is None
    # the arena holds the ONE attention layer; the state three mixers, by row
    assert srv.state.k.shape == (1, 1, 80, 2, 4, 16)
    assert srv.state.recurrent["ssm"].shape == (1, 3, 2, 8, 16, 16)
    assert srv.state.recurrent["conv"].shape == (1, 3, 2, 3, 192)
    assert srv.state.recurrent["ssm"].dtype == jnp.float32
    assert srv.state.k_swa is None
    rng = np.random.default_rng(3)
    reqs = [srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 24)
            for n in (5, 37, 16, 19)]
    srv.run_until_idle()
    for r in reqs:
        assert len(r.tokens) == 24
        assert margins(params, r).max() < 1e-4
    # every prompt admitted chunk by chunk, in whole chunks: ONE program
    assert [srv._bucket(n) for n in (1, 5, 16, 17, 37)] == [16, 16, 16, 32, 64]
    chunks = {key for prog, key in metrics._SHAPE_KEYS_SEEN
              if prog == "serve_prefill_chunk" and key[2] == 128}
    assert {key[3] for key in chunks} == {16}
    assert not any(prog == "serve_admit" and key[2] == 128
                   for prog, key in metrics._SHAPE_KEYS_SEEN - seen_before)
    srv.close()


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
    assert margins(params, b).max() < 1e-4


def test_a_ring_of_alike_stages_carries_the_state(params):
    """Two stages of ``ME*`` each: a state and an arena a stage."""
    cfg = tiny_nemotron_h(hybrid_override_pattern="ME*ME*")
    keys = dict(KEYS, hybrid_override_pattern="ME*ME*")
    p = nemotron_h.init_params(cfg, jax.random.key(5), jnp.float32)
    srv = engine(p, cfg, num_stages=2).serve(paged_attn="xla", **PAGED)
    assert srv.state.recurrent["ssm"].shape[:3] == (2, 1, 4)
    assert srv.state.k.shape[:2] == (2, 1)
    prompt = np.random.default_rng(6).integers(0, 250, size=19).astype(np.int32)
    req = srv.submit(prompt, 16)
    srv.run_until_idle()
    srv.close()
    ids = np.concatenate([prompt, np.asarray(req.tokens)]).astype(np.int32)
    logits = reference_logits(p, ids, keys=keys)
    rows = logits[18:18 + 16]
    assert (rows.max(-1) - rows[np.arange(16), np.asarray(req.tokens)]).max() < 1e-4


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
    ({"cp": 2}, "cp over a model with sparse experts"),
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
        "restore": (lambda: PipelineServer.restore(eng, {"format": 99}),
                    None),
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
    call, word = calls[what]
    try:
        if what == "restore":
            # a snapshot of such a server cannot exist; one of another model
            # is refused by the model's name before anything is read
            from llm_sharding_tpu.runtime import server as server_mod

            with pytest.raises(NotImplementedError,
                               match="restore into a recurrent-state"):
                server_mod.refuse_kind_state(
                    CFG, "restore into", server_mod._SNAPSHOT_WHY)
        else:
            with pytest.raises(NotImplementedError, match=word):
                call()
    finally:
        srv.close()


def test_the_refusals_name_each_kind_of_model_through_one_helper():
    from llm_sharding_tpu.models.config import tiny_llama, tiny_mimo_v2
    from llm_sharding_tpu.runtime.server import (
        kind_state_name, refuse_kind_state,
    )

    assert kind_state_name(tiny_llama()) is None
    refuse_kind_state(tiny_llama(), "anything over", "never raised")
    with pytest.raises(NotImplementedError, match="a windowed model .mimo_v2.: w"):
        refuse_kind_state(tiny_mimo_v2(), "x of", ("w", "r"))
    with pytest.raises(NotImplementedError,
                       match="a recurrent-state model .nemotron_h.: r"):
        refuse_kind_state(CFG, "x of", ("w", "r"))
    with pytest.raises(NotImplementedError, match="nemotron_h.: both"):
        refuse_kind_state(CFG, "x of", "both")


def test_the_step_programs_name_the_mixers_and_the_latent_space(
        params, monkeypatch):
    """The decode and the chunk program carry the four words PR 43 added to
    ``obs.stepline.SCOPES`` (what the trace's readers sum) beside the words
    of the layers they share with other models; ``serve_admit`` is never
    dispatched; the decode step's state update is a loop under ``ssm``."""
    import re

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
    new = {"ssm_proj", "conv", "ssm", "moe_latent"}
    assert new <= set(SCOPES)
    for text in texts.values():
        paths = set(re.findall(r'loc\("([^"]+)"', text))
        found = {w for w in SCOPES
                 if any(re.search(rf"(^|/){w}(/|$)", p) for p in paths)}
        assert new | {"router", "moe", "mlp", "attn", "qkv", "o_proj",
                      "kv_write", "norm", "state"} <= found
        assert "absorb" not in found and "rope" not in found  # no rotary
    decode = set(re.findall(r'loc\("([^"]+)"', texts["serve_chunk"]))
    assert any(re.search(r"(^|/)ssm/while", p) for p in decode)


def test_the_counters(params):
    """Rows holding a state and their bytes, and the positions through the
    scan, real and padded: host arithmetic at dispatch."""
    real0 = metrics.PREFILL_SCAN_POSITIONS.labels(kind="real").value
    pad0 = metrics.PREFILL_SCAN_POSITIONS.labels(kind="pad").value
    srv = engine(params).serve(paged_attn="xla", **PAGED)
    assert metrics.RECURRENT_ROW_BYTES.value == 3 * CFG.recurrent_row_bytes
    assert CFG.recurrent_row_bytes == 4 * (8 * 16 * 16 + 3 * 192)
    rng = np.random.default_rng(2)
    reqs = [srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 6)
            for n in (5, 12, 21)]
    srv.run_until_idle()
    recs = srv.stepline.snapshot()
    srv.close()
    # prompts admit by bucket: 5 and 12 share one chunk of 16 x 2 rows, 21
    # takes two; each row's LAST token enters as a decode step: 4 + 11 + 20
    real = metrics.PREFILL_SCAN_POSITIONS.labels(kind="real").value - real0
    pad = metrics.PREFILL_SCAN_POSITIONS.labels(kind="pad").value - pad0
    assert (real, pad) == (35, 3 * 2 * 16 - 35)
    scanned = [r["scan_positions"] for r in recs if "scan_positions" in r]
    assert sum(s["real"] for s in scanned) == 35
    assert sum(s["pad"] for s in scanned) == 61
    rows = [r["recurrent_rows"] for r in recs if "recurrent_rows" in r]
    assert rows and max(rows) == 2 and min(rows) >= 1
    text = metrics.REGISTRY.prometheus_text()
    for family in ("server_recurrent_rows_in_use", "server_recurrent_row_bytes",
                   'server_prefill_scan_positions_total{kind="real"}'):
        assert family in text
    # a model without recurrent layers records none of it
    from llm_sharding_tpu.models import llama
    from llm_sharding_tpu.models.config import tiny_llama

    cfg = tiny_llama()
    p = llama.init_params(cfg, jax.random.key(0), jnp.float32)
    plain = engine(p, cfg).serve(**dict(PAGED, paged_attn="xla"))
    assert not plain.recurrent and plain.state.recurrent is None
    r = plain.submit(np.arange(5, dtype=np.int32), 3)
    plain.run_until_idle()
    assert all("recurrent_rows" not in s and "scan_positions" not in s
               for s in plain.stepline.snapshot())
    assert len(r.tokens) == 3
    plain.close()


@pytest.mark.parametrize("forced,want", [("", "xla"), ("interpret", "interpret")])
def test_metrics_name_the_path_that_advances_the_state(
        params, monkeypatch, forced, want):
    """``server_recurrent_backend`` is one-hot for the path the decode step's
    state update takes (``ops/ssm.rows_backend``: the server's attention
    backend at the mixer's shapes), beside ``server_attn_backend``; a server
    of a model without recurrent layers adds to none of its labels; and a
    shape the kernel cannot tile falls back to XLA by name."""
    from llm_sharding_tpu.ops import ssm
    from llm_sharding_tpu.runtime.server import _update_load_gauges

    def gauge():
        _update_load_gauges()
        return {b: metrics.RECURRENT_BACKEND.labels(backend=b).value
                for b in metrics.RECURRENT_BACKENDS}

    before = gauge()
    monkeypatch.setenv("PAGED_FORCE_KERNEL", forced)
    srv = engine(params).serve(**PAGED)
    assert srv.recurrent_backend == want == srv.attn_impl
    after = gauge()
    assert {b: after[b] - before[b] for b in after} == {
        b: float(b == want) for b in after}
    assert f'server_recurrent_backend{{backend="{want}"}}' in (
        metrics.REGISTRY.prometheus_text())
    r = srv.submit(np.arange(5, dtype=np.int32), 4)
    srv.run_until_idle()
    assert len(r.tokens) == 4
    srv.close()
    assert gauge() == before
    # on the chip the tiny mixer (a state of 16) is no whole lane tile
    monkeypatch.setattr(ssm.jax, "default_backend", lambda: "tpu")
    from llm_sharding_tpu.models.config import nemotron3_super_120b_a12b

    assert ssm.rows_backend("kernel", CFG) == "xla"
    assert ssm.rows_backend("kernel", nemotron3_super_120b_a12b()) == "kernel"
    assert ssm.rows_backend("interpret", CFG) == "interpret"


def test_the_shard_store_and_the_converter_carry_the_kinds(params, tmp_path):
    """The store keeps one block a layer whatever its kind; the converter
    maps the published names (``backbone.layers.N.mixer.*``) onto the kinds'
    stacks and reads only the held experts."""
    from llm_sharding_tpu.models.config import ModelConfig
    from llm_sharding_tpu.utils import convert, shard_store

    shard_store.save_shards(CFG, params, str(tmp_path))
    stage = shard_store.load_stage(
        str(tmp_path), 0, CFG.num_hidden_layers, dtype=np.float32)
    for kind, stack in params["layers"].items():
        for name, a in stack.items():
            assert np.array_equal(stage["layers"][kind][name], a), (kind, name)
    # a published-style checkpoint of a chip that holds experts 4..7 of 8
    cfg = ModelConfig.from_hf_config(dict(
        KEYS, n_routed_experts=4, n_routed_experts_total=8, ep_rank=1))
    F, rng, names = cfg.moe_intermediate_size, np.random.default_rng(0), {}

    def put(name, *shape):
        names[name] = rng.standard_normal(shape).astype(np.float32)

    H, Hl = cfg.hidden_size, cfg.moe_latent_size
    for i, kind in enumerate(cfg.layer_kinds):
        pre = f"backbone.layers.{i}."
        put(pre + "norm.weight", H)
        if kind == "mamba":
            put(pre + "mixer.in_proj.weight",
                cfg.ssm_inner + cfg.conv_dim + cfg.mamba_num_heads, H)
            put(pre + "mixer.conv1d.weight", cfg.conv_dim, 1, cfg.conv_kernel)
            put(pre + "mixer.conv1d.bias", cfg.conv_dim)
            for n in ("dt_bias", "A_log", "D"):
                put(pre + "mixer." + n, cfg.mamba_num_heads)
            put(pre + "mixer.norm.weight", cfg.ssm_inner)
            put(pre + "mixer.out_proj.weight", H, cfg.ssm_inner)
        elif kind == "attn":
            D = cfg.head_dim_
            put(pre + "mixer.q_proj.weight", cfg.num_attention_heads * D, H)
            put(pre + "mixer.k_proj.weight", cfg.num_key_value_heads * D, H)
            put(pre + "mixer.v_proj.weight", cfg.num_key_value_heads * D, H)
            put(pre + "mixer.o_proj.weight", H, cfg.num_attention_heads * D)
        else:
            put(pre + "mixer.gate.weight", 8, H)
            put(pre + "mixer.gate.e_score_correction_bias", 8)
            put(pre + "mixer.fc1_latent_proj.weight", Hl, H)
            put(pre + "mixer.fc2_latent_proj.weight", H, Hl)
            for e in range(8):
                put(pre + f"mixer.experts.{e}.up_proj.weight", F, Hl)
                put(pre + f"mixer.experts.{e}.down_proj.weight", Hl, F)
            put(pre + "mixer.shared_experts.up_proj.weight",
                cfg.moe_shared_intermediate_size, H)
            put(pre + "mixer.shared_experts.down_proj.weight",
                H, cfg.moe_shared_intermediate_size)
    put("backbone.embeddings.weight", 300, H)
    put("backbone.norm_f.weight", H)
    put("lm_head.weight", 300, H)
    read = []

    def get(name):
        read.append(name)
        return names[name]

    got = convert.params_from_hf(cfg, get, jnp.float32)
    assert got["embed"].shape == (256, H) and got["lm_head"].shape == (H, 256)
    moe = got["layers"]["moe"]
    assert moe["we_up"].shape == (2, Hl, 4 * F)
    assert np.array_equal(
        moe["we_up"][0][:, :F],
        names["backbone.layers.1.mixer.experts.4.up_proj.weight"].T)
    assert np.array_equal(
        moe["we_down"][1][3 * F:],
        names["backbone.layers.4.mixer.experts.7.down_proj.weight"].T)
    assert not any(".experts.0." in n or ".experts.3." in n for n in read)
    mamba = got["layers"]["mamba"]
    assert mamba["conv_w"].shape == (3, cfg.conv_kernel, cfg.conv_dim)
    assert np.array_equal(
        mamba["conv_w"][1],
        names["backbone.layers.2.mixer.conv1d.weight"][:, 0, :].T)
    assert mamba["A_log"].dtype == jnp.float32
    assert got["layers"]["attn"]["wq"].shape == (1, H, 64)
