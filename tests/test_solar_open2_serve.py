"""``solar_open2`` through the engine and the server on the CPU at tiny widths
(``tests/test_solar_open2.py`` holds the model and its operations to the plain
reference): a KDA matrix state beside a per-head K/V arena that only every
fourth layer writes, through ``PipelineEngine.serve()``: prefill in chunks,
then decode through the arena and the recurrent rows, against the reference's
FULL forward in LOGITS, at prompts shorter than, equal to and longer than a
chunk, two requests live in one slot, on the interpreted kernels and in XLA;
the STATE a served request leaves against the full forward's, which a bf16
state and a ``β`` not doubled each fail; a row reused after a finished request
starting from zero; a masked layer leaving state and tail bit for bit; a ring
of alike stages; the counters, the gauges and the ``/metrics`` rows; the words
of the step programs; and what a recurrent state breaks, each refused by name
through the ONE helper the other recurrent families' refusals go through —
with no branch on the model's name in ``runtime/server.py`` or
``parallel/serve.py``."""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import solar_open2 as so
from llm_sharding_tpu.models.config import (
    tiny_solar_open2, tiny_solar_open2_keys,
)
from llm_sharding_tpu.obs import metrics
from llm_sharding_tpu.ops import kda
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.server import PipelineServer

from test_solar_open2 import CFG, KEYS, params, reference_logits  # noqa: F401

PAGED = dict(capacity=128, batch_per_slot=2, kv_block_size=4, kv_blocks=80,
             prefill_chunk=16)
# 8 KDA heads of 128 x 128: the shape the decode kernel tiles (interpreted here)
WIDE = dict(linear_attn_config=dict(
    short_conv_kernel_size=4, head_dim=128, num_heads=8, num_kv_heads=None))
TOL = 3e-4


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


def state_gap(params, cfg, srv, req, row=0):
    """The largest difference, relative to the largest value, between the
    recurrent state row ``row`` holds after ``req`` and the state the full
    forward leaves over everything the request FED the model: its prompt and
    every served token but the last (sampled, never fed)."""
    fed = np.concatenate([np.asarray(req.prompt), np.asarray(req.tokens)[:-1]])
    with jax.default_matmul_precision("highest"):
        _, want = so.forward_full(cfg, params, jnp.asarray([fed]))
    gaps = []
    for name in ("kda", "conv"):
        got = np.asarray(srv.state.recurrent[name])[0, :, row]
        w = np.asarray(want[name])[:, 0]
        gaps.append(np.abs(got - w).max() / np.abs(w).max())
    return max(gaps)


def test_prefill_then_decode_through_the_state_is_the_references_forward(
        monkeypatch):
    """The normal serve path, kernels interpreted (both paged kernels, the
    decode step's write kernel, the expert kernel and the KDA decode kernel at
    8 heads of 128 x 128):
    prompts under, at and over a chunk (1, 2 and 3 chunks), two rows of unlike
    lengths sharing a slot, replies of 20 tokens decoded through the state:
    every served token's reference logit is the reference's best over the
    WHOLE sequence within 3e-4."""
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    cfg, keys = tiny_solar_open2(**WIDE), tiny_solar_open2_keys(**WIDE)
    p = so.init_params(cfg, jax.random.key(7), jnp.float32)
    srv = engine(p, cfg).serve(prefix_cache="hbm", **PAGED)
    assert srv.attn_impl == "interpret" and srv.recurrent and not srv.windowed
    assert srv.recurrent_backend == "interpret"
    assert srv.recurrent_scan_path == "block"
    assert srv.recurrent_mixer_step is None
    # a hit cannot slice a recurrent state: accepted and switched off
    assert srv.prefix_cache == "off" and srv._radix is None
    # the arena holds the TWO attention layers of eight, 2 key/value heads of
    # 16; the state six mixers, by row
    assert srv.state.k.shape == (1, 2, 80, 2, 4, 16)
    assert srv.state.v.shape == (1, 2, 80, 2, 4, 16)
    assert srv.state.recurrent["kda"].shape == (1, 6, 2, 8, 128, 128)
    assert srv.state.recurrent["conv"].shape == (1, 6, 2, 3, 3072)
    assert srv.state.recurrent["kda"].dtype == jnp.float32
    assert srv.state.k_swa is None and srv.state.idx is None
    rng = np.random.default_rng(3)
    reqs = [srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 20)
            for n in (5, 37, 16, 19)]
    srv.run_until_idle()
    for r in reqs:
        assert len(r.tokens) == 20
        assert served_logit_gaps(p, r, keys).max() < TOL
    # every prompt admitted chunk by chunk, in whole chunks: ONE program
    assert [srv._bucket(n) for n in (1, 5, 16, 17, 37)] == [16, 16, 16, 32, 64]
    chunks = {key for prog, key in metrics._SHAPE_KEYS_SEEN
              if prog == "serve_prefill_chunk" and key[2] == 128}
    assert {key[3] for key in chunks} == {16}
    srv.close()


def test_the_xla_path_serves_the_same_logits_and_leaves_the_same_state(params):
    """... and the state a request leaves in its row — through two prefill
    chunks and 12 decode steps — is the full forward's over what it fed."""
    kw = dict(PAGED, batch_per_slot=1)
    srv = engine(params).serve(paged_attn="xla", **kw)
    assert srv.recurrent_backend == "xla"
    assert srv.recurrent_scan_path == "block"
    rng = np.random.default_rng(4)
    for n in (16, 33):
        req = srv.submit(rng.integers(0, 250, size=n).astype(np.int32), 12)
        srv.run_until_idle()
        assert served_logit_gaps(params, req).max() < TOL
        assert state_gap(params, CFG, srv, req) < 1e-4
    srv.close()


@pytest.mark.parametrize("wrong", ["bf16_state", "beta_01"])
def test_a_wrong_program_fails_that_test(params, monkeypatch, wrong):
    """The same server with the state rounded to bfloat16 after every update
    (a chunk's and a step's), or with ``β`` not doubled (in (0, 1)): the state
    it leaves is not the full forward's."""
    if wrong == "bf16_state":
        def rounded(fn, at):
            def call(*a, **kw):
                out = list(fn(*a, **kw))
                out[at] = out[at].astype(jnp.bfloat16).astype(jnp.float32)
                return tuple(out)
            return call

        monkeypatch.setattr(kda, "kda_chunk", rounded(kda.kda_chunk, 1))
        monkeypatch.setattr(kda, "kda_step_rows", rounded(kda.kda_step_rows, 1))
    else:
        real = so._mixer_in

        def halved(cfg, p, h, tail, live):
            q, k, v, g, beta, z, tail = real(cfg, p, h, tail, live)
            return q, k, v, g, 0.5 * beta, z, tail

        monkeypatch.setattr(so, "_mixer_in", halved)
    # (a shape of its own, so that no cached program of another model runs)
    kw = dict(PAGED, batch_per_slot=1,
              capacity={"bf16_state": 96, "beta_01": 112}[wrong])
    srv = engine(params).serve(paged_attn="xla", **kw)
    prompt = np.random.default_rng(4).integers(0, 250, size=33).astype(np.int32)
    req = srv.submit(prompt, 12)
    srv.run_until_idle()
    monkeypatch.undo()  # the full forward below is the sound one
    gap = state_gap(params, CFG, srv, req)
    srv.close()
    assert gap > 1e-3, gap
    if wrong == "beta_01":
        assert served_logit_gaps(params, req).max() > 10 * TOL


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
    left = np.asarray(srv.state.recurrent["kda"])
    assert np.abs(left).max() > 1e-3  # the finished request's state stays
    b = srv.submit(second, 12)
    srv.run_until_idle()
    srv.close()
    fresh = engine(params).serve(paged_attn="xla", **kw)
    c = fresh.submit(second, 12)
    fresh.run_until_idle()
    fresh.close()
    assert list(b.tokens) == list(c.tokens) and len(a.tokens) == 12
    assert served_logit_gaps(params, b).max() < TOL


def test_a_ring_of_alike_stages_carries_the_state(params):
    """Two stages of one period (``GKKK``) each: a state and an arena a
    stage."""
    srv = engine(params, num_stages=2).serve(paged_attn="xla", **PAGED)
    assert srv.state.recurrent["kda"].shape[:3] == (2, 3, 4)
    assert srv.state.k.shape[:2] == (2, 1)
    prompt = np.random.default_rng(6).integers(0, 250, size=19).astype(np.int32)
    req = srv.submit(prompt, 16)
    srv.run_until_idle()
    srv.close()
    assert served_logit_gaps(params, req).max() < TOL


def test_a_ring_of_unlike_stages_is_refused():
    # (``GKKKKGKK``: the first stage holds one attention layer of four, the
    # second its attention layer in another place)
    cfg = tiny_solar_open2(gqa_layers=[0, 5])
    p = so.init_params(cfg, jax.random.key(5), jnp.float32)
    with pytest.raises((ValueError, NotImplementedError), match="same sequence"):
        engine(p, cfg, num_stages=2).serve(paged_attn="xla", **PAGED)


def test_a_masked_layer_leaves_state_and_tail_bit_for_bit(params):
    """A decode step and a prefill chunk through ``forward_layers_paged`` with
    ONE KDA layer masked (a padded slot of a ragged stage): that layer's state
    and conv tail come back bit for bit, the other mixers' advance, and the
    hidden state passes the masked layer unchanged."""
    L = {k: jax.tree.leaves(v)[0].shape[0] for k, v in params["layers"].items()}
    B, T, BS = 2, 4, 4
    rng = jax.random.split(jax.random.key(60), 4)
    rec0 = {
        "kda": jax.random.normal(rng[0], (L["kda"], B, 4, 16, 16)),
        "conv": jax.random.normal(rng[1], (L["kda"], B, 3, 192)),
    }
    k_arena = jnp.zeros((L["gqa"], 1 + B * T, 2, BS, 16))
    table = 1 + jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    kv_pos = jnp.broadcast_to(jnp.arange(T * BS, dtype=jnp.int32), (B, T * BS))
    masked = 2  # slot 2 = the second KDA layer (kinds: G K K K G K K K)
    mask = jnp.ones((8,), bool).at[masked].set(False)
    for S, prefill in ((1, False), (8, True)):
        h = jax.random.normal(rng[2], (B, S, CFG.hidden_size))
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        rec = dict(rec0, row0=jnp.int32(0), fresh=jnp.bool_(False))
        out = so.forward_layers_paged(
            CFG, params["layers"], h, (k_arena, rec), k_arena, table, pos,
            kv_pos, pos, mask, backend="xla", prefill=prefill,
            moe_live=jnp.ones((B, S), bool),
        )
        got = out[1][1]
        # the stage's slots are its kinds' stacks end to end (gqa: 0-1, kda:
        # 2-7), so slot 2 is layer 0 of the KDA stack
        for name in ("kda", "conv"):
            assert bool(jnp.all(got[name][0] == rec0[name][0])), name
            for l in range(1, L["kda"]):
                assert not bool(jnp.all(got[name][l] == rec0[name][l]))
        # the masked layer read nothing of the experts either
        assert int(out[-1].experts_read[masked]) == 0
        assert int(out[-1].experts_read[masked + 1]) > 0


@pytest.mark.parametrize("kw, word", [
    ({"prefill_chunk": None}, "chunk by chunk"),
    ({"kv_block_size": None, "kv_blocks": None},
     "paged arena beside its recurrent state"),
    ({"kv_dtype": "int8"}, "beside a recurrent state"),
    ({"speculate": 2}, "roll the state back"),
    ({"snapshot_every_s": 1.0, "snapshot_path": "/tmp/x"}, "snapshots of"),
    ({"cp": 2}, "cp / tp over|cp over a model with sparse experts"),
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

    assert kind_state_name(CFG) == "a recurrent-state model (solar_open2)"
    with pytest.raises(NotImplementedError,
                       match="a recurrent-state model .solar_open2.: r"):
        refuse_kind_state(CFG, "x of", ("w", "r"))


def test_the_server_and_the_step_programs_do_not_name_the_model():
    """A model with a recurrent state is ONE code path: the state's shape
    comes from the configuration (``cfg.recurrent_shapes``), the layers that
    keep one from the kinds (``RECURRENT_KINDS`` / ``ARENA_KINDS``)."""
    import inspect

    from llm_sharding_tpu.parallel import serve as serve_ops
    from llm_sharding_tpu.runtime import server as server_mod

    for mod in (serve_ops, server_mod):
        text = inspect.getsource(mod)
        assert not re.search(r"model_type\s*[!=]=\s*[\"']bailing", text)
        assert '"kda"' not in text and "gqa_layers" not in text


def test_the_step_programs_name_the_mixer_and_its_projections(
        params, monkeypatch):
    """The decode and the chunk program carry ``kda_proj`` and ``kda`` — the
    words PR 53 added to ``obs.stepline.SCOPES`` — beside ``conv`` and the
    words of the layers they share with other models (attention's ``qkv`` /
    ``attn`` / ``o_proj``, the experts' ``router`` / ``moe``) and NO rotary
    embedding; ``serve_admit`` is never
    dispatched; on the XLA path the decode step's state update is a loop
    under ``kda``."""
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
    words = {"kda_proj", "kda", "conv"}
    assert words <= set(SCOPES)
    for name, text in texts.items():
        paths = set(re.findall(r'loc\("([^"]+)"', text))
        found = {w for w in SCOPES
                 if any(re.search(rf"(^|/){w}(/|$)", p) for p in paths)}
        assert words | {"mlp", "attn", "qkv", "o_proj", "kv_write", "norm",
                        "state", "router", "moe"} <= found
        # no positions, no Mamba mixer, no latent attention or experts, no
        # indexer
        assert not found & {"rope", "absorb", "ssm", "ssm_proj", "ssm_x",
                            "moe_latent", "indexer", "select"}
        if name == "serve_chunk":
            assert any(re.search(r"(^|/)kda/while", p) for p in paths)


def test_the_counters_and_the_metrics_rows(params, monkeypatch):
    """Rows holding a state and their bytes, the positions through the chunk
    form, real and padded (host arithmetic at dispatch), a K/V entry's bytes,
    the experts' counters, and the two one-hot gauges that name the
    paths — the recurrent family that exists, reading right for the third
    shape."""
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
    assert metrics.RECURRENT_ROW_BYTES.value == 6 * CFG.recurrent_row_bytes
    # 2 heads x (16 + 16) a token and attention layer, float32 here
    assert metrics.KV_ENTRY_BYTES.value == 2 * 32 * 4
    after = gauges()
    for was, now, want in zip(before, after, ("xla", "block")):
        assert {k: now[k] - was[k] for k in now} == {
            k: float(k == want) for k in now}
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
    # the experts' counters as a share-holding model's: a slot a layer, every
    # layer routing; pairs over ALL 8 experts
    routed = [r for r in recs if r.get("expert_steps")]
    assert routed and all(len(r["experts_read"]) == 8 for r in routed)
    assert all(min(r["experts_read"]) > 0 for r in routed)
    assert all(len(r["expert_tokens"]) == 8 for r in routed)
    for family in ("server_recurrent_rows_in_use", "server_recurrent_row_bytes",
                   'server_recurrent_backend{backend="xla"}',
                   'server_recurrent_scan_path{path="block"}',
                   'server_prefill_scan_positions_total{kind="real"}',
                   "server_kv_entry_bytes"):
        assert family in text
    # on the chip the tiny state's 16 x 16 a head is no whole tile; the
    # published widths' 64 heads of 128 x 128 are
    monkeypatch.setattr(ssm.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("PAGED_FORCE_KERNEL", raising=False)
    assert ssm.rows_backend("auto", CFG) == "xla"
    big = tiny_solar_open2(linear_attn_config=dict(
        short_conv_kernel_size=4, head_dim=128, num_heads=64,
        num_kv_heads=None))
    assert ssm.rows_backend("auto", big) == "kernel"
    assert ssm.scan_path("auto", big) == "block"
