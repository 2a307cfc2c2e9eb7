"""OLMoE on the program's paths (``tiny_olmoe``-sized: 2 layers, hidden 64,
4 heads, 8 experts, 2 a token), against the ONE plain reference the repo has
for it — ``benchmark/blocks/olmoe.py``, through ``benchmark.blocks.load`` —
in float32:

- ``llama.forward`` and prefill-then-decode through the paged arena: LOGITS;
- served ids through ``engine.serve()`` equal ``runtime.generate`` and pass
  ``reference.score``; one stage equals a two-stage ring;
- negative controls that must read not correct: q/k norm dropped, top-k
  renormalised, one expert fewer, the router zeroed, a bf16 router;
- int8 experts round-trip; the counters.

The tolerance: program and reference are both float32 on the CPU and differ
only in the order of their sums (the program sums a token's k experts, the
reference a dense product over all E·F columns): 2e-5 on logits of unit
scale, where the mildest wrong model here (a bf16 router: rounded weights
of the kept experts, and a flipped top-k choice wherever two lie close) moves
a logit by 0.006 — 300 times the tolerance — and the others by 0.5 and more.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from benchmark import blocks, reference, weights
from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.cache import (
    POS_SENTINEL, init_cache, paged_arena_shape,
)
from llm_sharding_tpu.models.config import ModelConfig, tiny_olmoe
from llm_sharding_tpu.ops.quant import (
    QTensor, dequantize, quantize_layer_params, quantize_params,
)
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate

MODEL = dict(
    model_type="olmoe", vocab_size=256, hidden_size=64, intermediate_size=32,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    max_position_embeddings=512, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=False, rms_norm_eps=1e-5, rope_theta=10000.0,
    tie_word_embeddings=False, clip_qkv=None, eos_token_id=255,
)
CFG = ModelConfig.from_hf_config(MODEL)
BLOCK = blocks.load("olmoe")
TOL = 2e-5
S = 40
IDS = np.random.default_rng(0).integers(0, 255, (S,)).astype(np.int32)


@pytest.fixture(scope="module")
def params():
    """The block's seeded float32 weights, in the engine's layout — what the
    benchmark serves, so program and reference hold the same arrays."""
    return weights.make_params(BLOCK, MODEL, 5, "f32", jax.devices()[:1])


def ref_logits(params, ids, **wrong):
    tables = {t.name: params[t.name] for t in BLOCK.tables(MODEL)}
    h = reference.hidden_states(
        BLOCK, MODEL, lambda l: jax.tree.map(lambda a: a[l], params["layers"]),
        tables, [ids], **wrong,
    )[0][: len(ids)]
    with jax.default_matmul_precision("highest"):
        return np.asarray(BLOCK.logits(h, tables, **BLOCK.head_static(MODEL)))


def test_the_preset_is_the_models_config():
    assert tiny_olmoe().num_experts == 8 and tiny_olmoe().qk_norm
    assert CFG.model_type == "llama"  # a flag of the llama block, no new type
    theirs = jax.eval_shape(lambda: llama.init_layer_params(CFG, jax.random.key(0), 1))
    assert {k: v.shape[1:] for k, v in theirs.items()} == {
        l.name: l.shape for l in BLOCK.layer_leaves(MODEL)}


def test_forward_logits_equal_the_reference(params):
    cache = init_cache(CFG, 1, capacity=S, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = llama.forward(
            CFG, params, jnp.asarray(IDS)[None], cache, jnp.arange(S)[None])
    np.testing.assert_allclose(np.asarray(got[0]), ref_logits(params, IDS), atol=TOL)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_prefill_then_decode_through_the_paged_arena(params, backend):
    """A 24-token prompt prefilled as one chunk (with pad positions and a
    dead second row), then 16 tokens decoded one at a time over the pooled
    arena: the logits at every position equal the reference's full forward."""
    BS, T, B, P = 8, 8, 2, 24
    L = CFG.num_hidden_layers
    shape = paged_arena_shape(CFG, 1 + B * T, BS)
    k_arena = jnp.zeros(shape, jnp.float32)
    v_arena = jnp.zeros(shape, jnp.float32)
    table = jnp.asarray(1 + np.arange(B * T).reshape(B, T), jnp.int32)
    kv_pos = np.full((B, T * BS), POS_SENTINEL, np.int32)

    def step(tokens, positions, cols, live, prefill):
        nonlocal k_arena, v_arena
        kv_pos[0, cols[0]] = positions[0]
        h = llama.embed(params, jnp.asarray(tokens))
        h, k_arena, v_arena, _, _, stats = llama.forward_layers_paged(
            CFG, params["layers"], h, k_arena, v_arena, table,
            jnp.asarray(cols), jnp.asarray(kv_pos), jnp.asarray(positions),
            backend=backend, prefill=prefill, moe_live=jnp.asarray(live),
        )
        return np.asarray(llama.final_logits(CFG, params, h))[0], stats

    Sc = 32  # the chunk: 24 real positions, 8 pads; row 1 is dead
    tokens = np.zeros((B, Sc), np.int32)
    tokens[0, :P] = IDS[:P]
    positions = np.full((B, Sc), POS_SENTINEL, np.int32)
    positions[0, :P] = np.arange(P)
    cols = np.broadcast_to(np.arange(Sc, dtype=np.int32), (B, Sc)).copy()
    live = positions != POS_SENTINEL
    with jax.default_matmul_precision("highest"):
        got, stats = step(tokens, positions, cols, live, True)
        logits = [got[:P]]
        # pads and the dead row count nothing: P positions x k, every layer
        assert np.asarray(stats.expert_tokens).sum(axis=1).tolist() == [2 * P] * L
        for t in range(P, S):
            tok = np.asarray([[IDS[t]], [0]], np.int32)
            pos = np.asarray([[t], [0]], np.int32)
            got, stats = step(tok, pos, pos.copy(), np.asarray([[True], [False]]), False)
            assert np.asarray(stats.experts_read).tolist() == [2] * L
            logits.append(got[:1])
    np.testing.assert_allclose(
        np.concatenate(logits), ref_logits(params, IDS), atol=TOL)


WRONG = {
    "q/k norm dropped": dict(qk_norm=False),
    "top-k renormalised": dict(renorm=True),
    "one expert fewer": dict(top_k=1),
    "a bf16 router": dict(router_dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("what", list(WRONG))
def test_a_wrong_model_reads_not_correct(params, what):
    """Each of these is a model the program must NOT be: its logits lie far
    outside the tolerance the program is held to."""
    err = np.abs(ref_logits(params, IDS, **WRONG[what]) - ref_logits(params, IDS)).max()
    assert err > 100 * TOL, (what, err)


def served(params, prompts, new, stages=1, **kw):
    eng = PipelineEngine(
        CFG, params, num_stages=stages, devices=jax.devices()[:stages],
        cache_dtype=jnp.float32,
    )
    srv = eng.serve(capacity=128, batch_per_slot=2, kv_block_size=8,
                    kv_blocks=65, prefill_chunk=16, prefix_cache="hbm", **kw)
    reqs = [srv.submit(p, new) for p in prompts]
    srv.run_until_idle()
    recs = srv.stepline_snapshot(10_000)
    srv.close()
    return [list(r.tokens) for r in reqs], recs


PROMPTS = [np.random.default_rng(i).integers(0, 255, n).astype(np.int32)
           for i, n in enumerate((5, 40, 9))]
NEW = 12


@pytest.fixture(scope="module")
def served_one_stage(params):
    return served(params, PROMPTS, NEW)


def test_served_ids_equal_generate_and_pass_the_reference(params, served_one_stage):
    ids, _ = served_one_stage
    for p, got in zip(PROMPTS, ids):
        want = generate(CFG, params, jnp.asarray(p)[None], NEW,
                        cache_dtype=jnp.float32)
        assert got == np.asarray(want.tokens)[0, len(p):len(p) + NEW].tolist()
    get = lambda l: jax.tree.map(lambda a: a[l], params["layers"])
    tables = {t.name: params[t.name] for t in BLOCK.tables(MODEL)}
    samples = [(p, np.asarray(g)) for p, g in zip(PROMPTS, ids)]
    scored = reference.score(BLOCK, MODEL, get, tables, samples)
    assert reference.verdict(scored, BLOCK) and scored["margin_max"] < 1e-4, scored
    # the router zeroed in the PROGRAM's weights: served ids no longer pass
    layers = dict(params["layers"], router=jnp.zeros_like(params["layers"]["router"]))
    bad, _ = served(dict(params, layers=layers), PROMPTS, NEW)
    scored = reference.score(
        BLOCK, MODEL, get, tables, [(p, np.asarray(g)) for p, g in zip(PROMPTS, bad)])
    assert not reference.verdict(scored, BLOCK), scored


@pytest.mark.parametrize("stages", [1, 2])
def test_chunks_write_tiles_and_serve_what_the_rows_serve(
        params, served_one_stage, stages):
    """The chunk write in the expert model's chunk program, on one stage and
    on a ring of two (an inactive microstep's tiles go to block 0 of their
    layer): the 40-token prompt's chunks write whole blocks, and the served
    ids are those of the row-wise write."""
    from paged_arena import tiles_then_rows

    ids = tiles_then_rows(lambda: served(params, PROMPTS, NEW, stages)[0])
    assert ids == served_one_stage[0]


def test_one_stage_equals_a_ring_of_two(params, served_one_stage):
    ids, recs = served(params, PROMPTS, NEW, stages=2)
    assert ids == served_one_stage[0]
    assert sum(sum(r.get("expert_tokens", ())) for r in recs) == sum(
        sum(r.get("expert_tokens", ())) for r in served_one_stage[1])


def test_counters_count_live_rows_and_real_positions_only(served_one_stage):
    """Every position of a request is routed exactly once — its prompt in the
    prefill programs (pads to the bucket or chunk, and the free rows of the
    slot, nowhere), each generated token but the last in a decode step."""
    _, recs = served_one_stage
    k, L, E = 2, 2, 8
    routed = sum(len(p) + NEW - 1 for p in PROMPTS)
    assert sum(sum(r.get("expert_tokens", ())) for r in recs) == routed * k * L
    steps = [r for r in recs if r.get("expert_steps")]
    assert steps
    for r in steps:
        assert len(r["experts_read"]) == L and len(r["expert_tokens"]) == E
        assert r["expert_rows"] <= 2 * r["expert_steps"]  # batch_per_slot 2
        assert max(r["experts_read"]) <= k * r["expert_rows"]
        assert min(r["experts_read"]) >= k * r["expert_steps"]
    assert any(r["expert_rows"] == 1 and r["experts_read"] == [k] * L for r in steps)
    from llm_sharding_tpu.obs.metrics import REGISTRY

    fam = REGISTRY.get("server_moe_expert_tokens_total")
    assert sum(c.value for _, c in fam.series()) >= routed * k * L
    # (a ring's idle stage-layers read nothing and pull the mean under k)
    assert 0 < REGISTRY.get("server_moe_experts_read").value <= 2 * k


def test_a_decoding_step_counts_the_log_before_it_waits_for_the_next(params):
    """What a log carries beside its tokens is counted by the NEXT step,
    while the device works and before that step waits (the tokens alone lie
    between a log's landing and a reader's eyes): in steady decode the
    series run one log behind the tokens; an idle server's are whole."""
    from llm_sharding_tpu.obs.metrics import REGISTRY

    fam = REGISTRY.get("server_moe_expert_tokens_total")
    total = lambda: sum(c.value for _, c in fam.series())
    eng = PipelineEngine(CFG, params, num_stages=1, devices=jax.devices()[:1],
                         cache_dtype=jnp.float32)
    srv = eng.serve(capacity=128, batch_per_slot=2, kv_block_size=8,
                    kv_blocks=65, prefill_chunk=16, prefix_cache="hbm")
    k, L = 2, 2
    t0 = total()
    req = srv.submit(PROMPTS[0], NEW)
    while len(req.tokens) < 4:
        srv.step()
    assert srv._pending
    assert len(srv._parked_counts) == 1  # the log whose token just surfaced
    seen = total() - t0
    srv.step()  # one more token: the parked log is counted, the next parked
    assert len(srv._parked_counts) == 1 and total() - t0 == seen + k * L
    srv.run_until_idle()
    assert not srv._parked_counts
    assert total() - t0 == (len(PROMPTS[0]) + NEW - 1) * k * L
    recs = srv.stepline_snapshot(10_000)
    assert sum(sum(r.get("expert_tokens", ())) for r in recs) == total() - t0
    srv.close()


def test_int8_experts_round_trip(params):
    layers = quantize_layer_params(dict(params["layers"]))
    for name in ("we_gate", "we_up", "we_down", "wq"):
        q = layers[name]
        assert isinstance(q, QTensor) and q.q.dtype == jnp.int8
        w = np.asarray(params["layers"][name])
        step = np.asarray(q.scale)[:, None, :]
        assert np.abs(np.asarray(dequantize(q)) - w).max() <= 0.5001 * step.max()
        assert (np.abs(np.asarray(dequantize(q)) - w) <= 0.5001 * step).all()
    assert not isinstance(layers["router"], QTensor)  # multiplied out in f32
    assert not isinstance(layers["q_norm"], QTensor)
    # the int8 model serves, and the reference scores it over (q, scale)
    qparams = quantize_params(dict(params))
    ids, _ = served(qparams, PROMPTS[:1], 6)
    get = lambda l: jax.tree.map(lambda a: a[l], qparams["layers"])
    tables = {t.name: qparams[t.name] for t in BLOCK.tables(MODEL)}
    scored = reference.score(BLOCK, MODEL, get, tables, [(PROMPTS[0], np.asarray(ids[0]))])
    assert reference.verdict(scored, BLOCK), scored


@pytest.mark.parametrize("name", ["we_gate", "we_up", "we_down"])
def test_what_one_experts_outlier_costs_the_others(params, name):
    """The price of 2-D expert leaves. ``we_gate`` / ``we_up`` ``[H, E·F]``
    carry one scale per COLUMN, so an expert has its own and another's
    outlier costs it nothing. ``we_down`` ``[E·F, H]`` carries one scale per
    output channel, the absmax over the rows of ALL experts: one expert
    whose weights are ten times larger coarsens every other expert's codes
    about tenfold. Seeded Gaussian experts never show it; a trained
    checkpoint may (README, "Sparse experts"; a per-expert ``[E, H]`` scale
    comes with the expert mesh axis)."""
    E, F = CFG.num_experts, CFG.intermediate_size
    w = np.asarray(params["layers"][name][:1])  # one layer is enough
    rows = name == "we_down"
    loud = np.ones((E * F,), np.float32)
    loud[:F] = 10.0  # expert 0
    w_loud = w * (loud[None, :, None] if rows else loud[None, None, :])
    others = np.s_[:, F:, :] if rows else np.s_[:, :, F:]

    def err(arr):
        q = quantize_layer_params({name: jnp.asarray(arr)})[name]
        return np.abs(np.asarray(dequantize(q)) - arr)[others].mean()

    ratio = err(w_loud) / err(w)
    if rows:
        assert 5.0 < ratio < 12.0, ratio
    else:
        assert ratio == 1.0, ratio


@pytest.mark.parametrize("what", ["tensor_parallel", "cp"])
def test_tp_and_cp_refuse_experts_by_name(params, what):
    with pytest.raises(NotImplementedError, match="experts"):
        if what == "tensor_parallel":
            PipelineEngine(CFG, params, num_stages=1, tensor_parallel=2,
                           devices=jax.devices()[:2], cache_dtype=jnp.float32)
        else:
            PipelineEngine(
                CFG, params, num_stages=1, devices=jax.devices()[:1],
                cache_dtype=jnp.float32,
            ).serve(capacity=64, batch_per_slot=2, kv_block_size=8,
                    kv_blocks=33, prefill_chunk=16, prefix_cache="hbm", cp=2)
