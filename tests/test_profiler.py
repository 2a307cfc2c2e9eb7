"""Profiler tests: fit machinery, reports, memory accounting, cold-start
(≙ the reference's NodeProfiler products, SURVEY.md §5 tracing/profiling)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.profiler.profiler import (
    ColdStartReport,
    Profiler,
    fit_latency_models,
    kv_cache_bytes_per_layer,
    layer_param_bytes,
    max_layers_fit,
    profile_cold_start,
)

CFG = tiny_llama()


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def test_fit_recovers_known_models():
    x = np.array([8, 16, 32, 64, 128, 256, 512], np.float64)
    y_lin = 0.003 * x + 0.5
    fits = fit_latency_models(x, y_lin)
    a, b = fits["linear"].coeffs
    assert abs(a - 0.003) < 1e-9 and abs(b - 0.5) < 1e-6
    assert fits["linear"].r2 > 0.999999

    y_quad = 2e-5 * x**2 + 0.001 * x + 0.2
    fq = fit_latency_models(x, y_quad)["quadratic"]
    aq, bq, cq = fq.coeffs
    assert abs(aq - 2e-5) < 1e-9 and abs(bq - 0.001) < 1e-6
    assert fq.rmse < 1e-9


def test_prefill_report(params):
    prof = Profiler(CFG, params, dtype=jnp.float32)
    rep = prof.profile_prefill(lengths=(8, 16, 32), repeats=2)
    assert rep.lengths == (8, 16, 32)
    assert all(t > 0 for t in rep.latencies_s)
    assert rep.capability_c_k > 0
    assert set(rep.fits) == {"linear", "quadratic"}
    assert rep.num_layers_measured == CFG.num_hidden_layers


def test_prefill_respects_max_position(params):
    prof = Profiler(CFG, params, dtype=jnp.float32)
    rep = prof.profile_prefill(lengths=(8, 16, 4096), repeats=1)
    assert 4096 not in rep.lengths  # ≙ node_profiler.py:352 guard


def test_partial_load_normalization(params):
    """Capability from a 2-layer slice is normalized to full-model units
    (≙ layer_num/loaded scaling, node_profiler.py:377)."""
    sub = {
        "layers": jax.tree.map(lambda a: a[:2], params["layers"]),
    }
    prof = Profiler(CFG, {**params, "layers": sub["layers"]}, dtype=jnp.float32)
    assert prof.num_layers_held == 2
    rep = prof.profile_prefill(lengths=(8, 16), repeats=1)
    assert rep.num_layers_measured == 2
    assert rep.capability_c_k > 0


def test_decode_report_and_similarity(params):
    prof = Profiler(CFG, params, dtype=jnp.float32)
    pre = prof.profile_prefill(lengths=(8, 16, 32), repeats=1)
    dec = prof.profile_decode(max_tokens=16, prompt_len=8, measure_every=4)
    assert len(dec.token_counts) == len(dec.cumulative_s)
    assert dec.cumulative_s[-1] >= dec.cumulative_s[0]
    verdict = Profiler.similarity_verdict(pre, dec)
    assert verdict.threshold == 0.30
    assert np.isfinite(verdict.avg_ratio)


def test_decode_requires_full_model(params):
    sub_layers = jax.tree.map(lambda a: a[:2], params["layers"])
    prof = Profiler(CFG, {**params, "layers": sub_layers}, dtype=jnp.float32)
    with pytest.raises(ValueError, match="full model"):
        prof.profile_decode(max_tokens=4)


def test_stage_profile_runs_for_partial_slice(params):
    """Assisted-profiling equivalent: any layer range times standalone."""
    sub_layers = jax.tree.map(lambda a: a[2:4], params["layers"])
    prof = Profiler(CFG, {**params, "layers": sub_layers}, dtype=jnp.float32)
    t = prof.profile_stage(seq_len=16, repeats=2)
    assert t > 0


def test_layer_bytes_exact(params):
    per_layer = jax.tree.map(lambda a: a[0], params["layers"])
    actual = sum(a.size * 4 for a in jax.tree.leaves(per_layer))  # fp32
    assert layer_param_bytes(CFG, jnp.float32) == actual


@pytest.mark.parametrize("preset", [
    "tiny_gpt2", "tiny_qwen2", "tiny_olmoe", "tiny_keye_vl2", "tiny_ouro",
    "tiny_deepseek_v3", "tiny_mimo_v2", "tiny_nemotron_h", "tiny_jamba",
    "tiny_solar_open2", "tiny_longcat_flash",
])
def test_layer_bytes_are_the_familys_own_leaves(preset):
    """Every family's layer is counted from the leaves its ``init_params``
    makes (gpt2's formula used to answer for every family but llama, and
    llama's dense count for its experts, indexer and biases); layers of
    several kinds count as their mean, rounded up."""
    from llm_sharding_tpu.models import config
    from llm_sharding_tpu.models.family import family

    cfg = getattr(config, preset)()
    layers = family(cfg).init_params(cfg, jax.random.key(0), jnp.float32)[
        "layers"]
    elements = sum(a.size for a in jax.tree.leaves(layers))
    L = cfg.num_hidden_layers
    assert layer_param_bytes(cfg, jnp.float32) == -(-elements // L) * 4
    if not cfg.layer_kinds:  # layers all alike: one layer's leaves, exactly
        assert elements % L == 0
    assert layer_param_bytes(cfg, jnp.int8) * 4 == layer_param_bytes(
        cfg, jnp.float32)


def test_max_layers_fit_accounting():
    # budget for exactly 3 layers + head/embed + 10% reserve
    head = CFG.vocab_size * CFG.hidden_size * 2 * 2 + CFG.hidden_size * 2
    per = layer_param_bytes(CFG) + kv_cache_bytes_per_layer(CFG, 1, 64)
    hbm = int((head + 3 * per) / 0.9) + 1024
    got = max_layers_fit(CFG, kv_capacity=64, hbm_bytes=hbm)
    assert got == 3
    # never reports more layers than the model has
    assert max_layers_fit(CFG, kv_capacity=64, hbm_bytes=10**12) == CFG.num_hidden_layers


def test_cold_start(tmp_path, params):
    from llm_sharding_tpu.utils import shard_store

    out = str(tmp_path / "cs")
    shard_store.save_shards(CFG, params, out)
    rep = profile_cold_start(out, dtype=jnp.float32)
    assert isinstance(rep, ColdStartReport)
    assert rep.num_layers == CFG.num_hidden_layers
    assert len(rep.per_layer_s) == CFG.num_hidden_layers
    assert rep.total_s >= max(rep.per_layer_s)


def test_stage_memory_quantized_head_accounting():
    """HBM planning distinguishes int8-resident layers from the head's own
    dtype: the default quantize mode (int8 layers, bf16 tables) must charge
    2 bytes/element for the vocab shard, quantize_head models 1."""
    from llm_sharding_tpu.parallel.head import head_bytes_per_stage
    from llm_sharding_tpu.parallel.placement import PlacementSpec
    from llm_sharding_tpu.profiler.profiler import stage_memory_bytes

    spec = PlacementSpec.balanced(CFG.num_hidden_layers, 4)
    all_int8 = stage_memory_bytes(CFG, spec, param_dtype=jnp.int8)
    mixed = stage_memory_bytes(
        CFG, spec, param_dtype=jnp.int8, head_dtype=jnp.bfloat16
    )
    want_delta = head_bytes_per_stage(CFG, 4, 2) - head_bytes_per_stage(
        CFG, 4, 1
    )
    assert mixed[0] - all_int8[0] == want_delta > 0


def test_calibrate_chain_grows_past_sync_jitter():
    """Regression: the old fixed-8× calibration measured a NEGATIVE delta
    when sync jitter swamped the hop work (a host↔device sync costing far
    more than µs of hops), clamping the per-hop estimate to 20 ns and
    pegging n_long at the 1 M cap. The geometric calibration must keep
    growing the chain until the delta provably exceeds the jitter floor,
    then size n_long from SIGNAL — not land on the cap."""
    from llm_sharding_tpu.profiler.profiler import _calibrate_chain

    per_hop = 1e-6  # true cost the calibration should recover
    # scripted timer: ~100 ms sync with jitter large enough that the FIRST
    # 8× chain delta (256-32 hops = 224 µs of work) comes out negative
    jitter = iter(
        [0.0, 1e-3, 5e-4]            # run(short) × 3 → spread 1 ms
        # n_mid=256 pairs (mid, short): the short draws the jitter spike,
        # so every first-round delta is 224 µs − 2 ms < 0 — the exact
        # negative-delta pathology
        + [0.0, 2e-3, 0.0, 2e-3, 0.0, 2e-3]
        + [0.0] * 100                 # later, larger chains measure clean
    )

    def make_run(n):
        return lambda: 0.1 + next(jitter, 0.0) + n * per_hop

    n_long, est, run_long = _calibrate_chain(make_run, 32)
    assert n_long < 1_000_000, "calibration pegged at the cap (pathology)"
    # the estimate comes from a chain whose delta beat the 10×-spread floor,
    # so it is within a small factor of the true per-hop cost
    assert per_hop / 3 < est < per_hop * 3
    assert abs(n_long - 0.4 / est) <= max(0.05 * n_long, 2048)


def test_calibrate_chain_caps_when_immeasurable():
    """Genuinely immeasurable hops (delta never beats the floor) stop at
    the cap with a non-degenerate positive estimate instead of looping."""
    from llm_sharding_tpu.profiler.profiler import _calibrate_chain

    calls = {"n": 0}

    def make_run(n):
        def run():
            calls["n"] += 1
            # pure alternating jitter, zero hop signal
            return 0.1 + (1e-3 if calls["n"] % 2 else 0.0)

        return run

    n_long, est, run_long = _calibrate_chain(make_run, 32, cap=10_000)
    assert n_long <= 10_000
    assert est >= 20e-9
    assert run_long is not None  # n_long == final n_mid: runner reused,
    # sparing the duplicate compile of an identical-size chain
    assert calls["n"] < 100  # bounded growth, no spin


def test_measure_hop_latency_ring8():
    """The north-star secondary metric's machinery: chain-delta calibration
    over an 8-device ring yields a positive, stable per-hop figure (the
    difference method must survive sync jitter; samples clamp at 0 only
    when jitter swamps the delta, which a real 8-ring never hits on CPU)."""
    from llm_sharding_tpu.parallel.mesh import pipeline_mesh
    from llm_sharding_tpu.profiler.profiler import measure_hop_latency

    rep = measure_hop_latency(
        pipeline_mesh(8), hidden_size=64, n_hops=32, repeats=5
    )
    assert rep.p50_us > 0
    assert rep.p99_us >= rep.p50_us
    assert rep.bytes_per_hop == 64 * 2  # bf16 block
    assert rep.hops_per_sample > 0 and rep.samples == 5
