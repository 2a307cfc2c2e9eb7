"""The ``longcat_flash`` block's own rehearsal (``blocks/longcat_flash.py``, the
configuration ``longcat_flash_omni``, the mix ``draft`` and the two readers PR
57 brought). CPU, tiny widths, Pallas in interpret mode:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_longcat_flash_block.py -q -p no:cacheprovider

``test_benchmark.py::test_a_configuration_resolves_to_a_block_with_the_programs_leaves``
picks up ``configs/longcat_flash_omni.json`` as a case by itself.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import test_benchmark as tb  # noqa: E402  (sets the CPU, interpret mode, paths)

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import blocks, harness, weights  # noqa: E402

TINY = tb.load(HERE, "data", "tiny_longcat_flash.json")
MODEL = harness.model_keys(TINY)
BLOCK = blocks.load("longcat_flash")
CELL = "longcat_flash_omni.draft"
DRAFT = tb.load(tb.BENCH, "traffic", "draft.json")
NEW = ("zero_expert_pairs_pct.draft", "decode_zero_expert_pct.draft")
BY_STEM = ("decode_moe_pct.draft", "moe_hbm_pct.draft",
           "experts_read_per_layer.draft", "expert_pairs_held_pct.draft",
           "decode_absorb_pct.draft", "latent_attn_hbm_pct.draft",
           "rows_per_step.draft", "kv_in_use_peak_pct.draft")

# The block's limits were read on the chip over ~8,000 positions of a
# vocabulary of 16,384; this toy scores ~60 of a vocabulary of 512 at hidden
# 128, where one pick chosen the other way at a near-tie reads alone what the
# chip's limit allows in the mean. A toy's limits: no cell has them.
TOY_DELTA_MEAN = 0.05
TOY_DELTA_MAX = 4.0

# what the program is handed in place of the seed's leaves; the reference
# keeps the seed's
WRONG = {
    "sound": None,
    "second attention's output dropped": "wo_1",
    "first dense MLP dropped": "w_down_0",
    "held experts dropped": "we_down",
}


def run_draft(tmp_path, readers, seconds=4.0):
    """``harness.run_cell`` with the ``draft`` mix at toy lengths: ONE client
    on the toy's two rows, every reply the same length."""
    traffic = json.loads(json.dumps(DRAFT))
    traffic["prompt_len"].update(median=24, max=100)
    traffic["output_len"].update(value=12, max=12)
    traffic.update(ramp_s=1.0, tail_s=4.0)
    return harness.run_cell(
        cell={"name": "tiny.draft"}, cfg_file=json.loads(json.dumps(TINY)),
        block=BLOCK, traffic=traffic, cell_params={"clients_per_row": 0.5},
        devices=jax.devices()[:1], seed=2**31 + 9, seconds=seconds,
        trace=False, out_dir=str(tmp_path), t_process=time.perf_counter(),
        readers=readers, attn="auto",
    )


def test_the_tiny_configuration_is_a_case_of_the_leaves_test():
    tb.test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        os.path.join(HERE, "data", "tiny_longcat_flash.json"), blocks.HERE)
    assert blocks.kinds(BLOCK, MODEL) is None  # every layer is one kind
    assert BLOCK.held_experts(MODEL) == (4, 4)
    assert (BLOCK.real_experts(MODEL), BLOCK.zero_experts(MODEL)) == (8, 4)
    names = [leaf.name for leaf in BLOCK.layer_leaves(MODEL)]
    assert len(names) == len(set(names)) == 2 * 13 + 5
    assert names[:2] == ["input_norm_0", "wq_a_0"] and names[-1] == "we_down"


def test_the_draw_keeps_the_scales_in_and_the_router_even():
    """What the module docstring's "Weights" promises, read from the leaves:
    the two latent scales are absorbed by ``wq_b`` / ``w_uk`` / ``w_uv``;
    the router's columns have length ``ROUTER_SCALE`` in antithetic pairs
    inside each run of ``held`` ids; both ``w_down`` and each expert's
    ``we_down`` have columns that sum to zero; the bias is never zero."""
    params = weights.make_params(BLOCK, MODEL, 7, "bf16", jax.devices()[:1])
    lay = {k: np.asarray(v, np.float32) for k, v in params["layers"].items()}
    s_q, s_kv = BLOCK.lora_scales(MODEL)
    assert (round(s_q, 4), round(s_kv, 4)) == (1.633, 1.4142)
    rq, rkv = MODEL["q_lora_rank"], MODEL["kv_lora_rank"]
    for i in (0, 1):
        assert lay[f"wq_b_{i}"].std() == pytest.approx(
            (rq * s_q ** 2) ** -0.5, rel=0.05)
        for name in ("w_uk", "w_uv"):
            assert lay[f"{name}_{i}"].std() == pytest.approx(
                (rkv * s_kv ** 2) ** -0.5, rel=0.05)
        assert np.abs(lay[f"w_down_{i}"].sum(1)).max() < 0.05  # bf16's sums
        assert not lay[f"wkv_a_{i}"][:, :, 80:].any()
        assert np.abs(lay[f"kv_a_norm_{i}"] - 1).max() > 0.05
    router = lay["router"]  # [L, H, 12]
    np.testing.assert_allclose(
        np.linalg.norm(router, axis=1), BLOCK.ROUTER_SCALE, rtol=0.01)
    runs = router.reshape(3, 128, 3, 4)
    np.testing.assert_array_equal(runs[..., :2], -runs[..., 2:])
    F = MODEL["expert_ffn_hidden_size"]
    assert np.abs(lay["we_down"].reshape(3, 4, F, 128).sum(2)).max() < 0.05
    bias = lay["router_bias"]
    assert bias.shape == (3, 12) and np.abs(bias).min() > 0  # never zero
    assert np.abs(bias).max() < 0.002


@pytest.mark.parametrize("what", list(WRONG))
def test_the_draft_cell_runs_through_the_harness(what, tmp_path, monkeypatch):
    """A tiny ``longcat_flash`` configuration (half the real experts held,
    four zero-compute experts) served paged through ``harness.run_cell`` under
    the ``draft`` mix with ONE client is correct, its step records carry the
    ``[E + Z]`` counters and the host-side readers read them — and it is not
    correct when the program is handed a second attention without its output,
    a first sub-layer without its MLP, or held experts that add nothing."""
    make, calls = weights.make_params, []

    def served_wrong(*args, **kw):
        params = make(*args, **kw)
        calls.append(1)
        if WRONG[what] is None or len(calls) > 1:  # the second is the check's
            return params
        layers = dict(params["layers"])
        layers[WRONG[what]] = jax.tree.map(
            jnp.zeros_like, layers[WRONG[what]])
        return dict(params, layers=layers)

    monkeypatch.setattr(weights, "make_params", served_wrong)
    monkeypatch.setattr(BLOCK, "DELTA_MEAN", TOY_DELTA_MEAN)
    monkeypatch.setattr(BLOCK, "DELTA_MAX", TOY_DELTA_MAX)
    e2e, layer, bench = tb._readers(CELL)
    got = run_draft(tmp_path, e2e)
    res, rec = got["result"], got["records"]
    assert len(calls) == 2 and rec["reference"]["positions"] > 20
    assert res["failed"] == 0 and rec["paths"]["attn_backend"] == "interpret"
    # judged on the gap and the set-up alone (PERF.md section 2)
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert rec["paths"]["arena_dtype"] == ["bfloat16"] and rec["arena_ok"]
    print(what, rec["reference"])
    assert res["correct"] == (WRONG[what] is None), rec["reference"]
    if WRONG[what] is not None:
        assert rec["kernels_ok"]
        assert rec["reference"]["margin_mean"] > 2 * TOY_DELTA_MEAN
        return
    # one client: never more than one live row; every reply the same length
    rows = layer["rows_per_step.draft"][0](rec)
    assert 0.7 <= rows <= 1.0 and max(s["rows"] for s in rec["steps"]) == 1
    done = [r for r in rec["requests"] if r["finished"] is not None]
    assert len(done) > 2 and {len(r["stamps"]) for r in done} == {12}
    # the counters: E + Z wide; held real experts only are read
    k, E, Z, L = 3, 8, 4, 3
    steps = [s for s in rec["steps"] if s.get("expert_steps")]
    assert steps
    for s in steps:
        assert len(s["experts_read"]) == L
        assert len(s["expert_tokens"]) == E + Z
        assert max(s["experts_read"]) <= min(k * s["expert_rows"], 4)
    n = layer["experts_read_per_layer.draft"][0](rec)
    assert 0 < n <= k
    held = layer["expert_pairs_held_pct.draft"][0](rec)
    zero = layer["zero_expert_pairs_pct.draft"][0](rec)
    assert 10.0 < held < 60.0  # 4 of 12 outputs held: neither none nor all
    assert 10.0 < zero < 60.0  # 4 of 12 zero-compute
    # the device-side readers have nothing to read in an untraced run
    for name in ("decode_moe_pct.draft", "moe_hbm_pct.draft",
                 "decode_absorb_pct.draft", "latent_attn_hbm_pct.draft",
                 "decode_zero_expert_pct.draft"):
        assert layer[name][0](rec) is None, name
    # and the byte count takes what the records say
    rec["traced"] = rec["window"]
    assert BLOCK.decode_step_bytes(MODEL, "int8", 1, 10.0, rec) == pytest.approx(
        hand_count(n, 10.0))


def hand_count(experts_read, live_tokens):
    """Bytes of a decode microstep of the tiny model, by hand (int8)."""
    H, Nh, dn, dr, dv, rq, rkv = 128, 4, 32, 16, 48, 48, 64
    I, F, EZ, V, L = 256, 64, 12, 512, 3
    mm = lambda i, o: i * o + o * 2  # an int8 matmul and its bf16 scales
    attn = (mm(H, rq) + mm(rq, Nh * (dn + dr)) + mm(H, 128)  # 80 padded
            + mm(Nh * dn, rkv) + mm(Nh * dv, rkv) + mm(Nh * dv, H)
            + 2 * (H + rq + rkv))  # three gains
    dense = mm(H, I) + mm(H, I) + mm(I, H) + 2 * H  # and the norm before it
    fixed = (H * EZ + EZ) * 2 + H * 2  # router, bias; we_down's scale
    expert = 3 * H * F + 2 * F * 2
    latents = live_tokens * 2 * 128 * 2  # TWO entries of 128 lanes a layer
    return (L * (2 * attn + 2 * dense + fixed) + experts_read * L * expert
            + H * V * 2 + L * latents)


def test_the_real_configuration_states_what_it_holds():
    cfg = tb.load(tb.BENCH, "configs", "longcat_flash_omni.json")
    model = harness.model_keys(cfg)
    assert BLOCK.arena_bytes_per_token_layer(model) == 2560
    assert "2560" in cfg["assumed"]["arena_bytes_per_token_layer"]
    program = harness.model_config(cfg)
    assert (program.cache_heads, program.cache_k_dim, program.cache_v_dim,
            program.arena_slots) == (1, 640, 0, 2)
    assert (program.num_experts, program.zero_experts) == (512, 256)
    assert (program.mla_q_scale, round(program.mla_kv_scale, 4)) == (2.0, 3.4641)
    assert BLOCK.held_experts(model) == (0, 16)
    assert cfg["eos_token_id"] >= cfg["vocab_size"]  # outside the held slice
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    # the cut's arithmetic, as the file's layout states it (MB of int8)
    assert BLOCK.attention_bytes(model, "int8") / 1e6 == pytest.approx(91.0, abs=0.1)
    assert BLOCK.dense_mlp_bytes(model, "int8") / 1e6 == pytest.approx(226.5, abs=0.1)
    assert BLOCK.expert_bytes(model, "int8") / 1e6 == pytest.approx(37.75, abs=0.02)
    assert BLOCK.layer_bytes(model, "int8") / 1e6 == pytest.approx(1248.7, abs=0.5)
    serve = cfg["serve"]
    held = BLOCK.held_bytes(model, "int8", serve["kv_blocks"],
                            serve["kv_block_size"])
    assert held["weights"] / 1e9 == pytest.approx(9.14, abs=0.01)
    assert held["latent_pool"] / 1e9 == pytest.approx(2.35, abs=0.01)
    assert 0.25 * 16e9 < held["total"] < 16e9  # 72% of the chip
    # a one-row decode step at 1.5 k of context, 0.22 experts a layer met
    rec = {"traced": [0.0, 1.0], "window": [0.0, 1.0], "steps": [
        {"t": 0.5, "expert_steps": 100, "experts_read": [22] * 7}]}
    step = BLOCK.decode_step_bytes(model, "int8", 1, 1500.0, rec)
    assert step / 1e9 == pytest.approx(4.80, abs=0.03)
    double_layer = 7 * 2 * (BLOCK.attention_bytes(model, "int8")
                            + BLOCK.dense_mlp_bytes(model, "int8"))
    assert 0.91 < double_layer / step < 0.94
    assert 0.22 * 7 * BLOCK.expert_bytes(model, "int8") / step < 0.015


def test_the_new_metrics_are_entries_with_readers():
    bench = tb.BENCHMARK
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "draft", "longcat_flash_omni")
    judged = [m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", (CELL,))]
    assert judged == ["itl_p95_ms", "setup_s"]
    for name in NEW + BY_STEM:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    for name in ("token_emit_lag_p50_ms", "token_emit_lag_p95_ms",
                 "landing_gap_p95_ms", "host_bound_steps_pct",
                 "queue_empty_lo_pct", "queue_empty_hi_pct"):
        assert by_name[name]["workloads"][-1] == CELL
    _, layer, _ = tb._readers(CELL)
    assert set(NEW + BY_STEM) <= set(layer)
    # an untraced run, a model without the mechanism: nothing, and no raise
    rec = {"traced": None, "steps": [], "window": [0.0, 1.0], "requests": [],
           "config": tb.TINY, "chips": 1, "peaks": {"hbm_bytes_per_s": 8e11}}
    for name in NEW:
        assert layer[name][0](dict(rec)) is None, name
    # a block with a share and NO zero-compute experts: nothing either
    other = tb.load(HERE, "data", "tiny_deepseek_v3.json")
    rec = dict(rec, config=other, steps=[
        {"t": 0.5, "expert_tokens": [1, 0, 0, 0, 2, 1, 0, 0]}])
    assert layer["zero_expert_pairs_pct.draft"][0](rec) is None


def test_the_new_readers_on_recorded_spans():
    """The readers over a reduction as ``span_reduce`` leaves it: the recorded
    trace of a dense model has no ``zero_expert`` scope (nothing to read);
    with the scope, the share is the seconds'; the counter's reader splits
    the pairs at the block's ``real_experts``."""
    _, layer, _ = tb._readers(CELL)
    recorded = tb.load(HERE, "data", "span.expect.json")
    rec = {"spans": recorded, "traced": [0.0, 1.0], "window": [0.0, 1.0],
           "config": TINY, "chips": 1, "steps": [], "requests": [],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert "serve_chunk" in recorded["scopes"]
    assert layer["decode_zero_expert_pct.draft"][0](rec) is None
    assert layer["zero_expert_pairs_pct.draft"][0](rec) is None  # no steps
    spans = {"scopes": {"serve_chunk": {
        "absorb": 0.2, "attn": 0.1, "mlp": 0.55, "moe": 0.05, "router": 0.05,
        "zero_expert": 0.05}}}
    rec = dict(
        rec, spans=spans,
        trace={"modules": {"serve_chunk": [[0.001] * 100]}},
        steps=[{"t": 0.5, "rows": 1, "expert_steps": 1,
                "experts_read": [0, 1, 0],
                # ids 0-3 elsewhere, 4-7 held, 8-11 zero-compute
                "expert_tokens": [1, 0, 0, 0, 2, 1, 0, 0, 1, 0, 3, 1]}],
        requests=[{"server_started_at": 0.1, "finished": None,
                   "prompt_len": 300, "stamps": [0.2, 0.3, 0.4]}],
    )
    assert layer["decode_zero_expert_pct.draft"][0](rec) == pytest.approx(5.0)
    assert layer["decode_moe_pct.draft"][0](rec) == pytest.approx(10.0)
    assert layer["zero_expert_pairs_pct.draft"][0](rec) == pytest.approx(
        100.0 * 5 / 9)
    assert layer["expert_pairs_held_pct.draft"][0](rec) == pytest.approx(
        100.0 * 3 / 9)
    # 303 live tokens x 512 bytes (two entries of 128 lanes) x 3 layers over
    # 1 ms of attn a step
    want = 100.0 * 303 * 512 * 3 / 819e9 / (0.1 / 100)
    assert layer["latent_attn_hbm_pct.draft"][0](rec) == pytest.approx(want)
    assert want < 100.0
