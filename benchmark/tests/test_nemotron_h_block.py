"""The ``nemotron_h`` block's own rehearsal (``blocks/nemotron_h.py``, the
configuration ``nemotron3_super_120b_a12b``, the mix ``think`` and the three
readers PR 43 brought). CPU, tiny widths, Pallas in interpret mode:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_nemotron_h_block.py -q -p no:cacheprovider

``test_benchmark.py::test_a_configuration_resolves_to_a_block_with_the_programs_leaves``
picks up ``configs/nemotron3_super_120b_a12b.json`` as a case by itself.
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

TINY = tb.load(HERE, "data", "tiny_nemotron_h.json")
MODEL = harness.model_keys(TINY)
BLOCK = blocks.load("nemotron_h")
CELL = "nemotron3_super_120b_a12b.think"
THINK = tb.load(tb.BENCH, "traffic", "think.json")
NEW = ("decode_ssm_pct.think", "ssm_state_hbm_pct.think",
       "decode_moe_latent_pct.think")

# a toy's limits (no cell has them): ~60 positions of a vocabulary of 512,
# where one held expert chosen the other way at a near-tie reads alone what
# the chip's limit allows in the mean
TOY_DELTA_MEAN = 0.04
TOY_DELTA_MAX = 4.0

# what the program is handed in place of the seed's leaves; the reference
# keeps the seed's
WRONG = {
    "sound": None,
    "conv bias dropped": ("mamba", "conv_b"),
    "skip term dropped": ("mamba", "D"),
    "shared expert dropped": ("moe", "ws_down"),
    "latent projection dropped": ("moe", "w_lat_up"),
}


def run_think(tmp_path, readers, seconds=4.0):
    """``harness.run_cell`` with the ``think`` mix at toy lengths: ONE client
    on the toy's two rows, every reply the same length."""
    traffic = json.loads(json.dumps(THINK))
    traffic["prompt_len"].update(median=24, max=100)
    traffic["output_len"].update(value=12, max=12)
    traffic.update(ramp_s=1.0, tail_s=4.0)
    return harness.run_cell(
        cell={"name": "tiny.think"}, cfg_file=json.loads(json.dumps(TINY)),
        block=BLOCK, traffic=traffic, cell_params={"clients_per_row": 0.5},
        devices=jax.devices()[:1], seed=2**31 + 9, seconds=seconds,
        trace=False, out_dir=str(tmp_path), t_process=time.perf_counter(),
        readers=readers, attn="auto",
    )


def test_the_tiny_configuration_is_a_case_of_the_leaves_test():
    tb.test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        os.path.join(HERE, "data", "tiny_nemotron_h.json"), blocks.HERE)
    assert blocks.kinds(BLOCK, MODEL) == (
        "mamba", "moe", "mamba", "attn", "moe", "mamba")
    assert BLOCK.held_experts(MODEL) == (4, 4) and BLOCK.total_experts(MODEL) == 8


def test_the_weight_rules_make_the_mechanism_visible():
    params = weights.make_params(BLOCK, MODEL, 7, "int8", jax.devices()[:1])
    mamba = {k: np.asarray(v, np.float32) for k, v in
             params["layers"]["mamba"].items() if not hasattr(v, "q")}
    dt = np.log1p(np.exp(mamba["dt_bias"]))  # softplus: the seeded dt
    assert 0.9e-3 < dt.min() and dt.max() < 0.11  # log-uniform in [1e-3, 0.1]
    A = np.exp(mamba["A_log"])
    assert 0.99 < A.min() and A.max() < 16.1
    for name in ("D", "conv_b", "conv_w", "gate_norm", "norm"):
        assert np.abs(mamba[name]).min() > 0, name  # never zero
    assert np.abs(mamba["D"] - 1).max() > 0.05
    assert "we_gate" not in params["layers"]["moe"]  # not gated: two matrices


def test_the_draw_favours_no_rank():
    """What keeps one seed's run as dear as another's (the block's docstring):
    router columns of length 1 in antithetic pairs inside each rank's share,
    down projections whose columns sum to zero over their rows (each
    expert's over its own)."""
    params = weights.make_params(BLOCK, MODEL, 11, "bf16", jax.devices()[:1])
    moe = params["layers"]["moe"]
    _, held = BLOCK.held_experts(MODEL)
    router = np.asarray(moe["router"], np.float32)  # [L, H, E]
    L, H, E = router.shape
    share = router.reshape(L, H, E // held, held)
    assert np.array_equal(share[..., :held // 2], -share[..., held // 2:])
    assert np.abs(np.linalg.norm(router, axis=1) - 1).max() < 5e-3  # bf16
    assert len(np.unique(np.abs(router[0, 0]))) == E // 2  # the pairs differ
    F = MODEL["moe_intermediate_size"]
    for stack, leaf, rows in (
        (moe, "ws_down", None), (moe, "we_down", F),
        (params["layers"]["mamba"], "w_out", None),
    ):
        w = np.asarray(stack[leaf], np.float32)
        w = w.reshape(w.shape[0], -1, rows or w.shape[1], w.shape[2])
        sums = np.abs(w.sum(axis=2)).max()
        assert sums < 0.02 * np.abs(w).sum(axis=2).min(), (leaf, sums)
        assert w.std() > 0.5 * w.shape[2] ** -0.5, leaf  # the scale stands


@pytest.mark.parametrize("what", list(WRONG))
def test_the_think_cell_runs_through_the_harness(what, tmp_path, monkeypatch):
    """A tiny ``nemotron_h`` configuration (three kinds of layer, half the
    experts held) served paged through ``harness.run_cell`` under the
    ``think`` mix with ONE client is correct, its step records carry the
    recurrent state's and the experts' counters and the host-side readers
    read them — and it is not correct when the program is handed no conv
    bias, no skip term, no shared expert or no way out of the latent space."""
    make, calls = weights.make_params, []

    def served_wrong(*args, **kw):
        params = make(*args, **kw)
        calls.append(1)
        if WRONG[what] is None or len(calls) > 1:  # the second is the check's
            return params
        kind, leaf = WRONG[what]
        layers = {k: dict(v) for k, v in params["layers"].items()}
        layers[kind][leaf] = jax.tree.map(jnp.zeros_like, layers[kind][leaf])
        return dict(params, layers=layers)

    monkeypatch.setattr(weights, "make_params", served_wrong)
    monkeypatch.setattr(BLOCK, "DELTA_MEAN", TOY_DELTA_MEAN)
    monkeypatch.setattr(BLOCK, "DELTA_MAX", TOY_DELTA_MAX)
    e2e, layer, bench = tb._readers(CELL)
    got = run_think(tmp_path, e2e)
    res, rec = got["result"], got["records"]
    assert len(calls) == 2 and rec["reference"]["positions"] > 20
    assert res["failed"] == 0 and rec["paths"]["attn_backend"] == "interpret"
    # judged on the gap and the set-up alone (PERF.md section 2)
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert rec["paths"]["arena_dtype"] == ["bfloat16"] and rec["arena_ok"]
    print(what, rec["reference"])
    assert res["correct"] == (WRONG[what] is None), rec["reference"]
    if WRONG[what] is not None:
        assert rec["kernels_ok"] and rec["reference"]["margin_mean"] > 2 * TOY_DELTA_MEAN
        return
    rows = layer["rows_per_step.think"][0](rec)
    assert 0.7 <= rows <= 1.0 and max(s["rows"] for s in rec["steps"]) == 1
    assert len(rec["requests"]) > 2
    # the recurrent state's counters: one row holds a state while it decodes;
    # every prompt went through the scan in whole chunks of 32 x 2 rows
    held = [s["recurrent_rows"] for s in rec["steps"] if "recurrent_rows" in s]
    assert held and set(held) == {1}
    scanned = [s["scan_positions"] for s in rec["steps"] if "scan_positions" in s]
    assert scanned and all((s["real"] + s["pad"]) % 64 == 0 for s in scanned)
    # the experts' counters: held experts only are read, every pair counted
    steps = [s for s in rec["steps"] if s.get("expert_steps")]
    assert steps
    for s in steps:
        assert len(s["experts_read"]) == 6 and len(s["expert_tokens"]) == 8
        assert [s["experts_read"][i] for i in (0, 1, 2, 5)] == [0, 0, 0, 0]
    n = layer["experts_read_per_layer.think"][0](rec)
    assert 0 < n <= 3 * 2 / 6  # at most k in each of 2 of the 6 layers
    pairs = layer["expert_pairs_held_pct.think"][0](rec)
    assert 20.0 < pairs < 80.0  # half the experts held
    for name in NEW + ("decode_moe_pct.think", "moe_hbm_pct.think"):
        assert layer[name][0](rec) is None, name  # untraced: nothing to read
    # and the byte count takes what the records say
    rec["traced"] = rec["window"]
    live = BLOCK.live_rows(rec)
    assert 0.7 <= live <= 1.0
    assert BLOCK.decode_step_bytes(MODEL, "int8", 1, 10.0, rec) == pytest.approx(
        hand_count(n, 10.0, live))


def hand_count(experts_read, live_tokens, rows):
    """Bytes of a decode microstep of the tiny model, by hand (int8)."""
    H, V = 128, 512
    nh, hd, ds, g, K = 16, 16, 16, 2, 4
    di, cd = nh * hd, nh * hd + 2 * g * ds
    Hl, F, Fs, E = 64, 64, 128, 8
    mm = lambda i, o: i * o + o * 2  # an int8 matmul and its bf16 scales
    mamba = (mm(H, di + cd + nh) + mm(di, H)
             + 2 * (H + K * cd + cd + 3 * nh + di))
    state = 2 * 4 * (nh * hd * ds + (K - 1) * cd)  # read and written
    moe = ((H * E + E + H) * 2 + mm(H, Hl) + mm(Hl, H) + mm(H, Fs) + mm(Fs, H)
           + Hl * 2)  # router, bias, norm; latent; shared; we_down's scale
    expert = 2 * Hl * F + F * 2
    attn = mm(H, 4 * 32) + 2 * mm(H, 2 * 32) + mm(4 * 32, H) + 2 * H
    kv = live_tokens * 2 * 2 * 32 * 2
    return (3 * (mamba + rows * state) + 2 * moe + experts_read * 6 * expert
            + attn + kv + H * V * 2)


def test_the_real_configuration_states_what_a_request_holds():
    cfg = tb.load(tb.BENCH, "configs", "nemotron3_super_120b_a12b.json")
    model = harness.model_keys(cfg)
    assert BLOCK.kind_layers(model) == {"mamba": 8, "moe": 7, "attn": 2}
    assert BLOCK.state_bytes_per_row_layer(model, moved=False) == 4 * (
        128 * 64 * 128 + 3 * 10240)
    assert BLOCK.arena_bytes_per_token_layer(model) == 1024
    program = harness.model_config(cfg)
    assert program.recurrent and program.recurrent_row_bytes == 4317184
    assert (program.cache_heads, program.cache_k_dim, program.cache_v_dim) == (
        2, 128, 128)
    assert BLOCK.held_experts(model) == (0, 128) and BLOCK.total_experts(model) == 512
    assert cfg["eos_token_id"] >= cfg["vocab_size"]  # outside the held slice
    assert cfg["hybrid_override_pattern"][:17] == "MEMEMEM*EMEMEMEM*"
    # the cut's arithmetic, as PERF.md section 4 states it (MB of int8)
    assert BLOCK.expert_bytes(model, "int8") / 1e6 == pytest.approx(5.51, abs=0.01)
    assert BLOCK.mamba_fixed_bytes(model, "int8") / 1e6 == pytest.approx(109.8, abs=0.3)
    assert BLOCK.attention_bytes(model, "int8") / 1e6 == pytest.approx(35.7, abs=0.1)
    fixed = BLOCK.moe_fixed_bytes(model, "int8") / 1e6
    assert fixed == pytest.approx(44.0 + 8.4 + 4.2, abs=0.3)
    total = (8 * BLOCK.mamba_fixed_bytes(model, "int8")
             + 7 * (BLOCK.moe_fixed_bytes(model, "int8")
                    + 128 * BLOCK.expert_bytes(model, "int8"))
             + 2 * BLOCK.attention_bytes(model, "int8")
             + 2 * 32768 * 4096 * 2)
    assert total / 1e9 == pytest.approx(6.8, abs=0.1)
    # every published key, the four reduced ones apart
    from llm_sharding_tpu.models.config import nemotron3_super_keys

    published = nemotron3_super_keys(num_nextn_predict_layers=1)
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"])


def test_the_new_metrics_are_entries_with_readers():
    bench = tb.BENCHMARK
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "think")
    judged = [m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", (CELL,))]
    assert judged == ["itl_p95_ms", "setup_s"]
    for name in NEW + ("decode_moe_pct.think", "moe_hbm_pct.think",
                       "experts_read_per_layer.think",
                       "expert_pairs_held_pct.think", "rows_per_step.think",
                       "kv_in_use_peak_pct.think"):
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    _, layer, _ = tb._readers(CELL)
    assert set(NEW) <= set(layer)
    # an untraced run, a model without the mechanism: nothing, and no raise
    rec = {"traced": None, "steps": [], "window": [0.0, 1.0], "requests": [],
           "config": tb.TINY, "chips": 1, "peaks": {"hbm_bytes_per_s": 8e11}}
    for name in NEW:
        assert layer[name][0](dict(rec)) is None, name


def test_the_new_readers_on_recorded_spans():
    """The readers over a reduction as ``span_reduce`` leaves it: the recorded
    trace of a dense model has none of the scopes (nothing to read); with
    them, the shares are the seconds' — and the roofline share is bytes over
    time and cannot pass 100 while the update moves each row's state once."""
    _, layer, _ = tb._readers(CELL)
    recorded = tb.load(HERE, "data", "span.expect.json")
    rec = {"spans": recorded, "traced": [0.0, 1.0], "window": [0.0, 1.0],
           "config": TINY, "chips": 1, "steps": [], "requests": [],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in NEW:
        assert layer[name][0](rec) is None, name
    spans = {"scopes": {"serve_chunk": {
        "ssm_proj": 0.2, "conv": 0.02, "ssm": 0.08, "moe_latent": 0.05,
        "attn": 0.05, "mlp": 0.4, "moe": 0.1, "router": 0.1}}}
    rec = dict(
        rec, spans=spans,
        trace={"modules": {"serve_chunk": [[0.001] * 100]}},
        steps=[{"t": 0.5, "rows": 1, "expert_steps": 1,
                "experts_read": [0, 1, 0, 0, 2, 0],
                "expert_tokens": [1, 0, 0, 0, 2, 1, 0, 0]}],
        requests=[{"server_started_at": 0.1, "finished": None,
                   "prompt_len": 30, "stamps": [0.2, 0.3, 0.4]}],
    )
    assert layer["decode_ssm_pct.think"][0](rec) == pytest.approx(30.0)
    assert layer["decode_moe_latent_pct.think"][0](rec) == pytest.approx(5.0)
    assert layer["decode_moe_pct.think"][0](rec) == pytest.approx(20.0)
    # one live row x 3 mixers x (state + tail, read and written) over the
    # 1 ms of conv + ssm a step
    state = 2 * 4 * (16 * 16 * 16 + 3 * (256 + 64))
    want = 100.0 * 1 * 3 * state / 819e9 / (0.1 / 100)
    assert layer["ssm_state_hbm_pct.think"][0](rec) == pytest.approx(want)
    assert want < 100.0
    assert layer["expert_pairs_held_pct.think"][0](rec) == pytest.approx(75.0)


def test_the_scan_counts():
    cfg = tb.load(tb.BENCH, "configs", "nemotron3_super_120b_a12b.json")
    model = harness.model_keys(cfg)
    # one block of 128: scores 8 groups, the masked product and two state
    # products of 128 heads
    one = 2 * 128 * 128 * 128 * 8 + 2 * 128 * 128 * 64 * 128 + 4 * 128 * 64 * 128 * 128
    assert BLOCK.scan_flops(model, 128) == one
    assert BLOCK.scan_flops(model, 256) == 2 * one
    assert BLOCK.scan_bytes(model, 256) == 256 * (2 * 8192 + 2 * 1024 + 128) * 4 + 8 * 128 * 64 * 128
