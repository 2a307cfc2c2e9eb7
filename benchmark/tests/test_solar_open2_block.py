"""The ``solar_open2`` block's own rehearsal (``blocks/solar_open2.py``, the
configuration ``solar_open2_250b``, the mix ``cot`` and the three readers PR 53
brought). CPU, tiny widths, Pallas in interpret mode:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_solar_open2_block.py -q -p no:cacheprovider

``test_benchmark.py::test_a_configuration_resolves_to_a_block_with_the_programs_leaves``
picks up ``configs/solar_open2_250b.json`` as a case by itself.
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

TINY = tb.load(HERE, "data", "tiny_solar_open2.json")
MODEL = harness.model_keys(TINY)
BLOCK = blocks.load("solar_open2")
CELL = "solar_open2_250b.cot"
COT = tb.load(tb.BENCH, "traffic", "cot.json")
NEW = ("decode_kda_pct.cot", "kda_state_hbm_pct.cot", "prefill_kda_pct.cot")
OLD = ("decode_moe_pct.cot", "moe_hbm_pct.cot", "attn_kv_hbm_pct.cot",
       "experts_read_per_layer.cot", "expert_pairs_held_pct.cot",
       "rows_per_step.cot", "kv_in_use_peak_pct.cot")

# a toy's limits (no cell has them): ~60 positions of a vocabulary of 512 in
# bfloat16, where one near-tie flipped reads alone what the chip's limit
# allows in the mean
TOY_DELTA_MEAN = 0.06
TOY_DELTA_MAX = 4.0

# what the program is handed in place of the seed's leaves; the reference
# keeps the seed's
WRONG = {
    "sound": None,
    "the conv's taps dropped": ("kda", "conv_w"),
    "the write strength's projection dropped": ("kda", "w_beta"),
    "the decay's bias dropped": ("kda", "dt_bias"),
    "the attention's gate dropped": ("gqa", "w_gate"),
}


def run_cot(tmp_path, readers, seconds=4.0):
    """``harness.run_cell`` with the ``cot`` mix at toy lengths: ONE client
    on the toy's two rows, every reply the same length."""
    traffic = json.loads(json.dumps(COT))
    traffic["prompt_len"].update(median=24, max=100)
    traffic["output_len"].update(value=12, max=12)
    traffic.update(ramp_s=1.0, tail_s=4.0)
    return harness.run_cell(
        cell={"name": "tiny.cot"}, cfg_file=json.loads(json.dumps(TINY)),
        block=BLOCK, traffic=traffic, cell_params={"clients_per_row": 0.5},
        devices=jax.devices()[:1], seed=2**31 + 9, seconds=seconds,
        trace=False, out_dir=str(tmp_path), t_process=time.perf_counter(),
        readers=readers, attn="auto",
    )


def test_the_tiny_configuration_is_a_case_of_the_leaves_test():
    tb.test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        os.path.join(HERE, "data", "tiny_solar_open2.json"), blocks.HERE)
    assert blocks.kinds(BLOCK, MODEL) == ("gqa", "kda", "kda", "kda") * 2
    assert [t.name for t in BLOCK.tables(MODEL)] == [
        "embed", "final_norm", "lm_head"]


def test_the_real_configuration_is_a_case_of_the_leaves_test():
    tb.test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        os.path.join(tb.BENCH, "configs", "solar_open2_250b.json"),
        blocks.HERE)


def test_the_weight_rules_make_the_mechanism_visible():
    params = weights.make_params(BLOCK, MODEL, 7, "bf16", jax.devices()[:1])
    kda = {k: np.asarray(v, np.float32)
           for k, v in params["layers"]["kda"].items()}
    # the decay's two leaves by ``blocks/nemotron_h.py``'s rules for Mamba-2:
    # exp(A_log) uniform in [1, 16] a head, softplus(dt_bias) log-uniform a
    # channel — over FOUR decades, [1e-5, 0.1] (the block says why)
    a = np.exp(kda["A_log"])
    assert 0.99 < a.min() and a.max() < 16.1 and a.std() > 2
    dt = np.log1p(np.exp(kda["dt_bias"].astype(np.float64)))
    assert 0.9e-5 < dt.min() < 3e-5 and 0.05 < dt.max() < 0.11
    # ... so that with the low-rank product of unit variance a step's gate
    # spans decades and has NO lower bound, and the state REMEMBERS: a
    # channel's half-life ln 2 / E|g| from a token to thousands
    g = np.random.default_rng(0).normal(size=(500, 1, 1))
    a3 = np.repeat(a, 128, axis=-1)[None]  # [1, layers, channels]
    rate = (a3 * np.log1p(np.exp(g + kda["dt_bias"][None]))).mean(0)
    half = np.log(2.0) / rate
    assert np.percentile(half, 10) < 4 and np.median(half) > 20
    assert np.percentile(half, 90) > 1024
    for name in ("conv_w", "w_beta", "gate_norm", "input_norm", "post_norm",
                 "router_bias"):
        assert np.abs(kda[name]).min() > 0, name  # never zero
    assert np.abs(kda["gate_norm"] - 1).max() > 0.05
    assert kda["gate_norm"].shape[-1] == 128  # ONE gain, every head's
    # the write strength spreads around 1, over AND under: β = 2 sigmoid
    x = np.random.default_rng(1).normal(size=(200, 128)).astype(np.float32)
    beta = 2 / (1 + np.exp(-(x @ kda["w_beta"][0])))
    assert (beta > 1.3).mean() > 0.1 and (beta < 0.7).mean() > 0.1
    # the experts' down projections: columns that sum to zero over their rows
    w = kda["ws_down"]
    assert np.abs(w.sum(axis=1)).max() < 0.02 * np.abs(w).sum(axis=1).min()
    assert w.std() > 0.5 * w.shape[1] ** -0.5
    per_expert = kda["we_down"].reshape(kda["we_down"].shape[0], 8, 64, -1)
    assert np.abs(per_expert.sum(axis=2)).max() < 0.05
    # the router's columns: each of length 1, antithetic inside a share of 8
    router = kda["router"][0]
    assert np.abs(np.linalg.norm(router, axis=0) - 1).max() < 0.02
    assert np.abs(router[:, :4] + router[:, 4:8]).max() < 0.02
    assert np.abs(router[:, 8:12] + router[:, 12:16]).max() < 0.02


@pytest.mark.parametrize("what", list(WRONG))
def test_the_cot_cell_runs_through_the_harness(what, tmp_path, monkeypatch):
    """A tiny ``solar_open2`` configuration (two kinds of layer, bf16 weights,
    8 of 16 experts held, the KDA decode kernel interpreted at 8 heads of 128
    x 128) served paged through ``harness.run_cell`` under the ``cot`` mix
    with ONE client is correct, its step records carry the recurrent state's
    and the experts' counters and the host-side readers read them — and it is
    not correct when the program is handed no conv taps, no write-strength
    projection, no decay bias or no attention gate."""
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
    got = run_cot(tmp_path, e2e)
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
        assert rec["reference"]["margin_mean"] > 1.5 * TOY_DELTA_MEAN
        return
    rows = layer["rows_per_step.cot"][0](rec)
    assert 0.7 <= rows <= 1.0 and max(s["rows"] for s in rec["steps"]) == 1
    assert len(rec["requests"]) > 2
    # the recurrent state's counters: one row holds a state while it decodes;
    # every prompt went through the chunk form in whole chunks of 32 x 2 rows
    held = [s["recurrent_rows"] for s in rec["steps"] if "recurrent_rows" in s]
    assert held and set(held) == {1}
    scanned = [s["scan_positions"] for s in rec["steps"] if "scan_positions" in s]
    assert scanned and all((s["real"] + s["pad"]) % 64 == 0 for s in scanned)
    # the experts' counters: eight layer slots, every one routing
    routed = [s for s in rec["steps"] if s.get("expert_steps")]
    assert routed and all(len(s["experts_read"]) == 8 for s in routed)
    assert 0 < layer["kv_in_use_peak_pct.cot"][0](rec) < 100
    # 8 of 16 held: an even router reads a half
    assert 35 < layer["expert_pairs_held_pct.cot"][0](rec) < 65
    assert 0 < layer["experts_read_per_layer.cot"][0](rec) <= 3
    for name in NEW + OLD[:3]:
        assert layer[name][0](rec) is None, name  # untraced: nothing to read
    # and the byte count takes what the records say
    rec["traced"] = rec["window"]
    live = BLOCK.live_rows(rec)
    assert 0.7 <= live <= 1.0
    n = BLOCK.experts_read_per_layer(rec)
    assert BLOCK.decode_step_bytes(MODEL, "bf16", 1, 10.0, rec) == pytest.approx(
        hand_count(10.0, live, n))
    # the attention layers' reads: the live context x 2 heads x (32 + 32) x
    # 2 B x TWO layers of eight
    assert BLOCK.attn_kv_bytes(MODEL, rec) == pytest.approx(
        2 * BLOCK.context_tokens(rec) * 2 * 64 * 2)


def hand_count(live_tokens, rows, experts_read):
    """Bytes of a decode microstep of the tiny model, by hand (bf16)."""
    H, V, F = 128, 512, 64
    nh, hd, K = 8, 128, 4
    D = nh * hd
    kda = 2 * (H + 3 * H * D + 2 * (H * hd + hd * D) + H * nh + K * 3 * D
               + nh + D + hd + D * H)
    state = 2 * 4 * (D * hd + (K - 1) * 3 * D)  # read and written
    gqa = 2 * (H + H * 128 + 2 * H * 64 + H * 128 + 128 * H)
    moe = 2 * (H + H * 16 + 16 + 3 * H * F)  # norm, router, bias, shared
    expert = 2 * 3 * H * F
    kv = live_tokens * 2 * 64 * 2
    return (6 * (kda + rows * state) + 2 * (gqa + kv) + 8 * moe
            + experts_read * 8 * expert + H * V * 2)


def test_the_real_configuration_states_what_a_request_holds():
    cfg = tb.load(tb.BENCH, "configs", "solar_open2_250b.json")
    model = harness.model_keys(cfg)
    assert BLOCK.kind_layers(model) == {"kda": 9, "gqa": 3}
    kinds = BLOCK.layer_kinds(model)
    assert [l for l, k in enumerate(kinds) if k == "gqa"] == [0, 4, 8]
    assert BLOCK.state_bytes_per_row_layer(model, moved=False) == 4_489_216
    assert BLOCK.state_bytes_per_row_layer(model) == 8_978_432
    assert BLOCK.arena_bytes_per_token_layer(model) == 4096
    assert BLOCK.held_experts(model) == (0, 40)
    assert BLOCK.total_experts(model) == 320
    assert BLOCK.expert_bytes(model, "int8") == 3 * 4096 * 1280 + 2 * 1280 * 2
    program = harness.model_config(cfg)
    assert program.recurrent and not program.latent_kv
    assert program.recurrent_row_bytes == 4_489_216
    assert program.recurrent_shapes == {
        "kda": (64, 128, 128), "conv": (3, 24576)}
    assert program.layer_kinds == kinds
    assert (program.cache_heads, program.cache_k_dim, program.cache_v_dim) == (
        8, 128, 128)
    assert program.held_experts_ == (0, 40) and program.num_experts == 320
    assert program.kda_beta_scale == 2.0 and program.attn_gate
    assert cfg["eos_token_id"] >= cfg["vocab_size"]  # no reply ends early
    assert cfg["deployment"]["weight_dtype"] == "int8"
    # every published key as the catalog's row has it but those in `reduced`
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(l) for l in f
                       if json.loads(l)["name"] == "Solar-Open2-250B")
        differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
        assert differ == sorted(cfg["reduced"])
        assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
        assert cfg["source"] == row["source_url"]
    assert cfg["num_hidden_layers"] == 12 and cfg["vocab_size"] * 8 == 196608
    assert cfg["gqa_layers"] == cfg["published"]["gqa_layers"][:3]
    # what the chip holds, to the byte: the configuration's own arithmetic
    wd = "int8"
    kda = BLOCK.mixer_fixed_bytes(model, wd, "kda")
    gqa = BLOCK.mixer_fixed_bytes(model, wd, "gqa")
    moe = BLOCK.moe_fixed_bytes(model, wd) + 40 * BLOCK.expert_bytes(model, wd)
    held = 9 * (kda + moe) + 3 * (gqa + moe) + 2 * 24576 * 4096 * 2
    assert held == BLOCK.held_bytes(model, wd)
    assert held / 1e9 == pytest.approx(9.75, abs=0.01)
    assert (kda + moe) / 1e6 == pytest.approx(785.9, abs=0.1)
    assert (gqa + moe) / 1e6 == pytest.approx(756.8, abs=0.1)
    # what the arena and the state hold at the cell's sizes: two thirds of
    # the chip's 16 GB with the weights (the driver's floor is a quarter)
    serve = cfg["serve"]
    arena = serve["kv_blocks"] * serve["kv_block_size"] * 3 * 4096
    state = serve["batch_per_slot"] * 9 * 4_489_216
    assert arena / 1e9 == pytest.approx(0.806, abs=0.001)
    assert state / 1e9 == pytest.approx(0.162, abs=0.001)
    assert 0.66 < (held + arena + state) / 16e9 < 0.68
    # a one-row step at no context reading 1 held expert a layer: 2.8 ms at
    # 819 GB/s, 58% of it the nine KDA mixers; the context adds 12 KB a token
    rec = {"window": [0.0, 1.0], "traced": None, "chips": 1,
           "requests": [{"server_started_at": 0.1, "finished": None}],
           "steps": [{"t": 0.5, "expert_steps": 1, "experts_read": [1] * 12}]}
    step = BLOCK.decode_step_bytes(model, wd, 1, 0.0, rec)
    assert step / 1e9 == pytest.approx(2.26, abs=0.01)
    assert step / 819e9 * 1e3 == pytest.approx(2.76, abs=0.02)
    assert BLOCK.decode_step_bytes(model, wd, 1, 4608.0, rec) - step == (
        3 * 4608 * 4096)
    mixers = 9 * (kda + BLOCK.state_bytes_per_row_layer(model))
    assert 0.56 < mixers / step < 0.60


def test_the_new_metrics_are_entries_with_readers():
    bench = tb.BENCHMARK
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "cot", "solar_open2_250b")
    judged = [m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", (CELL,))]
    assert judged == ["itl_p95_ms", "setup_s"]
    for name in NEW + OLD:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    _, layer, _ = tb._readers(CELL)
    assert set(NEW + OLD) <= set(layer)
    # the eight without a list are read by themselves
    assert {"decode_hbm_pct.chat", "decode_step_ms.chat",
            "device_idle_pct.chat", "host_ms_per_step.chat"} <= set(layer)
    # the traffic is ISSUE 53's, number for number: the think mix's
    think = tb.load(tb.BENCH, "traffic", "think.json")
    for key in ("loop", "greedy", "prompt_len", "output_len", "sharing",
                "cycle_requests", "ramp_s", "tail_s", "trace_s"):
        assert COT[key] == think[key], key
    assert COT["output_len"] == {"dist": "fixed", "value": 4096, "max": 4096}
    assert (COT["cycle_requests"], COT["ramp_s"], COT["tail_s"],
            COT["trace_s"]) == (256, 15.0, 30.0, 6.0)
    assert COT["shape_seed"] not in {
        tb.load(tb.BENCH, "traffic", f)["shape_seed"]
        for f in os.listdir(os.path.join(tb.BENCH, "traffic"))
        if f != "cot.json"}
    assert tb.load(tb.BENCH, "cells", CELL + ".json")["clients_per_row"] == 0.25
    # an untraced run, a model without the mechanism: nothing, and no raise
    rec = {"traced": None, "steps": [], "window": [0.0, 1.0], "requests": [],
           "config": tb.TINY, "chips": 1, "peaks": {"hbm_bytes_per_s": 8e11}}
    for name in NEW:
        assert layer[name][0](dict(rec)) is None, name


def test_the_new_readers_on_recorded_spans():
    """The readers over a reduction as ``span_reduce`` leaves it: the recorded
    trace of a dense model has none of the scopes (nothing to read); with
    them, the shares are the seconds' — and the roofline share is bytes over
    time, counted from the records, and cannot pass 100 while the update
    moves each live row's state once."""
    _, layer, _ = tb._readers(CELL)
    recorded = tb.load(HERE, "data", "span.expect.json")
    rec = {"spans": recorded, "traced": [0.0, 1.0], "window": [0.0, 1.0],
           "config": TINY, "chips": 1, "steps": [], "requests": [],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in NEW:
        assert layer[name][0](rec) is None, name
    spans = {"scopes": {
        "serve_chunk": {"kda_proj": 0.3, "conv": 0.02, "kda": 0.08,
                        "qkv": 0.05, "attn": 0.05, "moe": 0.2, "mlp": 0.3},
        "serve_prefill_chunk": {"kda_proj": 0.004, "conv": 0.001,
                                "kda": 0.003, "moe": 0.002, "mlp": 0.01},
    }}
    rec = dict(
        rec, spans=spans,
        trace={"modules": {"serve_chunk": [[0.001] * 100]}},
        steps=[{"t": 0.4, "rows": 1}, {"t": 0.5, "rows": 1}],
        requests=[{"server_started_at": 0.1, "finished": None,
                   "prompt_len": 30, "stamps": [0.2, 0.3, 0.4]}],
    )
    assert layer["decode_kda_pct.cot"][0](rec) == pytest.approx(40.0)
    assert layer["prefill_kda_pct.cot"][0](rec) == pytest.approx(20.0)
    # one live row x 6 KDA layers x (state + tail, read and written) over the
    # 1 ms of conv + kda a step
    state = 2 * 4 * (1024 * 128 + 3 * 3072)
    want = 100.0 * 1 * 6 * state / 819e9 / (0.1 / 100)
    assert layer["kda_state_hbm_pct.cot"][0](rec) == pytest.approx(want)
    assert 0 < want < 100.0
    bare = dict(rec, spans={"scopes": {"serve_chunk": {"mlp": 1.0}}})
    for name in NEW:
        assert layer[name][0](bare) is None, name
    # a Mamba program's ``conv`` alone is no KDA mixer
    mamba = dict(rec, spans={"scopes": {
        "serve_chunk": {"conv": 0.1, "ssm": 0.2},
        "serve_prefill_chunk": {"conv": 0.1, "ssm": 0.2}}})
    for name in NEW:
        assert layer[name][0](mamba) is None, name
