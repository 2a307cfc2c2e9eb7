"""The ``jamba`` block's own rehearsal (``blocks/jamba.py``, the configuration
``jamba2_3b``, the mix ``agent`` and the three readers PR 45 brought). CPU,
tiny widths, Pallas in interpret mode:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_jamba_block.py -q -p no:cacheprovider

``test_benchmark.py::test_a_configuration_resolves_to_a_block_with_the_programs_leaves``
picks up ``configs/jamba2_3b.json`` as a case by itself.
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

TINY = tb.load(HERE, "data", "tiny_jamba.json")
MODEL = harness.model_keys(TINY)
BLOCK = blocks.load("jamba")
CELL = "jamba2_3b.agent"
AGENT = tb.load(tb.BENCH, "traffic", "agent.json")
NEW = ("decode_ssm_x_pct.agent", "prefill_scan_pct.agent",
       "prefill_scan_hbm_pct.agent")
OLD = ("decode_ssm_pct.agent", "ssm_state_hbm_pct.agent",
       "rows_per_step.agent", "kv_in_use_peak_pct.agent",
       "prefill_ms_per_ktok.agent")

# a toy's limits (no cell has them): ~60 positions of a vocabulary of 512 in
# bfloat16, where one near-tie flipped reads alone what the chip's limit
# allows in the mean
TOY_DELTA_MEAN = 0.04
TOY_DELTA_MAX = 4.0

# what the program is handed in place of the seed's leaves; the reference
# keeps the seed's
WRONG = {
    "sound": None,
    "conv bias dropped": ("mamba", "conv_b"),
    "skip term dropped": ("mamba", "D"),
    "B norm's gain dropped": ("mamba", "b_norm"),
    "dt bias dropped": ("mamba", "dt_bias"),
}


def run_agent(tmp_path, readers, seconds=4.0):
    """``harness.run_cell`` with the ``agent`` mix at toy lengths: ONE client
    on the toy's two rows, every reply the same length."""
    traffic = json.loads(json.dumps(AGENT))
    traffic["prompt_len"].update(median=24, max=100)
    traffic["output_len"].update(value=12, max=12)
    traffic.update(ramp_s=1.0, tail_s=4.0)
    return harness.run_cell(
        cell={"name": "tiny.agent"}, cfg_file=json.loads(json.dumps(TINY)),
        block=BLOCK, traffic=traffic, cell_params={"clients_per_row": 0.5},
        devices=jax.devices()[:1], seed=2**31 + 9, seconds=seconds,
        trace=False, out_dir=str(tmp_path), t_process=time.perf_counter(),
        readers=readers, attn="auto",
    )


def test_the_tiny_configuration_is_a_case_of_the_leaves_test():
    tb.test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        os.path.join(HERE, "data", "tiny_jamba.json"), blocks.HERE)
    assert blocks.kinds(BLOCK, MODEL) == (
        "mamba", "mamba", "attn", "mamba", "mamba", "mamba")
    assert [t.name for t in BLOCK.tables(MODEL)] == ["embed", "final_norm"]


def test_the_real_configuration_is_a_case_of_the_leaves_test():
    tb.test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        os.path.join(tb.BENCH, "configs", "jamba2_3b.json"), blocks.HERE)


def test_the_weight_rules_make_the_mechanism_visible():
    params = weights.make_params(BLOCK, MODEL, 7, "bf16", jax.devices()[:1])
    assert "lm_head" not in params  # the head is the table
    mamba = {k: np.asarray(v, np.float32)
             for k, v in params["layers"]["mamba"].items()}
    dt = np.log1p(np.exp(mamba["dt_bias"]))  # softplus: the seeded dt
    assert 0.9e-3 < dt.min() and dt.max() < 0.11  # log-uniform in [1e-3, 0.1]
    # A_log[c, n] = log(n + 1), as published: a decay per state value
    want = np.log(np.arange(1, 9, dtype=np.float32))
    assert np.abs(mamba["A_log"] - want).max() < 0.01  # bf16
    assert mamba["A_log"].shape[1:] == (256, 8)
    for name in ("D", "conv_b", "conv_w", "dt_norm", "b_norm", "c_norm",
                 "norm", "post_norm"):
        assert np.abs(mamba[name]).min() > 0, name  # never zero
    for name in ("D", "dt_norm", "b_norm", "c_norm"):
        assert np.abs(mamba[name] - 1).max() > 0.05, name
    # down projections whose columns sum to zero over their rows
    for kind, leaf in (("mamba", "w_out"), ("mamba", "w_down"),
                       ("attn", "w_down")):
        w = np.asarray(params["layers"][kind][leaf], np.float32)
        assert np.abs(w.sum(axis=1)).max() < 0.02 * np.abs(w).sum(axis=1).min()
        assert w.std() > 0.5 * w.shape[1] ** -0.5, leaf  # the scale stands
    # logits of about unit variance against the tied table
    table = np.asarray(params["embed"], np.float32)
    assert 0.8 < np.linalg.norm(table, axis=1).mean() < 1.2


@pytest.mark.parametrize("what", list(WRONG))
def test_the_agent_cell_runs_through_the_harness(what, tmp_path, monkeypatch):
    """A tiny ``jamba`` configuration (two kinds of layer, bf16 weights, a
    tied head) served paged through ``harness.run_cell`` under the ``agent``
    mix with ONE client is correct, its step records carry the recurrent
    state's counters and the host-side readers read them — and it is not
    correct when the program is handed no conv bias, no skip term, no gain
    of the ``B`` norm or no ``dt`` bias."""
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
    got = run_agent(tmp_path, e2e)
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
    rows = layer["rows_per_step.agent"][0](rec)
    assert 0.7 <= rows <= 1.0 and max(s["rows"] for s in rec["steps"]) == 1
    assert len(rec["requests"]) > 2
    # the recurrent state's counters: one row holds a state while it decodes;
    # every prompt went through the scan in whole chunks of 32 x 2 rows
    held = [s["recurrent_rows"] for s in rec["steps"] if "recurrent_rows" in s]
    assert held and set(held) == {1}
    scanned = [s["scan_positions"] for s in rec["steps"] if "scan_positions" in s]
    assert scanned and all((s["real"] + s["pad"]) % 64 == 0 for s in scanned)
    assert not any(s.get("expert_steps") for s in rec["steps"])  # no experts
    assert 0 < layer["kv_in_use_peak_pct.agent"][0](rec) < 100
    for name in NEW + OLD[:2] + OLD[4:]:
        assert layer[name][0](rec) is None, name  # untraced: nothing to read
    # and the byte count takes what the records say
    rec["traced"] = rec["window"]
    live = BLOCK.live_rows(rec)
    assert 0.7 <= live <= 1.0
    assert BLOCK.decode_step_bytes(MODEL, "bf16", 1, 10.0, rec) == pytest.approx(
        hand_count(10.0, live))


def hand_count(live_tokens, rows):
    """Bytes of a decode microstep of the tiny model, by hand (bf16)."""
    H, V, F = 128, 512, 192
    di, ds, R, K = 256, 8, 8, 4
    mlp = 2 * (3 * H * F + H)
    mamba = 2 * (H + H * 2 * di + K * di + di + di * (R + 2 * ds) + R + 2 * ds
                 + R * di + di + di * ds + di + di * H) + mlp
    state = 2 * 4 * (di * ds + (K - 1) * di)  # read and written
    attn = 2 * (H + H * 4 * 32 + 2 * H * 32 + 4 * 32 * H) + mlp
    kv = live_tokens * 2 * 1 * 32 * 2
    return 5 * (mamba + rows * state) + attn + kv + H * V * 2


def test_the_real_configuration_states_what_a_request_holds():
    cfg = tb.load(tb.BENCH, "configs", "jamba2_3b.json")
    model = harness.model_keys(cfg)
    assert BLOCK.kind_layers(model) == {"mamba": 26, "attn": 2}
    kinds = BLOCK.layer_kinds(model)
    assert [l for l, k in enumerate(kinds) if k == "attn"] == [7, 21]
    assert BLOCK.state_bytes_per_row_layer(model, moved=False) == 389_120
    assert BLOCK.state_bytes_per_row_layer(model) == 778_240
    assert BLOCK.arena_bytes_per_token_layer(model) == 512
    program = harness.model_config(cfg)
    assert program.recurrent and program.recurrent_row_bytes == 389_120
    assert program.layer_kinds == kinds
    assert (program.cache_heads, program.cache_k_dim, program.cache_v_dim) == (
        1, 128, 128)
    assert cfg["eos_token_id"] >= cfg["vocab_size"]  # no reply ends early
    assert cfg["reduced"] == [] and cfg["deployment"]["weight_dtype"] == "bf16"
    # the whole model, to the byte: 3.03 B parameters, 6.06 GB of bf16, of
    # which the one-row decode step reads all but the embedding's gather
    assert BLOCK.total_params(model) == 3_029_337_472
    step = BLOCK.decode_step_bytes(model, "bf16", 1, 0.0)
    assert step == 2 * BLOCK.total_params(model) - 2 * 2560 + 26 * 778_240
    assert step / 1e9 == pytest.approx(6.08, abs=0.01)
    assert BLOCK.decode_step_bytes(model, "bf16", 1, 1000.0) - step == 2 * 512e3
    # the mixers' projections are a third of a step's bytes, the tied table
    # a twentieth
    proj = 26 * 2 * (2560 * 10240 + 5120 * 2560)
    assert 0.33 < proj / step < 0.35
    assert 0.054 < 2 * 65536 * 2560 / step < 0.056
    # every published key as the catalog's row has it, nothing reduced
    from llm_sharding_tpu.models.config import jamba2_3b_keys

    published = jamba2_3b_keys()
    assert [k for k, v in published.items() if cfg[k] != v] == []
    # what the arena and the state hold at the cell's sizes
    serve = cfg["serve"]
    arena = serve["kv_blocks"] * serve["kv_block_size"] * 2 * 512
    assert arena / 1e6 == pytest.approx(67.1, abs=0.1)
    assert serve["batch_per_slot"] * 26 * 389_120 / 1e6 == pytest.approx(
        40.5, abs=0.1)


def test_the_scan_counts():
    cfg = tb.load(tb.BENCH, "configs", "jamba2_3b.json")
    model = harness.model_keys(cfg)
    assert BLOCK.scan_flops(model, 256) == 256 * 5120 * 16 * 7
    # x, dt, z in and y out a channel, B and C a state value; the state once
    assert BLOCK.scan_bytes(model, 256) == 256 * (4 * 5120 + 32) * 4 + 8 * 5120 * 16
    # nothing of shape [positions, d_inner, state] is among them: that array
    # alone (84 MB a row, 335 MB at 4 rows) is four times the whole count
    assert 256 * 5120 * 16 * 4 > 3.8 * BLOCK.scan_bytes(model, 256)
    assert BLOCK.scan_bytes(model, 0) == 8 * 5120 * 16


def test_the_new_metrics_are_entries_with_readers():
    bench = tb.BENCHMARK
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "agent", "jamba2_3b")
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
    # the traffic is ISSUE 45's, number for number
    assert AGENT["loop"] == "closed" and AGENT["greedy"]
    assert AGENT["prompt_len"] == {"dist": "lognormal", "median": 192,
                                   "sigma": 1.0, "min": 16, "max": 512}
    assert AGENT["output_len"]["dist"] == "fixed"
    assert AGENT["output_len"]["value"] == 512
    assert (AGENT["cycle_requests"], AGENT["ramp_s"], AGENT["tail_s"],
            AGENT["trace_s"]) == (256, 15.0, 30.0, 8.0)
    assert AGENT["sharing"] == {"kind": "none"}
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
    time, counted from the program's counter of REAL positions, and cannot
    pass 100 while the scan moves each position's operands once."""
    _, layer, _ = tb._readers(CELL)
    recorded = tb.load(HERE, "data", "span.expect.json")
    rec = {"spans": recorded, "traced": [0.0, 1.0], "window": [0.0, 1.0],
           "config": TINY, "chips": 1, "steps": [], "requests": [],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in NEW + OLD[:2]:
        assert layer[name][0](rec) is None, name
    spans = {"scopes": {
        "serve_chunk": {"ssm_proj": 0.3, "conv": 0.02, "ssm": 0.08,
                        "ssm_x": 0.1, "attn": 0.05, "mlp": 0.45},
        "serve_prefill_chunk": {"ssm_proj": 0.004, "conv": 0.001,
                                "ssm": 0.003, "ssm_x": 0.001, "mlp": 0.011},
    }}
    rec = dict(
        rec, spans=spans,
        trace={"modules": {"serve_chunk": [[0.001] * 100]}},
        steps=[{"t": 0.4, "rows": 1, "scan_positions": {"real": 90, "pad": 38}},
               {"t": 0.5, "rows": 1},
               {"t": 1.5, "rows": 1, "scan_positions": {"real": 64, "pad": 0}}],
        requests=[{"server_started_at": 0.1, "finished": None,
                   "prompt_len": 30, "stamps": [0.2, 0.3, 0.4]}],
    )
    assert layer["decode_ssm_x_pct.agent"][0](rec) == pytest.approx(10.0)
    assert layer["decode_ssm_pct.agent"][0](rec) == pytest.approx(40.0)
    assert layer["prefill_scan_pct.agent"][0](rec) == pytest.approx(20.0)
    # 90 real positions (the step past the slice is not counted, the pads
    # neither) x 5 mixers x what one pass moves, over the 3 ms under ``ssm``
    need = 5 * (90 * (4 * 256 + 16) * 4 + 8 * 256 * 8)
    want = 100.0 * need / 819e9 / 0.003
    assert layer["prefill_scan_hbm_pct.agent"][0](rec) == pytest.approx(want)
    assert 0 < want < 100.0
    # one live row x 5 mixers x (state + tail, read and written) over the
    # 1 ms of conv + ssm a step
    state = 2 * 4 * (256 * 8 + 3 * 256)
    want = 100.0 * 1 * 5 * state / 819e9 / (0.1 / 100)
    assert layer["ssm_state_hbm_pct.agent"][0](rec) == pytest.approx(want)
    assert want < 100.0
    # a slice without a chunk, a program without the counter: nothing
    none = dict(rec, steps=[{"t": 0.5, "rows": 1}])
    assert layer["prefill_scan_hbm_pct.agent"][0](none) is None
    bare = dict(rec, spans={"scopes": {"serve_chunk": {"mlp": 1.0}}})
    for name in NEW:
        assert layer[name][0](bare) is None, name
