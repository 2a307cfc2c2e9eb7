"""The ``ouro`` block's own rehearsal (``blocks/ouro.py``, the configuration
``ouro_2p6b``, the mix ``ponder`` and the two readers PR 60 brought). CPU, tiny
widths, Pallas in interpret mode:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_ouro_block.py -q -p no:cacheprovider

``test_benchmark.py::test_a_configuration_resolves_to_a_block_with_the_programs_leaves``
and ``::test_a_cell_reports_what_it_is_judged_on_and_what_moves_that`` pick up
``configs/ouro_2p6b.json`` and ``ouro_2p6b.ponder`` as cases by themselves.
"""

import dataclasses
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

from benchmark import blocks, harness, roofline, weights  # noqa: E402

TINY = tb.load(HERE, "data", "tiny_ouro.json")
MODEL = harness.model_keys(TINY)
BLOCK = blocks.load("ouro")
CELL = "ouro_2p6b.ponder"
PONDER = tb.load(tb.BENCH, "traffic", "ponder.json")
NEW = ("decode_pass_close_pct.ponder", "exit_last_pass_pct.ponder")
BY_STEM = ("attn_kv_hbm_pct.ponder", "rows_per_step.ponder",
           "kv_in_use_peak_pct.ponder")

# The block's limits were read on the chip over ~3,000 positions of a
# vocabulary of 49,152; this toy scores ~200 of a vocabulary of 512 at hidden
# 128, where one pick chosen the other way at a near-tie reads alone what the
# chip's limit allows in the mean. A toy's limits: no cell has them. Over the
# first TOY_SAMPLES finished requests the sound toy reads a mean of 0.001-0.004
# (bf16, nine layer calls a token); the mildest wrong program below (the
# head's second norm) 0.2, the others 2 to 4 (CPU, PR 60: counts of a toy).
TOY_DELTA_MEAN = 0.03
TOY_DELTA_MAX = 4.0
TOY_SAMPLES = 16

WRONG = ("sound", "slot offset dropped", "a pass fewer",
         "an output norm dropped")


def first_finished(tracked, seed):
    """The first ``TOY_SAMPLES`` requests of the client that finished, in the
    order sent (``test_deepseek_v3_block.py`` says why not the harness's draw
    of 8: how many finish follows the machine's speed)."""
    done = [t for t in tracked
            if t.req is not None and t.req.done and t.error is None
            and len(t.req.tokens) > 0]
    return [(np.asarray(t.plan.prompt), np.asarray(t.req.tokens))
            for t in done[:TOY_SAMPLES]]


def run_ponder(tmp_path, readers, seconds=4.0):
    """``harness.run_cell`` with the ``ponder`` mix at toy lengths: ONE client
    on the toy's two rows, every reply the same length."""
    traffic = json.loads(json.dumps(PONDER))
    traffic["prompt_len"].update(median=24, max=100)
    traffic["output_len"].update(value=12, max=12)
    traffic.update(ramp_s=1.0, tail_s=4.0)
    return harness.run_cell(
        cell={"name": "tiny.ponder"}, cfg_file=json.loads(json.dumps(TINY)),
        block=BLOCK, traffic=traffic, cell_params={"clients_per_row": 0.5},
        devices=jax.devices()[:1], seed=2**31 + 9, seconds=seconds,
        trace=False, out_dir=str(tmp_path), t_process=time.perf_counter(),
        readers=readers, attn="auto",
    )


def test_the_tiny_configuration_is_a_case_of_the_leaves_test():
    tb.test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        os.path.join(HERE, "data", "tiny_ouro.json"), blocks.HERE)
    assert blocks.kinds(BLOCK, MODEL) is None  # every layer is one kind
    assert blocks.looped(BLOCK) and blocks.passes(BLOCK, MODEL) == 3
    assert BLOCK.dims(MODEL)["layers"] == 3  # ONE pass's layers
    names = [leaf.name for leaf in BLOCK.layer_leaves(MODEL)]
    assert len(names) == len(set(names)) == 11  # four norms, seven matmuls
    assert not any(n.startswith("b") for n in names)  # no bias
    assert [t.name for t in BLOCK.tables(MODEL)] == [
        "embed", "final_norm", "lm_head", "exit_gate", "exit_bias"]
    with pytest.raises(ValueError, match=r"ouro\.py.*run 0 times"):
        blocks.passes(BLOCK, dict(MODEL, total_ut_steps=0))


def test_the_draw_is_never_a_gain_of_one_or_a_bias_of_zero():
    """What the module docstring's "Weights" promises, read from the leaves:
    the input norms' gains and the final norm's are 1 + 0.1 n, the output
    norms' 0.25 (1 + 0.1 n), the gate's vector has a length of about 1 and its
    bias is not zero."""
    params = weights.make_params(BLOCK, MODEL, 7, "bf16", jax.devices()[:1])
    lay = {k: np.asarray(v, np.float32) for k, v in params["layers"].items()}
    for name in BLOCK.NORM_LEAVES:
        assert lay[name].shape == (3, 128)
        scale = 0.25 if name in BLOCK.OUT_NORM_LEAVES else 1.0
        assert 0.05 < np.abs(lay[name] / scale - 1).mean() < 0.15, name
    assert 0.05 < np.abs(np.asarray(params["final_norm"], np.float32) - 1).mean()
    gate = np.asarray(params["exit_gate"], np.float32)
    assert gate.shape == (128,) and 0.7 < np.linalg.norm(gate) < 1.3
    assert np.asarray(params["exit_bias"]).shape == (1,)
    assert abs(float(params["exit_bias"][0])) > 0


@pytest.mark.parametrize("what", WRONG)
def test_the_ponder_cell_runs_through_the_harness(
        what, tmp_path, monkeypatch, request):
    """A tiny ``ouro`` configuration (three layers three times a token, nine
    arena slots) served paged through ``harness.run_cell`` under the
    ``ponder`` mix with ONE client is correct, its step records carry the exit
    passes and the host-side readers read them — and it is not correct when
    the program drops the pass's slot offset, runs a pass fewer or is handed
    layers without an output norm. (The head norming once more is NOT among
    them: a second norm of a closed state scales each channel by its gain, 1
    + 0.1 n, which moves the logits by a tenth and the served TOKENS' margins
    by 0.002 on this toy — the test below holds it on the logits, and
    ``tests/test_ouro.py::test_each_wrong_model_fails_the_tolerance`` on the
    program's.)"""
    from llm_sharding_tpu.models import stack

    # (a patched helper is traced into a NEW program only: the step programs
    # of the case before are in jit's cache under the same key)
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)
    if what == "slot offset dropped":
        monkeypatch.setattr(stack, "_slot", lambda i, first_layer: i)
    elif what == "a pass fewer":
        config = harness.model_config
        monkeypatch.setattr(
            harness, "model_config",
            lambda f: dataclasses.replace(config(f), passes=2))
    elif what == "an output norm dropped":
        make, calls = weights.make_params, []

        def served_wrong(*args, **kw):
            params = make(*args, **kw)
            calls.append(1)
            if len(calls) > 1:  # the second is the check's
                return params
            layers = {k: v for k, v in params["layers"].items()
                      if k != "attn_out_norm"}
            return dict(params, layers=layers)

        monkeypatch.setattr(weights, "make_params", served_wrong)
    monkeypatch.setattr(BLOCK, "DELTA_MEAN", TOY_DELTA_MEAN)
    monkeypatch.setattr(BLOCK, "DELTA_MAX", TOY_DELTA_MAX)
    monkeypatch.setattr(harness, "pick_samples", first_finished)
    e2e, layer, bench = tb._readers(CELL)
    got = run_ponder(tmp_path, e2e)
    res, rec = got["result"], got["records"]
    assert rec["reference"]["positions"] > 20
    assert res["failed"] == 0 and rec["paths"]["attn_backend"] == "interpret"
    # judged on the gap and the set-up alone (PERF.md section 2)
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert rec["paths"]["arena_dtype"] == ["bfloat16"] and rec["arena_ok"]
    print(what, rec["reference"])
    assert res["correct"] == (what == "sound"), rec["reference"]
    if what != "sound":
        assert rec["kernels_ok"]
        assert rec["reference"]["margin_mean"] > 2 * TOY_DELTA_MEAN
        return
    # one client: never more than one live row; every reply the same length
    rows = layer["rows_per_step.ponder"][0](rec)
    assert 0.7 <= rows <= 1.0 and max(s["rows"] for s in rec["steps"]) == 1
    done = [r for r in rec["requests"] if r["finished"] is not None]
    # (how many finish in four seconds follows the machine's load)
    assert done and {len(r["stamps"]) for r in done} == {12}
    # the counter: three passes wide, everything at the last (threshold 1)
    steps = [s for s in rec["steps"] if s.get("exit_passes")]
    assert steps and all(len(s["exit_passes"]) == 3 for s in steps)
    assert layer["exit_last_pass_pct.ponder"][0](rec) == 100.0
    assert 0 < layer["kv_in_use_peak_pct.ponder"][0](rec) < 100
    # the device-side readers have nothing to read in an untraced run
    for name in ("decode_pass_close_pct.ponder", "attn_kv_hbm_pct.ponder"):
        assert layer[name][0](rec) is None, name


def test_the_heads_second_norm_moves_the_logits():
    """``logits`` is handed CLOSED states and norms nothing: with the final
    norm applied once more the reference's own logits move by far more than
    any tolerance of a logit test (a tenth of their size)."""
    import jax.numpy as jnp

    params = weights.make_params(BLOCK, MODEL, 7, "bf16", jax.devices()[:1])
    tables = {t.name: params[t.name] for t in BLOCK.tables(MODEL)}
    h = jax.random.normal(jax.random.key(1), (3, 20, 128), jnp.float32)
    kw = BLOCK.head_static(MODEL)
    closed = jnp.stack([BLOCK.close_pass(x, tables, step=0, **kw) for x in h])
    once = BLOCK.logits(closed, tables, **kw)
    again = BLOCK.logits(closed, tables, head_norm=True, **kw)
    assert once.shape == (20, 512)
    assert float(jnp.abs(once - again).max()) > 0.1


def test_bytes_of_a_decode_step():
    """``test_benchmark.py::test_bytes_of_a_decode_step``'s case for this
    block, at the published sizes: the layers FOUR times, the live K/V of four
    passes, the head once — by hand."""
    cfg = tb.load(tb.BENCH, "configs", "ouro_2p6b.json")
    model = harness.model_keys(cfg)
    H, I, V, L, T = 2048, 5632, 49152, 48, 4
    layer = 2 * (4 * H * H + 3 * H * I + 4 * H)
    assert BLOCK.layer_weight_bytes(model, "bf16") == layer == 102776832
    assert BLOCK.dims(model)["layers"] == L
    kv = roofline.kv_bytes_per_token_layer(BLOCK.dims(model))
    assert kv == 2 * 16 * 128 * 2 and T * L * kv == 1572864  # 1.5 MiB a token
    got = BLOCK.decode_step_bytes(model, "bf16", 1, 700.0)
    assert got == T * L * layer + H * V * 2 + T * L * 700.0 * kv
    assert got / 1e9 == pytest.approx(21.0, abs=0.1)  # 19.7 + 0.2 + 1.1
    # int8 would count a scale an output channel; one pass is the llama block
    one = dict(model, total_ut_steps=1)
    assert BLOCK.decode_step_bytes(one, "bf16", 1, 700.0) == (
        L * layer + H * V * 2 + L * 700.0 * kv)
    assert BLOCK.layer_weight_bytes(model, "int8") == (
        layer // 2 + 2 * H * 2  # the four gains stay bf16
        + 2 * (3 * H + H + 2 * I + H))
    # what the attention must read: live tokens x 8,192 B (K and V) x 48 x 4
    rec = {"traced": [0.0, 1.0], "window": [0.0, 1.0], "chips": 1,
           "steps": [{"t": 0.5}],
           "requests": [{"server_started_at": 0.1, "finished": None,
                         "prompt_len": 300, "stamps": [0.2, 0.3, 0.4]}]}
    assert BLOCK.attn_kv_bytes(model, rec, 0.0, 1.0) == 303 * 8192 * L * T
    assert BLOCK.attn_kv_bytes(model, dict(rec, steps=[]), 0.0, 1.0) is None


def test_the_real_configuration_states_what_it_holds():
    """Every published key as the catalog row has it, nothing reduced, and
    the bytes the file's layout states."""
    cfg = tb.load(tb.BENCH, "configs", "ouro_2p6b.json")
    model = harness.model_keys(cfg)
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        with open(guide) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
        assert cfg["source"] == row["source_url"]
        assert {k: model[k] for k in row["config"]} == row["config"]
    assert cfg["reduced"] == [] and cfg["eos_token_id"] >= cfg["vocab_size"]
    assert cfg["deployment"]["weight_dtype"] == "bf16"
    program = harness.model_config(cfg)
    assert (program.model_type, program.passes, program.exit_threshold,
            program.out_norms, program.attention_bias, program.arena_slots) == (
        "llama", 4, 1.0, True, False, 4)
    assert (program.num_key_value_heads, program.head_dim_) == (16, 128)
    params = 48 * BLOCK.layer_weight_bytes(model, "bf16") // 2 + sum(
        int(np.prod(t.shape)) for t in BLOCK.tables(model))
    assert params / 1e9 == pytest.approx(2.668, abs=0.001)
    serve = cfg["serve"]
    pool = serve["kv_blocks"] * serve["kv_block_size"] * 1572864
    assert serve["kv_blocks"] >= 129 and pool / 1e9 == pytest.approx(8.10, abs=0.01)
    assert 0.70 * 16e9 < 2 * params + pool < 15.75 * 2**30  # 84% of the chip
    # a one-row decode step at the middle of a reply
    step = BLOCK.decode_step_bytes(model, "bf16", 1, 448.0)
    assert step / 1e9 == pytest.approx(20.6, abs=0.1)
    assert 4 * 48 * BLOCK.layer_weight_bytes(model, "bf16") / step > 0.95


def test_the_new_metrics_are_entries_with_readers():
    bench = tb.BENCHMARK
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "ponder", "ouro_2p6b")
    assert bench["workloads"][-1] is cell and len(bench["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    judged = [m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", (CELL,))]
    assert judged == ["itl_p95_ms", "setup_s"]
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW + BY_STEM)
    for name in NEW + BY_STEM:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    for name in ("token_emit_lag_p50_ms", "token_emit_lag_p95_ms",
                 "landing_gap_p95_ms", "host_bound_steps_pct",
                 "queue_empty_lo_pct", "queue_empty_hi_pct"):
        assert by_name[name]["workloads"][-1] == CELL
    assert (PONDER["loop"], PONDER["output_len"], PONDER["shape_seed"]) == (
        "closed", {"dist": "fixed", "value": 512, "max": 512}, 20261060)
    assert tb.load(tb.BENCH, "cells", CELL + ".json")["clients_per_row"] == 0.25
    _, layer, _ = tb._readers(CELL)
    assert set(NEW + BY_STEM) <= set(layer)
    # an untraced run, a model whose layers run once: nothing, and no raise
    rec = {"traced": None, "steps": [{"t": 0.5, "rows": 1}],
           "window": [0.0, 1.0], "requests": [], "config": tb.TINY,
           "chips": 1, "peaks": {"hbm_bytes_per_s": 8e11}}
    for name in NEW:
        assert layer[name][0](dict(rec)) is None, name


def test_the_new_readers_on_recorded_spans():
    """The readers over a reduction as ``span_reduce`` leaves it: the recorded
    trace of a one-pass model has no ``pass_close`` scope (nothing to read);
    with the scope, the share is the seconds'; the counter's reader takes the
    share at the last pass."""
    _, layer, _ = tb._readers(CELL)
    recorded = tb.load(HERE, "data", "span.expect.json")
    rec = {"spans": recorded, "traced": [0.0, 1.0], "window": [0.0, 1.0],
           "config": TINY, "chips": 1, "steps": [], "requests": [],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert "serve_chunk" in recorded["scopes"]
    assert layer["decode_pass_close_pct.ponder"][0](rec) is None
    assert layer["exit_last_pass_pct.ponder"][0](rec) is None  # no steps
    spans = {"scopes": {"serve_chunk": {
        "attn": 0.1, "mlp": 0.6, "qkv": 0.15, "o_proj": 0.1, "norm": 0.03,
        "pass_close": 0.02}}}
    rec = dict(
        rec, spans=spans,
        trace={"modules": {"serve_chunk": [[0.001] * 100]}},
        steps=[{"t": 0.5, "rows": 1, "exit_passes": [0, 1, 3]},
               {"t": 0.6, "rows": 1, "exit_passes": [0, 0, 4]},
               {"t": 0.7, "rows": 1}],
        requests=[{"server_started_at": 0.1, "finished": None,
                   "prompt_len": 300, "stamps": [0.2, 0.3, 0.4]}],
    )
    assert layer["decode_pass_close_pct.ponder"][0](rec) == pytest.approx(2.0)
    assert layer["exit_last_pass_pct.ponder"][0](rec) == pytest.approx(87.5)
    # 303 live tokens x 2 x 4 heads x 32 x 2 B x 3 layers x 3 passes over 1 ms
    # of attn a step
    want = 100.0 * 303 * 512 * 9 / 819e9 / (0.1 / 100)
    assert layer["attn_kv_hbm_pct.ponder"][0](rec) == pytest.approx(want)
    assert want < 100.0
