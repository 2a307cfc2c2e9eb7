"""The ``deepseek_v3`` block's own rehearsal (``blocks/deepseek_v3.py``, the
configuration ``gigachat31_702b_a36b``, the mix ``stream`` and the three
readers PR 34 brought). CPU, tiny widths, Pallas in interpret mode:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_deepseek_v3_block.py -q -p no:cacheprovider

``test_benchmark.py::test_a_configuration_resolves_to_a_block_with_the_programs_leaves``
picks up ``configs/gigachat31_702b_a36b.json`` as a case by itself.
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

TINY = tb.load(HERE, "data", "tiny_deepseek_v3.json")
MODEL = harness.model_keys(TINY)
BLOCK = blocks.load("deepseek_v3")
CELL = "gigachat31_702b_a36b.stream"
STREAM = tb.load(tb.BENCH, "traffic", "stream.json")
NEW = ("decode_absorb_pct.stream", "latent_attn_hbm_pct.stream",
       "expert_pairs_held_pct.stream")

# The block's DELTA_MEAN was read on the chip over ~1,000 positions of a
# vocabulary of 16,032; this toy scores ~60 of a vocabulary of 512, where one
# held expert chosen the other way at a near-tie reads alone what the chip's
# limit allows in the mean. A toy's limit: no cell has it. Over the first
# TOY_SAMPLES finished requests (201 positions; ``first_finished`` says why
# not the harness's draw of 8) the sound toy reads 0.0118 at this seed; the
# dropped correction bias (0.01 n: blocks/deepseek_v3.py says why not more)
# 0.0925, the dropped shared expert 0.497, the dropped dense MLP 0.975 (CPU,
# PR 59: counts of a toy, the same in every run).
TOY_DELTA_MEAN = 0.04
TOY_DELTA_MAX = 4.0
TOY_SAMPLES = 24

# what the program is handed in place of the seed's leaves; the reference
# keeps the seed's
WRONG = {
    "sound": None,
    "correction bias dropped": ("moe", "router_bias"),
    "shared expert dropped": ("moe", "ws_down"),
    "the dense kind's MLP dropped": ("dense", "w_down"),
}


def first_finished(tracked, seed):
    """The first ``TOY_SAMPLES`` requests of the client that finished, in the
    order sent (any machine finishes them: this one ~70 in the five seconds).
    ``harness.pick_samples`` draws among ALL that finished, and how many do
    follows the machine's speed: the dropped correction bias read 0.0076,
    0.0105 and 0.069 in one hour on one machine (PR 59), where its limit
    stands at 0.04 and the test wants twice that."""
    done = [t for t in tracked
            if t.req is not None and t.req.done and t.error is None
            and len(t.req.tokens) > 0]
    return [(np.asarray(t.plan.prompt), np.asarray(t.req.tokens))
            for t in done[:TOY_SAMPLES]]


def run_stream(tmp_path, readers, clients_per_row=0.5, seconds=4.0):
    """``harness.run_cell`` with the ``stream`` mix at toy lengths: ONE client
    on the toy's two rows."""
    traffic = json.loads(json.dumps(STREAM))
    traffic["prompt_len"].update(median=24, max=100)
    traffic["output_len"].update(median=8, max=24, min=2)
    traffic.update(ramp_s=1.0, tail_s=4.0)
    return harness.run_cell(
        cell={"name": "tiny.stream"}, cfg_file=json.loads(json.dumps(TINY)),
        block=BLOCK, traffic=traffic,
        cell_params={"clients_per_row": clients_per_row},
        devices=jax.devices()[:1], seed=2**31 + 9, seconds=seconds,
        trace=False, out_dir=str(tmp_path), t_process=time.perf_counter(),
        readers=readers, attn="auto",
    )


def test_the_tiny_configuration_is_a_case_of_the_leaves_test():
    tb.test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        os.path.join(HERE, "data", "tiny_deepseek_v3.json"), blocks.HERE)
    assert blocks.kinds(BLOCK, MODEL) == ("dense", "moe", "moe")
    assert BLOCK.held_experts(MODEL) == (4, 4) and BLOCK.total_experts(MODEL) == 8


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("seed", [7, 2**31 + 3])
def test_the_weights_of_a_seed_are_bit_for_bit_those_pr_34_drew(seed, dtype):
    """The leaf order, keys and rules of both kinds, pinned: a later PR that
    reorders a leaf changes every served id of the cell."""
    recorded = tb.load(HERE, "data", "tiny_deepseek_v3.digests.json")["digests"]
    params = weights.make_params(BLOCK, MODEL, seed, dtype, jax.devices()[:1])
    assert tb.digests(params) == recorded[f"{seed}.{dtype}"]
    moe = params["layers"]["moe"]
    bias = np.asarray(moe["router_bias"], np.float32)
    assert bias.shape == (2, 8) and np.abs(bias).min() > 0  # never zero
    assert np.abs(np.asarray(moe["q_a_norm"], np.float32) - 1).max() > 0.05


@pytest.mark.parametrize("what", list(WRONG))
def test_the_stream_cell_runs_through_the_harness(what, tmp_path, monkeypatch):
    """A tiny ``deepseek_v3`` configuration (two kinds of layer, half the
    experts held) served paged through ``harness.run_cell`` under the
    ``stream`` mix with ONE client is correct, its step records carry the
    experts' counters and the host-side readers read them — and it is not
    correct when the program is handed a zero correction bias, no shared
    expert, or a dense kind without its MLP."""
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
    monkeypatch.setattr(harness, "pick_samples", first_finished)
    e2e, layer, bench = tb._readers(CELL)
    got = run_stream(tmp_path, e2e)
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
    # one client: never more than one live row, and a closed loop that kept
    # sending
    rows = layer["rows_per_step.stream"][0](rec)
    assert 0.7 <= rows <= 1.0 and max(s["rows"] for s in rec["steps"]) == 1
    assert len(rec["requests"]) > 2
    # the counters: held experts only are read, every routed pair is counted
    k, E, L = 2, 8, 3
    steps = [s for s in rec["steps"] if s.get("expert_steps")]
    assert steps
    for s in steps:
        assert len(s["experts_read"]) == L and len(s["expert_tokens"]) == E
        assert s["experts_read"][0] == 0  # the dense layer reads none
        assert max(s["experts_read"]) <= k * s["expert_rows"]
    n = layer["experts_read_per_layer.stream"][0](rec)
    assert 0 < n <= k * 2 / 3  # at most k in each of 2 of the 3 layers
    held = layer["expert_pairs_held_pct.stream"][0](rec)
    assert 20.0 < held < 80.0  # half the experts held: neither none nor all
    # the device-side readers have nothing to read in an untraced run
    for name in ("decode_moe_pct.stream", "moe_hbm_pct.stream",
                 "decode_absorb_pct.stream", "latent_attn_hbm_pct.stream"):
        assert layer[name][0](rec) is None, name
    # and the byte count takes what the records say
    rec["traced"] = rec["window"]
    assert BLOCK.decode_step_bytes(MODEL, "int8", 1, 10.0, rec) == pytest.approx(
        hand_count(n, 10.0))
    assert (n * BLOCK.dims(MODEL)["layers"] * BLOCK.expert_bytes(MODEL, "int8")
            == pytest.approx(n * 3 * (3 * 128 * 64 + 2 * 64 * 2)))


def hand_count(experts_read, live_tokens):
    """Bytes of a decode microstep of the tiny model, by hand (int8)."""
    H, Nh, dn, dr, dv, rq, rkv = 128, 4, 32, 16, 48, 48, 64
    I, F, E, V, L = 256, 64, 8, 512, 3
    mm = lambda i, o: i * o + o * 2  # an int8 matmul and its bf16 scales
    attn = (mm(H, rq) + mm(rq, Nh * (dn + dr)) + mm(H, 128)  # 80 padded
            + mm(Nh * dn, rkv) + mm(Nh * dv, rkv) + mm(Nh * dv, H)
            + 2 * (H + H + rq + rkv))  # four gains
    dense = mm(H, I) + mm(H, I) + mm(I, H)
    fixed = (mm(H, F) + mm(H, F) + mm(F, H)  # the shared expert
             + (H * E + E) * 2 + H * 2)  # router, bias; we_down's scale
    expert = 3 * H * F + 2 * F * 2
    latents = live_tokens * 128 * 2  # [c_kv 64 | k_pe 16] in 128 lanes, once
    return (L * attn + dense + 2 * fixed + experts_read * L * expert
            + H * V * 2 + L * latents)


def test_the_real_configuration_states_what_the_arena_holds():
    cfg = tb.load(tb.BENCH, "configs", "gigachat31_702b_a36b.json")
    model = harness.model_keys(cfg)
    assert BLOCK.arena_bytes_per_token_layer(model) == 1280
    assert "1280" in cfg["assumed"]["arena_bytes_per_token_layer"]
    program = harness.model_config(cfg)
    assert (program.cache_heads, program.cache_k_dim, program.cache_v_dim) == (
        1, 640, 0)
    assert BLOCK.held_experts(model) == (0, 16) and BLOCK.total_experts(model) == 256
    assert cfg["eos_token_id"] >= cfg["vocab_size"]  # outside the held slice
    assert blocks.kinds(BLOCK, model).count("dense") == 1
    assert blocks.kinds(BLOCK, model).count("moe") >= 8
    # the cut's arithmetic, as PERF.md section 4 states it (MB of int8)
    # 132.58 M published parameters + 0.46 MB of zero columns that pad wkv_a
    assert BLOCK.attention_bytes(model, "int8") / 1e6 == pytest.approx(133.1, abs=0.1)
    assert BLOCK.expert_bytes(model, "int8") / 1e6 == pytest.approx(44.04, abs=0.02)
    assert BLOCK.dense_mlp_bytes(model, "int8") / 1e6 == pytest.approx(396.4, abs=0.2)


def test_the_new_metrics_are_entries_with_readers():
    bench = tb.BENCHMARK
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "stream")
    judged = [m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", (CELL,))]
    assert judged == ["itl_p95_ms", "setup_s"]
    for name in NEW + ("decode_moe_pct.stream", "moe_hbm_pct.stream",
                       "experts_read_per_layer.stream", "rows_per_step.stream"):
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
    trace of a dense model has no ``absorb`` scope (nothing to read); with the
    scope, the shares are the seconds' — and the roofline share is bytes over
    time and cannot pass 100 while the kernel reads each latent once."""
    _, layer, _ = tb._readers(CELL)
    recorded = tb.load(HERE, "data", "span.expect.json")
    rec = {"spans": recorded, "traced": [0.0, 1.0], "window": [0.0, 1.0],
           "config": TINY, "chips": 1, "steps": [], "requests": [],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert "serve_chunk" in recorded["scopes"]
    assert layer["decode_absorb_pct.stream"][0](rec) is None
    spans = {"scopes": {"serve_chunk": {
        "absorb": 0.2, "attn": 0.1, "mlp": 0.5, "moe": 0.1, "router": 0.1}}}
    t = 0.5
    rec = dict(
        rec, spans=spans,
        trace={"modules": {"serve_chunk": [[0.001] * 100]}},
        steps=[{"t": t, "rows": 1, "expert_steps": 1, "experts_read": [0, 1, 0],
                "expert_tokens": [1, 0, 0, 0, 2, 1, 0, 0]}],
        requests=[{"server_started_at": 0.1, "finished": None,
                   "prompt_len": 300, "stamps": [0.2, 0.3, 0.4]}],
    )
    assert layer["decode_absorb_pct.stream"][0](rec) == pytest.approx(20.0)
    assert layer["decode_moe_pct.stream"][0](rec) == pytest.approx(20.0)
    # 303 live tokens x 256 bytes x 3 layers over 1 ms of attn a step
    want = 100.0 * 303 * 256 * 3 / 819e9 / (0.1 / 100)
    assert layer["latent_attn_hbm_pct.stream"][0](rec) == pytest.approx(want)
    assert want < 100.0
    # experts 4-7 are held: 3 of the 4 pairs routed
    assert layer["expert_pairs_held_pct.stream"][0](rec) == pytest.approx(75.0)
