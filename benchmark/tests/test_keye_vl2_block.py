"""The ``KeyeVL2`` block's own rehearsal (``blocks/KeyeVL2.py``, the
configuration ``keye_vl2_30b_a3b``, the mix ``longgen`` and the three readers
PR 49 brought). CPU, tiny widths, Pallas in interpret mode:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_keye_vl2_block.py -q -p no:cacheprovider

``test_benchmark.py::test_a_configuration_resolves_to_a_block_with_the_programs_leaves``
picks up ``configs/keye_vl2_30b_a3b.json`` as a case by itself.
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
from llm_sharding_tpu.ops import paged_attention as pa  # noqa: E402

TINY = tb.load(HERE, "data", "tiny_keye_vl2.json")
MODEL = harness.model_keys(TINY)
BLOCK = blocks.load("KeyeVL2")
CELL = "keye_vl2_30b_a3b.longgen"
LONGGEN = tb.load(tb.BENCH, "traffic", "longgen.json")
NEW = ("decode_index_pct.longgen", "index_hbm_pct.longgen",
       "sparse_kv_read_pct.longgen")
OLD = ("attn_kv_hbm_pct.longgen", "decode_moe_pct.longgen",
       "moe_hbm_pct.longgen", "experts_read_per_layer.longgen",
       "rows_per_step.longgen", "kv_in_use_peak_pct.longgen")

# a toy's limits (no cell has them): ~300 positions of a vocabulary of 512 in
# bfloat16 under a topk of 16 of ~100 keys, where ONE key at the edge of the
# top-k chosen the other way (bf16 products against the reference's float32)
# swaps a sixteenth of what the softmax attends: sound runs read 0.014-0.03
# (served token = reference argmax at ~94% of positions), the two controls
# 0.39 (no selection) and 0.66 (the most recent keys; 27-38%)
TOY_DELTA_MEAN = 0.08
TOY_DELTA_MAX = 4.0


def recent_tokens(scores, topk):
    """Control (a): the most recent ``topk`` attendable columns in place of
    the indexer's choice (a row's columns are in position order)."""
    ok = scores > -jnp.inf
    order = jnp.where(ok, jnp.arange(scores.shape[-1]), -1).astype(jnp.float32)
    return pa_select_tokens(jnp.where(ok, order, -jnp.inf), topk)


def recent_mask(scores, topk):
    ok = scores > -jnp.inf
    order = jnp.where(ok, jnp.arange(scores.shape[-1]), -1).astype(jnp.float32)
    return pa_select_mask(jnp.where(ok, order, -jnp.inf), topk)


pa_select_tokens, pa_select_mask = pa.select_tokens, pa.select_mask

# what the program is handed in place of its own selection; the reference
# keeps the model's
CONTROLS = {
    "sound": None,
    "the most recent topk keys": (recent_tokens, recent_mask),
    "no selection": (
        lambda scores, topk: pa_select_tokens(scores, scores.shape[-1]),
        lambda scores, topk: scores > -jnp.inf,
    ),
}


def run_longgen(tmp_path, readers, seconds=4.0):
    """``harness.run_cell`` with the ``longgen`` mix at toy lengths: ONE
    client on the toy's two rows, every reply 40 tokens — contexts of 16-140
    under a ``topk`` of 16: the selection bites from the first decode step."""
    traffic = json.loads(json.dumps(LONGGEN))
    traffic["prompt_len"].update(median=24, max=100)
    traffic["output_len"].update(value=40, max=40)
    traffic.update(ramp_s=1.0, tail_s=4.0)
    return harness.run_cell(
        cell={"name": "tiny.longgen"}, cfg_file=json.loads(json.dumps(TINY)),
        block=BLOCK, traffic=traffic, cell_params={"clients_per_row": 0.5},
        devices=jax.devices()[:1], seed=2**31 + 9, seconds=seconds,
        trace=False, out_dir=str(tmp_path), t_process=time.perf_counter(),
        readers=readers, attn="auto",
    )


def test_the_tiny_configuration_is_a_case_of_the_leaves_test():
    tb.test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        os.path.join(HERE, "data", "tiny_keye_vl2.json"), blocks.HERE)
    assert blocks.kinds(BLOCK, MODEL) is None  # all layers alike
    assert [l.name for l in BLOCK.layer_leaves(MODEL)][-5:] == [
        "wq_idx", "wk_idx", "w_idx", "k_idx_norm", "k_idx_bias"]


def test_the_real_configuration_is_the_catalogs_but_for_its_cut():
    cfg = tb.load(tb.BENCH, "configs", "keye_vl2_30b_a3b.json")
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "vocab_size": 151936}
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    model = harness.model_keys(cfg)
    assert BLOCK.indexer(model) == {"heads": 16, "dim": 64, "topk": 2048}
    mc = harness.model_config(cfg)
    assert mc.sparse_attn and mc.index_cache_dim == 128
    assert mc.eos_token_id >= cfg["vocab_size"]  # outside the held slice
    # a row's budget fits the capacity, and the pool holds every row twice
    serve = cfg["serve"]
    assert 512 + 8192 <= serve["capacity"]
    assert serve["kv_blocks"] == 1 + 2 * serve["batch_per_slot"] * (
        serve["capacity"] // serve["kv_block_size"])


@pytest.mark.parametrize("what", list(CONTROLS))
def test_the_keye_block_runs_through_the_harness(what, tmp_path, monkeypatch):
    """A tiny ``KeyeVL2`` configuration served paged through
    ``harness.run_cell`` under the ``longgen`` mix is correct, its step records
    carry the selection's counters and the readers read them — and it is NOT
    correct when the program keeps the most recent ``topk`` keys in place of
    the indexer's choice, or selects nothing at all (the two controls)."""
    monkeypatch.setattr(BLOCK, "DELTA_MEAN", TOY_DELTA_MEAN)
    monkeypatch.setattr(BLOCK, "DELTA_MAX", TOY_DELTA_MAX)
    if CONTROLS[what] is not None:
        tokens, mask = CONTROLS[what]
        monkeypatch.setattr(pa, "select_tokens", tokens)
        monkeypatch.setattr(pa, "select_mask", mask)
    jax.clear_caches()  # the step programs trace the selection they find
    try:
        e2e, layer, _ = tb._readers(CELL)
        got = run_longgen(tmp_path, e2e)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    res, rec = got["result"], got["records"]
    print(what, rec["reference"])
    assert res["failed"] == 0 and rec["paths"]["attn_backend"] == "interpret"
    assert rec["reference"]["positions"] >= 40 and rec["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}  # PERF.md section 2
    if CONTROLS[what] is not None:
        assert not res["correct"] and rec["kernels_ok"] and rec["arena_ok"]
        assert rec["reference"]["margin_mean"] > 3 * TOY_DELTA_MEAN
        return
    assert res["correct"], rec["reference"]
    # the counters: one live row a step; what a step read of K/V is
    # min(context, topk) a layer, scored and live the whole context
    steps = [s for s in rec["steps"] if s.get("sparse_tokens")]
    assert steps
    L, topk = 2, 16
    for s in steps:
        got_ = s["sparse_tokens"]
        assert got_["read"] <= got_["live"]
        assert got_["scored"] in (0, got_["live"])
        assert got_["read"] % L == 0 and got_["read"] <= topk * L * 8
    share = layer["sparse_kv_read_pct.longgen"][0](rec)
    assert 5.0 < share < 70.0  # contexts of 16-140 under a topk of 16
    assert layer["rows_per_step.longgen"][0](rec) == pytest.approx(1.0, abs=0.2)
    assert layer["kv_in_use_peak_pct.longgen"][0](rec) > 0
    assert 2 <= layer["experts_read_per_layer.longgen"][0](rec) <= 4
    # the device-side readers have nothing to read in an untraced run
    for name in ("decode_index_pct.longgen", "index_hbm_pct.longgen",
                 "attn_kv_hbm_pct.longgen", "decode_moe_pct.longgen",
                 "moe_hbm_pct.longgen"):
        assert layer[name][0](rec) is None, name


def test_bytes_of_a_decode_step_against_a_hand_count():
    """``decode_step_bytes``, ``attn_kv_bytes`` and ``index_bytes`` of the REAL
    configuration against a hand count: one request at context 5,000 (past
    ``topk``) and one at 1,000 (under it) in flight."""
    model = harness.model_keys(tb.load(tb.BENCH, "configs", "keye_vl2_30b_a3b.json"))
    req = lambda n: {"server_started_at": 0.0, "finished": None,
                     "prompt_len": n, "stamps": []}
    rec = {"window": [0.0, 10.0], "traced": [0.0, 10.0], "chips": 1,
           "requests": [req(5000), req(1000)],
           "steps": [{"t": 1.0, "rows": 2, "experts_read": [12] * 12,
                      "expert_steps": 1}]}
    H, F, E, L, V = 2048, 768, 128, 12, 37984
    expert = 3 * H * F + 2 * F * 2
    assert BLOCK.expert_bytes(model, "int8") == expert
    indexer = (H * 1024 + 1024 * 2) + (H * 64 + 64 * 2) + (H * 16 + 2 * 64) * 2
    assert BLOCK.indexer_weight_bytes(model, "int8") == indexer
    attn = ((H * 4096 + 4096 * 2) + (4096 * H + H * 2)  # wq, wo and scales
            + (H * 512 + 512 * 2) * 2)  # wk, wv
    dense = attn + indexer + (H * E + 2 * H + 2 * 128) * 2 + H * 2
    assert BLOCK.dense_layer_bytes(model, "int8") == dense
    live, chosen, scored = BLOCK.tokens_per_step(rec, 2048)
    assert (live, chosen, scored) == (6000, 2048 + 1000, 6000)
    assert BLOCK.attn_kv_bytes(model, rec) == L * 3048 * 2048
    assert BLOCK.index_bytes(model, "int8", rec) == L * (indexer + 6000 * 128)
    want = (L * (dense + 12 * expert) + H * V * 2 + L * 3048 * 2048
            + L * 6000 * 128)
    assert BLOCK.decode_step_bytes(model, "int8", 1, 6000.0, rec) == pytest.approx(want)
    # under topk nothing is scored and everything live is read
    short = dict(rec, requests=[req(1000)])
    assert BLOCK.tokens_per_step(short, 2048) == (1000, 1000, 0)
    with pytest.raises(ValueError, match="experts_read"):
        BLOCK.decode_step_bytes(model, "int8", 1, 100.0, {"window": [0, 1], "steps": []})


def test_the_new_readers_on_a_recorded_run():
    """The three readers on hand-built records — and None (not an error) on a
    program without the scopes or the counter, as the parent commit is."""
    _, layer, _ = tb._readers(CELL)
    read = {n: layer[n][0] for n in NEW}
    cfg = tb.load(tb.BENCH, "configs", "keye_vl2_30b_a3b.json")
    req = {"server_started_at": 0.0, "finished": None, "prompt_len": 4000,
           "stamps": []}
    steps = [{"t": 1.0 + i, "rows": 1, "experts_read": [8] * 12,
              "expert_steps": 1, "expert_rows": 1,
              "sparse_tokens": {"scored": 48000, "read": 24576, "live": 48000}}
             for i in range(4)]
    rec = {
        "config": cfg, "chips": 1, "window": [0.0, 10.0], "traced": [0.0, 10.0],
        "peaks": {"hbm_bytes_per_s": 819e9}, "steps": steps, "requests": [req],
        "trace": {"modules": {"serve_chunk": [[0.004] * 4]}},
        "spans": {"scopes": {"serve_chunk": {
            "attn": 0.008, "indexer": 0.003, "select": 0.001, "moe": 0.004}}},
    }
    assert read["decode_index_pct.longgen"](rec) == pytest.approx(100 * 4 / 16)
    assert read["sparse_kv_read_pct.longgen"](rec) == pytest.approx(51.2)
    model = harness.model_keys(cfg)
    need = BLOCK.index_bytes(model, "int8", rec, 0.0, 10.0)
    assert need == 12 * (BLOCK.indexer_weight_bytes(model, "int8") + 4000 * 128)
    assert read["index_hbm_pct.longgen"](rec) == pytest.approx(
        100 * need / 819e9 / 0.001)
    # a program without the words or the counter: nothing, and no error
    old = dict(rec, steps=[{"t": 1.0, "rows": 1}],
               spans={"scopes": {"serve_chunk": {"attn": 0.008, "mlp": 0.004}}})
    assert all(read[n](old) is None for n in NEW)
    assert all(read[n](dict(old, spans=None, trace=None)) is None for n in NEW)
