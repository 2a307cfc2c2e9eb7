"""The benchmark's own tests. Run on the CPU, tiny widths, Pallas in interpret
mode; not part of the program's ``tests/``:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

A CPU run here says correct or incorrect and counts; never a speed.
"""

import hashlib
import json
import os
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
)
os.environ["PAGED_FORCE_KERNEL"] = "interpret"

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import pytest  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import (  # noqa: E402
    blocks, harness, loadgen, reference, roofline, samples, trace_reduce,
    weights,
)

jax.config.update("jax_platforms", "cpu")


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


TINY = load(HERE, "data", "tiny_qwen2.json")
TINY_GPT2 = load(HERE, "data", "tiny_gpt2.json")
BENCHMARK = load(ROOT, "BENCHMARK.json")
CHAT = load(BENCH, "traffic", "chat.json")
BACKLOG = load(BENCH, "traffic", "backlog.json")


# ---------------------------------------------------------------- generator

def plan(seed, traffic=CHAT, rate=2.0, period=10.0):
    return loadgen.open_schedule(traffic, rate, period, 15.0 + period, 1000, seed)


def test_schedule_repeats_for_a_seed_and_only_the_ids_differ_for_another():
    a, b, c = plan(5), plan(5), plan(2**31 + 17)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    assert [p.max_new for p in a] == [p.max_new for p in b]
    # the chat mix as committed: another seed, other token ids — and the
    # same arrivals and sizes in the same order
    assert [p.due_s for p in a] == [p.due_s for p in c]
    assert [len(p.prompt) for p in a] == [len(p.prompt) for p in c]
    assert not any(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, c))


def test_a_window_holds_each_request_of_the_cycle_once():
    """The schedule repeats with the window as its period, so the window
    after the ramp holds the cycle's (prompt, reply) lengths exactly once."""
    period, ramp = 10.0, CHAT["ramp_s"]
    cycle = loadgen.Shape(CHAT, 20, period)
    want = sorted(zip(cycle.prompt_len.tolist(), cycle.output_len.tolist()))
    w = [p for p in plan(2**31 + 5, period=period)
         if ramp <= p.due_s < ramp + period]
    assert sorted((len(p.prompt), p.max_new) for p in w) == want


def test_the_chat_cells_send_what_their_why_says():
    """Five requests a window at 0.10 requests/s: the lengths BENCHMARK.json
    names for the chat cells are the lengths the generator sends."""
    p = loadgen.open_schedule(CHAT, 0.1, 50.0, 50.0, 1000, 1)
    assert sorted(len(x.prompt) for x in p) == [53, 114, 192, 324, 692]
    assert sorted(x.max_new for x in p) == [46, 84, 128, 195, 357]
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    for name in ("qwen25_7b.chat", "qwen25_14b_pp4.chat"):
        assert load(BENCH, "cells", name + ".json")["rate_rps"] == 0.1
        assert "half" in cells[name]["why"] and "0.8" not in cells[name]["why"]


def test_the_backlog_cell_sends_what_its_why_says():
    """Eight clients on the configuration's four rows, lengths inside the
    bounds the cell's ``why`` names, the same for every seed; judged on
    ``out_tok_s``, its per-layer entries read by the readers that are there."""
    cell = next(w for w in BENCHMARK["workloads"]
                if w["name"] == "qwen25_7b.backlog")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen25_7b", "backlog", 1)
    cfg = load(BENCH, "configs", "qwen25_7b.json")
    rows = cfg["serve"]["batch_per_slot"] * cell["chips"]
    per_row = load(BENCH, "cells", "qwen25_7b.backlog.json")["clients_per_row"]
    assert (rows, per_row * rows) == (4, 8)
    assert "8 clients on 4 rows" in cell["why"] and "out_tok_s" in cell["why"]
    max_prompt = harness.max_prompt_len(cfg, BACKLOG)
    a = loadgen.ClosedClients(BACKLOG, 8, 1000, 1, max_prompt)
    b = loadgen.ClosedClients(BACKLOG, 8, 1000, 2**31 + 5, max_prompt)
    xs = [a.next(i % 8) for i in range(BACKLOG["cycle_requests"])]
    ys = [b.next(i % 8) for i in range(BACKLOG["cycle_requests"])]
    assert [len(x.prompt) for x in xs] == [len(y.prompt) for y in ys]
    assert [x.max_new for x in xs] == [y.max_new for y in ys]
    assert [len(x.prompt) for x in xs[:8]] == [203, 69, 569, 637, 197, 265, 2048, 132]
    assert min(len(x.prompt) for x in xs) >= 16
    assert max(len(x.prompt) for x in xs) == 2048
    assert min(x.max_new for x in xs) >= 8 and max(x.max_new for x in xs) == 512
    # the same bounds as the chat mix: warm-up covers the same programs
    assert loadgen.reachable_buckets(BACKLOG, (8, 16, 32, 64, 128, 256, 512,
                                               1024, 2048, 4096), max_prompt) \
        == loadgen.reachable_buckets(CHAT, (8, 16, 32, 64, 128, 256, 512,
                                            1024, 2048, 4096), max_prompt)
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    assert e2e["out_tok_s"]["workloads"] == [
        "qwen25_7b.chat", "qwen25_7b.backlog", "olmoe_1b_7b.backlog"]
    assert "workloads" not in e2e["setup_s"]
    mine = [m for m in BENCHMARK["per_layer"]
            if m.get("workloads") == ["qwen25_7b.backlog"]]
    assert sorted(m["name"] for m in mine) == sorted(
        s + ".backlog" for s in ("rows_per_step", "admit_pad_pct",
                                 "queue_wait_p95_ms", "prefill_ms_per_ktok",
                                 "kv_in_use_peak_pct"))
    for m in mine:  # read by the reader of the name before the dot
        assert m["moves"] == "out_tok_s"
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"].split(".")[0] + ".py"))
        assert not os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))


JUDGED = {
    "qwen25_7b.chat": ["itl_p95_ms", "out_tok_s", "setup_s"],
    "qwen25_14b_pp4.chat": ["itl_p95_ms", "setup_s"],
    "qwen25_7b.backlog": ["itl_p95_ms", "out_tok_s", "setup_s"],
    "olmoe_1b_7b.backlog": ["out_tok_s", "setup_s"],
}


STEP_STEMS = {"decode_step_ms", "decode_hbm_pct", "device_idle_pct",
              "host_ms_per_step"}


def judged_in(cell):
    """The end-to-end metrics ``BENCHMARK.json`` has ``cell`` report: those
    without a list, and those whose list names it."""
    return sorted(m["name"] for m in BENCHMARK["end_to_end"]
                  if cell in m.get("workloads", [cell]))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_a_cell_reports_what_it_is_judged_on_and_what_moves_that(cell):
    """Every cell there is, and any that is added: ``JUDGED`` is a FLOOR for
    PR 32's four (their judged sets as written, the four still first and in
    that order); every other cell's judged set is read from ``BENCHMARK.json``
    and held to the rules. ``olmoe_1b_7b.backlog`` is judged on tokens per
    second and set-up, not on a 95th percentile that lies on the edge between
    one live row and two (PERF.md section 2). A per-layer metric is read in
    the cells its list names, or without a list in every cell that reports
    the end-to-end metric it moves — so each cell reports what its per-layer
    metrics move, and the quantities of the decode step are entries of their
    own (``.backlog``, moving ``out_tok_s``) in the cell that reports no
    ``itl_p95_ms``."""
    names = [w["name"] for w in BENCHMARK["workloads"]]
    assert names[:len(JUDGED)] == list(JUDGED) and len(set(names)) == len(names)
    judged = judged_in(cell)
    assert judged == JUDGED.get(cell, judged)
    # set-up and at least one thing a user of the cell would feel
    assert "setup_s" in judged and len(judged) >= 2
    e2e, layer, _ = _readers(cell)
    assert sorted(e2e) == judged
    moves = {m["name"]: m["moves"] for m in BENCHMARK["per_layer"]}
    assert layer and all(moves[name] in e2e for name in layer)
    stems = {name.split(".")[0] for name in layer}
    assert STEP_STEMS <= stems  # the decode step's four, under some suffix
    why = next(w["why"] for w in BENCHMARK["workloads"] if w["name"] == cell)
    assert 0 < len(why) <= 200 and "\n" not in why and "\t" not in why
    if cell == "olmoe_1b_7b.backlog":
        # (set-up's account, PR 41, moves ``setup_s`` and has no suffix)
        assert all(name.endswith(".backlog") for name in layer
                   if moves[name] != "setup_s")
        assert {"decode_moe_pct", "moe_hbm_pct", "experts_read_per_layer"} <= stems
        assert "out_tok_s" in why and "5%" not in why
    for m in BENCHMARK["per_layer"]:  # a listed cell reports what is moved
        if cell in m.get("workloads", ()):
            assert m["moves"] in judged, (m["name"], cell)


def test_lengths_follow_the_mix():
    big = loadgen.Shape(CHAT, 2000, 100.0)
    assert 150 <= np.median(big.prompt_len) <= 240
    assert big.prompt_len.min() >= 16 and big.prompt_len.max() <= 2048
    assert 100 <= np.median(big.output_len) <= 160
    assert abs(big.offset_s[-1] - 100.0) < 100.0 / 2000 * 12


def test_shared_prefix_and_sessions_are_parameters():
    shared = dict(CHAT, sharing={
        "kind": "shared_prefix", "groups": 3,
        "prefix_len": {"dist": "fixed", "value": 64}})
    p = loadgen.open_schedule(shared, 2.0, 10.0, 10.0, 1000, 3)
    heads = {tuple(x.prompt[:64]) for x in p}
    assert len(heads) == 3
    sess = dict(CHAT, sharing={
        "kind": "sessions", "groups": 2,
        "prefix_len": {"dist": "fixed", "value": 32}})
    p = loadgen.open_schedule(sess, 2.0, 10.0, 10.0, 1000, 3, max_prompt=4000)
    first, third = p[0].prompt, p[2].prompt  # same session, next turn
    assert np.array_equal(third[: len(first)], first) and len(third) > len(first)
    bursty = dict(CHAT, arrivals={"process": "gamma", "cv": 3.0})
    gaps = np.diff([x.due_s for x in
                    loadgen.open_schedule(bursty, 20.0, 10.0, 10.0, 1000, 3)])
    assert gaps.std() / gaps.mean() > 1.5


def test_closed_loop_clients_cycle_from_the_seed():
    a = loadgen.ClosedClients(BACKLOG, 4, 1000, 11)
    b = loadgen.ClosedClients(BACKLOG, 4, 1000, 11)
    xs, ys = [a.next(i % 4) for i in range(6)], [b.next(i % 4) for i in range(6)]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(xs, ys))
    assert xs[0].due_s is None


def test_reachable_buckets():
    buckets = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
    assert loadgen.reachable_buckets(CHAT, buckets, 3583) == [
        16, 32, 64, 128, 256, 512, 1024, 2048]


# ------------------------------------------------------------------ samples

def test_time_to_first_token_runs_from_the_due_time():
    rec = {"window": [100.0, 110.0], "tail_s": 3.0, "seconds": 10.0,
           "requests": [
               # submitted 2 s late: ttft is 3 s from DUE, not 1 s from submit
               {"due": 101.0, "submitted": 103.0, "stamps": [104.0, 104.5, 104.5]},
               # first token before the window: its gaps in the window count
               {"due": 95.0, "submitted": 95.0, "stamps": [99.0, 100.5]},
               # overdue: due long before the end, nothing shown
               {"due": 102.0, "submitted": 102.0, "stamps": []},
               # due near the end, nothing shown yet: not held against it
               {"due": 109.0, "submitted": 109.0, "stamps": []},
           ]}
    assert sorted(samples.ttft_s(rec)) == [3.0, 8.0]
    assert samples.overdue(rec) == [8.0]
    assert sorted(samples.gaps_s(rec)) == [0.0, 0.5, 1.5]
    assert samples.tokens_in_window(rec) == 4
    assert samples.percentile([1, 2, 3, 4, 5], 50) == 3
    # the pump stood still from 103 to 107.5: between two calls of step()
    rec["pump_marks"] = [(101.0, 103.0, True), (107.5, 108.0, False),
                         (108.001, 108.002, False)]
    assert samples.longest_pause_s(rec) == 4.5


def test_admissions_group_by_start_and_bucket():
    rec = {"window": [0.0, 10.0], "requests": [
        {"server_started_at": 1.0, "prompt_len": 100},
        {"server_started_at": 1.00001, "prompt_len": 120},
        {"server_started_at": 5.0, "prompt_len": 20},
        {"server_started_at": None, "prompt_len": 20},
    ]}
    groups = samples.admissions(rec)
    assert [len(g) for g in groups] == [2, 1]
    assert samples.bucket(100) == 128 and samples.bucket(8) == 8


# ---------------------------------------------------------- trace reduction

def test_interval_arithmetic():
    u = trace_reduce.union([(0, 5), (3, 8), (10, 12), (12, 13)])
    assert u == [(0, 8), (10, 13)] and trace_reduce.total(u) == 11
    assert trace_reduce.subtract(u, [(2, 4), (7, 11)]) == [(0, 2), (4, 7), (11, 13)]
    assert trace_reduce.module_name("jit_serve_chunk(123abc)") == "serve_chunk"
    assert trace_reduce.op_name("%while.18 = (s32[]{:T(128)}) while(%a)") == "while.18"
    nested = [("while.1", 0, 100), ("fusion.2", 10, 40), ("fusion.2", 50, 60),
              ("copy.3", 100, 120)]
    st = trace_reduce.self_times(nested)
    assert st["while.1"] == pytest.approx(60e-9)
    assert st["fusion.2"] == pytest.approx(40e-9)
    assert st["copy.3"] == pytest.approx(20e-9)


def test_reduction_of_synthetic_planes():
    ms = 1_000_000
    planes = {
        "devices": {
            0: {"modules": [("jit_serve_chunk(1)", 0, 40 * ms),
                            ("jit_serve_chunk(1)", 50 * ms, 90 * ms),
                            ("jit_serve_admit(2)", 100 * ms, 130 * ms)],
                "ops": [("fusion.1", 0, 30 * ms),
                        ("collective-permute.3", 30 * ms, 40 * ms),
                        ("fusion.1", 50 * ms, 90 * ms),
                        ("all-reduce.7", 80 * ms, 95 * ms),
                        ("fusion.2", 100 * ms, 130 * ms)]},
        },
        "host": {"bench.step": [(0, 45 * ms), (50 * ms, 99 * ms),
                                (140 * ms, 160 * ms)],
                 "bench.submit": [(96 * ms, 98 * ms)]},
    }
    out = trace_reduce.reduce_planes(planes, 0.2, [(0, 135 * ms)])
    chip = out["chips"][0]
    assert chip["busy_s"] == pytest.approx(0.115)
    assert chip["idle_pct"] == pytest.approx(42.5)
    assert chip["collective_s"] == pytest.approx(0.025)
    assert chip["collective_exposed_s"] == pytest.approx(0.015)
    assert out["modules"]["serve_chunk"] == [[0.04, 0.04]]
    assert out["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(0.06)]
    gaps = dict(out["breakdown"]["idle_gaps"])
    # the window is 0-200 (no stamp: from the first operation). gaps: 40-50
    # (5 in step, 5 between), 95-100 (4 in step incl 2 of submit), and after
    # the last operation 130-200: 5 between steps while a request was still
    # there, then 65 with the server empty
    assert gaps["bench.step"] == pytest.approx(0.009)
    assert gaps["between steps"] == pytest.approx(0.011)
    assert gaps["no request in flight"] == pytest.approx(0.065)
    assert gaps["longest single gap"] == pytest.approx(0.070)
    assert sum(v for k, v in gaps.items() if k != "longest single gap") == (
        pytest.approx(0.200 - chip["busy_s"]))
    # a trace with no operation at all is one gap, not none
    planes["devices"][0]["ops"] = []
    empty = dict(trace_reduce.idle_gaps(planes, [(0, 135 * ms)]))
    assert empty["longest single gap"] == pytest.approx(0.160)


def test_a_chip_that_never_rests_is_not_busier_than_the_window():
    """The profiler records from before the harness's stamp until after its
    last look at the clock (on the ring, 2 ms more than the window on a chip
    that idles 0.1%): everything is cut to the stamped window, so busy_s can
    reach window_s and not pass it, and an execution that began before the
    stamp is not the window's."""
    ms = 1_000_000
    planes = {
        "devices": {c: {
            "modules": [("jit_serve_chunk(1)", -30 * ms, 10 * ms),
                        ("jit_serve_chunk(1)", 10 * ms, 50 * ms),
                        ("jit_serve_chunk(1)", 95 * ms, 135 * ms)],
            "ops": [("while.1", -30 * ms, 135 * ms),
                    ("fusion.1", -30 * ms, 60 * ms),
                    ("collective-permute.3", 90 * ms, 120 * ms)],
            "async": [("collective-permute-start.3", 85 * ms, 125 * ms)],
        } for c in (0, 1)},
        "host": {"bench.traced": [(0, 1)],
                 "bench.step": [(-40 * ms, 140 * ms)]},
    }
    out = trace_reduce.reduce_planes(planes, 0.1, [(-50 * ms, 300 * ms)])
    assert out["window_s"] == 0.1
    assert out["busy_s"] == pytest.approx(0.1)
    assert [c["idle_pct"] for c in out["chips"]] == [pytest.approx(0.0)] * 2
    assert out["collective_s"] == pytest.approx(0.015)  # 85-100
    assert out["modules"]["serve_chunk"] == [[0.04, 0.04]] * 2
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.06)
    assert ops["while.1"] == pytest.approx(0.03)
    assert out["breakdown"]["idle_gaps"] == []
    # with the stamp 20 ms before the first operation, that is idle time
    planes["host"]["bench.traced"] = [(-50 * ms, -50 * ms + 1)]
    out = trace_reduce.reduce_planes(planes, 0.1, [(-50 * ms, 300 * ms)])
    assert out["busy_s"] == pytest.approx(0.08)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["between steps"] == pytest.approx(0.010)
    assert gaps["bench.step"] == pytest.approx(0.010)


RECORDED = os.path.join(HERE, "data", "small.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_reduction_of_a_recorded_trace():
    """A short trace recorded on a TPU v5 lite (tests/record_trace.py): four
    executions of one jitted matmul chain inside bench.step annotations."""
    planes = trace_reduce.read_planes(RECORDED)
    assert sorted(planes["devices"]) == [0]
    expect = load(HERE, "data", "small.expect.json")
    out = trace_reduce.reduce_planes(planes, expect["window_s"])
    assert len(out["modules"]["bench_probe"][0]) == expect["executions"]
    assert out["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert 0.0 < out["idle_pct"] < 100.0
    assert len(planes["host"]["bench.step"]) == expect["executions"]
    assert out["collective_s"] == 0.0
    assert out["breakdown"]["device_ops"]


# ---------------------------------------------------------------- reference

MODEL = harness.model_keys(TINY)
BLOCK = blocks.load(TINY["model_type"])
GPT2_BLOCK = blocks.load(TINY_GPT2["model_type"], os.path.join(HERE, "blocks"))
LOOP_BLOCK = blocks.load("qwen2_loop", os.path.join(HERE, "blocks"))


def loop_model(passes, threshold=1.0, close="final_norm", **more):
    """The tiny Qwen2 model run ``passes`` times (``tests/blocks/qwen2_loop.py``;
    the keys are named as Ouro's configuration names them)."""
    return dict(MODEL, model_type="qwen2_loop", total_ut_steps=passes,
                early_exit_threshold=threshold, loop_close=close, **more)


def tiny_weights(seed=3, dtype="int8"):
    params = weights.make_params(BLOCK, MODEL, seed, dtype, jax.devices()[:1])
    tables = {t.name: params[t.name] for t in BLOCK.tables(MODEL)}
    get = lambda l: weights.take_layer(params["layers"], None, l)
    return params, tables, get


def greedy(tables, get, prompt, n, block=BLOCK, model=MODEL, **wrong):
    """n greedy tokens from the reference, optionally made wrong."""
    ids = list(prompt)
    out = []
    kw = tuple(sorted(block.head_static(model).items()))
    for _ in range(n):
        (h,) = reference.hidden_states(block, model, get, tables, [ids], **wrong)
        _, best = reference.margins_from_hidden(
            h[..., len(ids) - 1 : len(ids), :], tables,
            jnp.zeros((1,), jnp.int32),
            logits=block.logits, kw=kw)
        out.append(int(best[0]))
        ids.append(out[-1])
    return np.asarray(out, np.int32)


def digests(params) -> dict:
    """dtype, shape and bytes of every array of a parameter tree."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(a)
        h = hashlib.sha256()
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
        out[jax.tree_util.keystr(path)] = h.hexdigest()[:16]
    return out


@pytest.mark.parametrize("toy,chips", [
    ("tiny_qwen2", 1), ("tiny_qwen2", 4), ("tiny_olmoe", 1), ("tiny_olmoe", 2)])
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("seed", [7, 2**31 + 3])
def test_the_weights_of_a_seed_are_bit_for_bit_those_before_the_seam(
        seed, dtype, toy, chips):
    """Recorded from the parent tree before ``blocks/qwen2.py`` existed (the
    tiny OLMoE: before ``layer_kinds`` did): the same leaf order, keys and
    arithmetic, so the served ids, the margins and every metric of the cells
    are what they were. A block without ``layer_kinds`` is drawn as ever."""
    recorded = load(HERE, "data", toy + ".digests.json")["digests"]
    cfg = load(HERE, "data", toy + ".json")
    params = weights.make_params(
        blocks.load(cfg["model_type"]), harness.model_keys(cfg), seed, dtype,
        jax.devices()[:chips])
    assert digests(params) == recorded[f"{seed}.{dtype}"]


def test_weights_repeat_for_a_seed_and_differ_for_another():
    params, _, _ = tiny_weights()
    again, _, _ = tiny_weights()
    assert np.array_equal(np.asarray(params["layers"]["wq"].q),
                          np.asarray(again["layers"]["wq"].q))
    other, _, _ = tiny_weights(seed=2**31 + 3)
    assert not np.array_equal(np.asarray(params["embed"]), np.asarray(other["embed"]))
    assert float(jnp.abs(params["layers"]["bq"].astype(jnp.float32)).mean()) > 0.01


def test_weights_split_over_a_ring_are_the_same_weights():
    one = weights.make_params(BLOCK, MODEL, 7, "bf16", jax.devices()[:1])
    four = weights.make_params(BLOCK, MODEL, 7, "bf16", jax.devices()[:4])
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(four)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_reference_agrees_with_itself_and_refuses_a_wrong_model():
    _, tables, get = tiny_weights()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, size=n, dtype=np.int32) for n in (12, 20, 31)]
    right = [(p, greedy(tables, get, p, 6)) for p in prompts]
    a = reference.score(BLOCK, MODEL, get, tables, right)
    b = reference.score(BLOCK, MODEL, get, tables, right[::-1])
    assert a["margin_max"] == 0.0 and a["argmax_share"] == 1.0
    assert a["margin_mean"] == pytest.approx(b["margin_mean"], abs=1e-6)
    assert reference.verdict(a, BLOCK)

    def no_bias(l):
        p = dict(get(l))
        for k in ("bq", "bk", "bv"):
            p[k] = jnp.zeros_like(p[k])
        return p

    wrong = {
        "dropped bias": [(p, greedy(tables, no_bias, p, 6)) for p in prompts],
        "rotary base": [(p, greedy(tables, get, p, 6, theta=1e4)) for p in prompts],
        "4-bit cache": [(p, greedy(tables, get, p, 6, kv_round="int4"))
                        for p in prompts],
    }
    for what, served in wrong.items():
        s = reference.score(BLOCK, MODEL, get, tables, served)
        assert not reference.verdict(s, BLOCK), (what, s)
    # an int8 cache errs by less than bf16 arithmetic does: at four layers
    # it flips no token at all, and token margins cannot see it (PERF.md)
    fine = [(p, greedy(tables, get, p, 6, kv_round="int8")) for p in prompts]
    assert reference.score(BLOCK, MODEL, get, tables, fine)["margin_mean"] < BLOCK.DELTA_MEAN


# ----------------------------------------------------------------- roofline

def test_bytes_of_a_decode_step():
    """The numbers of before the seam, asked of the configuration's block."""
    c7 = load(BENCH, "configs", "qwen25_7b.json")
    m7, b7 = harness.model_keys(c7), blocks.load(c7["model_type"])
    layer = b7.layer_weight_bytes(m7, "int8")
    assert 28 * layer == pytest.approx(6.53e9, rel=0.01)
    assert roofline.head_bytes(b7.dims(m7)) == 3584 * 152064 * 2
    assert roofline.kv_bytes_per_token_layer(b7.dims(m7)) * 28 == 56 * 1024
    full = b7.decode_step_bytes(m7, "int8", 1, 1000.0, rec=None)
    assert full == pytest.approx(
        28 * layer + roofline.head_bytes(b7.dims(m7)) + 1000 * 57344)
    assert full == 7618723840 + 1000 * 57344  # the parent's count, to the byte
    c14 = load(BENCH, "configs", "qwen25_14b_pp4.json")
    m14, b14 = harness.model_keys(c14), blocks.load(c14["model_type"])
    assert 48 * b14.layer_weight_bytes(m14, "bf16") == pytest.approx(
        26.4e9, rel=0.01)
    assert b14.decode_step_bytes(m14, "bf16", 4, 500.0) == 7020306432.0
    # the second block counts its own leaves, a tied head and no rotary
    g = harness.model_keys(TINY_GPT2)
    H, I = 128, 512
    assert GPT2_BLOCK.layer_weight_bytes(g, "bf16") == 2 * (
        4 * H * H + 2 * H * I + 9 * H + I)
    assert GPT2_BLOCK.decode_step_bytes(g, "bf16", 1, 10.0) == (
        4 * GPT2_BLOCK.layer_weight_bytes(g, "bf16") + 2 * H * 512
        + 4 * 10.0 * 2 * H * 2)
    # a looped block counts its passes itself: the layers and the live K/V
    # three times (a pass keeps keys and values of its own), the head once
    loop, once = loop_model(3), loop_model(1)
    per_layer = BLOCK.layer_weight_bytes(MODEL, "int8")
    kv = roofline.kv_bytes_per_token_layer(BLOCK.dims(MODEL))
    head = roofline.head_bytes(BLOCK.dims(MODEL))
    assert LOOP_BLOCK.dims(loop)["layers"] == 4  # ONE pass's layers
    assert LOOP_BLOCK.decode_step_bytes(loop, "int8", 1, 10.0) == (
        3 * 4 * per_layer + head + 3 * 4 * 10.0 * kv)
    assert LOOP_BLOCK.decode_step_bytes(once, "int8", 1, 10.0) == (
        BLOCK.decode_step_bytes(MODEL, "int8", 1, 10.0))
    assert LOOP_BLOCK.decode_step_bytes(loop, "bf16", 2, 10.0) == (
        3 * 2 * BLOCK.layer_weight_bytes(MODEL, "bf16") + head / 2
        + 3 * 2 * 10.0 * kv)


# ------------------------------------------------------------------ the run

def test_run_py_finds_no_tpu_on_the_cpu():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "qwen25_7b.chat", "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    assert "found no TPU" in r.stderr
    assert "{" not in r.stdout  # no result line


def run_py():
    """``run.py`` as a module (it is a script, not a package member)."""
    sys.argv = ["run.py"]
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


def _readers(cell="qwen25_7b.chat"):
    run = run_py()
    return (run.load_readers(BENCHMARK, "end_to_end", cell),
            run.load_readers(BENCHMARK, "per_layer", cell), BENCHMARK)


def run_tiny(loop, stages, tmp_path, readers, cfg=TINY, block=BLOCK):
    """What run.py calls after its device check, given a tiny configuration
    and the CPU's devices by this test."""
    cfg = json.loads(json.dumps(cfg))
    if stages > 1:
        cfg["deployment"].update(num_stages=stages, weight_dtype="bf16")
    traffic = json.loads(json.dumps(CHAT if loop == "chat" else BACKLOG))
    traffic["prompt_len"].update(median=24, max=100)
    traffic["output_len"].update(median=8, max=24, min=2)
    traffic.update(ramp_s=1.0, tail_s=4.0)
    return harness.run_cell(
        cell={"name": "tiny." + loop}, cfg_file=cfg, block=block,
        traffic=traffic,
        cell_params={"rate_rps": 4.0, "clients_per_row": 2},
        devices=jax.devices()[:stages], seed=2**31 + 9, seconds=4.0,
        trace=False, out_dir=str(tmp_path), t_process=time.perf_counter(),
        readers=readers, attn="auto",
    )


@pytest.mark.parametrize("loop,stages", [("chat", 1), ("backlog", 1), ("chat", 4)])
def test_a_cell_runs_end_to_end_on_the_cpu(loop, stages, tmp_path):
    """Both loop kinds, one chip and a ring. Counts and correctness only.
    Each loop kind reads the metrics of the 7B cell of its mix."""
    e2e, layer, bench = _readers("qwen25_7b." + loop)
    got = run_tiny(loop, stages, tmp_path, e2e)
    res, rec = got["result"], got["records"]
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "compared"]  # each number compared, with its limit
    for what, (value, limit) in res["compared"].items():
        assert value >= limit if what == "scored_positions" else value <= limit
    assert res["compared"]["margin_mean"][1] == BLOCK.DELTA_MEAN
    assert res["correct"], rec["reference"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"  # named for what it is
    assert rec["paths"]["attn_backend"] == "interpret"
    assert rec["paths"]["prefix_hit_tokens"] == 0
    assert rec["paths"]["arena_dtype"] == ["bfloat16"] and rec["arena_ok"]
    assert rec["compiles_in_window"] == 0
    # host-side per-layer readers work on an untraced run's records too
    host_side = {
        "chat": ("rows_per_step.chat", "queue_wait_p95_ms.chat",
                 "admit_pad_pct.chat", "kv_in_use_peak_pct.chat",
                 "host_ms_per_step.chat", "loadgen_late_p95_ms.chat",
                 "ttft_median_ms.chat", "ttft_max_ms.chat"),
        "backlog": ("rows_per_step.backlog", "queue_wait_p95_ms.backlog",
                    "admit_pad_pct.backlog", "kv_in_use_peak_pct.backlog",
                    "host_ms_per_step.chat"),
    }[loop]
    for name in host_side:
        assert layer[name][0](rec) is not None, name
    if loop == "chat":
        assert (layer["ttft_max_ms.chat"][0](rec)
                >= layer["ttft_median_ms.chat"][0](rec))
    else:
        # the closed loop kept more requests than rows in the server, and a
        # chat-only metric is not among the cell's
        assert len(rec["requests"]) > 4
        assert "ttft_median_ms.chat" not in layer
        assert "prefill_ms_per_ktok.backlog" in layer
        # what sets.sh leaves beside a run's log, and spread.py tabulates
        sys.path.insert(0, HERE)
        import gap_stats
        from benchmark import spread

        st = gap_stats.stats(rec)
        assert st["out_tok_s"] == res["metrics"]["out_tok_s"]["value"]
        assert st["gap_p95_ms"] == res["metrics"]["itl_p95_ms"]["value"]
        assert st["gap_p50_ms"] <= st["gap_p90_ms"] <= st["gap_p95_ms"] <= st["gap_p99_ms"]
        (tmp_path / "c.A.1.log").write_text("x\n" + json.dumps(res) + "\n")
        (tmp_path / "c.A.1.gaps").write_text("gaps: " + json.dumps(st) + "\n")
        run = spread.last_json(str(tmp_path / "c.A.1.log"))
        assert run["metrics"]["gap_p99_ms"]["value"] == st["gap_p99_ms"]
        assert run["metrics"]["out_tok_s"] == res["metrics"]["out_tok_s"]
    # and the trace readers return nothing where there is no trace
    assert layer["device_idle_pct.chat"][0](rec) is None
    assert layer["decode_hbm_pct.chat"][0](rec) is None
    json.dumps(rec, default=float)


def test_a_quantised_arena_under_a_bf16_label_is_not_correct(
        tmp_path, monkeypatch):
    """A program that kept int8 codes in the arena while the configuration
    says bf16: the token margins pass (they cannot see it), the arena's own
    type does not."""
    build = harness.build_server

    def quantising(cfg_file, *args, **kw):
        lying = json.loads(json.dumps(cfg_file))
        lying["serve"]["kv_dtype"] = "int8"
        return build(lying, *args, **kw)

    monkeypatch.setattr(harness, "build_server", quantising)
    got = run_tiny("chat", 1, tmp_path, _readers()[0])
    rec = got["records"]
    assert rec["paths"]["arena_dtype"] == ["int8"]
    assert rec["paths"]["arena_dtype_wanted"] == "bfloat16"
    assert reference.verdict(rec["reference"], BLOCK), rec["reference"]
    assert not got["result"]["correct"]


# ----------------------------------------------------------------- the seam

def config_files():
    folder = os.path.join(BENCH, "configs")
    return sorted(os.path.join(folder, f) for f in os.listdir(folder))


# (configuration file, folder its block is found in): every cell's, and the
# two toys of these tests
ALL_CONFIGS = [(p, blocks.HERE) for p in config_files()] + [
    (os.path.join(HERE, "data", "tiny_qwen2.json"), blocks.HERE),
    (os.path.join(HERE, "data", "tiny_gpt2.json"), os.path.join(HERE, "blocks")),
]


@pytest.mark.parametrize("path,folder", ALL_CONFIGS,
                         ids=[os.path.basename(p) for p, _ in ALL_CONFIGS])
def test_a_configuration_resolves_to_a_block_with_the_programs_leaves(
        path, folder):
    """Every file under ``configs/`` finds its block by ``model_type``, and the
    block's leaves are, name for name and shape for shape, those of the
    program's own ``init_layer_params`` for that ``ModelConfig`` (so a program
    PR that renames a leaf fails here, on the CPU, not on the chip)."""
    import importlib

    cfg_file = load(path)
    block = blocks.load(cfg_file["model_type"], folder)
    model = harness.model_keys(cfg_file)
    cfg = harness.model_config(cfg_file)
    # the program's module of this family, by the name its own config gives
    program = importlib.import_module(
        "llm_sharding_tpu.models." + cfg.model_type)
    theirs = jax.eval_shape(
        lambda: program.init_layer_params(cfg, jax.random.key(0), 1))
    shapes = lambda leaves: {leaf.name: leaf.shape for leaf in leaves}
    stacked = lambda stack: {k: tuple(v.shape[1:]) for k, v in stack.items()}
    leaves = block.layer_leaves(model)
    if blocks.kinds(block, model) is None:
        assert stacked(theirs) == shapes(leaves)
        every = leaves
    else:  # one stack per kind, on both sides
        assert {k: stacked(v) for k, v in theirs.items()} == {
            k: shapes(v) for k, v in leaves.items()}
        every = [leaf for of_kind in leaves.values() for leaf in of_kind]
    whole = jax.eval_shape(lambda: program.init_params(cfg, jax.random.key(0)))
    whole.pop("layers")
    assert {k: tuple(v.shape) for k, v in whole.items()} == {
        t.name: t.shape for t in block.tables(model)}
    d = block.dims(model)
    assert (d["layers"], d["hidden"], d["vocab"], d["kv_heads"], d["head_dim"]) == (
        cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size,
        cfg.num_key_value_heads, cfg.head_dim_)
    # a matmul leaf is [in, out]; a table splits along its vocabulary only
    assert all(len(l.shape) == 2 for l in every if l.matmul)
    assert all(t.shape[t.vocab_axis] == d["vocab"]
               for t in block.tables(model) if t.vocab_axis is not None)
    assert block.DELTA_MEAN > 0 and block.DELTA_MAX > block.DELTA_MEAN


def test_a_configuration_without_a_block_dies_naming_the_path(
        monkeypatch, capsys):
    with pytest.raises(FileNotFoundError, match="benchmark/blocks/no_such.py"):
        blocks.load("no_such")
    assert blocks.load("qwen2") is blocks.load("qwen2")  # one module a file
    run = run_py()
    real = run.load

    def another_block(*parts):
        got = real(*parts)
        if parts[-1].startswith(os.path.join("benchmark", "configs")):
            got["model_type"] = "no_such"
        return got

    monkeypatch.setattr(run, "load", another_block)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "qwen25_7b.chat", "--seed", "1",
        "--seconds", "2"])
    with pytest.raises(SystemExit) as e:
        run.main()
    assert e.value.code != 0
    err = capsys.readouterr().err
    assert "benchmark/blocks/no_such.py is missing" in err and "qwen25_7b" in err


GPT2_WRONG = {"sound": None, "no position table": "pos_embed",
              "no qkv bias": "b_qkv"}


@pytest.mark.parametrize("what", list(GPT2_WRONG))
def test_a_second_block_runs_through_the_harness(what, tmp_path, monkeypatch):
    """``tests/blocks/gpt2.py`` differs from the Qwen2 block in every part of
    the seam (LayerNorm with bias, position table, fused biased qkv, GELU,
    tied head): served paged by the program through ``harness.run_cell`` it is
    correct — and not, when the program is handed weights whose position
    table or qkv bias is zero while the reference keeps the seed's."""
    dropped = GPT2_WRONG[what]
    make, calls = weights.make_params, []

    def served_without(*args, **kw):
        params = make(*args, **kw)
        calls.append(1)
        if dropped is None or len(calls) > 1:  # the second call is the check's
            return params
        if dropped in params:
            return dict(params, **{dropped: jnp.zeros_like(params[dropped])})
        layers = dict(params["layers"])
        layers[dropped] = jnp.zeros_like(layers[dropped])
        return dict(params, layers=layers)

    monkeypatch.setattr(weights, "make_params", served_without)
    e2e, _, _ = _readers()
    got = run_tiny("chat", 1, tmp_path, e2e, cfg=TINY_GPT2, block=GPT2_BLOCK)
    res, rec = got["result"], got["records"]
    assert len(calls) == 2 and rec["reference"]["positions"] > 20
    assert res["failed"] == 0 and rec["paths"]["attn_backend"] == "interpret"
    assert set(res["metrics"]) == {m["name"] for m in BENCHMARK["end_to_end"]}
    assert res["correct"] == (dropped is None), rec["reference"]
    if dropped is not None:  # the margins say so, nothing else
        assert rec["kernels_ok"] and rec["arena_ok"]
        assert rec["reference"]["margin_mean"] > 3 * GPT2_BLOCK.DELTA_MEAN


# ------------------------------------------------- layers of several kinds

KINDS_BLOCK = blocks.load("qwen2_kinds", os.path.join(HERE, "blocks"))


def kinds_model(*layer_types):
    return dict(MODEL, model_type="qwen2_kinds", layer_types=list(layer_types))


def kinds_weights(model, seed=3, dtype="int8", chips=1):
    """``(params, tables, get_layer)`` of a model with kinds; ``get_layer`` is
    the one ``harness.check`` builds."""
    params = weights.make_params(
        KINDS_BLOCK, model, seed, dtype, jax.devices()[:chips])
    put = lambda tree: jax.device_put(tree, jax.devices()[0])
    tables = put({t.name: params[t.name] for t in KINDS_BLOCK.tables(model)})
    kinds = blocks.kinds(KINDS_BLOCK, model)
    get = lambda l: put(weights.take_layer(params["layers"], kinds, l))
    return params, tables, get


@pytest.mark.parametrize("chips", [1, 2])
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_two_kinds_of_one_layer_are_the_one_kind_model(dtype, chips):
    """Both kinds are the Qwen2 layer: layer ``l`` keeps the key of its index
    in the WHOLE model, so each kind's stack holds, bit for bit, the layers of
    the one-kind stack at its indices — and the reference, told a kind per
    layer, gives the same margins to the last digit."""
    model = kinds_model("biased", "twin", "biased", "twin")
    params, tables, get = kinds_weights(model, 7, dtype, chips)
    one, one_tables, one_get = tiny_weights(7, dtype)
    assert sorted(params["layers"]) == ["biased", "twin"]
    for kind, at in (("biased", [0, 2]), ("twin", [1, 3])):
        want = jax.tree.map(lambda a: np.asarray(a)[at], one["layers"])
        got = jax.tree.map(np.asarray, params["layers"][kind])
        assert digests(got) == digests(want), kind
    assert digests({k: params[k] for k in tables}) == digests(
        {k: one[k] for k in one_tables})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, size=n, dtype=np.int32) for n in (12, 31)]
    served = [(p, rng.integers(0, 512, size=6, dtype=np.int32)) for p in prompts]
    assert reference.score(KINDS_BLOCK, model, get, tables, served) == (
        reference.score(BLOCK, MODEL, one_get, one_tables, served))
    # a cut in depth keeps each kept layer's weights
    cut = dict(kinds_model("biased", "twin"), num_hidden_layers=2)
    short, _, _ = kinds_weights(cut, 7, dtype)  # one chip: one period
    for kind, l in (("biased", 0), ("twin", 1)):
        assert digests(jax.tree.map(np.asarray, short["layers"][kind])) == (
            digests(jax.tree.map(lambda a: np.asarray(a)[[l]], one["layers"])))


def test_kinds_that_differ_in_leaves_score_correct_and_not_when_permuted(
        monkeypatch):
    """One kind has three leaves fewer than its neighbour. Tokens decoded
    greedily under the right order of kinds score correct (through
    ``harness.check``, which makes the weights and takes layer ``l`` from its
    kind's stack) — and not under a block that takes the kinds in another
    order, though every matmul weight is the same."""
    model = kinds_model("plain", "biased", "plain", "biased")
    cfg = dict(TINY, **model)
    params, tables, get = kinds_weights(model)
    assert set(params["layers"]["biased"]) - set(params["layers"]["plain"]) == {
        "bq", "bk", "bv"}
    assert params["layers"]["plain"]["wq"].q.shape[0] == 2
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, size=n, dtype=np.int32) for n in (12, 20, 31)]
    served = [(p, greedy(tables, get, p, 6, KINDS_BLOCK, model))
              for p in prompts]
    right = harness.check(cfg, KINDS_BLOCK, 3, jax.devices()[:1], None, served)
    assert right["margin_max"] == 0.0 and reference.verdict(right, KINDS_BLOCK)
    # the same through a ring's staged host copy: two stages, a period each
    host = weights.to_host(weights.make_params(
        KINDS_BLOCK, model, 3, "int8", jax.devices()[:2]))
    ring = harness.check(cfg, KINDS_BLOCK, 3, jax.devices()[:2], host, served)
    assert ring == right
    monkeypatch.setattr(KINDS_BLOCK, "layer_kinds",
                        lambda m: tuple(m["layer_types"])[::-1])
    wrong = harness.check(cfg, KINDS_BLOCK, 3, jax.devices()[:1], None, served)
    assert not reference.verdict(wrong, KINDS_BLOCK), wrong


def test_a_ring_that_cuts_a_period_dies_naming_the_kind():
    model = kinds_model("biased", "biased", "biased", "plain")
    with pytest.raises(ValueError, match="kind 'biased'.*2 stages"):
        weights.make_params(KINDS_BLOCK, model, 7, "bf16", jax.devices()[:2])
    assert weights.layers_of_kinds(("a", "b", "a", "b"), 2) == {
        "a": [0, 2], "b": [1, 3]}
    with pytest.raises(ValueError, match="names 3 layers, the model has 4"):
        blocks.kinds(KINDS_BLOCK, kinds_model("biased", "plain", "biased"))
    assert blocks.kinds(BLOCK, MODEL) is None
    assert blocks.place(None, 3) == (None, 3)
    assert blocks.place(("a", "b", "a", "b"), 3) == ("b", 1)


def test_the_rehearsal_builds_the_abstract_tree_per_kind(monkeypatch):
    """``aot_check.abstract_inputs`` hands the serve programs the tree the
    engine would be handed: one stack per kind, split over the stages."""
    import importlib.util

    from llm_sharding_tpu.parallel.mesh import pipeline_mesh

    spec = importlib.util.spec_from_file_location(
        "bench_aot_check", os.path.join(BENCH, "aot_check.py"))
    aot = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(aot)
    cfg = dict(TINY, layer_types=["plain", "biased", "plain", "biased"])
    cfg["deployment"] = dict(TINY["deployment"], num_stages=2)
    monkeypatch.setattr(blocks, "load", lambda model_type: KINDS_BLOCK)
    mesh = pipeline_mesh(2, jax.devices()[:2])
    _, layers, masks, _, _ = aot.abstract_inputs(cfg, mesh)
    assert sorted(layers) == ["biased", "plain"] and masks.shape == (2, 2)
    assert layers["plain"]["wq"].q.shape == (2, 1, 128, 128)
    assert layers["biased"]["bq"].shape == (2, 1, 128)
    assert "bq" not in layers["plain"]
    monkeypatch.undo()
    one = aot.abstract_inputs(TINY, pipeline_mesh(1, jax.devices()[:1]))[1]
    assert one["wq"].q.shape == (1, 4, 128, 128)  # no kinds: as ever


# ------------------------------------------- layers that run several times

def loop_weights(model, seed=3, dtype="int8", block=LOOP_BLOCK):
    """``(params, tables, get_layer)`` of a looped model; ``get_layer`` is the
    one ``harness.check`` builds."""
    params = weights.make_params(block, model, seed, dtype, jax.devices()[:1])
    tables = {t.name: params[t.name] for t in block.tables(model)}
    kinds = blocks.kinds(block, model)
    get = lambda l: weights.take_layer(params["layers"], kinds, l)
    return params, tables, get


def three_prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 512, size=n, dtype=np.int32) for n in (12, 20, 31)]


def three_passes_by_hand(get, tables, prompt, n):
    """n greedy tokens of the tiny model run THREE times, written out: three
    loops over the same four layers, the final norm after each, the head over
    the third pass's normed state (the gate's threshold at 1). Nothing of
    ``reference.py`` but ``dequant`` inside the Qwen2 layer."""
    kw = BLOCK.layer_static(MODEL)
    gain = jnp.asarray(tables["final_norm"], jnp.float32)
    head = jnp.asarray(tables["lm_head"], jnp.float32)
    layers = [{k: (tuple(v) if isinstance(v, tuple) else v)
               for k, v in get(l).items()} for l in range(4)]

    def norm(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * gain

    ids, out = list(prompt), []
    for _ in range(n):
        padded = np.pad(np.asarray(ids, np.int32), (0, -len(ids) % 256))
        h = jnp.asarray(tables["embed"], jnp.float32)[padded]
        for l in range(4):
            h = BLOCK.layer_forward(h, layers[l], **kw)
        h = norm(h)  # closes pass 0 and enters pass 1
        for l in range(4):
            h = BLOCK.layer_forward(h, layers[l], **kw)
        h = norm(h)
        for l in range(4):
            h = BLOCK.layer_forward(h, layers[l], **kw)
        h = norm(h)
        with jax.default_matmul_precision("highest"):
            out.append(int(jnp.argmax(h[len(ids) - 1] @ head)))
        ids.append(out[-1])
    return np.asarray(out, np.int32)


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_one_pass_closed_by_the_identity_is_the_one_pass_model(dtype):
    """(a) ``passes`` of 1 and a close that does nothing: the layers and the
    shared tables are the Qwen2 model's bit for bit (the gate's two tables
    are drawn after them), and the margins of the three prompts are equal to
    the last digit though ``logits`` was handed ``[1, rows, H]``."""
    model = loop_model(1, close="identity")
    params, tables, get = loop_weights(model, 3, dtype)
    one, one_tables, one_get = tiny_weights(3, dtype)
    assert digests(params["layers"]) == digests(one["layers"])
    assert digests({k: params[k] for k in one_tables}) == digests(
        {k: one[k] for k in one_tables})
    assert set(tables) - set(one_tables) == {"exit_gate", "exit_bias"}
    served = [(p, greedy(one_tables, one_get, p, 6)) for p in three_prompts()]
    (h,) = reference.hidden_states(LOOP_BLOCK, model, get, tables, [served[0][0]])
    assert h.shape == (1, 256, 128)
    a = reference.score(LOOP_BLOCK, model, get, tables, served)
    assert a == reference.score(BLOCK, MODEL, one_get, one_tables, served)
    assert a["margin_max"] == 0.0
    # and a block without ``passes`` is asked nothing new
    assert blocks.passes(BLOCK, MODEL) == 1 and not blocks.looped(BLOCK)
    assert blocks.passes(LOOP_BLOCK, loop_model(4)) == 4


def test_three_passes_written_out_by_hand_score_correct_and_fewer_do_not(
        monkeypatch):
    """(b) tokens decoded by a forward written out in this file — three loops
    over the SAME layers, the norm between — score a worst margin of 0.0
    through ``harness.check``, on one chip and through a ring's staged host
    copy, alike. (c) The same tokens are NOT correct under two passes, with
    the close dropped, or with the close after the last pass only."""
    model = loop_model(3)
    cfg = dict(TINY, **model)
    _, tables, get = loop_weights(model)
    served = [(p, three_passes_by_hand(get, tables, p, 6))
              for p in three_prompts()]
    # the layers are fetched again in every pass, one resident at a time
    fetched = []
    scored = reference.score(
        LOOP_BLOCK, model, lambda l: fetched.append(l) or get(l), tables,
        served)
    assert fetched == [0, 1, 2, 3] * 3
    # (``harness.check`` frees every array of the process before it starts)
    right = harness.check(cfg, LOOP_BLOCK, 3, jax.devices()[:1], None, served)
    assert right["margin_max"] == 0.0 and reference.verdict(right, LOOP_BLOCK)
    assert right["positions"] == 18 and right["argmax_share"] == 1.0
    assert right == scored
    host = weights.to_host(weights.make_params(
        LOOP_BLOCK, model, 3, "int8", jax.devices()[:2]))
    ring = harness.check(cfg, LOOP_BLOCK, 3, jax.devices()[:2], host, served)
    assert ring == right

    def check(cfg=cfg):
        return harness.check(cfg, LOOP_BLOCK, 3, jax.devices()[:1], None, served)

    two = check(dict(TINY, **loop_model(2)))
    assert not reference.verdict(two, LOOP_BLOCK), two
    close = LOOP_BLOCK.close_pass
    monkeypatch.setattr(
        LOOP_BLOCK, "close_pass",
        lambda h, t, *, step, **kw: close(h, t, step=step, **kw)
        if step == 2 else h)
    last_only = check()
    assert not reference.verdict(last_only, LOOP_BLOCK), last_only
    monkeypatch.delattr(LOOP_BLOCK, "close_pass")
    dropped = check()
    assert not reference.verdict(dropped, LOOP_BLOCK), dropped
    monkeypatch.undo()
    assert check() == right


def test_the_block_chooses_among_the_passes():
    """(d) ``logits`` is handed every pass's closed state. With the toy
    gate's threshold at 1 its logits are the last pass's to the last digit;
    at 0.5 some scored positions leave at an earlier pass, tokens served from
    the last pass are not correct there, and tokens decoded under the gate
    are."""
    late, early = loop_model(3), loop_model(3, threshold=0.5)
    _, tables, get = loop_weights(late)
    prompts = three_prompts()
    from_last = [(p, greedy(tables, get, p, 6, LOOP_BLOCK, late))
                 for p in prompts]
    assert all(np.array_equal(s, three_passes_by_hand(get, tables, p, 6))
               for p, s in from_last[:1])
    hidden = reference.hidden_states(
        LOOP_BLOCK, late, get, tables, [np.concatenate(x) for x in from_last])
    rows = jnp.concatenate([h[:, len(p) - 1 : len(p) + 5]
                            for h, (p, _) in zip(hidden, from_last)], axis=1)
    assert rows.shape == (3, 18, 128)
    kw = LOOP_BLOCK.head_static(late)
    with jax.default_matmul_precision("highest"):
        chosen = LOOP_BLOCK.logits(rows, tables, **kw)
        last = LOOP_BLOCK.logits(rows[-1:], tables, **kw)
        gated = LOOP_BLOCK.logits(rows, tables, **LOOP_BLOCK.head_static(early))
    assert np.array_equal(np.asarray(chosen), np.asarray(last))
    at = np.asarray(LOOP_BLOCK.exit_pass(rows, tables, 0.5))
    assert (at < 2).sum() >= 1 and len(set(at.tolist())) > 1, at
    assert np.array_equal(np.asarray(LOOP_BLOCK.exit_pass(rows, tables, 1.0)),
                          np.full(18, 2))
    with jax.default_matmul_precision("highest"):
        of_pass = np.stack([np.asarray(LOOP_BLOCK.logits(
            rows[t : t + 1], tables, **kw)) for t in range(3)])
    assert np.array_equal(np.asarray(gated), of_pass[at, np.arange(18)])
    assert (np.asarray(gated) != np.asarray(chosen)).any(axis=-1)[at < 2].all()
    score = lambda model, served: reference.score(
        LOOP_BLOCK, model, get, tables, served)
    assert reference.verdict(score(late, from_last), LOOP_BLOCK)
    wrong = score(early, from_last)
    assert not reference.verdict(wrong, LOOP_BLOCK), wrong
    under_gate = [(p, greedy(tables, get, p, 6, LOOP_BLOCK, early))
                  for p in prompts]
    ok = score(early, under_gate)
    assert ok["margin_max"] == 0.0 and reference.verdict(ok, LOOP_BLOCK)
    assert not reference.verdict(score(late, under_gate), LOOP_BLOCK)


def test_a_block_whose_layers_run_no_times_dies_naming_the_block():
    """(e)"""
    with pytest.raises(ValueError, match=r"qwen2_loop\.py.*run 0 times"):
        blocks.passes(LOOP_BLOCK, loop_model(0))
    _, tables, get = loop_weights(loop_model(1))
    with pytest.raises(ValueError, match="qwen2_loop"):
        reference.score(LOOP_BLOCK, loop_model(-1), get, tables,
                        [(np.arange(5), np.arange(3))])


def loop_of_kinds():
    """A looped block WITH kinds, put together here: the layers of
    ``tests/blocks/qwen2_kinds.py``, the passes, close, tables and logits of
    ``tests/blocks/qwen2_loop.py``."""
    import types

    both = types.ModuleType("benchmark_block_qwen2_loop_kinds")
    for name in ("dims", "layer_kinds", "layer_leaves", "layer_static",
                 "layer_forward", "DELTA_MEAN", "DELTA_MAX"):
        setattr(both, name, getattr(KINDS_BLOCK, name))
    for name in ("passes", "close_pass", "tables", "head_static", "embed",
                 "logits", "decode_step_bytes"):
        setattr(both, name, getattr(LOOP_BLOCK, name))
    return both


def test_kinds_and_passes_compose(monkeypatch):
    """(f) a looped block with two kinds: every pass walks the layers in
    layer order, each told its kind and taken from its kind's stack. Tokens
    decoded so score correct through ``harness.check`` — and not under the
    kinds permuted, nor under one pass."""
    both = loop_of_kinds()
    model = loop_model(3, layer_types=["plain", "biased", "plain", "biased"])
    cfg = dict(TINY, **model)
    params, tables, get = loop_weights(model, block=both)
    assert sorted(params["layers"]) == ["biased", "plain"]
    assert params["layers"]["plain"]["wq"].q.shape[0] == 2  # drawn ONCE
    walked = []
    forward = both.layer_forward
    monkeypatch.setattr(
        both, "layer_forward",
        lambda h, p, *, kind, **kw: walked.append(kind) or forward(
            h, p, kind=kind, **kw))
    prompts = three_prompts()
    served = [(p, greedy(tables, get, p, 4, both, model)) for p in prompts[:1]]
    assert walked[:12] == ["plain", "biased"] * 6
    right = harness.check(cfg, both, 3, jax.devices()[:1], None, served)
    assert right["margin_max"] == 0.0 and reference.verdict(right, both)
    monkeypatch.setattr(both, "layer_kinds",
                        lambda m: tuple(m["layer_types"])[::-1])
    wrong = harness.check(cfg, both, 3, jax.devices()[:1], None, served)
    assert not reference.verdict(wrong, both), wrong
    monkeypatch.undo()
    once = harness.check(dict(cfg, total_ut_steps=1), both, 3,
                         jax.devices()[:1], None, served)
    assert not reference.verdict(once, both), once
