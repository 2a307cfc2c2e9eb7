"""Tests of ``benchmark/span_reduce.py`` and the readers built on it. CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The parser is pinned on two traces recorded on the chip (``data/``), the
arithmetic on planes built here.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import pytest  # noqa: E402

from benchmark import span_reduce as sr, trace_reduce  # noqa: E402
from llm_sharding_tpu.obs.stepline import SCOPES  # noqa: E402

SMALL = os.path.join(HERE, "data", "small.xplane.pb")
SPAN = os.path.join(HERE, "data", "span.xplane.pb")
MS = 1_000_000  # ns


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ---------------------------------------------------------------- the parser

def test_the_wire_walk_reads_what_the_profiler_hides():
    """The recorded v5e trace of PR 23: an operation's event METADATA holds
    ``tf_op``, ``bytes_accessed`` and its program — ``ProfileData`` gives
    none of them — and the times agree with ``ProfileData``'s."""
    planes = sr.read_xspace(SMALL)
    assert sorted(planes["devices"]) == [0]
    metas = planes["op_meta"][0]
    fusion = [m for m in metas.values() if m["name"] == "convolution_tanh_fusion.3"]
    assert len(fusion) == 1
    assert fusion[0]["tf_op"] == "jit(bench_probe)/dot_general:"
    assert fusion[0]["bytes_accessed"] == 6291456
    assert fusion[0]["module"] == "bench_probe"
    # the same events, at the same times, as the reduction of PR 23 reads
    old = trace_reduce.read_planes(SMALL)["devices"][0]
    new = planes["devices"][0]
    assert len(new["ops"]) == len(old["ops"]) == 24
    assert len(new["modules"]) == len(old["modules"]) == 4
    for (mid, a, b), (name, oa, ob) in zip(new["ops"], old["ops"]):
        assert metas[mid]["name"] == name
        # ProfileData cuts start and length to whole ns, each on its own
        assert abs(a - oa) < 1 and abs(b - ob) < 2
    assert [n for n, _, _ in new["modules"]] == [n for n, _, _ in old["modules"]]
    # no program of that trace wrote serve.* annotations; nothing is scoped
    assert planes["spans"] == []
    out = sr.reduce_planes(planes, load(HERE, "data", "small.expect.json")["window_s"], SCOPES)
    assert set(out["scopes"]["bench_probe"]) == {sr.UNSCOPED}
    assert out["idle"]["work_s"] == 0.0


def test_scope_of_takes_the_innermost_word_of_the_vocabulary():
    v = frozenset(SCOPES)
    path = "jit(serve_chunk)/shard_map/state/while/body/closed_call/"
    assert sr.scope_of(path + "attn/kv_layout/transpose:", v) == "kv_layout"
    assert sr.scope_of(path + "attn/jit(paged_attention_tpu)/paged_decode/while/body/dot_general:", v) == "attn"
    assert sr.scope_of(path + "kv_take/dynamic_slice:", v) == "kv_take"
    assert sr.scope_of(path + "mul:", v) == "state"
    assert sr.scope_of(path + "vmap(sample)/sort:", v) == "sample"
    # the last component is the primitive, never a scope; none → unscoped
    assert sr.scope_of("jit(f)/transpose/embed:", v) == sr.UNSCOPED
    assert sr.scope_of("jit(embed)/mul:", v) == "embed"  # a jitted function named so
    assert sr.scope_of("jit(bench_probe)/dot_general:", v) == sr.UNSCOPED
    assert sr.scope_of("", v) == sr.UNSCOPED


# ------------------------------------------------------------- the arithmetic

def synthetic(spans=(), n_steps=4):
    """One chip, 100 ms window from t=0. ``serve_chunk`` runs ``n_steps``
    times, 10 ms each from 5, 25, 45, 65 ms: a ``while`` (8 ms, unscoped)
    holding a kv_take fusion (3 ms), an attn kernel (2 ms) and an mlp fusion
    (1 ms); 2 ms of the while are its own; then a kv_put op of 2 ms."""
    path = "jit(serve_chunk)/state/while/body/"
    metas = {
        1: {"name": "while.1", "tf_op": "", "bytes_accessed": 0, "module": "serve_chunk"},
        2: {"name": "fusion.7", "tf_op": path + "kv_take/dynamic_slice:", "bytes_accessed": 1000, "module": "serve_chunk"},
        # a fusion whose path names two words: the innermost counts, once
        3: {"name": "paged_decode.3", "tf_op": path + "attn/kv_layout/paged_decode:", "bytes_accessed": 500, "module": "serve_chunk"},
        4: {"name": "fusion.9", "tf_op": path + "mlp/dot_general:", "bytes_accessed": 200, "module": "serve_chunk"},
        5: {"name": "fusion.4", "tf_op": path + "kv_put/dynamic_update_slice:", "bytes_accessed": 1000, "module": "serve_chunk"},
        6: {"name": "fusion.1", "tf_op": "jit(serve_admit)/state/attn/dot_general:", "bytes_accessed": 10, "module": "serve_admit"},
    }
    ops, modules = [], []
    for i in range(n_steps):
        t = (5 + 20 * i) * MS
        modules.append(("jit_serve_chunk(7)", t, t + 10 * MS))
        ops += [(1, t, t + 8 * MS), (2, t + MS, t + 4 * MS),
                (3, t + 4 * MS, t + 6 * MS), (4, t + 6 * MS, t + 7 * MS),
                (5, t + 8 * MS, t + 10 * MS)]
    return {
        "devices": {0: {"modules": modules, "ops": ops, "async": []}},
        "host": {trace_reduce.TRACED_MARK: [(0, 1000)]},
        "op_meta": {0: metas},
        "spans": list(spans),
    }


def shares(out, module):
    whole = sum(out["scopes"][module].values())
    return {k: 100.0 * v / whole for k, v in out["scopes"][module].items()}


def test_scope_shares_are_own_time_and_sum_to_100():
    out = sr.reduce_planes(synthetic(), 0.100, SCOPES)
    got = out["scopes"]["serve_chunk"]
    # per execution: while 8 − (3 + 2 + 1) = 2 own; x4 executions
    assert got == pytest.approx({
        sr.UNSCOPED: 0.008, "kv_take": 0.012, "kv_layout": 0.008,
        "mlp": 0.004, "kv_put": 0.008,
    })
    assert sum(shares(out, "serve_chunk").values()) == pytest.approx(100.0)
    # the fusion under attn/kv_layout is counted once, under the innermost
    assert "attn" not in got
    assert out["bytes"]["serve_chunk"]["kv_take"] == 4000
    top = out["top_ops"]["serve_chunk"]
    assert top[0][:2] == ["fusion.7", "kv_take"] and top[0][3] == 4
    # the readers' arithmetic: kv_take + kv_layout + kv_put of serve_chunk
    rec = {"spans": out}
    assert sr.scope_share(rec, ("serve_chunk",), ("kv_take", "kv_layout", "kv_put")) == pytest.approx(70.0)
    assert sr.scope_share(rec, ("serve_chunk",), (sr.UNSCOPED,)) == pytest.approx(20.0)
    assert sr.scope_share(rec, sr.PREFILL_MODULES, ("attn",)) is None  # never ran


def test_everything_is_cut_to_the_stamped_window():
    """A 50 ms window holds two executions and half of the third's first
    5 ms: the third's operations are cut at the window's end."""
    out = sr.reduce_planes(synthetic(), 0.050, SCOPES)
    got = out["scopes"]["serve_chunk"]
    # third execution runs 45..55: while cut to 45..50 (5), kv_take 46..49
    # whole (3), the kernel cut to 49..50 (1): while's own 5 − 4 = 1
    assert got == pytest.approx({
        sr.UNSCOPED: 0.004 + 0.001, "kv_take": 0.006 + 0.003,
        "kv_layout": 0.004 + 0.001, "mlp": 0.002, "kv_put": 0.004,
    })
    assert sum(got.values()) == pytest.approx(0.025)  # busy inside the window
    assert out["idle"]["idle_s"] == pytest.approx(0.025)


def step(a, b, rows=0, queued=0, pending=0, n=0):
    return ("pump", sr.STEP, a * MS, b * MS,
            {"step_num": n, "rows": rows, "queued": queued, "pending": pending})


def span(name, a, b, **stats):
    return ("pump", name, a * MS, b * MS, stats)


def test_idle_is_split_by_work_and_by_the_innermost_phase():
    """Chip busy 5-15, 25-35, 45-55, 65-75 ms of 100. The server holds work
    from 3 ms (a step with a queued request) to 58 ms (the end of its last
    working step); the step at 58 ms is the closing one."""
    spans = [
        step(3, 22, queued=1, n=10),          # admit + dispatch + wait
        span("serve.admit", 3, 6),
        span("serve.fetch", 3.5, 4.5),        # nested: admit's own is 2 of 3
        span(sr.PREFILL, 4.5, 5.5, rows=4, prompt_tokens=53, positions=256),
        span("serve.dispatch", 6, 7),
        span("serve.fetch", 7, 21),
        span(sr.BLOCKED, 8, 20),
        step(23, 40, rows=1, pending=1, n=11),
        span("serve.dispatch", 23, 24),
        span("serve.fetch", 24, 39),
        span(sr.BLOCKED, 24.5, 38),
        step(41, 58, rows=1, pending=1, n=12),
        span(sr.BLOCKED, 42, 57),
        step(58, 58.5, n=13),                 # nothing held: the end of work
    ]
    out = sr.reduce_planes(synthetic(spans), 0.100, SCOPES)
    idle = out["idle"]
    assert idle["chip"] == 0
    assert idle["work_s"] == pytest.approx(0.055)           # 3 .. 58 ms
    assert idle["idle_s"] == pytest.approx(0.060)
    # idle inside work: 3-5, 15-25, 35-45, 55-58 = 25 ms
    assert idle["idle_with_work_s"] == pytest.approx(0.025)
    assert idle["idle_no_work_s"] == pytest.approx(0.035)
    by = dict(idle["by"])
    assert sum(by.values()) == pytest.approx(idle["idle_with_work_s"])
    assert by == pytest.approx({
        "serve.admit": 0.001,                # 3-3.5, 4.5-5: fetch is not its own
        "serve.fetch": 0.001 + 0.001 + 0.0005 + 0.001,  # 3.5-4.5, 20-21, 24-24.5, 38-39
        # 15-20, 24.5-25, 35-38, 42-45, 55-57
        sr.BLOCKED: 0.005 + 0.0005 + 0.003 + 0.003 + 0.002,
        "serve.dispatch": 0.001,             # 23-24
        "serve.step, in no phase": 0.001 + 0.001 + 0.001 + 0.001,  # 21-22, 39-40, 41-42, 57-58
        sr.BETWEEN_STEPS: 0.002,             # 22-23, 40-41
    }, abs=1e-9)
    assert out["annotations"]["serve.step"]["count"] == 4
    assert out["prefill"] == {"dispatches": 1, "prompt_tokens": 53, "positions": 256}
    rec = {"spans": out}
    reader = load_reader("idle_with_work_pct")
    assert reader(rec) == pytest.approx(100.0 * 25 / 55)
    # the trace ended inside the admission step after the closing one: its
    # serve.step is not on record, its finished phases are, and the run
    # reaches the window's end (idle 75-100 ms is then idle with work)
    cut = sr.reduce_planes(
        synthetic(spans + [step(60, 64, queued=1, n=14),
                           span("serve.dispatch", 64.5, 65)]),
        0.100, SCOPES)["idle"]
    assert cut["work_s"] == pytest.approx(0.055 + 0.040)
    assert cut["idle_with_work_s"] == pytest.approx(0.025 + 0.005 + 0.025)
    # 22-23, 40-41; 64-64.5 and 75-100 of the step that was cut
    assert dict(cut["by"])[sr.BETWEEN_STEPS] == pytest.approx(0.002 + 0.0255)
    # without serve.step annotations there is no work to divide by
    assert load_reader("idle_with_work_pct")({"spans": sr.reduce_planes(synthetic(), 0.1, SCOPES)}) is None


def test_interval_helpers():
    assert sr.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert sr.intersect([(0, 10)], []) == []
    own = sr.own_intervals([("a", 0, 10), ("b", 2, 4), ("c", 3, 4), ("b", 6, 7)])
    assert own == {"a": [(0, 2), (4, 6), (7, 10)], "b": [(2, 3), (6, 7)], "c": [(3, 4)]}
    steps = [(0, 1, {"queued": 1}), (2, 3, {"rows": 1}), (4, 5, {}),
             (6, 7, {}), (8, 9, {"pending": 1})]
    assert sr.work_intervals(steps) == [(0, 3), (8, 9)]
    # the profiler writes an annotation when it ends: the step in progress
    # at an edge of the trace is missing, and the run reaches that edge
    assert sr.work_intervals(steps, (-5, 20)) == [(0, 3), (8, 20)]
    assert sr.work_intervals(steps[1:], (-5, 20)) == [(-5, 3), (8, 20)]
    assert sr.work_intervals(steps[:4], (-5, 20)) == [(0, 3)]


# ------------------------------------------------------ the recorded trace

def assert_same(got, want, where="$"):
    """Equal, numbers within float rounding, all the way down."""
    if isinstance(want, dict):
        assert isinstance(got, dict) and set(got) == set(want), where
        for k in want:
            assert_same(got[k], want[k], f"{where}.{k}")
    elif isinstance(want, list):
        assert isinstance(got, list) and len(got) == len(want), where
        for i, (g, w) in enumerate(zip(got, want)):
            assert_same(g, w, f"{where}[{i}]")
    elif isinstance(want, float):
        assert got == pytest.approx(want, rel=1e-9, abs=1e-15), where
    else:
        assert got == want, where


def test_reduction_of_the_recorded_span_trace():
    """``data/span.xplane.pb`` (``record_span_trace.py``, on the chip): two
    programs under the vocabulary's scopes, driven through the program's
    own StepProfiler. The reduction gives what it gave on the day."""
    assert os.path.getsize(SPAN) < 100_000
    expect = load(HERE, "data", "span.expect.json")
    planes = sr.read_xspace(SPAN)
    out = sr.reduce_planes(planes, expect["window_s"], SCOPES)
    assert_same(json.loads(json.dumps(out)), expect)
    # and what the recorder did is what the trace says
    assert set(out["scopes"]) == {"serve_chunk", "serve_prefill_chunk"}
    chunk = shares(out, "serve_chunk")
    # the slice, the attention, the MLP and the write-back each kept their
    # own operations; the copies XLA made around the loop carry no tf_op
    assert {"kv_take", "attn", "mlp", "kv_put", sr.UNSCOPED} <= set(chunk)
    assert set(chunk) <= set(SCOPES) | {sr.UNSCOPED}
    ops = {o[0]: o[1] for o in out["top_ops"]["serve_chunk"]}
    assert ops["dynamic-slice_bitcast_fusion.2"] == "kv_take"
    assert ops["bitcast_dynamic-update-slice_fusion.2"] == "kv_put"
    assert ops["copy-done"] == ops["while.2"] == sr.UNSCOPED
    assert sum(chunk.values()) == pytest.approx(100.0)
    assert 0 < chunk[sr.UNSCOPED] < 50
    assert "mlp" in shares(out, "serve_prefill_chunk")
    ann = out["annotations"]
    # 2 runs x (3 working steps + 1 closing); 18 idle polls wrote nothing
    assert ann["serve.step"]["count"] == 8
    assert ann["serve.dispatch"]["count"] == 6
    assert ann[sr.BLOCKED]["count"] == 6
    assert ann[sr.PREFILL]["count"] == 2
    assert out["prefill"] == {"dispatches": 2, "prompt_tokens": 648, "positions": 4096}
    steps = [s for s in planes["spans"] if s[1] == sr.STEP]
    assert [s[4]["queued"] for s in steps] == [1, 0, 0, 0, 1, 0, 0, 0]
    assert [s[4]["rows"] for s in steps] == [0, 1, 1, 0, 0, 1, 1, 0]
    nums = [s[4]["step_num"] for s in steps]
    assert nums[:4] == list(range(nums[0], nums[0] + 4))
    idle = out["idle"]
    assert 0 < idle["idle_with_work_s"] < idle["work_s"] < expect["window_s"]
    assert idle["idle_no_work_s"] > 0
    assert sr.BETWEEN_STEPS in dict(idle["by"])  # the recorder slept there


# --------------------------------------------------------------- the readers

def load_reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


NEW = ("decode_attn_pct", "decode_matmul_pct", "decode_unscoped_pct",
       "prefill_attn_pct", "idle_with_work_pct", "admit_pad_pct")


def test_the_new_metrics_are_entries_with_readers_and_return_nothing_untraced():
    bench = load(ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    first = [m["name"] for m in bench["per_layer"]].index(NEW[0] + ".chat")
    # the layers PERF.md section 3 lists, letter for letter
    layers = {"load generator", "scheduler", "host loop", "KV manager",
              "admission", "step programs", "kernels and step", "ring",
              "device"}
    assert {m["layer"] for m in bench["per_layer"]} == layers
    for name in NEW:
        m = by_name[name + ".chat"]
        assert m["moves"] in e2e and m["unit"] == "%"
        assert m["layer"] in layers  # a layer the benchmark already names
        read = load_reader(name)
        # an untraced run, a run of a program without the vocabulary: nothing
        rec = {"traced": None, "steps": [], "window": [0.0, 1.0]}
        assert read(rec) is None
    assert [m["name"] for m in bench["per_layer"][first:first + len(NEW)]] == [
        n + ".chat" for n in NEW]
    # retired by PR 26: 0.0 in every line since PR 25; the mirror of a counter
    assert not {"decode_kv_copy_pct.chat", "prompt_pad_pct.chat"} & set(by_name)
    # the ring reports out_tok_s in no cell: the two that move it are 7B's
    for name in ("prefill_attn_pct", "admit_pad_pct"):
        assert by_name[name + ".chat"]["workloads"] == ["qwen25_7b.chat"]


def test_a_program_without_the_vocabulary_reads_as_nothing(monkeypatch):
    """The parent commit has no SCOPES: ``spans`` is None there, stored, and
    no reader raises."""
    monkeypatch.setattr(sr, "program_scopes", lambda: None)
    rec = {"traced": (0.0, 1.0), "trace": {"xplane_bytes": 1}}
    assert sr.spans(rec) is None and rec["spans"] is None
    assert load_reader("decode_attn_pct")(rec) is None
    assert load_reader("idle_with_work_pct")(rec) is None


def test_spans_reduces_the_runs_own_trace_once_and_keeps_it(tmp_path, monkeypatch):
    import shutil

    monkeypatch.setattr(sr, "HERE", str(tmp_path))
    d = tmp_path / "out" / "trace" / "plugins" / "profile" / "2026_09_27"
    d.mkdir(parents=True)
    shutil.copy(SPAN, d / "host.xplane.pb")
    expect = load(HERE, "data", "span.expect.json")
    rec = {"traced": (10.0, 10.0 + expect["window_s"]),
           "trace": {"xplane_bytes": os.path.getsize(SPAN)}}
    out = sr.spans(rec)
    assert out is rec["spans"] and out["seconds"] >= 0
    assert_same(out["scopes"], expect["scopes"])
    assert sr.spans(rec) is out
    json.dumps(rec)  # the records file keeps it
    assert load_reader("decode_attn_pct")(rec) == pytest.approx(
        sr.scope_share(rec, ("serve_chunk",), ("attn",)))
    # a trace that is not the one trace_reduce measured is not this run's
    other = {"traced": (0.0, 1.0), "trace": {"xplane_bytes": 1}}
    assert sr.spans(other) is None


def test_the_programs_count_of_padding_agrees_with_the_outside_rule(tmp_path):
    """``admit_pad_pct`` (the program's counter, fed where prefills are
    dispatched) against the rule of admission by slot worked out from outside
    — every admission prefills ``batch_per_slot`` rows at the bucket of its
    longest prompt — on a tiny cell on the CPU: equal while admission goes by
    slot. (The rule was the metric ``prompt_pad_pct`` until PR 26 retired it:
    it goes wrong the day admission is by row; the counter does not.)"""
    import test_benchmark as tb
    from benchmark import samples

    e2e, layer, _ = tb._readers()
    rec = tb.run_tiny("chat", 1, tmp_path, e2e)["records"]
    inside = layer["admit_pad_pct.chat"][0](rec)
    rows = rec["config"]["serve"]["batch_per_slot"]
    groups = samples.admissions(rec)
    real = sum(r["prompt_len"] for g in groups for r in g)
    padded = sum(rows * samples.bucket(max(r["prompt_len"] for r in g))
                 for g in groups)
    assert inside is not None and 0 < inside < 100
    assert inside == pytest.approx(100.0 * (1.0 - real / padded), abs=1e-9)
