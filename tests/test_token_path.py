"""A token's path on the host's clock (ISSUE 55): every program the device
is handed is stamped at enqueue and at landing, the step record carries the
stamps, starved time is counted at every enqueue by one rule, and the
benchmark's readers turn the records into the band between the device's step
and the token's gap.

Four parts: the profiler under a fake clock; a CPU ``PipelineServer`` whose
records must account for every program and every gap; the five readers on a
hand-made record; and the flight recorder, which the per-step loop spans no
longer turn over.
"""

import importlib.util
import json
import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.obs import stepline
from llm_sharding_tpu.obs.metrics import REGISTRY
from llm_sharding_tpu.obs.stepline import PHASES, StepProfiler
from llm_sharding_tpu.obs.trace import FLIGHT_RECORDER
from llm_sharding_tpu.runtime.engine import PipelineEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = tiny_llama(
    num_hidden_layers=2, max_position_embeddings=512,
    eos_token_id=10**6, eos_token_ids=[10**6],  # replies run to their length
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def now(self):
        return self.t


def _value(name, **labels):
    return REGISTRY.get(name).labels(**labels).value


# ------------------------------------------------ (a) the profiler, fake clock


def _one_step(p, clk):
    """A step of 4.5 s from t=100: a dispatch that ends a bubble, a wait of
    1.0 for a log that landed at 102.75, its apply, and gaps between."""
    clk.t = 100.0
    p.begin_step(rows=1)
    clk.t = 100.5
    p.push("dispatch")
    clk.t = 101.0
    # the previous program landed at 99.0 (last seen busy at 98.0): before
    # this run of steps held work, so the bubble is cut to the step's begin
    p.dispatched("chunk", 7, 101.0, 0, 99.0, 98.0)
    p.pop()
    clk.t = 101.5
    p.push("fetch")
    clk.t = 102.0
    with p.blocking():
        clk.t = 103.0
    p.log_landed("chunk", 6, 41, 99.5, 102.75, True, True)
    clk.t = 103.25
    p.push("apply")
    clk.t = 103.75
    p.pop()
    p.log_applied(3)
    clk.t = 104.0
    p.pop()
    clk.t = 104.5
    return p.end_step(rows=1, tokens=3, pending=1)


def test_a_step_record_carries_the_tokens_path():
    clk = FakeClock()
    p = StepProfiler(clock=clk.now, name="t-path")
    lag_n = REGISTRY.get("server_token_emit_lag_seconds").labels().count
    lo0 = _value("server_device_starved_seconds_total", bound="lo")
    hi0 = _value("server_device_starved_seconds_total", bound="hi")
    rec = _one_step(p, clk).to_dict()
    assert (rec["seq"], rec["t0"], rec["wall_s"], rec["end"]) == (
        0, 100.0, 4.5, 4.5)
    assert rec["logs"] == [{
        "n": 6, "kind": "chunk", "by": 41, "enq": -0.5, "landed": 2.75,
        "exact": True, "waited": True, "applied": 3.75, "tokens": 3,
    }]
    assert rec["dispatches"] == [{
        "n": 7, "kind": "chunk", "enq": 1.0, "in_flight": 0,
        "starved_lo_s": 1.0, "starved_hi_s": 1.0,
    }]
    assert rec["idle_s"] == rec["starved_hi_s"] == 1.0
    # the old invariant stands ...
    assert rec["phases"] == {"dispatch": 0.5, "fetch": 1.0, "apply": 0.5}
    assert rec["blocked_s"] == 1.0 and rec["unattributed_s"] == 1.5
    # ... and the stretch from the landing to the step's end is split by
    # phase: the wait's last 0.25 and the closing 0.5 belong to no phase
    after = rec["after_landing"]
    assert after == {"fetch": 0.5, "apply": 0.5, "unattributed": 0.75}
    assert sum(after.values()) == rec["end"] - rec["logs"][0]["landed"]
    assert set(after) <= set(PHASES) | {"unattributed"}
    # the operator's view of the same step
    assert REGISTRY.get(
        "server_token_emit_lag_seconds").labels().count == lag_n + 1
    assert _value(
        "server_device_starved_seconds_total", bound="lo") == lo0 + 1.0
    assert _value(
        "server_device_starved_seconds_total", bound="hi") == hi0 + 1.0
    assert p.stats()["device_idle_frac"] == pytest.approx(1.0 / 4.5)


def test_the_steps_end_is_read_after_its_series_are_fed():
    """``wall_s`` closes the invariant; ``end`` is the step's last look at
    the clock, so what ``end_step`` costs is in ``after_landing``."""

    class Ticking(FakeClock):
        def now(self):
            self.t += 0.125
            return self.t

    clk = Ticking()
    p = StepProfiler(clock=clk.now, name="t-end")
    p.begin_step(rows=1)
    p.push("fetch")
    p.log_landed("chunk", 0, 0, 0.0, clk.t, True, True)
    p.pop()
    rec = p.end_step(rows=1).to_dict()
    assert rec["end"] > rec["wall_s"]
    after = rec["after_landing"]
    assert sum(after.values()) == pytest.approx(
        rec["end"] - rec["logs"][0]["landed"], abs=1e-12)
    assert after["unattributed"] >= rec["end"] - rec["wall_s"]
    host = sum(rec["phases"].values())
    assert rec["wall_s"] == pytest.approx(
        host + rec["blocked_s"] + rec["unattributed_s"], abs=1e-12)


def test_a_found_landing_is_a_bracket_and_a_full_queue_starves_nothing():
    clk = FakeClock(10.0)
    p = StepProfiler(clock=clk.now, name="t-bracket")
    p.begin_step(rows=1)
    clk.t = 11.0
    p.push("dispatch")
    # a poll at 10.75 found the log landed; the last that saw the device
    # busy was at 10.25: the device ran dry somewhere in between
    p.dispatched("admit", 3, 11.0, 0, 10.75, 10.25)
    # behind a program still out nothing is starved, whatever the stamps
    p.dispatched("chunk", 4, 11.0, 1, 10.75, 10.25)
    # the first program of a server has no landing before it
    p.dispatched("chunk", 5, 11.0, 0, None, None)
    clk.t = 12.0
    p.pop()
    # the found log is applied by this step: the dispatch phase's time up to
    # the poll's stamp is before the landing, the rest after
    p.log_landed("admit", 2, 0, 9.0, 10.75, False, False)
    p.log_applied(1)
    rec = p.end_step(rows=1, tokens=1).to_dict()
    lo_hi = [(d["starved_lo_s"], d["starved_hi_s"]) for d in rec["dispatches"]]
    assert lo_hi == [(0.25, 0.75), (0.0, 0.0), (0.0, 0.0)]
    assert [d["in_flight"] for d in rec["dispatches"]] == [0, 1, 0]
    assert (rec["idle_s"], rec["starved_hi_s"]) == (0.25, 0.75)
    assert rec["after_landing"] == {"dispatch": 1.0, "unattributed": 0.25}


def test_host_bound_steps_and_idle_steps():
    clk = FakeClock()
    p = StepProfiler(clock=clk.now, name="t-bound")
    bound0 = _value("server_steps_host_bound_total")
    # a decode log an earlier step's poll found landed: every phase of the
    # applying step lies after its landing, and the step is host-bound
    clk.t = 1.0
    p.begin_step(rows=1, pending=1)
    p.push("admit")
    clk.t = 1.5
    p.pop()
    p.push("fetch")
    p.log_landed("chunk", 0, 0, 0.25, 0.75, False, False)
    clk.t = 2.0
    p.log_applied(1)
    p.pop()
    rec = p.end_step(rows=1, tokens=1).to_dict()
    assert rec["logs"][0]["waited"] is False
    assert rec["after_landing"] == {
        "admit": 0.5, "fetch": 0.5, "unattributed": 0.25}
    assert _value("server_steps_host_bound_total") == bound0 + 1
    # only a decode chunk's log counts: an admission's carries no decode
    # step, and a verify's step drains its own program
    for n, kind in ((1, "admit"), (2, "verify")):
        p.begin_step(rows=1, pending=1)
        p.log_landed(kind, n, 1, 1.0, 1.5, False, False)
        p.end_step(rows=1)
    assert _value("server_steps_host_bound_total") == bound0 + 1
    # while the server holds work a bubble runs across the steps' borders:
    # from the landing an earlier step's last poll found to this enqueue
    clk.t = 3.0
    p.begin_step(rows=1)
    p.dispatched("chunk", 3, 3.5, 0, 2.5, 2.25)
    rec = p.end_step(pending=1).to_dict()  # the last row is done
    assert rec["dispatches"][0]["starved_lo_s"] == 1.0
    assert rec["dispatches"][0]["starved_hi_s"] == 1.25
    # that step ended with no live row and no queue (a log of the finished
    # row still out): the account is closed, whether or not the caller goes
    # on stepping. The next bubble starts at the begin of the step that
    # first sees work again — the 46 s between are the client's, not the
    # device's
    clk.t = 50.0
    p.begin_step(queued=1, pending=1)
    clk.t = 50.5
    p.dispatched("admit", 4, 50.5, 0, 4.0, 4.0)
    rec = p.end_step(rows=1, pending=1).to_dict()
    assert rec["dispatches"][0]["starved_lo_s"] == 0.5
    assert rec["dispatches"][0]["starved_hi_s"] == 0.5


def test_step_report_prints_the_tokens_path(tmp_path, capsys):
    from llm_sharding_tpu import cli

    clk = FakeClock()
    p = StepProfiler(clock=clk.now, name="t-report")
    first = _one_step(p, clk).to_dict()
    # a second step 10 s on: its log (the program after the first's) landed
    # 9.5 s after the first's and was there before the host came for it
    clk.t = 110.0
    p.begin_step(rows=1, pending=1)
    clk.t = 112.0
    p.push("fetch")
    clk.t = 112.25
    p.log_landed("chunk", 7, 0, 101.0, 112.25, True, False)
    clk.t = 113.0
    p.log_applied(2)
    p.pop()
    second = p.end_step(rows=1, tokens=2).to_dict()
    bundle = tmp_path / "steps.json"
    bundle.write_text(json.dumps({"profiler": "server",
                                  "steps": [first, second]}))
    assert cli.main(["step-report", "--json", str(bundle)]) == 0
    path = json.loads(capsys.readouterr().out)["token_path"]
    assert (path["token_logs"], path["landing_gaps"]) == (2, 1)
    assert path["emit_lag_p50_ms"] == pytest.approx(1250.0)  # 1.75 and 0.75
    assert path["landing_gap_p50_ms"] == pytest.approx(9500.0)
    assert path["host_bound_frac"] == 0.5
    assert (path["starved_lo_s"], path["starved_hi_s"]) == (1.0, 1.0)
    rows = {r["phase"]: r for r in path["after_landing"]}
    assert rows["fetch"]["total_s"] == pytest.approx(0.5 + 0.75)
    assert sum(r["lag_pct"] for r in rows.values()) == pytest.approx(100.0)
    assert cli.main(["step-report", str(bundle)]) == 0
    text = capsys.readouterr().out
    assert "emit lag (landing -> step end)" in text
    assert "after the last log's landing, by phase:" in text
    # records of a build without the stamps: the rest of the report stands
    old = [{k: v for k, v in first.items()
            if k not in ("logs", "dispatches", "end", "after_landing")}]
    from llm_sharding_tpu.obs.report import render_step_report, token_path

    assert token_path(old) is None
    assert "token's path" not in render_step_report(old)


def test_a_disabled_profiler_never_looks_at_the_clock():
    def clock():
        raise AssertionError("a disabled profiler read the clock")

    p = StepProfiler(clock=clock, name="t-off")
    p.set_enabled(False)
    p.begin_step(rows=1)
    p.dispatched("chunk", 0, 1.0, 0, 0.5, 0.5)
    p.log_landed("chunk", 0, 0, 1.0, 2.0, True, True)
    p.log_applied(1)
    assert p.end_step() is None and p.steps_total == 0


# ------------------------------------------- (b) a CPU server's own account


@pytest.fixture(scope="module")
def engine():
    params = llama.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    return PipelineEngine(
        CFG, params, num_stages=1, devices=jax.devices()[:1],
        cache_dtype=jnp.float32,
    )


def _prompt(seed, n=5):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n).astype(np.int32)


def _pump(srv, reqs, records):
    """Step until ``reqs`` are done and nothing is in flight, keeping every
    step's record (the idle tail's too)."""
    while not all(r.done for r in reqs) or srv._pending:
        srv.step()
        records.append(srv.stepline_snapshot(1)[-1])


def _lag_steps(records):
    """(end, landed, lag) on the absolute clock, per token-carrying step."""
    out = []
    for s in records:
        carried = [log for log in s["logs"] if log["tokens"]]
        if carried:
            landed = s["t0"] + carried[-1]["landed"]
            out.append((s["t0"] + s["end"], landed, s["t0"] + s["end"] - landed))
    return out


@pytest.mark.parametrize("rows", [1, 4])
def test_a_servers_records_account_for_every_program_and_every_gap(
    engine, rows, monkeypatch
):
    srv = engine.serve(capacity=512, batch_per_slot=rows)
    records: list = []
    reqs = [srv.submit(_prompt(i), 300) for i in range(rows)]
    _pump(srv, reqs, records)
    assert sum(s["tokens"] for s in records) == 300 * rows
    assert max(s["rows"] for s in records) == rows
    assert len(records) >= 300

    # a token's gap is the device's pace plus what the host's lag changed by
    steps = _lag_steps(records)
    assert len(steps) >= 300
    for (end0, landed0, lag0), (end1, landed1, lag1) in zip(steps, steps[1:]):
        assert end1 - end0 == pytest.approx(
            (landed1 - landed0) + (lag1 - lag0), abs=1e-9)
        assert landed1 >= landed0 and lag1 > 0.0

    # every program appears once where it was dispatched and once where its
    # log was applied, by the number of the step that dispatched it
    dispatched = {}
    for s in records:
        assert s["seq"] == (records[0]["seq"] + records.index(s))
        for d in s["dispatches"]:
            assert d["n"] not in dispatched
            dispatched[d["n"]] = (d["kind"], s["seq"])
            assert d["starved_hi_s"] >= d["starved_lo_s"] >= 0.0
    assert sorted(dispatched) == list(range(len(dispatched)))
    applied = {}
    for s in records:
        for log in s["logs"]:
            assert log["n"] not in applied
            applied[log["n"]] = (log["kind"], log["by"])
            assert log["by"] <= s["seq"]
            assert log["enq"] <= log["landed"] <= log["applied"] <= s["end"]
            # a landing the host waited for was waited for by this step
            assert log["waited"] or not log["exact"]
    assert applied == dispatched
    assert {k for k, _ in dispatched.values()} == {"admit", "chunk"}
    for s in records:
        host = sum(s["phases"].values())
        assert s["wall_s"] == pytest.approx(
            host + s["blocked_s"] + s["unattributed_s"], abs=1e-9)
        if "after_landing" in s:
            landed = [l["landed"] for l in s["logs"] if l["landed"] is not None]
            assert sum(s["after_landing"].values()) == pytest.approx(
                s["end"] - landed[-1], abs=1e-9)

    # an admission under load: the step flushes every log in flight before
    # it dispatches the prefill, so the device has nothing to run from the
    # last chunk's landing to the admission's enqueue. That bubble is
    # counted where the program is enqueued — by the rule a decode chunk's is
    load = srv.submit(_prompt(98), 40)
    more: list = []
    for _ in range(5):
        srv.step()
        more.append(srv.stepline_snapshot(1)[-1])
    _pump(srv, [load, srv.submit(_prompt(99), 4)], more)
    late = [(i, d) for i, s in enumerate(more) for d in s["dispatches"]
            if d["kind"] == "admit"][1]
    assert late[1]["in_flight"] == 0 and late[1]["starved_lo_s"] > 0.0
    assert more[late[0]]["idle_s"] >= late[1]["starved_lo_s"]
    # the server held work from the first admission to that one
    assert all(s["rows"] or s["queued"] for s in more[:late[0]])

    # a client's think time is not the device's: a caller that stops
    # stepping when its reply is whole (``result``) leaves no idle step
    # behind, and the next admission's bubble still starts at its own step
    srv.result(srv.submit(_prompt(97), 4))
    time.sleep(0.05)
    after: list = []
    _pump(srv, [srv.submit(_prompt(96), 4)], after)
    first = after[0]["dispatches"][0]
    assert first["kind"] == "admit" and first["in_flight"] == 0
    assert 0.0 < first["starved_hi_s"] <= first["enq"]

    # a slow host phase between the dispatch and the look at the last log:
    # the device has finished by then, the step did not wait, and is counted
    bound0 = _value("server_steps_host_bound_total")
    sweep = srv._sweep_gauges_if_due

    def slow_sweep():
        time.sleep(0.02)
        return sweep()

    monkeypatch.setattr(srv, "_sweep_gauges_if_due", slow_sweep)
    slow: list = []
    _pump(srv, [srv.submit(_prompt(100), 6)], slow)
    decode = [log for s in slow for log in s["logs"] if log["kind"] == "chunk"]
    assert decode and not any(log["waited"] for log in decode)
    assert _value("server_steps_host_bound_total") >= bound0 + len(decode) - 1
    srv.close()


def test_a_chunked_admission_is_in_the_queues_account(engine):
    """Prefill chunks have no log of their own; they are programs of the
    queue all the same, numbered with the rest and counted by the one rule."""
    srv = engine.serve(
        capacity=256, kv_block_size=8, kv_blocks=65, prefill_chunk=16,
        prefix_cache="hbm",
    )
    records: list = []
    _pump(srv, [srv.submit(_prompt(5, 40), 8)], records)
    kinds = [d["kind"] for s in records for d in s["dispatches"]]
    # a bucket of 64 in chunks of 16, and the slot's arming
    assert kinds.count("prefill_chunk") == 4 and kinds.count("arm") == 1
    assert "admit" not in kinds
    ns = [d["n"] for s in records for d in s["dispatches"]]
    assert ns == list(range(len(ns)))
    first_chunk = next(
        d for s in records for d in s["dispatches"] if d["kind"] == "chunk")
    # the decode chunk behind them found the queue full, not starved
    assert first_chunk["in_flight"] >= 1 and first_chunk["starved_lo_s"] == 0.0
    logs = {log["n"] for s in records for log in s["logs"]}
    with_log = {d["n"] for s in records for d in s["dispatches"]
                if d["kind"] not in ("prefill_chunk", "arm")}
    assert logs == with_log
    srv.close()


# ------------------------------------------------- (c) the benchmark's readers


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name,
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


READERS = ("token_emit_lag_p50_ms", "token_emit_lag_p95_ms",
           "landing_gap_p95_ms", "host_bound_steps_pct", "queue_empty_lo_pct",
           "queue_empty_hi_pct")


def _step(i, landed, lag, kind="chunk", n=None, exact=True, waited=True,
          tokens=1, starved=(0.0, 0.0)):
    """Step ``i`` of 10 ms steps: one log landed at ``landed`` (absolute),
    the step ended ``lag`` later; one program dispatched."""
    t0 = 0.010 * i
    n = i if n is None else n
    return {
        "t": t0 + 0.010, "t0": t0, "wall_s": 0.009, "end": landed + lag - t0,
        "rows": 1, "queued": 0, "pending": 1,
        "logs": [{"n": n, "kind": kind, "by": i - 1, "enq": -0.009,
                  "landed": landed - t0, "exact": exact, "waited": waited,
                  "applied": landed - t0, "tokens": tokens}],
        "dispatches": [{"n": n + 1, "kind": "chunk", "enq": 0.001,
                        "in_flight": 1, "starved_lo_s": starved[0],
                        "starved_hi_s": starved[1]}],
    }


def test_the_readers_on_a_hand_made_record():
    # 101 steps whose logs land 10 ms apart but for the gap into step 60
    # (12 ms) and out of it (8 ms); lags of 0.1 ms, 0.3 ms in ten of them
    steps = []
    for i in range(1, 102):
        landed = 0.010 * i + 0.005 + (0.002 if i == 60 else 0.0)
        steps.append(_step(i, landed, 0.0003 if i % 10 == 0 else 0.0001))
    # an admission between two decode logs: the pair around it is no gap of
    # the device's pace, though both stamps are exact
    steps[30]["logs"][0]["kind"] = "admit"
    # a found stamp: the pairs on both sides of it are left out
    steps[45]["logs"][0].update(exact=False, waited=False)
    # a program whose log the records do not hold (applied by an idle step)
    for s in steps[80:]:
        s["logs"][0]["n"] += 1
        s["dispatches"][0]["n"] += 1
    steps[20]["dispatches"][0].update(starved_lo_s=0.0045, starved_hi_s=0.009)
    rec = {"window": [0.0, 2.0], "steps": steps}
    got = {name: _reader(name)(rec) for name in READERS}
    assert got["token_emit_lag_p50_ms"] == pytest.approx(0.1)
    assert got["token_emit_lag_p95_ms"] == pytest.approx(0.3)
    assert got["host_bound_steps_pct"] == pytest.approx(100.0 / 100)
    assert got["queue_empty_lo_pct"] == pytest.approx(
        100.0 * 0.0045 / (101 * 0.009))
    assert got["queue_empty_hi_pct"] == pytest.approx(
        100.0 * 0.009 / (101 * 0.009))
    from benchmark import path_reduce

    gaps = path_reduce.landing_gaps_s(rec)
    # 100 pairs less the admission's two, the found stamp's two and the
    # one across the missing program
    assert len(gaps) == 95
    assert max(gaps) == pytest.approx(0.012) and min(gaps) == pytest.approx(0.008)
    assert got["landing_gap_p95_ms"] == pytest.approx(10.0)
    # a step outside the window is no sample
    rec["window"] = [0.0, 0.5]
    assert len(path_reduce.emit_lags_s(rec)) == 49


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_stamps_reads_as_nothing(name):
    """The driver runs the parent under this PR's benchmark files: its step
    records have no ``logs``, and no reader raises."""
    read = _reader(name)
    parent_step = {"t": 0.5, "wall_s": 0.01, "idle_s": 0.0, "rows": 1,
                   "queued": 0, "pending": 1, "tokens": 1}
    assert read({"window": [0.0, 1.0], "steps": [parent_step]}) is None
    assert read({"window": [0.0, 1.0], "steps": []}) is None
    # the stamps are there and the window holds nothing to read
    quiet = _step(1, 0.015, 0.0001, kind="admit", tokens=0)
    quiet["dispatches"] = []
    quiet["wall_s"] = 0.0
    assert read({"window": [0.0, 1.0], "steps": [quiet]}) is None


def test_the_new_entries_are_appended_with_their_cells():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    nine = next(m for m in bench["end_to_end"]
                if m["name"] == "itl_p95_ms")["workloads"]
    # PR 55's nine, together where they were appended (a later PR's entries
    # come after them: PR 57's ten)
    first = [m["name"] for m in bench["per_layer"]].index(
        "token_emit_lag_p50_ms")
    tail = bench["per_layer"][first:first + 9]
    assert [m["name"] for m in tail] == [
        "token_emit_lag_p50_ms", "token_emit_lag_p95_ms",
        "landing_gap_p95_ms", "host_bound_steps_pct",
        "host_bound_steps_pct.backlog", "queue_empty_lo_pct",
        "queue_empty_hi_pct", "queue_empty_lo_pct.backlog",
        "queue_empty_hi_pct.backlog",
    ]
    for m in tail:
        # what the host's clock reads is entered under the host's layer
        assert m["layer"] == (
            "step programs" if m["name"] == "landing_gap_p95_ms"
            else "host loop")
        backlog = m["name"].endswith(".backlog")
        assert m["workloads"] == (["olmoe_1b_7b.backlog"] if backlog else nine)
        assert m["moves"] == ("out_tok_s" if backlog else "itl_p95_ms")
        assert m["better"] == "lower"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics",
            m["name"].split(".")[0] + ".py"))


# ------------------------------------------------------ (d) the flight recorder


def test_the_flight_recorder_keeps_a_request_through_4096_decode_steps(engine):
    """Two spans a step turned the postmortem ring over every 2,048 steps;
    without them the first request's tree outlives a long reply."""
    FLIGHT_RECORDER.clear()
    srv = engine.serve(capacity=512)
    first = srv.submit(_prompt(1), 8)
    srv.run_until_idle()
    steps0 = srv.stepline.steps_total
    for i in range(9):
        srv.submit(_prompt(2 + i), 480)
        srv.run_until_idle()
    assert srv.stepline.steps_total - steps0 > FLIGHT_RECORDER.capacity == 4096
    events = FLIGHT_RECORDER.snapshot()
    mine = {e["span"] for e in events
            if e.get("trace_id") == first.trace.trace_id}
    assert {"request", "prefill"} <= mine
    assert not {"chunk", "apply"} & {e["span"] for e in events}
    srv.close()
