"""One run of one cell, after the device check: set-up, ramp, window, records.

``run_cell`` is what ``run.py`` calls once it has found the chips the cell
asks for. The tests call it too, with a tiny configuration and the CPU's
devices — the only way a CPU reaches it, since ``run.py`` has no option that
lets a measurement fall back.

The harness owns the step loop, as the program's own daemon does
(``runtime/ingress._pump_loop``): one pump thread is the only caller of
``server.step()``; a generator thread calls ``server.submit()`` at each
request's due time. After every step the pump stamps each newly visible
token of every live request with ``time.perf_counter()`` — what a reader of
``server.stream()`` on another thread would see. From the program the
harness takes the server, its requests' own stamps, its step records and its
gauges; every end-to-end number is the harness's own clock.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import os
import threading
import time
from typing import Optional

import numpy as np
import jax

from benchmark import blocks, loadgen, reference, samples, weights

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WARM_NEW_TOKENS = 2
# a configuration's ``serve.kv_dtype`` → the type the arena's arrays must have
ARENA_DTYPES = {"bf16": "bfloat16", "int8": "int8", "fp8": "float8_e4m3fn"}


# --------------------------------------------------------------- records

class Tracked:
    """One request as the harness saw it (all times ``perf_counter``)."""

    __slots__ = ("plan", "due", "submitted", "req", "seen", "stamps",
                 "error", "finished")

    def __init__(self, plan: loadgen.Planned, due: float):
        self.plan = plan
        self.due = due
        self.submitted: Optional[float] = None
        self.req = None
        self.seen = 0
        self.stamps: list[float] = []
        self.error: Optional[str] = None
        self.finished: Optional[float] = None

    def to_dict(self) -> dict:
        r = self.req
        return {
            "index": self.plan.index,
            "prompt_len": int(len(self.plan.prompt)),
            "max_new": self.plan.max_new,
            "due": self.due,
            "submitted": self.submitted,
            "server_submitted_at": getattr(r, "submitted_at", None),
            "server_started_at": getattr(r, "started_at", None),
            "stamps": self.stamps,
            "finished": self.finished,
            "error": self.error,
        }


class CompileCounter:
    """Counts XLA compilations (and loads from the compile cache) by when
    they ended, so the window can be shown to hold none."""

    def __init__(self):
        self.ends: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.ends.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.ends)


# ------------------------------------------------------------------ drive

class Driver:
    """The pump and the generator around one server."""

    def __init__(self, server, annotate: bool):
        self.server = server
        self.annotate = annotate
        self.tracked: list[Tracked] = []
        self._new: collections.deque = collections.deque()
        self._live: list[Tracked] = []
        self.steps: list[dict] = []
        self.kv_samples: list[tuple] = []
        self.pump_marks: list[tuple] = []  # (t_begin, t_end, progressed)
        self._stop = threading.Event()
        self.on_finish = None  # closed loop: called in the pump thread
        self.pump_error: Optional[BaseException] = None
        from llm_sharding_tpu.obs.metrics import REGISTRY

        self._kv_in_use = REGISTRY.get("server_kv_blocks_in_use")
        self._kv_total = REGISTRY.get("server_kv_blocks_total")

    def _span(self, name: str):
        if self.annotate:
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def submit(self, t: Tracked) -> None:
        """Hand one request to the server, now."""
        t.submitted = time.perf_counter()
        try:
            with self._span("bench.submit"):
                t.req = self.server.submit(t.plan.prompt, t.plan.max_new)
        except Exception as e:  # refused: QueueFull, ServerClosed, too long
            t.error = repr(e)
            t.finished = time.perf_counter()
        self.tracked.append(t)
        if t.req is not None:
            self._new.append(t)

    def _after_step(self, now: float) -> None:
        while self._new:
            self._live.append(self._new.popleft())
        still = []
        for t in self._live:
            n = len(t.req.tokens)
            if n > t.seen:
                t.stamps.extend([now] * (n - t.seen))
                t.seen = n
            if t.req.done:
                t.finished = now
                if t.req.error is not None:
                    t.error = repr(t.req.error)
                if self.on_finish is not None:
                    self.on_finish(t)
            else:
                still.append(t)
        self._live = still

    def pump(self) -> None:
        try:
            while not self._stop.is_set():
                t0 = time.perf_counter()
                with self._span("bench.step"):
                    progressed = self.server.step()
                now = time.perf_counter()
                self._after_step(now)
                self.pump_marks.append((t0, now, bool(progressed)))
                if progressed:
                    rec = self.server.stepline_snapshot(1)
                    if rec:
                        self.steps.append(dict(rec[-1], t=now))
                    self.kv_samples.append(
                        (now, self._kv_in_use.value, self._kv_total.value)
                    )
                else:
                    time.sleep(0.0005)
        except BaseException as e:  # surfaces in the main thread
            self.pump_error = e

    def generate(self, plan: list, t0: float, stop: threading.Event) -> None:
        """Open loop: submit each request at its due time, late or not."""
        for p in plan:
            due = t0 + p.due_s
            while not stop.is_set():
                wait = due - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
            if stop.is_set():
                return
            self.submit(Tracked(p, due))

    def stop(self) -> None:
        self._stop.set()


# ------------------------------------------------------------------ set-up

def model_config(cfg_file: dict):
    from llm_sharding_tpu.models.config import ModelConfig

    return ModelConfig.from_hf_config(model_keys(cfg_file))


OWN_KEYS = ("name", "source", "reduced", "assumed", "deployment", "serve")


def model_keys(cfg_file: dict) -> dict:
    """The published keys of a configuration file: everything at its top
    level that is not the benchmark's own."""
    return {k: v for k, v in cfg_file.items() if k not in OWN_KEYS}


def build_server(cfg_file: dict, block, devices, seed: int, attn: str,
                 marks: dict):
    """Weights from the seed → engine → server. ``block`` is the
    configuration's block (``blocks.load(model_type)``). Returns ``(server,
    engine, host_params)``; ``host_params`` is the staged host copy on a ring
    and None on one chip (where the weights are made again for the check)."""
    from llm_sharding_tpu.runtime.engine import PipelineEngine

    model = model_keys(cfg_file)
    dep = cfg_file["deployment"]
    stages = int(dep["num_stages"])
    if len(devices) != stages:
        raise ValueError(
            f"configuration wants {stages} chips, run has {len(devices)}"
        )
    cfg = model_config(cfg_file)
    t = time.perf_counter()
    params = weights.make_params(
        block, model, seed, dep["weight_dtype"], devices
    )
    jax.block_until_ready(params)
    marks["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    host_params = None
    if stages == 1:
        wrapped = weights.hand_off(params)
        del params
        engine = PipelineEngine(
            cfg, wrapped, num_stages=1, devices=list(devices),
            host_staging=False,
        )
    else:
        host_params = weights.to_host(params)
        del params
        engine = PipelineEngine(
            cfg, host_params, num_stages=stages, devices=list(devices),
        )
    jax.block_until_ready((engine.stage_layers, engine.head_params))
    marks["engine_s"] = time.perf_counter() - t
    t = time.perf_counter()
    server = engine.serve(paged_attn=attn, **cfg_file["serve"])
    jax.block_until_ready(server.state)
    marks["server_s"] = time.perf_counter() - t
    return server, engine, host_params


def warm_up(driver: Driver, traffic: dict, cfg_file: dict, vocab: int) -> None:
    """Run one request through every program the mix can reach: each admit
    bucket up to the prefill chunk, the chunked path and its finish, and the
    decode step. Greedy only, so no sampling variant is compiled."""
    from llm_sharding_tpu.runtime.server import ADMIT_BUCKETS

    serve = cfg_file["serve"]
    chunk = serve.get("prefill_chunk")
    max_prompt = max_prompt_len(cfg_file, traffic)
    buckets = loadgen.reachable_buckets(traffic, ADMIT_BUCKETS, max_prompt)
    lengths = [b for b in buckets if chunk is None or b <= chunk]
    if chunk is not None and any(b > chunk for b in buckets):
        lengths.append(chunk + 1)
    rng = np.random.default_rng(0)
    for i, n in enumerate(lengths):
        plan = loadgen.Planned(
            index=-1 - i, due_s=0.0,
            prompt=rng.integers(0, vocab, size=min(n, max_prompt),
                                dtype=np.int32),
            max_new=WARM_NEW_TOKENS,
        )
        t = Tracked(plan, time.perf_counter())
        driver.submit(t)
        # one at a time: co-admission would skip a bucket's own program
        deadline = time.perf_counter() + 1100.0
        while t.finished is None:
            if time.perf_counter() > deadline:
                raise RuntimeError(f"warm-up of length {n} did not finish")
            _sleep_until(time.perf_counter() + 0.01, driver)
        if t.error is not None:
            raise RuntimeError(f"warm-up of length {n} failed: {t.error}")
    driver.tracked.clear()


def max_prompt_len(cfg_file: dict, traffic: dict) -> int:
    """The longest prompt the server can take with this mix's longest reply."""
    cap = int(cfg_file["serve"]["capacity"])
    out_max = int(traffic["output_len"].get("max", 0))
    return min(int(traffic["prompt_len"].get("max", cap)), cap - out_max - 1)


# ---------------------------------------------------------------- the run

class Session:
    """One process's life with one server: ``setup`` once, ``measure`` a
    window (the sweep measures several, a cell's run exactly one), then
    ``finish`` frees the server and checks the served tokens."""

    def __init__(self, *, cfg_file: dict, block, traffic: dict, devices,
                 seed: int, out_dir: str, attn: str = "kernel",
                 trace: bool = False):
        self.cfg_file, self.block, self.traffic = cfg_file, block, traffic
        self.devices, self.seed = list(devices), int(seed)
        self.out_dir, self.attn, self.trace = out_dir, attn, trace
        self.vocab = block.dims(model_keys(cfg_file))["vocab"]
        self.marks: dict = {}
        self.compiles = CompileCounter()
        self.server, self.engine, self.host_params = build_server(
            cfg_file, block, self.devices, self.seed, attn, self.marks
        )
        self.driver = Driver(self.server, annotate=trace)
        self.pump = threading.Thread(
            target=self.driver.pump, name="bench-pump", daemon=True
        )
        self.pump.start()
        t = time.perf_counter()
        warm_up(self.driver, traffic, cfg_file, self.vocab)
        self.marks["warm_s"] = time.perf_counter() - t

    def measure(self, cell_params: dict, seconds: float) -> dict:
        """Ramp, then one window of ``seconds``; returns the window's
        records. The generator is stopped at the window's end; the pump
        keeps running."""
        traffic, driver = self.traffic, self.driver
        ramp_s = float(traffic.get("ramp_s", 0.0))
        seconds = float(seconds)
        max_prompt = max_prompt_len(self.cfg_file, traffic)
        first = len(driver.tracked)
        stop_gen = threading.Event()
        gen = None
        t_ramp = time.perf_counter()
        if traffic["loop"] == "open_poisson":
            plan = loadgen.open_schedule(
                traffic, float(cell_params["rate_rps"]), seconds,
                ramp_s + seconds, self.vocab, self.seed, max_prompt,
            )
            gen = threading.Thread(
                target=driver.generate, args=(plan, t_ramp, stop_gen),
                name="bench-gen", daemon=True,
            )
            gen.start()
        elif traffic["loop"] == "closed":
            rows = int(self.cfg_file["serve"]["batch_per_slot"]) * len(
                self.devices
            )
            clients = loadgen.ClosedClients(
                traffic, int(cell_params["clients_per_row"] * rows),
                self.vocab, self.seed, max_prompt,
            )

            def again(t: Tracked) -> None:
                if not stop_gen.is_set():
                    driver.submit(
                        Tracked(clients.next(t.plan.client), t.finished)
                    )

            driver.on_finish = again
            for c in range(clients.clients):
                driver.submit(Tracked(clients.next(c), time.perf_counter()))
        else:
            raise ValueError(f"unknown loop kind {traffic['loop']!r}")

        t0 = t_ramp + ramp_s
        t1 = t0 + seconds
        traced = None
        _sleep_until(t0, driver)
        if self.trace:
            # the profiler sees the first ``trace_s`` seconds of the window,
            # in every cell of the mix: nothing steers it towards the work
            trace_s = min(float(traffic.get("trace_s", 8.0)), seconds)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(
                os.path.join(self.out_dir, "trace"), profiler_options=opts
            )
            with jax.profiler.TraceAnnotation("bench.traced"):
                ta = time.perf_counter()  # ties the trace's clock to ours
            _sleep_until(ta + trace_s, driver)
            tb = time.perf_counter()
            jax.profiler.stop_trace()
            traced = (ta, tb)
        _sleep_until(t1, driver)
        stop_gen.set()
        if gen is not None:
            gen.join(timeout=10.0)
            if gen.is_alive():
                raise RuntimeError("the generator thread did not stop")
        tracked = list(driver.tracked[first:])
        return {
            "seed": self.seed, "seconds": seconds, "window": [t0, t1],
            "traced": traced,
            "requests": [t.to_dict() for t in tracked],
            "steps": [s for s in driver.steps if s["t"] >= t_ramp],
            "kv_samples": [s for s in driver.kv_samples if s[0] >= t_ramp],
            "pump_marks": [m for m in driver.pump_marks if m[1] >= t_ramp],
            "compiles_in_window": self.compiles.between(t0, t1),
            "tail_s": float(traffic.get("tail_s", seconds)),
            "cell_params": cell_params,
        }

    def drain(self, timeout_s: float = 300.0) -> None:
        """Let every submitted request finish (between a sweep's rates)."""
        deadline = time.perf_counter() + timeout_s
        while any(t.finished is None for t in self.driver.tracked):
            if time.perf_counter() > deadline:
                raise RuntimeError("the server did not drain in time")
            _sleep_until(time.perf_counter() + 0.05, self.driver)

    def finish(self) -> dict:
        """Stop the pump, read what only a live server can tell, free it,
        and score a sample of the served requests under the reference."""
        from llm_sharding_tpu.obs.metrics import REGISTRY

        driver = self.driver
        driver.stop()
        self.pump.join(timeout=120.0)
        if self.pump.is_alive():
            raise RuntimeError("the pump thread did not stop")
        if driver.pump_error is not None:
            raise driver.pump_error

        def one_hot(name: str, label: str) -> Optional[str]:
            fam = REGISTRY.get(name)
            on = [dict(zip(fam.label_names, v))[label]
                  for v, child in fam.series() if child.value > 0]
            return on[0] if len(on) == 1 else None

        paths = {
            "attn_backend": one_hot("server_attn_backend", "backend"),
            "prefill_path": one_hot("server_prefill_path", "path"),
            "prefix_hit_tokens": sum(
                c.value for _, c in REGISTRY.get(
                    "server_prefix_cache_hit_tokens_total").series()
            ),
        }
        # what the arena really holds, read from the arrays themselves: the
        # token margins cannot tell an int8 arena from a bf16 one
        paths["arena_dtype"] = sorted(
            {str(self.server.state.k.dtype), str(self.server.state.v.dtype)}
        )
        paths["arena_dtype_wanted"] = ARENA_DTYPES[
            self.cfg_file["serve"].get("kv_dtype", "bf16")
        ]
        memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices
        )
        samples = pick_samples(driver.tracked, self.seed)
        self.server.close()
        self.server = self.engine = driver.server = None
        gc.collect()
        scored = check(
            self.cfg_file, self.block, self.seed, self.devices,
            self.host_params, samples,
        )
        kernels_ok = self.attn != "kernel" or (
            paths["attn_backend"] == "kernel"
            and paths["prefill_path"] in ("kernel", None)
        )
        return {
            "paths": paths, "memory_peak_bytes": int(memory_peak),
            "reference": scored, "kernels_ok": bool(kernels_ok),
            "arena_ok": paths["arena_dtype"] == [paths["arena_dtype_wanted"]],
            "marks": self.marks,
        }


def _sleep_until(t: float, driver: Driver) -> None:
    while True:
        if driver.pump_error is not None:
            raise driver.pump_error
        wait = t - time.perf_counter()
        if wait <= 0:
            return
        time.sleep(min(wait, 0.05))


def run_cell(*, cell: dict, cfg_file: dict, block, traffic: dict,
             cell_params: dict, devices, seed: int, seconds: float,
             trace: bool, out_dir: str, t_process: float, readers: dict,
             attn: str = "kernel", peaks: Optional[dict] = None) -> dict:
    """Everything between the device check and the result line. ``block`` is
    the configuration's block module; ``readers`` maps each metric this run
    reports (end-to-end untraced, per-layer traced) to ``(read, unit)``.
    Returns ``{"result": the contract's last line, "records": what the run's
    file keeps}``."""
    session = Session(
        cfg_file=cfg_file, block=block, traffic=traffic, devices=devices,
        seed=seed, out_dir=out_dir, attn=attn, trace=trace,
    )
    rec = session.measure(cell_params, seconds)
    rec.update(session.finish())
    rec.update({
        "cell": cell["name"], "config": cfg_file, "traffic": traffic,
        "chips": len(devices), "peaks": peaks,
        "setup_s": rec["window"][0] - t_process,
    })
    if trace:
        from benchmark import trace_reduce

        rec["trace"] = trace_reduce.reduce_dir(
            os.path.join(out_dir, "trace"), rec
        )
    late = samples.overdue(rec)
    errors = sum(1 for r in rec["requests"] if r["error"] is not None)
    t0, t1 = rec["window"]
    attempted = sum(1 for r in rec["requests"] if t0 <= r["due"] <= t1)
    correct = bool(
        reference.verdict(rec["reference"], block)
        and rec["compiles_in_window"] == 0
        and rec["kernels_ok"]
        and rec["arena_ok"]
    )
    # each number ``correct`` rests on beside its limit: [value, limit]; the
    # first is a floor, the rest ceilings; no sample scored leaves no margin
    ref = rec["reference"]
    scored = ref["positions"] > 0
    compared = {
        "scored_positions": [ref["positions"], 1],
        "margin_mean": [ref["margin_mean"] if scored else None,
                        block.DELTA_MEAN],
        "margin_max": [ref["margin_max"] if scored else None, block.DELTA_MAX],
        "compiles_in_window": [rec["compiles_in_window"], 0],
        "kernels_off_path": [int(not rec["kernels_ok"]), 0],
        "arena_of_another_type": [int(not rec["arena_ok"]), 0],
    }
    values = {}
    for name, (read, unit) in readers.items():
        v = read(rec)
        if v is not None:
            values[name] = {"value": float(v), "unit": unit}
    rec["metrics"] = values
    dev0 = devices[0]
    device = {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices), "memory_peak_bytes": rec["memory_peak_bytes"],
    }
    result = {
        "correct": correct, "attempted": int(attempted),
        "failed": int(errors + len(late)), "metrics": values,
        "device": device,
    }
    if trace:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = rec["trace"]["breakdown"]
    result["compared"] = compared  # last in the line
    return {"result": result, "records": rec}


# ------------------------------------------------------------- correctness

CHECK_SAMPLES = 8
CHECK_MAX_PROMPT = 512


def pick_samples(tracked: list, seed: int) -> list:
    """A seeded sample of finished requests with short enough prompts."""
    done = [
        t for t in tracked
        if t.req is not None and t.req.done and t.error is None
        and len(t.plan.prompt) <= CHECK_MAX_PROMPT and len(t.req.tokens) > 0
    ]
    rng = np.random.default_rng([int(seed), 0x636865])
    pick = rng.permutation(len(done))[:CHECK_SAMPLES]
    return [
        (np.asarray(done[i].plan.prompt), np.asarray(done[i].req.tokens))
        for i in sorted(pick)
    ]


def check(cfg_file: dict, block, seed: int, devices, host_params,
          samples) -> dict:
    """Score the sample under the block's float32 reference on chip 0, with
    the very arrays the engine was given: the staged host copy on a ring; on
    one chip (where the engine consumed them) the same jitted call made
    again."""
    if not samples:
        return {"positions": 0, "samples": 0, "margin_mean": float("inf"),
                "margin_max": float("inf"), "margin_p99": float("inf"),
                "argmax_share": 0.0}
    for a in jax.live_arrays():
        a.delete()
    model = model_keys(cfg_file)
    dev = devices[0]
    if host_params is None:
        params = weights.make_params(
            block, model, seed, cfg_file["deployment"]["weight_dtype"],
            devices,
        )
    else:
        params = host_params
    put = lambda tree: jax.tree.map(lambda a: jax.device_put(a, dev), tree)
    tables = put({t.name: params[t.name] for t in block.tables(model)})

    kinds = blocks.kinds(block, model)

    def get_layer(l: int):  # one layer resident, out of its kind's stack
        return put(weights.take_layer(params["layers"], kinds, l))

    return reference.score(block, model, get_layer, tables, samples)
