#!/usr/bin/env python3
"""What the margins of ``reference.py`` read when the arena is NOT what the
configuration says. Run ON THE CHIP, once, when δ is set:

    python3 benchmark/tests/calibrate_delta.py qwen25_7b.chat int8

It runs the cell's set-up, ramp and a short window with ``serve.kv_dtype``
overridden (one arena type per process: a process holds its chip), and prints
the margins of the served tokens under the float32 reference. Not a cell's
run: no result line.
"""

import importlib.util
import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.argv, ARGS = sys.argv[:1], sys.argv[1:]

spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)


def main(cell_name: str, kv_dtype: str) -> None:
    from benchmark import blocks, harness

    bench = run.load(run.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg_file = run.load(run.ROOT, entry["file"])
    cfg_file["serve"]["kv_dtype"] = kv_dtype
    devices, peaks = run.find_devices(int(cell["chips"]))
    got = harness.run_cell(
        cell=cell, cfg_file=cfg_file, block=blocks.load(cfg_file["model_type"]),
        traffic=run.load(BENCH, "traffic", cell["traffic"] + ".json"),
        cell_params=run.load(BENCH, "cells", cell_name + ".json"),
        devices=devices, seed=77, seconds=30.0, trace=False,
        out_dir=os.path.join(BENCH, "out"), t_process=T0,
        readers=run.load_readers(bench, "end_to_end", cell_name), peaks=peaks,
    )
    print("calibrate", kv_dtype, json.dumps(got["records"]["reference"]),
          "verdict", got["result"]["correct"], flush=True)


if __name__ == "__main__":
    import jax
    from llm_sharding_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache("tpu")
    main(ARGS[0], ARGS[1])
