#!/usr/bin/env python3
"""One run of one cell of the benchmark; or, with ``--sweep``, the knee sweep.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, traced ``breakdown``, and
last ``compared``: each number ``correct`` rests on beside its limit, which
are also the last lines of standard error);
everything else the run learned goes to ``benchmark/out/``. With ``--trace 0``
the metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics.

The run fails — another exit code than 0, no result line — unless JAX finds
TPUs of a kind listed in ``peaks.json``, as many as the cell's ``chips``, and
the program under test beside the benchmark. No option lets a measurement
fall back to the CPU.

``--sweep r1,r2,…`` is not a cell's run: one set-up, then one ramp and window
per rate, a table of what each rate did to the queue, and no result line. It
is how a cell's knee is found, once, when the cell is defined.
"""

import time

T_PROCESS = time.perf_counter()  # set-up runs from here to the window's start

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def die(msg: str) -> "NoReturn":
    print(f"benchmark/run.py: {msg}", file=sys.stderr)
    sys.exit(1)


def load(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_readers(bench: dict, group: str, cell: str) -> dict:
    """``{metric name: (read, unit)}`` for the metrics of ``group`` that this
    cell reports. A metric's reader is ``benchmark/<group>/<name>.py``, or,
    for a name with a suffix after a dot (``queue_wait_p95_ms.chat``), the
    file of the name before the dot: the suffix only says which cells."""
    folder = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[group]
    judged = {m["name"] for m in bench["end_to_end"]
              if cell in m.get("workloads", (cell,))}
    readers = {}
    for m in bench[group]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        # without a list: every cell (end to end), or every cell that
        # reports the end-to-end metric the per-layer metric moves
        if "workloads" not in m and m.get("moves", m["name"]) not in judged:
            continue
        for stem in (m["name"], m["name"].split(".", 1)[0]):
            path = os.path.join(HERE, folder, stem + ".py")
            if os.path.exists(path):
                break
        else:
            die(f"no reader for metric {m['name']!r} under benchmark/{folder}/")
        spec = importlib.util.spec_from_file_location(
            f"benchmark.{folder}.{stem.replace('.', '_')}", path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        readers[m["name"]] = (mod.read, m["unit"])
    return readers


def find_devices(chips: int):
    """The chips to run on and their peaks — or no run."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        die(f"found no TPU: jax reports platform {devs[0].platform!r}")
    peaks = load(HERE, "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        die(f"device kind {kind!r} is not in benchmark/peaks.json")
    if len(devs) < chips:
        die(f"the cell asks for {chips} chips, jax found {len(devs)}")
    return devs[:chips], peaks[kind]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates (requests/s): the knee sweep")
    args = ap.parse_args()

    bench = load(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        die(f"no cell {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg_file = load(ROOT, cfg_entry["file"])
    from benchmark import blocks  # imports nothing of jax until load()

    try:
        blocks.find(cfg_file.get("model_type"))
    except FileNotFoundError as e:
        die(f"configuration {cfg_entry['name']!r}: {e}")
    traffic = load(HERE, "traffic", cell["traffic"] + ".json")
    cell_params = load(HERE, "cells", cell["name"] + ".json")

    try:
        import llm_sharding_tpu  # noqa: F401  the system under test
    except ImportError as e:
        die(f"the program under test is not beside the benchmark: {e}")
    devices, peaks = find_devices(int(cell["chips"]))

    import jax
    from llm_sharding_tpu.utils.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache("tpu")
    # the benchmark's own small programs (weights, reference) are cached too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"devices: {len(devices)} x {devices[0].device_kind}; "
          f"compile cache: {cache_dir}", flush=True)

    from benchmark import harness

    block = blocks.load(cfg_file["model_type"])
    out_dir = os.path.join(HERE, "out")
    os.makedirs(out_dir, exist_ok=True)
    if args.sweep:
        from benchmark import sweep

        sweep.run(
            rates=[float(r) for r in args.sweep.split(",")], cfg_file=cfg_file,
            block=block, traffic=traffic, devices=devices, seed=args.seed,
            seconds=args.seconds, out_dir=out_dir, cell=cell["name"],
        )
        return
    got = harness.run_cell(
        cell=cell, cfg_file=cfg_file, block=block, traffic=traffic,
        cell_params=cell_params, devices=devices, seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace), out_dir=out_dir, t_process=T_PROCESS,
        readers=load_readers(
            bench, "per_layer" if args.trace else "end_to_end", cell["name"]),
        peaks=peaks,
    )
    rec, result = got["records"], got["result"]
    name = (f"{cell['name']}.seed{args.seed}.trace{args.trace}."
            f"{int(time.time())}.json")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, default=float)
    from benchmark import samples

    print("set-up by phase (s):", json.dumps(rec["marks"]))
    print("samples: ttft", len(samples.ttft_s(rec)), "gaps",
          len(samples.gaps_s(rec)), "tokens", samples.tokens_in_window(rec),
          "steps", len(samples.steps_in_window(rec)),
          "longest step (s)", round(max(
              (b - a for a, b, _ in rec["pump_marks"]
               if samples.in_window(rec, b)), default=0.0), 3),
          "longest pause of the pump (s)",
          round(samples.longest_pause_s(rec), 3),
          "compiles in window", rec["compiles_in_window"])
    print("paths:", json.dumps(rec["paths"]))
    print("reference:", json.dumps(rec["reference"]))
    if args.trace:
        print("per chip:", json.dumps(rec["trace"]["chips"]))
    print(json.dumps(result), flush=True)
    for what, (value, limit) in result["compared"].items():
        print(f"compared: {what} {value} limit {limit}", file=sys.stderr)


if __name__ == "__main__":
    main()
