#!/usr/bin/env python3
"""Records the small trace that test_benchmark.py reduces. Run ON THE CHIP:

    python3 benchmark/tests/record_trace.py <out_dir>

Four executions of one small jitted program (named ``bench_probe``), each in
a ``bench.step`` annotation, with host sleeps in between so the trace has
idle gaps. Writes ``small.xplane.pb`` and ``small.expect.json`` (what the
reduction gave on the day, so the test pins the arithmetic, not the chip).
"""

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import trace_reduce  # noqa: E402


def main(out_dir: str) -> None:
    assert jax.devices()[0].platform == "tpu", "record on the chip"

    @jax.jit
    def bench_probe(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    bench_probe(x).block_until_ready()
    tmp = os.path.join(out_dir, "probe_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.perf_counter()
    for _ in range(4):
        with jax.profiler.TraceAnnotation("bench.step"):
            bench_probe(x).block_until_ready()
        time.sleep(0.002)
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(tmp)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    out = trace_reduce.reduce_planes(trace_reduce.read_planes(path), window_s)
    with open(os.path.join(out_dir, "small.expect.json"), "w") as f:
        json.dump({"window_s": window_s, "busy_s": out["busy_s"],
                   "executions": len(out["modules"]["bench_probe"][0])}, f)
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out_dir, "small.xplane.pb")), "bytes",
          json.dumps(out["breakdown"]))


if __name__ == "__main__":
    main(sys.argv[1])
