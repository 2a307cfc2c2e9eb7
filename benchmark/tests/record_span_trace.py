#!/usr/bin/env python3
"""Records the small trace that test_span_reduce.py reduces. Run ON THE CHIP:

    python3 benchmark/tests/record_span_trace.py <out_dir>

Two small jitted programs named like the serve programs (``serve_chunk``,
``serve_prefill_chunk``) whose operations sit under words of the program's
scope vocabulary (``obs.stepline.SCOPES``; one operation under none), driven
through the program's own ``StepProfiler`` with the server's annotation
factory, so the host plane holds real ``serve.step`` / ``serve.<phase>`` /
``serve.blocked`` / ``serve.prefill`` annotations: idle polls (which must
write nothing), two runs of working steps with host sleeps between steps,
and each run's closing step. Writes ``span.xplane.pb`` and
``span.expect.json`` (what the reduction gave on the day, so the test pins
the arithmetic and the parser, not the chip).
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

from benchmark import span_reduce, trace_reduce  # noqa: E402
from llm_sharding_tpu.obs.stepline import SCOPES, StepProfiler  # noqa: E402
from llm_sharding_tpu.runtime.server import _profiler_annotation  # noqa: E402

LAYERS = 3


def programs():
    # optimization barriers keep the arena's slice, its transpose and the
    # attention's output apart, as a kernel's operands are in the real
    # programs: at this size XLA would fuse the whole layer into one fusion
    # under one scope
    keep = jax.lax.optimization_barrier

    def layer(l, carry):
        h, arena = carry
        with jax.named_scope("kv_take"):
            k = keep(jax.lax.dynamic_index_in_dim(arena, l, keepdims=False))
        with jax.named_scope("attn"):
            with jax.named_scope("kv_layout"):
                kh = keep(jnp.transpose(k, (1, 0)))
            h = keep(jax.nn.softmax(h @ kh, axis=-1))
        with jax.named_scope("mlp"):
            h = jax.nn.silu(h @ h)
        with jax.named_scope("kv_put"):
            arena = jax.lax.dynamic_update_slice(arena, (k + 1)[None], (l, 0, 0))
        return h, arena

    @jax.jit
    def serve_chunk(h, arena):
        h = h * 2  # under no scope of the vocabulary

        @jax.named_scope("state")
        def body(h, arena):
            return jax.lax.fori_loop(0, LAYERS, layer, (h, arena))

        return body(h, arena)

    @jax.jit
    @jax.named_scope("state")
    def serve_prefill_chunk(h):
        with jax.named_scope("attn"):
            h = jnp.tanh(h @ h.T)
        with jax.named_scope("mlp"):
            return jax.nn.silu(h @ h)

    return serve_chunk, serve_prefill_chunk


def main(out_dir: str) -> None:
    assert jax.devices()[0].platform == "tpu", "record on the chip"
    serve_chunk, serve_prefill_chunk = programs()
    h = jnp.ones((512, 512), jnp.bfloat16)
    arena = jnp.ones((LAYERS, 512, 512), jnp.bfloat16)
    jax.block_until_ready((serve_chunk(h, arena), serve_prefill_chunk(h)))
    sl = StepProfiler(name="recorder", annotate=_profiler_annotation)

    def step(rows=0, queued=0, pending=0, prefill=False, decode=False):
        sl.begin_step(rows, queued, pending)
        sl.push("admit")
        if prefill:
            with sl.prefill(rows=4, prompt_tokens=324, positions=2048):
                serve_prefill_chunk(h)
        sl.pop()
        out = None
        if decode:
            sl.push("dispatch")
            out = serve_chunk(h, arena)
            sl.pop()
        sl.push("fetch")
        if out is not None:
            with sl.blocking():
                jax.block_until_ready(out)
        sl.pop()
        sl.end_step()

    tmp = os.path.join(out_dir, "span_probe_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.TRACED_MARK):
        t0 = time.perf_counter()
    for _ in range(3):
        step()  # an empty server being polled
        time.sleep(0.0005)
    for run in range(2):
        step(queued=1, prefill=True, decode=True)
        for _ in range(2):
            time.sleep(0.001)  # the chip waits for the host between steps
            step(rows=1, pending=1, decode=True)
        step()  # the closing step
        for _ in range(3):
            time.sleep(0.0005)
            step()
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(tmp)
    shutil.copy(path, os.path.join(out_dir, "span.xplane.pb"))
    out = span_reduce.reduce_planes(
        span_reduce.read_xspace(path), window_s, SCOPES)
    with open(os.path.join(out_dir, "span.expect.json"), "w") as f:
        json.dump(out, f, indent=1)
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out_dir, "span.xplane.pb")), "bytes")
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
