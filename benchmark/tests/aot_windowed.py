#!/usr/bin/env python3
"""``aot_check.py`` for a configuration with a KV state per kind of layer
(a windowed model), which ``aot_check.py`` itself cannot describe: it makes the state with one arena, and it compiles
``serve_admit``, which such a model never dispatches (every prompt admits
through the arena-native chunked path).

    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_windowed.py mimo_v25

The same rehearsal, with the state made as the server makes it (the window
layers' pool is every row's share of ``ceil((window + chunk) / BS) + 1``
blocks and the trash block): the decode program and the chunked prefill —
ONE chunk length, a windowed model prefills in whole chunks — compiled by the TPU's own compiler for a described ``v5e:2x2``. Nothing runs;
no number here is a chip's.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import aot_check  # noqa: E402  (sets JAX_PLATFORMS)

import jax  # noqa: E402


def check(name: str, chunks=None, texts: bool = False) -> dict:
    from jax.experimental import topologies
    from benchmark import blocks, harness
    from llm_sharding_tpu.parallel import serve as serve_ops
    from llm_sharding_tpu.parallel.mesh import pipeline_mesh

    with open(os.path.join(aot_check.HERE, "configs", name + ".json")) as f:
        cfg_file = json.load(f)
    block = blocks.load(cfg_file["model_type"])
    model, serve = harness.model_keys(cfg_file), cfg_file["serve"]
    swa = block.attn_layers(model)["swa"]
    chunk = serve["prefill_chunk"]
    quota = -(-(model["sliding_window"] + chunk) // serve["kv_block_size"]) + 1
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = pipeline_mesh(1, list(topo.devices)[:1])
    make = functools.partial(
        serve_ops.make_state, swa_layers=swa,
        kv_blocks_swa=serve["batch_per_slot"] * quota + 1,
    )
    out = {}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(serve_ops, "make_state", make):
        lowered = []
        for Sc in chunks or (chunk,):
            cf = dict(cfg_file, serve=dict(serve, prefill_chunk=Sc))
            gen = aot_check.programs(cf, mesh)
            decode, prefill = next(gen), next(gen)  # not serve_admit
            if Sc == chunk:
                lowered.append(decode)
            lowered.append((f"{prefill[0]}[{Sc}]", prefill[1]))
    for prog, low in lowered:
        t = time.perf_counter()
        compiled = low.compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        if texts:
            out[prog] = text
            continue
        out[prog] = {
            "compile_s": round(time.perf_counter() - t, 1),
            "argument_GiB": round(m.argument_size_in_bytes / aot_check.GIB, 3),
            "temp_GiB": round(m.temp_size_in_bytes / aot_check.GIB, 3),
            "peak_GiB": round(
                (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
                / aot_check.GIB, 3),
            "mosaic_kernels": text.count("tpu_custom_call"),
            "weight_copies_over_16MiB": sum(
                1 for line in text.split("\n")
                if " copy(" in line and "stage_layers" in line
            ),
        }
        print(name, prog, json.dumps(out[prog]), flush=True)
    return out


if __name__ == "__main__":
    jax.config.update("jax_enable_compilation_cache", False)
    for name in sys.argv[1:] or ["mimo_v25"]:
        check(name)
