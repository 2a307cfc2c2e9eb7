#!/usr/bin/env python3
"""``aot_check.py`` for a configuration with a recurrent state beside the
arena (``nemotron_h``): ``aot_check.py`` describes its state (``make_state``
reads the mixers and the attention layers off the configuration) but also
compiles ``serve_admit``, which such a model never dispatches (every prompt
admits through the arena-native chunked path).

    JAX_PLATFORMS=cpu python3 benchmark/tests/aot_recurrent.py nemotron3_super_120b_a12b

The same rehearsal without it: the decode program and the chunked prefill
— ONE chunk length, in whole chunks — compiled by the TPU's own compiler for a described ``v5e:2x2``: each
program's peak and temporaries, its Mosaic calls, and whether any weight stack
is copied (re-laid). Nothing runs; no number here is a chip's.
"""

from __future__ import annotations

import json
import os
import sys
import time
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import aot_check  # noqa: E402  (sets JAX_PLATFORMS)

import jax  # noqa: E402


def check(name: str, texts: bool = False) -> dict:
    from jax.experimental import topologies
    from llm_sharding_tpu.parallel.mesh import pipeline_mesh

    with open(os.path.join(aot_check.HERE, "configs", name + ".json")) as f:
        cfg_file = json.load(f)
    chunk = cfg_file["serve"]["prefill_chunk"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = pipeline_mesh(1, list(topo.devices)[:1])
    out = {}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        gen = aot_check.programs(cfg_file, mesh)
        decode, prefill = next(gen), next(gen)  # not serve_admit
    for prog, low in (decode, (f"{prefill[0]}[{chunk}]", prefill[1])):
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
            "weight_copies": sum(
                1 for line in text.split("\n")
                if " copy(" in line and "stage_layers" in line
            ),
        }
        print(name, prog, json.dumps(out[prog]), flush=True)
    return out


if __name__ == "__main__":
    jax.config.update("jax_enable_compilation_cache", False)
    for name in sys.argv[1:] or ["nemotron3_super_120b_a12b"]:
        check(name)
