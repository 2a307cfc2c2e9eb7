#!/usr/bin/env python3
"""No chip: are a configuration's step programs the ones ANOTHER checkout
lowers? For a PR that must leave the accepted cells' programs alone.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python3 benchmark/tests/same_programs.py /path/to/parent [config ...]

Each side lowers ``aot_check.programs`` (``serve_chunk``,
``serve_prefill_chunk``, ``serve_admit[256]`` at the published widths, for the
described v5e) in a process of its own, from its own checkout. Two texts are
the same program when they are equal outside the Mosaic kernels' serialized
bodies AND each kernel's MLIR is equal once source locations are dropped: a
kernel's bytes hold the line numbers of ``ops/*.py``, which any edit above it
moves. Prints one line a program and exits 1 if any differs.
"""

import base64
import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]*)\\22')

LOWER = """
import json, os, sys
root = sys.argv[1]
sys.path[:0] = [root, os.path.join(root, "benchmark")]
from unittest import mock
import jax
jax.config.update("jax_enable_compilation_cache", False)
import aot_check
from jax.experimental import topologies
from llm_sharding_tpu.parallel.mesh import pipeline_mesh
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
out = {}
for name in sys.argv[3:]:
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        cfg_file = json.load(f)
    S = int(cfg_file["deployment"]["num_stages"])
    mesh = pipeline_mesh(S, list(topo.devices)[:S])
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        for prog, lowered in aot_check.programs(cfg_file, mesh):
            out[name + " " + prog] = lowered.as_text()
with open(sys.argv[2], "w") as f:
    json.dump(out, f)
"""


def lowered(root: str, names: list, out: str) -> dict:
    subprocess.run(
        [sys.executable, "-c", LOWER, root, out, *names], check=True,
        cwd=root, stderr=subprocess.DEVNULL,
    )
    with open(out) as f:
        return json.load(f)


def kernel_text(body: str) -> str:
    """A serialized Mosaic module as MLIR text without source locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def same(a: str, b: str) -> bool:
    if BODY.sub("BODY", a) != BODY.sub("BODY", b):
        return False
    ka, kb = BODY.findall(a), BODY.findall(b)
    return len(ka) == len(kb) and all(
        x == y or kernel_text(x) == kernel_text(y) for x, y in zip(ka, kb)
    )


if __name__ == "__main__":
    other = os.path.abspath(sys.argv[1])
    names = sys.argv[2:] or ["qwen25_7b", "qwen25_14b_pp4", "olmoe_1b_7b"]
    tmp = os.environ.get("TMPDIR", "/tmp")
    mine = lowered(ROOT, names, os.path.join(tmp, "same_programs.mine.json"))
    theirs = lowered(other, names, os.path.join(tmp, "same_programs.other.json"))
    differ = 0
    for key in mine:
        ok = key in theirs and same(mine[key], theirs[key])
        differ += not ok
        kernels = len(BODY.findall(mine[key]))
        print(f"{key}: {'the same program' if ok else 'DIFFERS'} "
              f"({len(mine[key])} characters, {kernels} Mosaic kernels)",
              flush=True)
    sys.exit(1 if differ else 0)
