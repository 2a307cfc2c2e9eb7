#!/usr/bin/env python3
"""Rehearsal without the chip: compile a configuration's serve programs at
their real geometry for a described ``v5e:2x2`` topology.

    JAX_PLATFORMS=cpu python3 benchmark/aot_check.py qwen25_7b qwen25_14b_pp4

For each configuration it hands the decode program (``serve_chunk``), the
chunked prefill (``serve_prefill_chunk``) and the largest one-shot admission
(``serve_admit`` at the prefill chunk's bucket) shapes with shardings on the
described chips and runs the TPU's own compiler: a kernel Mosaic refuses, a
program that does not fit, or a sharding rule that fails shows here and costs
no chip time. It prints each program's ``memory_analysis()`` per chip, from
which ``kv_blocks`` is chosen. Nothing runs: this says nothing about results
or times. The compile is not a chip run and is never reported as one.
"""

from __future__ import annotations

import json
import os
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

GIB = 1 << 30


def abstract_inputs(cfg_file: dict, mesh):
    """Shapes with shardings on ``mesh`` for everything a serve program
    takes: stage layers, masks, head, state."""
    from benchmark import blocks, harness, weights
    from llm_sharding_tpu.ops.quant import QTensor
    from llm_sharding_tpu.parallel import serve as serve_ops
    from llm_sharding_tpu.parallel.mesh import PIPE_AXIS

    model = harness.model_keys(cfg_file)
    block = blocks.load(cfg_file["model_type"])
    cfg = harness.model_config(cfg_file)
    S = mesh.shape[PIPE_AXIS]
    Lp = cfg.num_hidden_layers // S
    int8 = cfg_file["deployment"]["weight_dtype"] == "int8"
    act = jnp.bfloat16
    pipe = NamedSharding(mesh, P(PIPE_AXIS))
    rep = NamedSharding(mesh, P())
    sds = lambda shape, dtype, sh: jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def stack(leaves, per_stage: int) -> dict:
        out = {}
        for leaf in leaves:
            full = (S, per_stage, *leaf.shape)
            if int8 and leaf.matmul:
                out[leaf.name] = QTensor(
                    q=sds(full, jnp.int8, pipe),
                    scale=sds((S, per_stage, leaf.shape[-1]), act, pipe),
                )
            else:
                out[leaf.name] = sds(full, act, pipe)
        return out

    kinds = blocks.kinds(block, model)
    if kinds is None:
        layers = stack(block.layer_leaves(model), Lp)
    else:  # the per-kind tree the engine is handed (weights.make_params)
        leaves = block.layer_leaves(model)
        layers = {
            kind: stack(leaves[kind], len(ids) // S)
            for kind, ids in weights.layers_of_kinds(kinds, S).items()
        }
    masks = sds((S, Lp), jnp.bool_, pipe)
    # a table with a vocabulary dimension is held as one slice a stage
    head = {}
    for t in block.tables(model):
        if t.vocab_axis is None:
            head[t.name] = sds(t.shape, act, rep)
        else:
            shape = list(t.shape)
            shape[t.vocab_axis] = -(-shape[t.vocab_axis] // S)
            head[t.name] = sds((S, *shape), act, pipe)
    serve = cfg_file["serve"]
    cpu_mesh = jax.sharding.Mesh(
        np.asarray(jax.devices("cpu")[:S]), (PIPE_AXIS,)
    )
    shapes = jax.eval_shape(
        lambda: serve_ops.make_state(
            cfg, cpu_mesh, Lp, capacity=serve["capacity"],
            batch_per_slot=serve["batch_per_slot"], cache_dtype=act,
            act_dtype=act, kv_blocks=serve["kv_blocks"],
            kv_block_size=serve["kv_block_size"],
        )
    )
    specs = serve_ops.state_specs(shapes)
    state = jax.tree.map(
        lambda s, spec: sds(s.shape, s.dtype, NamedSharding(mesh, spec)),
        shapes, specs,
    )
    return cfg, layers, masks, head, state


def programs(cfg_file: dict, mesh):
    """``(name, lowered)`` for the programs a chat cell dispatches."""
    from llm_sharding_tpu.parallel import serve as serve_ops
    from llm_sharding_tpu.parallel.mesh import PIPE_AXIS

    cfg, layers, masks, head, state = abstract_inputs(cfg_file, mesh)
    S = mesh.shape[PIPE_AXIS]
    serve = cfg_file["serve"]
    Bs, BS, Sc = serve["batch_per_slot"], serve["kv_block_size"], serve["prefill_chunk"]
    rep = NamedSharding(mesh, P())
    arr = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=rep)
    i32 = lambda *shape: arr(shape, jnp.int32)
    yield "serve_chunk", serve_ops.serve_chunk.lower(
        cfg, mesh, layers, masks, head, state, S, S, False, False,
        tp=1, block_size=BS, attn="kernel", cp=1,
    )
    yield "serve_prefill_chunk", serve_ops.serve_prefill_chunk.lower(
        cfg, mesh, layers, masks, head, state, i32(Bs, Sc), i32(Bs, Sc),
        i32(), i32(), arr((), jnp.bool_), S, tp=1, block_size=BS,
        cache_dtype=jnp.bfloat16, prefix_off=i32(), attn="kernel", cp=1,
    )
    yield f"serve_admit[{Sc}]", serve_ops.serve_admit.lower(
        cfg, mesh, layers, masks, head, state, i32(Bs, Sc), i32(Bs),
        arr((Bs,), jnp.bool_), i32(), i32(Bs), i32(Bs),
        arr((Bs,), jnp.float32), i32(Bs), arr((Bs,), jnp.float32), S,
        jnp.bfloat16, prompt_embeds=None, filtering=False, prefix_kv=None,
        prefix_len=None, key_override=None, tp=1, block_size=BS,
        prefix_in_arena=False, cp=1,
    )


def check(name: str) -> dict:
    from jax.experimental import topologies
    from llm_sharding_tpu.parallel.mesh import pipeline_mesh

    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg_file = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    S = int(cfg_file["deployment"]["num_stages"])
    mesh = pipeline_mesh(S, list(topo.devices)[:S])
    out = {}
    # the program asks jax.default_backend() which attention to lower; here
    # that is the CPU, so the rehearsal answers for the described chip
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered_all = list(programs(cfg_file, mesh))
    for prog, lowered in lowered_all:
        t = time.perf_counter()
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        out[prog] = {
            "compile_s": round(time.perf_counter() - t, 1),
            "argument_GiB": round(m.argument_size_in_bytes / GIB, 3),
            "output_GiB": round(m.output_size_in_bytes / GIB, 3),
            "alias_GiB": round(m.alias_size_in_bytes / GIB, 3),
            "temp_GiB": round(m.temp_size_in_bytes / GIB, 3),
            "peak_GiB": round(
                (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes) / GIB, 3),
            "mosaic_kernels": text.count("tpu_custom_call"),
            "collective_permutes": text.count("collective-permute("),
        }
        print(name, prog, json.dumps(out[prog]), flush=True)
    return out


if __name__ == "__main__":
    jax.config.update("jax_enable_compilation_cache", False)
    for name in sys.argv[1:] or ["qwen25_7b", "qwen25_14b_pp4"]:
        check(name)
