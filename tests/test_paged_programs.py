"""The paged step programs as the compiler and the tracer see them: what
Mosaic and XLA accept for a described v5e (no chip: the compile is real,
nothing runs), the named scopes a lowered program carries, and the structure
of the traced programs (the arena stays where it lies). ``test_paged.py``
serves; ``test_paged_ops.py`` holds the ops to their XLA forms.
"""

import collections
import json
import os
import re
from unittest import mock

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama
from llm_sharding_tpu.runtime.engine import PipelineEngine
from llm_sharding_tpu.runtime.generate import generate

from paged_arena import (
    CFG, _block_shapes, _inner_jaxprs, _pallas_calls, oracle_tokens, prompt,
    tiny_engine,
)


@pytest.fixture(scope="module")
def setup():
    return tiny_engine()


def test_kernel_rules_learned_from_the_v5e_compiler(monkeypatch):
    """What Mosaic refused during bring-up stays refused — or repaired.

    Shape rule: the scalar-prefetched block table, and the decode kernel's
    two entries a row beside it, must fit scalar memory: ``[128, 2048]``
    and ``[124, 2048]`` int32 "exceeded smem capacity" (by 1.6K), ``[120,
    2048]`` compiles since the walk is a loop in the body and no longer an
    entry a cell (with it, PR 28 to PR 53, it exceeded by 62.1K); ``[2000,
    33]`` (an odd width: rows pad to 128 entries) is held ineligible with
    16 KiB to spare, ``[1500, 33]`` and ``[1900, 33]`` compile, and the
    number of key/value heads no longer counts — ``[100, 2048]`` compiles
    at 32 (AOT compiles of ``paged_attention_tpu`` for a described v5e;
    PERF.md, PR 28 and PR 54). Repairs: a
    ``kv_positions`` tile that is neither 128 lanes wide nor the whole
    window (odd table width at block 16) and the int8/fp8 scale operand
    (a ``(1, 1)`` block of ``[NB, Nkv]``) now lower for the TPU platform —
    the block-shape check runs at lowering, so the CPU can hold the line.
    The operands are the layer-stacked head-major pool and the layer index
    (``test_kernels_compile_for_a_described_v5e`` runs Mosaic itself)."""
    from llm_sharding_tpu.ops.paged_attention import (
        kernel_eligible, paged_attention_tpu, paged_prefill_tpu,
    )

    ok = dict(head_dim=128, block_size=16, cache_dtype=jnp.bfloat16,
              kv_heads=4)
    assert not kernel_eligible(**ok, rows=128, table_width=2048)
    assert not kernel_eligible(**ok, rows=124, table_width=2048)
    assert kernel_eligible(**ok, rows=120, table_width=2048)
    assert kernel_eligible(**ok, rows=104, table_width=2048)
    assert not kernel_eligible(**ok, rows=2000, table_width=33)
    assert kernel_eligible(**ok, rows=1900, table_width=33)
    assert kernel_eligible(**ok, rows=1500, table_width=33)
    assert not kernel_eligible(**ok, rows=4000, table_width=33)
    # the walk is the body's: neither the heads a block nor the store count
    assert kernel_eligible(**{**ok, "kv_heads": 32}, rows=100,
                           table_width=2048)
    assert kernel_eligible(**{**ok, "block_size": 32,
                              "cache_dtype": jnp.int8},
                           rows=104, table_width=2048)

    S = jax.ShapeDtypeStruct
    B, Nh, Nkv, D, NB, Lp = 4, 28, 4, 128, 64, 3  # G = 7: Qwen2.5-7B's fold
    for fn, Sq in ((paged_attention_tpu, 1), (paged_prefill_tpu, 128)):
        for store, block, T in ((jnp.bfloat16, 16, 33), (jnp.int8, 32, 32)):
            quant = store == jnp.int8
            arena = S((Lp, NB, Nkv, block, D), store)
            scale = S((Lp, NB, Nkv), jnp.float32) if quant else None
            jax.jit(
                lambda q, k, v, l, t, qp, kp, ks, vs, fn=fn: fn(
                    q, k, v, l, t, qp, kp, k_scale=ks, v_scale=vs
                )
            ).trace(
                S((B, Sq, Nh, D), jnp.bfloat16), arena, arena,
                S((), jnp.int32), S((B, T), jnp.int32),
                S((B, Sq), jnp.int32), S((B, T * block), jnp.int32),
                scale, scale,
            ).lower(lowering_platforms=("tpu",))

    # --paged-attn kernel fails at construction, by name, never mid-serve
    cfg = tiny_llama(num_hidden_layers=2, head_dim=128)
    eng = PipelineEngine(
        cfg, llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32),
        num_stages=1, cache_dtype=jnp.float32,
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=r"block table \[128, 2048\].*scalar"):
        eng.serve(
            capacity=32768, batch_per_slot=128, kv_block_size=16,
            kv_blocks=4097, paged_attn="kernel",
        )


#: Both benchmark cells' kernel shapes (``benchmark/configs/*.json``): 4
#: rows x 128 table entries of 32-token blocks, head 128; 28 q / 4 kv heads
#: (Qwen2.5-7B, one chip) and 40 / 8 (Qwen2.5-14B, a stage of the ring);
#: decode (S = 1) and a 256-token prefill chunk. The stack is cut to 3
#: layers x 260 blocks: the kernels' tiles do not depend on either.
#: OLMoE-1B-7B is plain MHA: 16 key/value heads and a query tile of G = 1.
_CELL_SHAPES = {"qwen25_7b": (28, 4), "qwen25_14b_pp4": (40, 8),
                "olmoe_1b_7b": (16, 16)}


@pytest.fixture(scope="module")
def v5e_host():
    """The four chips of a DESCRIBED v5e host: the TPU's compiler is
    installed, no chip is attached. Described here, inside a fixture of
    this one file (never at import: only one process may load the TPU's
    library)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return list(topo.devices)


@pytest.fixture(scope="module")
def v5e_chip(v5e_host):
    """One chip of that host, as the sharding of a single-chip program."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_host[0])


@pytest.mark.parametrize("store", ["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["paged_decode", "paged_prefill"])
@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_kernels_compile_for_a_described_v5e(v5e_chip, cell, kernel, store):
    """The TPU's own compiler (Mosaic included) accepts both kernels with
    the 5-D stacked operands — a squeezed layer dim, the ``(BS, D)`` tile at
    ``(layer, table[b, t], head)`` — and the layer index as one more
    scalar-prefetch operand, at both benchmark cells' shapes, over bf16 and
    int8 arenas. No chip: the topology is described (``v5e:2x2``), the
    compile is real, nothing runs."""
    from llm_sharding_tpu.ops.paged_attention import (
        paged_attention_tpu, paged_prefill_tpu,
    )

    Nh, Nkv = _CELL_SHAPES[cell]
    B, T, BS, D, Lp, NB = 4, 128, 32, 128, 3, 260
    Sq, fn = {
        "paged_decode": (1, paged_attention_tpu),
        "paged_prefill": (256, paged_prefill_tpu),
    }[kernel]
    dt = jnp.bfloat16 if store == "bf16" else jnp.int8
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )
    arena = S((Lp, NB, Nkv, BS, D), dt)
    scale = S((Lp, NB, Nkv), jnp.float32) if store == "int8" else None
    # conftest asks every matmul for "highest" precision (CPU oracles);
    # the chip runs the default, and Mosaic refuses an fp32 contraction of
    # bf16 operands
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda q, k, v, l, t, qp, kp, ks, vs: fn(
                q, k, v, l, t, qp, kp, k_scale=ks, v_scale=vs
            )
        ).lower(
            S((B, Sq, Nh, D), jnp.bfloat16), arena, arena, S((), jnp.int32),
            S((B, T), jnp.int32), S((B, Sq), jnp.int32),
            S((B, T * BS), jnp.int32), scale, scale,
        ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and kernel in text
    # the pool goes to the kernel as it lies: no copy or transpose of an
    # arena-sized operand beside the custom call
    arena_elems = Lp * NB * Nkv * BS * D
    for m in re.finditer(r"= \w+\[([\d,]+)\][^ ]* (copy|transpose)\(", text):
        assert np.prod([int(x) for x in m.group(1).split(",")]) < arena_elems


#: a chunk's write at the cells' arena entries: key/value heads, the lanes
#: of a stored key and of a value (0: the latent arena holds none; MiMo's
#: window layers store a key of 192 in 256 lanes beside a value of 128)
_CHUNK_WRITE_SHAPES = {
    "olmoe_1b_7b": (16, 128, 128), "qwen25_7b": (4, 128, 128),
    "gigachat31_702b_a36b": (1, 640, 0), "mimo_v25_swa": (8, 256, 128),
}


@pytest.mark.parametrize("cell", sorted(_CHUNK_WRITE_SHAPES))
def test_a_chunks_tile_write_leaves_the_carried_stack_where_it_lies(
        v5e_chip, cell):
    """``write_chunk_kv`` inside a scan that carries both arenas, as the
    layer scan does, compiled for the described v5e: the only operations
    whose result is as large as an arena are the scatters themselves — no
    copy, transpose or select of the stack (what a scatter with a
    non-contiguous window costs: ``write_block_kv``'s note) — and the
    program's temporaries stay far under one arena."""
    from llm_sharding_tpu.ops.paged_attention import write_chunk_kv

    Nkv, Dk, Dv = _CHUNK_WRITE_SHAPES[cell]
    B, Sc, BS, T, Lp, NB = 4, 256, 32, 128, 3, 260
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )

    def run(k_arena, v_arena, table, col0, k_new, v_new, valid):
        def one(carry, layer):
            return write_chunk_kv(
                *carry, layer, table, col0, k_new, v_new, valid=valid
            ), None
        return jax.lax.scan(
            one, (k_arena, v_arena), jnp.arange(Lp, dtype=jnp.int32)
        )[0]

    compiled = jax.jit(run, donate_argnums=(0, 1)).lower(
        S((Lp, NB, Nkv, BS, Dk), jnp.bfloat16),
        S((Lp, NB, Nkv, BS, Dv), jnp.bfloat16), S((B, T), jnp.int32),
        S((), jnp.int32), S((B, Sc, Nkv, Dk), jnp.bfloat16),
        S((B, Sc, Nkv, Dv), jnp.bfloat16), S((), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    arena_elems = Lp * NB * Nkv * BS * min(d for d in (Dk, Dv) if d)
    big = [
        # an arena-sized fusion is the scatter's own (in place), no loop
        "scatter" if m.group(2) == "fusion" and "kind=kCustom" in m.group(0)
        else m.group(2)
        for m in re.finditer(
            r"= \w+\[([\d,]+)\][^ ]* ([\w-]+)\(.*", text)
        if np.prod([int(x) for x in m.group(1).split(",")]) >= arena_elems
    ]
    assert "scatter" in big
    assert set(big) <= {
        "scatter", "parameter", "get-tuple-element", "bitcast", "while",
        "tuple",
    }, sorted(set(big))
    assert compiled.memory_analysis().temp_size_in_bytes < arena_elems // 4


@pytest.mark.parametrize("walk", ["built_in_the_op", "handed_in"])
@pytest.mark.parametrize(
    "cell", sorted(_CELL_SHAPES) + ["gigachat31_702b_a36b"]
)
def test_the_prefill_kernel_has_one_grid_axis_of_traced_length(
        v5e_chip, cell, walk):
    """All four configurations' chunk shapes (the latent one: 64 heads over
    one latent head of 640 lanes, values its first 512) compile for the
    described v5e with ONE grid axis whose bound is a traced scalar — the
    walk's length, no shape of the program — whether the op builds the
    walk or ``serve_prefill_chunk`` hands it in."""
    from llm_sharding_tpu.ops.paged_attention import (
        paged_prefill_tpu, prefill_walk,
    )

    Nh, Nkv, D, lv = {**{k: (*v, 128, 0) for k, v in _CELL_SHAPES.items()},
                      "gigachat31_702b_a36b": (64, 1, 640, 512)}[cell]
    B, T, BS, Lp, NB, Sq = 4, 128, 32, 3, 260, 256
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )

    def fn(q, k, v, l, t, qp, kp):
        w = prefill_walk(t, qp, kp, q_heads=Nh, kv_heads=Nkv)
        return paged_prefill_tpu(
            q, k, v, l, t, qp, kp, latent_v=lv,
            walk=w if walk == "handed_in" else None,
        )

    with jax.default_matmul_precision("default"):
        lowered = jax.jit(fn).lower(
            S((B, Sq, Nh, D), jnp.bfloat16),
            S((Lp, NB, Nkv, BS, D), jnp.bfloat16),
            S((Lp, NB, Nkv, BS, 0 if lv else D), jnp.bfloat16),
            S((), jnp.int32), S((B, T), jnp.int32), S((B, Sq), jnp.int32),
            S((B, T * BS), jnp.int32),
        )
        text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "paged_prefill" in text
    # one grid axis, its bound no constant of the kernel (Mosaic writes a
    # dynamic bound as the least int64): handed to it at run time
    import base64
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    body = re.search(
        r'\\22body\\22: \\22([A-Za-z0-9+/=]*)\\22', lowered.as_text()
    ).group(1)
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        kernel = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False
        )
    bounds = re.findall(r"iteration_bounds = array<i64: ([^>]*)>", kernel)
    assert bounds == [str(-2**63)]


def test_the_prefill_walk_counts_against_scalar_memory():
    """``kernel_eligible`` holds the table AND the prefill kernel's walk
    (an entry per cell of every row, head and query tile) to the scalar
    memory the v5e has: the benchmark's geometries fit with room, a slot of
    64 rows of Qwen2.5-7B at a 32k capacity does not."""
    from llm_sharding_tpu.ops.paged_attention import (
        kernel_eligible, prefill_query_tiles,
    )

    assert prefill_query_tiles(7, 256) == 7  # Qwen2.5-7B: a tile a group
    assert prefill_query_tiles(1, 256) == 1  # MHA
    assert prefill_query_tiles(64, 256) == 64  # absorbed latent attention
    assert prefill_query_tiles(2, 16) == 1  # a chunk under the tile
    ok = dict(head_dim=128, block_size=32, cache_dtype=jnp.bfloat16)
    for kv, tiles in ((4, 7), (8, 5), (16, 1), (1, 64)):
        assert kernel_eligible(**ok, rows=4, table_width=128, kv_heads=kv,
                               prefill_tiles=tiles)
    big = dict(rows=64, table_width=1024, kv_heads=4)
    assert kernel_eligible(**ok, **big)  # the decode walk alone fits
    assert not kernel_eligible(**ok, **big, prefill_tiles=7)


_HLO_BYTES = {"s8": 1, "u8": 1, "bf16": 2, "f16": 2, "f32": 4, "s32": 4}


def _weight_stack_relayouts(text, floor=16 << 20):
    """The ``copy`` instructions of a compiled program that re-lay a weight
    out: the operand a ``stage_layers`` / ``head_params`` parameter (by the
    name jax gave it), the result above ``floor`` bytes, the result's
    minor-to-major order another than its operand's. A prefetch into
    another memory (the same order, an ``S(1)`` suffix) is a move and is
    not returned."""
    order = dict(re.findall(
        r"(%[\w.\-]+) = \w+\[[\d,]*\]\{([\d,]*)", text
    ))
    found = []
    for m in re.finditer(
        r"(%[\w.\-]+) = (\w+)\[([\d,]*)\]\{([\d,]*)[^ ]* "
        r"copy\((%[\w.\-]+)\)[^\n]*"
        r"op_name=\"(?:stage_layers|head_params)[^\n]*", text,
    ):
        name, dtype, shape, minor_to_major, operand = m.groups()
        size = _HLO_BYTES.get(dtype, 4) * int(
            np.prod([int(x) for x in shape.split(",")])
        )
        if size > floor and order.get(operand) != minor_to_major:
            found.append(m.group(0)[:160])
    return found


def _arena_ops(text, floor=4 << 20):
    """The instructions of a compiled program, kernels aside, whose result is
    a whole K/V arena: bf16, ``[..., NB, Nkv, 32, D]`` with the layer (and
    the stage) in front, ``floor`` elements or more — a scatter into the
    carried stack, a copy of it, a move of it into another memory (a
    ``copy-start``'s result is a tuple that begins with the copy). Returns
    ``[(operation, dims)]``."""
    found = []
    for m in re.finditer(
        r"%[\w.\-]+ = \(?bf16\[([\d,]+)\][^\n]*? "
        r"(copy|copy-start|scatter|dynamic-update-slice|fusion|select)\(",
        text,
    ):
        dims = [int(x) for x in m.group(1).split(",")]
        if len(dims) in (5, 6) and dims[-2] == 32 and np.prod(dims) >= floor:
            found.append((m.group(2), tuple(dims)))
    return found


def _windowed_projections(text):
    """The ``qkv`` dots of a compiled program, and those of them the
    compiler wrote as a convolution over a window wider than 1 (the head
    axis as a spatial dim: the form that wants its weights input-minor)."""
    dots = [
        l for l in text.splitlines()
        if " convolution(" in l and "/qkv/dot_general" in l
    ]
    windowed = [
        l.strip()[:200] for l in dots
        if any(
            int(n) > 1 for w in re.findall(r"window=\{size=([\dx]+)", l)
            for n in w.split("x")
        )
    ]
    return dots, windowed


@pytest.fixture(scope="module")
def compiled_serve_chunk(v5e_host):
    """``text(cell)``: the compiled text of a benchmark configuration's
    ``serve_chunk`` at its real geometry for the described v5e
    (``benchmark/aot_check.py`` builds the abstract inputs; the ring takes
    four chips). Compiled once a cell, for the tests of this file."""
    from benchmark import aot_check
    from llm_sharding_tpu.parallel.mesh import pipeline_mesh

    texts = {}

    def text(cell):
        if cell not in texts:
            path = os.path.join(aot_check.HERE, "configs", cell + ".json")
            with open(path) as f:
                cfg_file = json.load(f)
            stages = int(cfg_file["deployment"]["num_stages"])
            mesh = pipeline_mesh(stages, v5e_host[:stages])
            # conftest's "highest" matmul precision is the CPU oracles'; the
            # program asks jax.default_backend() which attention to lower
            with jax.default_matmul_precision("default"), mock.patch.object(
                jax, "default_backend", lambda: "tpu"
            ):
                name, lowered = next(aot_check.programs(cfg_file, mesh))
            assert name == "serve_chunk"
            texts[cell] = lowered.compile().as_text()
        return texts[cell]

    return text


@pytest.mark.parametrize(
    "cell", sorted(_CELL_SHAPES) + [
        "gigachat31_702b_a36b", "nemotron3_super_120b_a12b",
        "keye_vl2_30b_a3b", "longcat_flash_omni", "ouro_2p6b"])
def test_a_decode_step_reads_its_weights_as_they_are_stored(
        compiled_serve_chunk, cell):
    """The compiled ``serve_chunk`` of each benchmark configuration, at its
    real geometry for the described v5e (``benchmark/aot_check.py`` builds
    the abstract inputs; the ring takes four chips), consumes every weight
    stack in the layout it is stored in: no re-laying ``copy`` of a
    parameter above 16 MiB, inside or outside the layer loop, and the k
    and v projections plain dots like q's. Before the projection's edge
    was held (``models/llama.py::attn_mlp_block``) XLA folded the head
    split into the two small dots and transposed the whole ``wk`` / ``wv``
    stacks at the top of every call: 0.25-0.27 ms of a decode step on the
    chip (``PERF.md``, PR 31). Nor does any operation but a kernel produce
    an arena (PR 46: the two scatters a layer of a step's fresh K/V went,
    and with them what XLA copied around them). Latent attention (PR 34) met the same twice
    (``wq_b``'s head split, held the same way) and once from the STORED side:
    a ``[H, 576]`` weight is not whole lane tiles, the chip keeps it
    input-minor, and the stack of ``wkv_a`` was re-laid every call until the
    leaf was padded to the arena entry's 640 columns; ``longcat_flash`` (PR
    57) runs that attention TWICE a layer over leaves with a ``_0`` / ``_1``
    suffix — the same edges, twice; ``ouro`` (PR 60), the llama block with NO
    bias and no q norm, met it on ``wq`` (nothing stood between the dot and
    the head split: 403 MB re-laid a call and a layer's slice copied before
    its dot, 3.6 ms of a 33.4 ms step on the chip, until q left the projection
    through the same edge). Nothing runs: a compile is not a time."""
    text = compiled_serve_chunk(cell)
    assert _weight_stack_relayouts(text) == []
    dots, windowed = _windowed_projections(text)
    assert len(dots) >= 3 and windowed == []
    # and writes its arena where it lies (PR 46): no operation of the
    # program but a kernel produces an arena — no scatter into the carried
    # stack, no copy or staging of it around one. Since PR 61 that kernel is
    # the attention's own (``paged_decode`` stores the step's fresh K/V from
    # its frontier cell, each arena aliased over itself): the write kernel
    # ``paged_kv_write`` is gone from every program but Keye's, whose index
    # arena it still feeds (the score call reads it before the attention)
    writes = text.count("paged_kv_write/pallas_call")
    assert writes == (1 if cell == "keye_vl2_30b_a3b" else 0)
    decodes = [
        ln for ln in text.split("\n")
        if "tpu_custom_call" in ln and "paged_decode/pallas_call" in ln]
    assert decodes and all(
        "output_to_operand_aliasing" in ln for ln in decodes), decodes
    assert _arena_ops(text) == []


@pytest.mark.parametrize("cell", [
    "olmoe_1b_7b", "gigachat31_702b_a36b", "nemotron3_super_120b_a12b",
    "keye_vl2_30b_a3b", "longcat_flash_omni"])
def test_a_decode_expert_call_is_one_kernel(compiled_serve_chunk, cell):
    """The compiled ``serve_chunk`` of the expert cells (PR 63): under the
    ``moe`` scope the layer body holds ONE ``moe_experts`` call and, beside
    it, nothing but the compares, selects and sums that mask the dead rows'
    ids and count the pairs an expert has — no ``dynamic_index_in_dim`` of a
    scale stack (the parent sliced two a call, 2.2 us each on the chip), no
    sort or gather that lists the tiles, no ``[NT, tm, H]`` float32 tile
    output and no ``einsum`` that weighs it; and no scale stack is re-laid
    for the call (``[L, E·F]`` rides in where it lies: the kernel copies the
    sublane tile of rows the layer's row is in)."""
    text = compiled_serve_chunk(cell)
    calls = [
        ln for ln in text.split("\n")
        if 'custom_call_target="tpu_custom_call"' in ln
        and "moe_experts/pallas_call" in ln]
    # one a layer body (``nemotron_h``'s pattern of layer kinds is written
    # out: seven bodies with experts)
    assert len(calls) == (7 if cell == "nemotron3_super_120b_a12b" else 1)
    # (a share's call sits inside the ``cond`` that skips a call that meets
    # no held expert: ``moe/cond/branch_1_fun/jit(expert_decode_tpu)/..``)
    assert all(re.search(
        r"/moe/(cond/\w+/)?jit\(expert_decode_tpu\)/moe_experts", ln)
        for ln in calls)
    beside = {
        op for op in re.findall(r'op_name="[^"]*/moe/([^"]*)"', text)
        if not op.startswith("zero_expert/") and "/moe_experts/" not in op}
    assert beside, cell
    for op in beside:
        assert not re.search(
            r"dynamic_slice|dynamic_update_slice|dot_general|sort|gather"
            r"|pad|transpose|argsort|cumsum", op), (cell, op)
    # nothing under the scope is a stack of tiles: the kernel's one output
    # is the rows' [N, H]
    for ln in text.split("\n"):
        if "/moe/" in ln and "zero_expert" not in ln:
            assert not re.search(r"= \(?f32\[\d+,\d+,\d+\]", ln), ln[:200]
    # and no scale stack (two dims, above 256 K entries) is copied
    for m in re.finditer(
            r"= (?:bf16|f32)\[(\d+),(\d+)\]\S* (?:copy|transpose)\(", text):
        assert int(m.group(1)) * int(m.group(2)) < 1 << 18, m.group(0)


def test_a_selecting_decode_step_reads_k_and_v_through_a_kernel_only(
        compiled_serve_chunk):
    """``keye_vl2_30b_a3b``'s compiled ``serve_chunk`` (PR 50): the selection
    reaches the attention as key positions, so nothing but a kernel reads the
    K or V arena — no ``gather`` has an arena, or a reshape of one, for its
    operand (the parent gathered the 2,048 chosen tokens' rows out of the
    flattened pools, 16,384 rows a layer call: 34% of its step on the chip) —
    and the decode kernel appears ONCE in the layer body, outside the
    ``cond`` that chooses the key positions (a score kernel and a top-k on
    one side, the positions as they are on the other), not once a branch."""
    text = compiled_serve_chunk("keye_vl2_30b_a3b")
    shape = {
        name: [int(x) for x in dims.split(",") if x]
        for name, dims in re.findall(r"(%[\w.\-]+) = \(?\w+\[([\d,]*)\]", text)
    }
    gathers = re.findall(
        r"= (\w+)\[([\d,]*)\][^\n]*? gather\((%[\w.\-]+), ", text)
    assert gathers  # the embedding's rows, the experts' order
    for dtype, dims, operand in gathers:
        # an arena (or a flat view of one) holds 12 layers x 2305 blocks x 4
        # heads x 32 tokens of 128: 453 M elements; the largest operand of a
        # gather here is the embedding table's 78 M
        assert int(np.prod(shape.get(operand, [0]) or [1])) < 100 << 20, (
            dtype, dims, operand)
    kernels = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]+)"', text)
    decode = [k for k in kernels if k.endswith("paged_decode/pallas_call")]
    assert len(decode) == 1 and "/cond/" not in decode[0], kernels
    scores = [k for k in kernels if k.endswith("index_scores/pallas_call")]
    assert len(scores) == 1 and "/cond/branch_1_fun/" in scores[0], kernels
    # and the score kernel takes the layer-stacked index arena WHOLE, once
    # (PR 56: it copies a block by hand; a block was an operand, the arena
    # eight times over)
    (call,) = [
        ln for ln in text.split("\n")
        if "tpu_custom_call" in ln and "index_scores/pallas_call" in ln]
    operands = call.split("operand_layout_constraints=")[1].split("}}")[0]
    assert re.findall(r"\w+\[(?:\d+,){4}\d+\]", operands) == [
        "bf16[12,2305,1,32,128]"], operands


def _called_from(text, root):
    """The instructions of computation ``root`` and of every computation it
    calls (fusions, reductions, branches, loops)."""
    comps = {
        m.group(1): m.group(2).split("\n") for m in re.finditer(
            r"\n(?:ENTRY )?(%[\w.\-]+) [^\n]*\{\n(.*?)\n\}", text, re.S)
    }
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += [ref.strip() for ref in group.split(",")]
    return [line for name in seen for line in comps[name]]


def _elements(line):
    dims = re.search(r"= \(?\w+\[([\d,]*)\]", line)
    return int(np.prod([int(d) for d in dims.group(1).split(",") if d] or [1]))


def test_a_selecting_decode_step_finds_its_topk_th_score_without_a_sort(
        compiled_serve_chunk):
    """``keye_vl2_30b_a3b``'s compiled ``serve_chunk`` (PR 51): nothing of
    the selecting branch of the layer body sorts or scans — ``select_mask``
    finds the ``topk``-th score by a search, the kernel ``select_topk`` for a
    decode step's slot since PR 58 (the parent's ``lax.top_k`` over
    the slot's ``[4, 9216]`` scores was 70 us a layer call on the chip, the
    largest device operation of a step, and its tie rule's ``cumsum`` 6.5 us
    more OUTSIDE every scope: the compiler's ``reduce-window`` rewrite drops
    the metadata) — and all of the branch lies under ``indexer`` or
    ``select``, the scopes ``decode_index_pct`` and ``index_hbm_pct`` divide
    by: by name where an instruction has one, and no instruction without one
    makes an array as wide as the window."""
    text = compiled_serve_chunk("keye_vl2_30b_a3b")
    lines = text.split("\n")
    # what sorts is the router's top-k and the experts' order
    sorts = [ln for ln in lines if " sort(" in ln or "TopK" in ln]
    assert sorts and all(
        re.search(r'op_name="[^"]*/(router|moe)/', ln) for ln in sorts), sorts
    # nothing scans: the kernels of a decode step walk the slot's rows in
    # their bodies (the score kernel laid its rows end to end until PR 56)
    scans = [ln for ln in lines if " reduce-window(" in ln]
    assert all(_elements(ln) <= 4 for ln in scans), scans
    # the layer's cond: the score kernel lies in its branch 1
    (branch,) = [
        ref.split(",")[1].strip() for ln in lines
        for ref in re.findall(r"branch_computations=\{([^}]*)\}", ln)
        if "cond/branch_1_fun" not in ln
    ]
    chosen = _called_from(text, branch)
    assert any("index_scores/pallas_call" in ln for ln in chosen)
    named = [ln for ln in chosen if "/cond/branch_1_fun/" in ln]
    searched = [ln for ln in named if "/select/" in ln]
    # the search is ONE Pallas call since PR 58 (a slot's four queries): its
    # passes — candidates compared, the hits counted — are turns of a loop in
    # the kernel's body, none of them an XLA reduction of its own any more
    assert sum("select_topk/pallas_call" in ln for ln in searched) == 1
    assert not [ln for ln in searched if " reduce(" in ln]
    for ln in named:
        assert re.search(r'op_name="[^"]*/(select|indexer)/', ln), ln
    for ln in chosen:
        if "op_name=" not in ln and _elements(ln) >= 9216:
            # (the positions leave the branch's fast memory by an async copy
            # since the decode kernel takes them as a lane row a cell)
            assert re.search(
                r" (parameter|get-tuple-element|bitcast|tuple|copy"
                r"|copy-start|copy-done)\(", ln), ln


@pytest.mark.parametrize("cell", ["qwen25_7b", "olmoe_1b_7b"])
def test_a_model_without_an_indexer_traces_nothing_of_the_selection(
        compiled_serve_chunk, cell):
    """No operation of a configuration without ``sparse_attn`` lies under the
    ``select`` or the ``indexer`` scope (by the scope, not by the word
    ``sort``: a router's own ``top_k`` is not the selection's)."""
    names = re.findall(r'op_name="([^"]*)"', compiled_serve_chunk(cell))
    assert len(names) > 100
    assert not [n for n in names if re.search(r"/(select|indexer)/", n)]


def test_a_windowed_models_step_programs_compile_and_read_weights_as_stored(
        v5e_host):
    """``mimo_v25`` (a KV state per kind of attention layer, which
    ``aot_check.py`` cannot describe: ``benchmark/tests/aot_windowed.py``
    makes the state as the server does): the decode program and the chunked
    prefill compile for the described v5e — both paged kernels with a lower
    bound on their walk, a sink operand, keys of 256 lanes and values of 128
    — and the decode step re-lays no weight stack: the fused qkv projection
    leaves its dot through a barrier, and the runs take each layer out of its
    kind's stack inside the scan."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "aot_windowed", os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "tests",
            "aot_windowed.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with jax.default_matmul_precision("default"):
        texts = mod.check("mimo_v25", chunks=(256,), texts=True)
    decode = texts["serve_chunk"]
    assert _weight_stack_relayouts(decode) == []
    dots, windowed = _windowed_projections(decode)
    assert len(dots) >= 3 and windowed == []
    # five runs of one kind: ONE attention kernel each (it stores the step's
    # fresh K/V itself since PR 61: the write kernel before it is gone), an
    # expert kernel in four
    assert decode.count("tpu_custom_call") == 9
    assert "paged_kv_write" not in decode
    assert decode.count("paged_decode/pallas_call") >= 5
    assert "paged_prefill" in texts["serve_prefill_chunk[256]"]
    # a decode step's fresh K/V lands inside the attention kernel: XLA scatters
    # into no arena. What is left is its own choice of memory for a SMALL
    # array the loop carries: the window layers' 31 MB value arena moves
    # into fast memory before the step's loops and back after them, once a
    # step, as it did around the scatters (40 + 3 us of a 2.9 ms step on
    # the chip: PERF.md, PR 46)
    assert _arena_ops(decode) == [
        ("copy-start", (1, 9, 53, 8, 32, 128)),
        ("copy-start", (9, 53, 8, 32, 128)),
    ]


def test_a_recurrent_models_step_programs_compile_and_read_weights_as_stored(
        v5e_host):
    """``nemotron3_super_120b_a12b`` (a recurrent state beside the arena;
    ``benchmark/tests/aot_recurrent.py`` compiles the two programs such a
    model dispatches): the decode program and the chunked
    prefill compile for the described v5e at the published widths — the
    ``relu2`` expert kernel over tiles of 896 columns, both paged kernels for
    the two attention layers, the decode step's state update as ONE kernel a
    mixer layer (``ssm_rows``) and the block-form scan in XLA — and the
    decode step re-lays no weight stack: ``w_in`` leaves its dot
    through a barrier, no ``w_in`` / ``w_out`` / expert stack is copied."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "aot_recurrent", os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "tests",
            "aot_recurrent.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with jax.default_matmul_precision("default"):
        texts = mod.check("nemotron3_super_120b_a12b", texts=True)
    decode = texts["serve_chunk"]
    assert _weight_stack_relayouts(decode) == []
    dots, windowed = _windowed_projections(decode)
    assert len(dots) >= 3 and windowed == []
    # seventeen runs of one kind: an expert kernel in seven, the decode
    # kernel (it stores the step's fresh K/V itself: PR 61) in two, the
    # state kernel in eight
    assert decode.count("tpu_custom_call") == 17
    assert "paged_kv_write" not in decode
    assert "paged_decode" in decode and "moe_experts" in decode
    assert decode.count("ssm_rows/pallas_call") >= 8
    prefill = texts["serve_prefill_chunk[256]"]
    assert "paged_prefill" in prefill and "moe_experts" in prefill
    assert _weight_stack_relayouts(prefill) == []
    # the recurrent state is updated where it lies: neither program copies
    # an array of the state's size (134 MB in and out of every step, 22% of
    # it, before the carried state went through a barrier)
    for text in (decode, prefill):
        assert [line for line in text.split("\n")
                if " copy(" in line and "128,64,128]" in line] == []


def test_the_compiled_program_guard_sees_a_transposed_weight_stack():
    """The guard's own reading, on the lines the parent's compiled 7B
    program held: the transposed int8 stack and the windowed dot are
    found; a prefetch of the router stack (a move, 4 MiB) is not."""
    text = """
  %stage_layers__wk___q.1 = s8[1,28,3584,512]{3,2,1,0:T(8,128)(4,1)} parameter(9), metadata={op_name="stage_layers['wk'].q"}
  %copy.18 = s8[1,28,3584,512]{2,3,1,0:T(8,128)(4,1)S(1)} copy(%stage_layers__wk___q.1), sharding={replicated}, metadata={op_name="stage_layers['wk'].q"}
  %copy-done.9 = bf16[1,16,2048,64]{3,2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.9)
  %copy.40 = bf16[1,16,2048,64]{3,2,1,0:T(8,128)(2,1)S(1)} copy(%copy-done.9), metadata={op_name="stage_layers['router']"}
  %stage_layers__wo___q.1 = s8[1,28,3584,3584]{3,2,1,0:T(8,128)(4,1)} parameter(11), metadata={op_name="stage_layers['wo'].q"}
  %copy.50 = s8[1,28,3584,3584]{3,2,1,0:T(8,128)(4,1)S(1)} copy(%stage_layers__wo___q.1), metadata={op_name="stage_layers['wo'].q"}
  %convolution.45 = bf16[4,4,128]{2,0,1:T(4,128)(2,1)} convolution(%fusion.188, %fusion.189), window={size=4 pad=3_3 rhs_reversal=1}, dim_labels=bf0_0oi->b0f, metadata={op_name="jit(serve_chunk)/state/while/body/closed_call/qkv/dot_general"}
  %convolution.9 = bf16[4,3584]{1,0:T(4,128)(2,1)} convolution(%fusion.1, %fusion.2), dim_labels=bf_io->bf, metadata={op_name="jit(serve_chunk)/state/while/body/closed_call/qkv/dot_general"}
"""
    found = _weight_stack_relayouts(text)
    assert len(found) == 1 and found[0].startswith("%copy.18 ")
    dots, windowed = _windowed_projections(text)
    assert len(dots) == 2 and len(windowed) == 1


@pytest.mark.parametrize("store", ["bf16", "int8"])
@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_decode_kernel_takes_a_blocks_heads_together(cell, store):
    """The decode kernel at the three cells' shapes, read from the traced
    ``pallas_call`` (nothing runs): ONE invocation — no grid over cells,
    heads or rows: the walk is a loop in the body —, three scalar-prefetch
    operands (layer, table, the frontier), both arenas WHOLE and in HBM —
    no operand a block: the body copies them by hand — and a double-buffered
    VMEM scratch a cell wide for each, ``(2, bps, Nkv, BS, D)``: all
    key/value heads of a block in one copy, ``bps`` the shapes'
    (``decode_blocks_per_cell``); an int8 arena's scales a cell's row in
    SCALAR memory, the blocks' ``2·Nkv`` side by side."""
    from llm_sharding_tpu.ops import paged_attention as pa

    Nh, Nkv = _CELL_SHAPES[cell]
    B, T, BS, D, Lp, NB = 4, 128, 32, 128, 3, 260
    S = jax.ShapeDtypeStruct
    dt = jnp.bfloat16 if store == "bf16" else jnp.int8
    arena = S((Lp, NB, Nkv, BS, D), dt)
    scale = S((Lp, NB, Nkv), jnp.float32) if store == "int8" else None
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, l, t, qp, kp, ks, vs: pa.paged_attention_tpu(
            q, k, v, l, t, qp, kp, k_scale=ks, v_scale=vs
        )
    )(
        S((B, 1, Nh, D), jnp.bfloat16), arena, arena, S((), jnp.int32),
        S((B, T), jnp.int32), S((B, 1), jnp.int32),
        S((B, T * BS), jnp.int32), scale, scale,
    )
    (call,) = _pallas_calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    bps = pa.decode_blocks_per_cell(T, BS, Nkv, 2 * D, dt.dtype.itemsize)
    assert bps == {4: 16, 8: 8, 16: 4}[Nkv]
    assert tuple(gm.grid) == (1,)
    assert gm.num_index_operands == 3
    # the pool reaches the kernel twice, whole, where it lies
    pools = [bm.block_aval for bm in gm.block_mappings
             if len(bm.block_aval.shape) == 5]
    assert [(a.shape, str(a.memory_space)) for a in pools] == [
        ((Lp, NB, Nkv, BS, D), "hbm")] * 2
    scratch = [v.aval for v in call.params["jaxpr"].invars][
        -gm.num_scratch_operands:]
    cells = [a.shape for a in scratch if len(a.shape) == 5]
    assert cells == [(2, bps, Nkv, BS, D)] * 2
    smem = [a.shape for a in scratch if str(a.memory_space) == "smem"]
    assert smem == ([(2, 1, bps * 2 * Nkv)] if store == "int8" else [])



#: table widths at Keye's index arena (12 layers x 2305 blocks of 32 tokens x
#: 128 bf16 lanes, 16 index heads, 4 rows) -> the blocks a cell
_SCORE_TABLES = {288: 96, 256: 64, 33: 33}


@pytest.mark.parametrize("table", sorted(_SCORE_TABLES))
def test_the_score_kernel_walks_the_index_arena_by_hand(table):
    """The score kernel at Keye's shape, read from the traced ``pallas_call``
    (nothing runs): ONE invocation — no grid over cells: the walk is a loop
    in the body —, three scalar-prefetch operands (layer, table, the
    frontier: no walk laid end to end), the index arena WHOLE and in HBM,
    once — no operand a block —, a double-buffered VMEM scratch a cell wide
    and the whole call's scores one output block, ``[B, T·BS]`` as the search
    reads them."""
    from llm_sharding_tpu.ops import paged_attention as pa

    B, Hi, lanes, BS, L, NB = 4, 16, 128, 32, 12, 2305
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(pa.index_scores_tpu)(
        S((B, Hi, lanes), jnp.bfloat16), S((B, Hi), jnp.float32),
        S((L, NB, 1, BS, lanes), jnp.bfloat16), S((), jnp.int32),
        S((B, table), jnp.int32), S((B, 1), jnp.int32),
        S((B, table * BS), jnp.int32),
    )
    (call,) = _pallas_calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    bps = pa.index_blocks_per_cell(table, BS, lanes, 2)
    assert bps == _SCORE_TABLES[table]
    assert tuple(gm.grid) == (1,) and gm.num_index_operands == 3
    pools = [bm.block_aval for bm in gm.block_mappings
             if len(bm.block_aval.shape) == 5]
    assert [(a.shape, str(a.memory_space)) for a in pools] == [
        ((L, NB, 1, BS, lanes), "hbm")]
    assert _block_shapes(call)[-1] == (B, table * BS)
    scratch = [v.aval for v in call.params["jaxpr"].invars][
        -gm.num_scratch_operands:]
    assert [a.shape for a in scratch if len(a.shape) == 3] == [
        (2, bps * BS, lanes)]


@pytest.mark.parametrize("table", sorted(_SCORE_TABLES))
def test_the_score_kernel_compiles_for_a_described_v5e(v5e_chip, table):
    """The TPU's own compiler (Mosaic included) accepts the score kernel at
    Keye's shape — a block's ``(BS, lanes)`` tile copied by hand out of the
    5-D stacked arena into a slice of a slot, a cell's scores stored at its
    lane offset of the one ``[B, T·BS]`` output block — at a table of three
    cells of 96 blocks, of four of 64 and an odd one of ONE cell; and
    nothing re-lays the scores after the call. No chip: the compile is real,
    nothing runs."""
    from llm_sharding_tpu.ops import paged_attention as pa

    B, Hi, lanes, BS, L, NB = 4, 16, 128, 32, 12, 2305
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )
    with jax.default_matmul_precision("default"):
        text = jax.jit(pa.index_scores_tpu).lower(
            S((B, Hi, lanes), jnp.bfloat16), S((B, Hi), jnp.float32),
            S((L, NB, 1, BS, lanes), jnp.bfloat16), S((), jnp.int32),
            S((B, table), jnp.int32), S((B, 1), jnp.int32),
            S((B, table * BS), jnp.int32),
        ).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "index_scores" in text
    for m in re.finditer(r"= f32\[([\d,]+)\][^ ]* (copy|reduce)\(", text):
        assert np.prod([int(x) for x in m.group(1).split(",")]) < (
            B * table * BS)
    for m in re.finditer(r"= \w+\[([\d,]+)\][^ ]* (copy|transpose)\(", text):
        assert np.prod([int(x) for x in m.group(1).split(",")]) < (
            L * NB * BS * lanes)


@pytest.mark.parametrize("weights", ["int8", "bf16"])
@pytest.mark.parametrize("rows", [4, 1024])
def test_expert_kernel_compiles_for_a_described_v5e(v5e_chip, rows, weights):
    """Mosaic accepts the expert kernel (``ops/moe.py``) at OLMoE-1B-7B's
    published widths — 64 experts of 2048 x 1024, the layer-stacked weights
    read in place through scalar-prefetched layer and expert indices — in
    both regimes: a decode step's 4 rows (one tile per distinct expert) and a
    prefill chunk's 1,024 positions (grouped tiles of 128 rows). The stack is
    cut to 2 layers; no weight-sized copy may stand beside the custom call."""
    from unittest import mock

    from llm_sharding_tpu.ops import moe
    from llm_sharding_tpu.ops.quant import QTensor

    L, H, E, F, k = 2, 2048, 64, 1024, 8
    S = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e_chip
    )
    if weights == "int8":
        leaf = lambda *shape: QTensor(
            S((L, *shape), jnp.int8), S((L, shape[-1]), jnp.bfloat16))
    else:
        leaf = lambda *shape: S((L, *shape), jnp.bfloat16)

    def fn(x, w, ids, live, layer, wg, wu, wd):
        return moe.expert_mlp(
            x, w, ids, wg, wu, wd, E, live=live, layer=layer, backend="kernel"
        )

    with jax.default_matmul_precision("default"), mock.patch.object(
        jax, "default_backend", lambda: "tpu"
    ):
        compiled = jax.jit(fn).lower(
            S((rows, H), jnp.bfloat16), S((rows, k), jnp.float32),
            S((rows, k), jnp.int32), S((rows,), jnp.bool_), S((), jnp.int32),
            leaf(H, E * F), leaf(H, E * F), leaf(E * F, H),
        ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "moe_experts" in text
    import re

    for m in re.finditer(r"= \w+\[([\d,]+)\][^ ]* (copy|transpose)\(", text):
        assert np.prod([int(x) for x in m.group(1).split(",")]) < H * E * F


# ------------------------------------- named scopes in the step programs

#: Which words of ``obs.stepline.SCOPES`` each step program must NOT carry
#: when lowered (paged arena, chunked prefill, the kernel code path
#: emulated). The arena-native programs (serve_chunk, serve_prefill_chunk)
#: slice no layer out of the pool, write none back and lay nothing out: the
#: kernels index the carried stack. serve_admit prefills a DENSE window
#: (the dense scan's kv_take / kv_put) and cuts it into head-major blocks
#: (kv_layout) before the scatter. serve_prefill_chunk samples nothing.
#: serve_admit_finish only embeds each row's last token.
#: A model's MLP is dense (``mlp``) or sparse experts (``router`` and
#: ``moe``, ``ops/moe.py``), never both: the model of a case says which
#: words its programs lack.
_NO_ARENA_COPY = {"kv_take", "kv_layout", "kv_put"}
_NO_HEAD = {"head", "sample"}
#: ``absorb`` is latent attention's (``models/deepseek_v3.py``): neither model here has it.
# (a Mamba mixer's and a LatentMoE's words are ``nemotron_h``'s and
# ``jamba``'s alone, a KDA mixer's ``solar_open2``'s:
# ``tests/test_nemotron_h_serve.py``, ``tests/test_jamba_serve.py`` and
# ``tests/test_solar_open2_serve.py`` hold their programs to them)
_RECURRENT_WORDS = {
    "ssm_proj", "conv", "ssm", "ssm_x", "moe_latent", "kda_proj", "kda",
}
# (``indexer`` / ``select`` are a token-selecting model's alone:
# ``tests/test_keye_vl2_serve.py`` holds its programs to them)
_SELECT_WORDS = {"indexer", "select"}
# (``zero_expert`` is ``longcat_flash``'s alone — experts without weights and
# the shortcut's join: ``tests/test_longcat_flash_serve.py`` holds its
# programs to it)
_SHORTCUT_WORDS = {"zero_expert"}
# (``pass_close`` is a looped stack's alone — the final norm that closes a
# pass and the exit gate: ``tests/test_ouro_serve.py`` holds its programs to
# it, and these one-pass models' to being without it)
_LOOP_WORDS = {"pass_close"}
_OTHERS_WORDS = (
    _RECURRENT_WORDS | _SELECT_WORDS | _SHORTCUT_WORDS | _LOOP_WORDS
)
_MLP_WORDS = {
    "dense": {"router", "moe", "absorb"} | _OTHERS_WORDS,
    "experts": {"mlp", "absorb"} | _OTHERS_WORDS,
}
PROGRAM_SCOPES = {
    # a decode step's fresh K/V is stored INSIDE ``paged_decode`` (under
    # ``attn``) on the kernel path these programs take: nothing is left under
    # ``kv_write`` there (PR 61)
    "serve_chunk": _NO_ARENA_COPY | {"kv_write"},
    "serve_prefill_chunk": _NO_ARENA_COPY | _NO_HEAD,
    "serve_admit": set(),
    "serve_admit_finish": None,  # exactly: embed, state
}


@pytest.fixture(scope="module")
def lowered_programs(setup):
    return _lower_programs(*setup, cfg=CFG)


@pytest.fixture(scope="module")
def lowered_programs_experts():
    """The same through a model with sparse experts (a ring of two)."""
    from llm_sharding_tpu.models.config import tiny_olmoe

    cfg = tiny_olmoe(max_position_embeddings=CFG.max_position_embeddings)
    params = llama.init_params(cfg, jax.random.key(12), dtype=jnp.float32)
    eng = PipelineEngine(cfg, params, num_stages=2, cache_dtype=jnp.float32,
                         devices=jax.devices()[:2])
    return _lower_programs(params, eng, cfg=cfg)


def _lower_programs(params, eng, cfg):
    """Serve a one-shot and a chunked admission through the interpreted
    kernels, lowering each step program with the very arguments the server
    dispatched it with. Returns ``(texts, served, oracle)``."""
    from llm_sharding_tpu.parallel import serve as serve_ops

    texts = {}

    def spy(mp, name):
        orig = getattr(serve_ops, name)

        def call(*a, **kw):
            if name not in texts:
                texts[name] = orig.lower(*a, **kw).as_text(debug_info=True)
            return orig(*a, **kw)

        mp.setattr(serve_ops, name, call)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PAGED_FORCE_KERNEL", "interpret")
        for name in PROGRAM_SCOPES:
            spy(mp, name)
        srv = eng.serve(
            capacity=64, batch_per_slot=2, kv_block_size=8, kv_blocks=65,
            prefill_chunk=16,
        )
        assert srv.attn_impl == "interpret"
        prompts = [prompt(301, n=5), prompt(302, n=20)]
        reqs = [srv.submit(p, 5) for p in prompts]
        srv.run_until_idle()
        srv.close()
    served = [list(r.tokens) for r in reqs]
    oracle = []
    for p in prompts:
        res = generate(cfg, params, p, 5, cache_dtype=jnp.float32)
        oracle.append(list(res.tokens[0, len(p): int(res.lengths[0])]))
    return texts, served, oracle


def _scopes_in(text):
    """The vocabulary words on any operation's name-stack path."""
    import re

    from llm_sharding_tpu.obs.stepline import SCOPES

    paths = set(re.findall(r'loc\("([^"]+)"', text))
    return {
        w for w in SCOPES
        if any(re.search(rf"(^|/){w}(/|$)", p) for p in paths)
    }, paths


@pytest.mark.parametrize("model", sorted(_MLP_WORDS))
@pytest.mark.parametrize("program", sorted(PROGRAM_SCOPES))
def test_step_programs_carry_the_scope_vocabulary(request, program, model):
    """Every step program names its device work by the closed vocabulary
    (``obs.stepline.SCOPES``) — what a profiler trace's ``tf_op`` then
    carries — and naming changes no token: the served ids equal the
    monolithic oracle's, as before the scopes. A model with sparse experts
    carries ``router`` and ``moe`` where a dense one carries ``mlp``, in all
    three programs that run layers."""
    from llm_sharding_tpu.obs.stepline import SCOPES

    texts, served, oracle = request.getfixturevalue(
        "lowered_programs" if model == "dense" else "lowered_programs_experts"
    )
    assert served == oracle
    found, paths = _scopes_in(texts[program])
    missing_ok = PROGRAM_SCOPES[program]
    want = (
        {"embed", "state"} if missing_ok is None
        else set(SCOPES) - missing_ok - _MLP_WORDS[model]
    )
    assert found == want, (sorted(want - found), sorted(found - want))
    if program in ("serve_chunk", "serve_prefill_chunk"):
        # no arena copy: no layer sliced out of the pool, no operand of a
        # kernel transposed, nothing written back around the layer — under
        # any enclosing scope (MLIR locations are relative to the traced
        # function, XLA joins them into tf_op)
        for gone in ("kv_take/", "kv_layout/", "kv_put/"):
            assert not any(gone in p + "/" for p in paths), gone
        # the read is the kernel's own block DMAs; a chunk's write is the
        # scatter into the carried stack, a decode step's (one entry a row,
        # a plain arena, the attention on its kernel) the attention
        # kernel's own, which leaves XLA no scatter into the arena and the
        # program no write kernel
        scatter = any(p.endswith("kv_write/scatter") for p in paths)
        assert not any("paged_kv_write" in p for p in paths)
        assert scatter == (program != "serve_chunk")
    if program == "serve_chunk":
        assert any(p.endswith("ring_hop/ppermute") for p in paths)


def test_the_pallas_kernels_are_named(lowered_programs):
    """``name=`` on the pallas_calls: a trace names the kernels
    ``paged_decode`` / ``paged_prefill``, not by a numbered fusion."""
    texts, _, _ = lowered_programs
    _, decode = _scopes_in(texts["serve_chunk"])
    _, prefill = _scopes_in(texts["serve_prefill_chunk"])
    assert any(p.startswith("paged_decode/") for p in decode)
    assert any(p.startswith("paged_prefill/") for p in prefill)
    assert not any(p.startswith("paged_prefill/") for p in decode)


def test_the_expert_kernel_is_named(lowered_programs_experts):
    texts, _, _ = lowered_programs_experts
    for program in ("serve_chunk", "serve_prefill_chunk", "serve_admit"):
        _, paths = _scopes_in(texts[program])
        assert any("moe_experts" in p for p in paths), program


# ----------------------- the arena stays where it lies (program structure)


def _leaf_eqns(jaxpr):
    """Every equation of ``jaxpr`` that holds no inner jaxpr (a
    ``pallas_call`` counts as one equation), inner jaxprs walked through."""
    for eqn in jaxpr.eqns:
        subs = (
            [] if eqn.primitive.name == "pallas_call"
            else list(_inner_jaxprs(eqn))
        )
        if subs:
            for sub in subs:
                yield from _leaf_eqns(sub)
        else:
            yield eqn


def _layer_scans(jaxpr, block_shape):
    """The scans that carry a layer-stacked arena ``[L, *block_shape]``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and any(
            tuple(v.aval.shape[1:]) == block_shape and v.aval.ndim == 5
            for v in eqn.invars
        ):
            yield eqn
            continue
        for sub in _inner_jaxprs(eqn):
            yield from _layer_scans(sub, block_shape)


#: what may touch a value of a layer-arena's size: the three operations
#: that address (layer, block) INSIDE the carried stack ...
_IN_PLACE = {"gather", "scatter", "pallas_call"}
#: ... and, outside the layer scan, the relabelings of the whole state leaf
#: at a program's edge (the stage dim stripped and restored: no data moves)
_RELABEL = {"squeeze", "broadcast_in_dim", "reshape"}


def _arena_sized_offenders(eqns, stack_shape, allowed):
    """Equations with an operand or result of a LAYER-arena's size or more
    that are not ``allowed`` — or that are, but touch something other than
    the whole stack (a layer of it sliced out or put back is the copy this
    test exists to keep out)."""
    layer = int(np.prod(stack_shape[1:]))
    bad = []
    for eqn in eqns:
        big = [
            v.aval for v in (*eqn.invars, *eqn.outvars)
            if hasattr(v.aval, "shape") and int(np.prod(v.aval.shape)) >= layer
        ]
        if not big:
            continue
        name = eqn.primitive.name
        whole = all(
            int(np.prod(a.shape)) == int(np.prod(stack_shape)) for a in big
        )
        full_slice = name == "slice" and (
            eqn.invars[0].aval.shape == eqn.outvars[0].aval.shape
        )
        if not ((name in allowed or full_slice) and whole):
            bad.append((name, [tuple(a.shape) for a in big]))
    return bad


@pytest.fixture(scope="module", params=["xla", "interpret"])
def traced_programs(request, setup):
    """The jaxprs of the three arena-native step programs as a paged server
    dispatched them — a chunked admission, decode chunks, and (second
    server) speculative verify — on one attention backend, bf16-style and
    int8 arenas. Returns ``{(program, kv_dtype): jaxpr}``, the local arena
    stack's shape, what was served against the oracle, and per ``(kv_dtype,
    speculate)`` server what its decode / verify dispatches wrote by the
    write's form: the counter's rise and the step records' sum."""
    from llm_sharding_tpu.obs.metrics import (
        DECODE_KV_ENTRIES_WRITTEN, DECODE_KV_WRITES,
    )

    def written():
        return {w: DECODE_KV_ENTRIES_WRITTEN.labels(write=w).value
                for w in DECODE_KV_WRITES}

    from llm_sharding_tpu.parallel import serve as serve_ops

    params, eng = setup
    backend = request.param
    jaxprs, writes = {}, {}
    served, oracle = [], []
    with pytest.MonkeyPatch.context() as mp:
        if backend == "interpret":
            mp.setenv("PAGED_FORCE_KERNEL", "interpret")
        kvd = {"now": None}
        for name in ("serve_chunk", "serve_prefill_chunk", "serve_verify"):
            orig = getattr(serve_ops, name)

            def call(*a, _orig=orig, _name=name, **kw):
                if (_name, kvd["now"]) not in jaxprs:
                    jaxprs[_name, kvd["now"]] = _orig.trace(*a, **kw).jaxpr
                return _orig(*a, **kw)

            mp.setattr(serve_ops, name, call)
        for kv_dtype in ("bf16", "int8"):
            for spec in (0, 2):
                kvd["now"] = kv_dtype
                w0 = written()
                srv = eng.serve(
                    capacity=64, batch_per_slot=2, kv_block_size=8,
                    kv_blocks=65, kv_dtype=kv_dtype,
                    paged_attn="xla" if backend == "xla" else "auto",
                    # a speculative server has no chunked admission
                    **(dict(speculate=spec) if spec
                       else dict(prefill_chunk=16)),
                )
                assert srv.attn_impl == backend
                stack_shape = tuple(srv.state.k.shape[1:])
                prompts = [prompt(311 + spec, n=5), prompt(312 + spec, n=20)]
                reqs = [srv.submit(p, 5) for p in prompts]
                srv.run_until_idle()
                recs = collections.Counter()
                for r in srv.stepline.snapshot():
                    recs.update(r.get("decode_kv_entries", {}))
                srv.close()
                w1 = written()
                writes[kv_dtype, spec] = (
                    {w: w1[w] - w0[w] for w in w0}, dict(recs)
                )
                if kv_dtype == "bf16":  # exact arena: token-exact serving
                    served += [list(r.tokens) for r in reqs]
                    oracle += [oracle_tokens(params, p, 5) for p in prompts]
    return jaxprs, stack_shape, served, oracle, writes


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize(
    "program", ["serve_chunk", "serve_prefill_chunk", "serve_verify"]
)
def test_no_arena_sized_copy_in_a_step_program(
    traced_programs, program, kv_dtype
):
    """THE invariant of the head-major, layer-indexed arena: in the body of
    the paged layer scan no equation has an input or output of a
    layer-arena's size or more, except the gather, the scatter and the
    ``pallas_call`` that take the WHOLE carried stack as an operand and
    address ``(layer, block)`` inside it. Around the scan, in the rest of
    the program, the only other arena-sized equations are the relabelings
    of the state leaf at the program's edge. So a decode or prefill step
    holds no arena-sized transpose, slice or update, on either backend —
    and what it serves still equals the dense-path oracle."""
    jaxprs, stack_shape, served, oracle, _ = traced_programs
    assert served == oracle
    jaxpr = jaxprs[program, kv_dtype]
    scans = list(_layer_scans(jaxpr.jaxpr, stack_shape[1:]))
    assert scans, "no layer scan carries the stacked arena"
    for scan in scans:
        body = scan.params["jaxpr"].jaxpr
        eqns = list(_leaf_eqns(body))
        assert _arena_sized_offenders(eqns, stack_shape, _IN_PLACE) == []
        # the three in-place operations are really there
        names = {e.primitive.name for e in eqns}
        assert "scatter" in names
        assert ("pallas_call" in names) or ("gather" in names)
    assert _arena_sized_offenders(
        _leaf_eqns(jaxpr.jaxpr), stack_shape, _IN_PLACE | _RELABEL
    ) == []


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("program", ["serve_chunk", "serve_verify"])
def test_a_layer_scan_holds_one_decode_kernel_over_whole_blocks(
    traced_programs, program, kv_dtype
):
    """The decode programs as a server dispatched them: every layer scan
    that carries the arena holds exactly ONE attention ``pallas_call``,
    named ``paged_decode``, and the tiles of its two cell buffers are
    ``(Nkv, BS, D)`` wide — a block's key/value heads together in one copy.
    It is the scan's ONLY Pallas call: where a step writes one entry a row
    into a plain arena (``serve_chunk`` over bf16) the kernel stores the
    entry itself, both arenas aliased over outputs of the call and the
    entries among its operands (PR 61: no ``paged_kv_write`` before it); a
    verify's ``K + 1`` entries and an int8 arena keep the scatter, and
    their attention call aliases nothing."""
    jaxprs, stack_shape, _, _, _ = traced_programs
    jaxpr = jaxprs[program, kv_dtype]
    _, _, Nkv, BS, D = stack_shape
    scans = list(_layer_scans(jaxpr.jaxpr, stack_shape[1:]))
    assert scans
    if not list(_pallas_calls(jaxpr.jaxpr)):
        # the XLA backend: the same scans read the pool by a gather
        for scan in scans:
            names = {e.primitive.name
                     for e in _leaf_eqns(scan.params["jaxpr"].jaxpr)}
            assert "gather" in names
        return
    writes = program == "serve_chunk" and kv_dtype == "bf16"
    for scan in scans:
        (call,) = _pallas_calls(scan.params["jaxpr"].jaxpr)
        assert call.params["name"] == "paged_decode"
        scratch = call.params["jaxpr"].invars[
            -call.params["grid_mapping"].num_scratch_operands:]
        cells = [v.aval.shape for v in scratch if len(v.aval.shape) == 5]
        assert len(cells) == 2 and {c[2:] for c in cells} == {(Nkv, BS, D)}
        # the arenas, each handed in ONCE and aliased over its own output;
        # the fresh entries [rows, Nkv, D] ride in beside them
        aliased = [
            (call.invars[i].aval.shape, call.outvars[o].aval.shape)
            for i, o in call.params["input_output_aliases"]]
        assert aliased == ([(stack_shape,) * 2] * 2 if writes else [])
        arenas = [v for v in call.invars if v.aval.shape == stack_shape]
        assert len(arenas) == 2
        entries = [v for v in call.invars if v.aval.shape[1:] == (Nkv, D)]
        assert len(entries) == (2 if writes else 0)


@pytest.mark.parametrize("spec", [0, 2])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_the_decode_write_is_counted_by_its_form(
    traced_programs, request, kv_dtype, spec
):
    """``server_decode_kv_entries_written_total{write=}`` and the step
    record's ``decode_kv_entries`` say how a served step's fresh K/V landed:
    ``attention`` where the step program's statics let the attention kernel
    store it (one entry a row, a plain arena, the attention on its kernel) —
    the same predicate ``paged_attention_write`` asks, so the count is the
    program's — and ``scatter`` for a verify step, an int8 arena and the XLA
    path; ``kernel`` (what ``paged_kv_write`` still stores: a selecting
    model's index keys) stays 0 for a model without an indexer."""
    *_, writes = traced_programs
    backend = request.node.callspec.params["traced_programs"]
    counted, recorded = writes[kv_dtype, spec]
    form = "attention" if (
        backend == "interpret" and kv_dtype == "bf16" and not spec
    ) else "scatter"
    assert counted[form] > 0 and sum(counted.values()) == counted[form], (
        counted)
    assert recorded == {form: counted[form]}
    if spec:  # a verify writes K + 1 entries a live row
        assert counted[form] % (spec + 1) == 0


def test_the_structural_check_sees_a_sliced_out_layer():
    """The check above is not vacuous: the retired pattern — a layer
    sliced out of the stack, used, and written back — is reported."""
    stack = jnp.zeros((3, 9, 2, 8, 16), jnp.float32)

    def retired(stack, l):
        one = jax.lax.dynamic_index_in_dim(stack, l, keepdims=False)
        one = jnp.transpose(one, (0, 2, 1, 3))
        one = jnp.transpose(one + 1.0, (0, 2, 1, 3))
        return jax.lax.dynamic_update_slice(stack, one[None], (l, 0, 0, 0, 0))

    eqns = list(_leaf_eqns(jax.make_jaxpr(retired)(stack, 1).jaxpr))
    found = {n for n, _ in _arena_sized_offenders(
        eqns, stack.shape, _IN_PLACE
    )}
    assert {"dynamic_slice", "transpose", "dynamic_update_slice"} <= found
