"""Shared helpers for the op-level paged-KV tests: build the layer-stacked
head-major arena the ops take (``[L, NB, Nkv, BS, D]`` — every layer with
its own contents, so an op that read the wrong layer cannot pass) and read
a row's logical window back out of it the plain numpy way (the oracle the
XLA gather is held to)."""

import contextlib
from unittest import mock

import numpy as np
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.config import tiny_llama

#: the model of the serve-level paged tests and of their step programs
CFG = tiny_llama(num_hidden_layers=8)

#: layers of a test stack, and the layers the parametrised cases attend:
#: the first, a middle one, the last
LAYERS = 4
LAYER_CASES = (0, 1, LAYERS - 1)


def make_stack(rng, NB, Nkv, bs, D, dtype=jnp.float32, L=LAYERS):
    """Two random stacks (K and V), every layer different."""
    shape = (L, NB, Nkv, bs, D)
    return (
        jnp.asarray(rng.normal(size=shape), dtype),
        jnp.asarray(rng.normal(size=shape), dtype),
    )


def int8_stack(rng, k_arena, v_arena, dtype=jnp.int8):
    """Two float stacks as an int8 (or, ``dtype``, fp8) arena: codes
    spanning the code range and random per-(layer, block, head) scales.
    Returns ``(k_codes, v_codes, scales)``, ``scales`` the ops'
    ``k_scale``/``v_scale`` keywords."""
    from llm_sharding_tpu.ops.quant import kv_qmax

    qmax = kv_qmax(dtype)
    coded = (
        np.clip(np.asarray(a) * (qmax / 3.0), -qmax, qmax)
        for a in (k_arena, v_arena)
    )
    k_codes, v_codes = (
        jnp.asarray(np.round(c) if dtype == jnp.int8 else c, dtype)
        for c in coded
    )
    sc = rng.uniform(0.5, 1.5, (2, *k_arena.shape[:3])) * (3.0 / qmax)
    return k_codes, v_codes, {"k_scale": jnp.asarray(sc[0], jnp.float32),
                              "v_scale": jnp.asarray(sc[1], jnp.float32)}


def window(arena, layer, tbl):
    """Numpy oracle: the token-major logical window ``[B, T*BS, Nkv, D]``
    of each row of ``tbl`` at ``layer`` of a head-major stack."""
    a = np.asarray(arena)[layer][np.asarray(tbl)]  # [B, T, Nkv, BS, D]
    B, T, Nkv, bs, D = a.shape
    return a.transpose(0, 1, 3, 2, 4).reshape(B, T * bs, Nkv, D)


def others_untouched(before, after, layer):
    """Every layer but ``layer`` holds the bytes it held."""
    before, after = np.asarray(before), np.asarray(after)
    keep = [l for l in range(before.shape[0]) if l != layer]
    np.testing.assert_array_equal(after[keep], before[keep])


@contextlib.contextmanager
def row_wise_chunk_write():
    """Within: every chunk program built writes its K/V row-wise
    (``write_block_kv``), as the program before the tile write did, and the
    host counts it so — what the tile write is held to. The chunk programs
    built either way are dropped from jit's cache on the way in and out, so
    no test meets the other's program."""
    from llm_sharding_tpu.ops import paged_attention as pa
    from llm_sharding_tpu.parallel import serve as serve_ops

    serve_ops.serve_prefill_chunk.clear_cache()
    try:
        with mock.patch.object(pa, "chunk_writes_tiles", lambda *a: False):
            yield
    finally:
        serve_ops.serve_prefill_chunk.clear_cache()


def kv_blocks_written() -> dict:
    """``server_prefill_kv_blocks_written_total`` by the write's form."""
    from llm_sharding_tpu.obs.metrics import (
        PREFILL_KV_BLOCKS_WRITTEN, PREFILL_KV_WRITES,
    )

    return {
        w: PREFILL_KV_BLOCKS_WRITTEN.labels(write=w).value
        for w in PREFILL_KV_WRITES
    }


def counted(run):
    """``(run(), the blocks its chunks wrote by the write's form)``."""
    c0 = kv_blocks_written()
    out = run()
    c1 = kv_blocks_written()
    return out, {w: c1[w] - c0[w] for w in c0}


def tiles_then_rows(run):
    """``run()`` → served token lists, once as the program stands and once
    with the row-wise chunk write: the chunks of the first wrote tiles only,
    those of the second as many blocks row-wise, and both served the same
    tokens. Returns them."""
    tiles, n_tiles = counted(run)
    with row_wise_chunk_write():
        rows, n_rows = counted(run)
    assert n_tiles["tile"] > 0 and n_tiles["rows"] == 0, n_tiles
    assert n_rows == {"tile": 0, "rows": n_tiles["tile"]}, n_rows
    assert tiles == rows
    return tiles


def tiny_engine():
    """``(params, engine)``: ``CFG`` over a ring of four, float32."""
    from llm_sharding_tpu.runtime.engine import PipelineEngine

    params = llama.init_params(CFG, jax.random.key(11), dtype=jnp.float32)
    eng = PipelineEngine(CFG, params, num_stages=4, cache_dtype=jnp.float32)
    return params, eng


def prompt(seed, n=5):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n
    ).astype(np.int32)


def oracle_tokens(params, p, n, **kw):
    from llm_sharding_tpu.runtime.generate import generate

    res = generate(CFG, params, p, n, cache_dtype=jnp.float32, **kw)
    return list(res.tokens[0, len(p): int(res.lengths[0])])


def _inner_jaxprs(eqn):
    """The jaxprs an equation holds in its parameters (scan, while, cond,
    pjit, shard_map alike)."""
    from jax.extend import core as jex

    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, jex.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex.Jaxpr):
                yield x


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation under ``jaxpr``, inner jaxprs walked."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for sub in _inner_jaxprs(eqn):
                yield from _pallas_calls(sub)


def _block_shapes(eqn):
    """A ``pallas_call``'s operand and result blocks as the kernel sees
    them, from its grid mapping: one tuple per block, a squeezed dim None."""
    return [
        tuple(getattr(d, "block_size", None) for d in bm.block_shape)
        for bm in eqn.params["grid_mapping"].block_mappings
    ]
