"""Preallocated, jit-stable KV cache.

The reference uses HF ``DynamicCache`` — one per node, growing unboundedly with
each decode step (``/root/reference/utils/node_worker.py:184, 253-258``).
Unbounded growth would force an XLA recompile every step; instead the cache is
a fixed-capacity ring of arrays plus a scalar length, updated functionally with
``lax.dynamic_update_slice`` so the whole decode loop stays inside one compiled
program (SURVEY.md §7 "KV cache shape discipline under jit").

Layout: ``k, v: [num_layers, batch, capacity, num_kv_heads, head_dim]``
(a latent cache, ``cfg.latent_kv``: ``k`` one entry of ``cfg.cache_k_dim`` a
token, ``v`` zero wide) plus
``pos: [batch, capacity]`` — the absolute token position of each slot's key,
initialized to a large sentinel. Attention masks on ``pos <= query_position``,
so uninitialized slots and padded prompt tokens (written with the sentinel)
are excluded automatically; this is what makes right-padded batched decode
correct — a capability the reference (batch=1 only) never needed. ``length``
is only the shared write offset. ``clear()`` gives the semantics of the
reference's clear-KV-cache ring protocol (``utils/node_worker.py:319-355``)
without reallocating.

Capacity contract: writes beyond ``capacity`` cannot raise inside jit (XLA
clamps dynamic-slice starts), so callers must guarantee
``prompt_len + max_new_tokens <= capacity`` at the host boundary — the decode
APIs in ``runtime/`` validate this before tracing.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from .config import ModelConfig


# "no key here" — larger than any real position. Deliberately a NUMPY scalar:
# a module-level jnp constant would initialize the XLA backend at import
# time, which breaks multi-controller runs (jax.distributed.initialize must
# run before any backend use — parallel/distributed.py).
POS_SENTINEL = np.int32(2**30)


class KVCache(NamedTuple):
    k: jax.Array  # [L, B, C, Hkv, D]
    v: jax.Array  # [L, B, C, Hkv, D]
    pos: jax.Array  # [B, C] int32 — absolute position of each key, or sentinel
    length: jax.Array  # scalar int32 — shared write offset

    @property
    def capacity(self) -> int:
        return self.k.shape[2]

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]


def init_cache(
    cfg: ModelConfig,
    batch_size: int,
    capacity: int,
    num_layers: int | None = None,
    dtype=jnp.bfloat16,
) -> KVCache:
    """Allocate an empty cache for ``num_layers`` LAYERS (a pipeline stage's
    slice): ``cfg.arena_slots`` cache layer slots each."""
    L = cfg.num_hidden_layers if num_layers is None else num_layers
    L *= cfg.arena_slots
    shape = (L, batch_size, capacity, cfg.cache_heads)
    return KVCache(
        k=jnp.zeros((*shape, cfg.cache_k_dim), dtype),
        v=jnp.zeros((*shape, cfg.cache_v_dim), dtype),
        pos=jnp.full((batch_size, capacity), POS_SENTINEL, jnp.int32),
        length=jnp.zeros((), jnp.int32),
    )


def paged_arena_shape(
    cfg: ModelConfig,
    num_blocks: int,
    block_size: int,
    num_layers: int | None = None,
    heads: int | None = None,
) -> tuple:
    """Per-stage shape of the POOLED paged-KV arena, HEAD-MAJOR: ``[L,
    num_blocks, Nkv, block_size, Dh]`` — the paged replacement for a dense
    cache's ``[L, B, C, Nkv, Dh]``. One block's one head is the
    ``(block_size, Dh)`` tile the Pallas kernels stream, so the pool is
    stored in the layout it is read in (``ops/paged_attention``: kernels
    and gathers index ``(layer, block)`` of this array in place; nothing
    transposes or slices it). Block 0 is reserved as the trash sink
    (``runtime/blocks.TRASH_BLOCK``); rows own block subsets through the
    per-row block tables in ``parallel/serve.ServeState``, so total KV HBM
    scales with tokens actually in flight instead of rows × capacity.
    Bytes persisted in this layout (host and disk tiers, paged snapshots)
    carry its name, ``runtime/blocks.PAGED_KV_LAYOUT``. ``heads`` overrides
    ``cfg.cache_heads``: a windowed model (``cfg.windowed``) has one arena per
    kind of attention, each with that kind's key/value heads."""
    L = cfg.num_hidden_layers if num_layers is None else num_layers
    if num_blocks < 2:
        raise ValueError(
            f"num_blocks must be >= 2 (block 0 is the reserved trash "
            f"sink), got {num_blocks}"
        )
    if block_size < 1 or (block_size & (block_size - 1)):
        raise ValueError(
            f"block_size must be a power of two, got {block_size}"
        )
    return (
        L, num_blocks, cfg.cache_heads if heads is None else heads,
        block_size, cfg.cache_k_dim,
    )


def clear(cache: KVCache) -> KVCache:
    """Reset without reallocating (≙ reference ``clear_KV_cache``,
    ``/root/reference/utils/node_worker.py:319-355``)."""
    return cache._replace(
        pos=jnp.full_like(cache.pos, POS_SENTINEL),
        length=jnp.zeros((), jnp.int32),
    )
