"""SPMD layer-pipeline over a TPU mesh — the reference's model chain, TPU-native.

This module is the compute-path replacement for the reference's entire
runtime triangle — ``Communicator`` (ZMQ PUSH/PULL hops,
``/root/reference/utils/node_worker.py:13-67``), ``NodeWorker.
pass_through_shard`` (``:227-272``) and ``receive_next_token`` (``:275-309``),
and the ring-closure protocol of ``run_worker_loop`` (``:493-559``) — as ONE
jit-compiled program under ``shard_map``:

- Every device holds one stage's layer slice (padded + masked for ragged
  splits) and that stage's KV cache. Chain position = mesh coordinate on the
  "pipe" axis.
- The stage→stage hidden-state hop is ``lax.ppermute`` over ICI — replacing
  the reference's torch.save→disk→TCP→disk→torch.load wire format
  (``node_worker.py:44-67``), i.e. microseconds instead of a double disk
  round-trip per hop.
- The vocab head is SHARDED over the pipe axis (see ``parallel/head.py``):
  embedding lookups psum partial rows, the greedy winner is assembled from
  per-shard logit maxima — the reference's role split (embedding on
  user-facing nodes, lm_head on the last node, ``node_worker.py:105-125,
  155-164``) becomes vocab parallelism, and no stage holds or computes the
  full vocab.
- The next-token ring closure (last stage → argmax → token id back to node 0,
  ``node_worker.py:515-525``) happens in-program: the final hidden block
  lands on stage 0 by the same ring permute; its last-position hidden is
  psum-broadcast and all stages agree on the next token — so stop
  bookkeeping (EOS/max-token, ``node_worker.py:290-292``) is replicated and
  needs no extra collective (the in-program analogue of the reference's
  ring-propagated clear-KV command, ``:507-513``).
- RoPE is recomputed per-stage from the position scalar instead of shipping
  (cos, sin) down the chain with every activation
  (``node_worker.py:238-243`` — see ops/rope.py).

Chain semantics match the reference exactly: one request in flight, stages
idle while the token is elsewhere (SURVEY.md §2 "exactly one parallelism
strategy"). The throughput play on top of this — interleaved microbatched
decode filling all stages every microstep — lives in ``schedule.py``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.cache import KVCache, POS_SENTINEL
from ..models.config import ModelConfig
from ..models.family import family, refuse_axes
from ..models.stack import close_tables
from ..ops.quant import base
from ..ops.sampling import is_stop as _is_stop, validate_top_p
from .head import (
    head_specs,
    is_sharded_head,
    local_view,
    psum_from,
    shard_head_host,
    sp_embed,
    sp_sample,
)
from .mesh import PIPE_AXIS
from jax import shard_map


class ModelFns(NamedTuple):
    """A family's stage functions (``models/family.py``) as the ring calls
    them, the parallel axes bound."""

    stage: Any  # (cfg, layers, h, cache, positions, mask) -> (h, cache)
    # paged serve-decode stage over the pooled arena (no materialized
    # window): (cfg, layers, h, k_arena, v_arena, tbl, cols, kv_pos,
    # positions, mask, write_valid, backend, k_scale, v_scale) ->
    # (h, k_arena, v_arena, k_scale, v_scale) — the scale arenas ride a
    # quantized (int8/fp8) arena and come back None otherwise
    stage_paged: Any = None
    # a model whose layers do not all attend alike (a KV state per kind of
    # attention layer; attention in a few layers of many): the chunked-
    # prefill kernel's work lists (one per kind) and their counts —
    # (cfg, tables, positions, kv_pos, nlive, layers) -> (walks, counts)
    prefill_walks: Any = None


def model_fns(
    cfg: ModelConfig,
    tp_axis: Optional[str] = None,
    cp_axis: Optional[str] = None,
) -> ModelFns:
    """``cp_axis`` threads the serve-side context-parallel combine into
    the paged stage fn: each shard's ``stage_paged`` sees a per-shard
    arena/table slice and attention partials reduce across ``cp_axis``
    (``models/llama.paged_decoder_layer``). Gated to llama upstream
    (``engine.serve`` validation) — gpt2's paged path never sees it."""
    fam = family(cfg)
    refuse_axes(cfg, tp_axis, cp_axis)
    if cp_axis is not None and not fam.paged_cp:
        raise NotImplementedError(
            "context-parallel serving supports the llama family only"
        )
    fwd, fwd_paged = fam.forward_layers, fam.forward_layers_paged

    # ``moe_live`` ([B, S] bool; a model with experts only): the positions
    # that route. Both stage fns return the layers' stats as their LAST
    # result (``MoeStats``; None for a dense model — models/stack.py).
    # ``close`` (a looped stack only, ``stack.close_tables``): what closes a
    # pass; the stage then hands on the closed state its exit gate chose and,
    # as its stats, the pass that came from (``[B, S]`` int32).
    def stage(cfg_, layers, h, cache, positions, mask, moe_live=None,
              close=None):
        kw = {} if moe_live is None else {"moe_live": moe_live}
        if close is not None:
            kw["close"] = close
        return fwd(
            cfg_, layers, h, cache, positions, mask, tp_axis=tp_axis, **kw
        )

    def stage_paged(cfg_, layers, h, k_arena, v_arena, tbl, cols, kv_pos,
                    positions, mask, write_valid=True, backend="auto",
                    k_scale=None, v_scale=None, prefill=False, walk=None,
                    moe_live=None, close=None):
        kw = {} if cp_axis is None else {"cp_axis": cp_axis}
        if moe_live is not None:
            kw["moe_live"] = moe_live
        if close is not None:
            kw["close"] = close
        return fwd_paged(
            cfg_, layers, h, k_arena, v_arena, tbl, cols, kv_pos,
            positions, mask, write_valid=write_valid, tp_axis=tp_axis,
            backend=backend, k_scale=k_scale, v_scale=v_scale,
            prefill=prefill, walk=walk, **kw,
        )

    return ModelFns(
        stage=stage, stage_paged=stage_paged, prefill_walks=fam.prefill_walks
    )


def mesh_axis_sizes(mesh: Mesh) -> tuple[int, int, int]:
    """(data, pipe, tensor) axis sizes of a (possibly hybrid) mesh — absent
    axes count as 1, so the 1-D pipe mesh is the degenerate case."""
    from .tensor import TENSOR_AXIS
    from .mesh import DATA_AXIS

    shape = dict(mesh.shape)
    return (
        shape.get(DATA_AXIS, 1),
        shape.get(PIPE_AXIS, 1),
        shape.get(TENSOR_AXIS, 1),
    )


def stage_layer_specs(cfg: ModelConfig, tp: int, stage_layers: Any = None):
    """shard_map in_specs for the [num_stages, Lp, ...] stage arrays: pipe on
    the leading axis; with tensor parallelism, megatron column/row sharding on
    the weight dims (specs from ``tensor.*_tp_specs`` shifted under the two
    leading stack axes). gpt2's fused qkv is column-permuted by
    ``pipeline_generate`` itself so each shard's slice is a head-aligned
    (q, k, v) triple. int8 ``QTensor`` leaves (detected from
    ``stage_layers``) get per-component specs — q sharded like the raw
    weight, scale on the output axis (``tensor.quant_leaf_spec``)."""
    if tp == 1:
        return P(PIPE_AXIS)  # pytree-prefix spec: applies to every leaf
    tp_specs = family(cfg).tp_specs
    if tp_specs is None:
        raise NotImplementedError(f"pp×tp: {cfg.model_type!r} unsupported")
    per_leaf = tp_specs(stacked=False)["layers"]
    from .tensor import quant_leaf_spec

    # restrict to the keys actually present (optional bias keys exist only
    # for checkpoints that carry them); with stage_layers=None (the engine's
    # per-key lookup path) return the full table
    keys = per_leaf if stage_layers is None else stage_layers
    return {
        k: quant_leaf_spec(
            P(PIPE_AXIS, None, *per_leaf[k]),
            None if stage_layers is None else stage_layers.get(k),
        )
        for k in keys
    }


def _tree_where(pred, new, old):
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), new, old)


def refuse_looped_ring(cfg: ModelConfig, num_stages: int) -> None:
    """A looped stack over a ring of stages is refused by name: a token
    would lap the ring once a pass (the chains' microstep count, the
    interleaved schedule's slots), which no schedule here does."""
    if cfg.passes > 1 and num_stages > 1:
        raise NotImplementedError(
            f"a looped stack ({cfg.passes} passes over the same layers) over "
            f"a ring of {num_stages} stages is not implemented: a token would "
            "lap the ring once a pass — serve it with num_stages=1"
        )


def moe_stats_zero(cfg: ModelConfig, num_layers: int, h=None):
    """An all-zero ``MoeStats`` stacked over ``num_layers`` layers: what the
    ring chains start their sums from. None for a dense model (an empty
    pytree: no leaf joins its loop carry); a looped stack's stats are the
    exit pass a position of ``h``."""
    from ..ops.moe import MoeStats

    if cfg.passes > 1:
        return jnp.zeros(h.shape[:2], jnp.int32)
    if not cfg.num_experts:
        return None
    return MoeStats(
        jnp.zeros((num_layers, cfg.router_experts), jnp.int32),
        jnp.zeros((num_layers,), jnp.int32),
    )


def ring_chain(fns, cfg, layers, lmask, sidx, ring, num_stages, h, cache,
               positions, moe_live=None, close=None):
    """One full trip around the ring: each stage applies its layer slice on
    its active microstep, then the block hops to the next device
    (≙ one traversal of the reference's device chain,
    ``node_worker.py:541-543``). Shared by the sequential pipeline and the
    interleaved scheduler's prefill. Returns ``(h, cache, stats)``: this
    stage's ``MoeStats`` of its active microstep, stacked over its layers
    (None for a dense model); ``moe_live`` names the positions that route.
    ``close``: a looped stack's (``model_fns``; one stage only)."""
    refuse_looped_ring(cfg, num_stages)

    def micro(m, carry):
        h, cache, stats = carry
        h_new, cache_new, stats_new = fns.stage(
            cfg, layers, h, cache, positions, lmask, moe_live=moe_live,
            close=close,
        )
        active = m == sidx
        h = jnp.where(active, h_new, h)
        cache = _tree_where(active, cache_new, cache)
        with jax.named_scope("ring_hop"):
            h = jax.lax.ppermute(h, PIPE_AXIS, ring)
        # only the active microstep's pass over the layers counts
        stats = jax.tree.map(
            lambda t, n: t + jnp.where(active, n, 0), stats, stats_new
        )
        return h, cache, stats

    return jax.lax.fori_loop(
        0, num_stages, micro,
        (h, cache, moe_stats_zero(cfg, lmask.shape[0], h)),
    )


def ring_chain_paged(fns, cfg, layers, lmask, sidx, ring, num_stages, h,
                     k_arena, v_arena, tbl, cols, kv_positions, positions,
                     backend="auto", k_scale=None, v_scale=None,
                     prefill=False, walk=None, moe_live=None, close=None):
    """``ring_chain`` over the pooled paged arena (the serve programs'
    kernel decode path): the per-microstep activity gate moves from a
    whole-cache ``_tree_where`` (which would copy the ARENA — the whole
    pool, not one slot's window — every microstep) down to
    ``write_block_kv``'s per-entry ``valid``, so an inactive microstep's
    entries land in the layer's trash block. The hidden-state
    gate is unchanged. Quantized arenas carry their scale arenas through
    the loop (None carries are empty pytree nodes — the bf16 path is
    unchanged); returns ``(h, k_arena, v_arena, k_scale, v_scale, stats)``.
    ``prefill`` (static) runs the traversal as a CHUNKED-PREFILL one:
    chunk-shaped queries attend through the query-tiled
    ``paged_prefill`` kernel, ``walk`` its work list where the caller
    built it for all layers (``prefill_walk``) — the ``stage_paged``-style
    prefill traversal behind ``serve_prefill_chunk``. The sixth result is
    this stage's ``MoeStats`` as in ``ring_chain`` (an inactive microstep
    routes nowhere and counts nothing). ``close`` as in ``ring_chain``."""
    refuse_looped_ring(cfg, num_stages)

    def micro(m, carry):
        h, ka, va, ks, vs, stats = carry
        active = m == sidx
        h_new, ka, va, ks, vs, stats_new = fns.stage_paged(
            cfg, layers, h, ka, va, tbl, cols, kv_positions, positions,
            lmask, write_valid=active, backend=backend,
            k_scale=ks, v_scale=vs, prefill=prefill, walk=walk,
            moe_live=moe_live, close=close,
        )
        h = jnp.where(active, h_new, h)
        with jax.named_scope("ring_hop"):
            h = jax.lax.ppermute(h, PIPE_AXIS, ring)
        return h, ka, va, ks, vs, jax.tree.map(jnp.add, stats, stats_new)

    return jax.lax.fori_loop(
        0, num_stages, micro,
        (h, k_arena, v_arena, k_scale, v_scale,
         moe_stats_zero(cfg, lmask.shape[0], h)),
    )


def validate_request(
    cfg: ModelConfig, prompt_tokens: int, max_new_tokens: int, capacity: Optional[int]
) -> int:
    """Host-boundary request validation shared by both pipeline schedulers
    (see models/cache.py capacity contract). Returns the resolved capacity."""
    total = prompt_tokens + max_new_tokens
    capacity = capacity or total
    if total > capacity:
        raise ValueError(
            f"prompt ({prompt_tokens}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds cache capacity ({capacity})"
        )
    if total > cfg.max_position_embeddings:
        raise ValueError(
            f"requested {total} positions > max_position_embeddings "
            f"({cfg.max_position_embeddings})"
        )
    return capacity


def check_stage_shapes(layer_masks, num_stages: int) -> None:
    if layer_masks.shape[0] != num_stages:
        raise ValueError(
            f"stage params built for {layer_masks.shape[0]} stages but mesh "
            f"has {num_stages} on '{PIPE_AXIS}'"
        )


def ensure_sharded_head(cfg: ModelConfig, head_params, num_stages: int):
    """Host-boundary convenience: accept either a full (unsharded) head dict
    or one already stacked by ``shard_head_host``. Hot paths (the engine)
    pre-shard once per placement; tests/dryruns may pass the full head."""
    if is_sharded_head(head_params):
        got = base(head_params["embed"]).shape[0]
        if got != num_stages:
            # a head pre-stacked for S stages silently mis-slices vocab on a
            # mesh whose pipe size divides S — garbage tokens, no error
            raise ValueError(
                f"head was vocab-sharded for {got} stages but the mesh has "
                f"{num_stages}; re-shard with shard_head_host"
            )
        return head_params
    return shard_head_host(cfg, head_params, num_stages)


class PipelineResult(NamedTuple):
    tokens: np.ndarray  # [B, S + max_new_tokens]
    lengths: np.ndarray  # [B]


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "mesh", "num_stages", "max_new_tokens", "capacity",
        "cache_dtype", "temperature", "top_k", "top_p",
    ),
)
def _pipeline_generate_jit(
    cfg: ModelConfig,
    mesh: Mesh,
    stage_layers: Any,  # leaves [num_stages, Lp, ...]
    layer_masks: jnp.ndarray,  # [num_stages, Lp]
    head_params: Any,  # vocab-sharded head (see parallel/head.py)
    prompt: jnp.ndarray,  # [B, S]
    prompt_len: jnp.ndarray,  # [B]
    rng: jnp.ndarray,  # [2] raw uint32 key data (replicated)
    prompt_embeds: Optional[jnp.ndarray],  # [B, S, H] or None (token entry)
    num_stages: int,
    max_new_tokens: int,
    capacity: int,
    cache_dtype,
    temperature: float,
    top_k: int,
    top_p: float,
):
    from .mesh import DATA_AXIS

    from .tensor import TENSOR_AXIS

    dp, _, tp = mesh_axis_sizes(mesh)
    fns = model_fns(cfg, tp_axis=TENSOR_AXIS if tp > 1 else None)
    B, S = prompt.shape
    Bl = B // dp  # rows per data replica
    total = S + max_new_tokens
    Lp = layer_masks.shape[1] * cfg.arena_slots  # the cache's layer slots
    Nkv_local = cfg.cache_heads // tp
    ring = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def body(stage_layers, layer_mask, head_params, prompt, prompt_len, rng,
             prompt_embeds):
        # Local views: shard_map gives leading stage dim of 1 — drop it.
        layers = jax.tree.map(lambda a: a[0], stage_layers)
        mask = layer_mask[0]
        hd = local_view(head_params)
        sidx = jax.lax.axis_index(PIPE_AXIS)
        # Key chain mirrors the monolith's (`runtime/generate.py`): one split
        # for the prefill token, one per decode step — so a seeded sample is
        # token-exact vs the monolithic path. With data parallelism the batch
        # rows differ per replica, so fold the replica index in (deterministic,
        # but not monolith-identical — the monolith has no replicas).
        key = jax.random.wrap_key_data(rng)
        if dp > 1:
            key = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))

        cache = KVCache(
            k=jnp.zeros(
                (Lp, Bl, capacity, Nkv_local, cfg.cache_k_dim),
                cache_dtype,
            ),
            v=jnp.zeros(
                (Lp, Bl, capacity, Nkv_local, cfg.cache_v_dim),
                cache_dtype,
            ),
            pos=jnp.full((Bl, capacity), POS_SENTINEL, jnp.int32),
            length=jnp.zeros((), jnp.int32),
        )

        def chain(h, cache, positions):
            return ring_chain(
                fns, cfg, layers, mask, sidx, ring, num_stages, h, cache,
                positions, close=close_tables(cfg, hd),
            )[:2]

        # ---- prefill (≙ receive_user_request → chain traversal,
        # node_worker.py:188-272) ----
        idx = jnp.arange(S, dtype=jnp.int32)
        positions = jnp.where(
            idx[None, :] < prompt_len[:, None], idx[None, :], POS_SENTINEL
        )
        if prompt_embeds is None:
            h = sp_embed(cfg, hd, prompt, positions)
        else:
            # Privacy entry (≙ the reference's request-injection channel,
            # node_worker.py:476-491): the caller embedded host-side
            # (engine.embed_prompt); raw token ids never enter the program.
            # Pad positions carry caller zeros instead of pad-token
            # embeddings — both are sentinel-masked out of attention, so
            # decoding is token-exact vs the ids path.
            h = prompt_embeds
        h, cache = chain(h, cache, positions)
        # The fully-processed block has landed back on stage 0; pull its
        # last real position and broadcast so every stage can project its
        # vocab slice.
        h_last = jnp.take_along_axis(h, (prompt_len - 1)[:, None, None], axis=1)[
            :, 0
        ]
        h_last = psum_from(h_last, 0)
        key, sub = jax.random.split(key)
        tok = sp_sample(
            cfg, hd, h_last, sub, temperature, top_k, num_stages, top_p
        )  # [B], replicated

        out = jnp.zeros((Bl, total), jnp.int32)
        out = jax.lax.dynamic_update_slice(out, prompt, (0, 0))
        out = out.at[jnp.arange(Bl), prompt_len].set(tok)
        done = _is_stop(cfg, tok)
        lengths = prompt_len + 1

        # ---- decode (≙ receive_next_token → re-embed → chain traversal,
        # node_worker.py:275-309). All bookkeeping is replicated — every
        # stage derived the same token — so the loop predicate is uniform
        # without a stop-broadcast collective. ----
        state = dict(
            out=out, tok=tok, pos=prompt_len, done=done, cache=cache,
            lengths=lengths, n=jnp.ones((), jnp.int32), key=key,
        )

        def cond(s):
            return (s["n"] < max_new_tokens) & ~jnp.all(s["done"])

        def step(s):
            tok_pos = s["pos"][:, None]
            h = sp_embed(cfg, hd, s["tok"][:, None], tok_pos)
            h, cache = chain(h, s["cache"], tok_pos)
            h_last = psum_from(h[:, 0], 0)
            key, sub = jax.random.split(s["key"])
            nxt = sp_sample(
                cfg, hd, h_last, sub, temperature, top_k, num_stages, top_p
            )
            nxt = jnp.where(s["done"], 0, nxt)
            new_pos = s["pos"] + 1
            out = s["out"].at[jnp.arange(Bl), new_pos].set(nxt)
            out = jnp.where(s["done"][:, None], s["out"], out)
            done = s["done"] | _is_stop(cfg, nxt)
            return dict(
                out=out,
                tok=nxt,
                pos=new_pos,
                done=done,
                cache=cache,
                lengths=jnp.where(s["done"], s["lengths"], s["lengths"] + 1),
                n=s["n"] + 1,
                key=key,
            )

        state = jax.lax.while_loop(cond, step, state)
        return state["out"], state["lengths"]

    batch_spec = P(DATA_AXIS) if dp > 1 else P()
    out, lengths = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            stage_layer_specs(cfg, tp, stage_layers),
            P(PIPE_AXIS),
            head_specs(head_params),
            batch_spec,
            batch_spec,
            P(),
            batch_spec,  # no-op when prompt_embeds is None (leafless pytree)
        ),
        out_specs=(batch_spec, batch_spec),
        check_vma=False,
    )(stage_layers, layer_masks, head_params, prompt, prompt_len, rng,
      prompt_embeds)
    return out, lengths


def pipeline_generate(
    cfg: ModelConfig,
    mesh: Mesh,
    stage_layers: Any,
    layer_masks: jnp.ndarray,
    head_params: Any,
    prompt_ids,
    max_new_tokens: int = 128,
    *,
    prompt_len=None,
    capacity: Optional[int] = None,
    cache_dtype=jnp.bfloat16,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    seed: int = 0,
    prompt_embeds=None,  # [B, S, H]: privacy entry — ids never enter
) -> PipelineResult:
    """Pipelined generation across the mesh (host-facing entry). Greedy by
    default; ``temperature``/``top_k``/``top_p``/``seed`` sample token-exactly
    vs the monolithic ``runtime.generate`` (r2 weak #8 — one sampling surface
    for every path).

    ``prompt_embeds`` is the embeddings-in privacy entry (≙ the reference's
    request-injection channel: any embedding-capable node embeds locally and
    injects post-embedding hidden states, so raw text/ids never leave it —
    ``/root/reference/utils/node_worker.py:476-491``, ``README.md:17``).
    Pass ``engine.embed_prompt(ids)`` (or any [B, S, H] hidden states) and a
    ``prompt_len``; ``prompt_ids`` then only sizes the output buffer — pass
    zeros. Token-exact vs the ids path (tests/test_pipeline.py)."""
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    if prompt_ids.ndim == 1:
        prompt_ids = prompt_ids[None]
    if prompt_embeds is not None:
        prompt_embeds = jnp.asarray(prompt_embeds)
        if prompt_embeds.ndim == 2:
            prompt_embeds = prompt_embeds[None]
        if (
            prompt_embeds.shape[:2] != tuple(prompt_ids.shape)
            or prompt_embeds.shape[-1] != cfg.hidden_size
        ):
            raise ValueError(
                f"prompt_embeds {prompt_embeds.shape} does not match "
                f"[{prompt_ids.shape[0]}, {prompt_ids.shape[1]}, "
                f"{cfg.hidden_size}]"
            )
        # cast to the stage activation dtype: fp32 embeds on a bf16 model
        # would run prefill at a different precision than the ids path and
        # could flip greedy ties, breaking the token-exactness contract
        from ..ops.quant import QTensor

        leaf = jax.tree.leaves(
            stage_layers, is_leaf=lambda x: isinstance(x, QTensor)
        )[0]
        act_dtype = leaf.scale.dtype if isinstance(leaf, QTensor) else leaf.dtype
        prompt_embeds = prompt_embeds.astype(act_dtype)
    B, S = prompt_ids.shape
    if prompt_len is None:
        prompt_len = jnp.full((B,), S, jnp.int32)
    else:
        prompt_len = jnp.asarray(prompt_len, jnp.int32)

    capacity = validate_request(cfg, S, max_new_tokens, capacity)
    num_stages = mesh.shape[PIPE_AXIS]
    check_stage_shapes(layer_masks, num_stages)
    head_params = ensure_sharded_head(cfg, head_params, num_stages)

    dp, _, tp = mesh_axis_sizes(mesh)
    if tp > 1:
        from .tensor import validate_tp

        validate_tp(cfg, tp)
        tp_permute = family(cfg).tp_permute
        if tp_permute is not None:
            # gpt2's fused-qkv column permutation happens HERE, not as a
            # caller precondition — callers pass raw layers and can neither
            # forget nor double-apply it; memoized so repeated requests over
            # the same stage arrays don't re-gather the weights
            stage_layers = tp_permute(stage_layers, tp)
    if B % dp != 0:
        raise ValueError(f"batch {B} not divisible by data-parallel size {dp}")

    rng = jax.random.key_data(jax.random.key(seed))
    if jax.process_count() > 1:
        # Multi-controller: every host passes the same GLOBAL batch; each
        # process materializes only its addressable slice (for dp meshes that
        # is its process_local_batch rows — see parallel/distributed.py).
        from jax.sharding import NamedSharding

        from .distributed import put_global
        from .mesh import DATA_AXIS

        sh = NamedSharding(mesh, P(DATA_AXIS) if dp > 1 else P())
        prompt_ids = put_global(prompt_ids, sh)
        prompt_len = put_global(prompt_len, sh)
        rng = put_global(rng, NamedSharding(mesh, P()))
        if prompt_embeds is not None:
            prompt_embeds = put_global(prompt_embeds, sh)
    out, lengths = _pipeline_generate_jit(
        cfg,
        mesh,
        stage_layers,
        layer_masks,
        head_params,
        prompt_ids,
        prompt_len,
        rng,
        prompt_embeds,
        num_stages,
        max_new_tokens,
        capacity,
        cache_dtype,
        float(temperature),
        int(top_k),
        validate_top_p(top_p),
    )
    if jax.process_count() > 1 and dp > 1:
        # dp-sharded outputs span non-addressable devices; assemble the
        # global value on every host (small: token ids + lengths)
        from jax.experimental import multihost_utils

        out = multihost_utils.process_allgather(out, tiled=True)
        lengths = multihost_utils.process_allgather(lengths, tiled=True)
    return PipelineResult(np.asarray(out), np.asarray(lengths))
