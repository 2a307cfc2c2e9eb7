"""Persistent interleaved-decode programs: admit / chunk, with state carried
across calls — the compute side of continuous batching.

The reference's serving story is a daemon: ``run_worker_loop`` accepts
requests forever, one at a time (``/root/reference/utils/node_worker.py:
493-559``). Round 1's ``interleaved_generate`` is call-and-return: membership
is fixed at program start and finished slots idle until the full drain. This
module closes that gap the TPU way — the interleaved schedule's device state
(per-stage KV caches, in-flight ring blocks, per-slot offsets) becomes an
explicit ``ServeState`` pytree that round-trips between three jitted
``shard_map`` programs:

- ``serve_admit``: prefill ONE slot's rows (a ring traversal writing that
  slot's cache rows on every stage) while other slots stay mid-decode —
  the dynamic-admission analogue of ``receive_user_request``
  (``node_worker.py:188-224``). The slot's first decode embedding is
  precomputed and parked in ``inject``; stage 0 consumes it the next time
  the schedule hands it that slot.
- ``serve_chunk``: run a fixed number of interleaved microsteps
  (``lax.fori_loop`` — fixed trip count, one compiled program reused for the
  server's lifetime). Bookkeeping (tokens, lengths, done) is replicated via
  the vocab-sharded head (see ``schedule.py``), so the host reads results
  with a cheap fetch after each chunk and can stream tokens per ring cycle.
- block validity travels WITH the ring: each device carries an ``h_valid``
  bit for the block it holds, permuted alongside it, so freshly admitted
  slots ramp in correctly no matter where the schedule phase stands (the
  generalization of the one-shot program's ``m >= sidx`` wavefront).

The host-side queue/daemon that drives these programs lives in
``runtime/server.py``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.cache import KVCache, POS_SENTINEL
from ..models.config import ARENA_KINDS, RECURRENT_KINDS, ModelConfig
from ..models.stack import close_tables
from ..obs.metrics import REGISTRY
from ..ops.paged_attention import prefill_walk, window_from_blocks
from ..ops.quant import is_kv_quantized, kv_dequantize, kv_qmax, kv_quantize
from ..ops.sampling import is_stop as _is_stop
from .head import (
    _local_logits, head_specs, key_chain_split, local_view, psum_from,
    seed_chain_init, sp_embed, sp_next_token, sp_sample_rows,
)
from .mesh import CP_AXIS, PIPE_AXIS
from .pipeline import (
    model_fns, ring_chain, ring_chain_paged, stage_layer_specs,
)
from .tensor import TENSOR_AXIS
from jax import shard_map

# Admission-bucket usage, labeled by the padded prompt bucket — each label
# value is one compiled serve_admit shape, so this counter shows which rungs
# of the bucket ladder actually carry traffic (and which ones paid a compile
# for nothing). Incremented host-side by PipelineServer._admit_pending; the
# device programs below stay metric-free (nothing traceable runs in jit).
ADMIT_BUCKET_USED = REGISTRY.counter(
    "server_admit_bucket_total",
    "Admissions per prompt bucket (one compiled serve_admit shape each)",
    labels=("bucket",),
)


class ServeState(NamedTuple):
    """Device state of a live interleaved pipeline between program calls.

    Leaves marked [dev] differ per device (sharded over the pipe axis with a
    leading stage dim); the rest are replicated bookkeeping.
    """

    k: jax.Array          # [dev] dense: [S, Lp, M, C, Nkv, Dh];
    #   paged: the pooled arena, head-major [S, Lp, NB, Nkv, BS, Dh] —
    #   rows own block subsets via ``block_tables`` (block 0 = the reserved
    #   trash sink). Quantized KV serving stores the arena as int8/fp8 CODES
    v: jax.Array          # [dev] same layout as k
    k_scale: jax.Array    # [dev] [S, Lp, NB, Nkv] f32 per-block-per-head
    #   scales of a QUANTIZED arena (running absmax / qmax — see
    #   ops/quant's KV section); dense and bf16-paged modes carry a
    #   [S, 1, 1, 1] placeholder for pytree/snapshot parity, exactly like
    #   ``block_tables`` in dense mode
    v_scale: jax.Array    # [dev] same layout as k_scale
    kpos: jax.Array       # [dev] [S, M, W] key positions / sentinel, indexed
    #   by LOGICAL column (dense: W == C == the cache column; paged: column
    #   c lives in arena block table[row, c // BS] at slot c % BS) — always
    #   per-row private, so position masking is mode-independent
    h: jax.Array          # [dev] [S, Bs, 1, H] in-flight ring block
    h_valid: jax.Array    # [dev] [S] bool — the held block is real data
    pos_slots: jax.Array  # [dev] [S, M] this device's view of row positions
    write_off: jax.Array  # [dev] [S, num_slots] per-slot cache write offset
    out: jax.Array        # [M, OUT_CAP] int32 token buffer (prompt + gen)
    lengths: jax.Array    # [M] valid length per row
    done: jax.Array       # [M] bool
    budget: jax.Array     # [M] max total length (prompt + max_new) per row
    inject: jax.Array     # [M, 1, H] pending stage-0 injection embeddings
    inject_pending: jax.Array  # [M] bool
    rng: jax.Array        # [M, 2] raw uint32 PRNG key data, one chain per row
    temp: jax.Array       # [M] f32 sampling temperature (<= 0 → greedy)
    topk: jax.Array       # [M] int32 per-row top-k (0 → off)
    topp: jax.Array       # [M] f32 per-row top-p (1.0 → off)
    block_tables: jax.Array  # [M, T] int32 per-row arena block ids (paged
    #   mode; replicated — the host owns it and pushes updates between
    #   dispatches). Dense mode carries a [M, 1] placeholder so the pytree
    #   structure (state_specs parity, snapshots) is mode-independent.
    m: jax.Array          # scalar int32 microstep counter
    # A windowed model (``cfg.windowed``, paged only) keeps a KV state PER
    # KIND of attention: ``k`` / ``v`` / ``block_tables`` above are the FULL
    # layers' (``[S, L_full, NB, Hkv, BS, ·]``), these three the WINDOW
    # layers' — an arena with its own layer count, pool size and head count
    # (``[S, L_swa, NB_swa, Hkv_swa, BS, ·]``) and a table whose entries
    # behind the window the host hands back to the pool (trash). None (an
    # empty pytree: no operand of any program) for every other model.
    k_swa: Any = None
    v_swa: Any = None
    tables_swa: Any = None
    # A model with recurrent layers (``cfg.recurrent``, paged only;
    # ``models/nemotron_h.py``, ``models/jamba.py``, ``models/solar_open2.py``)
    # keeps, beside the arena, a recurrent state of FIXED size a request,
    # indexed by ROW and not paged by token: a small tree by name — ``{name:
    # [S, L_mixer, M, *cfg.recurrent_shapes[name]] f32}`` [dev], ``ssm`` (a KDA
    # mixer: ``kda``) the mixers' state and ``conv`` the conv's last inputs,
    # shaped by the configuration alone. ``k`` / ``v`` above are then the
    # ATTENTION layers' arena alone (``[S, L_attn, NB, ...]``). None (an
    # empty pytree: no operand of any program)
    # for every other model.
    recurrent: Any = None
    # A token-selecting model (``cfg.sparse_attn``, paged only): ONE index key
    # a token and layer, ``[S, Lp, NB, 1, BS, cfg.index_cache_dim]`` [dev] (the
    # key padded with zeros to whole 128-lane tiles), in the
    # same blocks under the same ``block_tables`` as ``k`` / ``v`` — allocated,
    # freed and written with them. None (an empty pytree: no operand of any
    # program) for every other model.
    idx: Any = None


def _dev(spec: P) -> bool:
    """True for per-device leaves — the bodies strip/restore their leading
    sharded dim (pipe-stacked state, or the cp-stacked block-table planes).
    A prefix match, not equality: with tensor parallelism the KV leaves
    carry a TENSOR_AXIS entry on the heads dim."""
    return len(spec) > 0 and spec[0] in (PIPE_AXIS, CP_AXIS)


def _kv_spec(tp: int, cp: int = 1, paged: bool = False) -> P:
    """Spec of every serve-side KV array — the dense [S, Lp, rows, C, Nkv,
    Dh] state leaves and the [S, Lp, 1, Spx, Nkv, Dh] prefix handle
    (token-major: heads are dim 4), and with ``paged`` the head-major
    arena [S, Lp, NB, Nkv, BS, Dh] (heads are dim 3). tp > 1 megatron-
    shards the heads dim, wherever the layout asked for keeps it (the stage
    fn computes only its tensor shard's heads — the caches store exactly
    those). cp > 1 (paged only, tp gated to 1 by the server) shards the
    arena's BLOCK dim instead: each cp shard owns a contiguous sub-arena of
    ``kv_blocks`` blocks. THE single source of the KV layout; state_specs,
    make_state, prefix_prefill and gather_prefix_kv all read it."""
    if cp > 1:
        return P(PIPE_AXIS, None, CP_AXIS)
    if tp == 1:
        return P(PIPE_AXIS)
    heads = 3 if paged else 4
    return P(PIPE_AXIS, *([None] * (heads - 1)), TENSOR_AXIS)


def state_specs(
    state: ServeState, tp: int = 1, cp: int = 1, quantized: bool = False,
    paged: bool = False,
) -> ServeState:
    dev = P(PIPE_AXIS)
    rep = P()
    kv = _kv_spec(tp, cp, paged)
    # scale arenas are pipe-sharded only (full Nkv per shard; quantized KV
    # is gated to tp == 1 by the server — heads-sharded scale plumbing is
    # future work). Under cp > 1 a QUANTIZED arena's scales follow the
    # block dim's cp sharding; the bf16 placeholder ([S, 1, 1, 1]) stays
    # pipe-only (nothing to shard).
    scale = P(PIPE_AXIS, None, CP_AXIS) if (cp > 1 and quantized) else dev
    # block tables: replicated host-pushed [M, T] normally; under cp > 1
    # the host pushes PER-SHARD planes [cp, M, T] of LOCAL block ids (each
    # shard's plane maps unowned columns to its local trash block 0), so
    # the leaf is cp-stacked and the bodies strip the leading dim like any
    # pipe leaf.
    tbl = P(CP_AXIS) if cp > 1 else rep
    swa = state.k_swa is not None  # a windowed model's second KV state
    return ServeState(
        k=kv, v=kv, k_scale=scale, v_scale=scale, kpos=dev, h=dev,
        h_valid=dev, pos_slots=dev, write_off=dev, out=rep, lengths=rep,
        done=rep, budget=rep, inject=rep, inject_pending=rep, rng=rep,
        temp=rep, topk=rep, topp=rep, block_tables=tbl, m=rep,
        k_swa=kv if swa else None, v_swa=kv if swa else None,
        tables_swa=tbl if swa else None,
        recurrent=(
            None if state.recurrent is None
            else {name: dev for name in state.recurrent}
        ),
        idx=None if state.idx is None else kv,
    )


# ---- paged-KV window assembly (serve_admit's one-shot scatter only) -------
# Inside the shard_map bodies a slot's rows are normally a dynamic SLICE of
# the per-row cache; the one remaining full-window producer is
# ``serve_admit``'s ONE-SHOT prefill, which builds the fresh slot window in
# registers and scatters it through the rows' block tables below. Every
# OTHER paged path is arena-native: decode microsteps (serve_chunk),
# spec-verify traversals (serve_verify) AND chunked prefill
# (serve_prefill_chunk — the ``_gather_window`` gather→recompute→scatter
# round trip it used to pay per chunk is retired) land fresh KV in the
# owning blocks — serve_chunk inside the attention KERNEL itself
# (ops/paged_attention.paged_attention_tpu(fresh=): one entry a row over a
# plain arena with the attention on its kernel, stored from the frontier
# cell the kernel holds, the arena left in place) and otherwise,
# like serve_verify's K + 1 entries a row, as ROWS (write_block_kv: a
# per-entry scatter, each row at its own column; paged_attention_write
# picks, from the program's statics), serve_prefill_chunk as whole-block TILES
# (write_chunk_kv: its rows share their columns and start on a block
# boundary, so a chunk is ``Sc / BS`` contiguous blocks a row — the
# block-sized write ``_scatter_pages`` below makes for a whole window;
# rows again where the chunk is under a block or the arena is int8/fp8,
# which is what the program's statics say and no option) — and attend
# straight off the arena through ``paged_attention`` /
# ``paged_prefill`` — the Pallas kernels stream exactly the blocks the
# tables name (per-step HBM traffic ∝ blocks actually written), the XLA
# backend gathers inside the op (the bit-exact CPU/tier-1 fallback, which
# also zero-gates trash-mapped entries — see gather_block_kv's
# trash-zeroing contract in ops/paged_attention). The admit scatter may hit
# duplicate arena blocks across rows — shared prefix blocks (every
# duplicate writes the identical broadcast values) and the trash block (a
# garbage sink) — so last-wins scatter order is immaterial.


@jax.named_scope("kv_layout")
def _window_blocks(window, block_size):
    """A token-major logical window ``[Lp, Bs, W, Nkv, D]`` cut into the
    arena's head-major blocks ``[Lp, Bs, T, Nkv, BS, D]`` — a layout change
    of the WINDOW one admission computed, never of the pool."""
    Lp, Bs, W = window.shape[0], window.shape[1], window.shape[2]
    vals = window.reshape(Lp, Bs, W // block_size, block_size,
                          *window.shape[3:])
    return jnp.transpose(vals, (0, 1, 2, 4, 3, 5))


def _scatter_pages(arena, tbl, window, block_size):
    """Write a logical window back through the tables (inverse gather)."""
    return arena.at[:, tbl].set(_window_blocks(window, block_size))


def _scatter_pages_q(arena, scale, tbl, window, block_size):
    """Quantizing inverse gather for an int8/fp8 arena: per-block-per-head
    absmax scales computed over the FULLY materialized window (the prefill
    paths always scatter whole blocks, so no running-max bookkeeping —
    each mapped block's scale is simply reset to its content's absmax).
    Collisions are the same population as ``_scatter_pages``'s and stay
    race-free for the same reasons: shared prefix blocks receive identical
    broadcast values (hence identical codes AND scales) from every
    admission, and the trash block is a garbage sink whose codes/scales
    are never dequantized (readers zero-gate table entry 0)."""
    vals = _window_blocks(window, block_size)  # [Lp, Bs, T, Nkv, BS, D]
    qmax = kv_qmax(arena.dtype)
    sc = (
        jnp.max(jnp.abs(vals.astype(jnp.float32)), axis=(4, 5)) / qmax
    )  # [Lp, Bs, T, Nkv]
    q = kv_quantize(vals, sc[..., None, None], arena.dtype)
    return arena.at[:, tbl].set(q), scale.at[:, tbl].set(sc)


def moe_log_width(cfg: ModelConfig, num_stages: int, layers_per_stage: int) -> int:
    """Columns a model with experts appends to what the host already
    fetches (``serve_chunk``'s log rows, ``serve_admit``'s first tokens):
    tokens per expert ``[E]``, distinct experts read per layer slot
    ``[S·Lp]``, and the live rows (positions) they came from. 0 for a model
    without experts: its programs return what they always did."""
    if not cfg.num_experts:
        return 0
    return cfg.router_experts + num_stages * layers_per_stage + 1


def _moe_counts(stats, live, sidx, num_stages):
    """One stage's ``MoeStats`` (stacked over its layers) and its live
    ``[B, S]`` mask → the replicated ``[E + S·Lp + 1]`` int32 vector of
    ``moe_log_width``, summed over the ring."""
    with jax.named_scope("moe"):
        Lp = stats.experts_read.shape[0]
        read = jax.lax.dynamic_update_slice(
            jnp.zeros((num_stages * Lp,), jnp.int32),
            stats.experts_read.astype(jnp.int32), (sidx * Lp,),
        )
        vec = jnp.concatenate([
            jnp.sum(stats.expert_tokens, axis=0).astype(jnp.int32), read,
            jnp.sum(live).astype(jnp.int32)[None],
        ])
        return jax.lax.psum(vec, PIPE_AXIS)


def pass_log_width(cfg: ModelConfig, rows: int) -> int:
    """Columns a LOOPED model (``cfg.passes`` > 1) appends to what the host
    already fetches, after a model with experts' counters: the pass each of
    the ``rows`` committed tokens' logits were read from (the exit gate's
    choice, ``models/stack.run_passes``), -1 beside a row that committed
    nothing. 0 for a model whose layers run once: its programs return what
    they always did."""
    return rows if cfg.passes > 1 else 0


def _slot_tables(st, row0, Bs):
    """The slot rows' block tables — a windowed model's a PAIR, full then
    window (``models/mimo_v2.forward_layers_paged`` takes pairs)."""
    tbl = jax.lax.dynamic_slice_in_dim(st.block_tables, row0, Bs, axis=0)
    if st.tables_swa is None:
        return tbl
    return tbl, jax.lax.dynamic_slice_in_dim(st.tables_swa, row0, Bs, axis=0)


def _arenas(st, row0=None, fresh=None):
    """``(k, v)`` as the stage function takes them: the arrays, or a
    windowed model's pairs. A model with recurrent layers gets its state
    beside ``k`` — ``(k, {"ssm", "conv", "row0", "fresh"})``: the slot's first
    row, and whether this dispatch is the rows' first chunk (the state then
    starts from zero inside the program; False in a decode step)."""
    if st.recurrent is not None:
        fresh = jnp.zeros((), bool) if fresh is None else fresh
        return (st.k, {**st.recurrent, "row0": row0, "fresh": fresh}), st.v
    if st.idx is not None:  # a token-selecting model: its index keys beside k
        return (st.k, st.idx), st.v
    if st.k_swa is None:
        return st.k, st.v
    return (st.k, st.k_swa), (st.v, st.v_swa)


def _arena_upd(st, k_new, v_new) -> dict:
    """The ``_replace`` keywords that put a stage function's arenas back."""
    if st.recurrent is not None:
        k_new, rec = k_new
        return {
            "k": k_new, "v": v_new,
            "recurrent": {name: rec[name] for name in st.recurrent},
        }
    if st.idx is not None:
        return {"k": k_new[0], "idx": k_new[1], "v": v_new}
    if st.k_swa is None:
        return {"k": k_new, "v": v_new}
    return {"k": k_new[0], "k_swa": k_new[1], "v": v_new[0], "v_swa": v_new[1]}


def make_state(
    cfg: ModelConfig,
    mesh: Mesh,
    layers_per_stage: int,
    *,
    capacity: int,
    batch_per_slot: int = 1,
    cache_dtype=jnp.bfloat16,
    act_dtype=jnp.bfloat16,
    tp: int = 1,
    kv_blocks: int = 0,
    kv_block_size: int = 0,
    cp: int = 1,
    swa_layers: int = 0,
    kv_blocks_swa: int = 0,
) -> ServeState:
    """Host-constructed empty state (all slots free / done).

    A model with recurrent layers (``cfg.recurrent``, paged): the mixers
    among a stage's ``layers_per_stage`` layers (the model's first that many:
    every stage holds the same kinds) keep their state in the ``recurrent``
    entry; the arena holds the stage's ATTENTION layers only.

    A windowed model (``cfg.windowed``, paged): ``swa_layers`` of the
    stage's ``layers_per_stage`` slots are window layers; the full layers'
    arena has the rest and ``kv_blocks`` blocks, the window layers' arena
    ``kv_blocks_swa`` — each with its kind's key/value heads — and a row has
    a block table per kind.

    With ``kv_blocks``/``kv_block_size`` set, the KV leaves become the
    POOLED paged arena, head-major ``[S, Lp, kv_blocks, Nkv,
    kv_block_size, Dh]`` (``models/cache.paged_arena_shape``) instead of
    per-row ``[.., M, C, ..]`` reservations, and every row's logical
    window is ``W = ceil(C / BS) * BS`` columns mapped through
    ``block_tables`` (all entries start at the trash block 0). HBM then
    scales with the arena size the operator budgets, not rows × capacity —
    the whole point of paged serving.

    With ``cp > 1`` (paged only) ``kv_blocks`` is PER SHARD: the global
    arena holds ``cp * kv_blocks`` blocks sharded contiguously over the cp
    axis (global block id ``g`` lives on shard ``g // kv_blocks`` at local
    id ``g % kv_blocks`` — the identity the host's table projection and
    ``ShardedBlockAllocator`` both rely on), and ``block_tables`` becomes
    the cp-stacked per-shard planes ``[cp, M, T]`` of LOCAL ids."""
    S = mesh.shape[PIPE_AXIS]
    Bs = batch_per_slot
    M = S * Bs
    Lp = layers_per_stage
    # a stage's first Lp layers (every stage holds the same kinds)
    recurrent_layers = sum(
        k in RECURRENT_KINDS for k in cfg.layer_kinds[:Lp]
    ) if cfg.recurrent else 0
    paged = kv_block_size > 0
    if paged:
        # logical window: capacity rounded up to whole blocks. out/kpos are
        # W wide so every column index the programs compute (write offsets,
        # spec scratch at the top of the window) has a table-mapped home.
        T = -(-capacity // kv_block_size)
        C = T * kv_block_size
    else:
        T = 1  # dense placeholder table (leaf exists for pytree parity)
        C = capacity
    H = cfg.hidden_size
    dev = NamedSharding(mesh, P(PIPE_AXIS))
    rep = NamedSharding(mesh, P())
    dev_kv = NamedSharding(mesh, _kv_spec(tp, cp, paged))

    single = jax.process_count() == 1

    def put(arr, sh):
        """Small bookkeeping arrays: host-built, placed per runtime."""
        if single:
            return jax.device_put(arr, sh)
        from .distributed import put_global

        return put_global(arr, sh)

    def zeros(shape, dtype, sh):
        """Big arrays (the KV state is hundreds of MB at serving
        capacities): created DIRECTLY SHARDED on device via a jitted fill —
        no whole-array staging on one chip (a plain jnp.zeros would
        materialize the global array on the default device first) and no
        host→device transfer of hundreds of MB of zeros. Multi-controller
        keeps the per-process put_global assembly."""
        if single and 0 in shape:
            # a latent cache's empty value array: nothing to fill, and XLA
            # gives a zero-sized result a sharding of its own
            return jax.device_put(np.zeros(shape, dtype), sh)
        if single:
            return jax.jit(
                lambda: jnp.zeros(shape, dtype), out_shardings=sh
            )()
        from .distributed import put_global

        return put_global(np.zeros(shape, dtype), sh)

    if paged:
        from ..models.cache import paged_arena_shape

        # (``cfg.arena_slots``: a layer of two attentions fills two slots)
        arena_layers = Lp * cfg.arena_slots - swa_layers
        if recurrent_layers:
            arena_layers = sum(k in ARENA_KINDS for k in cfg.layer_kinds[:Lp])
        kv_shape = (
            S, *paged_arena_shape(
                cfg, cp * kv_blocks, kv_block_size, arena_layers,
                heads=cfg.kv_heads_of("full") if swa_layers else None,
            )
        )
    else:
        kv_shape = (
            S, Lp * cfg.arena_slots, M, C, cfg.cache_heads, cfg.cache_k_dim
        )
    # the value array beside it: the same but for the last dim — ZERO wide
    # for a latent cache, whose value read is a slice of the key read. The
    # array stays, empty, so that state, snapshots and host tiers keep one
    # structure for every model
    v_shape = (*kv_shape[:-1], cfg.cache_v_dim)
    # quantized (int8/fp8) arenas carry per-block-per-head scale arenas;
    # everything else gets the minimal placeholder (pytree parity — same
    # treatment as dense mode's [M, 1] block-table stub)
    quantized = paged and is_kv_quantized(cache_dtype)
    scale_shape = (
        (S, Lp * cfg.arena_slots, cp * kv_blocks, cfg.cache_heads)
        if quantized
        else (S, 1, 1, 1)
    )
    dev_scale = (
        NamedSharding(mesh, P(PIPE_AXIS, None, CP_AXIS))
        if (cp > 1 and quantized) else dev
    )
    tbl_shape = (cp, M, T) if cp > 1 else (M, T)
    tbl_sh = NamedSharding(mesh, P(CP_AXIS)) if cp > 1 else rep
    state = ServeState(
        k=zeros(kv_shape, cache_dtype, dev_kv),
        v=zeros(v_shape, cache_dtype, dev_kv),
        k_scale=zeros(scale_shape, jnp.float32, dev_scale),
        v_scale=zeros(scale_shape, jnp.float32, dev_scale),
        kpos=put(np.full((S, M, C), int(POS_SENTINEL), np.int32), dev),
        h=put(np.zeros((S, Bs, 1, H), act_dtype), dev),
        h_valid=put(np.zeros((S,), np.bool_), dev),
        pos_slots=put(np.zeros((S, M), np.int32), dev),
        write_off=put(np.zeros((S, S), np.int32), dev),
        out=put(np.zeros((M, C), np.int32), rep),
        lengths=put(np.zeros((M,), np.int32), rep),
        done=put(np.ones((M,), np.bool_), rep),
        budget=put(np.zeros((M,), np.int32), rep),
        inject=put(np.zeros((M, 1, H), act_dtype), rep),
        inject_pending=put(np.zeros((M,), np.bool_), rep),
        rng=put(np.zeros((M, 2), np.uint32), rep),
        temp=put(np.zeros((M,), np.float32), rep),
        topk=put(np.zeros((M,), np.int32), rep),
        topp=put(np.ones((M,), np.float32), rep),
        block_tables=put(np.zeros(tbl_shape, np.int32), tbl_sh),
        m=put(np.zeros((), np.int32), rep),
    )
    if swa_layers:
        if not paged or cp > 1 or tp > 1 or quantized:
            raise NotImplementedError(
                "a windowed model's per-kind KV state needs a paged bf16 "
                "arena and no tp / cp"
            )
        swa_shape = (
            S, *paged_arena_shape(
                cfg, kv_blocks_swa, kv_block_size, swa_layers,
                heads=cfg.kv_heads_of("swa"),
            )
        )
        state = state._replace(
            k_swa=zeros(swa_shape, cache_dtype, dev_kv),
            v_swa=zeros(
                (*swa_shape[:-1], cfg.cache_v_dim), cache_dtype, dev_kv
            ),
            tables_swa=put(np.zeros(tbl_shape, np.int32), tbl_sh),
        )
    if cfg.sparse_attn:
        if not paged or cp > 1 or tp > 1 or quantized:
            raise NotImplementedError(
                "a token-selecting model's index arena needs a paged bf16 "
                "arena and no tp / cp"
            )
        state = state._replace(idx=zeros(
            (*kv_shape[:3], 1, kv_block_size, cfg.index_cache_dim),
            cache_dtype, dev_kv,
        ))
    if recurrent_layers:
        if not paged or cp > 1 or tp > 1 or quantized:
            raise NotImplementedError(
                "a recurrent state beside the arena needs a paged bf16 arena "
                "and no tp / cp"
            )
        state = state._replace(recurrent={
            name: zeros((S, recurrent_layers, M, *shape), jnp.float32, dev)
            for name, shape in cfg.recurrent_shapes.items()
        })
    return state


@functools.partial(
    jax.jit, static_argnames=("cfg", "mesh", "num_stages", "cache_dtype", "tp")
)
def prefix_prefill(
    cfg: ModelConfig,
    mesh: Mesh,
    stage_layers: Any,
    layer_masks: jnp.ndarray,
    head_params: Any,  # vocab-sharded
    prefix: jnp.ndarray,      # [1, Sp] right-padded prefix ids
    prefix_len: jnp.ndarray,  # scalar int32
    num_stages: int,
    cache_dtype,
    tp: int = 1,
):
    """Prefill a SHARED PREFIX once, returning its per-stage KV — the device
    side of prefix caching. Requests admitted with this handle skip the
    prefix's prefill entirely (``serve_admit(prefix_kv=...)`` seeds the
    slot's cache rows from it): an N-request batch over a shared system
    prompt pays the prompt's FLOPs once instead of N times. Returns
    ``(k [S, Lp, 1, Sp, Nkv, Dh], v, pos [S, 1, Sp])`` — pipe-sharded, like
    a 1-row slice of the serve state's cache."""
    fns = model_fns(cfg, tp_axis=TENSOR_AXIS if tp > 1 else None)
    Sp = prefix.shape[1]
    nkv = cfg.cache_heads // tp  # heads LOCAL to a tensor shard
    ring = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def body(stage_layers, layer_mask, head_params, prefix, prefix_len):
        layers = jax.tree.map(lambda a: a[0], stage_layers)
        lmask = layer_mask[0]
        hd = local_view(head_params)
        sidx = jax.lax.axis_index(PIPE_AXIS)
        Lp = lmask.shape[0] * cfg.arena_slots  # the cache's layer slots
        cache = KVCache(
            k=jnp.zeros((Lp, 1, Sp, nkv, cfg.cache_k_dim), cache_dtype),
            v=jnp.zeros((Lp, 1, Sp, nkv, cfg.cache_v_dim), cache_dtype),
            pos=jnp.full((1, Sp), POS_SENTINEL, jnp.int32),
            length=jnp.zeros((), jnp.int32),
        )
        idx = jnp.arange(Sp, dtype=jnp.int32)
        positions = jnp.where(
            idx[None, :] < prefix_len, idx[None, :], POS_SENTINEL
        )
        h = sp_embed(cfg, hd, prefix, positions)
        _, cache, _ = ring_chain(
            fns, cfg, layers, lmask, sidx, ring, num_stages, h, cache,
            positions,
            moe_live=(positions != POS_SENTINEL) if cfg.num_experts else None,
            close=close_tables(cfg, hd),
        )
        return cache.k[None], cache.v[None], cache.pos[None]

    kv_spec = _kv_spec(tp)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            stage_layer_specs(cfg, tp, stage_layers), P(PIPE_AXIS),
            head_specs(head_params), P(), P(),
        ),
        out_specs=(kv_spec, kv_spec, P(PIPE_AXIS)),
        check_vma=False,
    )(stage_layers, layer_masks, head_params, prefix, prefix_len)


@functools.partial(
    jax.jit, static_argnames=("mesh", "block_size", "tp", "out_dtype")
)
def gather_prefix_kv(
    mesh: Mesh,
    k_arena: jnp.ndarray,  # ServeState.k, paged arena [S, Lp, NB, Nkv, BS, Dh]
    v_arena: jnp.ndarray,
    blocks: jnp.ndarray,   # [T] int32 arena block ids covering the prefix
    block_size: int,
    tp: int = 1,
    k_scale: jnp.ndarray = None,  # ServeState.k_scale — quantized arenas:
    v_scale: jnp.ndarray = None,  # the handle dequantizes to out_dtype
    out_dtype=None,
):
    """Assemble a ``serve_admit``-compatible prefix handle STRAIGHT FROM
    THE ARENA — the device half of the automatic radix prefix cache
    (``runtime/radix.py``). Where ``prefix_prefill`` pays the prefix's
    forward pass to build ``(k [S, Lp, 1, Spx, Nkv, Dh], v, pos)``, this
    just gathers the ``T`` cached blocks a radix match named and lays the
    gathered blocks out token-major: same output layout, zero prefill
    FLOPs. Every token slot is real (matches are block-aligned by
    construction), so ``pos`` is simply ``arange(Spx)``.

    The admission that consumes this re-scatters the identical values
    through the new row's table (shared blocks receive the bytes they
    already hold — race-free under device program order, same contract as
    the PrefixHandle broadcast), which is what lets one ``serve_admit``
    program serve both the explicit-handle and the radix path."""
    arena_spec = _kv_spec(tp, paged=True)
    kv_spec = _kv_spec(tp)  # the handle: token-major like a dense row

    def body(k, v, tbl, ks, vs):
        k, v = k[0], v[0]  # local [Lp, NB, nkv, BS, Dh]
        gk = k[:, tbl]     # [Lp, T, nkv, BS, Dh]
        gv = v[:, tbl]
        if ks is not None:
            # quantized arena: the handle carries DEQUANTIZED values (the
            # admission that consumes it requantizes at its own scatter) —
            # prefix compute quality is full precision either way
            sk = ks[0][:, tbl]  # [Lp, T, nkv]
            sv = vs[0][:, tbl]
            gk = kv_dequantize(gk, sk[..., None, None], out_dtype)
            gv = kv_dequantize(gv, sv[..., None, None], out_dtype)
        # one row per layer: [Lp, T*BS, nkv, Dh] -> [Lp, 1, T*BS, nkv, Dh]
        gk = window_from_blocks(gk)[:, None]
        gv = window_from_blocks(gv)[:, None]
        pos = jnp.arange(gk.shape[2], dtype=jnp.int32)[None]
        return gk[None], gv[None], pos[None]

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            arena_spec, arena_spec, P(),
            P(PIPE_AXIS), P(PIPE_AXIS),  # leafless no-ops when None
        ),
        out_specs=(kv_spec, kv_spec, P(PIPE_AXIS)),
        check_vma=False,
    )(k_arena, v_arena, blocks, k_scale, v_scale)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def write_arena_blocks(k_arena, v_arena, blocks, k_host, v_host):
    """Write host-tier block KV back into the pooled arena (the radix
    cache streaming a demoted node in on a hit, a disagg hand-off landing
    a streamed prefix): a block-axis scatter, donated so the arena
    updates in place — restore never transiently doubles the dominant HBM
    consumer. Bit-exact: the values written are the bytes ``read`` pulled
    out (same cache dtype end to end), whole blocks in ARENA layout
    (``[S, Lp, n, Nkv, BS, Dh]`` — the host tier, the disk tier and the
    disagg hand-off carry them opaquely; the block axis is dim 2). On a
    context-parallel arena (block axis sharded over cp) ``blocks`` are
    GLOBAL ids — positions on the logical concatenated axis — so GSPMD
    lands each block's write on exactly its owner shard; the host tensors
    are tiny (a prefix's blocks), so the replicated operand cost is noise
    next to the arena."""
    return (
        k_arena.at[:, :, blocks].set(k_host),
        v_arena.at[:, :, blocks].set(v_host),
    )


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def write_arena_blocks_q(
    k_arena, v_arena, k_scale, v_scale, blocks,
    k_host, v_host, ks_host, vs_host,
):
    """``write_arena_blocks`` for a QUANTIZED arena: the demoted codes AND
    their per-block-per-head scales restore verbatim (the host tier
    round-trips quantized bytes — twice the cached tokens per host-RAM
    byte, same bit-exactness contract)."""
    return (
        k_arena.at[:, :, blocks].set(k_host),
        v_arena.at[:, :, blocks].set(v_host),
        k_scale.at[:, :, blocks].set(ks_host),
        v_scale.at[:, :, blocks].set(vs_host),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def serve_cancel_rows(state: ServeState, rows_mask: jnp.ndarray) -> ServeState:
    """Mark rows done from the host between chunks (request cancellation,
    host-side stop sequences, deadline expiry, failure containment). Safe by
    the same mechanism EOS uses: a row whose ``done`` flips at a chunk
    boundary stops committing tokens, its in-flight block is dropped by the
    post-update validity gating in ``serve_chunk``, and the slot frees once
    all its rows are done."""
    return state._replace(done=state.done | rows_mask)


# Rows cancelled per serve_cancel_rows dispatch: the deadline sweep and the
# failure-containment paths batch every row they stop into ONE device call
# per step — a per-row dispatch would pay one host→device round trip per
# straggler under deadline pressure, exactly when the server is busiest.
CANCEL_BATCH_ROWS = REGISTRY.histogram(
    "server_cancel_batch_rows",
    "Rows stopped per batched serve_cancel_rows dispatch (cancel, deadline "
    "sweep, failure containment)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)


def cancel_rows_batched(state: ServeState, rows, n_rows: int) -> ServeState:
    """Stop every row in ``rows`` with one ``serve_cancel_rows`` dispatch.
    ``n_rows`` is the server's total row count (stages × batch_per_slot)."""
    rows = list(rows)
    mask = np.zeros((n_rows,), bool)
    mask[rows] = True
    CANCEL_BATCH_ROWS.observe(len(rows))
    return serve_cancel_rows(state, jnp.asarray(mask))


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "mesh", "num_stages", "cache_dtype", "filtering", "tp",
        "block_size", "prefix_in_arena", "cp",
    ),
    donate_argnums=(5,),  # the previous ServeState buffers are dead on
    # return (the server reassigns self.state) — donation halves the
    # state's transient HBM footprint and lets XLA update in place
)
def serve_admit(
    cfg: ModelConfig,
    mesh: Mesh,
    stage_layers: Any,
    layer_masks: jnp.ndarray,
    head_params: Any,  # vocab-sharded
    state: ServeState,
    prompts: jnp.ndarray,     # [Bs, Sp] right-padded (Sp = admission bucket)
    prompt_len: jnp.ndarray,  # [Bs]
    row_valid: jnp.ndarray,   # [Bs] bool — False rows stay free/done
    slot: jnp.ndarray,        # scalar int32
    max_new: jnp.ndarray,     # [Bs] per-row new-token budget
    seeds: jnp.ndarray,       # [Bs] int32 per-request sampling seeds
    temperature: jnp.ndarray,  # [Bs] f32; <= 0 → greedy for that row
    top_k: jnp.ndarray,       # [Bs] int32 per-request top-k (0 → off)
    top_p: jnp.ndarray,       # [Bs] f32 per-request top-p (1.0 → off)
    num_stages: int,
    cache_dtype,
    prompt_embeds: Any = None,  # [Bs, Sp, H]: privacy entry — ids never enter
    filtering: bool = True,  # static: compile top-k/top-p machinery
    prefix_kv: Any = None,  # (k, v, pos) from prefix_prefill — prefix caching
    prefix_len: Any = None,  # scalar int32 real prefix length
    key_override: Any = None,  # ([Bs, 2] uint32 carried chains, [Bs] bool
    #   mask): migrated rows resume their sampling chain mid-stream — see
    #   the key-chain note below
    tp: int = 1,  # static: tensor-parallel degree (megatron-sharded heads)
    block_size: int = 0,  # static: paged-KV block size (0 = dense state)
    prefix_in_arena: bool = False,  # static: the prefix blocks ALREADY hold
    #   this KV (radix-hit admission) — skip re-scattering them; see below
    cp: int = 1,  # static: context-parallel degree — the arena's block dim
    #   is sharded over CP_AXIS and block_tables is the cp-stacked [cp, M,
    #   T] per-shard planes. The one-shot prefill itself is cp-REPLICATED
    #   (dense in-register compute, no arena reads); only the scatter back
    #   differs per shard, and it lands owned columns in real local blocks
    #   while unowned columns fall into the shard's local trash block 0.
):
    """Prefill ``slot`` with up to Bs new requests while the rest of the
    pipeline state is parked. Returns the updated state.

    Paged mode (``block_size > 0``): the fresh slot window is built exactly
    as in dense mode (the window width IS ``state.out.shape[1]``), then
    scattered through the slot rows' block tables instead of into per-row
    cache columns. The host mapped the tables BEFORE this dispatch, so the
    scatter fully initializes every block the rows own — including shared
    prefix blocks, which receive the identical broadcast prefix values on
    every admission that maps them (storage is shared; the broadcast is
    the same per-admission compute dense mode pays).

    Returns ``(state, tok0)``: the first generated token per row, sampled at
    admission — the host appends it to the request and mirrors lengths/done
    from it, so steady-state serving needs NO bookkeeping fetches (see
    ``serve_chunk``'s log). A model with experts appends the prefill's
    ``moe_log_width`` counters to ``tok0`` (pads and free rows route nowhere
    and count nothing).

    With ``prompt_embeds`` the admission skips the vocab-parallel embedding
    lookup and enters the ring with caller-provided hidden states (≙ the
    reference's request-injection channel, ``node_worker.py:476-491`` — raw
    text/ids never leave the node that accepted the request); ``prompts``
    then only fills the replicated out buffer — pass zeros.

    With ``prefix_kv`` (a ``prefix_prefill`` result) the slot's cache rows
    are SEEDED with the shared prefix's keys/values — ``prompts`` carries
    only each request's suffix, at absolute positions ``prefix_len + i``,
    and the prefix's prefill compute is never repeated (prefix caching).

    ``prefix_in_arena`` (static, paged + prefix only) marks a RADIX-HIT
    admission whose prefix operand was gathered straight from the arena
    (``gather_prefix_kv``): the mapped shared blocks already hold the
    prefix bytes, so the scatter back covers only the suffix/budget region
    past them. For a bf16 arena the skipped writes were identical bytes (a
    pure write saving); for a QUANTIZED arena they were NOT — the operand
    dequantizes codes into the compute dtype, and requantizing that
    rounded window re-snaps each shared block's scale and can drift its
    codes by ±1 ulp, so every radix hit used to rewrite slightly different
    bytes under concurrent readers of the same blocks. Skipping makes the
    insert-time quantization the one-time scale snap it was meant to be:
    shared block bytes are byte-stable across any number of hits. An
    explicit ``PrefixHandle`` admission must NOT set this — its freshly
    allocated blocks are first WRITTEN by the admission that maps them.

    Key-chain note (``key_override``): a row resuming a MIGRATED sampled
    request carries the chain its source replica would hold after the
    tokens already streamed — ``t`` splits of ``key(seed)``. For masked
    rows the admission draws ``tok0`` from ``split(carried)`` (the exact
    draw the unfaulted run would make for token ``t+1``) and stores the
    advanced chain; unmasked rows walk the fresh ``seed_chain_init`` chain
    unchanged, so carried and fresh requests co-admit in one batch."""
    fns = model_fns(cfg, tp_axis=TENSOR_AXIS if tp > 1 else None)
    Bs, Sp = prompts.shape
    nkv = cfg.cache_heads // tp  # heads LOCAL to a tensor shard
    ring = [(i, (i + 1) % num_stages) for i in range(num_stages)]
    C = state.out.shape[1]
    quantized = is_kv_quantized(state.k.dtype)  # trace-time constant

    @jax.named_scope("state")
    def body(stage_layers, layer_mask, head_params, state, prompts,
             prompt_len, row_valid, slot, max_new, seeds, temperature,
             top_k, top_p, prompt_embeds, prefix_kv, prefix_len,
             key_override):
        layers = jax.tree.map(lambda a: a[0], stage_layers)
        lmask = layer_mask[0]
        hd = local_view(head_params)
        sidx = jax.lax.axis_index(PIPE_AXIS)
        st = jax.tree.map(
            lambda spec, leaf: leaf[0] if _dev(spec) else leaf,
            state_specs(state, tp, cp, quantized, bool(block_size)), state,
        )
        row0 = slot * Bs

        # fresh cache rows for this slot only (``Lp``: the layer slots)
        Lp = lmask.shape[0] * cfg.arena_slots
        kv_shape = (Lp, Bs, C, nkv)
        cache = KVCache(
            k=jnp.zeros((*kv_shape, cfg.cache_k_dim), cache_dtype),
            v=jnp.zeros((*kv_shape, cfg.cache_v_dim), cache_dtype),
            pos=jnp.full((Bs, C), POS_SENTINEL, jnp.int32),
            length=jnp.zeros((), jnp.int32),
        )
        idx = jnp.arange(Sp, dtype=jnp.int32)
        if prefix_kv is None:
            pfx = jnp.zeros((), jnp.int32)  # no prefix: positions from 0
        else:
            pfx = prefix_len
            pk, pv, ppos = prefix_kv  # [1, Lp, 1, Spx, Nkv, Dh] local views
            pk, pv, ppos = pk[0], pv[0], ppos[0]
            Spx = pk.shape[2]
            # broadcast the 1-row prefix over the slot's Bs rows; the suffix
            # prefill writes AFTER the (bucket-padded) prefix region
            kb = jnp.broadcast_to(
                pk, (Lp, Bs, Spx, *pk.shape[3:])
            ).astype(cache_dtype)
            vb = jnp.broadcast_to(
                pv, (Lp, Bs, Spx, *pv.shape[3:])
            ).astype(cache_dtype)
            posb = jnp.broadcast_to(ppos, (Bs, Spx))
            cache = KVCache(
                k=jax.lax.dynamic_update_slice(cache.k, kb, (0, 0, 0, 0, 0)),
                v=jax.lax.dynamic_update_slice(cache.v, vb, (0, 0, 0, 0, 0)),
                pos=jax.lax.dynamic_update_slice(cache.pos, posb, (0, 0)),
                length=jnp.asarray(Spx, jnp.int32),
            )
        positions = jnp.where(
            idx[None, :] < prompt_len[:, None],
            pfx + idx[None, :],
            POS_SENTINEL,
        )
        if prompt_embeds is None:
            h = sp_embed(cfg, hd, prompts, positions)
        else:
            h = prompt_embeds
        # a model with experts: pads and free rows route nowhere, neither
        # read for nor counted
        moe_live = (
            (positions != POS_SENTINEL) & row_valid[:, None]
            if cfg.num_experts else None
        )
        h, cache, moe_stats = ring_chain(
            fns, cfg, layers, lmask, sidx, ring, num_stages, h, cache,
            positions, moe_live=moe_live, close=close_tables(cfg, hd),
        )
        h_last = jnp.take_along_axis(
            h, (prompt_len - 1)[:, None, None], axis=1
        )[:, 0]
        h_last = psum_from(h_last, 0)
        # Per-row key chains mirror the monolith's (key(seed) → split →
        # sample), so a seeded temperature>0 request draws the monolith's
        # B=1 tokens exactly (r2 weak #8).
        row_keys, subs = seed_chain_init(seeds)  # [Bs, 2] each
        if key_override is not None:
            # migrated rows: one split of the carried chain yields exactly
            # the (stored, sub) pair the unfaulted run's next commit would
            ko, ko_mask = key_override
            ck, cs = key_chain_split(ko)
            row_keys = jnp.where(ko_mask[:, None], ck, row_keys)
            subs = jnp.where(ko_mask[:, None], cs, subs)
        tok0 = sp_sample_rows(
            cfg, hd, h_last, subs, temperature, top_k, top_p, num_stages,
            filtering=filtering,
        )  # [Bs] replicated
        tok0 = jnp.where(row_valid, tok0, 0)

        # ---- scatter the slot into the parked state ----
        # total sequence length per row (prefix + suffix; pfx is 0 without
        # a prefix handle) drives every length-indexed bookkeeping field
        total = pfx + prompt_len
        off0 = 0 if prefix_kv is None else int(prefix_kv[0].shape[3])
        # radix-hit admissions skip the prefix-region scatter (the mapped
        # shared blocks already hold these bytes — see the docstring); the
        # match is block-aligned by construction, asserted at trace time
        npfx = 0
        if prefix_in_arena and block_size and off0:
            assert off0 % block_size == 0, (
                f"prefix_in_arena needs a block-aligned prefix, got "
                f"{off0} tokens at block size {block_size}"
            )
            npfx = off0 // block_size
        w0 = npfx * block_size
        scale_upd = {}
        if block_size and quantized:
            # insert-quantization: the slot's full-precision window (the
            # prefill just computed it) scatters as codes + fresh
            # per-block scales — quantized KV never exists as bf16 in HBM
            tbl = _slot_tables(st, row0, Bs)[:, npfx:]
            k_new, ks_new = _scatter_pages_q(
                st.k, st.k_scale, tbl, cache.k[:, :, w0:], block_size
            )
            v_new, vs_new = _scatter_pages_q(
                st.v, st.v_scale, tbl, cache.v[:, :, w0:], block_size
            )
            scale_upd = {"k_scale": ks_new, "v_scale": vs_new}
        elif block_size:
            tbl = _slot_tables(st, row0, Bs)[:, npfx:]
            k_new = _scatter_pages(st.k, tbl, cache.k[:, :, w0:], block_size)
            v_new = _scatter_pages(st.v, tbl, cache.v[:, :, w0:], block_size)
        else:
            k_new = jax.lax.dynamic_update_slice_in_dim(
                st.k, cache.k, row0, axis=1
            )
            v_new = jax.lax.dynamic_update_slice_in_dim(
                st.v, cache.v, row0, axis=1
            )
        kpos_new = jax.lax.dynamic_update_slice_in_dim(
            st.kpos, cache.pos, row0, axis=0
        )
        pos_slots = jax.lax.dynamic_update_slice_in_dim(
            st.pos_slots, total, row0, axis=0
        )
        write_off = st.write_off.at[slot].set(off0 + Sp)

        rows = row0 + jnp.arange(Bs, dtype=jnp.int32)
        out_rows = jnp.zeros((Bs, C), jnp.int32)
        out_rows = jax.lax.dynamic_update_slice(out_rows, prompts, (0, 0))
        # ``out`` column == PREFIX-INCLUSIVE sequence index for everything a
        # row generates (``serve_chunk`` commits at wpos = lengths, which
        # counts the prefix): tok0 must land at column ``total``, not the
        # suffix-relative ``prompt_len`` — a prefix admission previously left
        # an n-column gap between tok0 and the chunk commits (ADVICE r5).
        # For prefix rows, columns [prompt_len, total) stay zero (the prefix
        # ids live in the handle, not in ``out``); the generated run is
        # contiguous from column ``total`` on.
        out_rows = out_rows.at[jnp.arange(Bs), total].set(tok0)
        out = jax.lax.dynamic_update_slice_in_dim(st.out, out_rows, row0, axis=0)

        lengths = jax.lax.dynamic_update_slice_in_dim(
            st.lengths, jnp.where(row_valid, total + 1, 0), row0, axis=0
        )
        budget = jax.lax.dynamic_update_slice_in_dim(
            st.budget, jnp.where(row_valid, total + max_new, 0), row0,
            axis=0,
        )
        done0 = _is_stop(cfg, tok0) | ~row_valid | (max_new <= 1)
        done = jax.lax.dynamic_update_slice_in_dim(st.done, done0, row0, axis=0)

        inj = sp_embed(cfg, hd, tok0[:, None], total[:, None])  # [Bs,1,H]
        inject = jax.lax.dynamic_update_slice_in_dim(
            st.inject, inj.astype(st.inject.dtype), row0, axis=0
        )
        inject_pending = jax.lax.dynamic_update_slice_in_dim(
            st.inject_pending, row_valid & ~done0, row0, axis=0
        )
        rng = jax.lax.dynamic_update_slice_in_dim(
            st.rng, row_keys, row0, axis=0
        )
        temp = jax.lax.dynamic_update_slice_in_dim(
            st.temp, jnp.where(row_valid, temperature, 0.0), row0, axis=0
        )
        topk = jax.lax.dynamic_update_slice_in_dim(
            st.topk, jnp.where(row_valid, top_k, 0), row0, axis=0
        )
        topp = jax.lax.dynamic_update_slice_in_dim(
            st.topp, jnp.where(row_valid, top_p, 1.0), row0, axis=0
        )

        # Defense in depth vs stale parked blocks: the device whose next
        # microstep serves this slot currently holds a block belonging to it
        # (dead — the slot was free); mark it invalid so the injection path
        # is the only way the new request's data enters the ring.
        next_served = jnp.mod(st.m - sidx, num_stages)
        h_valid = jnp.where(next_served == slot, False, st.h_valid)

        new = st._replace(
            k=k_new, v=v_new, kpos=kpos_new, pos_slots=pos_slots,
            write_off=write_off, out=out, lengths=lengths, budget=budget,
            done=done, inject=inject, inject_pending=inject_pending,
            h_valid=h_valid, rng=rng, temp=temp, topk=topk, topp=topp,
            **scale_upd,
        )
        new = jax.tree.map(
            lambda spec, leaf: leaf[None] if _dev(spec) else leaf,
            state_specs(state, tp, cp, quantized, bool(block_size)), new,
        )
        if cfg.num_experts:
            # the experts' counters ride the array the host fetches anyway
            tok0 = jnp.concatenate(
                [tok0, _moe_counts(moe_stats, moe_live, sidx, num_stages)]
            )
        if cfg.passes > 1:
            # ... and a looped model's exit pass of each first token
            # (``moe_stats``: the pass a position's closed state came from)
            exit0 = jnp.take_along_axis(
                moe_stats, (prompt_len - 1)[:, None], axis=1
            )[:, 0]
            tok0 = jnp.concatenate([tok0, jnp.where(row_valid, exit0, -1)])
        return new, tok0

    specs = state_specs(
        state, tp, cp, quantized,
        bool(block_size),
    )
    out_state, tok0 = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            stage_layer_specs(cfg, tp, stage_layers), P(PIPE_AXIS),
            head_specs(head_params), specs,
            P(), P(), P(), P(), P(), P(), P(), P(), P(),
            P(),  # no-op when prompt_embeds is None (leafless pytree)
            # prefix_kv (k, v, pos) is sharded like a DENSE serve cache
            # ([S, Lp, 1, Spx, Nkv, Dh] token-major whatever the state's
            # mode: heads on TENSOR under tp; pos pipe-only); both entries
            # are leafless no-ops when prefix caching is off
            P(PIPE_AXIS) if prefix_kv is None
            else (_kv_spec(tp), _kv_spec(tp), P(PIPE_AXIS)),
            P(),
            P(),  # key_override: replicated (leafless no-op when None)
        ),
        out_specs=(specs, P()),
        check_vma=False,
    )(stage_layers, layer_masks, head_params, state, prompts, prompt_len,
      row_valid, slot, max_new, seeds, temperature, top_k, top_p,
      prompt_embeds, prefix_kv, prefix_len, key_override)
    return out_state, tok0


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "mesh", "num_stages", "tp", "block_size", "cache_dtype",
        "attn", "cp",
    ),
    donate_argnums=(5,),  # see serve_admit
)
def serve_prefill_chunk(
    cfg: ModelConfig,
    mesh: Mesh,
    stage_layers: Any,
    layer_masks: jnp.ndarray,
    head_params: Any,  # vocab-sharded
    state: ServeState,
    tokens: jnp.ndarray,     # [Bs, Sc] one chunk of the (right-padded) prompts
    positions: jnp.ndarray,  # [Bs, Sc] absolute positions; sentinel where the
    #   row is past its prompt AND at each row's final real token (that token
    #   is processed later via the injection path — see serve_admit_finish)
    slot: jnp.ndarray,       # scalar int32
    chunk_off: jnp.ndarray,  # scalar int32 SUFFIX-relative offset of this
    #   chunk (the ``out``-buffer column of its first token); the cache
    #   column is ``prefix_off + chunk_off``
    reset: jnp.ndarray,      # scalar bool — first chunk zeroes the slot rows
    num_stages: int,
    tp: int = 1,
    block_size: int = 0,  # static: paged-KV block size (0 = dense state)
    cache_dtype=None,  # static: retained for shape-key compat; the paged
    #   path no longer round-trips a dequantized window between chunks
    #   (fresh KV quantizes at insert, attention dequantizes in-op)
    prefix_off: Any = None,  # scalar int32 — logical position/column where
    #   this admission's SUFFIX starts: a radix-hit admission with a long
    #   leftover suffix starts at n0 > 0 with the prefix KV already
    #   RESIDENT in the arena (shared blocks mapped read-only into the
    #   slot rows' tables). None/0 = a cold admission. Paged-only.
    attn: str = "xla",  # static: paged attention backend for the chunk's
    #   arena-native attention — "xla" (gather inside the op, the exact
    #   CPU/tier-1 fallback), "kernel" (the Pallas chunked-prefill
    #   kernel), "interpret" (the kernel emulated, CI on CPU). Resolved
    #   host-side by runtime/server.py; ignored in dense mode
    cp: int = 1,  # static: context-parallel degree. Each cp shard writes
    #   the chunk's fresh KV through ITS table plane (owned columns land in
    #   real local blocks, the rest in local trash) and computes partial
    #   attention stats over its local blocks; the layer combines partials
    #   across CP_AXIS (online-softmax merge) — the RING-PASS form of
    #   chunked prefill. Forces attn="xla" stats mode inside the op.
):
    """One bounded chunk of an admission prefill (r2 weak #4 / next-#4).

    Where ``serve_admit`` traverses the whole prompt in one parked-pipeline
    program — freezing every live stream for the full prefill — this program
    processes ``Sc`` tokens and returns, so the host can interleave decode
    cycles between chunks (``runtime/server.py`` drives the loop). The slot
    stays inactive (``done``) until ``serve_admit_finish`` arms it; the
    interleaved decode cycles between chunks leave the parked slot's state
    untouched (their per-entry write gating skips inactive slots), so each
    chunk resumes exactly where the previous one stopped.

    Paged mode attends the arena IN PLACE (flash-style chunked prefill —
    ROADMAP item 3): the chunk's fresh KV lands via ``write_chunk_kv`` —
    whole-block tiles through the rows' tables where the chunk is whole
    blocks, ``write_block_kv``'s rows otherwise (quantizing at insert on an
    int8/fp8 arena — no inter-chunk dequant→requant round trip) — and its
    queries attend every
    previously-written block through ``ops/paged_attention.paged_prefill``
    (scalar-prefetched block tables, online-softmax, causal masking by
    position — intra-chunk included), so the retired ``_gather_window``
    round trip (gather O(W) KV, recompute, scatter O(W) back — per chunk)
    never happens and per-chunk attention HBM traffic is bounded by the
    written frontier, not the row's whole mapped window.

    ``prefix_off`` is what makes the chunk RADIX-COMPOSABLE: with the
    matched prefix's blocks already resident (mapped read-only into the
    slot's tables), the first chunk seeds the prefix columns' key
    positions (``0..n0-1`` — matches are block-aligned and gap-free by
    construction) and every chunk writes/attends at absolute columns
    ``n0 + chunk_off + i``. The shared prefix blocks are never written —
    for a quantized arena that also keeps their codes+scales byte-stable
    under concurrent readers, the same argument as ``serve_admit``'s
    ``prefix_in_arena``.

    Returns ``(state, counts)``, ``counts`` a device array the host need
    not wait for (it reads it once a later fetch has shown the chunk done):
    a model with experts' ``moe_log_width`` counters, then the prefill
    kernel's walk over the chunk's layer calls — the cells it walked
    (``prefill_walk``, one walk for all layers, times a stage's layer
    slots, summed over the ring) and the ``rows x heads x query tiles x
    cells`` of the table's whole width; both 0 where no kernel ran.
    """
    fns = model_fns(
        cfg, tp_axis=TENSOR_AXIS if tp > 1 else None,
        cp_axis=CP_AXIS if cp > 1 else None,
    )
    Bs, Sc = tokens.shape
    ring = [(i, (i + 1) % num_stages) for i in range(num_stages)]
    quantized = is_kv_quantized(state.k.dtype)  # trace-time constant
    if prefix_off is None:
        prefix_off = jnp.zeros((), jnp.int32)

    @jax.named_scope("state")
    def body(stage_layers, layer_mask, head_params, state, tokens, positions,
             slot, chunk_off, reset, prefix_off):
        layers = jax.tree.map(lambda a: a[0], stage_layers)
        lmask = layer_mask[0]
        hd = local_view(head_params)
        sidx = jax.lax.axis_index(PIPE_AXIS)
        st = jax.tree.map(
            lambda spec, leaf: leaf[0] if _dev(spec) else leaf,
            state_specs(state, tp, cp, quantized, bool(block_size)), state,
        )
        row0 = slot * Bs
        col0 = prefix_off + chunk_off  # absolute cache column of the chunk
        # a model with experts: pad positions (sentinel) route nowhere; a
        # recurrent state: they do not advance it
        moe_live = (
            (positions != POS_SENTINEL)
            if cfg.num_experts or cfg.recurrent else None
        )
        p_rows = jax.lax.dynamic_slice_in_dim(st.kpos, row0, Bs, axis=0)
        W = p_rows.shape[1]
        scale_upd = {}
        walk, counts = None, jnp.zeros((2,), jnp.int32)
        if block_size:
            tbl = _slot_tables(st, row0, Bs)
            # first chunk: the resident prefix columns carry their real
            # positions (block-aligned radix matches are gap-free, so
            # position == column), everything past them the sentinel —
            # stale values in reallocated private blocks are masked out
            # (finite previous-occupant KV; the trash block is zero-gated
            # by the attention op, so no NaN channel)
            colidx = jnp.arange(W, dtype=jnp.int32)[None, :]
            kpos0 = jnp.where(colidx < prefix_off, colidx, POS_SENTINEL)
            p_rows = jnp.where(
                reset, jnp.broadcast_to(kpos0, p_rows.shape), p_rows
            )
            kv_pos = jax.lax.dynamic_update_slice(p_rows, positions, (0, col0))
            cols = jnp.broadcast_to(
                col0 + jnp.arange(Sc, dtype=jnp.int32)[None, :], (Bs, Sc)
            )
            if quantized:
                # reset the slot's PRIVATE blocks' running-absmax scales
                # on the first chunk: a previous occupant's (or a parked
                # interleave's) inflated scale would otherwise coarsen
                # every fresh entry this admission inserts — the shared
                # radix prefix blocks (and trash, whose scale is never
                # dequantized) keep theirs
                n_pfx = prefix_off // block_size
                bidx = jnp.arange(tbl.shape[1], dtype=jnp.int32)[None, :]
                priv = jnp.where(bidx >= n_pfx, tbl, 0)
                ks = jnp.where(
                    reset, st.k_scale.at[:, priv].set(0.0), st.k_scale
                )
                vs = jnp.where(
                    reset, st.v_scale.at[:, priv].set(0.0), st.v_scale
                )
            else:
                ks = vs = None
            if attn in ("kernel", "interpret") and cp == 1:
                # what the chunk's real queries have to walk, once for all
                # layers; ``nlive``, the blocks covering the written
                # frontier after this chunk, clamps it (sentinel masking
                # already excludes everything past it)
                nlive = jnp.broadcast_to(
                    (col0 + Sc + block_size - 1) // block_size, (Bs,)
                ).astype(jnp.int32)
                if fns.prefill_walks is not None:
                    # a walk per kind of attention, the window's with its
                    # lower bound; the counts hold each kind's layer calls
                    walk, counts = fns.prefill_walks(
                        cfg, tbl, positions, kv_pos, nlive, layers
                    )
                else:
                    walk = prefill_walk(
                        tbl, positions, kv_pos, nlive,
                        q_heads=cfg.num_attention_heads // tp,
                        kv_heads=st.k.shape[2],
                    )
                    counts = jnp.stack(
                        [walk.steps, walk.run_of.shape[0] - 1]
                    ).astype(jnp.int32) * lmask.shape[0]
            h = sp_embed(cfg, hd, tokens, positions)
            h, k_new, v_new, ks_new, vs_new, moe_stats = ring_chain_paged(
                fns, cfg, layers, lmask, sidx, ring, num_stages, h,
                *_arenas(st, row0, reset), tbl, cols, kv_pos, positions,
                backend=attn,
                k_scale=ks, v_scale=vs, prefill=True, walk=walk,
                moe_live=moe_live, close=close_tables(cfg, hd),
            )
            if quantized:
                scale_upd = {"k_scale": ks_new, "v_scale": vs_new}
            kpos_new = jax.lax.dynamic_update_slice_in_dim(
                st.kpos, kv_pos, row0, axis=0
            )
        else:
            k_rows = jax.lax.dynamic_slice_in_dim(st.k, row0, Bs, axis=1)
            v_rows = jax.lax.dynamic_slice_in_dim(st.v, row0, Bs, axis=1)
            zero = jnp.zeros_like(k_rows)
            sent = jnp.full_like(p_rows, POS_SENTINEL)
            cache = KVCache(
                k=jnp.where(reset, zero, k_rows),
                v=jnp.where(reset, zero, v_rows),
                pos=jnp.where(reset, sent, p_rows),
                length=chunk_off,
            )
            h = sp_embed(cfg, hd, tokens, positions)
            h, cache, moe_stats = ring_chain(
                fns, cfg, layers, lmask, sidx, ring, num_stages, h, cache,
                positions, moe_live=moe_live, close=close_tables(cfg, hd),
            )
            k_new = jax.lax.dynamic_update_slice_in_dim(
                st.k, cache.k, row0, axis=1
            )
            v_new = jax.lax.dynamic_update_slice_in_dim(
                st.v, cache.v, row0, axis=1
            )
            kpos_new = jax.lax.dynamic_update_slice_in_dim(
                st.kpos, cache.pos, row0, axis=0
            )
        write_off = st.write_off.at[slot].set(col0 + Sc)
        # accumulate the prompt into the replicated out buffer chunk by chunk
        # (first chunk clears the previous occupant's rows). Columns stay
        # SUFFIX-relative (chunk_off) like the one-shot radix admission: a
        # resident prefix's ids live in the tree, not in ``out``.
        out_rows = jax.lax.dynamic_slice_in_dim(st.out, row0, Bs, axis=0)
        out_rows = jnp.where(reset, jnp.zeros_like(out_rows), out_rows)
        out = jax.lax.dynamic_update_slice_in_dim(st.out, out_rows, row0, axis=0)
        out = jax.lax.dynamic_update_slice(out, tokens, (row0, chunk_off))

        new = st._replace(
            **_arena_upd(st, k_new, v_new), kpos=kpos_new,
            write_off=write_off, out=out, **scale_upd,
        )
        new = jax.tree.map(
            lambda spec, leaf: leaf[None] if _dev(spec) else leaf,
            state_specs(state, tp, cp, quantized, bool(block_size)), new,
        )
        counts = jax.lax.psum(counts, PIPE_AXIS)
        if cfg.num_experts:
            counts = jnp.concatenate(
                [_moe_counts(moe_stats, moe_live, sidx, num_stages), counts]
            )
        return new, counts

    specs = state_specs(
        state, tp, cp, quantized,
        bool(block_size),
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            stage_layer_specs(cfg, tp, stage_layers), P(PIPE_AXIS),
            head_specs(head_params), specs,
            P(), P(), P(), P(), P(), P(),
        ),
        out_specs=(specs, P()),
        check_vma=False,
    )(stage_layers, layer_masks, head_params, state, tokens, positions,
      slot, chunk_off, reset, jnp.asarray(prefix_off, jnp.int32))


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "num_stages", "tp", "cp", "block_size"),
    donate_argnums=(3,),  # see serve_admit
)
def serve_admit_finish(
    cfg: ModelConfig,
    mesh: Mesh,
    head_params: Any,  # vocab-sharded
    state: ServeState,
    last_tok: jnp.ndarray,    # [Bs] each row's final real prompt token id
    prompt_len: jnp.ndarray,  # [Bs]
    row_valid: jnp.ndarray,   # [Bs] bool
    slot: jnp.ndarray,        # scalar int32
    max_new: jnp.ndarray,     # [Bs]
    seeds: jnp.ndarray,       # [Bs] int32
    temperature: jnp.ndarray,  # [Bs] f32
    top_k: jnp.ndarray,       # [Bs] int32 (0 → off)
    top_p: jnp.ndarray,       # [Bs] f32 (1.0 → off)
    num_stages: int,
    tp: int = 1,
    key_override: Any = None,  # ([Bs, 2] uint32, [Bs] bool) — see below
    cp: int = 1,  # static: context-parallel degree (spec plumbing only —
    #   this program touches no KV; see serve_prefill_chunk)
    block_size: int = 0,  # static: paged-KV block size (0 = dense state) —
    #   spec plumbing too: under tp the paged arena shards another dim
):
    """Arm a chunk-prefilled slot: park each row's final prompt token in the
    injection path at position ``prompt_len - 1``. The slot's first
    interleaved microstep processes it through the ring (its KV was
    deliberately sentinel-masked during prefill, so the cache sees it exactly
    once), and the normal completion path samples the first generated token —
    the chunked admission needs no separate logit extraction.

    Key-chain note: the stored per-row key is UNSPLIT (``key(seed)``); the
    first commit in ``serve_chunk`` performs the first split — the same
    chain the monolith walks, so seeded sampling stays token-exact. With
    ``key_override``, masked rows store the CARRIED chain instead (a
    migrated request resuming mid-stream: ``t`` splits of ``key(seed)``) —
    the next commit's split then yields draw ``t+1``, exactly where the
    source replica's chain stood."""
    Bs = last_tok.shape[0]
    quantized = is_kv_quantized(state.k.dtype)  # trace-time constant

    @jax.named_scope("state")
    def body(head_params, state, last_tok, prompt_len, row_valid, slot,
             max_new, seeds, temperature, top_k, top_p, key_override):
        hd = local_view(head_params)
        sidx = jax.lax.axis_index(PIPE_AXIS)
        st = jax.tree.map(
            lambda spec, leaf: leaf[0] if _dev(spec) else leaf,
            state_specs(state, tp, cp, quantized, bool(block_size)), state,
        )
        row0 = slot * Bs

        pos_slots = jax.lax.dynamic_update_slice_in_dim(
            st.pos_slots, prompt_len - 1, row0, axis=0
        )
        lengths = jax.lax.dynamic_update_slice_in_dim(
            st.lengths, jnp.where(row_valid, prompt_len, 0), row0, axis=0
        )
        budget = jax.lax.dynamic_update_slice_in_dim(
            st.budget, jnp.where(row_valid, prompt_len + max_new, 0), row0,
            axis=0,
        )
        done = jax.lax.dynamic_update_slice_in_dim(
            st.done, ~row_valid | (max_new < 1), row0, axis=0
        )
        inj = sp_embed(cfg, hd, last_tok[:, None], (prompt_len - 1)[:, None])
        inject = jax.lax.dynamic_update_slice_in_dim(
            st.inject, inj.astype(st.inject.dtype), row0, axis=0
        )
        inject_pending = jax.lax.dynamic_update_slice_in_dim(
            st.inject_pending, row_valid & (max_new >= 1), row0, axis=0
        )
        row_keys = jax.vmap(
            lambda s: jax.random.key_data(jax.random.key(s))
        )(seeds)
        if key_override is not None:
            ko, ko_mask = key_override
            row_keys = jnp.where(ko_mask[:, None], ko, row_keys)
        rng = jax.lax.dynamic_update_slice_in_dim(
            st.rng, row_keys, row0, axis=0
        )
        temp = jax.lax.dynamic_update_slice_in_dim(
            st.temp, jnp.where(row_valid, temperature, 0.0), row0, axis=0
        )
        topk = jax.lax.dynamic_update_slice_in_dim(
            st.topk, jnp.where(row_valid, top_k, 0), row0, axis=0
        )
        topp = jax.lax.dynamic_update_slice_in_dim(
            st.topp, jnp.where(row_valid, top_p, 1.0), row0, axis=0
        )
        # same stale-parked-block defense as serve_admit
        next_served = jnp.mod(st.m - sidx, num_stages)
        h_valid = jnp.where(next_served == slot, False, st.h_valid)

        new = st._replace(
            pos_slots=pos_slots, lengths=lengths, budget=budget, done=done,
            inject=inject, inject_pending=inject_pending, rng=rng, temp=temp,
            topk=topk, topp=topp, h_valid=h_valid,
        )
        return jax.tree.map(
            lambda spec, leaf: leaf[None] if _dev(spec) else leaf,
            state_specs(state, tp, cp, quantized, bool(block_size)), new,
        )

    specs = state_specs(
        state, tp, cp, quantized,
        bool(block_size),
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            head_specs(head_params), specs,
            P(), P(), P(), P(), P(), P(), P(), P(), P(),
            P(),  # key_override: replicated (leafless no-op when None)
        ),
        out_specs=specs,
        check_vma=False,
    )(head_params, state, last_tok, prompt_len, row_valid, slot, max_new,
      seeds, temperature, top_k, top_p, key_override)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "mesh", "num_stages", "n_micro", "sampling", "filtering", "tp",
        "block_size", "attn", "cp",
    ),
    donate_argnums=(5,),  # see serve_admit
)
def serve_chunk(
    cfg: ModelConfig,
    mesh: Mesh,
    stage_layers: Any,
    layer_masks: jnp.ndarray,
    head_params: Any,
    state: ServeState,
    num_stages: int,
    n_micro: int,
    sampling: bool = False,
    filtering: bool = True,
    tp: int = 1,
    block_size: int = 0,  # static: paged-KV block size (0 = dense state)
    attn: str = "xla",  # static: paged attention backend for the decode
    #   microsteps — "xla" (exact gather inside the op, the CPU/tier-1
    #   fallback), "kernel" (Pallas: streams only each row's mapped
    #   blocks) or "interpret" (the kernel emulated, CI on CPU). Resolved
    #   host-side by runtime/server.py; ignored in dense mode
    cp: int = 1,  # static: context-parallel degree — each shard attends
    #   its LOCAL arena blocks (unowned columns are trash-mapped and
    #   zero-gated) emitting online-softmax partials (acc, m, l) that the
    #   layer combines across CP_AXIS; fresh decode KV scatters through
    #   each shard's own table plane so exactly the owner keeps it.
):
    """Run ``n_micro`` interleaved microsteps on the live state. Returns
    ``(state, log)`` where ``log`` is ``[n_micro, Bs]`` int32 — the token
    each completing row committed that microstep, or -1. The log is the
    host's ONLY per-chunk read: at microstep ``m`` the completing slot is
    ``(m - (S-1)) mod S`` (the host mirrors ``m``), so lengths/done are
    reconstructed host-side from a few hundred bytes instead of fetching the
    bookkeeping arrays — each fetch is a blocking device→host sync, and
    r3's step paid three of them. A model with experts appends
    ``moe_log_width`` columns to each log row: that microstep's tokens per
    expert and distinct experts read per layer, from LIVE rows only.

    ``sampling`` statically selects the token-selection path: False compiles
    pure greedy (no per-row key splits, no full-vocab noise regeneration —
    measured ~20% serve throughput on v5e at 3B); True compiles the per-row
    seeded sampler. The host flips it the first time a temperature>0 request
    is admitted (one extra compile, then cached). ``filtering`` likewise
    compiles the top-k/top-p machinery in only when some request uses it.

    MULTI-DISPATCH CONTRACT (what the step loop's ``pipeline_depth`` rests
    on): ``state`` is donated and the chunk is fully self-contained —
    everything the next chunk needs is in the returned ``ServeState``
    handle, nothing depends on the host having read ``log``. Chunk k+1 may
    therefore be dispatched off chunk k's returned handle BEFORE k's log is
    fetched, to any depth: the dispatches serialize on the device as one
    deterministic state chain, so the committed tokens are identical
    however many chunks later the host fetches each log. The
    host block-table push (``_flush_tables``) needs only the PLANNED
    mirror deltas, never fetched tokens, so it keeps its place before
    each dispatch."""
    fns = model_fns(
        cfg, tp_axis=TENSOR_AXIS if tp > 1 else None,
        cp_axis=CP_AXIS if cp > 1 else None,
    )
    ring = [(i, (i + 1) % num_stages) for i in range(num_stages)]
    last = num_stages - 1
    M = state.out.shape[0]
    Bs = M // num_stages
    quantized = is_kv_quantized(state.k.dtype)  # trace-time constant

    @jax.named_scope("state")
    def body(stage_layers, layer_mask, head_params, state):
        layers = jax.tree.map(lambda a: a[0], stage_layers)
        lmask = layer_mask[0]
        hd = local_view(head_params)
        close = close_tables(cfg, hd)  # a looped stack's; else None
        sidx = jax.lax.axis_index(PIPE_AXIS)
        st = jax.tree.map(
            lambda spec, leaf: leaf[0] if _dev(spec) else leaf,
            state_specs(state, tp, cp, quantized, bool(block_size)), state,
        )

        def micro(_, s: ServeState) -> ServeState:
            m = s.m
            r = jnp.mod(m - sidx, num_stages)
            row0 = r * Bs
            served_rows = row0 + jnp.arange(Bs, dtype=jnp.int32)

            pos_rows = jax.lax.dynamic_slice_in_dim(s.pos_slots, row0, Bs)
            off_r = jax.lax.dynamic_index_in_dim(
                s.write_off, r, keepdims=False
            )
            done_served = jax.lax.dynamic_slice_in_dim(s.done, row0, Bs)
            pend_rows = jax.lax.dynamic_slice_in_dim(
                s.inject_pending, row0, Bs
            )
            inj_rows = jax.lax.dynamic_slice_in_dim(s.inject, row0, Bs, axis=0)

            # stage 0 consumes a pending injection for this slot; the block
            # becomes valid data. (Whole-slot admission → pend uniform.)
            injecting = (sidx == 0) & jnp.any(pend_rows)
            h_in = jnp.where(injecting, inj_rows.astype(s.h.dtype), s.h)
            valid_now = injecting | s.h_valid
            slot_active = ~jnp.all(done_served)
            advance = valid_now & slot_active
            # a model with experts: the slot's dead rows route nowhere; a
            # recurrent state: they are neither read nor written
            moe_live = (
                (advance & ~done_served)[:, None]
                if cfg.num_experts or cfg.recurrent else None
            )

            # Unconditional commit: a garbage write lands at an offset the
            # next real serve overwrites (offsets only advance on `advance`).
            # Paged mode keeps this safe two ways: a LIVE row's write offset
            # is always inside its own mapped blocks (the host covers the
            # full prompt+budget at admission), and a FREED row's table was
            # remapped to the trash block before its blocks could be
            # reallocated — garbage from a done slot lands in the sink.
            def upd(big, small, axis):
                return jax.lax.dynamic_update_slice_in_dim(
                    big, small, row0, axis=axis
                )

            if block_size:
                # Paged decode: NO materialized window. The step's single
                # fresh KV entry per row scatters into the block the table
                # owns at column off_r (write_block_kv inside stage_paged)
                # and attention runs straight off the arena — the Pallas
                # kernel streams only the slot's mapped blocks; the XLA
                # backend gathers inside the op (exact fallback). Key
                # positions are recorded at the write column exactly as
                # scan_layers does for the dense window. The write itself
                # is gated by ``advance`` (write_block_kv's per-entry
                # valid — cheap, unlike the dense path's whole-cache
                # where): a PARKED slot (mid-chunked-admission, or a dead
                # block in flight) must not scatter garbage into its live
                # mapped blocks — the arena-native prefill path no longer
                # re-scatters the window between chunks, and on a
                # quantized arena a garbage write would permanently
                # inflate the touched block's running-absmax scale.
                tbl_r = _slot_tables(s, row0, Bs)
                kpos_rows = jax.lax.dynamic_slice_in_dim(
                    s.kpos, row0, Bs, axis=0
                )
                kv_pos = jax.lax.dynamic_update_slice(
                    kpos_rows, pos_rows[:, None], (0, off_r)
                )
                h_new, k_st, v_st, ks_st, vs_st, moe_stats = fns.stage_paged(
                    cfg, layers, h_in, *_arenas(s, row0), tbl_r,
                    jnp.broadcast_to(off_r, (Bs, 1)), kv_pos,
                    pos_rows[:, None], lmask, write_valid=advance,
                    backend=attn,
                    k_scale=s.k_scale if quantized else None,
                    v_scale=s.v_scale if quantized else None,
                    moe_live=moe_live, close=close,
                )
                scale_upd = (
                    {"k_scale": ks_st, "v_scale": vs_st} if quantized
                    else {}
                )
                kpos_st = upd(
                    s.kpos, jnp.where(advance, kv_pos, kpos_rows), 0
                )
            else:
                cache_r = KVCache(
                    k=jax.lax.dynamic_slice_in_dim(s.k, row0, Bs, axis=1),
                    v=jax.lax.dynamic_slice_in_dim(s.v, row0, Bs, axis=1),
                    pos=jax.lax.dynamic_slice_in_dim(s.kpos, row0, Bs, axis=0),
                    length=off_r,
                )
                h_new, cache_r_new, moe_stats = fns.stage(
                    cfg, layers, h_in, cache_r, pos_rows[:, None], lmask,
                    moe_live=moe_live, close=close,
                )
                k_st = upd(s.k, cache_r_new.k, 1)
                v_st = upd(s.v, cache_r_new.v, 1)
                kpos_st = upd(s.kpos, cache_r_new.pos, 0)
                scale_upd = {}
            write_off = jnp.where(
                advance, s.write_off.at[r].add(1), s.write_off
            )
            pos_slots = jnp.where(
                advance, s.pos_slots.at[served_rows].add(1), s.pos_slots
            )

            # ---- completion for the slot the LAST stage served ----
            r_done = jnp.mod(m - last, num_stages)
            rowd = r_done * Bs
            done_rows = jax.lax.dynamic_slice_in_dim(s.done, rowd, Bs)
            row_ids = rowd + jnp.arange(Bs, dtype=jnp.int32)

            h_done = psum_from(h_new[:, 0], last)  # [Bs, H]
            valid_done = (
                psum_from(valid_now.astype(jnp.int32), last) > 0
            )
            if sampling:
                # Advance each completing row's key chain exactly when it
                # commits a token — one split per generated token, mirroring
                # the monolith's decode loop, so seeded draws stay
                # token-exact.
                rng_rows = jax.lax.dynamic_slice_in_dim(
                    s.rng, rowd, Bs, axis=0
                )
                new_keys, subs = key_chain_split(rng_rows)
                temp_rows = jax.lax.dynamic_slice_in_dim(s.temp, rowd, Bs)
                topk_rows = jax.lax.dynamic_slice_in_dim(s.topk, rowd, Bs)
                topp_rows = jax.lax.dynamic_slice_in_dim(s.topp, rowd, Bs)
                nxt = sp_sample_rows(
                    cfg, hd, h_done, subs, temp_rows, topk_rows, topp_rows,
                    num_stages, filtering=filtering,
                )
            else:
                nxt = sp_next_token(cfg, hd, h_done)
            nxt = jnp.where(done_rows, 0, nxt)

            len_rows = jax.lax.dynamic_slice_in_dim(s.lengths, rowd, Bs)
            bud_rows = jax.lax.dynamic_slice_in_dim(s.budget, rowd, Bs)
            commit = valid_done & ~done_rows & (len_rows < bud_rows)
            wpos = len_rows
            cur = s.out[row_ids, wpos]
            out = s.out.at[row_ids, wpos].set(jnp.where(commit, nxt, cur))
            lengths = s.lengths.at[row_ids].add(commit.astype(jnp.int32))
            if sampling:
                rng = s.rng.at[row_ids].set(
                    jnp.where(commit[:, None], new_keys, rng_rows)
                )
            else:
                rng = s.rng
            new_len = len_rows + commit.astype(jnp.int32)
            done = s.done.at[row_ids].set(
                done_rows
                | (commit & (_is_stop(cfg, nxt) | (new_len >= bud_rows)))
            )

            # re-embed fresh tokens; last stage sends them around the ring
            h_embed = sp_embed(cfg, hd, nxt[:, None], wpos[:, None])
            h_send = jnp.where(sidx == last, h_embed.astype(s.h.dtype), h_new)
            with jax.named_scope("ring_hop"):
                h_out = jax.lax.ppermute(h_send, PIPE_AXIS, ring)
            # Validity gating uses POST-update done state: the sent block
            # belongs to this device's served slot r (on the last stage
            # r == r_done), and a block whose slot just finished (or was
            # already finished) is dead and must travel invalid — otherwise a
            # slot re-admitted at a chunk boundary within one ring cycle of
            # finishing would decode from the previous request's leftover
            # block.
            done_sent = jax.lax.dynamic_slice_in_dim(done, row0, Bs)
            sent_valid = valid_now & ~jnp.all(done_sent)
            with jax.named_scope("ring_hop"):
                h_valid_out = (
                    jax.lax.ppermute(
                        sent_valid.astype(jnp.int32), PIPE_AXIS, ring
                    )
                    > 0
                )

            # stage 0 consumed its slot's injection this microstep — clear it
            # (identical computation on every device: stage 0's slot is m mod S)
            clear0 = jnp.mod(m, num_stages) * Bs + jnp.arange(
                Bs, dtype=jnp.int32
            )
            inject_pending = s.inject_pending.at[clear0].set(False)

            log_i = jnp.where(commit, nxt, -1)  # [Bs] this microstep's commits
            if cfg.num_experts:
                log_i = jnp.concatenate([
                    log_i,
                    _moe_counts(moe_stats, moe_live, sidx, num_stages),
                ])
            if cfg.passes > 1:
                # a looped model: the pass each committed token's logits
                # were read from (one stage: this device served the rows)
                log_i = jnp.concatenate(
                    [log_i, jnp.where(commit, moe_stats[:, 0], -1)]
                )

            new_s = s._replace(
                **_arena_upd(s, k_st, v_st), kpos=kpos_st, h=h_out,
                h_valid=h_valid_out,
                pos_slots=pos_slots, write_off=write_off, out=out,
                lengths=lengths, done=done, inject_pending=inject_pending,
                rng=rng, m=m + 1, **scale_upd,
            )
            return new_s, log_i

        def micro_carry(i, carry):
            s, log = carry
            s, log_i = micro(i, s)
            return s, jax.lax.dynamic_update_slice_in_dim(
                log, log_i[None], i, axis=0
            )

        log0 = jnp.full(
            (n_micro,
             Bs + moe_log_width(cfg, num_stages, layer_mask.shape[1])
             + pass_log_width(cfg, Bs)),
            -1, jnp.int32,
        )
        st, log = jax.lax.fori_loop(0, n_micro, micro_carry, (st, log0))
        st = jax.tree.map(
            lambda spec, leaf: leaf[None] if _dev(spec) else leaf,
            state_specs(state, tp, cp, quantized, bool(block_size)), st,
        )
        return st, log

    specs = state_specs(
        state, tp, cp, quantized,
        bool(block_size),
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            stage_layer_specs(cfg, tp, stage_layers), P(PIPE_AXIS),
            head_specs(head_params), specs,
        ),
        out_specs=(specs, P()),
        check_vma=False,
    )(stage_layers, layer_masks, head_params, state)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "mesh", "num_stages", "K", "sampling", "filtering", "tp",
        "block_size", "attn", "cp",
    ),
    donate_argnums=(5,),  # see serve_admit
)
def serve_verify(
    cfg: ModelConfig,
    mesh: Mesh,
    stage_layers: Any,
    layer_masks: jnp.ndarray,
    head_params: Any,  # vocab-sharded
    state: ServeState,
    draft: jnp.ndarray,      # [Bs, K] right-padded n-gram draft ids
    draft_len: jnp.ndarray,  # [Bs] valid draft tokens per row
    slot: jnp.ndarray,       # scalar int32
    cache_delta: jnp.ndarray,  # [Bs] per-row constant (cache slot − token
    #   position), fixed at admission: bucket padding [+ padded-prefix
    #   columns − real prefix length]. The canonical slot of the pending
    #   token's KV is pos + delta — per-row because speculative acceptance
    #   diverges row from row, unlike the per-slot write_off microsteps use
    num_stages: int,
    K: int,
    sampling: bool = False,
    filtering: bool = True,
    tp: int = 1,
    block_size: int = 0,  # static: paged-KV block size (0 = dense state)
    attn: str = "xla",  # static: paged attention backend (see serve_chunk)
    cp: int = 1,  # static: context-parallel degree — cp > 1 is rejected
    #   (speculation is gated off under cp by the server; the guard makes
    #   the program's contract explicit if that gate ever regresses)
):
    """Speculative verify for one slot: ONE parked-pipeline ring traversal
    over the K+1 draft positions per row — a tiny prefill (the ``serve_admit``
    machinery) that also reads logits at EVERY position — committing a
    VARIABLE number of tokens per row. Returns ``(state, log)`` with ``log``
    ``[Bs, K+1]`` int32: the committed run per row, -1 padded — the host's
    only read (it feeds the next draft and replays the mirrors exactly like
    a chunk log).

    Greedy rows accept by exact leading match against the model's argmax
    choices, so a speculative server is token-identical to a chunked one —
    drafts only set how many tokens commit per weight pass. Sampled rows
    (temperature > 0) use rejection acceptance against the point-mass draft:
    accept d with probability p(d) under the row's filtered target, else
    resample from the target with d masked — the committed stream keeps the
    target distribution. The sampled path gathers the full [rows*(K+1), V]
    distribution on every stage (like ``sp_sample_rows``'s filtering path);
    greedy stays shard-local.

    KV rollback — dense: the traversal writes its K+1 entries into the
    SCRATCH columns at the top of the cache (the server allocates ``K+1``
    columns over its usable capacity); the accepted prefix is then
    compacted to each row's canonical columns at ``cache_off`` and the
    scratch key positions rewound to the sentinel — rejected positions are
    logically discarded (never attended) without copying live state.
    Paged: no scratch at all — entries scatter straight into each row's
    canonical columns during the traversal (``write_block_kv`` handles
    per-row columns where the dense path's shared write offset cannot;
    overflow past the mapped budget is absorbed by the trash block) and
    rollback is purely the position rewind. ``pos_slots``/``lengths``/
    ``done``/``out``/``rng`` update exactly as if the committed tokens had
    arrived one microstep at a time, so snapshots taken between steps stay
    restore-compatible."""
    # the shard-agnostic verify math (leading-match acceptance, rejection
    # commit assembly, EOS/budget capping) lives in runtime/spec.py — ONE
    # definition shared with the monolith verify, so the two decode paths
    # cannot silently diverge (lazy import: parallel must not pull the
    # runtime package at module load)
    from ..runtime.spec import _leading_true_count, cap_commits, rejection_commit

    if cp > 1:
        raise NotImplementedError(
            "serve_verify does not support context-parallel serving (cp > "
            "1): speculative decode commits a VARIABLE number of tokens per "
            "row, and the cross-shard combine for its K+1-position "
            "traversal is not wired — the server gates speculate off under "
            "cp (ROADMAP: cp-aware speculation)"
        )
    fns = model_fns(cfg, tp_axis=TENSOR_AXIS if tp > 1 else None)
    Bs = draft.shape[0]
    ring = [(i, (i + 1) % num_stages) for i in range(num_stages)]
    C_total = state.out.shape[1]
    scratch = C_total - (K + 1)
    quantized = is_kv_quantized(state.k.dtype)  # trace-time constant

    @jax.named_scope("state")
    def body(stage_layers, layer_mask, head_params, state, draft, draft_len,
             slot, cache_delta):
        layers = jax.tree.map(lambda a: a[0], stage_layers)
        lmask = layer_mask[0]
        hd = local_view(head_params)
        sidx = jax.lax.axis_index(PIPE_AXIS)
        st = jax.tree.map(
            lambda spec, leaf: leaf[0] if _dev(spec) else leaf,
            state_specs(state, tp, paged=bool(block_size)), state,
        )
        row0 = slot * Bs
        rows = row0 + jnp.arange(Bs, dtype=jnp.int32)
        iota = jnp.arange(K + 1, dtype=jnp.int32)

        pos_rows = jax.lax.dynamic_slice_in_dim(st.pos_slots, row0, Bs)
        cache_off = pos_rows + cache_delta  # pending token's canonical slot
        done_rows = jax.lax.dynamic_slice_in_dim(st.done, row0, Bs)
        len_rows = jax.lax.dynamic_slice_in_dim(st.lengths, row0, Bs)
        bud_rows = jax.lax.dynamic_slice_in_dim(st.budget, row0, Bs)
        out_rows = jax.lax.dynamic_slice_in_dim(st.out, row0, Bs, axis=0)
        # pending token = the last committed one (its KV is not yet written;
        # out column == prefix-inclusive sequence index == lengths - 1)
        tok_pend = jnp.take_along_axis(
            out_rows, jnp.clip(len_rows - 1, 0, C_total - 1)[:, None], axis=1
        )[:, 0]

        toks_in = jnp.concatenate([tok_pend[:, None], draft], axis=1)
        positions = jnp.where(
            done_rows[:, None], POS_SENTINEL,
            pos_rows[:, None] + iota[None, :],
        )
        h = sp_embed(cfg, hd, toks_in, positions)
        if block_size:
            # Paged verify: NO materialized window and NO scratch columns —
            # the K+1 in-flight entries scatter DIRECTLY into each row's
            # canonical columns ``cache_off + i`` during the traversal
            # (per-row columns are fine for write_block_kv's scatter, where
            # the dense path's shared-offset dynamic_update_slice forced
            # the scratch/compaction dance). Entries past a row's mapped
            # budget land in the trash block, which absorbs them: only
            # never-committable positions (cap_commits bounds the run by
            # the remaining budget) can overflow, and the attention of any
            # committable query never reads them. The traversal's queries
            # see the in-flight entries through ``kv_pos`` — a TEMPORARY
            # position window; the state's kpos update below keeps only
            # the accepted prefix (rollback = position rewind, no copy).
            tbl = _slot_tables(st, row0, Bs)
            cols = cache_off[:, None] + iota[None, :]  # [Bs, K+1]
            rowsel = jnp.arange(Bs, dtype=jnp.int32)[:, None]
            colsel = jnp.clip(cols, 0, C_total - 1)
            kpos_rows = jax.lax.dynamic_slice_in_dim(
                st.kpos, row0, Bs, axis=0
            )
            kv_pos = kpos_rows.at[rowsel, colsel].set(positions)
            h, k_full, v_full, ks_full, vs_full, _ = ring_chain_paged(
                fns, cfg, layers, lmask, sidx, ring, num_stages, h,
                st.k, st.v, tbl, cols, kv_pos, positions, backend=attn,
                k_scale=st.k_scale if quantized else None,
                v_scale=st.v_scale if quantized else None,
            )
            scale_upd = (
                {"k_scale": ks_full, "v_scale": vs_full} if quantized
                else {}
            )
        else:
            scale_upd = {}
            cache = KVCache(
                k=jax.lax.dynamic_slice_in_dim(st.k, row0, Bs, axis=1),
                v=jax.lax.dynamic_slice_in_dim(st.v, row0, Bs, axis=1),
                pos=jax.lax.dynamic_slice_in_dim(st.kpos, row0, Bs, axis=0),
                length=jnp.asarray(scratch, jnp.int32),
            )
            h, cache, _ = ring_chain(
                fns, cfg, layers, lmask, sidx, ring, num_stages, h, cache,
                positions,
            )
        # final-depth hidden for ALL K+1 positions, replicated from stage 0
        # (the block lands back on its origin after the full ring trip)
        hf = psum_from(h.reshape(Bs * (K + 1), -1), 0)

        valid_draft = iota[None, :K] < draft_len[:, None]  # [Bs, K]
        choices = sp_next_token(cfg, hd, hf).reshape(Bs, K + 1)
        match = (choices[:, :K] == draft) & valid_draft
        a = _leading_true_count(match)
        commit = choices

        if sampling:
            temp_rows = jax.lax.dynamic_slice_in_dim(st.temp, row0, Bs)
            topk_rows = jax.lax.dynamic_slice_in_dim(st.topk, row0, Bs)
            topp_rows = jax.lax.dynamic_slice_in_dim(st.topp, row0, Bs)
            rng_rows = jax.lax.dynamic_slice_in_dim(st.rng, row0, Bs, axis=0)
            new_keys, subs = key_chain_split(rng_rows)
            logits_loc, _lo = _local_logits(cfg, hd, hf)  # [Bs*(K+1), Vs]
            allv = jax.lax.all_gather(logits_loc, PIPE_AXIS)  # [S, N, Vs]
            full = jnp.transpose(allv, (1, 0, 2)).reshape(allv.shape[1], -1)
            Vp = full.shape[-1]
            full = full.reshape(Bs, K + 1, Vp)
            safe_t = jnp.where(temp_rows > 0, temp_rows, 1.0)
            scaled = full / safe_t[:, None, None]
            if filtering:
                from ..ops.sampling import top_p_threshold

                desc = -jnp.sort(-scaled, axis=-1)  # [Bs, K+1, Vp]
                k_idx = jnp.clip(topk_rows - 1, 0, Vp - 1)
                kth = jnp.take_along_axis(
                    desc, k_idx[:, None, None], axis=-1
                )
                kth = jnp.where(
                    (topk_rows > 0)[:, None, None], kth, -jnp.inf
                )
                desc_k = jnp.where(desc < kth, -jnp.inf, desc)
                pth = top_p_threshold(
                    desc_k.reshape(Bs * (K + 1), Vp),
                    jnp.repeat(topp_rows, K + 1),
                    presorted=True,
                ).reshape(Bs, K + 1, 1)
                pth = jnp.where(
                    (topp_rows < 1.0)[:, None, None], pth, -jnp.inf
                )
                scaled = jnp.where(
                    scaled < jnp.maximum(kth, pth), -jnp.inf, scaled
                )
            # per-(row, position) draws off the row chain: one chain split
            # per verify step (replicated keys -> identical on every stage)
            def pos_draws(kd):
                ku, kg = jax.random.split(jax.random.wrap_key_data(kd))
                u = jax.random.uniform(ku, (K,))
                g = jax.random.gumbel(kg, (K + 1, Vp), jnp.float32)
                return u, g

            u, g = jax.vmap(pos_draws)(subs)
            a_s, commit_s = rejection_commit(scaled, draft, valid_draft, u, g)
            is_samp = temp_rows > 0
            a = jnp.where(is_samp, a_s, a)
            commit = jnp.where(is_samp[:, None], commit_s, commit)

        # ---- cap the run: EOS inside it, per-row budget, done rows ----
        c, log, eos_hit = cap_commits(
            cfg, commit, a, bud_rows - len_rows, done_rows
        )
        new_len = len_rows + c
        new_done = done_rows | eos_hit | ((c > 0) & (new_len >= bud_rows))

        # ---- out: the committed run lands at columns len .. len+c-1 ----
        colidx = jnp.arange(C_total, dtype=jnp.int32)[None, :]
        rel = colidx - len_rows[:, None]
        in_run = (rel >= 0) & (rel < c[:, None])
        vals = jnp.take_along_axis(commit, jnp.clip(rel, 0, K), axis=1)
        out_rows = jnp.where(in_run, vals, out_rows)

        # ---- KV rollback (see docstring) ----
        row_pos = jnp.where(
            iota[None, :] < c[:, None], pos_rows[:, None] + iota[None, :],
            POS_SENTINEL,
        ).astype(jnp.int32)
        if block_size:
            # The traversal already wrote every entry at its canonical
            # column (k_full/v_full above); rollback is purely the
            # position rewind — accepted entries get their real positions,
            # rejected ones the sentinel (their stale values sit invisible
            # until the row's decode genuinely reaches that column and
            # overwrites them, exactly like the dense compaction's
            # unconditional K+1-entry copy).
            pos_slot = kpos_rows.at[rowsel, colsel].set(row_pos)
        else:
            # Dense compaction: the traversal wrote the K+1 entries into
            # the SCRATCH columns at the top of the window (the shared
            # scalar write offset cannot express per-row columns); copy
            # them to each row's canonical columns and rewind scratch.
            chunk_k = jax.lax.dynamic_slice_in_dim(
                cache.k, scratch, K + 1, axis=2
            )
            chunk_v = jax.lax.dynamic_slice_in_dim(
                cache.v, scratch, K + 1, axis=2
            )

            def compact(row_kv, row_chunk, start):
                return jax.lax.dynamic_update_slice(
                    row_kv, row_chunk, (0, start, 0, 0)
                )

            k_slot = jax.vmap(compact, in_axes=(1, 1, 0), out_axes=1)(
                cache.k, chunk_k, cache_off
            )
            v_slot = jax.vmap(compact, in_axes=(1, 1, 0), out_axes=1)(
                cache.v, chunk_v, cache_off
            )
            pos_slot = jax.vmap(
                lambda p_row, vals_row, start: jax.lax.dynamic_update_slice(
                    p_row, vals_row, (start,)
                )
            )(cache.pos, row_pos, cache_off)
            pos_slot = jax.lax.dynamic_update_slice(
                pos_slot,
                jnp.full((Bs, K + 1), POS_SENTINEL, jnp.int32),
                (0, scratch),
            )
            k_full = jax.lax.dynamic_update_slice_in_dim(
                st.k, k_slot, row0, axis=1
            )
            v_full = jax.lax.dynamic_update_slice_in_dim(
                st.v, v_slot, row0, axis=1
            )

        if sampling:
            rng_new = jnp.where((c > 0)[:, None], new_keys, rng_rows)
        inject_pending = st.inject_pending.at[rows].set(False)
        new = st._replace(
            k=k_full,
            v=v_full,
            **scale_upd,
            kpos=jax.lax.dynamic_update_slice_in_dim(
                st.kpos, pos_slot, row0, axis=0
            ),
            pos_slots=jax.lax.dynamic_update_slice_in_dim(
                st.pos_slots, pos_rows + c, row0, axis=0
            ),
            out=jax.lax.dynamic_update_slice_in_dim(
                st.out, out_rows, row0, axis=0
            ),
            lengths=jax.lax.dynamic_update_slice_in_dim(
                st.lengths, new_len, row0, axis=0
            ),
            done=jax.lax.dynamic_update_slice_in_dim(
                st.done, new_done, row0, axis=0
            ),
            inject_pending=inject_pending,
            rng=(
                jax.lax.dynamic_update_slice_in_dim(
                    st.rng, rng_new, row0, axis=0
                )
                if sampling else st.rng
            ),
        )
        new = jax.tree.map(
            lambda spec, leaf: leaf[None] if _dev(spec) else leaf,
            state_specs(state, tp, paged=bool(block_size)), new,
        )
        return new, log

    specs = state_specs(
        state, tp,
        paged=bool(block_size),
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            stage_layer_specs(cfg, tp, stage_layers), P(PIPE_AXIS),
            head_specs(head_params), specs,
            P(), P(), P(), P(),
        ),
        out_specs=(specs, P()),
        check_vma=False,
    )(stage_layers, layer_masks, head_params, state, draft, draft_len,
      slot, cache_delta)
