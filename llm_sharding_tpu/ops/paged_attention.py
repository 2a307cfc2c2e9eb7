"""Ragged paged attention over a pooled KV arena (PagedAttention, Kwon et
al., SOSP'23 — the vLLM allocation model, TPU-native).

Dense serving reserves ``capacity`` KV columns per row and decode attention
reads all of them every step (``ops/attention.cached_attention`` over
``[B, C, ...]``). Paged serving stores KV in a shared arena of fixed-size
blocks, HEAD-MAJOR and layer-stacked: ``[L, num_blocks, Nkv, block_size,
D]`` (``models/cache.paged_arena_shape``) — one block's one head is a
``(block_size, D)`` tile and one block's ``Nkv`` heads are contiguous, so
the pool is stored in the layout it is read in and nothing ever transposes
it. Each row maps the blocks covering its ACTUAL tokens through a block
table ``[B, T]`` (entry 0 — the reserved trash block — pads unmapped
slots). Every op here takes the WHOLE stack plus a ``layer`` index and
addresses ``(layer, block)`` inside it: the layer scan carries the stack
and no operation produces or consumes a value of a layer's arena size (the
gathers, the scatters and the kernels' block DMAs index the carried array
in place). This module provides the attention over that layout:

- ``gather_block_kv`` / ``paged_attention_xla``: the exact XLA path — an
  advanced-indexing gather assembles each row's logical window, then the
  standard position-masked attention runs over it. This is what the tier-1
  CPU mesh (and the serve programs in ``parallel/serve.py``, which gather
  at the shard_map boundary) execute; numerics are identical to dense
  attention over the same positions by construction.
- ``paged_attention_tpu``: a Pallas DECODE kernel that never materializes
  the gathered window in HBM and whose work is the tokens that are
  WRITTEN, not the width the table reserves. It derives each row's
  frontier — the last table entry holding a key some query may attend —
  from the table and the position arrays it is given, and ONE invocation
  walks the rows' live cells end to end in a loop of its body whose trip
  count is read from the frontiers: a dead row, and the unwritten tail of
  a live one, cost nothing. The block table, the layer index and the
  frontiers ride as SCALAR-PREFETCH operands
  (``pltpu.PrefetchScalarGridSpec``); the arenas ride in ONCE each, where
  they lie in HBM, and the body fetches a cell's blocks BY HAND: one
  async copy a block — ALL key/value heads of it, the ``(Nkv, block_size,
  D)`` tile at ``(layer, table[b, idx])`` — into one slot of a
  double-buffered VMEM scratch, the next cell's copies started before
  this one is scored (``decode_blocks_per_cell`` blocks a cell: as many
  as the scratch and the score tiles hold, 16 at 4 heads). A block was a
  ``BlockSpec`` operand until PR 54 and the pipeline's bookkeeping a ref
  cost as much as its 32 KiB DMA (PERF.md, PR 54: 5.9 → 3.1 ns a token of
  context at Keye's shape). Every head of a block is scored by one dot
  under a block-diagonal head mask, and blocks stream through VMEM with
  online-softmax accumulation exactly like ``ops/flash_attention``.
- ``paged_prefill_tpu``: the CHUNKED-PREFILL kernel — same table-driven
  KV streaming, but the query axis is a whole prompt chunk, GQA-folded
  and tiled at ``BLOCK_Q_PREFILL`` like the flash kernel, and the one
  grid axis runs over the live cells of the chunk's (row, key/value
  head, query tile) runs (``prefill_walk``: a traced bound, the walk
  scalar-prefetched, a block a ``BlockSpec`` operand): a padded row of
  the slot,
  a query tile past a short prompt and the cells past a tile's causal
  frontier cost nothing. This is what lets ``serve_prefill_chunk``
  attend the arena in place instead of round-tripping a gathered
  O(window) copy per chunk.
- ``paged_attention`` / ``paged_prefill``: backend dispatch (pallas on
  TPU for MXU-aligned head_dim, XLA elsewhere). Same masking contract
  everywhere: ``kv_pos <= q_pos``, sentinel = masked — so never-written
  block tails drop out for free, and trash-mapped entries (block 0)
  additionally gather/stream as ZEROS (both paths): the shared trash
  block accumulates parked rows' garbage, and a non-finite garbage value
  would otherwise turn the masked probability-0 positions into
  ``0 × Inf = NaN``.

The retired ``bucketed_decode_attention`` (the decode-window ``lax.switch``
whose branch copies made it SLOWER than full-capacity attention — see the
measured note in README) is superseded by this op: block granularity gives
the live-prefix-only HBM traffic the bucketed switch was after, without
copying the cache into a conditional branch. The SERVE programs call it
too: ``parallel/serve.serve_chunk`` / ``serve_verify`` route a decode
step's write and attention through ``paged_attention_write(backend=...)``
directly on the pooled arena (new KV entries land in the blocks the table
names, never through a full-window round trip), so per-step attention HBM
traffic scales with the blocks a row actually owns. The XLA gather path
remains the bit-exact CPU/tier-1 fallback behind the same dispatch.

Who writes the arena, and at what granularity (one algorithm — fresh
entries land in the blocks the table names, invalid ones in block 0 of
their layer or nowhere — at three granularities, chosen by what a
program's statics say, never by an option):

- ONE SUBLANE TILE A ROW, by the ATTENTION kernel (``paged_decode``,
  ``paged_attention_tpu(fresh=)``): a decode step (``serve_chunk``: one
  entry a row, at each row's own column) over a plain arena with the
  attention on its kernel (``decode_writes_in_kernel``). Each arena rides
  in once, aliased over itself; the row's frontier cell has the fresh
  column's block in its buffer anyway, so the body puts the entry into the
  ``(Nkv, SUB, D)`` tile that holds the slot there, scores the cell and
  meanwhile copies the tile back, K and V alike: a decode layer call is
  ONE kernel (PR 61; a call of its own before the attention,
  ``write_rows_tpu``'s ``paged_kv_write``, took 2.8-3.5 us a layer call
  from PR 46 on, the two scatters before it 12.8: ``chip_smoke.py
  --kv-decode``, PERF.md), and XLA, seeing no scatter, re-lays and stages
  nothing. A gated entry and a trash-mapped column are not stored at all.
  ``write_rows_tpu`` stays for a selecting model's index keys
  (``write_index_keys``), which the score call reads before the attention.
- ROWS, ``write_block_kv``: ``B x S x Nkv`` rows of ``D``, each with its
  own ``(layer, block, head, slot)``. What a decode step writes everywhere
  else — speculation's verify (``serve_verify``: ``K + 1`` entries a row),
  an int8/fp8 arena (running per-block scales), context parallel, the XLA
  attention path (the CPU mesh) — and a prefill chunk that cannot be
  tiles: shorter than a block (a context-parallel radix admission with a
  short suffix), or over an int8/fp8 arena.
- TILES, ``write_chunk_kv``: a prefill chunk (``serve_prefill_chunk``)
  of whole blocks over a plain arena. Its rows share their columns, which
  start on a block boundary, and the arena is head-major, so a layer
  call's fresh K/V is ``Sc / BS`` whole contiguous ``(Nkv, BS, D)`` blocks
  a row: that many block-sized writes where the row-wise scatter made
  ``B x Sc x Nkv`` (16,384 rows of 256 bytes a layer call at OLMoE's 16
  heads: 2.29 ms where the tiles take 0.064, ``chip_smoke.py
  --kv-write``, PERF.md PR 42).
- WHOLE WINDOWS, ``parallel/serve._scatter_pages`` (``serve_admit``'s
  one-shot prefill; every layer at once) and ``write_arena_blocks`` (the
  hand-off's block moves).

Backend selection (``paged_attention``'s ``backend=`` + the
``PAGED_FORCE_KERNEL`` env var): ``auto`` picks the Pallas kernel on TPU
for Mosaic-eligible shapes and the XLA gather elsewhere; ``kernel``/
``xla`` force a path; ``interpret`` runs the Pallas kernel in interpret
mode on any backend — how CI exercises the kernel code path through the
serve programs on the CPU mesh every PR.
"""

from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import cached_attention
from .quant import kv_dequantize, kv_qmax, kv_quantize

NEG_INF = -1e30  # python float: jnp constants can't be captured by kernels

#: Valid values for ``paged_attention(backend=)`` and the
#: ``PAGED_FORCE_KERNEL`` env override ("1" is accepted as "kernel").
BACKENDS = ("auto", "kernel", "xla", "interpret")


def forced_backend() -> str | None:
    """The ``PAGED_FORCE_KERNEL`` env override, validated, or None. Read
    per call (not at import): tests and CI set it around a run. It only
    overrides ``backend="auto"`` — an explicit caller choice wins."""
    raw = os.environ.get("PAGED_FORCE_KERNEL", "").strip().lower()
    if not raw:
        return None
    if raw == "1":
        return "kernel"
    if raw not in ("kernel", "xla", "interpret"):
        raise ValueError(
            f"PAGED_FORCE_KERNEL={raw!r}: expected kernel, xla, "
            f"interpret or 1"
        )
    return raw


def auto_blocks_per_step(t_blocks: int, block_size: int) -> int:
    """Auto-selected KV blocks batched per sequential grid step of the
    kernel that takes a block as a ``BlockSpec`` operand (the chunked
    prefill; one head of a block a ref): the largest of
    8/4/2/1 that divides the table width and keeps a step's keys — the
    lanes of its score tile — at or under 512 tokens. At small serving
    block sizes one arena block is a skinny tile that underfeeds the MXU
    and pays one DMA turnaround and the pipeline's per-operand bookkeeping
    (~50 ns a ref a step on a v5e) per block; batching ``bps`` blocks per
    step gives the compiler ``bps`` independent in-flight DMAs
    (double-buffered across steps) and dots that do not wait on each
    other. The decode kernel and the score kernel issue their own copies
    and are not held to eight operands: ``decode_blocks_per_cell``,
    ``index_blocks_per_cell``."""
    for bps in (8, 4, 2, 1):
        if t_blocks % bps == 0 and bps * block_size <= 512:
            return bps
    return 1


#: VMEM a cell of the decode kernel's walk may hold in K and V tiles, both
#: slots of its double buffer together (``decode_blocks_per_cell``); the
#: kernel's other scratch is a few hundred KiB and the compiler's scoped
#: limit on a v5e 16 MiB.
DECODE_CELL_VMEM = 4 << 20


def decode_blocks_per_cell(
    t_blocks: int, block_size: int, kv_heads: int, kv_lanes: int,
    itemsize: int,
) -> int:
    """Blocks a cell of the decode kernel's walk (``paged_attention_tpu``),
    from the shapes alone: the largest power of two that divides the table
    width and keeps

    - the cell's K and V tiles (``kv_heads`` x ``block_size`` x
      ``kv_lanes`` x ``itemsize`` bytes a block, ``kv_lanes`` = a key's
      lanes + a value's; a latent block is its keys alone), double
      buffered, inside ``DECODE_CELL_VMEM``;
    - its score tiles — ``bps`` of ``kv_heads·block_size`` lanes, every
      head of a block scored in one dot — at or under 2,048 lanes, which
      is 2 MiB of the above at 128-lane bf16 keys and values;
    - its keys at or under 512 tokens: what a row reads past its frontier
      inside its last cell (trash, masked) is half a cell on average.

    The body copies a block by hand, so nothing ties the width to a count
    of operands: 16 at 4 key/value heads (the 7B, Keye: 32 KiB a tile, 2
    MiB a cell's two slots), 8 at 8 (a 14B stage; MiMo's window layers at
    256 + 128 lanes: 3 MiB), 4 at 16 (OLMoE: 128 KiB a tile), 16 over a
    latent arena. Swept on the chip when a block was an operand (PERF.md,
    PR 28: 8 best at 4 and 8 heads, 4 at 16, 16 ahead only where every row
    held the full table, the per-operand bookkeeping in its way); by hand
    16 walks Keye's 8.7 k tokens at 3.1 ns a token where 8 operands took
    5.9 (PERF.md, PR 54)."""
    bps = 1
    while (
        t_blocks % (2 * bps) == 0 and 2 * bps * block_size <= 512
        and 2 * bps * block_size * kv_heads <= 2048
        and 2 * (2 * bps) * kv_heads * block_size * kv_lanes * itemsize
        <= DECODE_CELL_VMEM
    ):
        bps *= 2
    return bps


#: What a cell of the score kernel's walk may hold (``index_blocks_per_cell``):
#: index keys in ONE slot of its double buffer, and copies — a copy of an 8 KiB
#: block takes ~19 ns to issue whatever it moves, and 96 a cell was the best
#: width swept at Keye's shape (PERF.md, PR 56).
INDEX_CELL_VMEM = 1 << 20
INDEX_CELL_BLOCKS = 96


def index_blocks_per_cell(
    t_blocks: int, block_size: int, lanes: int, itemsize: int,
) -> int:
    """Blocks a cell of the score kernel's walk (``index_scores_tpu``), from
    the shapes alone: the most that divide the table width, stay within
    ``INDEX_CELL_BLOCKS`` copies and ``INDEX_CELL_VMEM`` of index keys a slot
    (``block_size`` x ``lanes`` x ``itemsize`` bytes a block: one head, no
    values) and make a cell's scores whole 128-lane tiles (a cell stores
    them at its own lane offset of the call's one ``[B, T·BS]`` output); the
    whole table where no width does (one cell, at offset 0).

    Not the decode kernel's rule. An index block is 8 KiB at Keye's shape —
    10 ns of HBM time under ~19 ns of issue, a trash copy past a row's
    frontier as much — and a cell costs ~0.27 us whatever its width: the
    walk is bound by the count of its copies and of its cells, not by its
    bytes. So wide cells, until the half cell of trash copies a row pays on
    average outweighs the cells saved: Keye's 8.7 k of context walk in 14.1
    / 10.1 / 8.8 / 8.2 / 7.2 us at 8 / 16 / 32 / 48 / 96 blocks a cell; 144
    take 6.5 there and 7.1 at 5.1 k where 96 take 4.8 (swept on the chip:
    PERF.md, PR 56)."""
    cap = min(
        INDEX_CELL_BLOCKS, INDEX_CELL_VMEM // (block_size * lanes * itemsize)
    )
    whole = [
        d for d in range(1, min(cap, t_blocks) + 1)
        if t_blocks % d == 0 and d * block_size % 128 == 0
    ]
    return max(whole, default=t_blocks)


def kernel_sublane(cache_dtype) -> int:
    """Mosaic sublane count of a KV storage dtype (8 at 4 bytes, 16 at 2,
    32 at 1-byte int8/fp8) — THE one definition; ``kernel_eligible`` and
    the serve-side error messages both read it so they cannot drift."""
    return 32 // max(jnp.dtype(cache_dtype).itemsize, 1)


#: Scalar-memory budget for the kernels' scalar-prefetched operands: the
#: block table, and beside it the decode kernel's frontiers (``nlive`` and a
#: windowed layer's ``first``, an entry a row each) or the prefill kernel's
#: walk (``PrefillWalk``).
#: The v5e compiler reports 1 MiB of SMEM and lays an int32 ``[rows, T]``
#: table out with rows padded to a multiple of 8 and each row to 128 words,
#: a 1-D array in whole KiB-words: a ``[128, 2048]`` table alone "exceeded
#: smem capacity by 1.2K"; with a walk of an entry a cell beside it (the
#: decode kernel's until PR 54, the prefill kernel's still) ``[120, 2048]``
#: exceeds it by 62.1K and ``[2000, 33]`` (one block a cell: 66,001
#: entries) by 253.1K, while ``[110, 2048]``, ``[104, 2048]`` and ``[1500,
#: 33]`` compile. 16 KiB is held back for the compiler's own scalars.
SMEM_TABLE_BUDGET = (1 << 20) - (16 << 10)


def kernel_eligible(
    head_dim: int, block_size: int, cache_dtype, *, rows: int,
    table_width: int, kv_heads: int = 1, prefill_tiles: int = 0,
) -> bool:
    """Mosaic eligibility of the real (non-interpret) kernels, as learned
    from the v5e compiler:

    - the (BS, D) block tiles as (sublane, 128) — D must be a lane
      multiple and BS a sublane multiple for the CACHE dtype
      (``kernel_sublane``);
    - the ``[rows, table_width]`` block table (``rows`` = the rows one call
      attends: a slot's ``batch_per_slot``) is scalar-prefetched whole,
      and beside it the decode kernel's six entries a row (its frontier, a
      windowed layer's first cell, and what a call that stores the step's
      fresh entries adds: their columns, the gate, the stretched frontier
      and the cell to patch); together they must fit
      ``SMEM_TABLE_BUDGET`` (a 1-byte arena's scales take two cells' worth
      of it, a few KiB). So
      must the table and the prefill kernel's walk where chunks are
      prefilled (``prefill_tiles`` = ``prefill_query_tiles`` of the chunk,
      0 = none): an entry per cell of every (row, key/value head, query
      tile) — ``table_width / bps`` cells at ``auto_blocks_per_step``'s
      ``bps`` — and two per run.

    Shared by the trace-time dispatch below and the host-side serve
    validation (``runtime/server.py``), so ``--paged-attn kernel`` fails
    loud at construction instead of as a compiler error mid-serve."""
    def pad(n, m):
        return -(-n // m) * m

    table = pad(rows, 8) * pad(table_width, 128)
    # the frontier and a windowed layer's first cell; a call that stores the
    # step's entries: their columns, the gate and two arrays of its own
    decode = table + 6 * pad(rows, 128)
    runs = rows * kv_heads * prefill_tiles
    cells = table_width // auto_blocks_per_step(table_width, block_size)
    prefill = table + pad(runs * cells + 1, 1024) + 2 * pad(runs, 128)
    return (
        head_dim % 128 == 0
        and block_size % kernel_sublane(cache_dtype) == 0
        and 4 * decode <= SMEM_TABLE_BUDGET
        and 4 * prefill <= SMEM_TABLE_BUDGET
    )


@jax.named_scope("kv_layout")
def window_from_blocks(blocks: jnp.ndarray) -> jnp.ndarray:
    """GATHERED head-major blocks ``[A, T, Nkv, BS, D]`` laid out as the
    token-major logical window ``[A, T*BS, Nkv, D]`` that
    ``cached_attention`` and a prefix handle read — a layout change of the
    gathered window (rows x T x BS entries), never of the pool."""
    A, T, Nkv, BS, D = blocks.shape
    return jnp.transpose(blocks, (0, 1, 3, 2, 4)).reshape(A, T * BS, Nkv, D)


def gather_block_kv(
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] pooled key blocks
    v_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D]
    layer,  # scalar int32 — the layer whose blocks the table names
    block_table: jnp.ndarray,  # [B, T] int32 arena block ids per row
    k_scale: jnp.ndarray = None,  # [L, NB, Nkv] f32 per-block-per-head
    v_scale: jnp.ndarray = None,  # scales (quantized arenas only)
    out_dtype=None,  # dequant target; defaults to the scale dtype
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Assemble each row's logical KV window ``[B, T*BS, Nkv, D]`` from the
    arena: ONE gather at ``(layer, block_table)`` of the stacked pool, then
    ``window_from_blocks`` lays the gathered window out token-major. The gather is the XLA fallback's only extra
    cost over dense attention; duplicate table entries (shared prefix
    blocks, trash padding) are plain repeated reads. Trash-mapped entries
    (block 0) gather as ZEROS: the shared trash block accumulates parked
    rows' garbage writes, and although attention masks those positions to
    probability exactly 0, a non-finite garbage value would still produce
    ``0 × Inf = NaN`` in the PV product — zeroing closes the channel
    without touching live numerics.

    With ``k_scale``/``v_scale`` (a quantized int8/fp8 arena) the gather
    DEQUANTIZES: each block's values multiply by its per-head scale and
    the window comes out in ``out_dtype`` — the XLA-path analogue of the
    Pallas kernel's in-VMEM fused dequant."""
    k = k_arena[layer, block_table]  # [B, T, Nkv, BS, D]
    v = v_arena[layer, block_table]
    if k_scale is not None:
        dt = out_dtype or k_scale.dtype
        k = kv_dequantize(k, k_scale[layer, block_table][..., None, None], dt)
        v = kv_dequantize(v, v_scale[layer, block_table][..., None, None], dt)
    live = (block_table != 0)[:, :, None, None, None]
    k = jnp.where(live, k, jnp.zeros((), k.dtype))
    v = jnp.where(live, v, jnp.zeros((), v.dtype))
    return window_from_blocks(k), window_from_blocks(v)


@jax.named_scope("kv_write")
def write_block_kv(
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] pooled key blocks
    v_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D]
    layer,  # scalar int32 — the layer the entries belong to
    block_table: jnp.ndarray,  # [B, T] int32 arena block ids per row
    cols: jnp.ndarray,  # [B, S] int32 logical columns of the new entries
    k_new: jnp.ndarray,  # [B, S, Nkv, D]
    v_new: jnp.ndarray,  # [B, S, Nkv, D]
    valid=None,  # scalar or [B, S] bool — False entries go to the trash
    #   block of their layer; their owning blocks keep their contents
    k_scale: jnp.ndarray = None,  # [L, NB, Nkv] f32 — quantized arenas only
    v_scale: jnp.ndarray = None,
):
    """Scatter a step's fresh KV entries into their OWNING arena blocks of
    the layer-stacked pool — the decode-path replacement for the
    full-window gather→update→scatter round trip: per step the arena
    update is ``B × S`` entries of ``(Nkv, D)`` at ``(layer, block, :,
    slot)`` of the array the layer scan carries, not the logical window
    and never a layer of the pool. Column ``c`` of row ``b`` lives in
    arena block ``block_table[b, c // BS]`` at slot ``c % BS``;
    trash-mapped columns (table entry 0) land in the shared trash sink,
    which absorbs them (parked-slot garbage, spec-verify overflow past a
    row's mapped budget — the sink's contents are never attended: readers
    gate entry 0 to zeros and position masking excludes them anyway).

    ``valid`` gates at ENTRY granularity and BY ADDRESS — an invalid
    entry is steered to the trash block of its OWN layer (``(layer, 0, :,
    slot)``), so ring-inactive microsteps, masked pipeline layers and a
    verify's rejected positions leave every owned block bit for bit alone
    without reading it back and without a full-arena ``where`` (which
    would copy the pool per layer per microstep). Collisions (several
    rows trash-mapped onto the same slot) resolve last-wins: only the
    sink can collide, and it is a garbage sink by contract. (The
    quantised branch below still gates by value: its block rewrite needs
    the owning block anyway.)

    With ``k_scale``/``v_scale`` (quantized int8/fp8 arena) the write
    QUANTIZES AT INSERT against a RUNNING per-block-per-head absmax: a
    fresh entry that raises its block's scale first requantizes the
    block's existing codes to the new scale (a dequant→requant round on
    exactly the touched blocks at ``(layer, block)`` — ≤ one block per
    written entry), then lands quantized. Scale updates scatter with
    ``.at[].max`` so several entries of one call hitting the same block
    resolve order-free, and the block-content rewrite is identical for
    every colliding entry (same source block, same final scale) —
    race-free like the prefix broadcast. Returns ``(k_arena, v_arena,
    k_scale, v_scale)`` in quantized mode, the plain ``(k_arena,
    v_arena)`` pair otherwise — always the whole stacks."""
    Nkv, BS = k_arena.shape[2], k_arena.shape[3]
    W = block_table.shape[1] * BS
    cols = jnp.clip(cols, 0, W - 1)  # defense: XLA clamps, tables don't
    blk = jnp.take_along_axis(block_table, cols // BS, axis=1)  # [B, S]
    slot = cols % BS
    # an entry is Nkv rows of D, one per head, at (layer, blk, h, slot): the
    # scatter (and the gate's gather) index EVERY dim but D, so the updated
    # window is the arena's minor dim alone. With the head dim left as a
    # window dim XLA's layout assignment re-lays the whole carried stack
    # token-major for the scatter's sake and copies it back, per layer, for
    # the kernel (seen in the compiled v5e program) — the very copies this
    # layout exists to remove.
    if k_scale is None and valid is not None:
        # gate by ADDRESS: an invalid entry lands in the trash block of its
        # own layer, so its owning block is never read back
        blk = jnp.where(valid, blk, 0)
    entry = (
        layer, blk[:, :, None], jnp.arange(Nkv)[None, None, :],
        slot[:, :, None],
    )  # → [B, S, Nkv] rows of D
    if k_scale is None:
        return (
            k_arena.at[entry].set(k_new.astype(k_arena.dtype)),
            # a latent arena holds no values (its value read is a slice of
            # the key read): nothing to write
            v_arena if v_arena.shape[-1] == 0
            else v_arena.at[entry].set(v_new.astype(v_arena.dtype)),
        )

    qmax = kv_qmax(k_arena.dtype)
    keep = None
    if valid is not None:
        keep = jnp.asarray(valid)
        if not keep.ndim:
            keep = jnp.broadcast_to(keep, cols.shape)

    def one(arena, scale, new):
        B, S, Nkv, D = new.shape
        # candidate scale of each fresh entry (per kv head); invalid
        # entries must neither grow the scale nor write
        cand = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1) / qmax
        if keep is not None:
            cand = jnp.where(keep[..., None], cand, 0.0)
        s_old = scale[layer, blk]  # [B, S, Nkv] pre-update block scales
        scale_new = scale.at[layer, blk].max(cand)
        s_fin = scale_new[layer, blk]  # post-scatter final scales
        # requantize the touched blocks' existing codes to the final scale
        # (a no-op rewrite when the scale did not grow: round(q * 1.0))
        old = arena[layer, blk]  # [B, S, Nkv, BS, D]
        old_f = kv_dequantize(old, s_old[..., None, None], jnp.float32)
        req = kv_quantize(old_f, s_fin[..., None, None], arena.dtype)
        arena = arena.at[layer, blk].set(req)
        qn = kv_quantize(new, s_fin[..., None], arena.dtype)
        if keep is not None:
            idx = jnp.broadcast_to(
                slot[:, :, None, None, None], (B, S, Nkv, 1, D)
            )
            old_entry = jnp.take_along_axis(req, idx, axis=3)[:, :, :, 0]
            qn = jnp.where(keep[..., None, None], qn, old_entry)
        return arena.at[entry].set(qn), scale_new

    k_arena, k_scale = one(k_arena, k_scale, k_new)
    v_arena, v_scale = one(v_arena, v_scale, v_new)
    return k_arena, v_arena, k_scale, v_scale


def chunk_writes_tiles(chunk: int, block_size: int, quantized: bool) -> bool:
    """Whether ``write_chunk_kv`` writes a prefill chunk of ``chunk``
    positions as whole-block tiles: what the chunk program's statics say —
    the chunk is whole blocks and the arena holds plain values. The host
    asks the same question for its counter (``runtime/server.py``)."""
    return bool(block_size) and chunk % block_size == 0 and not quantized


@jax.named_scope("kv_write")
def write_chunk_kv(
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] pooled key blocks
    v_arena: jnp.ndarray,  # [L, NB, Nkv, BS, Dv]
    layer,  # scalar int32
    block_table: jnp.ndarray,  # [B, T]
    col0,  # scalar int32 — the chunk's first column, the same in every row
    k_new: jnp.ndarray,  # [B, Sc, Nkv, D]
    v_new: jnp.ndarray,  # [B, Sc, Nkv, Dv]
    valid=None,  # SCALAR bool (ring-inactive microstep, masked layer)
    k_scale: jnp.ndarray = None,
    v_scale: jnp.ndarray = None,
):
    """``write_block_kv`` for a PREFILL CHUNK: every row's entries are the
    columns ``col0 .. col0 + Sc - 1``, so where the chunk is whole blocks
    (``chunk_writes_tiles``; ``col0`` is then a multiple of the block size:
    radix matches and chunk offsets are, and ``_admit_chunked`` refuses a
    start that is not) a layer call's fresh K/V is ``Sc / BS`` WHOLE
    ``(Nkv, BS, D)`` blocks a row — contiguous in the head-major arena —
    and lands as that many tiles at ``(layer, table[row, col0 // BS + j])``
    instead of ``B x Sc x Nkv`` rows of ``D``. The token-major activations
    are re-laid ``[B, Sc / BS, Nkv, BS, D]`` first: a transpose of the
    chunk's own entries, never of the pool. The scatter's window is the
    arena's three MINOR dims (``write_block_kv`` warns of a window that is
    not: XLA re-lays the stack), what ``serve_admit``'s ``_scatter_pages``
    writes through every layer at once.

    The same bytes land in the same blocks: pad positions and trash-mapped
    table entries (a padded row, a window layer's freed block) are written
    as the row-wise write writes them, the latter into block 0 of the
    layer. ``valid`` False steers EVERY tile there, by address: no owned
    block is read back. A chunk under a block (a cp-forced radix admission
    with a short suffix) and a quantized arena (its running per-block
    scales; a whole-block write would need none, as ``_scatter_pages_q``
    shows — ROADMAP C14) take the row-wise write as it is."""
    B, Sc, Nkv = k_new.shape[:3]
    BS = k_arena.shape[3]
    if not chunk_writes_tiles(Sc, BS, k_scale is not None):
        cols = jnp.broadcast_to(
            col0 + jnp.arange(Sc, dtype=jnp.int32)[None, :], (B, Sc)
        )
        return write_block_kv(
            k_arena, v_arena, layer, block_table, cols, k_new, v_new,
            valid=valid, k_scale=k_scale, v_scale=v_scale,
        )
    nb = Sc // BS
    # past the table's width (the server never asks: a row's budget is
    # mapped before its chunks run) a tile goes to the sink, not to a
    # clamped neighbour
    blk = jnp.take(
        block_table, col0 // BS + jnp.arange(nb, dtype=jnp.int32), axis=1,
        mode="fill", fill_value=0,
    )  # [B, nb]
    if valid is not None:
        blk = jnp.where(valid, blk, 0)

    def tiles(arena, new):
        t = new.astype(arena.dtype).reshape(B, nb, BS, Nkv, new.shape[-1])
        return arena.at[layer, blk].set(jnp.transpose(t, (0, 1, 3, 2, 4)))

    return (
        tiles(k_arena, k_new),
        # a latent arena holds no values: nothing to write
        v_arena if v_arena.shape[-1] == 0 else tiles(v_arena, v_new),
    )


def paged_attention_xla(
    q: jnp.ndarray,  # [B, S, Nh, D] (RoPE'd)
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D]
    v_arena: jnp.ndarray,
    layer,  # scalar int32
    block_table: jnp.ndarray,  # [B, T]
    q_positions: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, T*BS] logical-column key positions
    scale: float | None = None,
    k_scale: jnp.ndarray = None,  # [L, NB, Nkv] — quantized arenas only
    v_scale: jnp.ndarray = None,
    latent_v: int = 0,
    window: int = 0,
    sink: jnp.ndarray = None,
) -> jnp.ndarray:
    """Gather + position-masked attention: exact on every backend. A
    quantized arena dequantizes at the gather into the QUERY dtype — the
    same dequant target as the fused kernel, so the two paths match. With
    ``latent_v`` the values are the first ``latent_v`` lanes of the keys;
    ``window`` / ``sink`` as ``ops/attention.cached_attention``."""
    k, v = gather_block_kv(
        k_arena, v_arena, layer, block_table, k_scale, v_scale,
        out_dtype=q.dtype,
    )
    if latent_v:
        v = k[..., :latent_v]
    return cached_attention(
        q, k, v, q_positions, kv_positions, scale, window=window, sink=sink,
    )


def attn_stats_xla(
    q: jnp.ndarray,  # [B, S, Nh, D] (RoPE'd)
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D]
    v_arena: jnp.ndarray,
    layer,  # scalar int32
    block_table: jnp.ndarray,  # [B, T]
    q_positions: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, T*BS] logical-column key positions
    scale: float | None = None,
    k_scale: jnp.ndarray = None,  # [L, NB, Nkv] — quantized arenas only
    v_scale: jnp.ndarray = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Partial-softmax attention statistics over the LOCAL arena — the
    per-shard half of context-parallel attention. Returns the flash
    recurrence's running triple rather than a normalized output:
    ``acc [B, S, Nh, D]`` (f32, sum of ``exp(s - m) · v``), ``m [B, S,
    Nh]`` (f32 row max) and ``l [B, S, Nh]`` (f32 sum of ``exp(s - m)``),
    exactly the ``(acc, m, l)`` scratch ``_online_update`` carries —
    ``combine_attn_stats`` reduces shards' triples with the same
    recurrence, so the combined output equals single-shard attention over
    the union of windows by construction.

    Two masking differences vs ``cached_attention``: columns are masked
    by position AND by slot-liveness (``block_table != 0``). Under cp a
    column another shard owns maps to the local trash block — its
    position is real and its gathered K is the zero-gate's zeros, so a
    positional mask alone would hand it weight ``exp(0 · scale - m)``
    and corrupt ``l``. Masked columns contribute EXACTLY zero (``where``
    on the probabilities, not just NEG_INF scores): a fully-masked row
    yields ``(0, NEG_INF, 0)``, which the combine's correction factor
    wipes instead of counting ``exp(0) = 1`` per dead column."""
    B, S, Nh, D = q.shape
    BS = k_arena.shape[3]
    k, v = gather_block_kv(
        k_arena, v_arena, layer, block_table, k_scale, v_scale,
        out_dtype=q.dtype,
    )
    Nkv = k.shape[2]
    G = Nh // Nkv
    if scale is None:
        scale = D ** -0.5
    qg = q.reshape(B, S, Nkv, G, D)
    # same einsum/precision contract as cached_attention: fp32 ACCUMULATION
    # via preferred_element_type, operands in their storage dtype
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32,
    ) * scale
    live = jnp.repeat(block_table != 0, BS, axis=1)  # [B, T*BS]
    mask = (
        (kv_positions[:, None, :] <= q_positions[:, :, None])
        & live[:, None, :]
    )  # [B, S, W]
    mask = mask[:, None, None, :, :]  # [B,1,1,S,W]
    scores = jnp.where(mask, scores, jnp.float32(NEG_INF))
    m = scores.max(axis=-1)  # [B, Nkv, G, S]
    p = jnp.where(mask, jnp.exp(scores - m[..., None]), jnp.float32(0.0))
    l = p.sum(axis=-1)  # [B, Nkv, G, S]
    # probabilities down-cast to the cache dtype for the PV matmul — the
    # same precision contract as cached_attention / the Pallas kernel
    acc = jnp.einsum(
        "bkgst,btkd->bskgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).reshape(B, S, Nh, D)
    to_bsn = lambda x: jnp.transpose(x, (0, 3, 1, 2)).reshape(B, S, Nh)
    return acc, to_bsn(m), to_bsn(l)


@jax.named_scope("attn")
def combine_attn_stats(
    acc: jnp.ndarray,  # [B, S, Nh, D] f32 per-shard unnormalized output
    m: jnp.ndarray,  # [B, S, Nh] f32 per-shard row max
    l: jnp.ndarray,  # [B, S, Nh] f32 per-shard exp-sum
    axis_name: str,
) -> jnp.ndarray:
    """Cross-shard online-softmax combine: rebase every shard's ``(acc,
    l)`` onto the global row max and psum — one step of the
    ``_online_update`` recurrence applied across ``axis_name`` instead of
    across streamed KV tiles. Exact by the usual flash identity:
    ``softmax(concat(s_i)) · V = Σ_i exp(m_i - m) · acc_i / Σ_i
    exp(m_i - m) · l_i``. Rows no shard attends anywhere (parked rows
    mapped entirely to trash) come out as zeros, not NaN — ``l`` stays 0
    through the psum and the guard below short-circuits the division.
    Returns the normalized f32 output ``[B, S, Nh, D]`` (callers cast
    back to the activation dtype)."""
    m_all = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_all)  # exp(NEG_INF - finite) == 0: dead shards drop
    l_all = jax.lax.psum(l * corr, axis_name)
    acc_all = jax.lax.psum(acc * corr[..., None], axis_name)
    return jnp.where(
        l_all[..., None] > 0.0,
        acc_all / jnp.maximum(l_all, 1e-30)[..., None],
        jnp.float32(0.0),
    )


def _scale_operand(scale: jnp.ndarray) -> jnp.ndarray:
    """The prefill kernel's view of a ``[L, NB, Nkv]`` scale arena: ``[L,
    NB, Nkv, 1, 1]`` f32, so one layer's one block's one head's scale is a
    ``(1, 1, 1, 1)`` VMEM tile (layer dim squeezed) whose last two dims ARE
    the array's. Mosaic refuses a ``(1, 1)`` block of the lower-rank array
    in any memory space (the last two block dims must be multiples of (8,
    128) or the whole array's). (The decode kernel reads a cell's scales
    as scalars: ``paged_attention_tpu``.)"""
    return scale.astype(jnp.float32)[..., None, None]


def _layer_operand(layer) -> jnp.ndarray:
    """The layer index as the kernels' scalar-prefetch operand: ``[1]``
    int32 in scalar memory, read by every arena/scale index map and by the
    decode kernel's copies."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _online_update(q, tiles, scale, acc_ref, m_ref, l_ref):
    """One flash-attention recurrence step over the KV tiles one grid cell
    streamed — ``tiles`` is a sequence of ``(k, v, mask)``, ``k``/``v``
    ``[N, D]`` and ``mask`` ``[rows, N]``: score every tile, take ONE running
    max over all of them, fold them into the (acc, m, l) running-softmax
    scratch with one rescale. The tiles' dots do not depend on each other
    (a chain of per-tile updates would serialize them through ``m``).
    Shared by the decode kernel (a cell's ``bps`` blocks) and the
    chunked-prefill kernel (one tile per call) — the masking and
    accumulation contract is ``ops/flash_attention._flash_kernel``'s
    (NEG_INF masking; an all-masked tile's garbage is wiped by the first
    real tile's correction factor)."""
    scores = []
    for k, _, mask in tiles:
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, N] f32
        scores.append(jnp.where(mask, s, NEG_INF))

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = m_prev
    for s in scores:
        m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr
    pv = None
    for s, (_, v, _) in zip(scores, tiles):
        p = jnp.exp(s - m_new)
        l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
        d = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, D]
        pv = d if pv is None else pv + d
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _first_blocks(block_table, q_positions, kv_positions, window, nlive):
    """The walk from the other side: per row the index of the FIRST table
    entry a windowed layer (``window`` keys back from a query) must read,
    ``[B]`` int32 — the first entry that is not trash and holds a key
    position ``>`` the row's smallest real query position ``- window``. An
    entry before it is one the window's mask wipes whole (or one the host
    already gave back to the pool: trash). ``nlive`` where there is none."""
    from ..models.cache import POS_SENTINEL  # models imports this module

    B, T = block_table.shape
    q_lo = jnp.min(q_positions, axis=1)  # pad queries sit at the sentinel
    need = (kv_positions > q_lo[:, None] - window) & (
        kv_positions < POS_SENTINEL
    )
    need = need.reshape(B, T, -1).any(axis=2) & (block_table != 0)
    first = jnp.min(
        jnp.where(need, jnp.arange(T, dtype=jnp.int32), T), axis=1
    )
    return jnp.minimum(first, nlive).astype(jnp.int32)


def _live_blocks(block_table, q_positions, kv_positions):
    """Per-row count of table entries the decode kernel must walk, ``[B]``
    int32: the leading entries of row ``b`` up to the LAST one that is not
    trash (``block_table != 0``) and holds a key position some query of the
    row may attend (``kv_pos <= `` the row's largest real query position —
    a finished row of ``serve_verify`` queries at the sentinel and counts
    for nothing). Computed from the very arrays the mask is made of, so a
    block past it is one the mask wipes whole: a reduce over ``[B, T, BS]``
    int32. A dead row (table all trash, or no real query) reads 0."""
    from ..models.cache import POS_SENTINEL  # models imports this module

    B, T = block_table.shape
    q_hi = jnp.max(
        jnp.where(q_positions < POS_SENTINEL, q_positions, -1), axis=1
    )
    seen = (kv_positions <= q_hi[:, None]).reshape(B, T, -1).any(axis=2)
    seen &= block_table != 0
    ends = jnp.where(seen, jnp.arange(1, T + 1, dtype=jnp.int32), 0)
    return jnp.max(ends, axis=1)


def _end_to_end(nent, bps, width):
    """Runs of ``nent[r]`` table entries laid end to end in cells of ``bps``
    (a kernel's walk over a grid: the prefill kernel's runs; the decode
    kernel and the score kernel walk in their bodies and need none):
    ``start[r]`` the grid step of run ``r``'s cell 0, ``owner[i]`` the run
    grid step ``i`` walks, ``ends`` the running sum of the runs' cells
    (``ends[-1]`` = the grid's length). Steps past the cells' sum never run,
    but the pipeline evaluates the index maps one step AHEAD of the one it
    runs: ``owner`` holds one entry more than the most steps there can be
    (``width`` cells a run), or the core halts."""
    return _cells_end_to_end(-(-nent // bps), width)


def _cells_end_to_end(cells, width):
    """``_end_to_end`` of runs given as their COUNT of cells (a windowed
    walk's run does not start at the table's cell 0)."""
    ends = jnp.cumsum(cells)
    start = ends - cells
    step = jnp.arange(cells.shape[0] * width + 1, dtype=jnp.int32)
    owner = jnp.minimum(
        jnp.sum(step[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        cells.shape[0] - 1,
    )  # the runs whose cells end at or before step i
    return start, owner, ends


#: Score tiles of a decode cell that fold into the running softmax in one
#: ``_online_update`` (``_paged_kernel``).
FOLD_TILES = 8


def entry_tile_rows(cache_dtype, block_size: int) -> int:
    """Rows of the tile a decode step's ONE fresh entry is stored with: the
    storage dtype's sublanes (one ``(SUB, 128)`` tile a head and lane
    group), or the block where it is not whole tiles."""
    sub = kernel_sublane(cache_dtype)
    return sub if block_size % sub == 0 else block_size


def _entry_into_tile(new, old_ref, out_ref, at):
    """``old_ref`` ``[Nkv, SUB, D]`` into ``out_ref`` (which may be the same
    ref) with the fresh entry ``new`` ``[Nkv, D]`` at sublane ``at``: a
    select along the tile's sublanes against the slot, on 32-bit lanes (a
    2-byte float widens and narrows again exactly) — the entry's bits land
    as the scatter would have stored them and every other row of the tile
    goes back as it came."""
    Nkv, sub, D = old_ref.shape
    wide = old_ref.dtype if old_ref.dtype.itemsize == 4 else jnp.float32
    here = jax.lax.broadcasted_iota(jnp.int32, (sub, D), 0) == at
    new = new.astype(wide)  # a head a sublane
    for h in range(Nkv):
        out_ref[h] = jnp.where(
            here, new[h:h + 1], old_ref[h].astype(wide)
        ).astype(out_ref.dtype)


def _paged_kernel(
    layer_ref,  # scalar-prefetch [1] — the layer of the stack the copies read
    tbl_ref,  # scalar-prefetch [B, T] (the copies' block ids + the trash gate)
    nlive_ref,  # scalar-prefetch [B] — the row's frontier (_live_blocks)
    *rest,  # windowed: first scalar-prefetch [B] — the table CELL the row's
    #   walk starts at; then q [B, M, D] — every row's every head's query
    #   rows, M = Nkv·G·S; the K arena and (not latent) the V arena where
    #   they lie in HBM, [L, NB, Nkv, BS, D]; qpos [B, M, 1], qhead [M, 1],
    #   kvpos in HBM [B, T/bps, 1, bps·BS], khead [1, Nkv·BS]; quantized:
    #   the scales of the table's blocks in HBM [B, T/bps, 1, bps·2·Nkv]
    #   f32 (a block's K scales, then its V scales); (sink [M, 1]), out [B,
    #   M, Dv]; scratch: the cell buffers, TWO slots each — k [2, bps, Nkv,
    #   BS, D], v, kvpos [2, 1, bps·BS] in VMEM, quantized: the scales [2,
    #   1, bps·2·Nkv] in SMEM — a DMA semaphore a slot, acc [M, Dv] f32, m
    #   [M, 128] f32, l [M, 128] f32 (the two lane rows padded to whole
    #   128-lane tiles)
    scale,
    bps,
    quantized=False,
    latent_v=0,  # a latent arena: no V arena, a block's values are the first
    #   ``latent_v`` lanes of its keys (one copy a block, not two)
    window=0,  # > 0: a query keeps keys ``q_pos - window < kv_pos`` and the
    #   walk starts at the row's ``first`` cell (cells behind it are not
    #   walked; the cell the window's edge cuts is masked)
    sink=False,  # a [M, 1] f32 ref after khead: a per-head logit that joins
    #   the softmax's denominator and nothing else
    fresh=False,  # the call STORES the step's one fresh entry a row: two more
    #   scalar-prefetch operands after ``first`` — col [B], the entry's
    #   column, and ok [B], the write gate —, the entries new_k [B, Nkv, D]
    #   (and new_v) after khead, after ``out`` each arena AGAIN as an output,
    #   aliased over its operand (the body reads and writes the arena through
    #   that one ref), and two more scratch arrays in scalar memory, [B]
    #   each: the row's frontier stretched to the entry's block, and the
    #   table CELL that holds it (-1: the row stores nothing)
):
    if window:
        first_ref, rest = rest[0], rest[1:]
    if fresh:
        col_ref, ok_ref, rest = rest[0], rest[1], rest[2:]
    n_src = 1 if latent_v else 2  # the arenas a block is copied out of
    q_ref, srcs, rest = rest[0], rest[1:1 + n_src], rest[1 + n_src:]
    qpos_ref, qhead_ref, kvpos_hbm, khead_ref, rest = *rest[:4], rest[4:]
    rows = [kvpos_hbm]  # what a cell brings in one copy each, a lane row
    if quantized:
        rows.append(rest[0])
        rest = rest[1:]
    if fresh:
        news, rest = rest[:n_src], rest[n_src:]
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    out_ref, rest = rest[0], rest[1:]
    if fresh:
        srcs, rest = rest[:n_src], rest[n_src:]
    bufs, rest = rest[:n_src], rest[n_src:]
    row_bufs, rest = rest[:len(rows)], rest[len(rows):]
    if fresh:
        live_ref, wcell_ref, rest = rest[0], rest[1], rest[2:]
    sem, acc_ref, m_ref, l_ref = rest
    pos_buf = row_bufs[0]
    B = q_ref.shape[0]
    Nkv, BS, D = bufs[0].shape[2:]

    if fresh:
        def column(b):
            """Row ``b``'s fresh column, clipped as ``write_block_kv``'s."""
            return jnp.clip(col_ref[b], 0, tbl_ref.shape[1] * BS - 1)

        # ONCE a row, before the walk (scalar work a CELL would be paid a
        # cell: 60 ns each on the chip, PERF.md PR 61): a row STORES its
        # entry where the gate is open and the column lies in a block the
        # row owns; its walk then reaches that block at least — a selection
        # may have masked the fresh key itself out of ``nlive``'s sight, and
        # an entry the mask wipes whole adds exactly nothing to the softmax
        def row(b, _):
            went = column(b) // BS
            stores = (ok_ref[b] != 0) & (tbl_ref[b, went] != 0)
            live_ref[b] = jnp.maximum(
                nlive_ref[b], jnp.where(stores, went + 1, 0)
            )
            wcell_ref[b] = jnp.where(stores, went // bps, -1)
            return _

        jax.lax.fori_loop(0, B, row, 0)
    else:
        live_ref = nlive_ref

    def cells(b):
        """Row ``b``'s walk: the table cells ``lo <= c < hi``."""
        hi = (live_ref[b] + bps - 1) // bps
        return (first_ref[b] if window else 0), hi

    def next_live(b):
        """The first row at or after ``b`` with a cell to walk; ``B``: none."""
        def dead(r):
            lo, hi = cells(jnp.minimum(r, B - 1))
            return (r < B) & (hi <= lo)
        return jax.lax.while_loop(dead, lambda r: r + 1, b)

    def copies(slot, b=0, c=0, fetch=True):
        """The async copies that bring cell ``c`` of row ``b`` into ``slot``:
        its key positions (a quantized arena: its blocks' scales too, into
        scalar memory), and a block's ``(Nkv, BS, D)`` tile out of each
        arena at ``(layer, table[b, idx])`` — every head of a block in one
        contiguous copy, read where it lies. An entry past the frontier
        inside the frontier's cell names the trash block. ``fetch=False``:
        the same copies to WAIT on — a wait reads its copy's size and
        semaphore, not its source, so it spares the table reads."""
        out = [
            pltpu.make_async_copy(row.at[b, c], buf.at[slot], sem.at[slot])
            for row, buf in zip(rows, row_bufs)
        ]
        for j in range(bps):
            idx = c * bps + j
            at = (layer_ref[0], jnp.where(
                idx < live_ref[b], tbl_ref[b, idx], 0
            )) if fetch else (0, 0)
            out += [
                pltpu.make_async_copy(
                    src.at[at], buf.at[slot, j], sem.at[slot]
                )
                for src, buf in zip(srcs, bufs)
            ]
        return out

    def fresh_tiles(slot, b):
        """Per arena, the sublane tile ``(Nkv, SUB, D)`` that holds row
        ``b``'s fresh slot, in the cell's buffer and where it lies in the
        arena, ``(layer, table[b, col // BS], :, tile, :)``; and the slot's
        sublane inside it."""
        col = column(b)
        went = col // BS
        sub = entry_tile_rows(bufs[0].dtype, BS)
        rows_of = pl.ds(pl.multiple_of(col % BS // sub * sub, sub), sub)
        return [
            (buf.at[slot, went % bps, :, rows_of, :],
             arena.at[layer_ref[0], tbl_ref[b, went], :, rows_of, :])
            for buf, arena in zip(bufs, srcs)
        ], col % sub

    def stores(tiles):
        """The copies that put the patched tiles back: ONE an arena."""
        return [
            pltpu.make_async_copy(tile, home, sem.at[2])
            for tile, home in tiles
        ]

    def patch(slot, b):
        """The fresh entry into its slot of the block's buffer, so that the
        scores see it, and the tile on its way back to the arena."""
        tiles, at = fresh_tiles(slot, b)
        for (tile, _), new_ref in zip(tiles, news):
            _entry_into_tile(new_ref[b], tile, tile, at)
        for cp in stores(tiles):
            cp.start()

    # a row the walk never visits reads zeros
    out_ref[...] = jnp.zeros_like(out_ref)
    # one block's Nkv head tiles are one [Nkv·BS, D] tile, and the score of
    # EVERY query row against it is one dot: a query row keeps the columns
    # of its own key/value head (block-diagonal) and of positions it may
    # attend. Same layout contract as ops/flash_attention._flash_kernel:
    # qpos/qhead ride sublane-major, kvpos/khead lane-major, so the mask
    # broadcast maps onto the score tile with no Mosaic relayout. Sentinel
    # positions (never-written block tails) mask out here.
    own = qhead_ref[...] == khead_ref[...]  # [M, Nkv·BS]

    def cell(carry):
        """One cell of the walk: the rows' live cells end to end, the next
        cell's copies in flight (the other slot) while this one is scored."""
        b, c, slot = carry
        lo, hi = cells(b)
        nlive = live_ref[b]
        last = c + 1 >= hi  # the row's frontier cell
        nb = next_live(jnp.where(last, b + 1, b))
        nc = jnp.where(last, cells(jnp.minimum(nb, B - 1))[0], c + 1)

        @pl.when(nb < B)
        def _prefetch():
            for cp in copies(1 - slot, nb, nc):
                cp.start()

        for cp in copies(slot, fetch=False):
            cp.wait()

        @pl.when(c == lo)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

        if fresh:
            # the cell that holds the fresh column's block (the row's
            # frontier cell): patched in VMEM, attended, stored under the
            # scoring
            storing = c == wcell_ref[b]
            pl.when(storing)(lambda: patch(slot, b))

        q = q_ref[b]  # [M, D]
        qpos = qpos_ref[b]  # [M, 1]

        def tile(j):
            """Block ``j`` of the cell as ``_online_update`` takes it."""
            idx = c * bps + j
            k_blk = bufs[0][slot, j]  # [Nkv, BS, D]
            v_blk = k_blk[..., :latent_v] if latent_v else bufs[1][slot, j]
            if quantized:
                # THE fused dequant: the block streamed into VMEM as 1-byte
                # codes (half/quarter the DMA bytes of bf16) and dequantizes
                # here against its per-(block, head) scales, scalars the
                # cell's copy left in SMEM — the bf16 window never exists in
                # HBM. Dequant target is the query dtype, matching the XLA
                # gather path bit for bit.
                k_blk, v_blk = (
                    jnp.stack([
                        blk[h].astype(jnp.float32)
                        * row_bufs[1][slot, 0, (2 * j + kv) * Nkv + h]
                        for h in range(Nkv)
                    ]).astype(q.dtype)
                    for kv, blk in enumerate((k_blk, v_blk))
                )
            # trash blocks (table entry 0, and what a sub-block past the
            # frontier inside the frontier's cell names) stream as zeros:
            # their garbage contents are position-masked to probability 0
            # below, but non-finite garbage would still NaN the masked
            # positions (0 x Inf) through the score and PV products. where(),
            # not multiply — Inf * 0 is itself NaN.
            live = (idx < nlive) & (tbl_ref[b, idx] != 0)
            k_blk = jnp.where(live, k_blk, jnp.zeros_like(k_blk))
            v_blk = jnp.where(live, v_blk, jnp.zeros_like(v_blk))
            kvpos = jnp.concatenate(
                [pos_buf[slot, :, j * BS:(j + 1) * BS]] * Nkv, axis=1
            )
            k_tile = k_blk.reshape(Nkv * BS, D)
            v_tile = v_blk.reshape(Nkv * BS, v_blk.shape[-1])
            seen = own & (kvpos <= qpos)
            if window:
                seen &= kvpos > qpos - window
            return k_tile, v_tile, seen

        # the cell's score tiles fold into the running softmax EIGHT at a
        # time: eight ``[M, Nkv·BS]`` float32 tiles and their probabilities
        # fill the vector registers, sixteen spill — and a row's output
        # then does not depend on how wide the shapes make a cell
        for j in range(0, bps, FOLD_TILES):
            _online_update(
                q, [tile(i) for i in range(j, min(j + FOLD_TILES, bps))],
                scale, acc_ref, m_ref, l_ref,
            )

        @pl.when(last)
        def _finish():
            l = l_ref[:, :1]
            if sink:
                l = l + jnp.exp(sink_ref[...] - m_ref[:, :1])
            out_ref[b] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(
                out_ref.dtype
            )

        if fresh:
            # the tile has left the buffer before the next cell's prefetch
            # (or the call's end) takes this slot
            @pl.when(storing)
            def _stored():
                for cp in stores(fresh_tiles(slot, b)[0]):
                    cp.wait()

        return nb, nc, 1 - slot

    b0 = next_live(jnp.int32(0))
    c0 = cells(jnp.minimum(b0, B - 1))[0]

    @pl.when(b0 < B)
    def _first():
        for cp in copies(0, b0, c0):
            cp.start()

    jax.lax.while_loop(
        lambda carry: carry[0] < B, cell,
        (b0, jnp.asarray(c0, jnp.int32), jnp.int32(0)),
    )


class Fresh(NamedTuple):
    """A decode step's ONE fresh entry a row, handed to the attention call
    that stores it (``paged_attention_tpu(fresh=)``): the keys ``[B, Nkv,
    D]`` and values ``[B, Nkv, Dv]`` (None over a latent arena, which holds
    none), each row's column ``[B]`` and the write gate, a scalar or ``[B]``
    bool (False: the row stores nothing)."""

    k: jnp.ndarray
    v: jnp.ndarray
    cols: jnp.ndarray
    ok: jnp.ndarray


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "interpret", "blocks_per_step", "latent_v", "window",
    ),
)
def paged_attention_tpu(
    q: jnp.ndarray,  # [B, S, Nh, D]
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] — the layer-stacked pool
    v_arena: jnp.ndarray,
    layer,  # scalar int32 — scalar-prefetched beside the table
    block_table: jnp.ndarray,  # [B, T] int32
    q_positions: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, T*BS]
    scale: float | None = None,
    interpret: bool = False,
    k_scale: jnp.ndarray = None,  # [L, NB, Nkv] — quantized arenas only
    v_scale: jnp.ndarray = None,
    blocks_per_step: int | None = None,  # static; None = auto-selected
    latent_v: int = 0,  # static: a latent arena — ``v_arena`` is not read,
    #   a block's values are the first ``latent_v`` lanes of its keys and
    #   the output is ``[B, S, Nh, latent_v]``
    window: int = 0,  # static: a windowed layer — a query keeps the keys
    #   ``q_pos - window < kv_pos <= q_pos`` and a row's walk STARTS at the
    #   first cell that holds one (``_first_blocks``)
    sink: jnp.ndarray = None,  # [Nh] a per-head logit in the softmax's
    #   denominator (its column dropped: it adds nothing to the output)
    fresh: Fresh = None,  # the step's one fresh entry a row: the call
    #   stores it and returns ``(out, k_arena, v_arena)``
) -> jnp.ndarray:
    """Pallas paged DECODE attention whose work is the tokens that are
    written: ONE kernel invocation walks the LIVE cells of the call, a cell
    being ``bps`` consecutive table entries of one row (``blocks_per_step``,
    ``decode_blocks_per_cell`` of the shapes when None), for all key/value
    heads.

    The frontier. ``nlive[b]`` (``_live_blocks``) is derived here from the
    table and the two position arrays — no caller passes it — and rides as
    a scalar-prefetch operand beside the layer index and the table (a
    windowed layer's first cell too). The walk is a loop INSIDE the body:
    row ``b`` has ``ceil(nlive[b] / bps)`` cells, the loop runs the rows'
    cells end to end and reads its trip count from ``nlive``, so a row
    costs the blocks that are written and a dead row a compare. A skipped
    block is one the position mask wiped whole, so the result is the whole
    table's; a row with no live block returns zeros.

    The copies are the body's. Both arenas ride in ONCE, unblocked, where
    they lie in HBM (``memory_space=pl.ANY``: no slice of a layer, no
    layout change, the gathered window never exists), and the body starts
    one async copy a block — the ``(Nkv, BS, D)`` tile at ``(layer,
    table[b, idx])`` of the 5-D stacked pool, ALL key/value heads of a
    block in one contiguous DMA — into one of the two slots of a VMEM
    scratch: cell ``c + 1``'s copies (the next live row's first cell after
    a row's last) are started before cell ``c`` is scored and waited on
    just before their use. No ``BlockSpec`` operand a block: the pipeline
    paid ~50 ns of bookkeeping a ref a grid step, sixteen refs a cell
    (PERF.md, PR 54). Every head of a block is scored in ONE dot: the
    query tile is all ``M = Nkv·G·S`` rows (head ``h = k·G + g``, the fold
    of ``cached_attention``), a block is one ``[Nkv·BS, D]`` tile, and a
    query row keeps the columns of its own key/value head — the mask is
    block-diagonal over heads times ``kv_pos <= q_pos``. The cell's
    ``bps`` score tiles fold into the running softmax ``FOLD_TILES`` (8)
    at a time, one rescale a fold (``_online_update``): a cell's width
    decides who copies a block and when, not a bit of the result. Decode-shaped: every row's ``M`` query rows sit
    in VMEM whole, so keep ``B·Nh·S`` small (serving decode is S = 1,
    verify K + 1).

    VMEM is 2 x bps ``(Nkv, BS, D)`` K blocks and as many V blocks (the
    two slots: 2 MiB at 4 bf16 heads of 32 x 128 and bps 16, or 16 heads
    and bps 4) + the ``(M, Nkv·BS)`` f32 score tiles + ``(B, M, D)``
    queries and outputs + (M, D) + 2·(M, 128) scratch. Real-TPU use wants
    D a lane multiple (128) and BS a sublane multiple for the cache dtype;
    ``paged_attention`` gates on that and interpret mode covers the rest.

    Quantized arenas (``k_scale``/``v_scale``): a block's copy moves
    1-byte codes — HALF (int8 vs bf16) the per-step attention HBM traffic
    — and the scales of the blocks the table names (``[B, T, 2, Nkv]``, one
    small gather out of each scale arena) come a cell at a time, one more
    copy, into scalar memory; the dequant multiply, a head's tile by its
    scalar, runs in VMEM right before the score dot. Int8 tiles want BS a
    multiple of 32 (1-byte sublane — ``kernel_eligible``).

    The kernel writes what it attends (``fresh=``, a decode step's ONE
    entry a row over a plain arena: ``decode_writes_in_kernel``). The cell
    that holds the fresh column's block — the row's frontier cell: the
    step's ``kv_positions`` already hold the fresh column, and the walk is
    stretched to its block where a selection masked the fresh key out — has
    that block in its buffer anyway: after the cell's copies have landed
    the body puts the entry into its slot there (the sublane tile ``(Nkv,
    SUB, D)`` that holds it, a select against the slot), scores the cell —
    the fresh key with it —, and meanwhile ONE copy an arena takes the tile
    back to ``(layer, table[b, col // BS], :, tile, :)``, waited on before
    the slot is reused. "Stored, then attended" is "patched in VMEM,
    attended, stored under the scoring": the bytes that land in OWNED blocks
    and the scores are ``write_block_kv`` + this kernel's, bit for bit.
    Each arena rides in ONCE, aliased over an output of the call
    (``input_output_aliases``; the body reads and writes it through that
    one ref), so the carried pool stays where it lies — handed in as a
    read-only operand AND an aliased one, XLA copied it whole every call
    (PERF.md, PR 46). A gated row (``ok`` 0: a ring stage's bubble
    microstep, a parked slot) and a column the table maps to trash store
    NOTHING — block 0, which ``write_block_kv`` uses as their sink, is left
    as it was, and every owned block too; a row the walk does not visit
    (table all trash) likewise."""
    B, S, Nh, D = q.shape
    Nkv, BS = k_arena.shape[2], k_arena.shape[3]
    T = block_table.shape[1]
    G = Nh // Nkv
    M = Nh * S
    quantized = k_scale is not None
    Dv = latent_v or v_arena.shape[-1]  # a value may be narrower than a key
    if latent_v and quantized:
        raise NotImplementedError("a quantized latent arena is not done")
    if fresh is not None and (quantized or S != 1):
        raise NotImplementedError(
            "the attention call stores ONE entry a row into a plain arena"
        )
    if scale is None:
        scale = D ** -0.5
    if kv_positions.shape != (B, T * BS):
        raise ValueError(
            f"kv_positions must be [B, T*BS]={B, T * BS}, got "
            f"{kv_positions.shape}"
        )
    bps = blocks_per_step or decode_blocks_per_cell(
        T, BS, Nkv, D + (0 if latent_v else Dv), k_arena.dtype.itemsize
    )
    if T % bps != 0:
        raise ValueError(
            f"blocks_per_step={bps} does not divide the table width {T}"
        )

    def cell_rows(x):
        """``[B, T, ...]`` as a lane row a cell, ``[B, T/bps, 1, lanes]``:
        the body copies a cell's row by hand, and Mosaic slices a copy's
        source in whole 128-lane tiles, so a narrower or ragged row (an odd
        table width, 32 heads a block, a cell's few scales) is padded up."""
        x = x.reshape(B, T // bps, 1, -1)
        return jnp.pad(x, ((0, 0),) * 3 + ((0, -x.shape[-1] % 128),))

    kp = cell_rows(kv_positions)

    # GQA fold (the reshape contract of cached_attention: head h = k*G + g):
    # query row r = (k*G + g)*S + s belongs to key/value head r // (G*S)
    # and sits at position q_positions[s]
    qh = jnp.transpose(q, (0, 2, 1, 3)).reshape(B, M, D)
    qp = jnp.tile(q_positions, (1, Nh))[..., None]  # [B, M, 1]
    qhead = (np.arange(M, dtype=np.int32) // (G * S))[:, None]  # [M, 1]
    khead = (np.arange(Nkv * BS, dtype=np.int32) // BS)[None]  # [1, Nkv*BS]

    nlive = _live_blocks(block_table, q_positions, kv_positions)
    prefetch = [_layer_operand(layer), block_table, nlive]
    if window:
        # the walk from the other side: cells wholly behind the window are
        # not walked, a row's first cell is the table's cell ``first``
        prefetch.append(_first_blocks(
            block_table, q_positions, kv_positions, window, nlive
        ) // bps)
    if fresh is not None:
        prefetch += [
            fresh.cols.astype(jnp.int32),
            jnp.broadcast_to(fresh.ok, (B,)).astype(jnp.int32),
        ]

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    # what a block brings: its keys and its values (a latent block is read
    # once: its values are a slice of its keys)
    arenas = [k_arena] + ([] if latent_v else [v_arena])
    operands = [qh, *arenas, qp, qhead, kp, khead]
    in_specs = [
        whole((B, M, D)), *[in_hbm] * len(arenas), whole((B, M, 1)),
        whole((M, 1)), in_hbm, whole((1, Nkv * BS)),
    ]
    row_bufs = [pltpu.VMEM((2, *kp.shape[2:]), kp.dtype)]
    if quantized:
        # the scales of the blocks the table names, a block's Nkv of K then
        # its Nkv of V: one small gather out of each scale arena, a row a
        # cell that the body copies into SCALAR memory
        sc = cell_rows(jnp.stack(
            [s.astype(jnp.float32)[layer, block_table]
             for s in (k_scale, v_scale)], axis=2,
        ))
        operands.append(sc)
        in_specs.append(in_hbm)
        row_bufs.append(pltpu.SMEM((2, *sc.shape[2:]), sc.dtype))
    stored = []
    if fresh is not None:
        # the arenas the call stores into, and the entry for each
        stored = arenas
        for arena, new in zip(arenas, (fresh.k, fresh.v)):
            in_specs.append(whole(new.shape))
            operands.append(new.astype(arena.dtype))
    if sink is not None:
        # query row r = h·S + s carries head h's logit, sublane-major
        in_specs.append(whole((M, 1)))
        operands.append(jnp.repeat(sink.astype(jnp.float32), S)[:, None])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(1,),
        in_specs=in_specs,
        out_specs=[whole((B, M, Dv)), *[in_hbm] * len(stored)],
        scratch_shapes=[
            *[pltpu.VMEM((2, bps, *a.shape[2:]), a.dtype) for a in arenas],
            *row_bufs,
            *[pltpu.SMEM((B,), jnp.int32)] * (2 if stored else 0),
            # a slot's copies in; the fresh tiles' copies out
            pltpu.SemaphoreType.DMA((3 if stored else 2,)),
            pltpu.VMEM((M, Dv), jnp.float32),
            pltpu.VMEM((M, 128), jnp.float32),
            pltpu.VMEM((M, 128), jnp.float32),
        ],
    )
    out, *stored = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, bps=bps, quantized=quantized,
            latent_v=latent_v,
            window=window, sink=sink is not None, fresh=fresh is not None,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, M, Dv), q.dtype),
            *[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in stored],
        ],
        grid_spec=grid_spec,
        # each arena over itself: it follows the scalars and the queries
        input_output_aliases={
            len(prefetch) + 1 + i: 1 + i for i in range(len(stored))
        },
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_decode",
    )(*prefetch, *operands)
    out = jnp.transpose(out.reshape(B, Nh, S, Dv), (0, 2, 1, 3))
    if fresh is None:
        return out
    return out, stored[0], (v_arena if latent_v else stored[1])


#: Query-row tile of the chunked-prefill kernel (G·Sc folded rows per
#: grid cell). 256 keeps the f32 score tile at (256, bps·BS ≤ 512) —
#: ≤ 512 KB — and the whole per-step VMEM well under the flash kernel's
#: audited budget; chunks smaller than this run as one (padded) tile.
BLOCK_Q_PREFILL = 256


def prefill_query_tiles(group: int, chunk: int) -> int:
    """Query tiles a key/value head's ``group`` x ``chunk`` folded query
    rows make in the chunked-prefill kernel."""
    return -(-group * chunk // min(BLOCK_Q_PREFILL, group * chunk))


def _folded_q_positions(q_positions: jnp.ndarray, group: int) -> jnp.ndarray:
    """``[B, S]`` query positions as the prefill kernel's query tiles see
    them, ``[B, tiles, BQ]``: folded row ``g·S + s`` carries position
    ``q_positions[s]``, the last tile's padding the sentinel."""
    from ..models.cache import POS_SENTINEL  # models imports this module

    B, S = q_positions.shape
    tiles = prefill_query_tiles(group, S)
    qp = jnp.tile(q_positions, (1, group))
    pad_q = tiles * min(BLOCK_Q_PREFILL, group * S) - group * S
    if pad_q:
        qp = jnp.pad(qp, ((0, 0), (0, pad_q)), constant_values=POS_SENTINEL)
    return qp.reshape(B, tiles, -1)


class PrefillWalk(NamedTuple):
    """The chunked-prefill kernel's work list (``prefill_walk``): the live
    cells of every run laid end to end, a RUN being one (row, key/value
    head, query tile) — run ``r = (b·Nkv + k)·tiles + tile`` — and a cell
    ``bps`` consecutive table entries. All int32."""

    nent: jnp.ndarray  # [R] table entries the run walks (its frontier)
    start: jnp.ndarray  # [R] the grid step of the run's cell 0
    run_of: jnp.ndarray  # [R·T/bps + 1] the run grid step i walks
    steps: jnp.ndarray  # scalar: live cells = the grid's length
    first: jnp.ndarray = None  # [R] a WINDOWED walk: the table cell the
    #   run's walk starts at (cells behind the window are in no run)


def prefill_walk(
    block_table: jnp.ndarray,  # [B, T] int32
    q_positions: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, T*BS]
    nlive: jnp.ndarray = None,  # [B] the caller's clamp; None = the table
    *,
    q_heads: int,
    kv_heads: int,
    blocks_per_step: int | None = None,
    window: int = 0,  # a windowed layer: a query keeps ``window`` keys back
) -> PrefillWalk:
    """What a chunk's queries have to walk, from the arrays the mask is
    made of (the prefill counterpart of ``_live_blocks``). A query tile's
    frontier is the LAST table entry that is not trash (``block_table !=
    0``), lies under the row's ``nlive`` and holds a key position ``<=``
    the tile's largest real query position; the tile's runs (one a
    key/value head) walk the cells up to it. A dead row (every query at
    the sentinel: the padded rows of a slot), a dead query tile (the
    chunk's tail past a short prompt) and the cells past a tile's causal
    frontier are then in no run: everything left out is what the position
    mask wipes whole. ``nlive`` is trusted as a clamp only — a padded row
    is found from its positions, whatever ``nlive`` says of it.

    It does not depend on the layer: ``serve_prefill_chunk`` builds it
    once a chunk, outside the layer scan, and hands it to every layer's
    ``paged_prefill``."""
    from ..models.cache import POS_SENTINEL  # models imports this module

    B, T = block_table.shape
    BS = kv_positions.shape[1] // T
    bps = blocks_per_step or auto_blocks_per_step(T, BS)
    qp = _folded_q_positions(q_positions, q_heads // kv_heads)
    tiles = qp.shape[1]
    q_hi = jnp.max(jnp.where(qp < POS_SENTINEL, qp, -1), axis=2)  # [B, tiles]
    k_lo = jnp.min(kv_positions.reshape(B, T, BS), axis=2)  # [B, T]
    entry = jnp.arange(T, dtype=jnp.int32)
    ok = block_table != 0
    if nlive is not None:
        ok &= entry[None, :] < nlive[:, None]
    seen = ok[:, None, :] & (k_lo[:, None, :] <= q_hi[:, :, None])
    nent = jnp.max(jnp.where(seen, entry + 1, 0), axis=2)  # [B, tiles]

    def per_run(a):
        return jnp.broadcast_to(
            a[:, None, :], (B, kv_heads, tiles)
        ).reshape(-1).astype(jnp.int32)

    if window:
        # from the other side: the first entry holding a key some real query
        # of the tile keeps (``k_hi > q_lo - window``); pad columns between
        # a short prompt and the decode region carry the sentinel
        kv = kv_positions.reshape(B, T, BS)
        k_hi = jnp.max(jnp.where(kv < POS_SENTINEL, kv, -1), axis=2)
        q_lo = jnp.min(qp, axis=2)  # [B, tiles]; pad queries: the sentinel
        need = ok[:, None, :] & (k_hi[:, None, :] > q_lo[:, :, None] - window)
        first = jnp.minimum(
            jnp.min(jnp.where(need, entry, T), axis=2), nent
        ) // bps
        nent, first = per_run(nent), per_run(first)
        start, run_of, ends = _cells_end_to_end(
            -(-nent // bps) - first, T // bps
        )
        return PrefillWalk(nent, start, run_of, ends[-1], first)
    nent = per_run(nent)
    start, run_of, ends = _end_to_end(nent, bps, T // bps)
    return PrefillWalk(nent, start, run_of, ends[-1])


def _paged_prefill_kernel(
    layer_ref,  # scalar-prefetch [1] — read by the index maps only
    tbl_ref,  # scalar-prefetch [B, T]
    nent_ref,  # scalar-prefetch [R] — the run's frontier (PrefillWalk)
    start_ref,  # scalar-prefetch [R] — the grid step of the run's cell 0
    run_ref,  # scalar-prefetch [R·T/bps + 1] — the run grid step i walks
    *rest,  # windowed: first scalar-prefetch [R] — the table cell the run's
    #   walk starts at; then q [1, BQ, D] — the run's query tile; bps k refs
    #   [1, 1, BS, D], bps v refs; quantized: + bps ks
    #   refs and bps vs refs ([1, 1, 1, 1]); then qpos [1, BQ, 1], kvpos
    #   [1, 1, 1, bps·BS], out [1, BQ, Dv], scratch acc/m/l
    scale,
    bps,
    runs_per_row,  # Nkv · query tiles
    quantized=False,
    latent_v=0,  # as in the decode kernel: values are a slice of the keys
    window=0,  # as in the decode kernel
    sink=False,  # a [1, BQ, 1] f32 ref after kvpos: the tile's rows' logits
):
    if window:
        first_ref, rest = rest[0], rest[1:]
    q_ref, rest = rest[0], rest[1:]
    k_refs, rest = rest[:bps], rest[bps:]
    if not latent_v:
        v_refs, rest = rest[:bps], rest[bps:]
    if quantized:
        ks_refs, rest = rest[:bps], rest[bps:]
        vs_refs, rest = rest[:bps], rest[bps:]
    if sink:
        sink_ref, rest = rest[2], rest[:2] + rest[3:]
    qpos_ref, kvpos_ref, out_ref, acc_ref, m_ref, l_ref = rest
    i = pl.program_id(0)
    r = run_ref[i]
    t = i - start_ref[r]  # which cell of the run's walk
    nent = nent_ref[r]
    b = r // runs_per_row
    first = t == 0
    if window:
        t = t + first_ref[r]  # which cell of the row's table

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # [BQ, D]
    ks, vs = [], []
    for j in range(bps):
        k_blk = k_refs[j][0, 0]  # [BS, D]
        v_blk = k_blk[:, :latent_v] if latent_v else v_refs[j][0, 0]
        if quantized:
            # fused dequant, same contract as the decode kernel: codes
            # stream, the bf16 window never exists in HBM
            k_blk = (
                k_blk.astype(jnp.float32) * ks_refs[j][0, 0]
            ).astype(q.dtype)
            v_blk = (
                v_blk.astype(jnp.float32) * vs_refs[j][0, 0]
            ).astype(q.dtype)
        # trash blocks (table entry 0, and what a sub-block past the run's
        # frontier inside its last cell names) stream as zeros. Their
        # positions are masked below anyway; zeroing closes the 0 × Inf =
        # NaN channel of the shared trash block's garbage.
        idx = t * bps + j
        live = (idx < nent) & (tbl_ref[b, idx] != 0)
        ks.append(jnp.where(live, k_blk, jnp.zeros_like(k_blk)))
        vs.append(jnp.where(live, v_blk, jnp.zeros_like(v_blk)))
    # the cell's blocks as ONE key tile: one score dot bps·BS keys wide (a
    # block alone is 32) and one rescale of the accumulator a cell, not
    # bps; the cell's key positions arrive joined (Mosaic joins no
    # booleans along lanes). Causal masking WITHIN the chunk falls out of
    # the position compare: the chunk's own entries were scattered into
    # the arena (with their kv positions) before this kernel runs, so a
    # query at position p attends exactly the prefix ≤ p — earlier chunks,
    # the radix prefix, and the chunk's own earlier tokens.
    mask = kvpos_ref[0, 0] <= qpos_ref[0]  # [BQ, bps·BS]
    if window:
        mask &= kvpos_ref[0, 0] > qpos_ref[0] - window
    _online_update(
        q, [(jnp.concatenate(ks, axis=0), jnp.concatenate(vs, axis=0), mask)],
        scale, acc_ref, m_ref, l_ref,
    )

    @pl.when((t + 1) * bps >= nent)  # the run's frontier cell
    def _finish():
        l = l_ref[:, :1]
        if sink:
            l = l + jnp.exp(sink_ref[0] - m_ref[:, :1])
        out_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(
            out_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "interpret", "blocks_per_step", "latent_v", "window",
    ),
)
def paged_prefill_tpu(
    q: jnp.ndarray,  # [B, S, Nh, D] — S = the chunk length (many rows)
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] — the layer-stacked pool
    v_arena: jnp.ndarray,
    layer,  # scalar int32 — scalar-prefetched beside the table
    block_table: jnp.ndarray,  # [B, T] int32
    q_positions: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, T*BS]
    scale: float | None = None,
    interpret: bool = False,
    k_scale: jnp.ndarray = None,  # [L, NB, Nkv] — quantized arenas only
    v_scale: jnp.ndarray = None,
    nlive: jnp.ndarray = None,  # [B] int32 — the caller's clamp: blocks
    #   covering each row's written frontier; None = the table's width
    blocks_per_step: int | None = None,  # static; None = auto-selected
    latent_v: int = 0,  # static: a latent arena (see paged_attention_tpu)
    walk: PrefillWalk = None,  # ``prefill_walk`` of these very operands,
    #   built by a caller that runs many layers over them; None = built here
    window: int = 0,  # static: a windowed layer (see paged_attention_tpu);
    #   ``walk`` must have been built with the same window
    sink: jnp.ndarray = None,  # [Nh] (see paged_attention_tpu)
) -> jnp.ndarray:
    """Flash-style CHUNKED-PREFILL attention over the paged arena whose
    work is what the chunk's real queries can see: the query axis is a
    whole prompt chunk (folded with the GQA groups and tiled at
    ``BLOCK_Q_PREFILL`` like ``ops/flash_attention``), the KV axis streams
    the arena blocks the scalar-prefetched table names — the gathered [B,
    W, Nkv, D] window of the retired ``_gather_window`` round trip never
    exists in HBM, and nothing is scattered back (the chunk's own KV
    landed via ``write_block_kv`` before the call).

    ONE sequential grid axis over the call's live cells (``prefill_walk``:
    a traced bound; the walk rides as scalar-prefetch operands beside the
    layer index and the table, the decode kernel's recipe). A run — one
    (row, key/value head, query tile) — walks its cells in order with
    (acc, m, l) online-softmax scratch carried across them, initialised at
    its first cell and written out at its last: the blocked flash
    recurrence, causality enforced by the ``kv_pos <= q_pos`` position
    compare (intra-chunk included: the chunk's entries carry their real
    positions). A cell's ``blocks_per_step`` blocks (one head of each: a
    chunk is bound by its dots, and a block-diagonal dot over ``Nkv``
    heads would do ``Nkv`` times the work) are scored as one key tile.
    A row with no real query, a query tile with none and the cells past a
    tile's frontier are in no run and cost nothing; a skipped cell is one
    the mask wiped whole, so a real query reads what the whole table's
    walk gave it.

    **The rows of pad queries are zeros** (position at the sentinel; and
    a query whose row maps no key it may attend): a tile no grid step
    visits is never written, so the result is selected by the real-query
    mask after the call. (The XLA path gives a pad query a softmax over
    the whole window; nothing reads either: a pad position routes to no
    expert, writes its KV under the sentinel and samples nothing.)"""
    from ..models.cache import POS_SENTINEL  # models imports this module

    B, S, Nh, D = q.shape
    Nkv, BS = k_arena.shape[2], k_arena.shape[3]
    T = block_table.shape[1]
    G = Nh // Nkv
    quantized = k_scale is not None
    Dv = latent_v or v_arena.shape[-1]  # a value may be narrower than a key
    if latent_v and quantized:
        raise NotImplementedError("a quantized latent arena is not done")
    if scale is None:
        scale = D ** -0.5
    if kv_positions.shape != (B, T * BS):
        raise ValueError(
            f"kv_positions must be [B, T*BS]={B, T * BS}, got "
            f"{kv_positions.shape}"
        )
    bps = blocks_per_step or auto_blocks_per_step(T, BS)
    if T % bps != 0:
        raise ValueError(
            f"blocks_per_step={bps} does not divide the table width {T}"
        )
    tiles = prefill_query_tiles(G, S)
    R = B * Nkv * tiles
    if walk is None:
        walk = prefill_walk(
            block_table, q_positions, kv_positions, nlive,
            q_heads=Nh, kv_heads=Nkv, blocks_per_step=bps, window=window,
        )
    if (walk.first is None) != (not window):
        raise ValueError(
            "the walk was built for another window than the call's"
        )
    if walk.run_of.shape != (R * (T // bps) + 1,):
        raise ValueError(
            f"walk of {walk.run_of.shape[0] - 1} cells was not built for "
            f"{B} rows x {Nkv} heads x {tiles} query tiles x {T // bps} "
            f"cells (prefill_walk's q_heads / kv_heads / blocks_per_step)"
        )

    # GQA fold + query tiling (the flash_attention pattern): head h =
    # k*G + g, folded row g*S + s carries position q_positions[s]; run r =
    # (b*Nkv + k)*tiles + tile owns query tile r of the flat [R, BQ, D]
    GS = G * S
    block_q = min(BLOCK_Q_PREFILL, GS)
    pad_q = tiles * block_q - GS
    qh = jnp.transpose(q, (0, 2, 1, 3)).reshape(B, Nkv, GS, D)
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    qh = qh.reshape(R, block_q, D)
    # sublane-major query positions, lane-major key positions, a cell's
    # together (see _flash_kernel)
    qp = _folded_q_positions(q_positions, G).reshape(B * tiles, block_q, 1)
    kp = kv_positions.reshape(B, T // bps, 1, bps * BS)

    def cell(i, ne, st, run, *fst):
        c = i - st[run[i]]
        if fst:
            c = c + fst[0][run[i]]
        return jnp.minimum(c, T // bps - 1)

    # arena-block specs, (layer, block, head) of the stacked pool like the
    # decode kernel's; a sub-block past the run's frontier inside its last
    # cell names the trash block
    def arena_index(i, lyr, tbl, ne, st, run, *fst, j):
        r = run[i]
        idx = cell(i, ne, st, run, *fst) * bps + j
        blk = jnp.where(idx < ne[r], tbl[r // (Nkv * tiles), idx], 0)
        return (lyr[0], blk, (r // tiles) % Nkv, 0, 0)

    def block_spec(j, width=D):
        return pl.BlockSpec(
            (None, 1, 1, BS, width), functools.partial(arena_index, j=j)
        )

    def scale_spec(j):
        return pl.BlockSpec(
            (None, 1, 1, 1, 1), functools.partial(arena_index, j=j)
        )

    def of_run(i, lyr, tbl, ne, st, run, *fst):
        return (run[i], 0, 0)

    lead = [walk.first] if window else []
    in_specs = [
        pl.BlockSpec((1, block_q, D), of_run),
        *[block_spec(j) for j in range(bps)],
        *([] if latent_v else [block_spec(j, Dv) for j in range(bps)]),
    ]
    operands = [
        _layer_operand(layer), block_table, walk.nent, walk.start,
        walk.run_of, *lead, qh,
        *([k_arena] * bps), *([] if latent_v else [v_arena] * bps),
    ]
    if quantized:
        in_specs += (
            [scale_spec(j) for j in range(bps)]
            + [scale_spec(j) for j in range(bps)]
        )
        operands += (
            [_scale_operand(k_scale)] * bps + [_scale_operand(v_scale)] * bps
        )
    in_specs += [
        pl.BlockSpec(
            (1, block_q, 1),
            lambda i, lyr, tbl, ne, st, run, *fst: (
                run[i] // (Nkv * tiles) * tiles + run[i] % tiles, 0, 0
            ),
        ),
        pl.BlockSpec(
            (1, 1, 1, bps * BS),
            lambda i, lyr, tbl, ne, st, run, *fst: (
                run[i] // (Nkv * tiles), cell(i, ne, st, run, *fst), 0, 0
            ),
        ),
    ]
    operands += [qp, kp]
    if sink is not None:
        # folded row g·S + s of key/value head k carries head k·G + g's
        # logit; tile (k, tile) of every row alike
        sk = jnp.repeat(sink.astype(jnp.float32).reshape(Nkv, G), S, axis=1)
        if pad_q:
            sk = jnp.pad(sk, ((0, 0), (0, pad_q)))
        in_specs.append(pl.BlockSpec(
            (1, block_q, 1),
            lambda i, lyr, tbl, ne, st, run, *fst: (
                run[i] % (Nkv * tiles), 0, 0
            ),
        ))
        operands.append(sk.reshape(Nkv * tiles, block_q, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 + len(lead),
        grid=(walk.steps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, Dv), of_run),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_prefill_kernel, scale=scale, bps=bps,
            runs_per_row=Nkv * tiles, quantized=quantized, latent_v=latent_v,
            window=window, sink=sink is not None,
        ),
        out_shape=jax.ShapeDtypeStruct((R, block_q, Dv), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_prefill",
    )(*operands)
    # a tile no run visited was never written, and a pad query's row of a
    # tile that was is a softmax over what its sentinel lets it see: zeros
    keep = (qp.reshape(B, 1, tiles, block_q) < POS_SENTINEL) & (
        (walk.nent > walk.first * bps).reshape(B, Nkv, tiles, 1) if window
        else walk.nent.reshape(B, Nkv, tiles, 1) > 0
    )
    out = jnp.where(
        keep[..., None], out.reshape(B, Nkv, tiles, block_q, Dv),
        jnp.zeros((), out.dtype),
    )
    out = out.reshape(B, Nkv, tiles * block_q, Dv)[:, :, :GS]
    out = out.reshape(B, Nkv, G, S, Dv)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, S, Nh, Dv)


def _ineligible_msg(op: str, k_arena, block_table) -> str:
    rows, width = block_table.shape
    return (
        f"{op} backend 'kernel': head_dim={k_arena.shape[-1]} / "
        f"block_size={k_arena.shape[3]} / block table [{rows}, {width}] are "
        f"not Mosaic-eligible for cache dtype "
        f"{jnp.dtype(k_arena.dtype).name} (head_dim must be a multiple of "
        f"128, the block size a sublane multiple, and the table with a "
        f"kernel's walk must fit {SMEM_TABLE_BUDGET} bytes of scalar "
        f"memory — see kernel_eligible); use backend='auto' or 'xla'"
    )


@jax.named_scope("attn")
def paged_prefill(
    q: jnp.ndarray,
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] — the layer-stacked pool
    v_arena: jnp.ndarray,
    layer,  # scalar int32 — which layer of the stack to attend
    block_table: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    scale: float | None = None,
    backend: str = "auto",
    k_scale: jnp.ndarray = None,  # [L, NB, Nkv] — quantized arenas only
    v_scale: jnp.ndarray = None,
    nlive: jnp.ndarray = None,  # [B] — kernel-path traffic clamp
    stats: bool = False,  # static: return (acc, m, l) partials (cp serve)
    latent_v: int = 0,  # static: a latent arena — values are the first
    #   ``latent_v`` lanes of the keys, ``v_arena`` (zero wide) is not read
    walk: PrefillWalk = None,  # the kernel path's work list, where the
    #   caller built it once for many layers (``prefill_walk``)
    window: int = 0,  # static: a windowed layer keeps ``window`` keys back
    sink: jnp.ndarray = None,  # [Nh] a per-head logit in the denominator
    select=None,  # a ``Selection``: each query attends the keys it chose
) -> jnp.ndarray:
    """Backend dispatch for CHUNKED-PREFILL attention over the arena,
    mirroring ``paged_attention``: the Pallas prefill kernel on TPU for
    Mosaic-eligible shapes, the exact XLA gather path otherwise;
    ``backend`` pins a path, ``PAGED_FORCE_KERNEL`` overrides ``auto``
    only, ``interpret`` emulates the kernel off-TPU (the CI lane).
    Identical numerics on every path for a REAL query (the XLA gather is
    the oracle the chunked-prefill tests assert against; a pad query's row
    is zeros from the kernel and a softmax over the whole window from the
    gather); ``nlive`` and ``walk`` only trim the kernel's work — the
    gather path reads the whole window regardless.

    ``stats=True`` (the context-parallel serve path) returns
    ``attn_stats_xla``'s unnormalized ``(acc, m, l)`` triple instead of a
    normalized output; stats mode always runs the XLA gather path —
    a stats-emitting kernel is the ROADMAP's ring-fusion leftover — so
    ``backend`` only selects the single-shard dispatch."""
    if backend not in BACKENDS:
        raise ValueError(
            f"paged_prefill backend {backend!r}: expected one of "
            f"{BACKENDS}"
        )
    if select is not None:
        return selected_prefill(
            q, k_arena, v_arena, layer, block_table, q_positions,
            kv_positions, select, scale, backend=backend, walk=walk,
        )
    if stats and (latent_v or window or sink is not None):
        raise NotImplementedError(
            "context-parallel attention over a latent arena, a windowed "
            "layer or a sink logit is not done"
        )
    if stats:
        return attn_stats_xla(
            q, k_arena, v_arena, layer, block_table, q_positions,
            kv_positions, scale, k_scale=k_scale, v_scale=v_scale,
        )
    if backend == "auto":
        backend = forced_backend() or "auto"
    eligible = kernel_eligible(
        q.shape[-1], k_arena.shape[3], k_arena.dtype,
        rows=block_table.shape[0],
        table_width=block_table.shape[1], kv_heads=k_arena.shape[2],
        prefill_tiles=prefill_query_tiles(
            q.shape[2] // k_arena.shape[2], q.shape[1]
        ),
    )
    if backend == "interpret":
        return paged_prefill_tpu(
            q, k_arena, v_arena, layer, block_table, q_positions,
            kv_positions, scale, interpret=True, k_scale=k_scale,
            v_scale=v_scale, nlive=nlive, latent_v=latent_v, walk=walk,
            window=window, sink=sink,
        )
    if backend == "kernel":
        if jax.default_backend() != "tpu":
            raise ValueError(
                f"paged_prefill backend 'kernel' requires a TPU backend "
                f"(got {jax.default_backend()}); use backend='interpret' "
                f"(or PAGED_FORCE_KERNEL=interpret) to emulate the kernel "
                f"off-TPU"
            )
        if not eligible:
            raise ValueError(
                _ineligible_msg("paged_prefill", k_arena, block_table)
            )
    use_pallas = backend == "kernel" or (
        backend == "auto" and jax.default_backend() == "tpu" and eligible
    )
    if use_pallas:
        return paged_prefill_tpu(
            q, k_arena, v_arena, layer, block_table, q_positions,
            kv_positions, scale, k_scale=k_scale, v_scale=v_scale, nlive=nlive,
            latent_v=latent_v, walk=walk,
            window=window, sink=sink,
        )
    return paged_attention_xla(
        q, k_arena, v_arena, layer, block_table, q_positions,
        kv_positions, scale, k_scale=k_scale, v_scale=v_scale,
        latent_v=latent_v, window=window, sink=sink,
    )


def _check_backend(backend: str) -> None:
    if backend not in BACKENDS:
        raise ValueError(
            f"paged_attention backend {backend!r}: expected one of "
            f"{BACKENDS}"
        )


def decode_path(backend: str, head_dim: int, k_arena, block_table) -> str:
    """What ``paged_attention`` runs for ``backend`` on these shapes:
    ``"kernel"`` (the Pallas kernel, compiled), ``"interpret"`` (the kernel
    emulated off-TPU) or ``"xla"`` (the gather). ``PAGED_FORCE_KERNEL``
    overrides ``auto`` only; ``kernel`` on a host without a TPU or on a
    shape Mosaic refuses is an error here, not a lowering failure later."""
    _check_backend(backend)
    if backend == "auto":
        backend = forced_backend() or "auto"
    if backend in ("interpret", "xla"):
        return backend
    eligible = kernel_eligible(
        head_dim, k_arena.shape[3], k_arena.dtype,
        rows=block_table.shape[0],
        table_width=block_table.shape[1], kv_heads=k_arena.shape[2],
    )
    if backend == "kernel":
        # curated here too, not only in the serve-side resolution: a
        # lingering PAGED_FORCE_KERNEL=kernel reaching a CPU host (or a
        # Mosaic-ineligible shape on TPU) through backend="auto" would
        # otherwise surface as a raw Pallas/Mosaic lowering error
        if jax.default_backend() != "tpu":
            raise ValueError(
                f"paged_attention backend 'kernel' requires a TPU backend "
                f"(got {jax.default_backend()}); use backend='interpret' "
                f"(or PAGED_FORCE_KERNEL=interpret) to emulate the kernel "
                f"off-TPU"
            )
        if not eligible:
            raise ValueError(
                _ineligible_msg("paged_attention", k_arena, block_table)
            )
        return "kernel"
    return "kernel" if jax.default_backend() == "tpu" and eligible else "xla"


@jax.named_scope("attn")
def paged_attention(
    q: jnp.ndarray,
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] — the layer-stacked pool
    v_arena: jnp.ndarray,
    layer,  # scalar int32 — which layer of the stack to attend
    block_table: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    scale: float | None = None,
    backend: str = "auto",
    k_scale: jnp.ndarray = None,  # [L, NB, Nkv] — quantized arenas only
    v_scale: jnp.ndarray = None,
    stats: bool = False,  # static: return (acc, m, l) partials (cp serve)
    latent_v: int = 0,  # static: a latent arena (see paged_prefill)
    window: int = 0,  # static: a windowed layer keeps ``window`` keys back
    sink: jnp.ndarray = None,  # [Nh] a per-head logit in the denominator
    fresh: Fresh = None,  # the step's fresh entry a row, for the KERNEL to
    #   store: the result is then ``(out, k_arena, v_arena)``
) -> jnp.ndarray:
    """Backend dispatch: the Pallas kernel on TPU for MXU-aligned shapes,
    the exact XLA gather path otherwise (CPU meshes, ragged head dims,
    sub-sublane block sizes — see ``kernel_eligible``). ``backend`` pins a
    path (``kernel`` / ``xla`` / ``interpret``); ``PAGED_FORCE_KERNEL``
    overrides ``auto`` only, so an explicit caller choice always wins.
    Identical numerics either way (interpret-mode tested on CPU). With
    ``k_scale``/``v_scale`` the arena is quantized (int8/fp8): the kernel
    fuses the dequant into its per-block DMA loop, the XLA path
    dequantizes at the gather — both into the query dtype.

    ``stats=True`` (the context-parallel serve path) returns
    ``attn_stats_xla``'s unnormalized ``(acc, m, l)`` triple for the
    cross-shard ``combine_attn_stats`` reduction; stats mode always runs
    the XLA gather path (the stats-emitting kernel is the ROADMAP
    ring-fusion leftover), so ``backend`` only governs the plain
    single-shard dispatch.

    ``fresh`` is ``paged_attention_write``'s: it hands the entries over
    only where ``decode_writes_in_kernel`` holds, i.e. where this dispatch
    ends in the kernel."""
    if stats and (latent_v or window or sink is not None):
        raise NotImplementedError(
            "context-parallel attention over a latent arena, a windowed "
            "layer or a sink logit is not done"
        )
    _check_backend(backend)
    if stats:
        return attn_stats_xla(
            q, k_arena, v_arena, layer, block_table, q_positions,
            kv_positions, scale, k_scale=k_scale, v_scale=v_scale,
        )
    path = decode_path(backend, q.shape[-1], k_arena, block_table)
    if path != "xla":
        return paged_attention_tpu(
            q, k_arena, v_arena, layer, block_table, q_positions,
            kv_positions, scale, interpret=path == "interpret",
            k_scale=k_scale, v_scale=v_scale, latent_v=latent_v,
            window=window, sink=sink, fresh=fresh,
        )
    if fresh is not None:
        raise ValueError("only the kernel stores a step's fresh entries")
    return paged_attention_xla(
        q, k_arena, v_arena, layer, block_table, q_positions,
        kv_positions, scale, k_scale=k_scale, v_scale=v_scale,
        latent_v=latent_v, window=window, sink=sink,
    )


def decode_writes_in_kernel(
    entries: int, quantized: bool, stats: bool, path: str
) -> bool:
    """Whether a decode step's fresh entries are stored by a KERNEL that
    leaves the arena where it lies instead of by ``write_block_kv``'s
    scatters — K and V by the attention call itself
    (``paged_attention_write`` → ``paged_attention_tpu(fresh=)``), a
    selecting model's index keys by ``write_rows_tpu``: what the step
    program's statics say — ONE entry a row (a decode step; a verify's ``K +
    1`` entries are rows), a plain arena (an int8/fp8 one keeps running
    per-block scales), no partial statistics (context parallel: its
    attention is the gather) and the attention itself on the kernel
    (``decode_path``: ``"kernel"`` or ``"interpret"``). The host asks the
    same question for its counter (``runtime/server.py``)."""
    return entries == 1 and not quantized and not stats and path != "xla"


def _write_rows_kernel(
    layer_ref,  # scalar-prefetch [1] — read by the index maps only
    tbl_ref,  # scalar-prefetch [B, T] — index maps only
    ok_ref,  # scalar-prefetch [B] — index maps only: 0 = the trash block
    col_ref,  # scalar-prefetch [B] — the entry's column
    *refs,  # per arena: new [1, Nkv, D] (the row's fresh entry), then per
    #   arena the tile [Nkv, SUB, D] read, then the same tile written
    column,  # the map from a row's column operand to its clipped column
    block_size,
):
    n = len(refs) // 3
    slot = column(col_ref[pl.program_id(0)]) % block_size
    for new_ref, old_ref, out_ref in zip(
        refs[:n], refs[n:2 * n], refs[2 * n:]
    ):
        _entry_into_tile(
            new_ref[0], old_ref, out_ref, slot % old_ref.shape[1]
        )


@jax.named_scope("kv_write")
def write_rows_tpu(
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] pooled key blocks
    v_arena: jnp.ndarray,  # [L, NB, Nkv, BS, Dv]; Dv == 0: a latent arena
    layer,  # scalar int32
    block_table: jnp.ndarray,  # [B, T]
    cols: jnp.ndarray,  # [B] int32 — each row's ONE fresh entry's column
    k_new: jnp.ndarray,  # [B, Nkv, D]
    v_new: jnp.ndarray,  # [B, Nkv, Dv] (not read over a latent arena)
    valid=None,  # scalar or [B] bool — False: the layer's trash block
    interpret: bool = False,
):
    """``write_block_kv`` at ONE entry a row over a plain arena as a KERNEL
    that leaves the arena where it lies: each arena rides in ONCE, aliased
    over itself (``input_output_aliases``, as ``ops/ssm.py`` carries its
    state), and one grid step a row reads the sublane tile ``(Nkv, SUB,
    D)`` of the entry's block that holds its slot, puts the fresh entry
    into it in VMEM and hands it back — keys and values in the same step.
    A block no row names is never touched, and XLA sees no scatter into
    the arena (on the chip the scatter pair cost more device time than the
    attention it fed: PERF.md, PR 46).

    The same bytes land in the same blocks as ``write_block_kv``'s: a
    trash-mapped column and a gated entry (``valid`` False) name block 0
    of the layer BY ADDRESS (the index map reads the gate), so no owned
    block is read back or rewritten; only the sink can be named by two
    rows, and it is a garbage sink by contract (its other rows go back as
    one of the racing steps read them). A row's frontier block is its own
    (radix-shared prefix blocks are full), so no step reads what another
    writes. The table and the columns ride as scalar-prefetch operands and
    the index maps look the block up, so no XLA operation prepares the
    call but the gate's conversion.

    What still calls it: ``write_index_keys`` alone (a selecting model's
    index arena — the score call that reads it runs BEFORE the attention,
    so the attention cannot be its writer). K and V went this way from PR
    46 to PR 60, a call of their own before ``paged_decode``, because that
    kernel then took the arena once per sub-block ``BlockSpec`` ref, and
    XLA answers a buffer that one call both reads through other operands
    and aliases to an output with a copy of the WHOLE arena a layer call;
    since PR 54 ``paged_decode`` takes each arena once and copies its
    blocks by hand, and since PR 61 it stores K's and V's fresh entry
    itself, in the frontier cell it already holds
    (``paged_attention_tpu(fresh=)``)."""
    B, Nkv, _ = k_new.shape
    BS = k_arena.shape[3]
    W = block_table.shape[1] * BS

    # the clip (as write_block_kv's) and the gather through the table are
    # the index map's: scalar work on operands the kernel prefetches, no
    # XLA operation a layer call
    def column(c):
        return jnp.clip(c, 0, W - 1)

    ok = jnp.broadcast_to(
        jnp.ones((), jnp.int32) if valid is None
        else jnp.asarray(valid).astype(jnp.int32), (B,)
    )
    sub = entry_tile_rows(k_arena.dtype, BS)  # the tile a step moves
    arenas = [(k_arena, k_new)]
    if v_arena.shape[-1]:  # a latent arena holds no values
        arenas.append((v_arena, v_new))

    def of_row(b, lyr, tbl, ok, col):
        return (b, 0, 0)

    def tile_index(b, lyr, tbl, ok, col):
        c = column(col[b])
        blk = jnp.where(ok[b] != 0, tbl[b, c // BS], 0)  # by ADDRESS
        return (lyr[0], blk, 0, c % BS // sub, 0)

    new_specs, tile_specs, news = [], [], []
    for arena, new in arenas:
        D = arena.shape[-1]
        new_specs.append(pl.BlockSpec((1, Nkv, D), of_row))
        tile_specs.append(pl.BlockSpec((None, None, Nkv, sub, D), tile_index))
        news.append(new.astype(arena.dtype))
    n = len(arenas)
    out = pl.pallas_call(
        functools.partial(_write_rows_kernel, column=column, block_size=BS),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a, _ in arenas],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=new_specs + tile_specs,
            out_specs=tile_specs,
        ),
        # each arena over itself: operand 4 + n + i is output i
        input_output_aliases={4 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_kv_write",
    )(
        _layer_operand(layer), block_table, ok, cols.astype(jnp.int32), *news,
        *(a for a, _ in arenas),
    )
    return out[0], (out[1] if n == 2 else v_arena)


def paged_attention_write(
    q: jnp.ndarray,  # [B, S, Nh, D] (RoPE'd)
    k_new: jnp.ndarray,  # [B, S, Nkv, D] the step's fresh keys
    v_new: jnp.ndarray,  # [B, S, Nkv, Dv]
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] — the layer-stacked pool
    v_arena: jnp.ndarray,
    layer,  # scalar int32
    block_table: jnp.ndarray,  # [B, T]
    cols: jnp.ndarray,  # [B, S] logical columns of the fresh entries
    q_positions: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, T*BS]
    valid=None,  # scalar or [B, S] bool — write_block_kv's gate
    scale: float | None = None,
    backend: str = "auto",
    k_scale: jnp.ndarray = None,  # [L, NB, Nkv] — quantized arenas only
    v_scale: jnp.ndarray = None,
    stats: bool = False,
    latent_v: int = 0,
    window: int = 0,
    sink: jnp.ndarray = None,
    select=None,  # a ``Selection``: the query attends the keys it chose
):
    """A DECODE layer call's two halves as one op: the step's fresh K/V
    lands in the arena and ``paged_attention`` attends it (the fresh
    entries included). Returns ``(out, k_arena, v_arena, k_scale,
    v_scale)`` — the scales None over a plain arena, ``out`` the ``(acc, m,
    l)`` triple with ``stats``.

    HOW the entries land follows what the call can see
    (``decode_writes_in_kernel``), never an option: one entry a row over a
    plain arena with the attention on its kernel → the entries ride INTO
    the attention call, which stores them from the frontier cell it holds
    (``paged_attention_tpu(fresh=)``: ONE Pallas call a layer,
    ``paged_decode``, the arena aliased over itself; under a selection
    too, whichever side of ``selected_attention``'s ``cond`` made the key
    positions); everything else — a verify's ``S = K + 1`` entries, an
    int8/fp8 arena's running scales, context parallel's partial
    statistics, the XLA path (the CPU mesh) — ``write_block_kv``'s
    scatter, then the attention. The stored bytes of every OWNED block and
    the scores are the same on both; a gated entry and a trash-mapped
    column land in block 0 on the scatter's path and nowhere on the
    kernel's (a garbage sink by contract: nothing reads it)."""
    path = "xla" if stats else decode_path(
        backend, q.shape[-1], k_arena, block_table
    )
    ks, vs, fresh = k_scale, v_scale, None
    if decode_writes_in_kernel(q.shape[1], k_scale is not None, stats, path):
        ok = True if valid is None else valid
        fresh = Fresh(
            k_new[:, 0], None if v_new is None else v_new[:, 0], cols[:, 0],
            ok[:, 0] if jnp.ndim(ok) else ok,
        )
    else:
        wrote = write_block_kv(
            k_arena, v_arena, layer, block_table, cols, k_new, v_new,
            valid=valid, k_scale=k_scale, v_scale=v_scale,
        )
        k_arena, v_arena, ks, vs = (
            wrote if k_scale is not None else (*wrote, None, None)
        )
    if select is not None:
        out = selected_attention(
            q, k_arena, v_arena, layer, block_table, q_positions,
            kv_positions, select, scale, backend=backend, fresh=fresh,
        )
    else:
        out = paged_attention(
            q, k_arena, v_arena, layer, block_table, q_positions,
            kv_positions, scale, backend=backend, k_scale=ks, v_scale=vs,
            stats=stats, latent_v=latent_v, window=window, sink=sink,
            fresh=fresh,
        )
    if fresh is not None:
        out, k_arena, v_arena = out
    return out, k_arena, v_arena, ks, vs


# ---- token selection (a learned sparse-attention indexer) ------------------
# A token-selecting model (``cfg.sparse_attn``; DeepSeek-V3.2-Exp's indexer,
# here over GQA) keeps ONE index key a token and layer in a THIRD arena,
# ``[L, NB, 1, BS, lanes]`` (the key padded with zeros to whole 128-lane tiles,
# ``cfg.index_cache_dim``), in the same blocks under the same table as K and V.
# A query scores every live index key of its row (``index_scores``), keeps the
# ``topk`` best (``select_mask``: a mask over the row's columns; all of them
# while the context is no longer than that) and attends those tokens ONLY.
# Nothing reads the choice in order, so nothing sorts: the mask is "at or above
# the ``topk``-th largest score", that score found by a search over the scores'
# bits and a tie at it resolved, lower column first, by the same search over the
# column's (PR 51; ``lax.top_k`` over a slot's ``[4, 9216]`` scores was the
# largest device operation of a step). The search has TWO FORMS, one algorithm,
# and ``select_mask`` takes the one its call can observe (``select_path``: the
# scores' shape and the backend, no argument): a decode step's FEW queries on a
# TPU run it as ONE Pallas call (``select_topk_tpu``, PR 58: the scores held in
# VMEM, every pass a turn of a loop in the body, the live rows only — eleven
# dependent XLA fusions over all four rows were 12.5 us a layer call for a
# search of one row that takes 3.2); a chunk's ``[256, W]`` queries and every
# off-TPU call run it as XLA operations, which stay in ``select_mask`` as they
# were: they are also what the tests hold the kernel to, bit for bit.
# ``select_tokens`` is the same choice as
# a sorted LIST (``lax.top_k``, stable): no path of the program runs it — it is
# the oracle the tests hold the mask to, and with ``select_mask`` one of the two
# names the benchmark's controls replace (``benchmark/tests/
# calibrate_keye_vl2.py``, ``test_keye_vl2_block.py``), so every selecting path
# goes through ``select_mask(scores, topk)`` — two arguments, looked up in this
# module where the step is traced — and the score call and the search stay two
# calls. A decode step hands the mask to the
# attention as KEY POSITIONS — a column the query did not choose sits at the
# sentinel (``selected_attention``) — and ``paged_attention`` runs as it is: it
# streams the row's live blocks once, where they lie, and its own position test
# keeps ``min(context, topk)`` tokens a row and layer (a row gather of the
# chosen tokens costs the chip ~10 ns a row of ``D``, ~150 us a layer call at
# any context, where the walk takes 22 us up to 2.5 k of context and 59 at
# 8.7 k: PERF.md, PR 49 and 50). A prefill chunk masks the others
# out of the dense product over the row's window (``selected_prefill``: one
# mask a QUERY, which the kernels' one position a key cannot carry). While no
# query of the call reaches past ``topk`` keys the selection is everything:
# nothing is scored, and both take the key positions / the unselected kernel
# as they are (``lax.cond``).


class Selection(NamedTuple):
    """What a layer hands its attention so that it selects: the index queries
    ``[B, S, Hi, Di]`` (rotated), a weight an index head ``[B, S, Hi]`` f32
    (the two ``^-1/2`` factors folded in), the layer-stacked index arena with
    this step's index keys already written, and how many keys a query keeps."""

    qi: jnp.ndarray
    wi: jnp.ndarray
    idx_arena: jnp.ndarray
    topk: int


def write_index_keys(
    idx_arena: jnp.ndarray,  # [L, NB, 1, BS, Di]
    layer,  # scalar int32
    block_table: jnp.ndarray,  # [B, T]
    cols,  # [B, S] the entries' columns (a decode step), or a chunk's first
    #   column, a scalar (its rows share their columns: ``write_chunk_kv``)
    ki: jnp.ndarray,  # [B, S, 1, Di]
    valid=None,
    backend: str = "auto",
) -> jnp.ndarray:
    """The step's index keys into the blocks the table names — K's writers
    over an arena of one head and NO values (a zero-wide value array, as a
    latent arena has): whole-block tiles for a chunk, the write kernel for a
    decode step whose attention is on its kernel, the row-wise scatter
    otherwise. An invalid entry lands in block 0 of its layer."""
    empty = jnp.zeros((*idx_arena.shape[:-1], 0), idx_arena.dtype)
    # a stored index key is whole 128-lane tiles, zeros past its own width
    ki = jnp.pad(ki, [(0, 0)] * 3 + [(0, idx_arena.shape[-1] - ki.shape[-1])])
    if jnp.ndim(cols) == 0:
        return write_chunk_kv(
            idx_arena, empty, layer, block_table, cols, ki, ki, valid=valid
        )[0]
    path = decode_path(backend, idx_arena.shape[-1], idx_arena, block_table)
    if decode_writes_in_kernel(ki.shape[1], False, False, path):
        return write_rows_tpu(
            idx_arena, empty, layer, block_table, cols[:, 0], ki[:, 0], None,
            valid=valid if valid is None or not jnp.ndim(valid)
            else valid[:, 0],
            interpret=path == "interpret",
        )[0]
    return write_block_kv(
        idx_arena, empty, layer, block_table, cols, ki, ki, valid=valid
    )[0]


def _attendable(block_table, q_positions, kv_positions, block_size):
    """``[B, S, W]``: the columns a query may attend — a key position at or
    before it in a block the row owns (a stale index key in the trash block
    or in a block another request left behind sits at the sentinel)."""
    from ..models.cache import POS_SENTINEL  # models imports this module

    live = jnp.repeat(block_table != 0, block_size, axis=1)  # [B, W]
    kv = kv_positions[:, None, :]
    return (
        (kv <= q_positions[:, :, None]) & (kv < POS_SENTINEL)
        & live[:, None, :]
    )


def _index_kernel(
    layer_ref,  # scalar-prefetch [1] — the layer of the stack the copies read
    tbl_ref,  # scalar-prefetch [B, T] (the copies' block ids + the trash gate)
    nlive_ref,  # scalar-prefetch [B] — the row's frontier (_live_blocks)
    qi_ref,  # [B, Hi, lanes] every row's index queries
    wi_ref,  # [B, Hi, 1] f32 a weight an index head
    arena,  # the index arena where it lies in HBM, [L, NB, 1, BS, lanes]
    out_ref,  # [B, T·BS] f32: every row's scores by logical column
    buf,  # scratch: a cell's index keys, TWO slots, [2, bps·BS, lanes]
    sem,  # a DMA semaphore a slot
    bps,
):
    B = qi_ref.shape[0]
    BS = arena.shape[3]
    W = bps * BS  # a cell's columns

    def cells(b):
        """Row ``b``'s walk: the table cells ``0 <= c < cells(b)``."""
        return (nlive_ref[b] + bps - 1) // bps

    def next_live(b):
        """The first row at or after ``b`` with a cell to walk; ``B``: none."""
        def dead(r):
            return (r < B) & (cells(jnp.minimum(r, B - 1)) <= 0)
        return jax.lax.while_loop(dead, lambda r: r + 1, b)

    def copies(slot, b=0, c=0, fetch=True):
        """The async copies that bring cell ``c`` of row ``b`` into ``slot``:
        a block's one contiguous ``(BS, lanes)`` tile at ``(layer, table[b,
        idx])``, read where it lies. An entry past the frontier inside the
        frontier's cell names the trash block. ``fetch=False``: the same
        copies to WAIT on (a wait reads its copy's size and semaphore, not
        its source: no table reads)."""
        out = []
        for j in range(bps):
            idx = c * bps + j
            at = (layer_ref[0], jnp.where(
                idx < nlive_ref[b], tbl_ref[b, idx], 0
            )) if fetch else (0, 0)
            out.append(pltpu.make_async_copy(
                arena.at[(*at, 0)], buf.at[slot, pl.ds(j * BS, BS)],
                sem.at[slot],
            ))
        return out

    # a cell the walk never visits reads zeros (masked by position outside)
    out_ref[...] = jnp.zeros_like(out_ref)

    def cell(carry):
        """One cell of the walk: the rows' live cells end to end, the next
        cell's copies in flight (the other slot) while this one is scored."""
        b, c, slot = carry
        last = c + 1 >= cells(b)  # the row's frontier cell
        nb = next_live(jnp.where(last, b + 1, b))
        nc = jnp.where(last, 0, c + 1)

        @pl.when(nb < B)
        def _prefetch():
            for cp in copies(1 - slot, nb, nc):
                cp.start()

        for cp in copies(slot, fetch=False):
            cp.wait()

        tiles = []
        for j in range(bps):
            k = buf[slot, j * BS:(j + 1) * BS]  # [BS, lanes]
            idx = c * bps + j
            # a trash block's garbage may be non-finite: it scores as zeros
            # (and its columns are masked by position outside). where(), not
            # multiply — Inf * 0 is itself NaN.
            live = (idx < nlive_ref[b]) & (tbl_ref[b, idx] != 0)
            tiles.append(jnp.where(live, k, jnp.zeros_like(k)))
        # ONE dot a cell, a column a dot product of its own: a column's score
        # does not depend on how wide the shapes make a cell
        s = jax.lax.dot_general(
            qi_ref[b], jnp.concatenate(tiles, axis=0),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # [Hi, bps·BS]
        # a cell's columns start on a lane tile (``index_blocks_per_cell``);
        # a table of one cell has no other cell to start anywhere
        col = 0 if W == out_ref.shape[1] else (
            pl.multiple_of(c * W, 128) if W % 128 == 0 else c * W
        )
        out_ref[pl.ds(b, 1), pl.ds(col, W)] = jnp.sum(
            wi_ref[b] * jnp.maximum(s, 0.0), axis=0, keepdims=True
        )
        return nb, nc, 1 - slot

    b0 = next_live(jnp.int32(0))

    @pl.when(b0 < B)
    def _first():
        for cp in copies(0, b0, 0):
            cp.start()

    jax.lax.while_loop(
        lambda carry: carry[0] < B, cell, (b0, jnp.int32(0), jnp.int32(0)),
    )


@functools.partial(jax.jit, static_argnames=("interpret", "blocks_per_cell"))
def index_scores_tpu(qi, wi, idx_arena, layer, block_table, q_positions,
                     kv_positions, interpret: bool = False,
                     blocks_per_cell: int | None = None):
    """The decode kernel's walk (``paged_attention_tpu``) over the INDEX
    arena: ONE invocation walks the rows' live cells end to end in a loop of
    its body (``_live_blocks`` gives each row's frontier; a dead row costs a
    compare), a cell being ``bps`` consecutive table entries of one row
    (``blocks_per_cell``, ``index_blocks_per_cell`` of the shapes when None).
    The arena rides in ONCE, where it lies in HBM, and the body fetches a
    cell's blocks BY HAND — one async copy a block, the contiguous ``(BS,
    lanes)`` tile at ``(layer, table[b, idx])`` — into one of two VMEM
    slots, the next cell's copies started before this one is waited on and
    scored: against the row's ``Hi`` index queries in one dot, ``relu``,
    weighted and summed over the index heads. A block was a ``BlockSpec``
    operand of a grid over the cells until PR 56: eight refs' pipeline
    bookkeeping a grid step for 64 KiB (PERF.md, PR 56). ``qi [B, Hi,
    lanes]`` (padded to the stored key's lanes), ``wi [B, Hi]`` f32 → ``[B,
    T·BS]`` f32 scores by logical column, held in VMEM whole for the call
    (147 KiB at Keye's ``[4, 9216]``; a cell stores its columns at its own
    lane offset) and written back once, in the layout the search reads; a
    cell the walk did not visit reads ZEROS (the caller masks by
    position)."""
    B, Hi, lanes = qi.shape
    BS = idx_arena.shape[3]
    T = block_table.shape[1]
    bps = blocks_per_cell or index_blocks_per_cell(
        T, BS, lanes, idx_arena.dtype.itemsize
    )
    if T % bps != 0:
        raise ValueError(
            f"blocks_per_cell={bps} does not divide the table width {T}"
        )
    nlive = _live_blocks(block_table, q_positions, kv_positions)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    return pl.pallas_call(
        functools.partial(_index_kernel, bps=bps),
        out_shape=jax.ShapeDtypeStruct((B, T * BS), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                whole((B, Hi, lanes)), whole((B, Hi, 1)),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=whole((B, T * BS)),
            scratch_shapes=[
                pltpu.VMEM((2, bps * BS, lanes), idx_arena.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="index_scores",
    )(
        _layer_operand(layer), block_table, nlive, qi,
        wi.astype(jnp.float32)[..., None], idx_arena,
    )


@jax.named_scope("indexer")
def index_scores(select: Selection, layer, block_table, q_positions,
                 kv_positions, ok, path: str = "xla") -> jnp.ndarray:
    """``I[b, s, w] = sum_j wi[b, s, j] · relu(qi[b, s, j] · ki[w])`` in
    float32 over the row's logical window (the index arena's blocks through
    the table), ``-inf`` where ``ok`` is False. ``path`` (``decode_path``):
    a decode step whose attention is on its kernel scores through
    ``index_scores_tpu``, which reads the rows' LIVE blocks where they lie;
    everything else gathers the window."""
    B, T = block_table.shape
    lanes = select.idx_arena.shape[-1]
    # the stored key's lanes past its own width are zeros: so are the query's
    qi = jnp.pad(
        select.qi, [(0, 0)] * 3 + [(0, lanes - select.qi.shape[-1])],
    )
    if path != "xla" and qi.shape[1] == 1:
        score = index_scores_tpu(
            qi[:, 0], select.wi[:, 0], select.idx_arena, layer, block_table,
            q_positions, kv_positions, interpret=path == "interpret",
        )[:, None]
        return jnp.where(ok, score, -jnp.inf)
    ki = select.idx_arena[layer, block_table, 0]  # [B, T, BS, lanes]
    ki = ki.reshape(B, -1, lanes)
    s = jnp.einsum(
        "bshd,bwd->bshw", qi, ki, preferred_element_type=jnp.float32
    )
    score = jnp.einsum("bsh,bshw->bsw", select.wi, jax.nn.relu(s))
    return jnp.where(ok, score, -jnp.inf)


@jax.named_scope("select")
def select_tokens(scores: jnp.ndarray, topk: int):
    """The ``topk`` best-scored columns of each query, ``[..., K]`` (``K =
    min(topk, W)``) and whether each is a real choice (a query with fewer
    attendable keys than ``K`` fills up with masked ones). A tie goes to the
    lower column (``lax.top_k`` is stable), which is the lower position. The
    program attends through ``select_mask``; this is the oracle its tests hold
    that to, and one of the two names the benchmark's controls replace."""
    vals, cols = jax.lax.top_k(scores, min(topk, scores.shape[-1]))
    return cols.astype(jnp.int32), vals > -jnp.inf


def _largest_digits(holds, nbits: int, bits: int, batch) -> jnp.ndarray:
    """The largest ``nbits``-bit number ``v`` of each query, ``[..., 1]``
    int32, for which ``holds(v)`` is true, where ``holds`` takes candidates
    ``[..., D]``, is true at 0 and false from some number on: built from the
    top bit down, ``bits`` a pass — a pass tries every value of its digit at
    once and keeps the largest that holds (as many as hold: they are the
    lowest ones)."""
    v = jnp.zeros((*batch, 1), jnp.int32)
    for shift in range(nbits - bits, -bits, -bits):
        shift, width = max(shift, 0), min(bits, shift + bits)
        digits = jnp.arange(1, 1 << width, dtype=jnp.int32) << shift
        digit = jnp.sum(holds(v | digits), axis=-1, keepdims=True,
                        dtype=jnp.int32)
        v = v | (digit << shift)
    return v


#: the search's kernel holds a step's scores and its mask in VMEM whole: this
#: many bytes of float32 scores at most (16 queries of 32 k columns)
SELECT_KERNEL_BYTES = 2 << 20


def select_path(batch, width: int) -> str:
    """The form ``select_mask``'s search takes for ``batch`` queries over
    ``width`` columns, from what the call can observe: ``kernel`` (ONE Pallas
    call, ``select_topk_tpu``) for a decode step's FEW queries (the count
    that already chose 3 bits a pass) over whole lane tiles that VMEM holds,
    on a TPU; ``interpret``, the same emulated, where ``PAGED_FORCE_KERNEL``
    asks for it; ``xla`` everywhere else — a chunk's many queries, every
    off-TPU call. The backend is ``ops/moe.resolve_backend``'s: the
    ``PAGED_FORCE_KERNEL`` override, else the platform."""
    from .moe import resolve_backend  # moe imports this module

    n = math.prod(batch)
    if not (0 < n <= 16 and width % 128 == 0
            and n * width * 4 <= SELECT_KERNEL_BYTES):
        return "xla"
    return resolve_backend("auto")


def _select_kernel(x_ref, out_ref, *, K: int, nbits: int, bits: int):
    """``select_mask`` of every row of ``x_ref [B, W]`` into ``out_ref``
    (int32, 1 = kept): the parent's digit search with everything it reads
    held in VMEM and every pass — the compares, the count, the next
    candidate — a turn of a loop INSIDE the body. A row is re-laid once as
    ``[W / 128, 128]``, WHOLE vregs (nine a thousand columns; the slot's
    ``[4, W]`` tiles hold a row in every fourth sublane) — as a VALUE: a
    reshape of the ref compiles too and reads other columns on the chip
    (PERF.md, PR 58) —, and a row of ``-inf`` alone (a dead row) is zeros
    for one compare. ``nbits``: a column's bits; ``bits`` a pass."""
    B, W = x_ref.shape
    R, lanes = W // 128, 128
    lowest = jnp.int32(-(2 ** 31))
    dead_key = jnp.int32(-(2 ** 31) + 0x00800000)  # -inf's key

    def count(hit):  # [R, 128] bool → [1, 1]: a vector, no scalar round trip
        return jnp.sum(hit.astype(jnp.int32), axis=(0, 1), keepdims=True)

    def largest(holds, n):
        """``_largest_digits`` for one row: the largest ``n``-bit ``v``
        ``[1, 1]`` for which ``holds(v)``, ``bits`` a pass from the top
        (the passes tile the ``n`` bits from bit 0 up: a digit of the first
        pass that reaches past the top bit holds nothing)."""
        passes = -(-n // bits)

        def one(i, v):
            shift = (passes - 1 - i) * bits
            digit = jnp.zeros((1, 1), jnp.int32)
            for j in range(1, 1 << bits):
                fits = shift + j.bit_length() <= n
                digit += (holds(v | (jnp.int32(j) << shift)) & fits).astype(
                    jnp.int32)
            return v | (digit << shift)

        return jax.lax.fori_loop(
            0, passes, one, jnp.zeros((1, 1), jnp.int32))

    def row(b, carry):
        x = x_ref[pl.ds(b, 1), :].reshape(R, lanes)
        raw = jax.lax.bitcast_convert_type(x, jnp.int32)
        key = jnp.where(raw < 0, lowest - raw, raw)

        def keep(mask):  # the row's mask, back in the slot's layout
            out_ref[pl.ds(b, 1), :] = mask.astype(jnp.int32).reshape(1, W)

        keep(jnp.zeros((R, lanes), jnp.bool_))

        @pl.when(jnp.max(key) > dead_key)
        def _search():
            kth = largest(
                lambda v: count(key >= (v ^ lowest)) >= K, 32) ^ lowest
            above = key > kth
            tie = (key == kth) & (x > -jnp.inf)
            left = K - count(above)
            keep(above | tie)

            @pl.when(jnp.sum(tie.astype(jnp.int32))
                     > K - jnp.sum(above.astype(jnp.int32)))
            def _lowest_ties():
                col = (
                    jax.lax.broadcasted_iota(jnp.int32, (R, lanes), 0) * lanes
                    + jax.lax.broadcasted_iota(jnp.int32, (R, lanes), 1)
                )
                last = largest(
                    lambda c: count(tie & (col < c)) < left, nbits)
                keep(above | (tie & (col <= last)))

        return carry

    jax.lax.fori_loop(0, B, row, 0)


@functools.partial(jax.jit, static_argnames=("topk", "bits", "interpret"))
def select_topk_tpu(scores, topk: int, bits: int = 3,
                    interpret: bool = False):
    """``select_mask`` of a decode step's ``[B, W]`` float32 scores (``W`` a
    whole number of lane tiles) as ONE Pallas call: the scores ride in whole,
    as the score kernel wrote them (147 KiB at Keye's ``[4, 9216]``), and
    the body walks the rows: a row with a score above ``-inf`` is searched
    (``bits`` a pass, the passes a loop of the body, not unrolled), a dead
    row is a compare. The mask leaves as int32 and is a bool again in the
    fusion that reads it."""
    B, W = scores.shape

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    return pl.pallas_call(
        functools.partial(
            _select_kernel, K=min(topk, W),
            nbits=max(W - 1, 1).bit_length(), bits=bits,
        ),
        out_shape=jax.ShapeDtypeStruct((B, W), jnp.int32),
        grid=(1,),
        in_specs=[whole((B, W))],
        out_specs=whole((B, W)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="select_topk",
    )(scores) != 0


@jax.named_scope("select")
def select_mask(scores: jnp.ndarray, topk: int) -> jnp.ndarray:
    """``select_tokens`` as a mask over the window ``[..., W]``: above the
    ``topk``-th largest score, and of the columns that tie with it the lowest
    ones, as many as are left to keep — the very set ``select_tokens`` lists,
    found by a SEARCH, not a sort: nothing reads the chosen columns in order
    (the sort of a slot's ``[4, 9216]`` scores was 70 us a layer call, the
    largest device operation of a Keye step; the search is 12-13: PERF.md,
    PR 51). The ``topk``-th VALUE is the largest number that at least
    ``topk`` scores reach, built digit by digit over the scores' bits taken
    as integers in float order (``_largest_digits``: a pass counts the scores
    at or above every candidate of a digit in one read); where more columns
    tie with it than are left to keep, the last COLUMN kept is found the same
    way over the column's bits. Exact: float32 scores, IEEE ``==`` ties (the
    two zeros tie) and the lower column first, as ``lax.top_k``'s stable
    order has it.

    Which form runs is read from the call itself (``select_path``): few
    queries (a decode step's slot) over whole lane tiles on a TPU — or
    wherever ``PAGED_FORCE_KERNEL`` asks for the kernel emulated — take ONE
    Pallas call, ``select_topk_tpu`` (3.2 us a layer call at one live row of
    four where the operations below take 12.5: PERF.md, PR 58); every other
    call — a chunk's many queries, the CPU — takes the XLA operations below,
    which are also the form the tests hold the kernel to. The signature is
    the seam the benchmark's controls replace: it takes no third argument."""
    W = scores.shape[-1]
    K = min(topk, W)
    batch = scores.shape[:-1]
    path = select_path(batch, W)
    if path != "xla":
        return select_topk_tpu(
            scores.reshape(-1, W), topk,
            interpret=path == "interpret",
        ).reshape(scores.shape)
    # a pass costs its latency plus its compares: few queries (a decode
    # step's slot) take 3 bits a pass, many (a chunk's 256) 2 — timed on the
    # chip at [4, 9216] and [256, 9216] (PERF.md, PR 51)
    bits = 3 if math.prod(batch) <= 16 else 2
    lowest = jnp.int32(-(2 ** 31))
    # integer order = float order: a negative float's magnitude bits m map
    # to -m, so -0.0 lands on +0.0's key and -inf (an unattendable column, a
    # dead row) is the smallest
    raw = jax.lax.bitcast_convert_type(scores, jnp.int32)
    key = jnp.where(raw < 0, lowest - raw, raw)

    def reached_by_k(v):  # v counts up from the smallest key: an offset
        at_or_above = key[..., None, :] >= (v ^ lowest)[..., :, None]
        return jnp.sum(at_or_above, axis=-1, dtype=jnp.int32) >= K

    kth = _largest_digits(reached_by_k, 32, bits, batch) ^ lowest
    above = key > kth
    # never -inf: fewer attendable keys than K keeps every real one only
    tie = (key == kth) & (scores > -jnp.inf)
    left = K - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)

    def lowest_ties(_):
        col = jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, scores.ndim - 1)

        def fewer_before(c):  # fewer than ``left`` ties lie below column c
            before = tie[..., None, :] & (col[..., None, :] < c[..., :, None])
            return jnp.sum(before, axis=-1, dtype=jnp.int32) < left

        last = _largest_digits(
            fewer_before, max(W - 1, 1).bit_length(), bits, batch)
        return above | (tie & (col <= last))

    # the usual case: one tie, the topk-th score itself
    many = jnp.any(jnp.sum(tie, axis=-1, keepdims=True, dtype=jnp.int32) > left)
    return jax.lax.cond(many, lowest_ties, lambda _: above | tie, None)


def selected_attention(
    q, k_arena, v_arena, layer, block_table, q_positions, kv_positions,
    select: Selection, scale=None, backend: str = "auto", fresh=None,
):
    """A DECODE step's attention under a selection (one query a row): the
    selection is a MASK over the row's columns, and it enters the attention
    as key positions — a column the query did not choose sits at the
    sentinel, which no query position reaches. The mask is ``select_mask``'s,
    called with the scores and ``topk`` alone and looked up in this module
    at trace time (the benchmark's controls replace it by that name): on a
    TPU a slot's few queries run its search as the kernel ``select_topk``,
    after the score kernel and as a call of its own. ``paged_attention`` takes
    them as it is: the kernel walks the row's live blocks once, where they
    lie (``_live_blocks`` reads the masked positions: the walk ends at the
    last block that holds a chosen token, and a dead row costs nothing), and
    its own ``kv_pos <= q_pos`` test drops every other column; the XLA and
    ``interpret`` paths mask by the same positions. ``fresh``
    (``paged_attention``'s) goes to that one call after the ``cond``, so
    both of its sides store the step's entry — also where the selection
    did not keep the fresh key (the kernel's walk reaches its block all
    the same)."""
    from ..models.cache import POS_SENTINEL  # models imports this module

    if q.shape[1] != 1:
        raise NotImplementedError(
            "a selecting decode step takes one query a row"
        )
    ok = _attendable(block_table, q_positions, kv_positions, k_arena.shape[3])
    path = decode_path(backend, q.shape[-1], k_arena, block_table)

    def chosen(_):
        scores = index_scores(
            select, layer, block_table, q_positions, kv_positions, ok, path
        )[:, 0]
        keep = select_mask(scores, select.topk)  # [B, W]
        with jax.named_scope("select"):
            return jnp.where(keep, kv_positions, POS_SENTINEL)

    # while no row's query reaches past topk keys the selection is
    # everything: no score, no top-k, the key positions as they are
    beyond = jnp.any(jnp.sum(ok, axis=-1) > select.topk)
    kv = jax.lax.cond(beyond, chosen, lambda _: kv_positions, None)
    return paged_attention(
        q, k_arena, v_arena, layer, block_table, q_positions, kv, scale,
        backend=backend, fresh=fresh,
    )


def selected_prefill(
    q, k_arena, v_arena, layer, block_table, q_positions, kv_positions,
    select: Selection, scale=None, backend: str = "auto", walk=None,
):
    """A prefill CHUNK's attention under a selection: per query, the dense
    product over the row's window with every key the query did not choose
    masked out — a row at a time, so that what it builds is one row's
    ``[heads, chunk, window]`` scores."""
    B, S, Nh, D = q.shape
    Nkv, BS = k_arena.shape[2], k_arena.shape[3]
    ok = _attendable(block_table, q_positions, kv_positions, BS)

    def dense(_):
        return paged_prefill(
            q, k_arena, v_arena, layer, block_table, q_positions,
            kv_positions, scale, backend=backend, walk=walk,
        )

    def one_row(args):
        q_r, qi_r, wi_r, tbl_r, ok_r = args
        sel = Selection(qi_r[None], wi_r[None], select.idx_arena, select.topk)
        keep = select_mask(
            index_scores(sel, layer, tbl_r[None], None, None, ok_r[None]),
            select.topk,
        )[0]  # [S, W]
        with jax.named_scope("attn"):
            k, v = gather_block_kv(k_arena, v_arena, layer, tbl_r[None])
            qg = q_r.reshape(S, Nkv, Nh // Nkv, D)
            sc = jnp.einsum(
                "skgd,tkd->kgst", qg, k[0], preferred_element_type=jnp.float32
            ) * (D ** -0.5 if scale is None else scale)
            sc = jnp.where(keep[None, None], sc, jnp.float32(NEG_INF))
            p = jnp.exp(sc - sc.max(axis=-1, keepdims=True))
            p = jnp.where(keep[None, None], p, 0.0)
            p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
            o = jnp.einsum(
                "kgst,tkd->skgd", p.astype(v.dtype), v[0],
                preferred_element_type=jnp.float32,
            )
            return o.reshape(S, Nh, D).astype(q.dtype)

    def chosen(_):
        return jax.lax.map(
            one_row, (q, select.qi, select.wi, block_table, ok)
        )

    beyond = jnp.any(jnp.sum(ok, axis=-1) > select.topk)
    return jax.lax.cond(beyond, chosen, dense, None)
