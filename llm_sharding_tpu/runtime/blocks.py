"""Host-side KV block allocator for paged serving.

The dense serve state reserves the full cache capacity ``C`` per row up
front (``parallel/serve.make_state``: ``k/v [S, Lp, M, C, Nkv, Dh]``) — a
short request holds exactly as much HBM as the longest one the server can
admit. Paged mode (PagedAttention, Kwon et al., SOSP'23) replaces the
per-row reservation with a POOLED arena, head-major ``[S, Lp, num_blocks,
Nkv, block_size, Dh]``; each row owns only the blocks covering its actual prompt +
budget, mapped through a per-row block table the device programs gather
through (``parallel/serve.py``). This module is the host half: a free list
with per-block reference counts.

Design points:

- **Block 0 is the trash sink**, never allocated. Every unmapped table
  entry points at it, so the interleaved schedule's unconditional garbage
  writes (``serve_chunk``'s "a garbage write lands at an offset the next
  real serve overwrites") land in a block nobody attends — the paged
  analogue of a dense row's private padding columns. Freeing a row is
  therefore two steps in strict order: remap its table to the trash block
  on device, THEN return the blocks to the free list (dispatch order makes
  this safe: any in-flight program predates the remap, any later program
  sees trash — a recycled block is always fully re-initialized by its new
  owner's admission before anything reads it).
- **Refcounts enable block-level prefix sharing**: ``prefill_prefix``
  allocates the prefix's blocks once; every admission ``share()``s them
  into the row's table read-only and ``free()`` only returns a block to
  the pool when its last reference drops.
- **Exhaustion is a typed condition**, not a crash: ``alloc`` raises
  ``BlockExhausted``; the server checks ``num_free`` first and leaves
  requests queued (admission gated on free blocks — queue wait, FIFO
  order preserved).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

TRASH_BLOCK = 0  # reserved garbage sink; table entries default here

#: The one layout paged KV is stored in (``models/cache.paged_arena_shape``
#: behind the stage dim), everywhere it is stored: the device arena, the
#: host tier, the disk tier's entries, a paged snapshot. Persisted bytes
#: name it, so bytes written under another layout are refused (snapshots)
#: or dropped (disk entries) instead of being read wrongly.
PAGED_KV_LAYOUT = "S,L,NB,Nkv,BS,D"


class BlockExhausted(RuntimeError):
    """``alloc`` could not satisfy the request: every non-reserved block is
    held. Callers shed or queue the admission instead of corrupting rows."""


class BlockAllocator:
    """Free list + per-block refcounts over ``num_blocks`` KV blocks of
    ``block_size`` token slots each. Block 0 (``TRASH_BLOCK``) is reserved.
    NOT thread-safe on its own — the owning server serializes every call
    under its mutex."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block {TRASH_BLOCK} is the "
                f"reserved trash sink), got {num_blocks}"
            )
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # LIFO free list: a just-freed block is reused first, so a steady
        # admit/finish churn touches a small hot set of arena blocks
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))
        self._ref = np.zeros(num_blocks, np.int32)
        self._ref[TRASH_BLOCK] = 1  # pinned forever
        # blocks whose owning reference belongs to the PREFIX CACHE
        # (runtime/radix.py) rather than a live row: they are reusable —
        # evictable on demand — so occupancy/waste accounting must not
        # read a healthy cold cache as leaked memory
        self._cached = np.zeros(num_blocks, bool)

    # ------------------------------------------------------------------ API

    @property
    def capacity_blocks(self) -> int:
        """Allocatable blocks (the trash block never counts)."""
        return self.num_blocks - 1

    def bytes_per_block(
        self, *, num_layers: int, num_kv_heads: int, head_dim: int,
        kv_dtype, value_dim: Optional[int] = None, index_dim: int = 0,
    ) -> int:
        """Device bytes ONE arena block costs across all layers: K + V
        codes (``2 × L × BS × Nkv × Dh × itemsize``) plus, for quantized
        1-byte dtypes, the block's slice of the per-block-per-head f32
        scale arenas (``2 × L × Nkv × 4``). This is the sizing primitive
        behind the ``server_arena_bytes{dtype=...}`` gauge and the
        capacity table in README — at equal HBM budget,
        ``budget // bytes_per_block`` is how many blocks each dtype
        admits (int8 ≈ 2× bf16). ``value_dim`` is the width of a value
        entry where it is not the key's (a latent arena: 0); ``index_dim``
        the width of the ONE index key a token a token-selecting model keeps
        in a third arena under the same blocks."""
        item = np.dtype(kv_dtype).itemsize
        widths = head_dim + (head_dim if value_dim is None else value_dim)
        kv = num_layers * self.block_size * (
            num_kv_heads * widths + index_dim
        ) * item
        scales = 2 * num_layers * num_kv_heads * 4 if item == 1 else 0
        return kv + scales

    def arena_bytes(
        self, *, num_layers: int, num_kv_heads: int, head_dim: int,
        kv_dtype, value_dim: Optional[int] = None, index_dim: int = 0,
    ) -> int:
        """Total device bytes of this pool's arena (every block including
        the reserved trash sink — the arrays exist whether or not a block
        is allocatable)."""
        return self.num_blocks * self.bytes_per_block(
            num_layers=num_layers, num_kv_heads=num_kv_heads,
            head_dim=head_dim, kv_dtype=kv_dtype, value_dim=value_dim,
            index_dim=index_dim,
        )

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.capacity_blocks - len(self._free)

    # -------------------------------------------- prefix-cache accounting

    @property
    def cache_held(self) -> int:
        """Blocks whose owning reference is the prefix cache's."""
        return int(self._cached.sum())

    @property
    def cache_cold(self) -> int:
        """Cache-held blocks no live row currently maps (refcount is the
        tree's alone): the evictable-on-demand population the KV gauges
        subtract from \"in use\" so a warm cache never reads as waste."""
        return int((self._cached & (self._ref == 1)).sum())

    def mark_cached(self, blocks) -> None:
        """Tag allocated blocks as cache-owned (``runtime/radix.py`` calls
        this when a node takes ownership of a row's blocks or restores a
        demoted node)."""
        for b in blocks:
            if self._ref[b] < 1 or b == TRASH_BLOCK:
                raise ValueError(f"mark_cached of unallocated block {b}")
        self._cached[list(blocks)] = True

    def unmark_cached(self, blocks) -> None:
        self._cached[list(blocks)] = False

    def alloc(self, n: int) -> list[int]:
        """Take ``n`` blocks (refcount 1 each). Raises ``BlockExhausted``
        without partial allocation when fewer than ``n`` are free."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise BlockExhausted(
                f"need {n} KV blocks, {len(self._free)} free "
                f"(of {self.capacity_blocks})"
            )
        taken = [self._free.pop() for _ in range(n)]
        self._ref[taken] = 1
        return taken

    def alloc_at(self, start_col: int, n: int) -> list[int]:
        """``alloc`` with a placement HINT: ``start_col`` is the first
        logical column index (in blocks) the allocation will map. The
        single-pool allocator has no placement to prefer — this exists so
        callers can be shard-agnostic (``ShardedBlockAllocator`` overrides
        it to stripe ownership across context-parallel shards)."""
        return self.alloc(n)

    def share(self, blocks) -> None:
        """Add a reference to each of ``blocks`` (prefix sharing: a row maps
        an already-allocated block read-only into its table)."""
        for b in blocks:
            if self._ref[b] < 1 or b == TRASH_BLOCK:
                raise ValueError(f"share of unallocated/reserved block {b}")
            self._ref[b] += 1

    def free(self, blocks) -> None:
        """Drop one reference per block; a block returns to the free list
        when its last reference drops."""
        for b in blocks:
            if b == TRASH_BLOCK:
                raise ValueError("free of the reserved trash block")
            if self._ref[b] < 1:
                raise ValueError(f"double free of block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(int(b))

    def restore(self, private_rows, shared_rows) -> None:
        """Rebuild allocation state from a snapshot's per-row ownership
        lists (``runtime/server.py`` snapshot format 2): private blocks get
        refcount 1, shared blocks one reference per row mapping them. Must
        be called on a freshly constructed allocator."""
        if self.in_use:
            raise ValueError("restore on a non-empty allocator")
        free = set(self._free)
        for blocks in private_rows:
            for b in blocks:
                if b not in free:
                    raise ValueError(
                        f"snapshot block {b} double-owned or reserved"
                    )
                free.discard(b)
                self._ref[b] = 1
        for blocks in shared_rows:
            for b in blocks:
                if b in free:
                    free.discard(b)
                    self._ref[b] = 1
                elif self._ref[b] >= 1:
                    self._ref[b] += 1
                else:
                    raise ValueError(f"snapshot shared block {b} reserved")
        # keep LIFO order deterministic after restore
        self._free = sorted(free, reverse=True)

    def check(self) -> None:
        """Allocator invariant (the chaos suites call this after every
        lifecycle path): free list and refcounted blocks exactly partition
        the non-reserved pool, with no double entries."""
        free = self._free
        if len(set(free)) != len(free):
            raise AssertionError(f"free list has duplicates: {free}")
        for b in free:
            if b == TRASH_BLOCK or not (0 < b < self.num_blocks):
                raise AssertionError(f"bad free-list entry {b}")
            if self._ref[b] != 0:
                raise AssertionError(f"free block {b} has refcount {self._ref[b]}")
            if self._cached[b]:
                raise AssertionError(f"free block {b} still cache-marked")
        held = [
            b for b in range(1, self.num_blocks) if self._ref[b] > 0
        ]
        if len(held) + len(free) != self.capacity_blocks:
            raise AssertionError(
                f"{len(held)} held + {len(free)} free != "
                f"{self.capacity_blocks} blocks"
            )
        if self._ref[TRASH_BLOCK] != 1:
            raise AssertionError("trash block refcount must stay pinned at 1")


class ShardedBlockAllocator(BlockAllocator):
    """Per-shard free lists over a GLOBALLY indexed block id space — the
    host half of context-parallel paged serving (``serve(cp=N)``).

    Global block id ``gid = shard · blocks_per_shard + local``: the device
    arena is ``[S, Lp, cp · NB, ...]`` sharded contiguously on its block
    axis, so this layout makes gid arithmetic (``gid // NB`` = owning
    shard, ``gid % NB`` = local block) line up with the device placement —
    the server's host table mirror keeps gids and
    ``_push_tables`` projects them to per-shard LOCAL tables. EVERY
    shard's local block 0 (gid ``s · NB``) is that shard's trash sink,
    pinned exactly like the base allocator's global block 0: a column one
    shard owns maps to trash on every other shard, so unowned writes land
    in a block nobody attends.

    ``alloc_at`` stripes ownership round-robin by logical column with a
    greedy most-free fallback, so TOTAL free blocks (``num_free``) remains
    a correct admission bound: as long as ``n <= num_free``, n picks each
    find some shard with a free block — allocation never fails on a
    per-shard bottleneck. The flat base free list is kept in sync as a
    view so every inherited accounting property (``num_free``,
    ``in_use``, the KV gauges' reads) stays truthful."""

    def __init__(self, shards: int, blocks_per_shard: int, block_size: int):
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if blocks_per_shard < 2:
            raise ValueError(
                f"blocks_per_shard must be >= 2 (each shard's local block "
                f"0 is its reserved trash sink), got {blocks_per_shard}"
            )
        super().__init__(shards * blocks_per_shard, block_size)
        self.shards = shards
        self.blocks_per_shard = blocks_per_shard
        for s in range(1, shards):
            self._ref[s * blocks_per_shard] = 1  # pin per-shard trash
        self._shard_free: list[list[int]] = [
            list(range(
                (s + 1) * blocks_per_shard - 1, s * blocks_per_shard, -1
            ))
            for s in range(shards)
        ]
        self._sync_free()

    def _sync_free(self) -> None:
        # the base's flat list is a derived VIEW (num_free/in_use/gauges
        # read it); the per-shard lists are the source of truth
        self._free = [b for fl in self._shard_free for b in fl]

    def owner(self, gid: int) -> int:
        """Owning shard of a global block id."""
        return int(gid) // self.blocks_per_shard

    def owner_shards(self, blocks) -> list[int]:
        """Sorted distinct owner shards of a global block list — the
        per-shard pass order of an arena block stream (snapshot capture,
        hand-off, host-tier demote/restore): reads gather each listed
        shard's slice, writes land each block on its owner, and a
        ``cp_shard_stream`` fault keyed by one of these indices aborts
        the stream at exactly that shard."""
        return sorted({self.owner(b) for b in blocks})

    @property
    def capacity_blocks(self) -> int:
        """Allocatable blocks: each shard donates its local block 0."""
        return self.shards * (self.blocks_per_shard - 1)

    def _take(self, shard: int) -> int:
        b = self._shard_free[shard].pop()
        self._ref[b] = 1
        return b

    def alloc(self, n: int) -> list[int]:
        """Positionless ``n``-block grab (radix restore, embedding rows):
        balance by always taking from the most-free shard."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > self.num_free:
            raise BlockExhausted(
                f"need {n} KV blocks, {self.num_free} free "
                f"(of {self.capacity_blocks} across {self.shards} shards)"
            )
        taken = []
        for _ in range(n):
            s = max(range(self.shards), key=lambda i: len(self._shard_free[i]))
            taken.append(self._take(s))
        self._sync_free()
        return taken

    def alloc_at(self, start_col: int, n: int) -> list[int]:
        """Column-striped allocation: block ``j`` of the run (logical
        column ``start_col + j``) prefers shard ``(start_col + j) % cp``
        so one row's KV — and with it each decode step's fresh-token
        write and every prefill chunk's columns — spreads across shards;
        falls back to the most-free shard when the preferred list is dry
        (which is what makes total-free a sufficient admission bound)."""
        if n < 0:
            raise ValueError(f"alloc_at({start_col}, {n})")
        if n > self.num_free:
            raise BlockExhausted(
                f"need {n} KV blocks, {self.num_free} free "
                f"(of {self.capacity_blocks} across {self.shards} shards)"
            )
        taken = []
        for j in range(n):
            s = (int(start_col) + j) % self.shards
            if not self._shard_free[s]:
                s = max(
                    range(self.shards),
                    key=lambda i: len(self._shard_free[i]),
                )
            taken.append(self._take(s))
        self._sync_free()
        return taken

    def share(self, blocks) -> None:
        for b in blocks:
            if int(b) % self.blocks_per_shard == 0:
                raise ValueError(
                    f"share of reserved trash block {int(b)}"
                )
        super().share(blocks)

    def mark_cached(self, blocks) -> None:
        for b in blocks:
            if int(b) % self.blocks_per_shard == 0:
                raise ValueError(
                    f"mark_cached of reserved trash block {int(b)}"
                )
        super().mark_cached(blocks)

    def free(self, blocks) -> None:
        for b in blocks:
            b = int(b)
            if b % self.blocks_per_shard == 0:
                raise ValueError("free of a reserved trash block")
            if self._ref[b] < 1:
                raise ValueError(f"double free of block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._shard_free[b // self.blocks_per_shard].append(b)
        self._sync_free()

    def restore(self, private_rows, shared_rows) -> None:
        super().restore(private_rows, shared_rows)
        self._shard_free = [
            sorted(
                (b for b in self._free
                 if b // self.blocks_per_shard == s),
                reverse=True,
            )
            for s in range(self.shards)
        ]
        self._sync_free()

    def check(self) -> None:
        NB = self.blocks_per_shard
        flat = [b for fl in self._shard_free for b in fl]
        if sorted(flat) != sorted(self._free):
            raise AssertionError(
                "per-shard free lists drifted from the flat view"
            )
        if len(set(flat)) != len(flat):
            raise AssertionError(f"free list has duplicates: {flat}")
        for s in range(self.shards):
            if self._ref[s * NB] != 1:
                raise AssertionError(
                    f"shard {s} trash refcount must stay pinned at 1"
                )
            if self._cached[s * NB]:
                raise AssertionError(f"shard {s} trash block cache-marked")
            for b in self._shard_free[s]:
                if b // NB != s or b % NB == 0:
                    raise AssertionError(
                        f"free-list entry {b} misfiled under shard {s}"
                    )
                if self._ref[b] != 0:
                    raise AssertionError(
                        f"free block {b} has refcount {self._ref[b]}"
                    )
                if self._cached[b]:
                    raise AssertionError(f"free block {b} still cache-marked")
        held = [
            b for b in range(self.num_blocks)
            if b % NB != 0 and self._ref[b] > 0
        ]
        if len(held) + len(flat) != self.capacity_blocks:
            raise AssertionError(
                f"{len(held)} held + {len(flat)} free != "
                f"{self.capacity_blocks} blocks"
            )
