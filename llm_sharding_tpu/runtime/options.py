"""The options of a serving daemon, written once.

``PipelineEngine.serve(**kw)`` builds the record, ``PipelineServer`` reads
it (``srv.options``; each field is also an attribute of the server),
``snapshot()`` writes its portable fields and ``restore`` rebuilds it from
them, and the CLI fills it from its flags. Here are the names, the
defaults and the checks that need no model: ``validate()`` is what both
the server's constructor and the CLI — before minutes of model loading —
refuse an inconsistent set with, in the same words. ``PipelineEngine.serve``
tells what the options do together; ``PipelineServer`` what a model refuses.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Optional

logger = logging.getLogger("llm_sharding_tpu.server")

PAGED_ATTN = ("auto", "kernel", "xla")
PREFIX_CACHES = ("off", "hbm", "host", "disk")

#: options earlier builds wrote into a snapshot's ``serve_kwargs`` and this
#: build no longer has. Either changed only how the host stepped — output was
#: token-identical at every value — so ``from_snapshot`` drops them.
RETIRED = ("inflight_steps", "chunk_cycles")


def _local(default: Any):
    """A field that stays with its process (a path, a fault plan, an
    observability pace): ``snapshot()`` leaves it out."""
    return dataclasses.field(default=default, metadata={"portable": False})


@dataclasses.dataclass(frozen=True)
class ServeOptions:
    capacity: int = 1024
    batch_per_slot: int = 1
    # top-k/top-p are PER-REQUEST row state (dynamic arrays in the serve
    # programs — no recompile per request, VERDICT r3 next-#7); these are
    # only the defaults ``submit`` falls back to
    top_k: int = 0
    top_p: float = 1.0
    # chunked admission (r2 weak #4): prompts longer than this are prefilled
    # in bounded chunks with decode cycles interleaved, so a long admission
    # never stalls live streams. None → one-shot admit
    prefill_chunk: Optional[int] = None
    # how many chunk logs may stay in flight: 1 overlaps the fetch with the
    # next chunk's compute; 2 additionally hides the post-completion fetch
    # latency (the device→host copy) at the cost of tokens surfacing one
    # more chunk late (throughput mode)
    pipeline_depth: int = 1
    trace_path: Optional[str] = _local(None)
    speculate: int = 0
    spec_ngram: int = 3
    max_queue: Optional[int] = None
    default_deadline_s: Optional[float] = None
    fault_plan: Any = _local(None)  # runtime.faults.FaultPlan (tests/chaos)
    fault_retries: int = _local(3)
    fault_backoff_s: float = _local(0.01)
    retryable_exceptions: tuple = _local(())
    snapshot_every_s: Optional[float] = _local(None)
    snapshot_path: Optional[str] = _local(None)
    # paged KV (PagedAttention-style block-granular serving): together they
    # switch the serve state from per-row dense reservations ([.., M,
    # capacity, ..]) to a pooled arena ([.., kv_blocks, kv_block_size, ..])
    # with per-row block tables: a request holds only the blocks covering
    # its prompt + budget, so skewed-length workloads admit several times
    # more concurrent rows in the same HBM. Greedy output is token-identical
    # to dense (the programs see the same logical window either way)
    kv_block_size: Optional[int] = None
    kv_blocks: Optional[int] = None
    # "bf16" stores the arena in the engine's compute cache dtype — the exact
    # path. "int8"/"fp8" store 1-byte codes with per-block-per-head scales in
    # a parallel scale arena: ~2× the blocks at equal HBM and half the
    # decode-attention DMA bytes, at a bounded greedy-token drift (the one
    # intentionally non-bit-exact serve variant)
    kv_dtype: str = "bf16"
    paged_attn: str = "auto"
    # the automatic prefix cache (runtime/radix.py). "hbm": radix tree over
    # token ids — every submit transparently reuses the longest cached
    # prefix, finished rows' prompt blocks are indexed instead of freed, cold
    # entries evict under allocator pressure. "host": additionally demotes
    # cold blocks to a pinned host-RAM pool (device→host copy, streamed back
    # bit-exact on a later hit) before dropping — HBM becomes a cache level,
    # not a hard ceiling. "disk": additionally spills cold host-pool nodes to
    # memory-mapped files under a bounded on-disk pool that survives
    # restarts (promoted disk→host→arena on a later hit). Explicit
    # PrefixHandles remain the manual/pinned escape hatch and bypass the tree
    prefix_cache: str = "off"
    host_pool_blocks: int = 0
    disk_pool_dir: Optional[str] = None
    disk_pool_blocks: int = 0
    gauge_sweep_every_s: float = _local(0.0)
    cp: int = 1

    @classmethod
    def names(cls, portable_only: bool = False) -> tuple:
        return tuple(
            f.name for f in dataclasses.fields(cls)
            if not portable_only or f.metadata.get("portable", True)
        )

    def portable(self) -> dict:
        """What ``snapshot()`` carries as ``serve_kwargs``."""
        return {n: getattr(self, n) for n in self.names(portable_only=True)}

    @classmethod
    def from_snapshot(cls, serve_kwargs: dict) -> "ServeOptions":
        """The record of a snapshot's ``serve_kwargs`` — input from outside.
        A key an older format lacks takes the field's default; a ``RETIRED``
        key is dropped; any other unknown key is refused by name."""
        kw = dict(serve_kwargs)
        retired = {k: kw.pop(k) for k in RETIRED if k in kw}
        if retired:
            logger.info(
                "snapshot carries retired serve options %s: dropped (they "
                "changed how the host stepped, never the tokens)", retired,
            )
        unknown = sorted(set(kw) - set(cls.names(portable_only=True)))
        if unknown:
            raise ValueError(
                f"snapshot serve_kwargs carry unknown option(s) {unknown}: "
                "written by a newer build, or not a snapshot of this program"
            )
        return cls(**kw)

    @property
    def paged(self) -> bool:
        return self.kv_block_size is not None

    def validate(self) -> None:
        """Every check that needs no model and no device: ranges, and which
        options go together. ``ValueError`` names the options at fault."""
        from ..ops.quant import KV_DTYPES

        if self.cp < 1:
            raise ValueError(f"cp must be >= 1, got {self.cp}")
        chunk = self.prefill_chunk
        if chunk is not None and (chunk < 1 or chunk & (chunk - 1)):
            raise ValueError("prefill_chunk must be a power of two")
        if self.pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        if self.speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {self.speculate}")
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        if self.default_deadline_s is not None and self.default_deadline_s <= 0:
            raise ValueError(
                f"default_deadline_s must be > 0, got {self.default_deadline_s}"
            )
        if (self.kv_block_size is None) != (self.kv_blocks is None):
            raise ValueError(
                "kv_block_size and kv_blocks go together (got "
                f"kv_block_size={self.kv_block_size!r}, "
                f"kv_blocks={self.kv_blocks!r})"
            )
        if self.paged:
            bs = self.kv_block_size
            if bs < 1 or bs & (bs - 1):
                raise ValueError(
                    f"kv_block_size must be a power of two, got {bs}"
                )
            if self.kv_blocks < 2:
                raise ValueError(
                    f"kv_blocks must be >= 2 (block 0 is the reserved "
                    f"trash sink), got {self.kv_blocks}"
                )
        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got {self.kv_dtype!r}"
            )
        if self.kv_dtype != "bf16" and not self.paged:
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r} needs paged KV serving (set "
                "kv_block_size/kv_blocks): quantization scales live per "
                "arena block — dense per-row reservations have no blocks"
            )
        if self.paged_attn not in PAGED_ATTN:
            raise ValueError(
                "paged_attn must be auto, kernel or xla, got "
                f"{self.paged_attn!r}"
            )
        if self.paged_attn != "auto" and not self.paged:
            raise ValueError(
                "paged_attn is only meaningful with paged KV serving "
                "(set kv_block_size/kv_blocks); dense decode has no block "
                "tables to stream"
            )
        cache = self.prefix_cache
        if cache not in PREFIX_CACHES:
            raise ValueError(
                f"prefix_cache must be off, hbm, host or disk, got {cache!r}"
            )
        if cache != "off" and not self.paged:
            raise ValueError(
                "prefix_cache needs paged KV serving (set kv_block_size/"
                "kv_blocks): the cache shares refcounted arena blocks — "
                "dense per-row reservations have nothing to share"
            )
        if self.host_pool_blocks and cache not in ("host", "disk"):
            raise ValueError(
                "host_pool_blocks sizes the host-RAM tier — it needs "
                f"prefix_cache='host' or 'disk' (got prefix_cache={cache!r})"
            )
        if self.host_pool_blocks < 0:
            raise ValueError(
                f"host_pool_blocks must be >= 0, got {self.host_pool_blocks}"
            )
        if (self.disk_pool_dir or self.disk_pool_blocks) and cache != "disk":
            raise ValueError(
                "disk_pool_dir/disk_pool_blocks size the on-disk tier — "
                f"they need prefix_cache='disk' (got prefix_cache={cache!r})"
            )
        if cache == "disk" and not self.disk_pool_dir:
            raise ValueError(
                "prefix_cache='disk' needs disk_pool_dir: the bounded "
                "pool of memory-mapped entry files is the persistent "
                "artifact cold nodes spill into"
            )
        if self.disk_pool_blocks < 0:
            raise ValueError(
                f"disk_pool_blocks must be >= 0, got {self.disk_pool_blocks}"
            )
        if self.fault_retries < 0:
            raise ValueError(
                f"fault_retries must be >= 0, got {self.fault_retries}"
            )
        if (self.snapshot_path is None) != (self.snapshot_every_s is None):
            raise ValueError(
                "snapshot_path and snapshot_every_s go together (got "
                f"snapshot_path={self.snapshot_path!r}, "
                f"snapshot_every_s={self.snapshot_every_s!r})"
            )
        if self.snapshot_every_s is not None and self.snapshot_every_s < 0:
            raise ValueError(
                f"snapshot_every_s must be >= 0, got {self.snapshot_every_s}"
            )
        if self.gauge_sweep_every_s < 0:
            raise ValueError(
                "gauge_sweep_every_s must be >= 0, got "
                f"{self.gauge_sweep_every_s}"
            )
        if self.cp > 1 and not self.paged:
            raise ValueError(
                "cp > 1 needs paged KV serving (set kv_block_size/"
                "kv_blocks): context-parallel serving shards the block "
                "arena — dense per-row reservations have no block dim "
                "to shard"
            )
        if self.cp > 1 and cache != "off" and chunk is None:
            raise ValueError(
                "cp > 1 with prefix_cache needs prefill_chunk: a radix "
                "hit's resident prefix spans multiple shards, so its "
                "suffix must prefill arena-native (chunked) — the "
                "one-shot gather path cannot assemble a cross-shard "
                "window"
            )
