"""Pipeline serving engine — the control plane + node runtime, TPU-native.

Replaces the reference's master/controller pair: ``ConfigSender`` pushing
6-key JSON configs to per-device ``NodeController`` processes
(``/root/reference/utils/config_sender.py:4-47``,
``utils/node_worker.py:385-559``). Here one host process owns the mesh; a
``PlacementSpec`` plays the role of the pushed config, and "applying" it
builds the sharded stage arrays. Capabilities preserved:

- **Hot reconfiguration** (≙ ``check_new_config`` rebinding sockets and
  reloading layer ranges in place, ``node_worker.py:445-474``):
  ``apply_placement`` re-slices stage params at any time. Because stage
  arrays are padded to ``max_layers_per_stage`` and the pipeline program is
  compiled per (num_stages, padded-layer-count, batch, lengths) shape key,
  a repartition that keeps those static shapes REUSES the compiled program —
  only device arrays move. This is the answer to SURVEY.md §7's "hot
  reconfiguration vs compilation" hard part; a changed stage count or pad
  size recompiles exactly once (jit cache keyed on shapes).
- **Between-request state clear** (≙ the clear-KV ring protocol,
  ``node_worker.py:319-382, 507-513``): caches are allocated inside each
  compiled request program, so every request starts clean by construction —
  the ring-propagated origin-marking trick is unnecessary when one host owns
  all chips (SURVEY.md §7 step 6).
- **Request-edge privacy** (≙ embedding-before-transport,
  ``node_worker.py:215-223`` and README privacy note): ``embed_prompt`` turns
  token ids into hidden states host-side; ``PipelineServer.submit_embedding``
  and ``pipeline_generate(..., prompt_embeds=)`` accept those hidden states
  directly (the stage-0 injection point, ≙ ``_forward_request``/
  ``receive_request``, ``node_worker.py:476-491``) — raw ids never enter the
  serving path, and decoding is token-exact vs the ids entry
  (tests/test_serve.py, tests/test_pipeline.py).
- **Streaming detokenized output** (≙ the streamed ``tokenizer.decode``
  prints, ``node_worker.py:286-298``): ``generate_text_stream`` yields text
  deltas.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Any, Iterator, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from ..models.family import family
from ..obs.metrics import REGISTRY, record_shape_key
from ..obs.setupline import SETUP, install as install_setup_listeners
from ..analysis.lockorder import named_lock
from ..parallel.mesh import PIPE_AXIS, pipeline_mesh
from ..parallel.pipeline import PipelineResult, pipeline_generate
from ..parallel.placement import PlacementSpec, stack_stage_params
from ..utils import shard_store
from .generate import generate

logger = logging.getLogger("llm_sharding_tpu.engine")

# Hot-reconfiguration visibility: placement swaps were a one-line log —
# their count, wall cost (host staging + device_put of every stage slice)
# and the resulting pipe depth now land in the registry, so repartition
# churn and its cost show up next to the serving latency it perturbs.
_M_SWAPS = REGISTRY.counter(
    "engine_placement_swaps_total", "apply_placement calls that committed",
)
_M_SWAP_SECONDS = REGISTRY.histogram(
    "engine_placement_swap_seconds",
    "Wall time of one placement swap (stage re-slice + device placement)",
)
_M_STAGES = REGISTRY.gauge(
    "engine_pipeline_stages", "Pipe-axis size of the engine's current mesh",
)


def _tree_bytes(tree) -> int:
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree))


@contextlib.contextmanager
def _placing(name: str, stages: int):
    """A set-up span around host → chips placement: the block appends what
    it put to the list it is given, and the span closes only when those
    arrays are ready on their devices (a put returns before the copy)."""
    with SETUP.span(name, stages=stages) as sp:
        placed: list = []
        yield placed
        jax.block_until_ready(placed)
        sp["bytes"] = _tree_bytes(placed)


class PipelineEngine:
    """One engine per model per mesh. Thread-safe for placement swaps."""

    @SETUP.wraps("setup.engine")
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,  # full params pytree (use .from_shards to load from disk)
        *,
        num_stages: Optional[int] = None,
        placement: Optional[PlacementSpec] = None,
        devices: Optional[list] = None,
        tokenizer: Any = None,
        cache_dtype=jnp.bfloat16,
        data_parallel: int = 1,
        tensor_parallel: int = 1,
        host_staging: bool = True,
    ):
        """``data_parallel``/``tensor_parallel`` compose with the pipeline:
        the engine builds a (data, pipe, tensor) mesh and the SAME shard_map
        program runs dp×pp / pp×tp hybrids (tests/test_hybrid.py wired these
        at the ``pipeline_generate`` level; here they are user-reachable).
        Stage count defaults to ``devices / (dp·tp)``. The continuous-
        batching server and the interleaved scheduler remain pipe-only.

        ``host_staging=False`` keeps device-resident params ON DEVICE for a
        SINGLE-STAGE engine (stage stacking is a device-side reshape): no
        host pull + re-push of the full weights (a multi-GB device→host→
        device round trip when the caller initialised params on device). Hot
        repartition to >1 stage is unavailable in this mode (it needs the
        host-resident repartition source)."""
        # every compile or cache load from here on is named in the set-up
        # ledger (obs/setupline.py): jax's monitoring events are its source
        install_setup_listeners(
            jax.monitoring.register_event_duration_secs_listener,
            jax.monitoring.register_event_listener,
        )
        self.cfg = cfg
        self._host_staging = bool(host_staging)
        if self._host_staging:
            # The repartition source stays on HOST (numpy): only each
            # device's stage slice ever lands in HBM — the whole point of
            # pipelining a model bigger than one chip. A store loaded with
            # shard_store.load_full already IS host arrays (np.asarray is a
            # no-op); device-resident params (init_params) are pulled once.
            with SETUP.span("setup.engine.host_pull") as sp:
                leaves = jax.tree.leaves(params)
                sp["leaves"] = len(leaves)
                sp["bytes"] = sum(
                    int(a.nbytes) for a in leaves
                    if not isinstance(a, np.ndarray)
                )
                self._full_layers = jax.tree.map(np.asarray, params["layers"])
                # tree.map keeps QTensor leaves (int8 q + scale) as host
                # QTensors
                self._head_host = jax.tree.map(
                    np.asarray,
                    {k: v for k, v in params.items() if k != "layers"},
                )
        else:
            self._full_layers = params["layers"]
            self._head_host = {
                k: v for k, v in params.items() if k != "layers"
            }
        self.tokenizer = tokenizer
        self.cache_dtype = cache_dtype
        self._lock = named_lock("engine.reconfig")
        self.data_parallel = int(data_parallel)
        self.tensor_parallel = int(tensor_parallel)
        if self.data_parallel < 1 or self.tensor_parallel < 1:
            raise ValueError("data_parallel/tensor_parallel must be >= 1")
        if self.tensor_parallel > 1:
            from ..parallel.tensor import validate_tp

            if cfg.num_experts:
                raise NotImplementedError(
                    "tensor parallelism over a model with sparse experts "
                    f"(num_experts={cfg.num_experts}) is not implemented: "
                    "an expert axis on the mesh is a later step. One chip "
                    "and a ring of stages (num_stages) serve it"
                )
            validate_tp(cfg, self.tensor_parallel)

        self._devices = devices
        if placement is None:
            n = num_stages
            if n is None:
                n_dev = len(devices or jax.devices())
                rep = self.data_parallel * self.tensor_parallel
                if n_dev % rep:
                    raise ValueError(
                        f"{n_dev} devices not divisible by dp×tp = {rep}"
                    )
                n = n_dev // rep
            placement = PlacementSpec.balanced(cfg.num_hidden_layers, n)
        self.mesh = self._build_mesh(
            self._pipe_size(placement.num_stages), devices
        )
        self._place(placement)

    def _pipe_size(self, num_virtual: int) -> int:
        """Pipe-axis size for a chain of ``num_virtual`` stages. A chain
        LONGER than the hardware runs k = num_virtual / pipe consecutive
        stage-slices per device (``PlacementSpec.grouped`` — ≙ the
        reference's multiple controllers per host, ``send_config.py:36-44``:
        chain length is decoupled from device count)."""
        n_dev = len(self._devices if self._devices is not None else jax.devices())
        cap = n_dev // (self.data_parallel * self.tensor_parallel)
        if num_virtual <= cap:
            return num_virtual
        # largest pipe size that divides the chain — a 12-stage chain on 8
        # devices runs 2 stages each on 6 of them (2 idle), not an error
        for pipe in range(cap, 0, -1):
            if num_virtual % pipe == 0:
                return pipe
        raise ValueError(
            f"a {num_virtual}-stage chain needs at least one pipe device; "
            f"{cap} available (dp×tp uses "
            f"{self.data_parallel * self.tensor_parallel} of {n_dev})"
        )

    def _build_mesh(self, num_stages: int, devices):
        if self.data_parallel == 1 and self.tensor_parallel == 1:
            return pipeline_mesh(num_stages, devices)
        from ..parallel.distributed import hybrid_mesh

        return hybrid_mesh(
            data=self.data_parallel,
            pipe=num_stages,
            tensor=self.tensor_parallel,
            devices=devices,
        )

    # -- construction helpers ------------------------------------------------

    @classmethod
    def from_shards(
        cls,
        shards_dir: str,
        *,
        num_stages: Optional[int] = None,
        placement: Optional[PlacementSpec] = None,
        devices: Optional[list] = None,
        dtype=jnp.bfloat16,
        cache_dtype=jnp.bfloat16,
        data_parallel: int = 1,
        tensor_parallel: int = 1,
    ) -> "PipelineEngine":
        """Load from a shard store (≙ NodeController startup: receive config
        → load_shards, ``node_worker.py:403-421``)."""
        cfg, params = shard_store.load_full(shards_dir, dtype=dtype)
        tokenizer = shard_store.load_tokenizer(shards_dir)
        return cls(
            cfg,
            params,
            num_stages=num_stages,
            placement=placement,
            devices=devices,
            tokenizer=tokenizer,
            cache_dtype=cache_dtype,
            data_parallel=data_parallel,
            tensor_parallel=tensor_parallel,
        )

    # -- control plane (≙ ConfigSender.send_config / check_new_config) ------

    def apply_placement(self, spec: PlacementSpec) -> None:
        """Hot-apply a new layer→stage mapping (≙ ``check_new_config``,
        ``node_worker.py:445-474``). Safe mid-service: in-flight requests
        finish on the old arrays; new requests see the new placement."""
        with SETUP.span("setup.repartition", stages=spec.num_stages):
            self._place(spec)

    def _place(self, spec: PlacementSpec) -> None:
        """Stack, pad and place ``spec``'s stage arrays, then swap them in:
        the constructor's work and a hot repartition's, under whichever
        set-up span its caller opened."""
        if spec.num_layers != self.cfg.num_hidden_layers:
            raise ValueError(
                f"placement covers {spec.num_layers} layers but model has "
                f"{self.cfg.num_hidden_layers}"
            )
        from ..parallel.pipeline import refuse_looped_ring

        refuse_looped_ring(self.cfg, spec.num_stages)
        swap_t0 = time.perf_counter()
        # A chain longer than the pipe axis executes grouped: k consecutive
        # stages per device, ppermute once per k virtual stages (r3 next-#8).
        pipe = self._pipe_size(spec.num_stages)
        exec_spec = (
            spec if pipe == spec.num_stages
            else spec.grouped(spec.num_stages // pipe)
        )
        if pipe != self.mesh.shape[PIPE_AXIS]:
            # stage-count change needs a new mesh (≙ worker recreation when
            # the role bit flips, node_worker.py:455-466); dp/tp carry over
            mesh = self._build_mesh(pipe, self._devices)
        else:
            mesh = self.mesh

        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.distributed import put_global
        from ..parallel.head import VOCAB_SHARDED, shard_head_host

        pipe_shard = NamedSharding(mesh, P(PIPE_AXIS))  # axis 0 → stages
        repl = NamedSharding(mesh, P())
        if not self._host_staging:
            # Device-resident fast path (single stage): stacking is just a
            # leading-dim reshape on device — the weights never cross the
            # host boundary.
            if (
                exec_spec.num_stages != 1
                or self.data_parallel > 1
                or self.tensor_parallel > 1
                or jax.process_count() > 1
            ):
                raise ValueError(
                    "host_staging=False supports a single-stage, pipe-only, "
                    "single-process placement (repartition needs the "
                    "host-resident source)"
                )
            with _placing("setup.engine.put", 1) as placed:
                stage_layers = jax.tree.map(
                    lambda a: jax.device_put(jnp.asarray(a)[None], pipe_shard),
                    self._full_layers,
                )
                L = self.cfg.num_hidden_layers
                masks = jax.device_put(
                    jnp.ones((1, L), bool), pipe_shard
                )
                head_params = {
                    k: jax.tree.map(
                        lambda a,
                        s=(pipe_shard if k in VOCAB_SHARDED else repl),
                        stack=(k in VOCAB_SHARDED):
                            jax.device_put(
                                jnp.asarray(a)[None] if stack
                                else jnp.asarray(a),
                                s,
                            ),
                        v,
                    )
                    for k, v in self._head_host.items()
                }
                placed += [stage_layers, masks, head_params]
            with self._lock:
                self.mesh = mesh
                self.placement = spec
                self.exec_placement = exec_spec
                self.stage_layers = stage_layers
                self.layer_masks = masks
                self.head_params = head_params
                self._servers = {}
            self._record_swap(swap_t0, 1)
            logger.info(
                "placement applied (device-resident, 1 stage): %s",
                list(spec.stages),
            )
            return

        with SETUP.span("setup.engine.stack", what="layers") as sp:
            stage_np, masks_np = stack_stage_params(
                exec_spec, self._full_layers, self.cfg.layer_kinds
            )
            sp["bytes"] = _tree_bytes(stage_np)
        # put_global (not device_put): each process materializes only its
        # addressable shards, so the same code path serves single-controller
        # and multi-controller runs (r2 missing #1 — the host-numpy
        # device_put broke under multi-host SPMD).
        # With tensor parallelism, llama weights land pre-split with the
        # megatron specs the pipeline program uses (no tensor-axis replica in
        # HBM); gpt2 stays pipe-sharded — pipeline_generate column-permutes
        # its fused qkv device-side before the tensor split applies.
        # int8 QTensor leaves take per-component specs (q like the raw
        # weight, scale on the output axis) — int8 × TP compose (r3 next-#4).
        relaid = self.tensor_parallel > 1 and family(self.cfg).presplit
        # one span from the first put to the last array's arrival: the
        # head's host staging runs while the layers' copies are in flight,
        # as a child (a reader takes it off the put's seconds)
        with _placing(
            "setup.engine.quant" if relaid else "setup.engine.put",
            exec_spec.num_stages,
        ) as placed:
            if relaid:
                from ..parallel.pipeline import stage_layer_specs
                from ..parallel.tensor import put_maybe_quant

                leaf_specs = stage_layer_specs(self.cfg, self.tensor_parallel)
                stage_layers = {
                    k: put_maybe_quant(a, leaf_specs[k], mesh, put=put_global)
                    for k, a in stage_np.items()
                }
            else:
                stage_layers = jax.tree.map(
                    lambda a: put_global(a, pipe_shard), stage_np
                )
            masks = put_global(masks_np, pipe_shard)
            # Vocab-shard the embedding/lm_head over the pipe axis: each
            # chip holds only its V/num_stages slice (≙ the reference's role
            # split — embedding on user-facing nodes, lm_head on the last
            # node, node_worker.py:105-125, 155-164 — done as vocab
            # parallelism).
            with SETUP.span("setup.engine.stack", what="head") as sp:
                head_np = shard_head_host(
                    self.cfg, self._head_host, exec_spec.num_stages
                )
                sp["bytes"] = _tree_bytes(head_np)
            # tree.map so int8 QTensor tables (q + per-row scale, both
            # stage-stacked on axis 0) take the pipe sharding leaf-by-leaf
            head_params = {
                k: jax.tree.map(
                    lambda a, s=(pipe_shard if k in VOCAB_SHARDED else repl):
                        put_global(a, s),
                    v,
                )
                for k, v in head_np.items()
            }
            placed += [stage_layers, masks, head_params]
        # Swap everything atomically — a concurrent generate sees either the
        # old (mesh, arrays) tuple or the new one, never a mix.
        with self._lock:
            self.mesh = mesh
            self.placement = spec  # the operator's chain (may be virtual)
            self.exec_placement = exec_spec  # what the devices actually run
            self.stage_layers = stage_layers
            self.layer_masks = masks
            self.head_params = head_params
            # live servers are bound to the old arrays — invalidate
            self._servers = {}
        self._record_swap(swap_t0, exec_spec.num_stages)
        logger.info(
            "placement applied: %d stages over %d pipe devices, ranges %s",
            spec.num_stages, exec_spec.num_stages, list(spec.stages),
        )

    @staticmethod
    def _record_swap(t0: float, pipe: int) -> None:
        _M_SWAPS.inc()
        _M_SWAP_SECONDS.observe(time.perf_counter() - t0)
        _M_STAGES.set(pipe)

    # -- serving ------------------------------------------------------------

    def generate_ids(
        self,
        prompt_ids,
        max_new_tokens: int = 128,
        *,
        prompt_len=None,
        capacity: Optional[int] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
    ) -> PipelineResult:
        with self._lock:
            stage_layers, masks = self.stage_layers, self.layer_masks
            mesh, head = self.mesh, self.head_params
        # host-side mirror of the jit cache key: a repartition that keeps
        # (stages, batch, lengths) static REUSES the compiled program — this
        # makes that reuse (or a recompile) visible as a hit/miss metric.
        # Normalized the way pipeline_generate normalizes, so equivalent
        # calls ((S,) vs (1, S) prompts, capacity=None vs its resolved
        # value) don't count phantom misses.
        shape = tuple(np.shape(prompt_ids))
        if len(shape) == 1:
            shape = (1,) + shape
        hit = record_shape_key(
            "pipeline_generate",
            (mesh.shape[PIPE_AXIS], shape, int(max_new_tokens),
             capacity or (shape[-1] + int(max_new_tokens)),
             int(masks.shape[1])),
        )
        result = pipeline_generate(
            self.cfg,
            mesh,
            stage_layers,
            masks,
            head,
            prompt_ids,
            max_new_tokens,
            prompt_len=prompt_len,
            capacity=capacity,
            cache_dtype=self.cache_dtype,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            seed=seed,
        )
        if not hit:
            # the tokens are on the host: the program's first run is over
            SETUP.landed()
        return result

    def generate_many(
        self,
        prompts,  # [M, S] right-padded, M <= num_stages
        max_new_tokens: int = 128,
        *,
        prompt_len=None,
        capacity: Optional[int] = None,
        temperature=0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seeds=None,
    ):
        """Serve up to ``num_stages`` requests concurrently with the
        interleaved schedule — all stages busy every microstep (the
        throughput mode; see parallel/schedule.py)."""
        self._require_pipe_only("generate_many")
        from ..parallel.schedule import interleaved_generate

        with self._lock:
            stage_layers, masks = self.stage_layers, self.layer_masks
            mesh, head = self.mesh, self.head_params
        return interleaved_generate(
            self.cfg,
            mesh,
            stage_layers,
            masks,
            head,
            prompts,
            max_new_tokens,
            prompt_len=prompt_len,
            capacity=capacity,
            cache_dtype=self.cache_dtype,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            seeds=seeds,
        )

    def generate_text(
        self,
        prompt: str,
        max_new_tokens: int = 128,
        *,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        seed: int = 0,
    ) -> str:
        tok = self._require_tokenizer()
        ids = np.asarray(tok(prompt)["input_ids"], np.int32)[None]
        res = self.generate_ids(
            ids, max_new_tokens, temperature=temperature, top_k=top_k,
            top_p=top_p, seed=seed,
        )
        out_ids = res.tokens[0, ids.shape[1] : int(res.lengths[0])]
        return tok.decode(out_ids, skip_special_tokens=True)

    def _validate_serve(self) -> None:
        """Engine-capability guards for continuous batching — shared by
        ``serve()`` and ``PipelineServer.restore`` (ADVICE r5: restore used
        to bypass these and die later with an obscure mesh error)."""
        if self.data_parallel > 1:
            raise NotImplementedError(
                "serve on an in-program dp engine: use "
                "runtime.replicated.ReplicatedServer — D replica servers "
                "over disjoint device groups behind a router (it forwards "
                "tensor_parallel, so dp×pp×tp serving is replicas of a "
                "pp×tp server)"
            )
        if self.tensor_parallel > 1 and not family(self.cfg).presplit:
            raise NotImplementedError(
                "serve×tp supports the llama family (llama/qwen2): the "
                "engine stores llama weights megatron-pre-split, while "
                "gpt2's fused qkv is column-permuted inside "
                "pipeline_generate — its serve-side permutation is not "
                "implemented"
            )

    @SETUP.wraps("setup.server", then=SETUP.server_built)
    def serve(self, **options):
        """Build a continuous-batching server over this engine's sharded
        arrays (≙ the reference's persistent ``run_worker_loop`` daemon,
        ``node_worker.py:493-559``). See ``runtime/server.py``. The keywords
        are the fields of ``runtime/options.ServeOptions`` (names, defaults
        and the checks that need no model are written there, once); an
        unknown one is a ``TypeError``.

        Composes with tensor parallelism: a pp×tp engine serves with
        megatron-sharded stage fns and a heads-sharded KV state (the serve
        programs take ``tp``). In-program data parallelism does not — use
        ``runtime.replicated.ReplicatedServer`` (which itself forwards
        ``tensor_parallel``, so dp×pp×tp serving is replica × this).

        ``speculate=K`` turns on speculative decoding: n-gram self-drafted
        tokens verified K+1 positions per forward, a variable number of
        tokens committed per row per step (``runtime/spec.py``). Greedy
        output stays token-identical; decode tok/s rises with the workload's
        n-gram predictability.

        ``kv_block_size``/``kv_blocks`` turn on paged KV serving (pooled
        block arena + per-row tables); ``paged_attn`` picks its decode
        attention implementation — ``auto`` (Pallas kernel on TPU for
        Mosaic-eligible shapes, exact XLA gather elsewhere), ``kernel`` or
        ``xla``. See ``ops/paged_attention.py``. ``kv_dtype`` (paged only)
        stores the arena quantized — ``"int8"``/``"fp8"`` codes with
        per-block-per-head scales, dequantized inside the attention op:
        ~2× the blocks at equal HBM and half the decode DMA bytes, at a
        bounded greedy-token drift (``"bf16"``, the default, keeps the
        exact path).

        ``prefix_cache`` (paged only) turns on the AUTOMATIC radix-tree
        prefix cache (``runtime/radix.py``): every submit transparently
        reuses the longest cached prompt prefix, finished rows' prompt
        blocks are indexed instead of freed, and — with ``"host"`` — cold
        blocks demote to a pinned host-RAM pool of ``host_pool_blocks``
        (default: arena-sized) before being dropped. ``"disk"`` extends
        the ladder one tier further: cold HOST blocks demote to
        memory-mapped files under ``disk_pool_dir`` (bounded by
        ``disk_pool_blocks``, default arena-sized), survive restarts, and
        promote disk → host → arena on a hit.

        Resilience knobs (see ``runtime/server.py``'s module docstring):
        ``max_queue=`` bounds the submit queue (``QueueFull`` past it),
        ``default_deadline_s=`` attaches a default per-request deadline,
        ``fault_plan=``/``fault_retries=``/``fault_backoff_s=``/
        ``retryable_exceptions=`` configure fault injection and the
        transient-retry policy, and ``snapshot_every_s=``+``snapshot_path=``
        arm periodic atomic crash-recovery checkpoints.

        ``gauge_sweep_every_s=`` paces the per-step load/KV/attn gauge
        sweep (0, the default, sweeps every step — the historical
        behavior); the step profiler (``server.stepline``) makes the
        sweep's per-step cost visible as its ``gauge_sweep`` phase.

        ``cp=N`` (paged only) turns on CONTEXT-PARALLEL serving: the server
        builds a ``(cp, pipe)`` mesh over ``N × num_stages`` devices and
        shards the paged arena's block pool over the cp axis — each shard
        owns ``kv_blocks`` blocks, so the admissible context grows ~N× at
        equal per-chip HBM. Prefill lands each chunk's KV on the owning
        shard only; decode combines per-shard attention partials with an
        online-softmax merge, so greedy output stays token-identical to
        ``cp=1``. Requires ``tensor_parallel == 1``, the llama family, no
        speculation, and (with ``prefix_cache``) ``prefill_chunk`` set —
        see ``PipelineServer`` for the exact gates. ``cp=1`` (default)
        compiles the exact pre-existing programs.

        A model with sliding-window layers (``cfg.windowed``; paged +
        ``prefill_chunk`` only) keeps an arena and a block table per kind of
        attention layer: ``kv_blocks`` sizes the full layers' pool, the
        window layers' is every row's share of the window (nothing to
        size). A window layer's blocks behind the window go back to its pool
        while the row decodes. Prefix-cache hits are not offered; snapshots,
        prefix handles, the embeddings entry, speculation, tp / cp and a
        quantized arena are refused by name."""
        from .options import ServeOptions
        from .server import PipelineServer

        options = ServeOptions(**options)  # (an unknown keyword: TypeError)
        self._validate_serve()
        if options.cp > 1 and self.cfg.num_experts:
            raise NotImplementedError(
                "serve×cp over a model with sparse experts is not "
                "implemented (untested: the experts' counters and reads "
                "would repeat per context shard); serve it on one chip or "
                "a ring of stages"
            )
        if options.cp > 1 and self.tensor_parallel > 1:
            raise NotImplementedError(
                "serve×cp×tp: the cp arena sharding and megatron heads "
                "sharding both claim the KV leaves' trailing dims — pick "
                "one (cp for long context, tp for big models)"
            )
        return PipelineServer(self, options)

    def _shared_server(self, prompt_len: int, max_new: int):
        """A capacity LADDER of coexisting shared servers (r3 weak #6): a
        request needing a bigger bucket gets a NEW server alongside the old
        one instead of draining it — in-flight streams on smaller servers
        keep producing (each stream pumps its own server). States are
        per-capacity and geometric, so worst-case HBM for the ladder is
        ~2× the largest state; ``apply_placement`` frees them all."""
        from .server import ADMIT_BUCKETS

        if prompt_len > ADMIT_BUCKETS[-1]:
            raise ValueError(
                f"prompt length {prompt_len} exceeds the largest admission "
                f"bucket ({ADMIT_BUCKETS[-1]})"
            )
        bucket = next(b for b in ADMIT_BUCKETS if b >= prompt_len)
        needed = bucket + max_new
        with self._lock:
            srvs_ref = self._servers
        for cap in sorted(srvs_ref):
            if cap >= needed:
                return srvs_ref[cap]
        cap = 64
        while cap < needed:
            cap *= 2
        srv = self.serve(capacity=cap)  # compile outside the lock
        with self._lock:
            if self._servers is srvs_ref:
                # a concurrent first request may have won the build race —
                # use the registered one so only one state exists per cap
                existing = self._servers.get(cap)
                if existing is not None:
                    return existing
                self._servers[cap] = srv
                return srv
        # apply_placement invalidated the ladder while we were building:
        # this server reads the OLD arrays — drop it and rebuild on the new
        return self._shared_server(prompt_len, max_new)

    def generate_text_stream(
        self,
        prompt: str,
        max_new_tokens: int = 128,
        *,
        temperature: float = 0.0,
        seed: int = 0,
        top_k: int = 0,
        top_p: float = 1.0,
        stop=None,
    ) -> Iterator[str]:
        """Streaming text deltas (≙ node_worker.py:286-298), served from the
        SHARDED pipeline: tokens surface one ring cycle at a time via the
        continuous-batching server, and the full model never materializes on
        a single device (the round-1 monolithic-streaming gap, ADVICE #4 /
        VERDICT missing #3)."""
        tok = self._require_tokenizer()
        ids = np.asarray(tok(prompt)["input_ids"], np.int32)
        srv = self._shared_server(ids.shape[0], max_new_tokens)
        req = srv.submit(
            ids, max_new_tokens, temperature=temperature, seed=seed,
            top_k=top_k, top_p=top_p, stop=stop,
        )
        prev = ""
        acc: list[int] = []
        for t in srv.stream(req):
            acc.append(t)
            text = tok.decode(acc, skip_special_tokens=True)
            if len(text) > len(prev) and not text.endswith("�"):
                yield text[len(prev):]
                prev = text

    # -- request edge / privacy (≙ embedding-before-transport) ---------------

    def embed_prompt(self, prompt_ids) -> jnp.ndarray:
        """Token ids → hidden states at the host boundary. What crosses into
        the pipeline afterwards is embeddings only (≙ the reference's privacy
        mechanism: raw text/ids never leave the accepting node,
        ``node_worker.py:215-223``). Computed from the host-resident full
        table — the device copies are vocab-sharded."""
        from ..ops.quant import QTensor

        ids = np.asarray(prompt_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        table = self._head_host["embed"]
        if isinstance(table, QTensor):  # int8 row-quantized: dequant the rows
            h = np.asarray(table.q)[ids].astype(np.float32)
            h = h * np.asarray(table.scale, np.float32)[ids][..., None]
            # back to the table's dtype so callers see the same embedding
            # dtype whether or not the head is quantized (device embed_rows
            # parity)
            h = h.astype(np.asarray(table.scale).dtype)
        else:
            h = np.asarray(table)[ids]
        if family(self.cfg).learned_positions:
            pos = np.arange(ids.shape[1])
            h = h + np.asarray(self._head_host["pos_embed"])[pos][None]
        if self.cfg.embed_multiplier != 1.0:  # gemma: hidden × sqrt(H)
            h = h * np.asarray(self.cfg.embed_multiplier, h.dtype)
        return jnp.asarray(h)

    def _require_pipe_only(self, what: str) -> None:
        if self.data_parallel > 1 or self.tensor_parallel > 1:
            raise NotImplementedError(
                f"{what} runs on a pipe-only engine; in-program dp/tp hybrid "
                "engines support generate_ids (the shard_map pipeline "
                "program) and serve() composes with tp. For data-parallel "
                "continuous batching use runtime.replicated.ReplicatedServer "
                "— D replica servers over disjoint device groups behind a "
                "router."
            )

    def _require_tokenizer(self):
        if self.tokenizer is None:
            raise ValueError(
                "engine has no tokenizer: construct via from_shards on a store "
                "with tokenizer files, or pass tokenizer= explicitly"
            )
        return self.tokenizer


class MonolithicEngine:
    """Single-device engine (≙ ``inference.py``, the reference's monolithic
    baseline) sharing the engine API for A/B correctness checks."""

    def __init__(self, cfg: ModelConfig, params: Any, tokenizer=None, cache_dtype=jnp.bfloat16):
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer
        self.cache_dtype = cache_dtype

    def generate_ids(self, prompt_ids, max_new_tokens: int = 128, **kw):
        return generate(
            self.cfg, self.params, prompt_ids, max_new_tokens,
            cache_dtype=self.cache_dtype, **kw,
        )
