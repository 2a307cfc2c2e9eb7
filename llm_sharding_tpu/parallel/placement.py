"""Placement: mapping layer ranges onto mesh devices.

TPU-native control plane replacing the reference's master-side ``ConfigSender``
(``/root/reference/utils/config_sender.py:4-47``): where the reference pushes
``{src_addr, dst_addr, can_receive_user_request, first_node_addr,
shards_start, shards_end}`` JSON dicts to per-device controller processes over
ZMQ, here a ``PlacementSpec`` maps each pipeline stage's ``[start, end)``
layer range onto a position along the mesh's "pipe" axis, and "sending the
config" becomes constructing (or re-constructing) the sharded computation.

Validation mirrors the reference's (``config_sender.py:29-31``,
``node_worker.py:134-135``) plus the chain-coverage checks the reference
leaves to the operator. Ragged splits (e.g. the 6/1/25 example in
``/root/reference/send_config.py:10-34``) are supported by padding every
stage to ``max_layers_per_stage`` with masked layers, so one SPMD program
serves any split.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import numpy as np
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PlacementSpec:
    """stages[i] = (start, end) layer range of pipeline stage i (chain order).

    Stage 0 is user-facing (holds the embedding; ≙ ``can_receive_user_request``,
    ``/root/reference/utils/node_worker.py:105-107``); the last stage holds
    final-norm + lm_head (``:155-164``).
    """

    stages: tuple  # tuple[tuple[int, int], ...]
    num_layers: int

    def __post_init__(self):
        object.__setattr__(
            self, "stages", tuple((int(a), int(b)) for a, b in self.stages)
        )
        self.validate()

    def validate(self) -> None:
        if not self.stages:
            raise ValueError("placement needs at least one stage")
        prev_end = 0
        for i, (start, end) in enumerate(self.stages):
            if not (0 <= start < end <= self.num_layers):
                raise ValueError(
                    f"stage {i}: invalid layer range [{start}, {end}) for "
                    f"{self.num_layers}-layer model"
                )
            if start != prev_end:
                raise ValueError(
                    f"stage {i} starts at layer {start}, but previous stage "
                    f"ended at {prev_end}: chain must cover layers contiguously"
                )
            prev_end = end
        if prev_end != self.num_layers:
            raise ValueError(
                f"chain covers layers [0, {prev_end}) but the model has "
                f"{self.num_layers} layers"
            )

    @property
    def num_stages(self) -> int:
        return len(self.stages)

    @property
    def max_layers_per_stage(self) -> int:
        return max(end - start for start, end in self.stages)

    @classmethod
    def balanced(cls, num_layers: int, num_stages: int) -> "PlacementSpec":
        """Even split, earlier stages take the remainder (the scheduler the
        reference's profiler feeds was meant to compute non-even splits from
        device capabilities; see ``utils/profiler.py`` for that input)."""
        if num_stages < 1 or num_stages > num_layers:
            raise ValueError(
                f"num_stages must be in [1, {num_layers}], got {num_stages}"
            )
        base, rem = divmod(num_layers, num_stages)
        stages, cursor = [], 0
        for i in range(num_stages):
            n = base + (1 if i < rem else 0)
            stages.append((cursor, cursor + n))
            cursor += n
        return cls(tuple(stages), num_layers)

    @classmethod
    def from_ranges(
        cls, ranges: Sequence[tuple[int, int]], num_layers: int
    ) -> "PlacementSpec":
        return cls(tuple(ranges), num_layers)

    def grouped(self, k: int) -> "PlacementSpec":
        """Merge ``k`` consecutive chain stages per device — the execution
        spec for a chain LONGER than the pipe axis (≙ the reference running
        multiple controllers per host: a 4-stage chain over 3 machines,
        ``/root/reference/send_config.py:36-44`` — chain length is a
        placement property, not a hardware one). Each device runs its k
        stage-slices back to back (they are consecutive in chain order, so
        the hop between them is local — the scan over the merged layer stack
        IS the 'scan over the extra stage dim'), and the ring permute fires
        once per k virtual stages. Stages are contiguous layer ranges, so
        each merged group is itself a contiguous range: execution is
        token-identical to the virtual chain by construction."""
        if k < 1 or self.num_stages % k:
            raise ValueError(
                f"{self.num_stages} stages cannot group by {k} per device"
            )
        merged = tuple(
            (self.stages[i * k][0], self.stages[i * k + k - 1][1])
            for i in range(self.num_stages // k)
        )
        return PlacementSpec(merged, self.num_layers)

    @classmethod
    def from_capabilities(
        cls, num_layers: int, capabilities: Sequence[float]
    ) -> "PlacementSpec":
        """Capability-weighted ragged split — the scheduler the reference's
        profiler exists to feed (``/root/reference/README.md:8``: measured
        per-device capabilities → layer allocation).

        ``capabilities[i]`` is a throughput proxy for stage i — higher =
        faster; use ``1 / c_k`` from ``profiler.PrefillReport.capability_c_k``
        or ``1 / stage_time`` from ``Profiler.profile_stage``. Layers are
        allocated proportionally (contiguous, ≥1 per stage) so per-stage time
        ``layers_i / capabilities_i`` is balanced.
        """
        caps = np.asarray(capabilities, np.float64)
        if caps.ndim != 1 or len(caps) < 1:
            raise ValueError("capabilities must be a 1-D sequence")
        if np.any(caps <= 0):
            raise ValueError(f"capabilities must be positive, got {caps}")
        S = len(caps)
        if S > num_layers:
            raise ValueError(f"{S} stages > {num_layers} layers")
        raw = caps / caps.sum() * num_layers
        counts = np.maximum(1, np.round(raw).astype(int))
        # repair rounding drift toward the proportional target, keeping ≥1
        while counts.sum() > num_layers:
            over = counts - raw  # most over-allocated stage gives one back
            over[counts <= 1] = -np.inf
            counts[int(np.argmax(over))] -= 1
        while counts.sum() < num_layers:
            counts[int(np.argmin(counts - raw))] += 1
        stages, cursor = [], 0
        for n in counts:
            stages.append((cursor, cursor + int(n)))
            cursor += int(n)
        return cls(tuple(stages), num_layers)

    @classmethod
    def from_stage_times(
        cls, num_layers: int, stage_times: Sequence[float]
    ) -> "PlacementSpec":
        """Split from measured per-stage (equal-layer) times: a stage that
        measured 2× slower gets ~half the layers."""
        t = np.asarray(stage_times, np.float64)
        return cls.from_capabilities(num_layers, 1.0 / t)


def stack_stage_params(
    spec: PlacementSpec, full_layers: dict[str, Any], kinds: tuple = (),
) -> tuple[dict[str, np.ndarray], np.ndarray]:
    """Slice full-model stacked layers [L, ...] into per-stage padded stacks.

    Returns ``(stage_layers, layer_masks)`` where each ``stage_layers`` leaf is
    ``[num_stages, max_layers_per_stage, ...]`` (shard axis 0 over "pipe") and
    ``layer_masks`` is ``[num_stages, max_layers_per_stage]`` bool.

    A model whose layers are of several ``kinds`` (one name per layer,
    ``ModelConfig.layer_kinds``) hands over ``{kind: {leaf: [L_kind, ...]}}``,
    one stack per kind in layer order, and gets the same back per kind:
    ``{kind: {leaf: [num_stages, P_kind, ...]}}``, each kind padded to the
    most any stage holds of it. A stage's layer SLOTS are then its kinds'
    stacks laid end to end (``models/stack.kind_spans``) and ``layer_masks``
    is ``[num_stages, sum of P_kind]`` in that order. A stage must hold its
    layers in slot order, i.e. each stage's layers must be sorted by first
    appearance of their kind — true of any contiguous range of a model with
    leading layers of one kind.

    Works on HOST (numpy) arrays and returns numpy: the caller device_puts the
    result with the mesh sharding (see ``runtime/engine.py``), so the padded
    stack never materializes whole on a single device — only each device's
    slice lands in its HBM.
    """
    def pad_stack(leaf, ranges, P) -> np.ndarray:
        leaf = np.asarray(leaf)
        parts = []
        for start, end in ranges:
            chunk = leaf[start:end]
            if end - start < P:
                pad = np.zeros((P - (end - start), *chunk.shape[1:]), chunk.dtype)
                chunk = np.concatenate([chunk, pad], axis=0)
            parts.append(chunk)
        return np.stack(parts)

    def masks_of(ranges, P) -> np.ndarray:
        masks = np.zeros((spec.num_stages, P), bool)
        for i, (start, end) in enumerate(ranges):
            masks[i, : end - start] = True
        return masks

    if not kinds:
        P = spec.max_layers_per_stage
        stage_layers = jax.tree.map(
            lambda leaf: pad_stack(leaf, spec.stages, P), full_layers
        )
        return stage_layers, masks_of(spec.stages, P)

    order = list(dict.fromkeys(kinds))
    # a model whose kinds ALTERNATE down the stack (window and full
    # attention: models/mimo_v2.py) runs a stage's layers as runs in model
    # order and reads the sequence off the model's first layers: every stage
    # must then hold that same sequence
    seqs = [tuple(kinds[start:end]) for start, end in spec.stages]
    alike = all(q == seqs[0] for q in seqs) and spec.stages[0][0] == 0
    stage_layers, masks = {}, []
    for kind in order:
        # each stage's layers of this kind as a range of the KIND's stack
        ranges = []
        for start, end in spec.stages:
            mine = list(kinds[start:end])
            if mine != sorted(mine, key=order.index) and not alike:
                raise ValueError(
                    f"stage layers {start}..{end} hold kinds {mine}: a "
                    "stage's layers must come kind after kind, or every "
                    "stage hold the same sequence of kinds (whole periods "
                    "of the model's pattern)"
                )
            before = kinds[:start].count(kind)
            ranges.append((before, before + mine.count(kind)))
        P = max(e - s for s, e in ranges)
        stage_layers[kind] = jax.tree.map(
            lambda leaf: pad_stack(leaf, ranges, P), full_layers[kind]
        )
        masks.append(masks_of(ranges, P))
    return stage_layers, np.concatenate(masks, axis=1)
