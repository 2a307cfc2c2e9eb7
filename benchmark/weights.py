"""Seeded random weights, made on the device in one jitted call.

The weights are DATA: the engine serves them and ``reference.py`` scores the
served tokens against the same arrays. WHICH leaves a layer has, their
shapes, the rule each is drawn by and the model's tables are the block's
(``blocks/<model_type>.py``: ``layer_leaves``, ``tables``, and ``layer_kinds``
where the layers are not all alike); this file is the generator every block
shares. Leaf ``i`` of layer ``l`` is drawn from
``fold_in(fold_in(root, l), i)`` and table ``i`` from ``fold_in(fold_in(root,
1 << 20), i)``: the same on one chip and split over a ring.

``weight_dtype == "int8"`` quantises the leaves a block marks ``matmul`` as
the program's loader does — symmetric, per output channel, absmax / 127 —
inside the same call, layer by layer under ``lax.map``, so the bf16 form of
more than one layer never exists. The arithmetic is written out here rather
than called from the program: the pair ``(q, scale)`` IS the model, and the
reference dequantises it as ``q · scale`` in float32.

The call is a ``shard_map`` over the ring's mesh: each chip makes the layers
of its own stage. On one chip the mesh has one device.
"""

from __future__ import annotations

import functools
import json
from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import blocks

import llm_sharding_tpu.models  # noqa: F401  (first: ops.* imports it in a cycle)
from llm_sharding_tpu.ops.quant import QTensor  # the container the engine reads

DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


class Leaf(NamedTuple):
    """One array a block asks for: a leaf of a layer or a table."""

    name: str
    shape: tuple
    rule: Callable  # a standard-normal float32 sample → the float32 value
    matmul: bool = False  # a matmul weight [in, out]: quantised under int8
    vocab_axis: Optional[int] = None  # tables: the dimension a ring splits


def root_key(seed: int) -> jax.Array:
    """A key from any whole number: seeds past 2**31 do not fit the int32 a
    key is made from, so the high bits are folded in."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def quantize(w: jax.Array, dtype) -> QTensor:
    """Symmetric per-output-channel int8 of ``w[in, out]``."""
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2)
    q = jnp.round(w32 / jnp.maximum(absmax, 1e-12)[None, :] * 127.0)
    return QTensor(q=q.astype(jnp.int8), scale=(absmax / 127.0).astype(dtype))


def make_layer(leaves: tuple, root: jax.Array, layer, dtype, int8: bool) -> dict:
    """One layer's leaves. ``layer`` may be traced."""
    key = jax.random.fold_in(root, layer)
    out = {}
    for i, leaf in enumerate(leaves):
        x = jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32)
        w = leaf.rule(x).astype(dtype)
        out[leaf.name] = quantize(w, dtype) if int8 and leaf.matmul else w
    return out


def make_tables(tables: tuple, root: jax.Array, dtype) -> dict:
    k = jax.random.fold_in(root, 1 << 20)  # past any layer index
    return {
        t.name: t.rule(jax.random.normal(
            jax.random.fold_in(k, i), t.shape, jnp.float32)).astype(dtype)
        for i, t in enumerate(tables)
    }


@functools.lru_cache(maxsize=None)
def _generator(block, model_json: str, mesh: Mesh, dtype, int8: bool):
    model = json.loads(model_json)
    leaves, tables = block.layer_leaves(model), block.tables(model)
    num_layers = block.dims(model)["layers"]
    axis = mesh.axis_names[0]
    kinds = blocks.kinds(block, model)

    def stack(leaves, root_data, layer_ids):
        """The layers ``layer_ids`` (their index in the WHOLE model) as one
        stack of ``leaves``, each chip making its own share of them."""
        def stage(root_data, layer_ids):
            root = jax.random.wrap_key_data(root_data)
            return jax.lax.map(
                lambda l: make_layer(leaves, root, l, dtype, int8), layer_ids
            )

        return jax.shard_map(
            stage, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P(axis),
        )(root_data, layer_ids)

    def whole(root_data):
        if kinds is None:
            layers = stack(
                leaves, root_data, jnp.arange(num_layers, dtype=jnp.int32))
        else:  # one stack per kind, in layer order
            layers = {
                kind: stack(leaves[kind], root_data, jnp.asarray(ids, jnp.int32))
                for kind, ids in layers_of_kinds(kinds, mesh.size).items()
            }
        made = make_tables(tables, jax.random.wrap_key_data(root_data), dtype)
        # each chip makes its slice of the vocabulary, so no chip holds the
        # float32 form of a whole table (3 GB at 14B) beside its layers
        def split(t: Leaf) -> P:
            if t.vocab_axis is None:
                return P()
            return P(*(axis if d == t.vocab_axis else None
                       for d in range(len(t.shape))))

        made = {
            t.name: jax.lax.with_sharding_constraint(
                made[t.name], NamedSharding(mesh, split(t)))
            for t in tables
        }
        return {"layers": layers, **made}

    return jax.jit(whole)


def layers_of_kinds(kinds: tuple, stages: int) -> dict:
    """``{kind: [its layers' indices in the whole model, in order]}``. A ring
    gives each stage as many consecutive layers as every other, and a stack
    is split evenly too, so every stage must hold the same count of each
    kind: whole periods of the model."""
    per_stage = len(kinds) // stages
    out = {}
    for kind in dict.fromkeys(kinds):
        counts = [kinds[s * per_stage:(s + 1) * per_stage].count(kind)
                  for s in range(stages)]
        if len(set(counts)) != 1:
            raise ValueError(
                f"layers of kind {kind!r} do not split evenly over {stages} "
                f"stages of {per_stage} layers: {counts} a stage (a stage "
                "must hold whole periods of the model's layer kinds)")
        out[kind] = [l for l, k in enumerate(kinds) if k == kind]
    return out


def take_layer(layers: dict, kinds, layer: int) -> dict:
    """Layer ``layer`` of the model out of ``params["layers"]``: out of the
    one stack, or with ``kinds`` (``blocks.kinds``) out of its kind's."""
    kind, i = blocks.place(kinds, layer)
    stack = layers if kind is None else layers[kind]
    return jax.tree.map(lambda a: a[i], stack)


def make_params(block, model: dict, seed: int, weight_dtype: str, devices) -> dict:
    """The whole model on ``devices`` (layers split evenly along the ring),
    in the engine's layout: ``{"layers": {leaf: [L, ...]}, <table>: ...}``;
    int8 leaves are ``QTensor(q, scale)``. For a block with ``layer_kinds``
    ``"layers"`` is ``{kind: {leaf: [L_kind, ...]}}``, one stack per kind in
    layer order; layer ``l`` of the model keeps the key ``fold_in(root, l)``
    whatever its kind, so a cut in depth keeps each kept layer's weights."""
    int8 = weight_dtype == "int8"
    dtype = jnp.bfloat16 if int8 else DTYPES[weight_dtype]
    L = block.dims(model)["layers"]
    if L % len(devices):
        raise ValueError(f"{L} layers do not split over {len(devices)} chips")
    mesh = Mesh(np.asarray(devices), ("pipe",))
    fn = _generator(block, json.dumps(model, sort_keys=True), mesh, dtype, int8)
    root_data = jax.device_put(
        jax.random.key_data(root_key(seed)), NamedSharding(mesh, P())
    )
    return fn(root_data)


class HandOff:
    """A leaf that gives its device array away the first time it is read.

    ``PipelineEngine(host_staging=False)`` keeps the tree it is given AND
    builds its own stacked copy, which for a model that fills half a chip
    would hold the weights twice. Wrapped, each array is released as soon
    as the engine has copied it, so the peak is the model plus one leaf."""

    def __init__(self, array: jax.Array):
        self._array = array
        self.shape, self.dtype = array.shape, array.dtype

    def __jax_array__(self) -> jax.Array:
        array, self._array = self._array, None
        if array is None:
            raise RuntimeError("this weight was already handed to the engine")
        return array


def hand_off(params: dict) -> dict:
    """``params`` with every array wrapped in ``HandOff``; the caller must
    drop its own references to the original tree."""
    return jax.tree.map(HandOff, params)


def to_host(params: dict) -> dict:
    """The tree as host arrays (what an engine with several stages stages
    from), freeing each device array once copied."""
    def pull(a):
        host = np.asarray(a)
        a.delete()
        return host

    return jax.tree.map(pull, params)
