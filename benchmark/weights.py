"""Seeded random weights, made on the device in one jitted call.

The weights are DATA: the engine serves them and ``reference.py`` scores the
served tokens against the same arrays. Every leaf of layer ``l`` is drawn
from ``fold_in(fold_in(root, l), leaf_index)``: the same on one chip and split
over a ring.

- matmul weights: normal, scaled by fan-in ** -0.5;
- q/k/v biases: normal × 0.1 — NOT zero, or a dropped bias would go unseen;
- norm gains: 1 + normal × 0.1;
- embedding: normal; output head: normal × hidden ** -0.5 (logits of about
  unit variance).

``weight_dtype == "int8"`` quantises the seven matmul weights of a layer as
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

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import llm_sharding_tpu.models  # noqa: F401  (first: ops.* imports it in a cycle)
from llm_sharding_tpu.ops.quant import QTensor  # the container the engine reads

MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LEAF_ORDER = (
    "input_norm", "wq", "wk", "wv", "wo", "post_norm",
    "w_gate", "w_up", "w_down", "bq", "bk", "bv",
)
BIAS_STD = 0.1
GAIN_STD = 0.1
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def root_key(seed: int) -> jax.Array:
    """A key from any whole number: seeds past 2**31 do not fit the int32 a
    key is made from, so the high bits are folded in."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def leaf_shapes(model: dict) -> dict:
    H, I = model["hidden_size"], model["intermediate_size"]
    D = model.get("head_dim") or H // model["num_attention_heads"]
    Nh, Nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return {
        "input_norm": (H,), "post_norm": (H,),
        "wq": (H, Nh * D), "wk": (H, Nkv * D), "wv": (H, Nkv * D),
        "wo": (Nh * D, H),
        "w_gate": (H, I), "w_up": (H, I), "w_down": (I, H),
        "bq": (Nh * D,), "bk": (Nkv * D,), "bv": (Nkv * D,),
    }


def quantize(w: jax.Array, dtype) -> QTensor:
    """Symmetric per-output-channel int8 of ``w[in, out]``."""
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2)
    q = jnp.round(w32 / jnp.maximum(absmax, 1e-12)[None, :] * 127.0)
    return QTensor(q=q.astype(jnp.int8), scale=(absmax / 127.0).astype(dtype))


def make_layer(shapes: dict, root: jax.Array, layer, dtype, int8: bool) -> dict:
    """One layer's leaves. ``layer`` may be traced."""
    key = jax.random.fold_in(root, layer)
    out = {}
    for i, name in enumerate(LEAF_ORDER):
        shape = shapes[name]
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in MATMUL_LEAVES:
            w = (x * shape[0] ** -0.5).astype(dtype)
            out[name] = quantize(w, dtype) if int8 else w
        elif name.endswith("_norm"):
            out[name] = (1.0 + GAIN_STD * x).astype(dtype)
        else:
            out[name] = (BIAS_STD * x).astype(dtype)
    return out


def make_tables(model: dict, root: jax.Array, dtype) -> dict:
    V, H = model["vocab_size"], model["hidden_size"]
    k = jax.random.fold_in(root, 1 << 20)  # past any layer index
    n = lambda i, shape: jax.random.normal(
        jax.random.fold_in(k, i), shape, jnp.float32
    )
    return {
        "embed": n(0, (V, H)).astype(dtype),
        "final_norm": (1.0 + GAIN_STD * n(1, (H,))).astype(dtype),
        "lm_head": (n(2, (H, V)) * H ** -0.5).astype(dtype),
    }


@functools.lru_cache(maxsize=None)
def _generator(shapes_items, num_layers: int, table_dims, mesh: Mesh,
               dtype, int8: bool):
    shapes = dict(shapes_items)
    model = dict(table_dims)
    axis = mesh.axis_names[0]

    def stage(root_data, layer_ids):
        root = jax.random.wrap_key_data(root_data)
        return jax.lax.map(
            lambda l: make_layer(shapes, root, l, dtype, int8), layer_ids
        )

    def whole(root_data):
        layers = jax.shard_map(
            stage, mesh=mesh, in_specs=(P(), P(axis)), out_specs=P(axis),
        )(root_data, jnp.arange(num_layers, dtype=jnp.int32))
        tables = make_tables(model, jax.random.wrap_key_data(root_data), dtype)
        # each chip makes its slice of the vocabulary, so no chip holds the
        # float32 form of a whole table (3 GB at 14B) beside its layers
        split = {"embed": P(axis, None), "lm_head": P(None, axis),
                 "final_norm": P()}
        tables = {
            k: jax.lax.with_sharding_constraint(v, NamedSharding(mesh, split[k]))
            for k, v in tables.items()
        }
        return {"layers": layers, **tables}

    return jax.jit(whole)


def make_params(model: dict, seed: int, weight_dtype: str, devices) -> dict:
    """The whole model on ``devices`` (layers split evenly along the ring),
    in the engine's layout: ``{"embed", "layers": {leaf: [L, ...]},
    "final_norm", "lm_head"}``; int8 leaves are ``QTensor(q, scale)``."""
    int8 = weight_dtype == "int8"
    dtype = jnp.bfloat16 if int8 else DTYPES[weight_dtype]
    L = int(model["num_hidden_layers"])
    if L % len(devices):
        raise ValueError(f"{L} layers do not split over {len(devices)} chips")
    mesh = Mesh(np.asarray(devices), ("pipe",))
    fn = _generator(
        tuple(sorted(leaf_shapes(model).items())), L,
        (("vocab_size", model["vocab_size"]),
         ("hidden_size", model["hidden_size"])),
        mesh, dtype, int8,
    )
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
