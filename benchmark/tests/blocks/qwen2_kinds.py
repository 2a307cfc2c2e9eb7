"""A block whose layers are of several kinds, for the benchmark's tests: the
rehearsal of what a configuration with, say, recurrent layers beside attention
layers brings — ONE block file with ``layer_kinds`` — at tiny widths, on the
CPU, with no configuration under ``configs/`` and no cell.

The kinds are named by the toy configuration's ``layer_types`` (one name per
layer, as published configurations of such models carry it):

- ``"biased"`` and ``"twin"`` are both the Qwen2 layer of ``blocks/qwen2.py``,
  leaf for leaf: a model of these two kinds has, layer by layer, the weights
  and the margins of the one-kind Qwen2 model of the same seed;
- ``"plain"`` is that layer WITHOUT its q/k/v biases — three leaves fewer, so
  its stack has another tree than its neighbour's. Its first nine leaves are
  drawn as the Qwen2 layer's are, so permuting ``layer_types`` moves the biases
  to other layers and nothing else: what a reference that took layer 1 for an
  attention layer when it is not would look like.

Everything but the layer is the Qwen2 block's own.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark import blocks

QWEN2 = blocks.load("qwen2")

dims, tables = QWEN2.dims, QWEN2.tables
head_static, embed, logits = QWEN2.head_static, QWEN2.embed, QWEN2.logits
DELTA_MEAN, DELTA_MAX = QWEN2.DELTA_MEAN, QWEN2.DELTA_MAX
decode_step_bytes = QWEN2.decode_step_bytes

BIASES = ("bq", "bk", "bv")


def layer_kinds(model: dict) -> tuple:
    return tuple(model["layer_types"])


def layer_leaves(model: dict) -> dict:
    full = QWEN2.layer_leaves(model)
    of_kind = {
        "biased": full, "twin": full,
        "plain": tuple(l for l in full if l.name not in BIASES),
    }
    return {kind: of_kind[kind] for kind in dict.fromkeys(layer_kinds(model))}


def layer_static(model: dict) -> dict:
    return QWEN2.layer_static(model)


def layer_forward(h, p, *, kind, **kw):
    """One layer of ``kind`` over a whole sequence: the Qwen2 layer, for
    ``"plain"`` with biases of zero (its stack has none)."""
    if kind == "plain":
        assert not set(BIASES) & set(p), sorted(p)
        out = lambda w: (w[0] if isinstance(w, tuple) else w).shape[-1]
        p = dict(p, **{"b" + n: jnp.zeros((out(p["w" + n]),), jnp.float32)
                       for n in "qkv"})
    else:
        assert kind in ("biased", "twin"), kind
    return QWEN2.layer_forward(h, p, **kw)
