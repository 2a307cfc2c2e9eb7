"""Tensor parallelism via GSPMD: megatron-style sharding with zero model edits.

The reference has no TP ("every layer's weights live wholly on one node",
SURVEY.md §2) — on TPU it falls out of the sharding system: annotate each
weight with a ``NamedSharding`` over the "tensor" mesh axis and jit the
UNCHANGED model; XLA partitions every matmul and inserts the all-reduces
(psum after wo/w_down) that Megatron implements by hand.

Layout (llama):
- attention: wq/wk/wv column-parallel (head dim), wo row-parallel
- MLP: w_gate/w_up column-parallel (intermediate dim), w_down row-parallel
- lm_head column-parallel (vocab-sharded logits)
- norms/embedding replicated

Requires num_attention_heads, num_key_value_heads and intermediate_size
divisible by the axis size.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.family import family
from ..ops.quant import QTensor

TENSOR_AXIS = "tensor"


def tensor_mesh(num_devices: int, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < num_devices:
        raise ValueError(f"need {num_devices} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:num_devices]), (TENSOR_AXIS,))


def llama_tp_specs(stacked: bool = True) -> dict[str, P]:
    """PartitionSpecs for (layer-stacked) llama params over TENSOR_AXIS."""
    L = (None,) if stacked else ()
    col = P(*L, None, TENSOR_AXIS)  # [L, in, out] sharded on out
    row = P(*L, TENSOR_AXIS, None)  # [L, in, out] sharded on in
    col_b = P(*L, TENSOR_AXIS)  # column-parallel bias: shards with its cols
    rep = P()
    return {
        "layers": {
            "input_norm": rep,
            "wq": col,
            "wk": col,
            "wv": col,
            "wo": row,
            "post_norm": rep,
            "w_gate": col,
            "w_up": col,
            "w_down": row,
            # optional bias keys (qwen2-family / biased-llama checkpoints);
            # consumers look up by the keys actually present
            "bq": col_b,
            "bk": col_b,
            "bv": col_b,
            "bo": rep,  # row-parallel output bias: added once, post-psum
            # a norm on each branch's output (Ouro): over the whole width,
            # after the row-parallel psum
            "attn_out_norm": rep,
            "mlp_out_norm": rep,
        },
        "embed": rep,
        "final_norm": rep,
        "lm_head": P(None, TENSOR_AXIS),
        # a looped stack's exit gate (present only where the model has one)
        "exit_gate": rep,
        "exit_bias": rep,
    }


def gpt2_tp_specs(stacked: bool = True) -> dict[str, P]:
    """PartitionSpecs for (layer-stacked) gpt2 params over TENSOR_AXIS.

    Column-parallel weights carry column-parallel biases; row-parallel
    matmuls (w_proj / w_out) psum first and add their bias once, replicated
    (see ``models/gpt2.attn_mlp_block``). For the EXPLICIT shard_map path
    the fused qkv weight/bias must be column-PERMUTED first so each shard's
    slice is [q_shard | k_shard | v_shard] — ``permute_gpt2_tp_layers``,
    applied (and memoized) by ``pipeline_generate``; the GSPMD path needs no
    permutation (global semantics, XLA reshards)."""
    L = (None,) if stacked else ()
    col = P(*L, None, TENSOR_AXIS)
    row = P(*L, TENSOR_AXIS, None)
    col_b = P(*L, TENSOR_AXIS)
    rep = P()
    return {
        "layers": {
            "ln1_w": rep, "ln1_b": rep,
            "w_qkv": col, "b_qkv": col_b,
            "w_proj": row, "b_proj": rep,
            "ln2_w": rep, "ln2_b": rep,
            "w_fc": col, "b_fc": col_b,
            "w_out": row, "b_out": rep,
        },
        "embed": rep,
        "pos_embed": rep,
        "final_norm": rep,
        "final_norm_bias": rep,
        "lm_head": P(None, TENSOR_AXIS),  # untied heads are model-supported
    }


def quant_leaf_spec(spec: P, leaf):
    """Per-component PartitionSpec for a maybe-quantized leaf (VERDICT r3
    next-#4: int8 × TP). A ``QTensor`` weight ``[.., in, out]`` carries a
    ``[.., out]`` scale: ``q`` shards exactly like the raw weight, and the
    scale drops the contracted (``in``) axis — so a column-parallel weight
    gets a column-sharded scale, and a row-parallel weight (sharded on
    ``in``) gets a replicated scale. Row-parallel correctness holds because
    the scale is constant along the contracted axis: ``psum((x_s @ q_s) *
    scale) == (Σ x_s @ q_s) * scale`` — the model's existing
    ``qmatmul``-then-``psum`` needs no changes. Raw leaves pass through."""
    if not isinstance(leaf, QTensor):
        return spec
    parts = tuple(spec)
    scale_spec = P(*parts[:-2], parts[-1]) if len(parts) >= 2 else P()
    return type(leaf)(q=spec, scale=scale_spec)


def put_maybe_quant(leaf, spec: P, mesh: Mesh, put=None):
    """device_put a maybe-quantized leaf with quant-aware per-component
    shardings. ``put`` overrides the placement call (e.g. ``put_global`` for
    multi-controller runs)."""
    put = put or jax.device_put
    if isinstance(leaf, QTensor):
        sub = quant_leaf_spec(spec, leaf)
        return type(leaf)(
            q=put(leaf.q, NamedSharding(mesh, sub.q)),
            scale=put(leaf.scale, NamedSharding(mesh, sub.scale)),
        )
    return put(leaf, NamedSharding(mesh, spec))


def qkv_perm_indices(h3: int, tp: int) -> np.ndarray:
    """Column permutation for a fused-qkv last axis [q | k | v] →
    [q_0 k_0 v_0 | q_1 k_1 v_1 | ...] so a contiguous 1/tp slice is a
    head-aligned (q, k, v) triple — what the explicit shard_map TP path
    splits locally (``models/gpt2.decoder_layer``). Head-aligned because
    each third is sliced in tp equal chunks and head boundaries divide them
    (validate_tp guarantees heads % tp == 0). Applied INSIDE
    ``pipeline_generate`` (device-side ``jnp.take``) — callers pass raw
    layers and can neither forget nor double-apply the permutation."""
    H = h3 // 3
    Hl = H // tp
    idx = []
    for t in range(tp):
        for blk in range(3):
            start = blk * H + t * Hl
            idx.extend(range(start, start + Hl))
    return np.asarray(idx, np.int32)


def _take_cols(w, idx):
    """Column-permute a maybe-quantized weight (the per-column scale
    permutes with its columns)."""
    if isinstance(w, QTensor):
        return type(w)(
            q=jnp.take(jnp.asarray(w.q), idx, axis=-1),
            scale=jnp.take(jnp.asarray(w.scale), idx, axis=-1),
        )
    return jnp.take(jnp.asarray(w), idx, axis=-1)


def permute_gpt2_tp_layers(layers: dict, tp: int) -> dict:
    """Permute the fused qkv weight + bias for explicit TP; other leaves
    pass through. Device-side gather — works on numpy or jax arrays."""
    idx = qkv_perm_indices(int(layers["b_qkv"].shape[-1]), tp)
    out = dict(layers)
    out["w_qkv"] = _take_cols(layers["w_qkv"], idx)
    out["b_qkv"] = jnp.take(jnp.asarray(layers["b_qkv"]), idx, axis=-1)
    return out


# Memo for the per-call permutation in pipeline_generate: keyed by the
# IDENTITY of the w_qkv leaf (a strong ref to the original is held in the
# entry, so an id can't be silently reused by a new array). Bounded — a
# serving process re-calls with the same stage arrays every request.
_PERMUTE_CACHE: dict = {}


def permute_gpt2_tp_layers_cached(layers: dict, tp: int) -> dict:
    key = (tp, id(layers["w_qkv"]))
    hit = _PERMUTE_CACHE.get(key)
    if hit is not None and hit[0] is layers["w_qkv"]:
        out = dict(layers)
        out.update(hit[1])
        return out
    permuted = permute_gpt2_tp_layers(layers, tp)
    if len(_PERMUTE_CACHE) >= 4:
        _PERMUTE_CACHE.clear()
    _PERMUTE_CACHE[key] = (
        layers["w_qkv"],
        {"w_qkv": permuted["w_qkv"], "b_qkv": permuted["b_qkv"]},
    )
    return permuted


def validate_tp(cfg: ModelConfig, tp: int) -> None:
    for name, val in (
        ("num_attention_heads", cfg.num_attention_heads),
        ("num_key_value_heads", cfg.num_key_value_heads),
        ("intermediate_size", cfg.intermediate_size),
    ):
        if val % tp != 0:
            raise ValueError(f"{name}={val} not divisible by tensor size {tp}")


def shard_params_tp(cfg: ModelConfig, params: Any, mesh: Mesh) -> Any:
    """device_put params with megatron shardings; GSPMD does the rest
    (llama and gpt2 — no permutation needed here: jit keeps global
    semantics and XLA reshards the fused qkv split as required). Quantized
    leaves get per-component specs via ``quant_leaf_spec`` — int8 and TP
    compose (≙ the reference quantizing and sharding together,
    ``/root/reference/utils/model_sharder.py:28-45``)."""
    tp_specs = family(cfg).tp_specs
    if tp_specs is None:
        raise NotImplementedError(f"TP specs: {cfg.model_type!r} unsupported")
    specs = tp_specs()
    tp = mesh.shape[TENSOR_AXIS]
    validate_tp(cfg, tp)

    def put(path_spec, leaf):
        return put_maybe_quant(leaf, path_spec, mesh)

    out = {
        k: put(specs[k], v)
        for k, v in params.items()
        if k not in ("layers", "lm_head")
    }
    out["layers"] = {
        k: put(specs["layers"][k], v) for k, v in params["layers"].items()
    }
    if "lm_head" in params:
        out["lm_head"] = put(specs["lm_head"], params["lm_head"])
    return out


def shard_cache_tp(cache, mesh: Mesh):
    """KV cache sharded over heads ([L, B, C, Hkv, D] → Hkv on the axis)."""
    kv_spec = NamedSharding(mesh, P(None, None, None, TENSOR_AXIS, None))
    rep = NamedSharding(mesh, P())
    return cache._replace(
        k=jax.device_put(cache.k, kv_spec),
        v=jax.device_put(cache.v, kv_spec),
        pos=jax.device_put(cache.pos, rep),
        length=jax.device_put(cache.length, rep),
    )
