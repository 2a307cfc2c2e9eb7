"""Int8 weight quantization: HBM-resident int8 weights, dequant fused into
the matmul.

Parity + perf in one mechanism. The reference loads checkpoints in int8/int4
through bitsandbytes (``/root/reference/utils/model_sharder.py:28-45`` —
``load_in_8bit``/``load_in_4bit``, weights stay quantized on the device); the
TPU-native equivalent keeps weights as int8 arrays in HBM with
per-output-channel scales and lets XLA fuse the int8→bf16 convert into the
dot's operand load. Single-chip decode is weight-read bandwidth-bound, so
halving weight bytes is a direct throughput lever.

Scheme: symmetric per-output-channel absmax. For a weight ``[.., in, out]``
the scale is ``absmax(w, axis=in) / 127`` per ``out`` column (stacked layer
weights ``[L, in, out]`` get per ``(L, out)`` scales). The matmul computes
``(x @ q.astype(x.dtype)) * scale`` — the scale factors out of the dot
because it is constant along the contracted axis.

``QTensor`` is a NamedTuple, hence automatically a pytree: layer stacking,
``lax.scan`` over stacked layers, shard_map pytree-prefix specs, and the
engine's host/device moves all work unchanged.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Union

import numpy as np
import jax
import jax.numpy as jnp


class QTensor(NamedTuple):
    q: jax.Array  # int8, same shape as the original weight [.., in, out]
    scale: jax.Array  # [.., out] per-output-channel scale (original dtype)


class Int4QTensor(QTensor):
    """Int4-quantized weight (≙ the reference's ``load_in_4bit``,
    ``/root/reference/utils/model_sharder.py:28-45``): values in [-7, 7] with
    absmax/7 scales. DEVICE residence is int8 (every QTensor code path —
    qmatmul, scan stacking, shard_map specs — applies unchanged); the shard
    store packs two values per byte on DISK (``utils/shard_store.py``), so
    int4 stores are half the int8 size.

    Why not int4 in HBM: measured on a v5e chip (jax 0.9.0), native ``S4``
    arrays fail at dispatch (RecursionError in jit with any int4 operand),
    and VPU nibble-unpacking of packed int8 (~4.2 ms per 400 MB, shifts +
    interleave don't fuse into the dot) is slower than simply reading the
    int8 bytes — int4-in-HBM loses to int8-in-HBM on this stack. The win
    int4 keeps is the 2× smaller checkpoint (the reference's edge story:
    shipping shards to devices), at int4 precision cost.

    A NamedTuple subclass flattens/unflattens as its own pytree node type,
    so tree ops rebuild Int4QTensor (not QTensor) and the store can detect
    it at save time."""


WeightLike = Union[jax.Array, np.ndarray, QTensor]


# Two separate jits, deliberately: in one program XLA CSEs the two uses of
# w.astype(f32) (the absmax reduce and the quantize chain) into a
# MATERIALIZED fp32 copy of the weight — 5.8 GB for a 7B-class stacked leaf,
# which OOMs next to the bf16 params. Split, each use fuses into its own
# loop and no fp32 buffer ever exists. The donating variant frees each bf16
# leaf as its int8 replacement is produced (peak = params + one int8 leaf).
@functools.partial(jax.jit, static_argnames=("contract_axis",))
def _absmax_jit(w, contract_axis: int):
    return jnp.max(jnp.abs(w.astype(jnp.float32)), axis=contract_axis)


def _q_impl(w, denom, qmax):
    return jnp.round(w.astype(jnp.float32) / denom * qmax).astype(jnp.int8)


_q_jit = jax.jit(_q_impl, static_argnames=("qmax",))
_q_donate_jit = jax.jit(_q_impl, donate_argnums=(0,), static_argnames=("qmax",))


def quantize_tensor(
    w, contract_axis: int = -2, donate: bool = False, bits: int = 8
) -> QTensor:
    """Symmetric per-output-channel quantization. ``contract_axis`` is the
    axis a matmul contracts over (the scale must be constant along it to
    factor out of the dot). ``donate=True`` consumes ``w`` (device buffers
    freed as the quantized copy is produced). ``bits`` is 8 (int8, qmax 127)
    or 4 (``Int4QTensor``: values in [-7, 7], int8-resident, nibble-packed
    on disk)."""
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    qmax = 127.0 if bits == 8 else 7.0
    w = jnp.asarray(w)
    absmax = _absmax_jit(w, contract_axis=contract_axis)
    scale = (absmax / qmax).astype(w.dtype)
    denom = jnp.expand_dims(jnp.maximum(absmax, 1e-12), contract_axis)
    q = (_q_donate_jit if donate else _q_jit)(w, denom, qmax=qmax)
    if donate:
        # block so the donated bf16 buffer is actually released before the
        # NEXT leaf's dispatch allocates its outputs — async dispatch
        # reserves output buffers ahead of execution, and at 7B scale the
        # un-released inputs + reserved outputs overrun HBM
        jax.block_until_ready(q)
    cls = QTensor if bits == 8 else Int4QTensor
    return cls(q=q, scale=scale)


def dequantize(t: QTensor, contract_axis: int = -2) -> jnp.ndarray:
    scale = jnp.expand_dims(t.scale, contract_axis)
    return t.q.astype(scale.dtype) * scale


def base(w: WeightLike):
    """The storage array of a maybe-quantized weight (for shape/ndim checks
    and host-side slicing that must not dequantize)."""
    return w.q if isinstance(w, QTensor) else w


def out_dim(w: WeightLike) -> int:
    """Output (last-axis) size of a maybe-quantized weight."""
    return base(w).shape[-1]


def qmatmul(x: jnp.ndarray, w: WeightLike) -> jnp.ndarray:
    """``x @ w`` accepting a raw array or a QTensor. For QTensor the int8
    operand is cast inside the dot (XLA fuses the convert into the operand
    load — no bf16 copy of the weight materializes in HBM) and the
    per-column scale is applied to the product."""
    if isinstance(w, QTensor):
        return (x @ w.q.astype(x.dtype)) * w.scale.astype(x.dtype)
    return x @ w


def embed_rows(table: WeightLike, ids: jnp.ndarray) -> jnp.ndarray:
    """Embedding lookup ``table[ids]`` accepting a raw ``[V, H]`` array or a
    row-quantized QTensor (``scale`` per vocab row — the layout
    ``quantize_params(quantize_head=True)`` produces). Gathers int8 rows and
    dequantizes only the gathered rows."""
    if isinstance(table, QTensor):
        dt = table.scale.dtype
        return table.q[ids].astype(dt) * table.scale[ids][..., None]
    return table[ids]


def head_logits(x: jnp.ndarray, w: WeightLike) -> jnp.ndarray:
    """Untied-head projection ``x @ w`` in fp32. For a QTensor the per-column
    scale is applied AFTER the fp32 cast — same precision contract as
    ``tied_logits`` (a bf16 scale-multiply on final logits would collapse
    sub-ulp logit differences and flip greedy/top-k ties vs the tied path)."""
    if isinstance(w, QTensor):
        prod = x @ w.q.astype(x.dtype)
        return prod.astype(jnp.float32) * w.scale.astype(jnp.float32)
    return (x @ w).astype(jnp.float32)


def tied_logits(x: jnp.ndarray, table: WeightLike) -> jnp.ndarray:
    """Tied-head projection ``x @ table.T`` (``einsum('...h,vh->...v')``) in
    fp32, accepting a raw table or a row-quantized QTensor. The per-row scale
    is constant along the contracted ``h`` axis, so it factors out of the dot
    and the int8 table is consumed directly by the matmul — the tied vocab
    table (788 MB bf16 at llama-3 geometry, read EVERY decode step by the
    head) halves to int8 bytes."""
    if isinstance(table, QTensor):
        prod = jnp.einsum("...h,vh->...v", x, table.q.astype(x.dtype))
        return prod.astype(jnp.float32) * table.scale.astype(jnp.float32)
    return jnp.einsum("...h,vh->...v", x, table).astype(jnp.float32)


# Layer-weight keys quantized by default: the matmul weights. Norm gains and
# biases stay in the model dtype (tiny, precision-critical).
# A model with experts (OLMoE) carries its expert matrices ``we_*`` in place
# of the dense MLP's; its router stays in the model dtype (it is multiplied
# out in float32 — a coarser router flips the top-k between near ties).
LLAMA_QUANT_KEYS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "we_gate", "we_up", "we_down",
)
GPT2_QUANT_KEYS = ("w_qkv", "w_out", "w_fc", "w_proj")
# deepseek_v3 (models/deepseek_v3.py): the latent attention's projections and
# absorbed factors and the shared expert, beside ``wo`` / ``w_*`` / ``we_*``
# above; the router and its float32 correction bias stay as they are
DEEPSEEK_QUANT_KEYS = (
    "wq_a", "wq_b", "wkv_a", "w_uk", "w_uv", "ws_gate", "ws_up", "ws_down",
)
# mimo_v2 (models/mimo_v2.py): the fused qkv projection beside ``wo`` /
# ``w_*`` / ``we_*``; router, correction bias and the float32 sink stay
MIMO_QUANT_KEYS = ("wqkv",)
# nemotron_h (models/nemotron_h.py): the mixer's two projections and the two
# latent projections beside ``wq`` .. ``wo`` / ``ws_*`` / ``we_*``; router,
# conv, ``A_log``, ``D``, ``dt_bias`` and the norms stay
NEMOTRON_QUANT_KEYS = ("w_in", "w_out", "w_lat_down", "w_lat_up")
# jamba (models/jamba.py): a Mamba-1 mixer's ``w_in`` / ``w_out`` are
# nemotron_h's names and its MLP llama's; the low-rank path to ``dt``, ``B``
# and ``C`` beside them (``w_x``, ``w_dt``); conv, ``A_log``, ``D``,
# ``dt_bias`` and the norms stay
JAMBA_QUANT_KEYS = ("w_x", "w_dt")
# solar_open2 (models/solar_open2.py): a mixer's ``wq`` / ``wk`` / ``wv`` /
# ``wo`` and the attention layers' ``w_gate`` are llama's names, its experts
# deepseek_v3's; a KDA mixer's two low-rank pairs (decay, output gate) beside
# them. ``w_beta`` (a column a head), the conv, ``A_log``, ``dt_bias`` and the
# norm gains stay
SOLAR_QUANT_KEYS = ("w_a_down", "w_a_up", "w_g_down", "w_g_up")
# longcat_flash (models/longcat_flash.py): a layer's two sub-layers carry
# deepseek_v3's attention and dense-MLP names with a ``_0`` / ``_1`` suffix,
# every one a plain ``[in, out]`` matmul; the experts' three are ``we_*``
# above; ``router``, ``router_bias`` and the norm gains stay
LONGCAT_QUANT_KEYS = tuple(
    f"{name}_{i}" for i in (0, 1) for name in (
        "wq_a", "wq_b", "wkv_a", "w_uk", "w_uv", "wo", "w_gate", "w_up",
        "w_down",
    )
)


def is_kinds_tree(layers: dict) -> bool:
    """A per-kind layers tree ``{kind: {leaf: ...}}`` (a model whose layers
    are of several kinds, ``ModelConfig.layer_kinds``) and not ``{leaf:
    ...}``: its values are dicts."""
    return bool(layers) and all(isinstance(v, dict) for v in layers.values())


def quantize_layer_params(
    layers: dict, keys=None, donate: bool = False, bits: int = 8
) -> dict:
    """Quantize a (stacked ``[L, in, out]``) layer pytree's matmul weights.
    Unknown keys pass through untouched. ``donate=True`` consumes each input
    leaf as its int8 replacement is produced (peak memory = params + one
    int8 leaf — required to quantize a 7B-class model in place on a 16 GB
    chip; the caller's original arrays are invalidated)."""
    if keys is None:
        keys = (
            LLAMA_QUANT_KEYS + GPT2_QUANT_KEYS + DEEPSEEK_QUANT_KEYS
            + MIMO_QUANT_KEYS + NEMOTRON_QUANT_KEYS + JAMBA_QUANT_KEYS
            + SOLAR_QUANT_KEYS + LONGCAT_QUANT_KEYS
        )
    if is_kinds_tree(layers):  # one stack per kind: each kind's leaves
        return {
            kind: quantize_layer_params(sub, keys, donate=donate, bits=bits)
            for kind, sub in layers.items()
        }
    if not donate:
        return {
            k: (
                quantize_tensor(v, bits=bits)
                if k in keys and not isinstance(v, QTensor)
                else v
            )
            for k, v in layers.items()
        }
    # Donating: POP each leaf out of the input dict so ours is the last
    # reference — a buffer that is still referenced elsewhere cannot actually
    # be released at donation time. The input dict is emptied (consumed).
    out: dict = {}
    for k in list(layers.keys()):
        v = layers.pop(k)
        if k in keys and not isinstance(v, QTensor):
            out[k] = quantize_tensor(v, donate=True, bits=bits)
        else:
            out[k] = v
        del v
    return out


def quantize_params(
    params: dict,
    keys=None,
    donate: bool = False,
    quantize_head: bool = False,
    bits: int = 8,
) -> dict:
    """Quantize a full model params pytree's layer weights. Norms stay in the
    model dtype (tiny, precision-critical).

    ``quantize_head=True`` additionally quantizes the vocab tables — the
    reference's ``load_in_8bit`` keeps lm_head fp16 (bitsandbytes default),
    so this is opt-in: ``embed [V, H]`` gets per-ROW scales (valid for both
    the gather lookup and the tied-head contraction over ``h``), an untied
    ``lm_head [H, V]`` gets per-column scales (plain ``qmatmul``). At
    llama-3.2-3b geometry the tied table is 788 MB bf16 — ~20% of ALL weight
    bytes read per decode step once the layers are int8. ``pos_embed``
    (gpt2 wpe) stays in the model dtype (small)."""
    out = dict(params)
    out["layers"] = quantize_layer_params(
        params["layers"], keys, donate=donate, bits=bits
    )
    if quantize_head:
        for k, ax in (("embed", -1), ("lm_head", -2)):
            if k not in out or isinstance(out[k], QTensor):
                continue
            v = out.pop(k)
            if donate:
                # drop the caller dict's reference too (same consumed-input
                # contract as the layers path above) — a table still
                # referenced elsewhere cannot actually be released
                params.pop(k, None)
            out[k] = quantize_tensor(v, contract_axis=ax, donate=donate, bits=bits)
            del v
    return out


def is_quantized(layers: dict) -> bool:
    if is_kinds_tree(layers):
        return any(is_quantized(sub) for sub in layers.values())
    return any(isinstance(v, QTensor) for v in layers.values())


# --------------------------------------------------------------- KV cache
# Quantized KV storage for the paged serve arena (KIVI, Liu et al. 2024;
# KVQuant, Hooper et al. 2024 — KV bytes dominate serving HBM once weights
# are int8). Scheme: symmetric per-block-per-kv-head absmax — one f32 scale
# per (arena block, kv head), stored in a parallel scale arena shaped like
# the block axis of the pool ([NB, Nkv] per layer). Per-head because head
# magnitudes differ by orders of magnitude (per-channel would double scale
# storage for little gain at serving block sizes); per-block because the
# block is the arena's transfer unit — the Pallas decode kernel DMAs a
# block and its one scale row together and dequantizes in VMEM
# (``ops/paged_attention``), so quantized KV never materializes as bf16 in
# HBM. Unlike weights, KV arrives incrementally: ``write_block_kv`` keeps
# a RUNNING absmax per block — when a new entry raises a block's scale,
# the block's existing codes are requantized to the new scale (a
# dequant→requant round on exactly the touched blocks). bf16 KV stays the
# serving default; quantized is opt-in and drift-gated (the benchmark's
# ``correct``).

#: ``--kv-dtype`` vocabulary. "bf16" means "store in the engine's compute
#: cache dtype" (no quantization — the pre-existing exact path).
KV_DTYPES = ("bf16", "int8", "fp8")

#: Largest-magnitude code point the quantizer maps absmax onto.
_KV_QMAX = {"int8": 127.0, "fp8": 448.0}  # e4m3fn max normal


def kv_storage_dtype(name: str, compute_dtype=jnp.bfloat16):
    """Resolve a ``--kv-dtype`` name to the arena storage dtype."""
    if name == "bf16":
        return jnp.dtype(compute_dtype)
    if name == "int8":
        return jnp.dtype(jnp.int8)
    if name == "fp8":
        return jnp.dtype(jnp.float8_e4m3fn)
    raise ValueError(f"kv dtype must be one of {KV_DTYPES}, got {name!r}")


def is_kv_quantized(dtype) -> bool:
    """True for 1-byte KV storage dtypes (int8 / fp8) — the arenas that
    carry a parallel scale arena and dequantize at read."""
    dt = jnp.dtype(dtype)
    return dt == jnp.dtype(jnp.int8) or dt == jnp.dtype(jnp.float8_e4m3fn)


def kv_qmax(dtype) -> float:
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.int8):
        return _KV_QMAX["int8"]
    if dt == jnp.dtype(jnp.float8_e4m3fn):
        return _KV_QMAX["fp8"]
    raise ValueError(f"{dt.name} is not a quantized KV dtype")


def fp8_kv_supported() -> bool:
    """Whether this jax backend can round-trip float8_e4m3fn arrays (the
    ``--kv-dtype fp8`` platform gate — checked once at server
    construction, so unsupported platforms fail with a curated message
    instead of a lowering error mid-serve)."""
    try:
        x = jnp.asarray([1.0, -2.0], jnp.float8_e4m3fn)
        jax.block_until_ready(x.astype(jnp.float32) * 2.0)
        return True
    except Exception:  # noqa: BLE001 — any backend failure means "no"
        return False


def kv_quantize(x: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Quantize KV values against a (broadcastable) per-block-per-head
    scale. ``scale`` is the running absmax / qmax, so values never exceed
    the code range; a zero scale (virgin block) quantizes zeros to zeros
    via the safe denominator."""
    y = x.astype(jnp.float32) / jnp.maximum(scale, 1e-12)
    qmax = kv_qmax(dtype)
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        return jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    return jnp.clip(y, -qmax, qmax).astype(dtype)


def kv_dequantize(q: jnp.ndarray, scale: jnp.ndarray, out_dtype) -> jnp.ndarray:
    """Inverse of ``kv_quantize`` (f32 multiply, cast to the compute
    dtype — the same op the fused kernel applies per streamed block)."""
    return (q.astype(jnp.float32) * scale).astype(out_dtype)
