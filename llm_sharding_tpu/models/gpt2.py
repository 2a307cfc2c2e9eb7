"""Pure-JAX GPT-2 causal LM — the reference's second architecture.

The reference's ``ModelSharder`` has a "gpt" branch that bundles wte+wpe into
``embedding.pth``, each ``h.{i}`` block into ``block_{i}.pth``, ``ln_f.pth``
and a wte-tied ``lm_head.pth`` (``/root/reference/utils/model_sharder.py:
96-132``). This module is the runtime consumer of that split in pytree form,
with the same stage interface as ``models/llama.py`` (scan over stacked layer
params, explicit KV cache, ragged-stage ``layer_mask``) so the pipeline
runtime is architecture-agnostic.

HF GPT-2 notes: Conv1D weights are stored ``[in, out]`` (no transpose on
conversion), attention/MLP have biases, activations are gelu_new (tanh
approximation), positions come from a learned ``wpe`` table added at embed
time — so unlike Llama there is nothing positional inside the layers, and the
reference's cos/sin-shipping problem never arises.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.flash_attention import attention_step
from ..ops.norms import layer_norm
from ..ops.quant import embed_rows, head_logits, out_dim, qmatmul, tied_logits
from .cache import KVCache
from .config import ModelConfig
from .stack import scan_layers

Params = dict[str, Any]


def init_layer_params(
    cfg: ModelConfig, key: jax.Array, num_layers: int, dtype=jnp.bfloat16
) -> Params:
    H, I = cfg.hidden_size, cfg.intermediate_size
    ks = jax.random.split(key, 4)
    L = num_layers

    def w(k, *shape):
        fan_in = shape[-2]
        return (jax.random.normal(k, (L, *shape), jnp.float32) * fan_in**-0.5).astype(
            dtype
        )

    return {
        "ln1_w": jnp.ones((L, H), dtype), "ln1_b": jnp.zeros((L, H), dtype),
        "w_qkv": w(ks[0], H, 3 * H), "b_qkv": jnp.zeros((L, 3 * H), dtype),
        "w_proj": w(ks[1], H, H), "b_proj": jnp.zeros((L, H), dtype),
        "ln2_w": jnp.ones((L, H), dtype), "ln2_b": jnp.zeros((L, H), dtype),
        "w_fc": w(ks[2], H, I), "b_fc": jnp.zeros((L, I), dtype),
        "w_out": w(ks[3], I, H), "b_out": jnp.zeros((L, H), dtype),
    }


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random weights with the converter's pytree layout (wte-tied head, so no
    ``lm_head`` leaf) — for tests/profiling, like ``models/llama.init_params``."""
    k_emb, k_pos, k_layers = jax.random.split(key, 3)
    V, H = cfg.vocab_size, cfg.hidden_size
    P = cfg.max_position_embeddings
    return {
        "embed": (jax.random.normal(k_emb, (V, H), jnp.float32) * H**-0.5).astype(dtype),
        "pos_embed": (jax.random.normal(k_pos, (P, H), jnp.float32) * 0.02).astype(dtype),
        "layers": init_layer_params(cfg, k_layers, cfg.num_hidden_layers, dtype),
        "final_norm": jnp.ones((H,), dtype),
        "final_norm_bias": jnp.zeros((H,), dtype),
    }


def embed(params: Params, token_ids: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """wte[ids] + wpe[positions] (≙ the reference's bundled GPT embedding,
    ``/root/reference/utils/model_sharder.py:100-108``). The wte table may be
    int8 row-quantized; wpe stays in the model dtype."""
    return embed_rows(params["embed"], token_ids) + params["pos_embed"][positions]


def attn_mlp_block(
    cfg: ModelConfig,
    p: Params,
    h: jnp.ndarray,  # [B, S, H]
    attn_fn,  # (q[B,S,Nh,D], k, v) -> [B,S,Nh,D]
    tp_axis=None,
) -> jnp.ndarray:
    """One GPT-2 block with the attention mechanism injected — the single
    implementation behind the cached (pipeline/decode) path and the
    ring-attention (context-parallel) path, mirroring
    ``models/llama.attn_mlp_block``.

    Under explicit tensor parallelism (``tp_axis`` set) each device holds a
    column slice of the PERMUTED fused qkv (layout [q_shard | k_shard |
    v_shard] per shard — applied by ``pipeline_generate`` via
    ``parallel/tensor.permute_gpt2_tp_layers``), so the local three-way
    split below yields the local head slice; the two row-parallel products
    (w_proj / w_out) psum, and their biases are added once, after the psum.
    """
    B, S, H = h.shape
    D = cfg.head_dim_
    # local head count from the (possibly TP-sharded) fused weight
    Nh = out_dim(p["w_qkv"]) // (3 * D)

    x = layer_norm(h, p["ln1_w"], p["ln1_b"], cfg.layer_norm_epsilon)
    qkv = qmatmul(x, p["w_qkv"]) + p["b_qkv"]  # [B, S, 3·Nh·D] (local)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, Nh, D)
    k = k.reshape(B, S, Nh, D)
    v = v.reshape(B, S, Nh, D)

    attn = attn_fn(q, k, v)
    attn_out = qmatmul(attn.reshape(B, S, Nh * D), p["w_proj"])
    if tp_axis is not None:
        attn_out = jax.lax.psum(attn_out, tp_axis)
    h = h + attn_out + p["b_proj"]

    x = layer_norm(h, p["ln2_w"], p["ln2_b"], cfg.layer_norm_epsilon)
    mlp = jax.nn.gelu(
        (qmatmul(x, p["w_fc"]) + p["b_fc"]).astype(jnp.float32),
        approximate=True,
    )
    mlp_out = qmatmul(mlp.astype(x.dtype), p["w_out"])
    if tp_axis is not None:
        mlp_out = jax.lax.psum(mlp_out, tp_axis)
    h = h + mlp_out + p["b_out"]
    return h


def decoder_layer(
    cfg: ModelConfig,
    p: Params,
    h: jnp.ndarray,  # [B, S, H]
    k_row: jnp.ndarray,  # [B, C, Nh_local, D]
    v_row: jnp.ndarray,
    positions: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, C]
    length: jnp.ndarray,
    tp_axis=None,
):
    rows = {}

    def attn_fn(q, k, v):
        k_r = jax.lax.dynamic_update_slice(
            k_row, k.astype(k_row.dtype), (0, length, 0, 0)
        )
        v_r = jax.lax.dynamic_update_slice(
            v_row, v.astype(v_row.dtype), (0, length, 0, 0)
        )
        rows["k"], rows["v"] = k_r, v_r
        return attention_step(q, k_r, v_r, positions, kv_positions, length)

    h = attn_mlp_block(cfg, p, h, attn_fn, tp_axis)
    return h, rows["k"], rows["v"], None  # no stats (models/stack.py)


def forward_layers(
    cfg: ModelConfig,
    layers: Params,
    h: jnp.ndarray,
    cache: KVCache,
    positions: jnp.ndarray,
    layer_mask: Optional[jnp.ndarray] = None,
    tp_axis: Optional[str] = None,
):
    """Returns ``(h, cache, None)``: the third slot is a model's layer
    stats (``models/stack.scan_layers``), which GPT-2 has none of."""
    def apply(p, h, k_row, v_row, kv_pos, length):
        return decoder_layer(
            cfg, p, h, k_row, v_row, positions, kv_pos, length, tp_axis
        )

    return scan_layers(layers, h, cache, positions, apply, layer_mask)


def forward_layers_paged(
    cfg: ModelConfig,
    layers: Params,
    h: jnp.ndarray,
    k_arena: jnp.ndarray,  # [L, NB, Nh, BS, D]
    v_arena: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, T]
    cols: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, T*BS]
    positions: jnp.ndarray,  # [B, S]
    layer_mask: Optional[jnp.ndarray] = None,
    write_valid=True,
    tp_axis: Optional[str] = None,
    backend: str = "auto",
    k_scale: Optional[jnp.ndarray] = None,  # [L, NB, Nkv] (quantized)
    v_scale: Optional[jnp.ndarray] = None,
    prefill: bool = False,  # static: chunked-prefill traversal — attend
    #   via the query-tiled paged_prefill kernel (see llama counterpart)
    walk=None,  # the prefill kernel's work list (``prefill_walk``)
):
    """Paged serve-decode counterpart of ``forward_layers`` (see
    ``models/llama.forward_layers_paged`` — same contract: fresh KV lands
    through ``paged_attention_write`` (quantizing at insert when the arena
    carries scales), attention streams the table's blocks (dequant fused), kpos
    bookkeeping stays with the caller; returns scale arenas too).
    ``prefill`` switches the attention dispatch to ``paged_prefill``
    for chunk-shaped queries."""
    from ..ops.paged_attention import (
        paged_attention_write, paged_prefill, write_chunk_kv,
    )
    from .stack import scan_layers_paged

    wv = write_valid if isinstance(write_valid, bool) else jnp.asarray(
        write_valid
    )
    # a chunk's rows share their columns: it writes whole blocks from its
    # first column on (llama's note)
    col0 = cols[0, 0] if prefill else None

    def apply(p, l, valid, h, k_all, v_all, ks_all, vs_all):
        out = {}

        def attn_fn(q, k, v):
            if not prefill:  # a decode step (llama's note)
                o, *out["kv"] = paged_attention_write(
                    q, k, v, k_all, v_all, l, block_table, cols, positions,
                    kv_positions, valid=wv & valid, backend=backend,
                    k_scale=ks_all, v_scale=vs_all,
                )
                return o
            kv = write_chunk_kv(
                k_all, v_all, l, block_table, col0, k, v,
                valid=wv & valid, k_scale=ks_all, v_scale=vs_all,
            )
            out["kv"] = kv if ks_all is not None else (*kv, None, None)
            k_a, v_a, ks, vs = out["kv"]
            return paged_prefill(
                q, k_a, v_a, l, block_table, positions, kv_positions,
                backend=backend, k_scale=ks, v_scale=vs, walk=walk,
            )

        h = attn_mlp_block(cfg, p, h, attn_fn, tp_axis)
        return (h, *out["kv"], None)  # no stats (models/stack.py)

    return scan_layers_paged(
        layers, h, k_arena, v_arena, apply, layer_mask,
        k_scale=k_scale, v_scale=v_scale,
    )


def final_logits(cfg: ModelConfig, params: Params, h: jnp.ndarray) -> jnp.ndarray:
    h = layer_norm(h, params["final_norm"], params["final_norm_bias"], cfg.layer_norm_epsilon)
    if "lm_head" in params:
        return head_logits(h, params["lm_head"])
    # GPT-2 always ties lm_head to wte — contract against the table directly.
    return tied_logits(h, params["embed"])


def forward(
    cfg: ModelConfig,
    params: Params,
    token_ids: jnp.ndarray,
    cache: KVCache,
    positions: jnp.ndarray,
) -> tuple[jnp.ndarray, KVCache]:
    h = embed(params, token_ids, positions)
    h, cache, _ = forward_layers(cfg, params["layers"], h, cache, positions)
    return final_logits(cfg, params, h), cache
