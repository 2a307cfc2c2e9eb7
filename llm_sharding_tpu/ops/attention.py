"""Cached causal attention (GQA-aware), pure JAX.

Replaces the native kernels under HF's attention path (cuBLAS/SDPA, reached
via ``LlamaDecoderLayer`` at ``/root/reference/utils/shard_loader.py:66-74``)
with XLA-compiled einsums sized for the MXU. The KV cache is an explicit
fixed-capacity array (see ``models/cache.py``) rather than HF ``DynamicCache``
(``/root/reference/utils/node_worker.py:184``): queries attend over the whole
capacity with a mask built from absolute positions, so prefill (S>1) and
decode (S=1) share one code path and one compiled shape per (B, S, C).

The reference never passes an attention mask (fine for batch-1 causal+cache,
``utils/node_worker.py:255``); here the mask is explicit, which also gives
correct batched decode — a capability the reference lacks (SURVEY.md §2, DP
row).
"""

from __future__ import annotations

import jax.numpy as jnp


def cached_attention(
    q: jnp.ndarray,  # [B, S, Nh, D] — already RoPE'd if applicable
    k_cache: jnp.ndarray,  # [B, C, Nkv, D] — new keys already written
    v_cache: jnp.ndarray,  # [B, C, Nkv, D]
    q_positions: jnp.ndarray,  # [B, S] absolute positions of the queries
    kv_positions: jnp.ndarray,  # [B, C] absolute position of each cache slot's
    #   key; empty/pad slots carry POS_SENTINEL and are masked out automatically
    scale: float | None = None,
    window: int = 0,  # static: > 0 keeps keys ``q_pos - window < kv_pos``
    sink=None,  # [Nh] f32: a per-head logit that joins the row's softmax
    #   and whose column is dropped — ``exp(sink)`` in the denominator only
) -> jnp.ndarray:
    """Causal attention of ``q`` over the cache. Returns ``[B, S, Nh, Dv]``.

    The mask is position-based (``kv_pos <= q_pos``), not slot-index-based, so
    one rule covers prefill, decode, right-padded batches, and uninitialized
    cache slots. GQA: ``Nh`` must be a multiple of ``Nkv``; query heads are
    grouped. Softmax in fp32 (bf16 activations otherwise).
    """
    B, S, Nh, D = q.shape
    C, Nkv = k_cache.shape[1], k_cache.shape[2]
    G = Nh // Nkv
    if scale is None:
        scale = D ** -0.5

    qg = q.reshape(B, S, Nkv, G, D)
    # scores[b, k, g, s, t] = q[b,s,(k,g)] · key[b,t,k]. fp32 ACCUMULATION via
    # preferred_element_type, but bf16 operands stay bf16 into the MXU — no
    # materialized fp32 copy of the K cache (it dominated decode HBM traffic).
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale

    mask = kv_positions[:, None, :] <= q_positions[:, :, None]  # [B, S, C]
    if window:
        mask &= kv_positions[:, None, :] > q_positions[:, :, None] - window
    mask = mask[:, None, None, :, :]  # [B,1,1,S,C]
    scores = jnp.where(mask, scores, jnp.float32(-1e30))

    if sink is None:
        probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        probs = probs / probs.sum(axis=-1, keepdims=True)
    else:
        s_h = sink.astype(jnp.float32).reshape(1, Nkv, G, 1, 1)
        m = jnp.maximum(scores.max(axis=-1, keepdims=True), s_h)
        probs = jnp.exp(scores - m)
        probs = probs / (probs.sum(axis=-1, keepdims=True) + jnp.exp(s_h - m))

    # probs down-cast to the cache dtype for the PV matmul — the same
    # precision contract as the Pallas kernel (`p.astype(v.dtype)`).
    out = jnp.einsum(
        "bkgst,btkd->bskgd", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, S, Nh, v_cache.shape[-1]).astype(q.dtype)


# ``bucketed_decode_attention`` (the decode-window ``lax.switch`` over
# power-of-two cache prefixes) was RETIRED here: measured on v5e (3B,
# C=4096) it was slower than full-capacity attention — 62 vs 75 tok/s —
# because XLA copies the full cache operands into the selected conditional
# branch (the README "Paged KV serving" section keeps the figure). Its
# goal — decode HBM traffic proportional to the live prefix, not the
# capacity — is delivered by ``ops/paged_attention.py``, now wired through
# the serve programs end to end: paged decode in ``parallel/serve.py``
# writes fresh KV via a block-indexed scatter and streams exactly the
# row's mapped blocks from the pooled arena (Pallas kernel; the XLA
# gather inside the op is the exact CPU fallback), with no branch copy
# and no materialized window.
