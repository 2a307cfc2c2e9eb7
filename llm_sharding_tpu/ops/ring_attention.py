"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Long-context capability the reference entirely lacks (SURVEY.md §5
"Long-context / sequence parallelism — absent": it processes the whole
sequence on every stage and grows a DynamicCache until OOM,
``/root/reference/utils/node_worker.py:184, 253-258``). Here the sequence
dimension is sharded across devices on a "seq" mesh axis; each device holds
its Q chunk and the KV blocks rotate around the ring via ``lax.ppermute``,
with flash-style online-softmax accumulation — memory per device is
O(S/N · S/N) per block instead of O(S²), and the ICI hops overlap compute.

The causal mask is position-based (``kv_pos <= q_pos``) like
``ops/attention.py``, so right-padding and ragged chunks work unchanged.
Matches the blockwise-parallel formulation of Liu et al.'s Ring Attention
(PAPERS.md) in its simplest rotate-KV form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ring_attention(
    q: jnp.ndarray,  # [B, Sq, Nh, D] — local query chunk (RoPE'd)
    k: jnp.ndarray,  # [B, Skv, Nkv, D] — local key chunk
    v: jnp.ndarray,  # [B, Skv, Nkv, D]
    q_positions: jnp.ndarray,  # [B, Sq] absolute positions (sentinel = pad)
    kv_positions: jnp.ndarray,  # [B, Skv]
    axis_name: str,
    scale: float | None = None,
) -> jnp.ndarray:
    """Exact attention of local queries over the GLOBAL (ring-gathered)
    key/value sequence. Returns [B, Sq, Nh, D]. Call under shard_map with the
    sequence dim sharded on ``axis_name``."""
    B, Sq, Nh, D = q.shape
    Nkv = k.shape[2]
    G = Nh // Nkv
    if scale is None:
        scale = D ** -0.5
    num_chunks = jax.lax.axis_size(axis_name)
    ring = [(i, (i + 1) % num_chunks) for i in range(num_chunks)]

    qg = q.reshape(B, Sq, Nkv, G, D).astype(jnp.float32)

    acc = jnp.zeros((B, Sq, Nkv, G, D), jnp.float32)
    m = jnp.full((B, Sq, Nkv, G), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, Sq, Nkv, G), jnp.float32)

    def step(_, carry):
        acc, m, l, k, v, kv_pos = carry
        # scores[b, s, nkv, g, t]
        scores = jnp.einsum(
            "bskgd,btkd->bskgt", qg, k.astype(jnp.float32)
        ) * scale
        mask = (kv_pos[:, None, :] <= q_positions[:, :, None])[:, :, None, None, :]
        scores = jnp.where(mask, scores, -jnp.inf)

        m_blk = scores.max(axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # rows with nothing valid anywhere yet keep m=-inf; make exp finite
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(scores), scores - safe_m[..., None], -jnp.inf))
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bskgt,btkd->bskgd", p, v.astype(jnp.float32)
        )
        k, v, kv_pos = jax.lax.ppermute((k, v, kv_pos), axis_name, ring)
        return acc_new, m_new, l_new, k, v, kv_pos

    acc, m, l, *_ = jax.lax.fori_loop(
        0, num_chunks, step, (acc, m, l, k, v, kv_positions)
    )
    out = jnp.where(l[..., None] > 0, acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
    return out.reshape(B, Sq, Nh, D).astype(q.dtype)
