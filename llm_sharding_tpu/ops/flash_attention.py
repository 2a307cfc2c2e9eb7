"""Pallas TPU kernel: fused causal flash attention for the prefill hot path.

The XLA path (``ops/attention.py``) materializes a [B, Nkv, G, S, C] score
tensor; this kernel streams K/V through VMEM in blocks with online-softmax
accumulation (scores never leave on-chip memory), blocked for the MXU with
fp32 accumulation. Same position-based masking contract as
``cached_attention`` (``kv_pos <= q_pos``; sentinel = masked) so it is a
drop-in for prefill over the KV cache.

Grid: (B, Nkv, G·S/BLOCK_Q, C/BLOCK_K) — GQA-aware: the G query heads that
share a KV head are FOLDED into the query-row axis before the kernel, so each
KV block is streamed from HBM once per KV head, not once per query head (G×
less KV traffic at llama3-8b geometry, G=4). The fold is exact because the
causal mask depends only on each row's position, which tiles across the G
copies. The KV dimension is innermost and sequential; scratch accumulators
(acc, m, l) carry the online softmax across KV blocks (standard flash
attention recurrence). Masking uses -1e30 (not
-inf): a block that is entirely future/padding contributes p=1 rows under a
still--1e30 running max, and the first real block's correction factor
exp(-1e30 - m_real) = 0 wipes that garbage — so fully-masked prefixes need no
special casing, and never-valid (sentinel) query rows degrade to the same
uniform-average garbage the XLA path produces for them (discarded by callers).

Kernel selection: ``attention_prefill`` picks pallas on TPU for prefill-sized
inputs and the XLA implementation elsewhere (CPU meshes, decode S=1, head_dim
not MXU-aligned). Identical numerics either way (interpret-mode tested on CPU;
cross-checked against the XLA path on a real v5e chip up to S=C=2048 bf16).

VMEM note: per-step working set is block-bounded (~6 MB at BLOCK_Q=512 /
BLOCK_K=1024 / D=128 counting the f32 score/p tiles and scratch) and
shape-independent, inside the 16 MB scoped-VMEM limit with headroom for the
compiler's double-buffering — re-audit this figure before any block bump. Position operands MUST keep their 2-D layouts (qpos
sublane-major, kvpos lane-major — see ``_flash_kernel``); 1-D position
vectors force Mosaic relayouts that blow the scoped-VMEM stack (~88 MB) and
fail compilation at any multi-block grid (the ADVICE r1 finding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import cached_attention

# Block sizes from a sweep of {128,256,512}x{512,1024,2048} at llama3-8b
# geometry, S=C=2048, timed device-side in a fori_loop: (512, 1024) ≈
# (512, 2048) ranked fastest. That sweep ran on another installation; the
# ranking has not been re-measured on this one (PERF.md). 1024 keeps the
# per-step K/V VMEM footprint at 0.5 MB and leaves room for future
# fully-masked-block skipping.
BLOCK_Q = 512
BLOCK_K = 1024
NEG_INF = -1e30  # python float: jnp constants can't be captured by kernels


def _flash_kernel(
    q_ref,  # [1, 1, BQ, D]
    k_ref,  # [1, 1, BK, D]
    v_ref,  # [1, 1, BK, D]
    qpos_ref,  # [1, BQ, 1] — sublane-major: rows align with score rows
    kvpos_ref,  # [1, 1, BK] — lane-major: columns align with score columns
    out_ref,  # [1, 1, BQ, D]
    acc_ref,  # scratch [BQ, D] f32
    m_ref,  # scratch [BQ, 128] f32 (running max, lane-replicated)
    l_ref,  # scratch [BQ, 128] f32 (running denominator)
    *,
    scale,
    kv_blocks,
):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]  # [BQ, D] bf16/f32
    k = k_ref[0, 0]  # [BK, D]
    v = v_ref[0, 0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [BQ, BK] f32

    # Layout-critical: qpos arrives as a [BQ, 1] sublane vector and kvpos as a
    # [1, BK] lane vector, so this broadcastred compare maps directly onto the
    # [BQ, BK] score tile with NO vector relayout. Reading both as 1-D vectors
    # (the round-1 layout) forced Mosaic into lane↔sublane relayouts whose
    # scoped-VMEM stack blew past the 16 MB limit (~88 MB) at any
    # multi-block grid — the compile failure flagged in ADVICE r1.
    mask = kvpos_ref[0] <= qpos_ref[0]  # [BQ, BK]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]  # [BQ, 1]
    l_prev = l_ref[:, :1]
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    p = jnp.exp(s - m_new)  # [BQ, BK]
    corr = jnp.exp(m_prev - m_new)  # [BQ, 1]
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [BQ, D]
    acc_ref[:] = acc_ref[:] * corr + pv
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        out_ref[0, 0] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_attention(
    q: jnp.ndarray,  # [B, S, Nh, D] (RoPE'd)
    k_cache: jnp.ndarray,  # [B, C, Nkv, D] — keys already written
    v_cache: jnp.ndarray,  # [B, C, Nkv, Dv] — Dv may differ from D (latent
    #   attention: values are a slice of the keys); the output is Dv wide
    q_positions: jnp.ndarray,  # [B, S]
    kv_positions: jnp.ndarray,  # [B, C]
    scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, Nh, D = q.shape
    C, Nkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = Nh // Nkv
    if scale is None:
        scale = D ** -0.5

    block_k = min(BLOCK_K, C)
    pad_k = (-C) % block_k
    if pad_k:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        # padded kv slots carry the sentinel so they are always masked
        kv_positions = jnp.pad(
            kv_positions, ((0, 0), (0, pad_k)), constant_values=jnp.int32(2**30)
        )
    Cp = C + pad_k
    kv_blocks = Cp // block_k

    # GQA fold: [B, S, Nh, D] -> [B, Nkv, G*S, D]. Head index h = k*G + g
    # (the reshape contract shared with ``cached_attention``), so folded row
    # g*S + s carries query head (k, g) at sequence position s, and its
    # position is q_positions[s] — tiled G times below. Each (b, k) grid cell
    # now covers ALL G query heads of KV head k: the KV block is fetched once.
    qh = jnp.transpose(q, (0, 2, 1, 3)).reshape(B, Nkv, G * S, D)
    qp = jnp.tile(q_positions, (1, G))  # [B, G*S]
    L = G * S
    block_q = min(BLOCK_Q, L)
    pad_q = (-L) % block_q
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, pad_q)), constant_values=jnp.int32(2**30))
    Lp = L + pad_q

    # head-major layouts for Mosaic (sublane, lane) = (seq, head_dim) tiling
    with jax.named_scope("kv_layout"):
        kh = jnp.transpose(k_cache, (0, 2, 1, 3))  # [B, Nkv, Cp, D]
        vh = jnp.transpose(v_cache, (0, 2, 1, 3))
    qp = qp[..., None]  # [B, Lp, 1] — sublane-major (see kernel)
    kp = kv_positions[:, None, :]  # [B, 1, Cp] — lane-major

    grid = (B, Nkv, Lp // block_q, kv_blocks)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, kv_blocks=kv_blocks),
        out_shape=jax.ShapeDtypeStruct((B, Nkv, Lp, Dv), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, k, i, j: (b, k, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, k, i, j: (b, k, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dv), lambda b, k, i, j: (b, k, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, k, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, k, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, Dv), lambda b, k, i, j: (b, k, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash",
    )(qh, kh, vh, qp, kp)
    out = out[:, :, :L].reshape(B, Nkv, G, S, Dv)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, S, Nh, Dv)


def attention_prefill(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    scale: float | None = None,
) -> jnp.ndarray:
    """Kernel selection: pallas flash kernel on TPU for prefill-sized inputs,
    XLA ``cached_attention`` otherwise (CPU meshes, decode S=1, non-aligned
    head_dim). Identical numerics either way (tested via interpret mode)."""
    B, S, Nh, D = q.shape
    use_pallas = (
        jax.default_backend() == "tpu"
        and S > 1
        and D % 128 == 0
        and v_cache.shape[-1] % 128 == 0
    )
    if use_pallas:
        return flash_attention(q, k_cache, v_cache, q_positions, kv_positions, scale)
    return cached_attention(q, k_cache, v_cache, q_positions, kv_positions, scale)


@jax.named_scope("attn")
def attention_step(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    length: jnp.ndarray,  # scalar write offset (pre-write) from the KVCache
    scale: float | None = None,
) -> jnp.ndarray:
    """Shape-dispatched attention: decode steps (S=1, static under jit) take
    the plain XLA path — already score-tensor-free at S=1; the real
    full-capacity-read fix is HOST-level cache segmentation in
    ``runtime/generate.py`` (an in-program ``lax.switch`` over bucket slices
    was measured SLOWER on v5e — 62 vs 75 tok/s at C=4096 — because XLA
    copies the full cache operand into the selected branch, per layer per
    step). Prefill keeps the flash/XLA selection. ``length`` is accepted so
    model layers stay agnostic to the dispatch policy."""
    del length
    if q.shape[1] == 1:
        return cached_attention(
            q, k_cache, v_cache, q_positions, kv_positions, scale
        )
    return attention_prefill(q, k_cache, v_cache, q_positions, kv_positions, scale)
