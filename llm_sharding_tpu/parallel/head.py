"""Vocab-sharded embedding + LM head over the pipeline axis.

The reference keeps the embedding only on user-facing nodes and final-norm +
lm_head only on the last chain node (``/root/reference/utils/node_worker.py:
105-125, 155-164``) — no node holds vocab tables it doesn't use. The TPU-native
equivalent of that role split under one SPMD program is *vocab parallelism*:
each pipeline stage holds a contiguous ``vocab_size / num_stages`` slice of the
embedding table (and of ``lm_head`` when untied), so

- per-chip HBM for the vocab tables drops by ``num_stages×`` (for a
  128256×4096 bf16 Llama-3 table: ~1.05 GB replicated → ~131 MB per stage on
  an 8-way pipe — twice that again when lm_head is untied);
- the full-vocab logit matmul — previously computed redundantly on every
  stage every microstep — is *distributed*: each stage computes only its
  ``[B, V/S]`` logit slice, and the greedy winner is assembled from per-shard
  maxima with one tiny ``all_gather``.

Collective pattern (all over the ``pipe`` axis, riding ICI):

- ``sp_embed``: masked local-table lookup + ``psum`` — every stage ends up
  with the full embedding of the token block (replicated), which is exactly
  what the pipeline needs since stage 0 consumes it on its next active
  microstep.
- ``sp_next_token``: local final-norm + local logit slice → per-shard
  (max, argmax), ``all_gather`` of 2 scalars per row, global argmax. Greedy
  selection is token-exact vs the monolithic oracle: per-column matmul
  results are independent of column partitioning, and tie-breaking picks the
  lowest stage = lowest vocab index, matching ``jnp.argmax`` semantics.

Host-side ``shard_head_host`` produces the stacked ``[num_stages, ...]``
arrays that ``shard_map`` splits one-slice-per-device (specs from
``head_specs``).
"""

from __future__ import annotations

from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.config import ModelConfig
from ..models.family import family
from ..ops.norms import layer_norm, rms_norm
from ..ops.quant import QTensor, base, embed_rows, head_logits, tied_logits
from .mesh import PIPE_AXIS

# Keys sharded over the vocab dimension (stacked [num_stages, ...] host-side).
VOCAB_SHARDED = ("embed", "lm_head")

HeadParams = dict[str, Any]


def vocab_shard_size(vocab_size: int, num_stages: int) -> int:
    """Per-stage vocab rows (vocab padded up to a multiple of num_stages)."""
    return -(-vocab_size // num_stages)


def shard_head_host(
    cfg: ModelConfig, head_host: HeadParams, num_stages: int
) -> HeadParams:
    """Stack vocab-dim shards: ``embed [V,H] → [S, V/S, H]``,
    ``lm_head [H,V] → [S, H, V/S]``; small leaves (norms, wpe) pass through
    replicated. Host-side numpy — the caller (or jit ingestion) device_puts
    each stage's slice onto its chip only.
    """
    Vs = vocab_shard_size(cfg.vocab_size, num_stages)
    Vp = Vs * num_stages
    pad = Vp - cfg.vocab_size

    def shard_embed(v):  # [V, H] -> [S, V/S, H]
        v = np.asarray(v)
        if pad:
            v = np.pad(v, ((0, pad), (0, 0)))
        return v.reshape(num_stages, Vs, v.shape[1])

    def shard_lm_head(v):  # [H, V] -> [S, H, V/S]
        v = np.asarray(v)
        if pad:
            v = np.pad(v, ((0, 0), (0, pad)))
        return np.transpose(v.reshape(v.shape[0], num_stages, Vs), (1, 0, 2))

    def shard_scale(v):  # per-vocab-row/column scale [V] -> [S, V/S]
        v = np.asarray(v)
        if pad:
            v = np.pad(v, ((0, pad),))
        return v.reshape(num_stages, Vs)

    out: HeadParams = {}
    for k, v in head_host.items():
        if k == "embed":
            # quantized tables (ops/quant.QTensor) shard like raw ones: the
            # scale is per vocab row, so it splits along the same axis as q;
            # type(v) keeps the Int4QTensor marker through the rebuild
            if isinstance(v, QTensor):
                out[k] = type(v)(q=shard_embed(v.q), scale=shard_scale(v.scale))
            else:
                out[k] = shard_embed(v)
        elif k == "lm_head":
            if isinstance(v, QTensor):
                out[k] = type(v)(
                    q=shard_lm_head(v.q), scale=shard_scale(v.scale)
                )
            else:
                out[k] = shard_lm_head(v)
        else:
            out[k] = np.asarray(v)
    return out


def is_sharded_head(head: HeadParams) -> bool:
    # rank check only — works on jax.Array / np.ndarray without transferring
    return base(head["embed"]).ndim == 3


def head_specs(head: HeadParams) -> dict[str, P]:
    """shard_map in_specs pytree for a sharded-head dict."""
    return {k: (P(PIPE_AXIS) if k in VOCAB_SHARDED else P()) for k in head}


def local_view(head: HeadParams) -> HeadParams:
    """Inside shard_map the sharded leaves carry a leading stage dim of 1 —
    drop it so the math below sees ``[Vs, H]`` / ``[H, Vs]``. QTensor leaves
    drop it on q AND scale (plain ``v[0]`` would tuple-index the NamedTuple)."""

    def drop(v):
        if isinstance(v, QTensor):
            return type(v)(q=v.q[0], scale=v.scale[0])
        return v[0]

    return {
        k: (drop(v) if k in VOCAB_SHARDED else v) for k, v in head.items()
    }


@jax.named_scope("ring_hop")
def psum_from(x: jnp.ndarray, owner, axis: str = PIPE_AXIS) -> jnp.ndarray:
    """Broadcast ``x`` from the stage whose axis index equals ``owner`` to all
    stages (the in-program analogue of the reference's ring token-return hop,
    ``node_worker.py:515-525``)."""
    sidx = jax.lax.axis_index(axis)
    return jax.lax.psum(jnp.where(sidx == owner, x, jnp.zeros_like(x)), axis)


@jax.named_scope("embed")
def sp_embed(
    cfg: ModelConfig,
    head: HeadParams,  # local view
    ids: jnp.ndarray,  # [B, S] int32
    positions: jnp.ndarray,  # [B, S] (gpt2 wpe; ignored for llama)
) -> jnp.ndarray:
    """Vocab-parallel embedding lookup → full [B, S, H] on every stage."""
    table = head["embed"]  # [Vs, H] (raw or row-quantized)
    Vs = base(table).shape[0]
    sidx = jax.lax.axis_index(PIPE_AXIS)
    local = ids - sidx * Vs
    ok = (local >= 0) & (local < Vs)
    rows = embed_rows(table, jnp.clip(local, 0, Vs - 1))
    h = jnp.where(ok[..., None], rows, 0)
    h = jax.lax.psum(h, PIPE_AXIS)
    if family(cfg).learned_positions:
        # plain indexing clamps out-of-bounds (sentinel positions of padded
        # prompt slots) exactly like the monolithic gpt2.embed
        h = h + head["pos_embed"][positions]
    if cfg.embed_multiplier != 1.0:  # gemma: hidden scaled by sqrt(H)
        h = h * jnp.asarray(cfg.embed_multiplier, h.dtype)
    return h


@jax.named_scope("head")
def _local_logits(
    cfg: ModelConfig, head: HeadParams, h_last: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Final norm + this stage's [B, V/S] fp32 logit slice (pad columns
    already masked to -inf). Returns (logits, lo) with ``lo`` the slice's
    global vocab offset."""
    if family(cfg).final_layer_norm:
        x = layer_norm(
            h_last, head["final_norm"], head["final_norm_bias"],
            cfg.layer_norm_epsilon,
        )
    elif cfg.passes > 1:
        # a looped stack hands on a CLOSED state: the final norm closed its
        # every pass, the last one too (``models/stack.run_passes``)
        x = h_last
    else:
        x = rms_norm(h_last, head["final_norm"], cfg.rms_norm_eps,
                     cfg.norm_offset)
    if "lm_head" in head:
        logits = head_logits(x, head["lm_head"])  # [B, Vs]
    else:  # tied: contract against the local embedding slice
        logits = tied_logits(x, head["embed"])
    Vs = logits.shape[-1]
    sidx = jax.lax.axis_index(PIPE_AXIS)
    lo = sidx * Vs
    col_ok = (lo + jnp.arange(Vs, dtype=jnp.int32)) < cfg.vocab_size
    return jnp.where(col_ok[None, :], logits, -jnp.inf), lo


def _assemble_argmax(vals: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    """Global argmax over vocab-sharded [B, V/S] values → [B] int32 global
    vocab ids, replicated. One all_gather of 2 scalars per row."""
    loc_max = jnp.max(vals, axis=-1)  # [B]
    loc_arg = jnp.argmax(vals, axis=-1).astype(jnp.int32) + lo  # [B]
    maxs = jax.lax.all_gather(loc_max, PIPE_AXIS)  # [S, B]
    args = jax.lax.all_gather(loc_arg, PIPE_AXIS)  # [S, B]
    # argmax over stages picks the LOWEST stage on ties = lowest vocab index,
    # matching jnp.argmax over the unsharded vocab.
    best = jnp.argmax(maxs, axis=0)  # [B]
    return jnp.take_along_axis(args, best[None, :], axis=0)[0]


@jax.named_scope("sample")
def sp_next_token(
    cfg: ModelConfig,
    head: HeadParams,  # local view
    h_last: jnp.ndarray,  # [B, H] final-depth hidden, replicated across stages
) -> jnp.ndarray:
    """Greedy next token over the vocab-sharded head → [B] int32, replicated.

    Each stage computes only its [B, V/S] logit slice (the full-vocab matmul
    is distributed, not replicated); the global argmax is assembled from
    per-shard (max, argmax) pairs with one all_gather.
    """
    logits, lo = _local_logits(cfg, head, h_last)
    return _assemble_argmax(logits, lo)


def _topk_threshold(scaled: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """Global k-th-largest of vocab-sharded [B, V/S] values → [B, 1].

    The global top-k is a subset of the union of per-shard top-k's, so
    gathering k values per shard and re-selecting reproduces the monolithic
    ``lax.top_k(full, k)[0][:, -1]`` bitwise."""
    Vs = scaled.shape[-1]
    kk = min(top_k, Vs)
    loc = jax.lax.top_k(scaled, kk)[0]  # [B, kk]
    allk = jax.lax.all_gather(loc, PIPE_AXIS)  # [S, B, kk]
    merged = jnp.transpose(allk, (1, 0, 2)).reshape(allk.shape[1], -1)
    return jax.lax.top_k(merged, top_k)[0][:, -1:]


def _topp_filter(scaled: jnp.ndarray, top_p: float) -> jnp.ndarray:
    """Nucleus filter over vocab-sharded [B, V/S] values. The threshold needs
    the full sorted distribution, so the shards are gathered ([B, Vp] fp32 —
    0.5 MB/step at V=128k, negligible next to the matmuls) and the monolith's
    ``ops.sampling.top_p_threshold`` runs replicated: pad columns are -inf →
    zero probability → bitwise the same threshold, hence the same filtered
    set (the top-k/top-p cross-path exactness contract)."""
    from ..ops.sampling import top_p_threshold

    allv = jax.lax.all_gather(scaled, PIPE_AXIS)  # [S, B, Vs]
    full = jnp.transpose(allv, (1, 0, 2)).reshape(allv.shape[1], -1)
    thresh = top_p_threshold(full, top_p)
    return jnp.where(scaled < thresh, -jnp.inf, scaled)


def _sliced_gumbel(
    noise_full: jnp.ndarray,  # [B, V] — the monolith's noise, regenerated
    vocab_size: int,
    num_stages: int,
) -> jnp.ndarray:
    """Each stage's [B, V/S] column slice of the full noise field. Slicing a
    replicated regeneration (0.5 MB/step at V=128k — negligible next to the
    matmuls) is what makes sharded draws EQUAL to monolithic draws."""
    B = noise_full.shape[0]
    Vs = vocab_shard_size(vocab_size, num_stages)
    pad = Vs * num_stages - vocab_size
    if pad:
        noise_full = jnp.concatenate(
            [noise_full, jnp.zeros((B, pad), noise_full.dtype)], axis=1
        )
    sidx = jax.lax.axis_index(PIPE_AXIS)
    return jax.lax.dynamic_slice_in_dim(noise_full, sidx * Vs, Vs, axis=1)


@jax.named_scope("sample")
def sp_sample(
    cfg: ModelConfig,
    head: HeadParams,  # local view
    h_last: jnp.ndarray,  # [B, H] replicated
    key: jnp.ndarray,  # replicated PRNG key (typed or raw uint32 data)
    temperature: float,  # static; <= 0 → greedy
    top_k: int,  # static
    num_stages: int,  # static
    top_p: float = 1.0,  # static
) -> jnp.ndarray:
    """Seeded sampling over the vocab-sharded head → [B] int32, replicated.

    Token-exact vs the monolithic ``ops.sampling.sample`` with the same key:
    the top-k threshold is assembled from per-shard top-k's (bitwise equal to
    the global one), the top-p threshold from a gathered full distribution
    (``_topp_filter``), and the Gumbel noise is regenerated in full on every
    stage from the replicated key, then column-sliced — so each shard
    perturbs its logits with exactly the noise values the monolith would.
    """
    if temperature <= 0.0:
        return sp_next_token(cfg, head, h_last)
    if jnp.issubdtype(key.dtype, jnp.integer):
        key = jax.random.wrap_key_data(key)
    logits, lo = _local_logits(cfg, head, h_last)
    scaled = logits / temperature
    if top_k > 0:
        kth = _topk_threshold(scaled, top_k)
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p < 1.0:
        scaled = _topp_filter(scaled, top_p)
    g_full = jax.random.gumbel(
        key, (h_last.shape[0], cfg.vocab_size), jnp.float32
    )
    g = _sliced_gumbel(g_full, cfg.vocab_size, num_stages)
    return _assemble_argmax(scaled + g, lo)


def seed_chain_init(seeds: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row key chains from integer seeds: ``key(seed) → split``, exactly
    the monolith's first step (``runtime/generate.py``). Returns raw uint32
    key data ``(new_keys [B,2], subs [B,2])`` — ``subs`` samples the first
    token, ``new_keys`` is the stored chain. ONE definition shared by the
    serve and interleaved paths: the cross-path seeded-draw parity the tests
    pin depends on every path walking the identical chain."""

    def mk(sd):
        k, sub = jax.random.split(jax.random.key(sd))
        return jax.random.key_data(k), jax.random.key_data(sub)

    return jax.vmap(mk)(seeds)


def key_chain_split(row_keys: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Advance per-row chains one step: raw ``[B, 2]`` key data → ``(new
    [B,2], subs [B,2])`` — the monolith's per-decode-step split."""

    def spl(kd):
        k, sub = jax.random.split(jax.random.wrap_key_data(kd))
        return jax.random.key_data(k), jax.random.key_data(sub)

    return jax.vmap(spl)(row_keys)


@jax.named_scope("sample")
def sp_sample_rows(
    cfg: ModelConfig,
    head: HeadParams,  # local view
    h_last: jnp.ndarray,  # [B, H] replicated
    row_keys: jnp.ndarray,  # [B, 2] raw uint32 key data, one chain per row
    temperature: jnp.ndarray,  # [B] f32; <= 0 → greedy for that row
    top_k: jnp.ndarray,  # [B] int32; 0 → no top-k for that row
    top_p: jnp.ndarray,  # [B] f32; 1.0 → no top-p for that row
    num_stages: int,  # static
    filtering: bool = True,  # static: compile the top-k/top-p machinery
) -> jnp.ndarray:
    """Per-row seeded sampling (the serving path: each slot row carries its
    own request's key chain, temperature, top-k and top-p — ALL dynamic, so
    per-request values never recompile the decode program). A row with
    temperature t>0 and a key chain seeded like the monolith's draws the
    monolith's B=1 tokens exactly, including its top-k/top-p filters.

    ``filtering=False`` statically compiles the filters OUT (no vocab
    gather, no sort) — the caller flips it the first time a request with
    top_k>0 or top_p<1 arrives, the same one-extra-compile pattern as the
    serve path's ``sampling`` flag. With it on:

    Both filters derive per-row VALUE thresholds from one gathered,
    descending-sorted full distribution ([B, Vp] fp32 — ~0.5 MB at V=128k,
    negligible next to the matmuls):

    - top-k: the k-th largest element — bitwise the monolith's
      ``lax.top_k(scaled, k)[0][:, -1]``;
    - top-p: the monolith's ``top_p_threshold`` (the shared nucleus
      definition, called with ``presorted=True``) over the post-top-k
      distribution, reproduced by VALUE-masking the sorted array at the
      top-k threshold (not position-masking at k), so duplicate logits tied
      at the k-th value survive into the nucleus exactly as they do in the
      monolith's sequential masking.

    Masking ``scaled < max(kth, pth)`` then equals the monolith's two
    sequential maskings (both are value thresholds on the same array)."""
    from ..ops.sampling import top_p_threshold

    logits, lo = _local_logits(cfg, head, h_last)
    greedy = _assemble_argmax(logits, lo)

    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[:, None]

    if filtering:
        allv = jax.lax.all_gather(scaled, PIPE_AXIS)  # [S, B, Vs]
        full = jnp.transpose(allv, (1, 0, 2)).reshape(allv.shape[1], -1)
        desc = -jnp.sort(-full, axis=-1)  # [B, Vp] descending
        Vp = desc.shape[-1]

        k_idx = jnp.clip(top_k - 1, 0, Vp - 1)
        kth = jnp.take_along_axis(desc, k_idx[:, None], axis=-1)  # [B, 1]
        kth = jnp.where((top_k > 0)[:, None], kth, -jnp.inf)

        # value mask keeps k-th-value ties; still descending → presorted
        desc_k = jnp.where(desc < kth, -jnp.inf, desc)
        pth = top_p_threshold(desc_k, top_p, presorted=True)
        pth = jnp.where((top_p < 1.0)[:, None], pth, -jnp.inf)

        thresh = jnp.maximum(kth, pth)
        scaled = jnp.where(scaled < thresh, -jnp.inf, scaled)
    # per-row noise: gumbel(key, (1, V)) row-reshaped == gumbel(key, (V,)),
    # so each row reproduces a B=1 monolith draw
    g_full = jax.vmap(
        lambda kd: jax.random.gumbel(
            jax.random.wrap_key_data(kd), (cfg.vocab_size,), jnp.float32
        )
    )(row_keys)
    g = _sliced_gumbel(g_full, cfg.vocab_size, num_stages)
    sampled = _assemble_argmax(scaled + g, lo)
    return jnp.where(temperature > 0, sampled, greedy)


def head_bytes_per_stage(
    cfg: ModelConfig, num_stages: int, dtype_bytes: int = 2
) -> int:
    """Per-chip bytes for the vocab tables under vocab sharding (embed shard
    + lm_head shard when untied + replicated norm)."""
    Vs = vocab_shard_size(cfg.vocab_size, num_stages)
    H = cfg.hidden_size
    n = Vs * H  # embed shard
    if not cfg.tie_word_embeddings:
        n += H * Vs
    n += H  # final norm
    return n * dtype_bytes


def head_bytes_replicated(cfg: ModelConfig, dtype_bytes: int = 2) -> int:
    """Per-chip bytes if the head were replicated on every stage (the round-1
    layout this module removes)."""
    n = cfg.vocab_size * cfg.hidden_size
    if not cfg.tie_word_embeddings:
        n += cfg.hidden_size * cfg.vocab_size
    n += cfg.hidden_size
    return n * dtype_bytes
