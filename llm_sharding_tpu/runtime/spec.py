"""Speculative decoding: n-gram self-drafting + batched verification.

Single-chip decode sits at ~78-87% of the v5e HBM roofline (VERDICT r5) —
one weight pass per token is the bound, and the only structural lever past
it is committing MORE THAN ONE token per weight pass (Leviathan et al. 2023,
*Fast Inference from Transformers via Speculative Decoding*). Prompt-lookup
/ n-gram drafting (Saxena 2023) gets there with NO draft model: drafts come
from the longest suffix match against the request's own prompt + generated
ids, which fits this repo exactly — checkpoints are sliced per layer and no
small-model artifact exists.

Pieces:

- ``ngram_draft``: the host-side drafter. Pure numpy over one row's token
  ids; returns up to K proposed continuation tokens (empty when no suffix
  recurs — the step then degenerates to a plain decode step).
- ``AdaptiveK``: per-row draft-width backoff. The verify program is compiled
  at a STATIC width K (one program, drafts right-padded), but each row's
  effective draft length is dynamic — rows whose drafts keep missing stop
  paying the K-wide verify for nothing.
- ``spec_generate``: the single-host decode loop (``runtime/generate``'s
  ``speculate=K`` path). Host drafts per row, one jitted verify step runs a
  single forward over the K+1 draft positions per row and commits a
  VARIABLE number of tokens per row (greedy: exact leading-match acceptance,
  so the output is token-identical to the non-speculative loop; sampled:
  rejection-style acceptance that preserves the target distribution).
- KV bookkeeping: the verify forward writes its K+1 entries into a SCRATCH
  region at the top of the cache (the cache is allocated ``K+1`` slots over
  the requested capacity), then the accepted prefix is compacted into the
  canonical position-aligned slots per row and the scratch positions reset
  to the sentinel — rejected draft positions are logically discarded by the
  rewind; nothing downstream ever attends them. Per-row acceptance means
  per-row write offsets, which the scratch+compact scheme provides without
  giving up the shared-offset cache layout the rest of the stack uses.

The serving-path analogue (``parallel/serve.serve_verify`` driven by
``runtime/server.PipelineServer``) shares the drafter, the adaptive-K
controller and the metrics below.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..models.cache import POS_SENTINEL, init_cache
from ..models.config import ModelConfig
from ..obs.metrics import REGISTRY

# -- observability: drafted/accepted tallies + per-step distributions -------
# Shared by the monolithic loop and the continuous-batching server, so
# /metrics answers "is speculation paying off" for either path.
M_SPEC_DRAFTED = REGISTRY.counter(
    "spec_drafted_total",
    "Draft tokens proposed by the n-gram drafter (both decode paths)",
)
M_SPEC_ACCEPTED = REGISTRY.counter(
    "spec_accepted_total",
    "Draft tokens accepted by verification (both decode paths)",
)
M_SPEC_ACC_RATE = REGISTRY.histogram(
    "spec_acceptance_rate",
    "Per-verify-step fraction of drafted tokens accepted (rows with a "
    "non-empty draft only)",
    buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
)
M_SPEC_TOKENS_PER_STEP = REGISTRY.histogram(
    "spec_tokens_per_step",
    "Tokens committed per row per verify step (1 = speculation idle, "
    "K+1 = full acceptance)",
    buckets=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0),
)


def ngram_draft(ids: np.ndarray, k: int, n: int = 3) -> np.ndarray:
    """Propose up to ``k`` continuation tokens for one row by longest-suffix
    match: the largest g <= n such that the row's trailing g-gram occurred
    earlier in ``ids`` wins, and the tokens FOLLOWING its most recent earlier
    occurrence are the draft (prompt-lookup decoding, Saxena 2023). Returns
    an int32 array of length <= k — possibly empty (no suffix recurs, or
    k == 0): speculation quietly idles instead of guessing blind."""
    ids = np.asarray(ids, np.int64).reshape(-1)
    L = ids.shape[0]
    if k <= 0 or L < 2:
        return np.zeros((0,), np.int32)
    for g in range(min(n, L - 1), 0, -1):
        pattern = ids[L - g:]
        # windows over ids[:-1]: every match ends strictly before the last
        # token, so the current suffix can never match itself and the draft
        # is always non-empty
        windows = np.lib.stride_tricks.sliding_window_view(ids[:-1], g)
        hits = np.nonzero((windows == pattern).all(axis=1))[0]
        if hits.size:
            start = int(hits[-1]) + g  # most recent occurrence wins
            return ids[start: start + k].astype(np.int32)
    return np.zeros((0,), np.int32)


class AdaptiveK:
    """Per-row draft-width controller: additive increase on full acceptance,
    halving backoff on a fully rejected draft. The verify program stays
    compiled at the static maximum ``k_max``; this only truncates what the
    drafter proposes, so rows with unpredictable continuations stop paying
    for K-wide verifies they never win."""

    __slots__ = ("k_max", "k")

    def __init__(self, k_max: int):
        self.k_max = int(k_max)
        self.k = int(k_max)

    def update(self, drafted: int, accepted: int) -> None:
        if drafted <= 0:
            return
        if accepted >= drafted:
            self.k = min(self.k_max, self.k + 1)
        elif accepted == 0:
            self.k = max(1, self.k // 2)


def _leading_true_count(flags: jnp.ndarray) -> jnp.ndarray:
    """[B, K] bool → [B] length of each row's leading all-True run."""
    return jnp.sum(jnp.cumprod(flags.astype(jnp.int32), axis=1), axis=1)


def _positionwise_stop(cfg: ModelConfig, toks: jnp.ndarray) -> jnp.ndarray:
    """[B, P] token grid → [B, P] bool EOS mask (ops.sampling.is_stop over
    the flattened grid)."""
    from ..ops.sampling import is_stop

    B, P = toks.shape
    return is_stop(cfg, toks.reshape(-1)).reshape(B, P)


def rejection_commit(
    scaled: jnp.ndarray,       # [B, K+1, V] filtered temperature-scaled logits
    draft: jnp.ndarray,        # [B, K]
    valid_draft: jnp.ndarray,  # [B, K] bool
    u: jnp.ndarray,            # [B, K] accept uniforms
    g: jnp.ndarray,            # [B, K+1, V] gumbel noise for resample/bonus
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Leviathan-style rejection acceptance against a point-mass (n-gram)
    proposal, shared by the monolith verify and ``serve_verify``: accept
    draft d_i with probability p_i(d_i) under the filtered target; the
    first non-accepted position resamples from the target with d masked out
    (the exact rejection residual for a deterministic proposal) — so the
    committed stream is distributed exactly as sequential sampling.
    Returns ``(a, commit)``: accepted-draft count and the [B, K+1] commit
    candidates (positions < a are the accepted drafts, position a the
    resample/bonus). Pure replicated math — safe inside shard_map bodies."""
    B, K = draft.shape
    V = scaled.shape[-1]
    iota = jnp.arange(K + 1, dtype=jnp.int32)
    probs = jax.nn.softmax(scaled, axis=-1)
    p_draft = jnp.take_along_axis(
        probs[:, :K], draft[..., None], axis=-1
    )[..., 0]
    acc = valid_draft & (u < p_draft)
    a = _leading_true_count(acc)
    rejected = jnp.concatenate(
        [valid_draft & ~acc, jnp.zeros((B, 1), bool)], axis=1
    )
    draft_pad = jnp.concatenate(
        [draft, jnp.zeros((B, 1), jnp.int32)], axis=1
    )
    col = jnp.arange(V, dtype=jnp.int32)
    masked = jnp.where(
        rejected[..., None] & (col[None, None, :] == draft_pad[..., None]),
        -jnp.inf,
        scaled,
    )
    resample = jnp.argmax(masked + g, axis=-1).astype(jnp.int32)
    commit = jnp.where(iota[None, :] < a[:, None], draft_pad, resample)
    return a, commit


def cap_commits(
    cfg: ModelConfig,
    commit: jnp.ndarray,      # [B, K+1] commit candidates
    a: jnp.ndarray,           # [B] accepted-draft count (run length - 1)
    budget_rem: jnp.ndarray,  # [B] tokens the row may still commit
    done: jnp.ndarray,        # [B] bool
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Cut each row's commit run at the first EOS inside it, its remaining
    budget, and done-ness — THE one definition of the per-step commit both
    decode paths share. Returns ``(c [B], log [B,K+1], eos_hit [B])``;
    ``log`` is the -1-padded host-facing commit log."""
    K1 = commit.shape[1]
    iota = jnp.arange(K1, dtype=jnp.int32)
    within = iota[None, :] < (a + 1)[:, None]
    eos = _positionwise_stop(cfg, commit) & within
    eos_before = jnp.cumsum(eos.astype(jnp.int32), axis=1) - eos.astype(
        jnp.int32
    )
    keep = (
        within
        & (eos_before == 0)
        & (iota[None, :] < budget_rem[:, None])
        & ~done[:, None]
    )
    c = jnp.sum(keep.astype(jnp.int32), axis=1)
    log = jnp.where(keep, commit, -1)
    return c, log, jnp.any(keep & eos, axis=1)


def count_accepted(committed: list, draft, drafted: int) -> int:
    """Accepted drafts in one row's fetched commit run: the leading match
    against what was drafted. NOT ``len(committed) - 1`` — a run cut by an
    accepted-EOS draft or the budget has no trailing bonus token, and that
    form undercounts acceptance on every request's final step."""
    n = 0
    for i in range(min(len(committed), drafted)):
        if committed[i] != int(draft[i]):
            break
        n += 1
    return n


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "K", "temperature", "top_k", "top_p", "fwd"),
    donate_argnums=(1,),
)
def _spec_verify_step(
    cfg: ModelConfig,
    state: dict,  # the generate.py decode-state dict (out/cache/tok/pos/...)
    params,
    budget: jnp.ndarray,     # [B] total-length budget (prompt_len + max_new)
    draft: jnp.ndarray,      # [B, K] right-padded draft ids
    draft_len: jnp.ndarray,  # [B] valid draft tokens per row
    K: int,
    temperature: float,
    top_k: int,
    top_p: float,
    fwd,
):
    """ONE forward over the K+1 draft positions per row; commit the accepted
    run plus the model's own next token. Returns ``(state, log)`` with
    ``log`` ``[B, K+1]`` int32 — committed tokens, -1 padded — the host's
    only per-step read (it feeds the next draft).

    Greedy acceptance is exact: committed tokens are the model's argmax
    choices whatever the draft said, so the output is token-identical to the
    sequential loop — drafts only decide HOW MANY of those choices commit
    per weight pass. Sampled acceptance is Leviathan-style rejection against
    a deterministic (point-mass) draft distribution: accept draft d with
    probability p(d) under the temperature/top-k/top-p-filtered target, else
    resample from the target with d masked out — the committed sequence is
    distributed exactly as sequential sampling."""
    from ..ops.sampling import top_p_threshold

    cache = state["cache"]
    B = draft.shape[0]
    C_total = cache.capacity
    scratch = C_total - (K + 1)  # static: scratch region at the cache top
    pos0 = state["pos"]          # [B] position of the pending token
    done0 = state["done"]
    lengths0 = state["lengths"]

    # ---- one forward over [tok, d_1..d_K] at positions pos0..pos0+K ----
    toks_in = jnp.concatenate([state["tok"][:, None], draft], axis=1)
    iota = jnp.arange(K + 1, dtype=jnp.int32)
    positions = jnp.where(
        done0[:, None], POS_SENTINEL, pos0[:, None] + iota[None, :]
    )
    cache = cache._replace(length=jnp.asarray(scratch, jnp.int32))
    logits, cache = fwd(cfg, params, toks_in, cache, positions)
    logits = logits.astype(jnp.float32)  # [B, K+1, V]

    # ---- acceptance ----
    valid_draft = iota[None, :K] < draft_len[:, None]  # [B, K]
    if temperature <= 0.0:
        choices = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
        match = (choices[:, :K] == draft) & valid_draft
        a = _leading_true_count(match)  # [B] accepted drafts
        commit = choices  # commit[i] == draft[i] for i < a; i == a is bonus
        key = state["key"]
    else:
        V = logits.shape[-1]
        scaled = logits / temperature
        if top_k > 0:
            kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        if top_p < 1.0:
            flat = scaled.reshape(B * (K + 1), V)
            thresh = top_p_threshold(flat, top_p).reshape(B, K + 1, 1)
            scaled = jnp.where(scaled < thresh, -jnp.inf, scaled)
        key, sub = jax.random.split(state["key"])
        k_u, k_g = jax.random.split(sub)
        u = jax.random.uniform(k_u, (B, K))  # accept draws per draft pos
        g = jax.random.gumbel(k_g, (B, K + 1, V), jnp.float32)
        a, commit = rejection_commit(scaled, draft, valid_draft, u, g)

    # ---- cap the commit run: EOS inside the run, per-row budget, done ----
    c, log, eos_hit = cap_commits(cfg, commit, a, budget - lengths0, done0)
    lengths = lengths0 + c
    done = done0 | eos_hit | ((c > 0) & (lengths >= budget))
    tok = jnp.where(
        c > 0,
        jnp.take_along_axis(
            commit, jnp.clip(c - 1, 0, K)[:, None], axis=1
        )[:, 0],
        state["tok"],
    )
    pos = pos0 + c

    # ---- out buffer: committed run lands at columns pos0+1 .. pos0+c ----
    total = state["out"].shape[1]
    colidx = jnp.arange(total, dtype=jnp.int32)[None, :]
    rel = colidx - (pos0[:, None] + 1)
    in_run = (rel >= 0) & (rel < c[:, None])
    vals = jnp.take_along_axis(commit, jnp.clip(rel, 0, K), axis=1)
    out = jnp.where(in_run, vals, state["out"])

    # ---- KV rollback: compact the accepted prefix out of scratch ----
    # The forward wrote K+1 entries at [scratch, scratch+K]; entries
    # 0..c-1 (the pending token's KV + the accepted drafts') move to the
    # canonical position-aligned slots [pos0, pos0+c); the rest are
    # discarded by the position rewind (scratch reset + sentinel kpos).
    chunk_k = jax.lax.dynamic_slice_in_dim(cache.k, scratch, K + 1, axis=2)
    chunk_v = jax.lax.dynamic_slice_in_dim(cache.v, scratch, K + 1, axis=2)

    def compact(row_kv, row_chunk, start):
        # row_kv [L, C, Nkv, D], row_chunk [L, K+1, Nkv, D]
        return jax.lax.dynamic_update_slice(
            row_kv, row_chunk, (0, start, 0, 0)
        )

    # clamp-free by construction: pos0 + K + 1 <= capacity + K + 1 = C_total
    k_new = jax.vmap(compact, in_axes=(1, 1, 0), out_axes=1)(
        cache.k, chunk_k, pos0
    )
    v_new = jax.vmap(compact, in_axes=(1, 1, 0), out_axes=1)(
        cache.v, chunk_v, pos0
    )
    # canonical key positions: real for the kept entries, sentinel beyond
    row_pos = jnp.where(
        iota[None, :] < c[:, None], pos0[:, None] + iota[None, :],
        POS_SENTINEL,
    ).astype(jnp.int32)
    pos_arr = jax.vmap(
        lambda p_row, vals_row, start: jax.lax.dynamic_update_slice(
            p_row, vals_row, (start,)
        )
    )(cache.pos, row_pos, pos0)
    # scratch rewind: those K+1 slots never survive a step
    pos_arr = jax.lax.dynamic_update_slice(
        pos_arr,
        jnp.full((B, K + 1), POS_SENTINEL, jnp.int32),
        (0, scratch),
    )
    cache = cache._replace(
        k=k_new, v=v_new, pos=pos_arr,
        length=jnp.asarray(scratch, jnp.int32),
    )

    new_state = dict(
        out=out, cache=cache, tok=tok, pos=pos, done=done,
        n=state["n"] + jnp.max(c), key=key, lengths=lengths,
    )
    return new_state, log


def spec_generate(
    cfg: ModelConfig,
    params,
    prompt_ids,
    max_new_tokens: int = 128,
    *,
    speculate: int = 4,
    spec_ngram: int = 3,
    spec_burst: int = 4,
    prompt_len: Optional[np.ndarray] = None,
    capacity: Optional[int] = None,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
    seed: int = 0,
    cache_dtype=jnp.bfloat16,
):
    """Speculative single-host generation — ``generate(..., speculate=K)``.

    The drafter is host-side (it needs the row's materialized ids), so the
    loop is host-driven: draft per row → one jitted verify forward over the
    K+1 positions → the [B, K+1] commit log feeds the next draft. Greedy
    output is token-identical to ``generate``; sampled output follows the
    same target distribution.

    ``spec_burst`` dispatches that many verify steps per host round trip,
    drafting step t+1 OPTIMISTICALLY from step t's assumed full acceptance
    (draft + the n-gram continuation as the assumed bonus token), and
    fetches the burst's logs in ONE batched device read. Safe because
    drafts are hints, never inputs the device trusts: the verify reads its
    pending token and lengths from device state, so a wrong guess commits
    exactly one correct token (a plain decode step's work at a plain decode
    step's weight-pass cost) instead of corrupting anything. The burst
    amortizes one host↔device round trip over up to ``burst × (K+1)``
    tokens — it pays wherever that round trip is long next to a step.
    """
    from .generate import (
        GenerateResult, _fetch_result, _prefill_jit, _validate_totals,
        forward_fn_for,
    )
    from ..ops.sampling import validate_top_p

    K = int(speculate)
    if K < 1:
        raise ValueError(f"speculate must be >= 1 on the spec path, got {K}")
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    if prompt_ids.ndim == 1:
        prompt_ids = prompt_ids[None]
    B, S = prompt_ids.shape
    if prompt_len is None:
        prompt_len = jnp.full((B,), S, jnp.int32)
    else:
        prompt_len = jnp.asarray(prompt_len, jnp.int32)

    total = S + max_new_tokens
    capacity = capacity or total
    _validate_totals(cfg, S, max_new_tokens, capacity)

    fwd = forward_fn_for(cfg)
    temperature, top_k = float(temperature), int(top_k)
    top_p = validate_top_p(top_p)

    # K+1 scratch slots over the requested capacity — the verify forward
    # lands there, the accepted prefix is compacted out (see module docs)
    cache = init_cache(cfg, B, capacity + K + 1, dtype=cache_dtype)
    state = _prefill_jit(
        cfg, params, prompt_ids, prompt_len, cache, jax.random.key(seed),
        max_new_tokens, capacity + K + 1, temperature, top_k, top_p, fwd,
    )
    budget = prompt_len + max_new_tokens

    # host mirrors of each row's ids (prompt + commits) — the drafter input
    plen_h = np.asarray(prompt_len)
    prompt_h = np.asarray(prompt_ids)
    first = np.asarray(state["tok"])
    rows = [list(prompt_h[b, : plen_h[b]]) + [int(first[b])] for b in range(B)]
    eos = frozenset(int(t) for t in cfg.eos_token_ids)
    done_h = [
        int(first[b]) in eos or max_new_tokens <= 1 for b in range(B)
    ]
    gen_count = [1] * B
    kctl = [AdaptiveK(K) for _ in range(B)]
    burst = max(int(spec_burst), 1)

    while not all(done_h):
        # one burst: dispatch up to `burst` verifies back to back, drafting
        # each from the previous step's ASSUMED outcome (full acceptance +
        # the n-gram continuation as the bonus guess), then fetch all logs
        # in one batched read and reconcile against what really committed
        assumed = [list(r) for r in rows]
        # assumed-done cuts the burst early at request tails: once every
        # live row's assumed commits reach its budget (or an assumed token
        # is EOS), further dispatches could only verify done rows — a full
        # weight pass each for nothing. Unknowable commits (empty drafts)
        # leave a row not-assumed-done; the burst cap bounds those.
        assumed_done = list(done_h)
        assumed_gen = list(gen_count)
        dispatched: list[tuple] = []  # (draft, draft_len) per step
        logs = []
        for _ in range(burst):
            if all(assumed_done):
                break
            draft = np.zeros((B, K), np.int32)
            draft_len = np.zeros((B,), np.int32)
            for b in range(B):
                if done_h[b]:
                    continue
                d = ngram_draft(
                    np.asarray(assumed[b]), kctl[b].k + 1, spec_ngram
                )
                draft[b, : min(d.shape[0], K)] = d[:K]
                draft_len[b] = min(d.shape[0], kctl[b].k)
                # optimistic: assume the K drafts accept and the (K+1)-th
                # lookup token is the bonus the model samples
                assumed[b].extend(int(t) for t in d)
                assumed_gen[b] += d.shape[0]
                if assumed_gen[b] >= max_new_tokens or any(
                    int(t) in eos for t in d
                ):
                    assumed_done[b] = True
            state, log = _spec_verify_step(
                cfg, state, params, budget, jnp.asarray(draft),
                jnp.asarray(draft_len), K, temperature, top_k, top_p, fwd,
            )
            logs.append(log)
            dispatched.append((draft, draft_len))
        for log, (draft, draft_len) in zip(jax.device_get(logs), dispatched):
            for b in range(B):
                if done_h[b]:
                    continue
                committed = [int(t) for t in log[b] if t >= 0]
                rows[b].extend(committed)
                gen_count[b] += len(committed)
                drafted = int(draft_len[b])
                accepted = count_accepted(committed, draft[b], drafted)
                kctl[b].update(drafted, accepted)
                if drafted:
                    M_SPEC_DRAFTED.inc(drafted)
                    M_SPEC_ACCEPTED.inc(accepted)
                    M_SPEC_ACC_RATE.observe(accepted / drafted)
                if committed:
                    M_SPEC_TOKENS_PER_STEP.observe(len(committed))
                if (
                    (committed and committed[-1] in eos)
                    or gen_count[b] >= max_new_tokens
                ):
                    done_h[b] = True

    res = _fetch_result(state)
    # hand back a cache of the REQUESTED capacity (scratch stripped), so the
    # result composes with decode_from_cache like the non-spec path's
    cache = res.cache
    from .generate import _slice_cache

    return GenerateResult(res.tokens, res.lengths, _slice_cache(cache, capacity))
