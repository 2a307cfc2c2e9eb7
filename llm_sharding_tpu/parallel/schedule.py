"""Interleaved microbatched pipeline decode — filling the pipeline the
reference leaves idle.

The reference keeps exactly one token in flight: while a token is on stage s,
every other stage idles (``/root/reference/utils/node_worker.py:493-547``;
SURVEY.md §3.2 "no overlap of communication and compute anywhere"). That caps
chain throughput at (1 token) / (S stage-times). This scheduler runs
``num_stages`` independent request *slots* in flight, round-robin: at every
microstep, each device computes a *different* slot's block, then the ring
permutes — so every stage does useful work every microstep and aggregate
throughput approaches one token per stage-time, an S× improvement that is the
mechanism behind the ≥100 tok/s v5e-8 headline target (BASELINE.md;
SURVEY.md §7 "hard parts": microbatched decode). Each slot additionally
carries ``batch_per_slot`` independent requests decoded as one batched block
— per-microstep work becomes a [Bs,·] matmul instead of a matvec, multiplying
aggregate throughput again at near-constant microstep latency.

Schedule (S = num_stages, slot r, microstep m):
- device d serves slot r = (m − d) mod S;
- the completed block surfaces on device S−1; the next token for each of its
  rows is assembled via the vocab-sharded head (``parallel/head.py`` — each
  stage projects only its V/S logit slice), so every stage learns the token
  and bookkeeping (EOS/done/lengths/output) is fully replicated — no
  stop-broadcast collective;
- the new token is re-embedded (vocab-parallel psum) and device S−1 sends it
  to stage 0 through the same ring permute that carries hidden blocks — the
  reference's token-return hop (``node_worker.py:515-525``) fused into the
  steady-state schedule;
- prefill runs all S·Bs requests as one batched sequential chain traversal
  (caches fill in a single trip), then the decode wavefront ramps in over the
  first S microsteps (validity-masked), runs steady, and drains.

Per-device KV caches hold all S·Bs rows ([Lp, S·Bs, C, ...]); each microstep
touches only the served slot's rows via dynamic slicing.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.cache import KVCache, POS_SENTINEL
from ..models.config import ModelConfig
from ..ops.sampling import is_stop as _is_stop, validate_top_p
from .head import (
    head_specs, key_chain_split, local_view, psum_from, seed_chain_init,
    sp_embed, sp_next_token, sp_sample_rows,
)
from .mesh import PIPE_AXIS
from .pipeline import (
    check_stage_shapes,
    ensure_sharded_head,
    model_fns,
    ring_chain,
    validate_request,
)
from jax import shard_map


class InterleavedResult(NamedTuple):
    tokens: np.ndarray  # [R, S + max_new_tokens]
    lengths: np.ndarray  # [R]


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "mesh", "num_stages", "max_new_tokens", "capacity",
        "cache_dtype", "sampling", "filtering",
    ),
)
def _interleaved_jit(
    cfg: ModelConfig,
    mesh: Mesh,
    stage_layers: Any,
    layer_masks: jnp.ndarray,
    head_params: Any,
    prompts: jnp.ndarray,  # [M, S] right-padded, M == num_stages * Bs rows
    prompt_len: jnp.ndarray,  # [M]
    slot_valid: jnp.ndarray,  # [M] bool — False for padding rows
    temperature: jnp.ndarray,  # [M] f32; <= 0 → greedy for that row
    seeds: jnp.ndarray,  # [M] int32 per-row sampling seeds
    topk: jnp.ndarray,  # [M] int32; 0 → no top-k for that row
    topp: jnp.ndarray,  # [M] f32; 1.0 → no top-p for that row
    num_stages: int,
    max_new_tokens: int,
    capacity: int,
    cache_dtype,
    sampling: bool,
    filtering: bool,
):
    fns = model_fns(cfg)
    M, S = prompts.shape
    Bs = M // num_stages  # rows per slot
    total = S + max_new_tokens
    Lp = layer_masks.shape[1] * cfg.arena_slots  # the cache's layer slots
    ring = [(i, (i + 1) % num_stages) for i in range(num_stages)]
    last = num_stages - 1

    def body(stage_layers, layer_mask, head_params, prompts, prompt_len,
             slot_valid, temperature, seeds, topk, topp):
        layers = jax.tree.map(lambda a: a[0], stage_layers)
        lmask = layer_mask[0]
        hd = local_view(head_params)
        sidx = jax.lax.axis_index(PIPE_AXIS)

        # ---- batched prefill: all M rows in one chain traversal ----
        cache = KVCache(
            k=jnp.zeros((Lp, M, capacity, cfg.cache_heads, cfg.cache_k_dim), cache_dtype),
            v=jnp.zeros((Lp, M, capacity, cfg.cache_heads, cfg.cache_v_dim), cache_dtype),
            pos=jnp.full((M, capacity), POS_SENTINEL, jnp.int32),
            length=jnp.zeros((), jnp.int32),
        )
        idx = jnp.arange(S, dtype=jnp.int32)
        positions = jnp.where(
            idx[None, :] < prompt_len[:, None], idx[None, :], POS_SENTINEL
        )
        h = sp_embed(cfg, hd, prompts, positions)
        h, cache, _ = ring_chain(
            fns, cfg, layers, lmask, sidx, ring, num_stages, h, cache, positions
        )
        # full-depth block landed on stage 0; assemble the first token for
        # every row via the sharded head (replicated result).
        h_last = jnp.take_along_axis(
            h, (prompt_len - 1)[:, None, None], axis=1
        )[:, 0]
        h_last = psum_from(h_last, 0)
        if sampling:
            # per-row key chains mirror the monolith's (key(seed) → split →
            # sample) — the SAME shared helpers as the serve path
            row_keys, subs = seed_chain_init(seeds)  # [M, 2] each
            tok0 = sp_sample_rows(
                cfg, hd, h_last, subs, temperature, topk, topp, num_stages,
                filtering=filtering,
            )
        else:
            row_keys = jnp.zeros((M, 2), jnp.uint32)
            tok0 = sp_next_token(cfg, hd, h_last)  # [M], replicated

        out = jnp.zeros((M, total), jnp.int32)
        out = jax.lax.dynamic_update_slice(out, prompts, (0, 0))
        out = out.at[jnp.arange(M), prompt_len].set(
            jnp.where(slot_valid, tok0, 0)
        )
        done0 = (_is_stop(cfg, tok0) | ~slot_valid)
        lengths = jnp.where(slot_valid, prompt_len + 1, prompt_len)

        # Ramp-in injections: stage 0's first serve of slot r feeds tok0's
        # embedding — precomputed here (replicated) so the steady-state loop
        # carries no extra embed collective for it.
        inject_all = sp_embed(cfg, hd, tok0[:, None], prompt_len[:, None])

        # ---- interleaved decode ----
        # Per-device per-row position of the row's current token.
        pos_slots = prompt_len  # [M]
        # per-slot cache write offset (shared by the slot's rows; prefill
        # wrote [0, S))
        write_off = jnp.full((num_stages,), S, jnp.int32)

        # tok0 (from prefill) is generated token #1; each row needs
        # max_new_tokens - 1 more completions, one per ring cycle. Slot r's
        # last completion happens at microstep r + (S-1) + (max_new-2)·S, so
        # the drain needs S·max_new − 1 microsteps for the last slot.
        total_micro = num_stages * max_new_tokens - 1

        state = dict(
            h=jnp.zeros((Bs, 1, cfg.hidden_size), h.dtype),
            cache=cache,
            out=out,
            done=done0,
            lengths=lengths,
            pos_slots=pos_slots,
            write_off=write_off,
            rng=row_keys,
            m=jnp.zeros((), jnp.int32),
        )

        def cond(s):
            return (s["m"] < total_micro) & ~jnp.all(s["done"])

        def micro(s):
            m = s["m"]
            r = jnp.mod(m - sidx, num_stages)  # slot this device serves
            row0 = r * Bs
            ramp_in = m < num_stages  # wavefront not yet arrived everywhere
            valid = m >= sidx  # device has real data from m == sidx onward

            pos_rows = jax.lax.dynamic_slice_in_dim(s["pos_slots"], row0, Bs)
            off_r = jax.lax.dynamic_index_in_dim(s["write_off"], r, keepdims=False)

            # stage 0 self-injects the slot's first decode embedding during
            # ramp-in (precomputed above)
            inject = jax.lax.dynamic_slice_in_dim(inject_all, row0, Bs, axis=0)
            h_in = jnp.where((sidx == 0) & ramp_in, inject, s["h"])

            # slice this slot's cache rows
            cache_r = KVCache(
                k=jax.lax.dynamic_slice_in_dim(s["cache"].k, row0, Bs, axis=1),
                v=jax.lax.dynamic_slice_in_dim(s["cache"].v, row0, Bs, axis=1),
                pos=jax.lax.dynamic_slice_in_dim(s["cache"].pos, row0, Bs, axis=0),
                length=off_r,
            )
            h_new, cache_r_new, _ = fns.stage(
                cfg, layers, h_in, cache_r, pos_rows[:, None], lmask
            )
            # Commit the slot cache UNCONDITIONALLY — a ramp-in garbage write
            # lands at the same offset the first valid serve will overwrite
            # (write_off only advances on valid serves), and nothing reads the
            # slot in between. This avoids a full-cache select per microstep.
            def upd(big, small, axis):
                return jax.lax.dynamic_update_slice_in_dim(big, small, row0, axis=axis)

            cache = KVCache(
                k=upd(s["cache"].k, cache_r_new.k, 1),
                v=upd(s["cache"].v, cache_r_new.v, 1),
                pos=upd(s["cache"].pos, cache_r_new.pos, 0),
                length=s["cache"].length,
            )
            write_off = jnp.where(
                valid, s["write_off"].at[r].add(1), s["write_off"]
            )

            # ---- token completion for the slot the LAST stage just served.
            # The completed block is broadcast; the vocab-sharded head
            # assembles the next token on every stage, so all bookkeeping
            # below is replicated (identical on every device).
            r_done = jnp.mod(m - last, num_stages)
            rowd = r_done * Bs
            row_ids = rowd + jnp.arange(Bs, dtype=jnp.int32)
            valid_done = m >= last

            h_done = psum_from(h_new[:, 0], last)  # [Bs, H]
            done_rows = jax.lax.dynamic_slice_in_dim(s["done"], rowd, Bs)
            if sampling:
                rng_rows = jax.lax.dynamic_slice_in_dim(
                    s["rng"], rowd, Bs, axis=0
                )
                new_keys, subs = key_chain_split(rng_rows)
                temp_rows = jax.lax.dynamic_slice_in_dim(temperature, rowd, Bs)
                topk_rows = jax.lax.dynamic_slice_in_dim(topk, rowd, Bs)
                topp_rows = jax.lax.dynamic_slice_in_dim(topp, rowd, Bs)
                nxt = sp_sample_rows(
                    cfg, hd, h_done, subs, temp_rows, topk_rows, topp_rows,
                    num_stages, filtering=filtering,
                )
            else:
                nxt = sp_next_token(cfg, hd, h_done)  # [Bs], replicated
            nxt = jnp.where(done_rows, 0, nxt)

            len_rows = jax.lax.dynamic_slice_in_dim(s["lengths"], rowd, Bs)
            plen_rows = jax.lax.dynamic_slice_in_dim(prompt_len, rowd, Bs)
            under_budget = (len_rows - plen_rows) < max_new_tokens
            commit = valid_done & ~done_rows & under_budget  # [Bs]
            wpos = len_rows  # next token's sequence index per row
            cur = s["out"][row_ids, wpos]
            out = s["out"].at[row_ids, wpos].set(jnp.where(commit, nxt, cur))
            lengths = s["lengths"].at[row_ids].add(commit.astype(jnp.int32))
            done = s["done"].at[row_ids].set(
                done_rows | (commit & _is_stop(cfg, nxt))
            )
            if sampling:
                rng = s["rng"].at[row_ids].set(
                    jnp.where(commit[:, None], new_keys, rng_rows)
                )
            else:
                rng = s["rng"]

            # re-embed the fresh tokens (vocab-parallel, replicated result);
            # only the last stage sends them around the ring
            h_embed = sp_embed(cfg, hd, nxt[:, None], wpos[:, None])
            h_send = jnp.where(sidx == last, h_embed, h_new)
            h_out = jax.lax.ppermute(h_send, PIPE_AXIS, ring)

            # this device will see slot r again in S microsteps, one token deeper
            served_rows = row0 + jnp.arange(Bs, dtype=jnp.int32)
            pos_slots = jnp.where(
                valid, s["pos_slots"].at[served_rows].add(1), s["pos_slots"]
            )

            return dict(
                h=h_out,
                cache=cache,
                out=out,
                done=done,
                lengths=lengths,
                pos_slots=pos_slots,
                write_off=write_off,
                rng=rng,
                m=m + 1,
            )

        state = jax.lax.while_loop(cond, micro, state)
        return state["out"], state["lengths"]

    out, lengths = shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(PIPE_AXIS),
            P(PIPE_AXIS),
            head_specs(head_params),
            P(),
            P(),
            P(),
            P(),
            P(),
            P(),
            P(),
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )(stage_layers, layer_masks, head_params, prompts, prompt_len, slot_valid,
      temperature, seeds, topk, topp)
    return out, lengths


def interleaved_generate(
    cfg: ModelConfig,
    mesh: Mesh,
    stage_layers: Any,
    layer_masks: jnp.ndarray,
    head_params: Any,
    prompts,  # [R, S] with R <= num_stages * batch_per_slot
    max_new_tokens: int = 128,
    *,
    prompt_len=None,
    capacity: Optional[int] = None,
    batch_per_slot: Optional[int] = None,
    cache_dtype=jnp.bfloat16,
    temperature=0.0,  # scalar or per-request [R]; <= 0 → greedy
    top_k=0,  # scalar or per-request [R]; 0 → off
    top_p=1.0,  # scalar or per-request [R]; 1.0 → off
    seeds=None,  # per-request sampling seeds [R] (default zeros)
) -> InterleavedResult:
    """Generate for up to ``num_stages * batch_per_slot`` requests
    concurrently, pipeline full. ``batch_per_slot`` defaults to the smallest
    value that fits all R requests. Sampling is per-row: request r with
    ``temperature[r] > 0`` draws the B=1 monolithic ``generate(...,
    temperature, top_k, top_p, seed=seeds[r])`` tokens exactly (the same
    key-chain contract as the serve path). ``top_k``/``top_p`` are dynamic
    per-row values — mixed filter settings share one compiled program."""
    if cfg.passes > 1:
        raise NotImplementedError(
            f"the interleaved schedule over a looped stack ({cfg.passes} "
            "passes) is not implemented: its slots advance one stage a "
            "microstep, and a looped token would lap the ring once a pass"
        )
    prompts = jnp.asarray(prompts, jnp.int32)
    if prompts.ndim == 1:
        prompts = prompts[None]
    R, S = prompts.shape
    num_stages = mesh.shape[PIPE_AXIS]
    if batch_per_slot is None:
        batch_per_slot = max(1, -(-R // num_stages))
    M = num_stages * batch_per_slot
    if R > M:
        raise ValueError(
            f"{R} requests > {M} rows (num_stages={num_stages} × "
            f"batch_per_slot={batch_per_slot}); batch into groups of {M}"
        )
    if prompt_len is None:
        prompt_len = jnp.full((R,), S, jnp.int32)
    else:
        prompt_len = jnp.asarray(prompt_len, jnp.int32)

    capacity = validate_request(cfg, S, max_new_tokens, capacity)
    check_stage_shapes(layer_masks, num_stages)
    head_params = ensure_sharded_head(cfg, head_params, num_stages)

    slot_valid = np.zeros((M,), bool)
    slot_valid[:R] = True
    if R < M:  # pad rows with dummy single-token prompts
        pad = np.zeros((M - R, S), np.int32)
        prompts = jnp.concatenate([prompts, jnp.asarray(pad)], axis=0)
        prompt_len = jnp.concatenate(
            [prompt_len, jnp.ones((M - R,), jnp.int32)], axis=0
        )

    temps = np.zeros((M,), np.float32)
    temps[:R] = np.broadcast_to(np.asarray(temperature, np.float32), (R,))
    seed_arr = np.zeros((M,), np.int32)
    if seeds is not None:
        seed_arr[:R] = np.broadcast_to(np.asarray(seeds, np.int32), (R,))
    topk_arr = np.zeros((M,), np.int32)
    topk_arr[:R] = np.broadcast_to(np.asarray(top_k, np.int32), (R,))
    topp_arr = np.ones((M,), np.float32)
    topp_arr[:R] = np.broadcast_to(
        np.asarray([validate_top_p(p) for p in np.atleast_1d(top_p)],
                   np.float32),
        (R,),
    )
    # top_k alone cannot change an argmax, so all-greedy batches compile the
    # plain greedy program regardless of top_k; likewise the filter
    # machinery (vocab gather + sort) compiles in only when some row uses it
    sampling = bool(np.any(temps > 0))
    filtering = sampling and bool(
        np.any((topk_arr > 0) | (topp_arr < 1.0))
    )

    out, lengths = _interleaved_jit(
        cfg,
        mesh,
        stage_layers,
        layer_masks,
        head_params,
        prompts,
        prompt_len,
        jnp.asarray(slot_valid),
        jnp.asarray(temps),
        jnp.asarray(seed_arr),
        jnp.asarray(topk_arr),
        jnp.asarray(topp_arr),
        num_stages,
        max_new_tokens,
        capacity,
        cache_dtype,
        sampling,
        filtering,
    )
    return InterleavedResult(np.asarray(out)[:R], np.asarray(lengths)[:R])
