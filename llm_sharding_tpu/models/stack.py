"""Shared layer-stack scan machinery (every family's; ``models/family.py``).

One implementation of: record this step's key positions, ``lax.scan`` over
layer-stacked params + per-layer cache rows, commit hidden/cache updates only
for valid (non-padding) layers. Architecture modules supply only the per-layer
function. Centralizing this keeps the ragged-stage and cache-write semantics
identical across model families (they power the pipeline's SPMD padding —
SURVEY.md §7 "uneven layer splits"). A model whose layers are of several kinds
runs them as RUNS of one kind (``Run``, ``stage_runs``, ``scan_run``,
``place_stats``); a looped stack's passes are ``run_passes``.
"""

from __future__ import annotations

import itertools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .cache import KVCache

# apply_layer(p, h, k_row, v_row, kv_pos, length) -> (h, k_row, v_row, stats)
# ``stats`` is what the layer counted (a model with experts: ``MoeStats``) or
# None; both scans return it stacked over layers as their LAST result. A None
# is an empty pytree: it adds no operand or output to a dense model's program.
ApplyLayerFn = Callable

#: Leaves a scan hands ``apply_layer`` WHOLE (layer-stacked, beside the
#: layer index under ``"layer"``) instead of slicing a layer out per
#: iteration: the experts of a sparse MLP (``ops/moe.py``). A step reads a
#: few experts of a layer through indices chosen at run time; slicing the
#: layer first would copy all of them (0.4 GB a layer at OLMoE's widths).
WHOLE_KEYS = ("we_gate", "we_up", "we_down")

#: ... and, of a Mamba-1 stack ALONE (one that has ``w_x``: Mamba-2's stack
#: has leaves of some of these names and stays scanned), what a decode
#: step's one kernel a mixer layer reads through the layer index
#: (``ops/ssm.mixer_step_rows``): a per-layer slice handed to a Pallas call
#: would be copied first, 2 MB of ``w_x`` a layer.
MAMBA1_WHOLE_KEYS = (
    "conv_w", "conv_b", "w_x", "dt_norm", "b_norm", "c_norm", "w_dt",
    "dt_bias", "D",
)


def split_whole(layers):
    """``(scanned, whole)``: ``whole`` is None for a model with no such leaf,
    and ``layers`` then comes back as it is."""
    if not isinstance(layers, dict):
        return layers, None
    keys = WHOLE_KEYS + (MAMBA1_WHOLE_KEYS if "w_x" in layers else ())
    if not any(k in layers for k in keys):
        return layers, None
    whole = {k: layers[k] for k in keys if k in layers}
    return {k: v for k, v in layers.items() if k not in whole}, whole


def join_whole(p, whole, l):
    return p if whole is None else {**p, **whole, "layer": l}


def kind_spans(layers: dict, kinds: tuple):
    """``[(kind, first, count)]`` of a per-kind tree ``{kind: {leaf: [L_kind,
    ...]}}`` (a model whose layers are of several kinds, ``cfg.layer_kinds``):
    a stage's layer SLOTS are its kinds' stacks laid end to end in the order
    the kinds first appear in the model — slot ``first + i`` of the stage's
    layer mask, cache and arena belongs to layer ``i`` of ``kind``'s stack.
    One stage of a model with leading dense layers holds them in model
    order; a padded stack's tail slots are masked like any padding layer."""
    spans, first = [], 0
    for kind in dict.fromkeys(kinds):
        count = jax.tree.leaves(layers[kind])[0].shape[0]
        spans.append((kind, first, count))
        first += count
    return spans


class Run(NamedTuple):
    """Consecutive layers of one kind, as a stage runs them. A layer's index
    in ITS kind's state (an arena, a recurrent state) is its index in its
    kind's stack, unless the kinds share arenas by their attention (MiMo:
    ``attn``, ``arena_first``)."""

    kind: str
    stack_first: int  # the run's first layer in its kind's stack
    count: int
    slot_first: int  # ... in the stage's layer slots (mask, stats, dense cache)
    attn: str = ""  # the kind's attention ("full" | "swa"), where kinds have one
    arena_first: int = 0  # ... in its attention's arena


def stage_runs(cfg, layers: dict, attn_of: Optional[Callable] = None) -> list:
    """The stage's layers as runs of one kind in MODEL order. The stage
    holds ``sum of its stacks`` layers and every stage the same sequence of
    kinds, so the sequence is the model's first that many
    (``parallel/placement`` refuses a ring that would not). ``attn_of(kind)``
    names the attention of a kind where the arenas are per attention."""
    spans = {k: (first, n) for k, first, n in kind_spans(layers, cfg.layer_kinds)}
    total = sum(n for _, n in spans.values())
    seq = cfg.layer_kinds[:total]
    for kind, (_, n) in spans.items():
        if seq.count(kind) != n:
            raise NotImplementedError(
                f"{cfg.model_type}: a stage holds {n} layers of kind {kind!r} "
                f"where the model's first {total} layers have "
                f"{seq.count(kind)}: "
                "every stage must hold the same sequence of layer kinds "
                "(whole periods of the pattern, none padded)"
            )
    runs, in_stack, in_arena = [], {}, {}
    for kind, group in itertools.groupby(seq):
        n = len(list(group))
        s0 = in_stack.get(kind, 0)
        if attn_of is None:
            runs.append(Run(kind, s0, n, spans[kind][0] + s0))
        else:
            attn = attn_of(kind)
            a0 = in_arena.get(attn, 0)
            runs.append(Run(kind, s0, n, spans[kind][0] + s0, attn, a0))
            in_arena[attn] = a0 + n
        in_stack[kind] = s0 + n
    return runs


def zero_recurrent(cfg, layers: int, rows: int) -> dict:
    """An empty recurrent state of ``layers`` mixers and ``rows`` rows, by
    name: ``{name: [layers, rows, *cfg.recurrent_shapes[name]]}`` float32 —
    the ONE constructor of the tree (every model with such a state, the
    tests; ``parallel/serve.init_state`` lays the same shapes out per stage)."""
    return {
        name: jnp.zeros((layers, rows, *shape), jnp.float32)
        for name, shape in cfg.recurrent_shapes.items()
    }


def masked_stats(stats, valid):
    """A masked (padding) layer read and counted nothing."""
    return jax.tree.map(lambda a: jnp.where(valid, a, jnp.zeros_like(a)), stats)


def zero_stats(cfg, count: int):
    """An all-zero ``MoeStats`` of ``count`` layers."""
    from ..ops.moe import MoeStats

    return MoeStats(
        jnp.zeros((count, cfg.num_experts), jnp.int32),
        jnp.zeros((count,), jnp.int32),
    )


def place_stats(cfg, total: int, parts):
    """The runs' stacked stats ``[(run, stats)]``, laid over the stage's
    ``total`` layer slots (a dense run reads and counts nothing)."""
    if not cfg.num_experts:
        return None
    out = zero_stats(cfg, total)
    for run, st in parts:
        if st is not None:
            out = jax.tree.map(
                lambda o, s: o.at[run.slot_first:run.slot_first + run.count].set(s),
                out, st,
            )
    return out


def _slot(i, first_layer):
    """Layer ``i`` of a stack that fills the slots ``first_layer …``."""
    if isinstance(first_layer, int) and first_layer == 0:
        return i
    return i + first_layer


def close_tables(cfg, tables: dict):
    """What closes a looped stack's pass, out of the head's tables: the final
    norm's gain and the exit gate (``[H]`` and its bias). None for a stack
    that runs once — the keyword every stage function takes as ``close``."""
    if cfg.passes == 1:
        return None
    return {k: tables[k] for k in ("final_norm", "exit_gate", "exit_bias")}


def run_passes(cfg, close: dict, h: jnp.ndarray, carry, one_pass,
               num_layers: int):
    """The ONE loop over a looped stack's passes (``cfg.passes`` = T > 1):
    ``one_pass(first_slot, h, carry) -> (h, carry)`` runs the stack's layers
    once over the cache slots ``first_slot …`` (pass ``t`` of ``num_layers``
    layers: ``t · num_layers``, traced), and every pass is CLOSED by the
    final norm, whose result enters the next pass and is that pass's closed
    state ``s_t``.

    The exit gate reads each closed state: ``g_t = sigmoid(s_t · exit_gate +
    exit_bias)``, ``p_t = g_t · Π_{u<t} (1 - g_u)`` (the last pass takes what
    is left), and a position's output is the closed state of the FIRST pass
    at which the running sum of ``p`` reaches ``cfg.exit_threshold``, else of
    the last. Every pass runs whatever the gate says (later tokens attend
    every pass's keys); the choice is kept as it goes — the chosen state, the
    survival product, the running sum and the exit pass — so no ``[T, B, S,
    H]`` stack is built. Returns ``(chosen [B, S, H], carry, exit_pass [B, S]
    int32)``."""
    from ..ops.norms import rms_norm

    if close is None:
        raise ValueError(
            f"a looped stack ({cfg.passes} passes) needs what closes a pass: "
            "hand the stage function close=close_tables(cfg, tables)"
        )
    T, L = cfg.passes, num_layers
    gate = close["exit_gate"].astype(jnp.float32)
    bias = close["exit_bias"].astype(jnp.float32).reshape(())

    def body(t, c):
        h, carry, chosen, stay, cum, exit_pass = c
        h, carry = one_pass(t * L, h, carry)
        with jax.named_scope("pass_close"):
            h = rms_norm(
                h, close["final_norm"], cfg.rms_norm_eps, cfg.norm_offset
            )
            g = jax.nn.sigmoid(
                jnp.sum(h.astype(jnp.float32) * gate, axis=-1) + bias
            )
            cum = cum + jnp.where(t == T - 1, stay, g * stay)
            stay = stay * (1.0 - g)
            take = (exit_pass < 0) & (
                (cum >= cfg.exit_threshold) | (t == T - 1)
            )
            chosen = jnp.where(take[..., None], h, chosen)
            exit_pass = jnp.where(take, t, exit_pass)
        return h, carry, chosen, stay, cum, exit_pass

    rows = h.shape[:2]
    _, carry, chosen, _, _, exit_pass = jax.lax.fori_loop(
        0, T, body,
        (h, carry, jnp.zeros_like(h), jnp.ones(rows, jnp.float32),
         jnp.zeros(rows, jnp.float32), jnp.full(rows, -1, jnp.int32)),
    )
    return chosen, carry, exit_pass


def scan_layers(
    layers,
    h: jnp.ndarray,
    cache: KVCache,
    positions: jnp.ndarray,
    apply_layer: ApplyLayerFn,
    layer_mask: Optional[jnp.ndarray] = None,
    first_layer=0,
):
    """Returns ``(h, cache, stats)``. ``layers`` fill the cache's layer slots
    ``first_layer …`` (one kind's stack of a model with several,
    ``kind_spans``; a looped stack's pass, ``run_passes``: a traced offset);
    ``layer_mask`` is theirs."""
    S = h.shape[1]
    layers, whole = split_whole(layers)
    L = cache.num_layers if layer_mask is None else layer_mask.shape[0]
    if layer_mask is None:
        layer_mask = jnp.ones((L,), bool)

    # Record this step's key positions once — shared by every layer.
    kv_pos = jax.lax.dynamic_update_slice(
        cache.pos, positions.astype(jnp.int32), (0, cache.length)
    )

    # The cache rides the scan CARRY with per-layer in-place writes of ONLY
    # the S new positions — not as stacked scan outputs. Output-stacking
    # (r1-r3) rewrote every layer's FULL [B, C, ...] row per step: at
    # decode S=1 that is C× the bytes actually produced (e.g. 0.5 GB/step
    # of dead writes for an 8-row C=512 serving cache). XLA keeps the
    # carried buffers in place (dynamic-index read + dynamic-update-slice
    # write on a loop carry is the standard aliasing pattern).
    def body(carry, xs):
        h, k_all, v_all = carry
        p, i, valid = xs
        l = _slot(i, first_layer)  # the cache's layer slot
        with jax.named_scope("kv_take"):
            k_row = jax.lax.dynamic_index_in_dim(k_all, l, keepdims=False)
            v_row = jax.lax.dynamic_index_in_dim(v_all, l, keepdims=False)
        h_new, k_new, v_new, stats = apply_layer(
            join_whole(p, whole, i), h, k_row, v_row, kv_pos, cache.length
        )
        h = jnp.where(valid, h_new, h)
        with jax.named_scope("kv_put"):
            # the layer only changed positions [length, length+S) of its row
            start = (0, cache.length, 0, 0)
            new_k = jax.lax.dynamic_slice(k_new, start, (k_new.shape[0], S, *k_new.shape[2:]))
            new_v = jax.lax.dynamic_slice(v_new, start, (v_new.shape[0], S, *v_new.shape[2:]))
            old_k = jax.lax.dynamic_slice(k_row, start, new_k.shape)
            old_v = jax.lax.dynamic_slice(v_row, start, new_v.shape)
            new_k = jnp.where(valid, new_k, old_k)
            new_v = jnp.where(valid, new_v, old_v)
            k_all = jax.lax.dynamic_update_slice(k_all, new_k[None], (l, *start))
            v_all = jax.lax.dynamic_update_slice(v_all, new_v[None], (l, *start))
        return (h, k_all, v_all), masked_stats(stats, valid)

    (h, k_all, v_all), stats = jax.lax.scan(
        body, (h, cache.k, cache.v),
        (layers, jnp.arange(L, dtype=jnp.int32), layer_mask),
    )
    new_cache = KVCache(k=k_all, v=v_all, pos=kv_pos, length=cache.length + S)
    return h, new_cache, stats


def scan_layers_paged(
    layers,
    h: jnp.ndarray,
    k_arena: jnp.ndarray,  # [L, NB, Nkv, BS, D] pooled head-major blocks
    v_arena: jnp.ndarray,
    apply_layer,  # (p, l, valid, h, k_all, v_all, ks_all, vs_all) ->
    #   (h, k_all, v_all, ks_all, vs_all, stats) — the WHOLE stacks plus
    #   the layer index; scale stacks are None unquantized, ``stats`` as in
    #   ``scan_layers``
    layer_mask: Optional[jnp.ndarray] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [L, NB, Nkv] f32 per-block-
    v_scale: Optional[jnp.ndarray] = None,  # per-head scales (quantized)
    first_layer=0,  # ``layers`` fill the arena's layer slots from here on
    #   (one kind's stack, ``kind_spans``; a looped stack's pass,
    #   ``run_passes``: a traced offset); ``layer_mask`` is theirs
):
    """Paged analogue of ``scan_layers``: the cache is the pooled block
    arena, and a layer's update is the tiny block-indexed write of this
    step's entries (``ops/paged_attention.paged_attention_write`` inside
    ``apply_layer``: inside the attention kernel or ``write_block_kv``'s
    scatter) —
    never a full-row or full-window write. The
    layer-stacked arena rides the scan carry and goes to ``apply_layer``
    WHOLE, with the layer index: the attention ops address ``(l, block)``
    inside it (the kernels through a scalar-prefetched ``l``, the XLA path
    through one gather), and the write scatters into it, so the scan body
    holds no operation that produces or consumes a layer of the pool — the
    per-layer slice / write-back pair that used to bracket ``apply_layer``
    is gone, and a step's cost no longer follows the pool's size.
    Key-position bookkeeping stays with the CALLER (the serve programs own
    the logical ``kpos`` window; there is no per-scan ``KVCache.pos``
    here). Layer validity is passed INTO ``apply_layer`` so masked
    (padding) layers gate their scattered entries instead of ``where``-ing
    the whole arena; the hidden-state gate stays here like the dense scan.

    A QUANTIZED arena (int8/fp8 storage) carries its scale stacks through
    the same scan (``None`` leaves are empty pytree nodes, so the
    unquantized carry is unchanged). Returns ``(h, k_arena, v_arena,
    k_scale, v_scale, stats)`` — the scale outputs are None when the arena
    is unquantized."""
    # (``k_arena`` may be a pair, a token-selecting model's K and index
    # arenas: a carry is a pytree)
    L = v_arena.shape[0] if layer_mask is None else layer_mask.shape[0]
    if layer_mask is None:
        layer_mask = jnp.ones((L,), bool)
    layers, whole = split_whole(layers)

    def body(carry, xs):
        h, k_all, v_all, ks_all, vs_all = carry
        p, i, valid = xs
        l = _slot(i, first_layer)  # the arena's layer slot
        h_new, k_all, v_all, ks_all, vs_all, stats = apply_layer(
            join_whole(p, whole, i), l, valid, h, k_all, v_all, ks_all, vs_all
        )
        h = jnp.where(valid, h_new, h)
        return (h, k_all, v_all, ks_all, vs_all), masked_stats(stats, valid)

    (h, k_arena, v_arena, k_scale, v_scale), stats = jax.lax.scan(
        body, (h, k_arena, v_arena, k_scale, v_scale),
        (layers, jnp.arange(L, dtype=jnp.int32), layer_mask),
    )
    return h, k_arena, v_arena, k_scale, v_scale, stats


def scan_run(run: Run, stack, mask, carry, apply_layer):
    """One run: ``lax.scan`` over layers ``run.stack_first …`` of ``stack``
    (its kind's whole stack), each layer's leaves taken out where they lie
    (the scan's own per-iteration slice, at an offset) — never a slice of
    the stack made beforehand, which would copy the run's weights a call.
    ``apply_layer(p, i, valid, carry) -> (carry, stats)``, ``i`` the layer's
    index in the run."""
    scanned, whole = split_whole(stack)

    def body(carry, xs):
        i, valid = xs
        at = i + run.stack_first
        p = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, at, keepdims=False),
            scanned,
        )
        carry, stats = apply_layer(join_whole(p, whole, at), i, valid, carry)
        return carry, masked_stats(stats, valid)

    return jax.lax.scan(
        body, carry, (jnp.arange(run.count, dtype=jnp.int32), mask)
    )
