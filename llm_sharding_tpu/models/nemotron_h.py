"""Pure-JAX ``nemotron_h`` causal LM (Nemotron-3-Super-120B-A12B): Mamba-2
mixers, LatentMoE feed-forwards and a few attention layers in ONE stack, a
RECURRENT state of fixed size a request beside the paged KV arena.

**Layers of three kinds, interleaved.** Every layer is ONE sub-block, ``h ← h
+ f(RMSNorm(h))``; ``cfg.layer_pattern[l]`` names ``f``: ``M`` a Mamba-2 mixer
(kind ``mamba``), ``E`` a LatentMoE (``moe``), ``*`` attention (``attn``).
``params["layers"] = {kind: {leaf: [L_kind, ...]}}``, one stack per kind in
layer order; a stage runs its layers as RUNS of one kind in model order
(``models/stack.stage_runs``), each run one ``lax.scan`` over a
range of its kind's stack. Every stage of a ring must hold the same sequence
of kinds.

**``mamba``** (``ops/ssm.py``). ``[z | xBC | dt] = x̂ w_in`` (``H → d_inner +
conv_dim + heads``); ``xBC ← silu(causal depthwise conv + bias)`` over the
``conv_kernel`` last inputs; ``x [heads, head_dim]``, ``B``, ``C [groups,
state]`` split from it; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
the state ``S [heads, head_dim, state]`` ← ``exp(dt A) S + dt (x ⊗ B)``, ``y = S
C + D x``; then the gated norm, gate FIRST (``RMSNorm_per_group(y · silu(z))``
with a gain) and ``w_out``. What a request keeps per layer is ``S`` (float32)
and the conv's last ``conv_kernel - 1`` inputs (float32): the RECURRENT STATE,
``ssm [L_mamba, rows, heads, head_dim, state]`` and ``conv [L_mamba, rows, K-1,
conv_dim]``, indexed by ROW. It rides the layer scan's carry and is updated
where it lies (a slot's rows of one layer are sliced out, advanced, written
back: nothing of a layer's size is produced beside it). A decode step
advances one position a row and touches the LIVE rows only
(``mamba_decode_rows`` → ``ssm.ssm_step_rows``: ONE kernel call a layer over
them on the chip, a loop over them in XLA; a dead row's 4 MB are neither read
nor written); a prefill chunk runs the block form over ``cfg.ssm_chunk``
positions (``ssm_chunk``) with the row's stored state as the carry in and
out. A position that is no real token (a pad, a dead row, a masked layer, a
ring-inactive microstep) has ``dt = 0`` and leaves the conv's tail alone: the
state stays EXACTLY what it was. A row's first
chunk starts from a zero state inside the chunk program (``fresh``).

**``moe``** (``ops/moe.py``). Router on the full width: ``route_noaux_tc`` over
all ``cfg.num_experts`` in one group, ``cfg.num_experts_per_tok`` kept, weights
normalised and scaled. The routed experts live in a ``cfg.moe_latent_size``
wide space: ``u = x̂ w_lat_down``, the HELD experts' terms ``Σ w_e relu(u
W1_e)² W2_e`` (``expert_mlp(act="relu2", held=)``: not gated, ONE up matrix),
then ``w_lat_up``; beside them one shared expert on the full width, ``relu(x̂
ws_up)² ws_down``.

**``attn``**. 32 query heads over 2 key/value heads of 128, causal softmax,
NO rotary embedding (the ``nemotron_h`` block applies none); plain GQA through
``paged_decode`` / ``paged_prefill``. The paged arena holds the attention
layers ONLY (``[L_attn, NB, Hkv, BS, D]``).

The serve programs hand the recurrent state over inside ``k_arena``: ``(k,
{"ssm", "conv", "row0", "fresh"})`` — ``row0`` the slot's first row, ``fresh``
whether this chunk is the rows' first — and take it back the same way.

Refused by name: the dense-cache path (``forward_layers``: a monolith or a
non-paged server has no place for the state), tensor and context parallelism,
a quantized arena, a stage whose kinds differ from the model's first stage's.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops import moe, ssm
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from .config import ModelConfig
from .llama import embed, final_logits  # noqa: F401  (the family's own)
from .family import refuse_axes
from .stack import (  # noqa: F401
    place_stats, scan_run, stage_runs, zero_recurrent,
)

Params = dict[str, Any]
f32 = jnp.float32


def kind_layer_counts(cfg: ModelConfig, layers: Params, axis: int = 1) -> dict:
    """``{kind: n}``: a stage's layers of each kind from its tree (``axis`` 1
    of the stage-stacked ``[S, P_kind, ...]`` leaves, 0 inside a stage
    program) — ``attn`` sizes the arena, ``mamba`` the recurrent state."""
    out = {"mamba": 0, "moe": 0, "attn": 0}
    for kind in dict.fromkeys(cfg.layer_kinds):
        out[kind] = jax.tree.leaves(layers[kind])[0].shape[axis]
    return out


# ---------------------------------------------------------------------------
# Initialization (random weights for tests; real ones come from convert.py)
# ---------------------------------------------------------------------------

def init_layer_params(
    cfg: ModelConfig, key: jax.Array, num_layers: int, dtype=jnp.bfloat16,
    kind: Optional[str] = None,
) -> Params:
    """``num_layers`` stacked layers of ``kind``; without a kind, that many
    of EACH kind the model has, as the per-kind tree."""
    if kind is None:
        return {
            k: init_layer_params(
                cfg, jax.random.fold_in(key, i), num_layers, dtype, k
            )
            for i, k in enumerate(dict.fromkeys(cfg.layer_kinds))
        }
    H, L = cfg.hidden_size, num_layers
    ks = iter(jax.random.split(key, 16))

    def w(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jax.random.normal(next(ks), (L, *shape), dtype) * jnp.asarray(
            fan_in ** -0.5, dtype
        )

    def u(lo, hi, *shape):
        return jax.random.uniform(next(ks), (L, *shape), f32, lo, hi)

    p = {"norm": jnp.ones((L, H), dtype)}
    if kind == "attn":
        Hq, Hkv, D = (
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
        )
        p.update(
            wq=w(H, Hq * D), wk=w(H, Hkv * D), wv=w(H, Hkv * D),
            wo=w(Hq * D, H),
        )
        return p
    if kind == "mamba":
        nh, di, K = cfg.mamba_num_heads, cfg.ssm_inner, cfg.conv_kernel
        # dt_bias: the inverse softplus of a log-uniform dt in
        # [time_step_min, time_step_max]; A_log = log U(1, 16)
        dt = jnp.exp(u(
            jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max), nh
        ))
        p.update(
            w_in=w(H, di + cfg.conv_dim + nh),
            conv_w=u(-0.5, 0.5, K, cfg.conv_dim),
            conv_b=u(-0.5, 0.5, cfg.conv_dim),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            A_log=jnp.log(u(1.0, 16.0, nh)),
            D=u(0.5, 1.5, nh),
            gate_norm=jnp.ones((L, di), dtype),
            w_out=w(di, H),
        )
        return p
    E, held, F = cfg.num_experts, cfg.experts_held_, cfg.moe_intermediate_size
    Hl, Fs = cfg.moe_latent_size, cfg.moe_shared_intermediate_size
    p.update(
        router=w(H, E),
        router_bias=0.1 * jax.random.normal(next(ks), (L, E), f32),
        w_lat_down=w(H, Hl), w_lat_up=w(Hl, H),
        we_up=w(Hl, held * F), we_down=w(held * F, Hl, fan_in=F),
        ws_up=w(H, Fs), ws_down=w(Fs, H),
    )
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    V, H = cfg.vocab_size, cfg.hidden_size
    kinds = cfg.layer_kinds
    return {
        "embed": (
            jax.random.normal(k_emb, (V, H), f32) * H ** -0.5
        ).astype(dtype),
        "layers": {
            kind: init_layer_params(
                cfg, jax.random.fold_in(k_layers, i), kinds.count(kind),
                dtype, kind,
            )
            for i, kind in enumerate(dict.fromkeys(kinds))
        },
        "final_norm": jnp.ones((H,), dtype),
        "lm_head": (
            jax.random.normal(k_head, (H, V), f32) * H ** -0.5
        ).astype(dtype),
    }


# ---------------------------------------------------------------------------
# The three sub-blocks. The named scopes are words of ``obs.stepline.SCOPES``.
# ---------------------------------------------------------------------------

def _mixer_in(cfg: ModelConfig, p: Params, h, tail, live):
    """A mixer up to its state update: the norm, ``w_in``, the conv (its
    ``tail`` shifted for the live positions) and the update's operands →
    ``(z, xs [B, S, heads, head_dim], dt [B, S, heads] — 0 where not live —,
    A, Bm, Cm [B, S, groups, state], tail)``."""
    B, S, _ = h.shape
    nh, hd, ds, g = (
        cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
        cfg.ssm_groups,
    )
    di, cd = cfg.ssm_inner, cfg.conv_dim
    with jax.named_scope("norm"):
        x = rms_norm(h, p["norm"], cfg.rms_norm_eps)
    with jax.named_scope("ssm_proj"):
        # the projection leaves as the dot made it (models/llama.py, PR 31):
        # the column splits below must not be folded into the dot
        zxd = jax.lax.optimization_barrier(qmatmul(x, p["w_in"]))
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]
    with jax.named_scope("conv"):
        if S == 1:
            xbc, shifted = ssm.conv_step(
                tail, xbc[:, 0], p["conv_w"], p["conv_b"]
            )
            xbc, tail = xbc[:, None], jnp.where(live[:, :, None], shifted, tail)
        else:
            xbc, tail = ssm.conv_chunk(
                tail, xbc, jnp.sum(live, axis=1).astype(jnp.int32),
                p["conv_w"], p["conv_b"],
            )
    with jax.named_scope("ssm"):
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
        dt = jnp.where(live[..., None], dt, 0.0)
        A = -jnp.exp(p["A_log"].astype(f32))
        xs = xbc[..., :di].reshape(B, S, nh, hd)
        Bm = xbc[..., di:di + g * ds].reshape(B, S, g, ds)
        Cm = xbc[..., di + g * ds:].reshape(B, S, g, ds)
    return z, xs, dt, A, Bm, Cm, tail


def _mixer_out(cfg: ModelConfig, p: Params, h, y, z):
    """A mixer after its state update: the gated norm of ``y [B, S, heads,
    head_dim]`` and ``w_out`` with the residual add."""
    B, S, _ = h.shape
    with jax.named_scope("ssm"):
        y = ssm.gated_group_norm(
            y.reshape(B, S, cfg.ssm_inner), z, p["gate_norm"], cfg.ssm_groups,
            cfg.rms_norm_eps,
        ).astype(h.dtype)
    with jax.named_scope("ssm_proj"):
        return h + qmatmul(y, p["w_out"])


def mamba_block(cfg: ModelConfig, p: Params, h, state, tail, live):
    """``h [B, S, H]``, the rows' ``state [B, heads, head_dim, state]`` and
    conv ``tail [B, K-1, conv_dim]``, ``live [B, S]`` the positions that are
    real tokens (a row's FIRST ``Σ live`` positions) → ``(h, state, tail)``.
    ``S == 1`` is the decode step, else the block form."""
    z, xs, dt, A, Bm, Cm, tail = _mixer_in(cfg, p, h, tail, live)
    with jax.named_scope("ssm"):
        if h.shape[1] == 1:
            y, state = ssm.ssm_step(
                state, xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], p["D"]
            )
            y = y[:, None]
        else:
            y, state = ssm.ssm_chunk(
                state, xs, dt, A, Bm, Cm, p["D"], cfg.ssm_chunk
            )
    return _mixer_out(cfg, p, h, y, z), state, tail


def mamba_decode_rows(cfg: ModelConfig, p: Params, h, s_all, at, tail, live,
                      backend: str = "auto"):
    """A decode step of a slot's rows with the state updated WHERE IT LIES
    and only where a row is live: ``s_all [L_mamba, rows, heads, head_dim,
    state]`` the whole carried state, ``at = (layer, first row)``, ``h [B, 1,
    H]``, ``live [B, 1]`` → ``(h, s_all, tail)``. A row that is not live
    (a finished request, an empty row of the slot, a parked slot) costs
    neither a read nor a write of its 4 MB: one live row of four moves a
    quarter of what ``mamba_block`` over the slot's rows would (its ``dt =
    0`` leaves a dead row's state as it was, but reads and writes it).
    ``backend``: ``ops/ssm.ssm_step_rows``'s (ONE kernel call a layer on the
    chip, a loop over the live rows in XLA)."""
    z, xs, dt, A, Bm, Cm, tail = _mixer_in(cfg, p, h, tail, live)
    with jax.named_scope("ssm"):
        alive = live[:, 0]
        y, s_all = ssm.ssm_step_rows(
            s_all, at, jnp.argsort(~alive),  # the live rows first
            jnp.sum(alive.astype(jnp.int32)), xs[:, 0], dt[:, 0], A,
            Bm[:, 0], Cm[:, 0], p["D"], backend=backend,
        )
    return _mixer_out(cfg, p, h, y[:, None], z), s_all, tail


def relu2_mlp(x, w_up, w_down):
    """``relu(x W_up)² W_down``, the activation in float32."""
    a = jnp.square(jax.nn.relu(qmatmul(x, w_up).astype(f32)))
    return qmatmul(a.astype(x.dtype), w_down)


def moe_block(cfg: ModelConfig, p: Params, h, live=None, backend="auto"):
    """A LatentMoE layer → ``(h, MoeStats)``; ``live [B, S]``: the positions
    that route."""
    B, S, H = h.shape
    with jax.named_scope("norm"):
        x = rms_norm(h, p["norm"], cfg.rms_norm_eps)
    x2 = x.reshape(B * S, H)
    with jax.named_scope("router"):
        weights, ids = moe.route_noaux_tc(
            x2, p["router"], p["router_bias"], cfg.num_experts_per_tok,
            cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor,
        )
    with jax.named_scope("moe_latent"):
        u = qmatmul(x2, p["w_lat_down"])
    r, stats = moe.expert_mlp(
        u, weights, ids, None, p["we_up"], p["we_down"], cfg.num_experts,
        live=None if live is None else live.reshape(B * S),
        layer=p.get("layer"), backend=backend, held=cfg.held_experts_,
        act="relu2",
    )
    with jax.named_scope("moe_latent"):
        r = qmatmul(r, p["w_lat_up"])
    with jax.named_scope("mlp"):
        shared = relu2_mlp(x2, p["ws_up"], p["ws_down"])
    return h + (r + shared).reshape(B, S, H), stats


def attn_block(cfg: ModelConfig, p: Params, h, attend):
    """An attention layer with the cache mechanism injected: ``attend(q
    [B,S,Hq,D], k [B,S,Hkv,D], v) -> (o [B,S,Hq,D], cache)``. No rotary
    embedding. Returns ``(h, cache)``."""
    B, S, _ = h.shape
    Hq, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
    with jax.named_scope("norm"):
        x = rms_norm(h, p["norm"], cfg.rms_norm_eps)
    with jax.named_scope("qkv"):
        qx, kx, vx = (
            qmatmul(x, p["wq"]), qmatmul(x, p["wk"]), qmatmul(x, p["wv"])
        )
        # q, k and v leave the projection as the dot made them
        # (models/llama.py holds k and v; with no rotary embedding between
        # the dot and the head split, XLA folds q's split into its dot too
        # and re-lays the whole ``wq`` stack every call)
        qx, kx, vx = jax.lax.optimization_barrier((qx, kx, vx))
    o, cache = attend(
        qx.reshape(B, S, Hq, D), kx.reshape(B, S, Hkv, D),
        vx.reshape(B, S, Hkv, D),
    )
    with jax.named_scope("o_proj"):
        return h + qmatmul(o.reshape(B, S, Hq * D), p["wo"]), cache


# ---------------------------------------------------------------------------
# Stage functions
# ---------------------------------------------------------------------------

def forward_layers(cfg, layers, h, cache, positions, layer_mask=None,
                   tp_axis=None, moe_live=None):
    """The dense-cache path is REFUSED: a ``KVCache`` row has no place for a
    mixer's recurrent state."""
    raise NotImplementedError(
        "nemotron_h over a dense KV cache (the monolith, a non-paged server): "
        "a Mamba-2 layer's recurrent state lives beside the PAGED arena only "
        "— serve it with kv_block_size, kv_blocks and prefill_chunk set"
    )


def forward_layers_paged(
    cfg: ModelConfig,
    layers: Params,  # {kind: stacked leaves}
    h: jnp.ndarray,
    k_arena,  # (k [L_attn, NB, Hkv, BS, D], {"ssm" [L_mamba, rows, nh, hd,
    #   ds], "conv" [L_mamba, rows, K-1, conv_dim], "row0", "fresh"})
    v_arena,
    block_table,  # [B, T]
    cols: jnp.ndarray,
    kv_positions: jnp.ndarray,
    positions: jnp.ndarray,
    layer_mask: Optional[jnp.ndarray] = None,
    write_valid=True,
    tp_axis: Optional[str] = None,
    backend: str = "auto",
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    prefill: bool = False,
    walk=None,
    cp_axis: Optional[str] = None,
    moe_live: Optional[jnp.ndarray] = None,  # [B, S] real positions / rows
):
    """Paged path (``models/llama.forward_layers_paged``'s contract, the
    recurrent state riding beside ``k_arena``). Returns ``(h, (k_arena,
    recurrent), v_arena, None, None, stats)``."""
    from ..ops.paged_attention import (
        paged_attention_write, paged_prefill, write_chunk_kv,
    )

    refuse_axes(cfg, tp_axis, cp_axis)
    if k_scale is not None:
        raise NotImplementedError(
            "a quantized (int8/fp8) arena under nemotron_h is not implemented"
        )
    k_all, rec = k_arena
    row0, fresh = rec["row0"], rec["fresh"]
    # every layer's slice and write-back below name THIS value: without the
    # edge XLA reads the first layer's slice from the program's parameter
    # (under the reshape that strips the stage dim) while it writes the
    # reshaped one, sees two buffers, and copies the whole state — 134 MB at
    # the published widths — once on the way in and once on the way out
    s_in, c_in = jax.lax.optimization_barrier((rec["ssm"], rec["conv"]))
    B, S = h.shape[:2]
    # a chunk's rows share their columns: it writes whole blocks from its
    # first column on (llama's note)
    col0 = cols[0, 0] if prefill else None
    wv = write_valid if isinstance(write_valid, bool) else jnp.asarray(
        write_valid
    )
    scale = cfg.head_dim_ ** -0.5
    n_slots = sum(kind_layer_counts(cfg, layers, axis=0).values())
    if layer_mask is None:
        layer_mask = jnp.ones((n_slots,), bool)
    carry = (h, k_all, v_arena, s_in, c_in)
    parts = []
    for run in stage_runs(cfg, layers):

        def apply(p, i, valid, carry, run=run):
            h, k_a, v_a, s_all, c_all = carry
            l = i + run.stack_first  # the layer's slot in its kind's state
            gate = jnp.asarray(wv) & valid
            live = jnp.broadcast_to(
                gate if moe_live is None else moe_live & gate, (B, S)
            )
            stats = None
            if run.kind == "attn":
                def attend(q, k, v):
                    if not prefill:  # a decode step (llama's note)
                        o, k_n, v_n, *_ = paged_attention_write(
                            q, k, v, k_a, v_a, l, block_table, cols,
                            positions, kv_positions, valid=gate,
                            scale=scale, backend=backend,
                        )
                        return o, (k_n, v_n)
                    k_n, v_n = write_chunk_kv(
                        k_a, v_a, l, block_table, col0, k, v, valid=gate,
                    )
                    o = paged_prefill(
                        q, k_n, v_n, l, block_table, positions,
                        kv_positions, scale, backend=backend, walk=walk,
                    )
                    return o, (k_n, v_n)

                h_new, (k_a, v_a) = attn_block(cfg, p, h, attend)
            elif run.kind == "moe":
                h_new, stats = moe_block(cfg, p, h, live, backend)
            else:
                at_c = (l, row0) + (0,) * (c_all.ndim - 2)
                with jax.named_scope("state"):
                    c = jax.lax.dynamic_slice(
                        c_all, at_c, (1, B, *c_all.shape[2:])
                    )[0]
                if prefill:
                    at_s = (l, row0) + (0,) * (s_all.ndim - 2)
                    with jax.named_scope("state"):
                        s = jax.lax.dynamic_slice(
                            s_all, at_s, (1, B, *s_all.shape[2:])
                        )[0]
                        # a row's first chunk starts from nothing
                        zero = fresh & gate
                        s = jnp.where(zero, jnp.zeros_like(s), s)
                        c = jnp.where(zero, jnp.zeros_like(c), c)
                    h_new, s, c = mamba_block(cfg, p, h, s, c, live)
                    with jax.named_scope("state"):
                        s_all = jax.lax.dynamic_update_slice(
                            s_all, s[None], at_s
                        )
                else:  # a decode step: the live rows' state, where it lies
                    h_new, s_all, c = mamba_decode_rows(
                        cfg, p, h, s_all, (l, row0), c, live, backend
                    )
                with jax.named_scope("state"):
                    c_all = jax.lax.dynamic_update_slice(c_all, c[None], at_c)
            return (
                jnp.where(valid, h_new, h), k_a, v_a, s_all, c_all
            ), stats

        carry, stats = scan_run(
            run, layers[run.kind],
            layer_mask[run.slot_first:run.slot_first + run.count],
            carry, apply,
        )
        parts.append((run, stats))
    h, k_all, v_all, s_all, c_all = carry
    s_all, c_all = jax.lax.optimization_barrier((s_all, c_all))
    rec = {"ssm": s_all, "conv": c_all, "row0": row0, "fresh": fresh}
    return (
        h, (k_all, rec), v_all, None, None, place_stats(cfg, n_slots, parts)
    )


def prefill_walks(cfg: ModelConfig, block_table, positions, kv_positions,
                  nlive, stage_layers):
    """The chunked-prefill kernel's work list (ONE: the attention layers are
    alike) and what it counts over the stage's ATTENTION layer calls."""
    from ..ops.paged_attention import prefill_walk

    w = prefill_walk(
        block_table, positions, kv_positions, nlive,
        q_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
    )
    n = kind_layer_counts(cfg, stage_layers, axis=0)["attn"]
    return w, n * jnp.stack([w.steps, w.run_of.shape[0] - 1]).astype(jnp.int32)


def forward_full(cfg: ModelConfig, params: Params, token_ids: jnp.ndarray,
                 moe_backend: str = "xla"):
    """The whole model over whole sequences from an empty state, with the
    SYSTEM's operations (the block-form scan, the expert product, the
    quantised matmuls) and plain causal attention: logits ``[B, S, V]`` and
    the recurrent state the sequences leave (``[L_mamba, B, ...]``). The
    tier-1 tests hold it to the reference (``benchmark/blocks/nemotron_h.py``)."""
    from ..ops.attention import cached_attention

    B, S = token_ids.shape
    h = embed(params, token_ids)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    live = jnp.ones((B, S), bool)
    seen = dict.fromkeys(params["layers"], 0)
    states, tails = [], []
    for kind in cfg.layer_kinds:
        i = seen[kind]
        seen[kind] = i + 1
        p = jax.tree.map(lambda a: a[i], params["layers"][kind])
        if kind == "attn":
            def attend(q, k, v):
                return cached_attention(
                    q, k, v, pos, pos, cfg.head_dim_ ** -0.5
                ), None

            h, _ = attn_block(cfg, p, h, attend)
        elif kind == "moe":
            h, _ = moe_block(cfg, p, h, live, moe_backend)
        else:
            zero = zero_recurrent(cfg, 1, B)
            h, s, c = mamba_block(
                cfg, p, h, zero["ssm"][0], zero["conv"][0], live
            )
            states.append(s)
            tails.append(c)
    return final_logits(cfg, params, h), {
        "ssm": jnp.stack(states), "conv": jnp.stack(tails)
    }
