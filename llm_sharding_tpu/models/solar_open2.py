"""Pure-JAX ``solar_open2`` causal LM (Solar-Open2-250B): KDA linear-attention
mixers and, every fourth layer, gated softmax attention WITHOUT positions in
ONE stack, routed experts in every layer, a RECURRENT matrix state of fixed
size a request beside a paged K/V arena that only the attention layers write.

**Layers of two kinds, each TWO sub-blocks.** ``h ← h + mixer(RMSNorm_in(h))``,
then ``h ← h + MoE(RMSNorm_post(h))``. ``cfg.layer_pattern[l]`` names the mixer
— ``K`` a KDA mixer (kind ``kda``), ``G`` gated GQA (``gqa``; the layers of the
published ``gqa_layers``). A kind's layer is its mixer AND its MLP:
``params["layers"] = {kind: {leaf: [L_kind, ...]}}``, one stack per kind in
layer order; a stage runs its layers as RUNS of one kind in model order
(``models/stack.stage_runs``, ``scan_run``). Every stage of a ring must
hold the same sequence of kinds.

**``kda``** (``ops/kda.py``; Kimi Linear, arXiv:2510.26692). ``q̃``, ``k̃``, ``ṽ`` =
``x̂ wq``, ``x̂ wk``, ``x̂ wv`` (``H → heads · head_dim`` each, no bias), a causal
depthwise conv of ``conv_kernel`` taps and SiLU over ``[q̃ | k̃ | ṽ]`` (no conv
bias); per head ``q = L2norm(q̃) · head_dim^-1/2``, ``k = L2norm(k̃)``, ``v = ṽ``;
NO positions. The decay, per head AND key channel: ``g = −exp(A_log) ⊙
softplus((x̂ w_a_down) w_a_up + dt_bias)`` (``A_log`` a head, ``dt_bias`` a
channel, the projection a low-rank pair of width ``head_dim``) — ``α = exp(g)``
in (0, 1) with NO lower bound; the write strength ``β = kda_beta_scale ·
sigmoid(x̂ w_beta)``, one a head, in (0, 2). The state a head, ``S [head_dim,
head_dim]`` (key x value) float32: ``S' = Diag(α) S``, ``u = v − S'ᵀ k``, ``S = S'
+ β k uᵀ``, ``o = Sᵀ q`` — the state is READ against the key and CORRECTED, not
only decayed and added to. Out: ``wo (RMSNorm_head(o) · gain ⊙ sigmoid((x̂
w_g_down) w_g_up))``, the norm over each head's channels with ONE gain of
``head_dim``, the gate a value a channel. What a request keeps per layer is
``S`` and the conv's last ``conv_kernel - 1`` inputs (float32): the RECURRENT
STATE, ``kda [L_kda, rows, heads, head_dim, head_dim]`` and ``conv [L_kda, rows,
K-1, 3 · heads · head_dim]`` (``cfg.recurrent_shapes``), indexed by ROW, riding
the layer scan's carry and updated where it lies. A decode step advances one
position a row and touches the LIVE rows only (``kda_decode_rows`` →
``kda.kda_step_rows``: ONE kernel call a layer over them on the chip, a loop in
XLA; a dead row's 4 MB are neither read nor written); a prefill chunk runs the
chunkwise WY form (``kda.kda_chunk``) with the row's stored state as the carry
in and out. A position that is no real token (a pad, a dead row, a masked
layer, a ring-inactive microstep) has ``g = 0``, ``β = 0`` and leaves the conv's
tail alone: the state stays EXACTLY what it was. A row's first chunk starts
from a zero state inside the chunk program (``fresh``).

**``gqa``**. ``q = x̂ wq`` (``num_attention_heads`` x ``head_dim``), ``k``, ``v``
(``num_key_value_heads`` each), no bias, NO rotary embedding and no other
position term, causal softmax at ``head_dim^-1/2`` through ``paged_decode`` /
``paged_prefill``, then ``wo (o ⊙ sigmoid(x̂ w_gate))`` — a gate value a
channel. The paged arena holds the attention layers ONLY (``[L_gqa, NB, Hkv,
BS, D]``).

**The MLP** is ``models/deepseek_v3.mlp_sub_block``: the held share of the
routed experts (``route_noaux_tc`` over ONE group, ``expert_mlp(held=)``) beside
the shared expert.

The serve programs hand the recurrent state over inside ``k_arena`` exactly as
``nemotron_h``'s: ``(k, {"kda", "conv", "row0", "fresh"})``.

Refused by name: the dense-cache path (``forward_layers``), tensor and
context parallelism, a quantized arena, a stage whose kinds differ from the
model's first stage's.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops import kda, ssm
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from .config import ModelConfig
from .deepseek_v3 import mlp_sub_block
from .family import refuse_axes
from .llama import embed, final_logits  # noqa: F401  (the family's own)
from .stack import place_stats, scan_run, stage_runs, zero_recurrent

Params = dict[str, Any]
f32 = jnp.float32

#: what ``L2norm`` adds under its root (the released KDA module's)
L2_EPS = 1e-6


# ---------------------------------------------------------------------------
# Initialization (random weights for tests; the benchmark's block draws its own)
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
    ks = iter(jax.random.split(key, 32))

    def w(*shape, fan_in=None):
        fan_in = fan_in or shape[-2]
        return jax.random.normal(next(ks), (L, *shape), dtype) * jnp.asarray(
            fan_in ** -0.5, dtype
        )

    def u(lo, hi, *shape):
        return jax.random.uniform(next(ks), (L, *shape), f32, lo, hi)

    E, F = cfg.num_experts, cfg.moe_intermediate_size
    held, Fs = cfg.experts_held_, F * cfg.n_shared_experts
    p = dict(
        input_norm=jnp.ones((L, H), dtype), post_norm=jnp.ones((L, H), dtype),
        router=w(H, E),
        router_bias=0.1 * jax.random.normal(next(ks), (L, E), f32),
        we_gate=w(H, held * F), we_up=w(H, held * F),
        we_down=w(held * F, H, fan_in=F),
        ws_gate=w(H, Fs), ws_up=w(H, Fs), ws_down=w(Fs, H),
    )
    if kind == "gqa":
        Hq, Hkv, D = (
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
        )
        p.update(
            wq=w(H, Hq * D), wk=w(H, Hkv * D), wv=w(H, Hkv * D),
            wo=w(Hq * D, H),
        )
        if cfg.attn_gate:
            p["w_gate"] = w(H, Hq * D)
        return p
    nh, hd, K = cfg.kda_num_heads, cfg.kda_head_dim, cfg.conv_kernel
    D = nh * hd
    # dt_bias: the inverse softplus of a log-uniform step in [time_step_min,
    # time_step_max]; A_log = log U(1, 16) (Mamba-2's draw: models/nemotron_h.py)
    dt = jnp.exp(u(jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max), D))
    p.update(
        wq=w(H, D), wk=w(H, D), wv=w(H, D),
        w_a_down=w(H, hd), w_a_up=w(hd, D),
        w_g_down=w(H, hd), w_g_up=w(hd, D),
        w_beta=w(H, nh),
        conv_w=u(-0.5, 0.5, K, 3 * D),
        A_log=jnp.log(u(1.0, 16.0, nh)),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        gate_norm=jnp.ones((L, hd), dtype),
        wo=w(D, H),
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


def kind_layer_counts(cfg: ModelConfig, layers: Params, axis: int = 1) -> dict:
    """``{"kda": n, "gqa": n}``: a stage's layers of each kind from its tree
    (``axis`` 1 of the stage-stacked leaves, 0 inside a stage program) — ``gqa``
    sizes the arena, ``kda`` the recurrent state."""
    out = {"kda": 0, "gqa": 0}
    for kind in dict.fromkeys(cfg.layer_kinds):
        out[kind] = jax.tree.leaves(layers[kind])[0].shape[axis]
    return out


# ---------------------------------------------------------------------------
# The two mixers. The named scopes are words of ``obs.stepline.SCOPES``.
# ---------------------------------------------------------------------------

def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _mixer_in(cfg: ModelConfig, p: Params, h, tail, live):
    """A KDA mixer up to its state update: the norm, the projections, the conv
    over ``[q | k | v]`` (its ``tail`` shifted for the live positions) and the
    update's operands → ``(q, k, v [B, S, heads, head_dim]``, ``g [B, S, heads,
    head_dim]`` and ``beta [B, S, heads]`` — both 0 where not live —, ``z [B, S,
    heads · head_dim]`` the output gate's logits, ``tail)``."""
    B, S, _ = h.shape
    nh, hd = cfg.kda_num_heads, cfg.kda_head_dim
    with jax.named_scope("norm"):
        x = rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
    with jax.named_scope("kda_proj"):
        # the projections leave as the dots made them (models/llama.py, PR
        # 31): the head splits below must not be folded into the dots
        qkv, a, b, z = jax.lax.optimization_barrier((
            jnp.concatenate(
                [qmatmul(x, p["wq"]), qmatmul(x, p["wk"]), qmatmul(x, p["wv"])],
                axis=-1,
            ),
            qmatmul(qmatmul(x, p["w_a_down"]), p["w_a_up"]),
            qmatmul(x, p["w_beta"]),
            qmatmul(qmatmul(x, p["w_g_down"]), p["w_g_up"]),
        ))
    with jax.named_scope("conv"):
        if S == 1:
            qkv, shifted = ssm.conv_step(tail, qkv[:, 0], p["conv_w"])
            qkv, tail = qkv[:, None], jnp.where(live[:, :, None], shifted, tail)
        else:
            qkv, tail = ssm.conv_chunk(
                tail, qkv, jnp.sum(live, axis=1).astype(jnp.int32),
                p["conv_w"],
            )
    with jax.named_scope("kda"):
        qkv = qkv.reshape(B, S, 3, nh, hd)
        q = _l2norm(qkv[:, :, 0]) * hd ** -0.5
        k = _l2norm(qkv[:, :, 1])
        v = qkv[:, :, 2]
        step = jax.nn.softplus(a.astype(f32) + p["dt_bias"].astype(f32))
        g = -jnp.exp(p["A_log"].astype(f32))[:, None] * step.reshape(
            B, S, nh, hd
        )
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(
            live[..., None],
            cfg.kda_beta_scale * jax.nn.sigmoid(b.astype(f32)), 0.0,
        )
    return q, k, v, g, beta, z, tail


def _mixer_out(cfg: ModelConfig, p: Params, h, o, z):
    """A KDA mixer after its state update: the norm of ``o [B, S, heads,
    head_dim]`` over each HEAD's channels with its one gain, the sigmoid gate
    a channel, and ``wo`` with the residual add."""
    B, S, nh, hd = o.shape
    with jax.named_scope("kda"):
        y = rms_norm(o.astype(f32), p["gate_norm"], cfg.rms_norm_eps)
        y = y * jax.nn.sigmoid(z.astype(f32)).reshape(B, S, nh, hd)
        y = y.reshape(B, S, nh * hd).astype(h.dtype)
    with jax.named_scope("kda_proj"):
        return h + qmatmul(y, p["wo"])


def kda_block(cfg: ModelConfig, p: Params, h, state, tail, live):
    """``h [B, S, H]``, the rows' ``state [B, heads, head_dim, head_dim]`` and
    conv ``tail [B, K-1, 3 · heads · head_dim]``, ``live [B, S]`` the positions
    that are real tokens (a row's FIRST ``Σ live`` positions) → ``(h, state,
    tail)``: the mixer sub-block alone. ``S == 1`` is the decode step, else the
    chunkwise form."""
    q, k, v, g, beta, z, tail = _mixer_in(cfg, p, h, tail, live)
    with jax.named_scope("kda"):
        if h.shape[1] == 1:
            o, state = kda.kda_step(
                state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
            )
            o = o[:, None]
        else:
            o, state = kda.kda_chunk(state, q, k, v, g, beta)
    return _mixer_out(cfg, p, h, o, z), state, tail


def kda_decode_rows(cfg: ModelConfig, p: Params, h, s_all, at, tail, live,
                    backend: str = "auto"):
    """A decode step of a slot's rows with the state updated WHERE IT LIES
    and only where a row is live: ``s_all [L_kda, rows, heads, head_dim,
    head_dim]`` the whole carried state, ``at = (layer, first row)``, ``h [B,
    1, H]``, ``live [B, 1]`` → ``(h, s_all, tail)``. A row that is not live (a
    finished request, an empty row of the slot, a parked slot) costs neither a
    read nor a write of its 4 MB. ``backend``: ``ops/kda.kda_step_rows``'s."""
    q, k, v, g, beta, z, tail = _mixer_in(cfg, p, h, tail, live)
    with jax.named_scope("kda"):
        alive = live[:, 0]
        o, s_all = kda.kda_step_rows(
            s_all, at, jnp.argsort(~alive),  # the live rows first
            jnp.sum(alive.astype(jnp.int32)), q[:, 0], k[:, 0], v[:, 0],
            g[:, 0], beta[:, 0], backend=backend,
        )
    return _mixer_out(cfg, p, h, o[:, None], z), s_all, tail


def gqa_block(cfg: ModelConfig, p: Params, h, attend):
    """An attention layer's mixer with the cache mechanism injected:
    ``attend(q [B,S,Hq,D], k [B,S,Hkv,D], v) -> (o [B,S,Hq,D], cache)``. No
    rotary embedding; the output gated a channel by ``sigmoid(x̂ w_gate)``
    before ``wo`` (where the layer has the leaf). Returns ``(h, cache)``."""
    B, S, _ = h.shape
    Hq, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
    with jax.named_scope("norm"):
        x = rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
    with jax.named_scope("qkv"):
        qx, kx, vx = (
            qmatmul(x, p["wq"]), qmatmul(x, p["wk"]), qmatmul(x, p["wv"])
        )
        gate = qmatmul(x, p["w_gate"]) if "w_gate" in p else None
        # q, k and v leave the projection as the dot made them
        # (models/nemotron_h.attn_block's note: no rotary embedding stands
        # between the dot and the head split)
        qx, kx, vx = jax.lax.optimization_barrier((qx, kx, vx))
    o, cache = attend(
        qx.reshape(B, S, Hq, D), kx.reshape(B, S, Hkv, D),
        vx.reshape(B, S, Hkv, D),
    )
    o = o.reshape(B, S, Hq * D)
    if gate is not None:
        with jax.named_scope("attn"):
            o = (o.astype(f32) * jax.nn.sigmoid(gate.astype(f32))).astype(
                h.dtype
            )
    with jax.named_scope("o_proj"):
        return h + qmatmul(o, p["wo"]), cache


# ---------------------------------------------------------------------------
# Stage functions
# ---------------------------------------------------------------------------

def forward_layers(cfg, layers, h, cache, positions, layer_mask=None,
                   tp_axis=None, moe_live=None):
    """The dense-cache path is REFUSED: a ``KVCache`` row has no place for a
    mixer's recurrent state."""
    raise NotImplementedError(
        "solar_open2 over a dense KV cache (the monolith, a non-paged "
        "server): a KDA layer's recurrent state lives beside the PAGED arena "
        "only — serve it with kv_block_size, kv_blocks and prefill_chunk set"
    )


def forward_layers_paged(
    cfg: ModelConfig,
    layers: Params,  # {kind: stacked leaves}
    h: jnp.ndarray,
    k_arena,  # (k [L_gqa, NB, Hkv, BS, D], {"kda" [L_kda, rows, nh, hd, hd],
    #   "conv" [L_kda, rows, K-1, 3·nh·hd], "row0", "fresh"})
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
    """Paged path (``models/nemotron_h.forward_layers_paged``'s contract).
    Returns ``(h, (k_arena, recurrent), v_arena, None, None, stats)``."""
    from ..ops.paged_attention import (
        paged_attention_write, paged_prefill, write_chunk_kv,
    )

    refuse_axes(cfg, tp_axis, cp_axis)
    if k_scale is not None:
        raise NotImplementedError(
            "a quantized (int8/fp8) arena under solar_open2 is not implemented"
        )
    k_all, rec = k_arena
    row0, fresh = rec["row0"], rec["fresh"]
    # every layer's slice and write-back below name THIS value
    # (models/nemotron_h.py says what the edge saves)
    s_in, c_in = jax.lax.optimization_barrier((rec["kda"], rec["conv"]))
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
            if run.kind == "gqa":
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

                h_new, (k_a, v_a) = gqa_block(cfg, p, h, attend)
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
                    h_new, s, c = kda_block(cfg, p, h, s, c, live)
                    with jax.named_scope("state"):
                        s_all = jax.lax.dynamic_update_slice(
                            s_all, s[None], at_s
                        )
                else:  # a decode step: the live rows' state, where it lies
                    h_new, s_all, c = kda_decode_rows(
                        cfg, p, h, s_all, (l, row0), c, live, backend
                    )
                with jax.named_scope("state"):
                    c_all = jax.lax.dynamic_update_slice(c_all, c[None], at_c)
            h_new, stats = mlp_sub_block(cfg, p, h_new, live, backend)
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
    rec = {"kda": s_all, "conv": c_all, "row0": row0, "fresh": fresh}
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
    n = kind_layer_counts(cfg, stage_layers, axis=0)["gqa"]
    return w, n * jnp.stack([w.steps, w.run_of.shape[0] - 1]).astype(jnp.int32)


def forward_full(cfg: ModelConfig, params: Params, token_ids: jnp.ndarray,
                 moe_backend: str = "xla"):
    """The whole model over whole sequences from an empty state, with the
    SYSTEM's operations (the chunkwise form, the expert product, the quantised
    matmuls) and plain causal attention: logits ``[B, S, V]`` and the recurrent
    state the sequences leave (``[L_kda, B, ...]``). The tier-1 tests hold it
    to the reference (``benchmark/blocks/solar_open2.py``)."""
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
        if kind == "gqa":
            def attend(q, k, v):
                return cached_attention(
                    q, k, v, pos, pos, cfg.head_dim_ ** -0.5
                ), None

            h, _ = gqa_block(cfg, p, h, attend)
        else:
            zero = zero_recurrent(cfg, 1, B)
            h, s, c = kda_block(
                cfg, p, h, zero["kda"][0], zero["conv"][0], live
            )
            states.append(s)
            tails.append(c)
        h, _ = mlp_sub_block(cfg, p, h, live, moe_backend)
    return final_logits(cfg, params, h), {
        "kda": jnp.stack(states), "conv": jnp.stack(tails)
    }
