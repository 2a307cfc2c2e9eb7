"""Pure-JAX ``jamba`` causal LM (AI21-Jamba2-3B): Mamba-1 mixers and a few
attention layers in ONE stack, a dense gated MLP in every layer, a RECURRENT
state of fixed size a request beside the paged KV arena, a tied head.

**Layers of two kinds, each TWO sub-blocks.** ``h ← h + mixer(RMSNorm_in(h))``,
then ``h ← h + MLP(RMSNorm_ff(h))``; ``cfg.layer_pattern[l]`` names the mixer:
``M`` a Mamba-1 mixer (kind ``mamba``), ``*`` attention (``attn``) — layer
``l`` attends where ``l % attn_layer_period == attn_layer_offset``. A kind's
layer is its mixer AND the MLP: ``params["layers"] = {kind: {leaf: [L_kind,
...]}}``, one stack per kind in layer order; a stage runs its layers as RUNS
of one kind in model order (``models/stack.stage_runs``, ``scan_run`` — the
helpers ``mimo_v2`` and ``nemotron_h`` use). Every stage of a ring must
hold the same sequence of kinds. NO positional embedding anywhere.

**``mamba``** (``ops/ssm.py``, "Mamba-1"). ``[x | z] = ĥ w_in`` (``H → 2
d_inner``, no bias); ``x ← silu(causal depthwise conv + bias)`` over ``x``'s
channels ONLY; the path that makes the step and the read-in / read-out
vectors, under the scope ``ssm_x``: ``[δ | B | C] = x w_x`` (``d_inner →
dt_rank + 2 state``), THREE norms Jamba adds to Mamba (``δ``, ``B``, ``C``
each through an RMSNorm with its own gain), ``dt = softplus(δ w_dt + b_dt)``
(``dt_rank → d_inner``, WITH bias); ``A = -exp(A_log)``, ``A_log [d_inner,
state]`` — a decay per channel AND state value; the state ``S [state,
d_inner]`` ← ``exp(dt A) S + dt B x``, ``y = S·C + D x``, ``y ← y · silu(z)`` (a
plain gate, no gated norm) and ``w_out``. What a request keeps per layer is
``S`` and the conv's last ``conv_kernel - 1`` inputs (float32): the RECURRENT
STATE, ``cfg.recurrent_shapes`` a row, indexed by ROW, riding the layer
scan's carry and updated where it lies. A decode step advances one position a
row and touches the LIVE rows only, in one of two forms chosen from what the
code sees (``ssm.mixer_step_path``: the backend, the shapes, plain ``w_x`` /
``w_dt``; no option). FUSED (``mamba_mixer_step``: the chip, and the kernels
interpreted): everything between ``w_in`` and ``w_out`` is ONE Pallas call a
layer, ``ssm.mixer_step_rows`` — the conv step, ``w_x``, the norms, ``w_dt``,
softplus, the update and the gate — that advances the state AND the conv's
tail inside the carried arrays and reads the stack's leaves through the
layer's index (the scan hands them WHOLE: ``models/stack.MAMBA1_WHOLE_KEYS``);
the live rows are counted ONCE a step (``live_rows``), and only ``A`` is made
outside the call (``ssm_x``). SPLIT (``mamba_mixer`` at ``S == 1``: the CPU's
XLA path, a shape the kernel cannot tile, int8 ``w_x`` / ``w_dt``): the tail
sliced out and written back (``state``), ``ssm.conv_step``, the path to ``dt``
in XLA, ``ssm.ssm_step_rows`` (the entry ``nemotron_h`` uses). A prefill
chunk is a SCAN IN TIME over the chunk's
positions (``ssm.scan_rows``: ONE Pallas kernel a layer call on the chip, the
rows with a real token its live rows) with the row's stored state the carry
in and out. A position that is no real token (a pad, a dead row, a masked
layer, a ring-inactive microstep) has ``dt = 0`` and leaves the conv's tail
alone — the fused step does not visit it at all —: the state stays EXACTLY
what it was. A row's first chunk starts from
a zero state inside the chunk program (``fresh``).

**``attn``**. ``num_attention_heads`` query heads over ``num_key_value_heads``
(ONE at the published widths) key/value heads, causal softmax at ``1/√head_dim``,
NO rotary embedding; plain GQA through ``paged_decode`` / ``paged_prefill``.
The paged arena holds the attention layers ONLY.

**The MLP** is ``models/llama.gated_mlp`` under ``mlp``; the head is the
embedding table (``tie_word_embeddings``: no ``lm_head`` leaf).

The serve programs hand the recurrent state over inside ``k_arena`` exactly
as ``nemotron_h``'s: ``(k, {"ssm", "conv", "row0", "fresh"})``.

Refused by name: the dense-cache path (``forward_layers``), tensor and
context parallelism, a quantized arena, a stage whose kinds differ from the
model's first stage's.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops import ssm
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul
from .config import ModelConfig
from .llama import embed, final_logits, gated_mlp  # noqa: F401
from .family import refuse_axes
from .nemotron_h import (  # noqa: F401  (``prefill_walks``: the family's)
    attn_block, kind_layer_counts, prefill_walks,
)
from .stack import MAMBA1_WHOLE_KEYS, scan_run, stage_runs, zero_recurrent

Params = dict[str, Any]
f32 = jnp.float32


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
    H, L, F = cfg.hidden_size, num_layers, cfg.intermediate_size
    ks = iter(jax.random.split(key, 20))

    def w(*shape):
        return jax.random.normal(next(ks), (L, *shape), dtype) * jnp.asarray(
            shape[-2] ** -0.5, dtype
        )

    def u(lo, hi, *shape):
        return jax.random.uniform(next(ks), (L, *shape), f32, lo, hi)

    p = {
        "norm": jnp.ones((L, H), dtype), "post_norm": jnp.ones((L, H), dtype),
        "w_gate": w(H, F), "w_up": w(H, F), "w_down": w(F, H),
    }
    if kind == "attn":
        Hq, Hkv, D = (
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
        )
        p.update(
            wq=w(H, Hq * D), wk=w(H, Hkv * D), wv=w(H, Hkv * D),
            wo=w(Hq * D, H),
        )
        return p
    di, ds, R, K = (
        cfg.ssm_inner, cfg.ssm_state_size, cfg.ssm_dt_rank, cfg.conv_kernel
    )
    # dt_bias: the inverse softplus of a log-uniform dt in [time_step_min,
    # time_step_max]; A_log[c, n] = log(n + 1), as published (S4D-real)
    dt = jnp.exp(u(jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max), di))
    p.update(
        w_in=w(H, 2 * di),
        conv_w=u(-0.5, 0.5, K, di), conv_b=u(-0.5, 0.5, di),
        w_x=w(di, R + 2 * ds),
        dt_norm=u(0.5, 1.5, R).astype(dtype),
        b_norm=u(0.5, 1.5, ds).astype(dtype),
        c_norm=u(0.5, 1.5, ds).astype(dtype),
        w_dt=w(R, di), dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        A_log=jnp.broadcast_to(
            jnp.log(jnp.arange(1, ds + 1, dtype=f32))[None, None], (L, di, ds)
        ),
        D=u(0.5, 1.5, di),
        w_out=w(di, H),
    )
    return p


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """The whole model; no ``lm_head`` where the head is tied."""
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    V, H = cfg.vocab_size, cfg.hidden_size
    kinds = cfg.layer_kinds
    params = {
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
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (H, V), f32) * H ** -0.5
        ).astype(dtype)
    return params


# ---------------------------------------------------------------------------
# The sub-blocks. The named scopes are words of ``obs.stepline.SCOPES``.
# ---------------------------------------------------------------------------

def _layer_leaves(p: Params) -> Params:
    """``p`` with every leaf the layer's own: the leaves a scan handed WHOLE
    (``models/stack.MAMBA1_WHOLE_KEYS``, beside the layer's index under
    ``"layer"``) sliced at that index — what the scan itself would have done
    — for the paths that read a layer's leaves in XLA."""
    if "layer" not in p:
        return p
    p = dict(p)
    at = p.pop("layer")
    for k in MAMBA1_WHOLE_KEYS:
        p[k] = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, at, keepdims=False), p[k]
        )
    return p


def _w_in(cfg: ModelConfig, p: Params, h):
    """A mixer's first projection: ``[x | z] = RMSNorm_in(h) w_in``."""
    with jax.named_scope("norm"):
        xh = rms_norm(h, p["norm"], cfg.rms_norm_eps)
    with jax.named_scope("ssm_proj"):
        # the projection leaves as the dot made it (models/llama.py, PR 31):
        # the column split after it must not be folded into the dot
        return jax.lax.optimization_barrier(qmatmul(xh, p["w_in"]))


def _decay(p: Params):
    """``A = -exp(A_log).T [state, d_inner]``, float32."""
    return -jnp.exp(p["A_log"].astype(f32)).T


def _mixer_in(cfg: ModelConfig, p: Params, h, tail, live):
    """A mixer up to its state update: the norm, ``w_in``, the conv over
    ``x`` (its ``tail`` shifted for the live positions) and the path that
    makes the update's operands → ``(x, z [B, S, d_inner], dt [B, S, d_inner]
    — 0 where not live —, A [state, d_inner], Bm, Cm [B, S, state], tail)``."""
    S = h.shape[1]
    di, ds, R = cfg.ssm_inner, cfg.ssm_state_size, cfg.ssm_dt_rank
    eps = cfg.rms_norm_eps
    xz = _w_in(cfg, p, h)
    x, z = xz[..., :di], xz[..., di:]
    with jax.named_scope("conv"):
        if S == 1:
            x, shifted = ssm.conv_step(tail, x[:, 0], p["conv_w"], p["conv_b"])
            x, tail = x[:, None], jnp.where(live[:, :, None], shifted, tail)
        else:
            x, tail = ssm.conv_chunk(
                tail, x, jnp.sum(live, axis=1).astype(jnp.int32),
                p["conv_w"], p["conv_b"],
            )
    with jax.named_scope("ssm_x"):
        dbc = qmatmul(x.astype(h.dtype), p["w_x"]).astype(f32)
        delta = rms_norm(dbc[..., :R], p["dt_norm"], eps)
        Bm = rms_norm(dbc[..., R:R + ds], p["b_norm"], eps)
        Cm = rms_norm(dbc[..., R + ds:], p["c_norm"], eps)
        dt = jax.nn.softplus(
            qmatmul(delta.astype(h.dtype), p["w_dt"]).astype(f32)
            + p["dt_bias"].astype(f32)
        )
        dt = jnp.where(live[..., None], dt, 0.0)
        A = _decay(p)
    return x, z, dt, A, Bm, Cm, tail


def live_rows(live):
    """``(order [B], n_live)`` of ``live [B, S]``: the slot's rows with those
    that hold a live position first, and their count — what the kernels of
    ``ops/ssm.py`` walk."""
    alive = jnp.any(live, axis=1)
    return jnp.argsort(~alive), jnp.sum(alive.astype(jnp.int32))


def mamba_mixer(cfg: ModelConfig, p: Params, h, s_all, at, tail, live,
                backend: str = "auto", rows=None):
    """A Mamba-1 mixer of a slot's rows with the state updated WHERE IT LIES
    and only where a row is live: ``s_all [L_mamba, rows, state, 8, d_inner /
    8]`` the whole carried state, ``at = (layer, first row)``, ``h [B, S, H]``,
    conv ``tail [B, K-1, d_inner]``, ``live [B, S]`` the positions that are
    real tokens (a row's FIRST ``Σ live``) → ``(h, s_all, tail)``. ``S == 1``
    is the decode step in its SPLIT form (``ssm.conv_step``, the path to
    ``dt`` in XLA, ``ssm.ssm_step_rows``; the fused form is
    ``mamba_mixer_step``), else the scan in time over the chunk's positions
    (``ssm.scan_rows``); a row with no live position costs neither a read nor
    a write of its state. ``rows``: ``live_rows(live)`` where the caller has
    it already. ``backend``: ``ops/ssm``'s."""
    p = _layer_leaves(p)
    x, z, dt, A, Bm, Cm, tail = _mixer_in(cfg, p, h, tail, live)
    with jax.named_scope("ssm"):
        order, n_live = live_rows(live) if rows is None else rows
        if h.shape[1] == 1:
            y, s_all = ssm.ssm_step_rows(
                s_all, at, order, n_live, x[:, 0], dt[:, 0], A, Bm[:, 0],
                Cm[:, 0], p["D"], backend=backend, z=z[:, 0],
            )
            y = y[:, None]
        else:
            y, s_all = ssm.scan_rows(
                s_all, at, order, n_live, x, dt, z, A, Bm, Cm, p["D"],
                backend=backend,
            )
    with jax.named_scope("ssm_proj"):
        return h + qmatmul(y.astype(h.dtype), p["w_out"]), s_all, tail


def mixer_step_fused(cfg: ModelConfig, p: Params, backend: str) -> bool:
    """Whether a decode step of this mixer runs as ``mamba_mixer_step``, from
    what the code can see: the leaves handed whole beside the layer's index,
    ``w_x`` and ``w_dt`` plain arrays, and ``ssm.mixer_step_path``'s answer
    for the backend and the shapes."""
    return "layer" in p and ssm.mixer_step_path(backend, cfg, p) == "fused"


def mamba_mixer_step(cfg: ModelConfig, p: Params, h, s_all, c_all, at, rows,
                     backend: str = "auto"):
    """A Mamba-1 mixer's DECODE step, fused: between ``w_in`` and ``w_out``
    ONE Pallas call (``ssm.mixer_step_rows``, under the scope ``ssm``) that
    advances the conv's tail AND the state of the slot's live rows where they
    lie — ``c_all [L_mamba, rows, K-1, d_inner]`` beside ``s_all``, ``at =
    (layer, first row)``, ``rows = (order, n_live)`` — and reads the stack's
    leaves through the same layer index (``models/stack.MAMBA1_WHOLE_KEYS``
    come whole: a layer's slot in the state is its index in its stack).
    Outside it only ``A = -exp(A_log).T`` (``ssm_x``). ``h [B, 1, H]`` →
    ``(h, s_all, c_all)``."""
    xz = _w_in(cfg, p, h)
    with jax.named_scope("ssm_x"):
        A = _decay(p)
    with jax.named_scope("ssm"):
        y, s_all, c_all = ssm.mixer_step_rows(
            s_all, c_all, at, *rows, xz[:, 0],
            {k: p[k] for k in MAMBA1_WHOLE_KEYS}, A, cfg.rms_norm_eps,
            backend=backend,
        )
    with jax.named_scope("ssm_proj"):
        return (
            h + qmatmul(y[:, None].astype(h.dtype), p["w_out"]), s_all, c_all
        )


def mixer_block(cfg: ModelConfig, p: Params, h, s_all, c_all, at, live,
                rows=None, backend: str = "auto", zero=None):
    """A mixer layer's first sub-block over the CARRIED recurrent state:
    ``s_all`` and ``c_all [L_mamba, rows, K-1, d_inner]`` whole, ``at =
    (layer, first row)``, ``live [B, S]``, ``rows = (order, n_live)`` where
    the caller counted them → ``(h, s_all, c_all)``. A decode step (``S ==
    1``, ``zero`` None) whose leaves and backend allow it
    (``mixer_step_fused``) is ``mamba_mixer_step`` — the conv's tail in the
    kernel's pass: no slice of ``c_all`` and no write-back. Anything else
    slices the slot's tails out, runs ``mamba_mixer`` and writes them back
    (``state``); a prefill chunk hands ``zero``, whether this is its rows'
    FIRST chunk: they then start from nothing."""
    B, S = h.shape[:2]
    if S == 1 and zero is None and mixer_step_fused(cfg, p, backend):
        return mamba_mixer_step(
            cfg, p, h, s_all, c_all, at,
            live_rows(live) if rows is None else rows, backend,
        )
    at_c = at + (0,) * (c_all.ndim - 2)
    with jax.named_scope("state"):
        c = jax.lax.dynamic_slice(c_all, at_c, (1, B, *c_all.shape[2:]))[0]
        if zero is not None:
            at_s = at + (0,) * (s_all.ndim - 2)
            s = jax.lax.dynamic_slice(s_all, at_s, (1, B, *s_all.shape[2:]))
            s_all = jax.lax.dynamic_update_slice(
                s_all, jnp.where(zero, jnp.zeros_like(s), s), at_s
            )
            c = jnp.where(zero, jnp.zeros_like(c), c)
    h, s_all, c = mamba_mixer(cfg, p, h, s_all, at, c, live, backend, rows)
    with jax.named_scope("state"):
        return h, s_all, jax.lax.dynamic_update_slice(c_all, c[None], at_c)


def mlp_block(cfg: ModelConfig, p: Params, h):
    """A layer's second sub-block: ``h + MLP(RMSNorm_ff(h))``."""
    with jax.named_scope("norm"):
        x = rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
    with jax.named_scope("mlp"):
        return h + gated_mlp(cfg, p, x)


# ---------------------------------------------------------------------------
# Stage functions
# ---------------------------------------------------------------------------

def forward_layers(cfg, layers, h, cache, positions, layer_mask=None,
                   tp_axis=None, moe_live=None):
    """The dense-cache path is REFUSED: a ``KVCache`` row has no place for a
    mixer's recurrent state."""
    raise NotImplementedError(
        "jamba over a dense KV cache (the monolith, a non-paged server): a "
        "Mamba layer's recurrent state lives beside the PAGED arena only — "
        "serve it with kv_block_size, kv_blocks and prefill_chunk set"
    )


def forward_layers_paged(
    cfg: ModelConfig,
    layers: Params,  # {kind: stacked leaves}
    h: jnp.ndarray,
    k_arena,  # (k [L_attn, NB, Hkv, BS, D], {"ssm" [L_mamba, rows, state, 8,
    #   d_inner / 8], "conv" [L_mamba, rows, K-1, d_inner], "row0", "fresh"})
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
    Returns ``(h, (k_arena, recurrent), v_arena, None, None, None)``."""
    from ..ops.paged_attention import (
        paged_attention_write, paged_prefill, write_chunk_kv,
    )

    refuse_axes(cfg, tp_axis, cp_axis)
    if k_scale is not None:
        raise NotImplementedError(
            "a quantized (int8/fp8) arena under jamba is not implemented"
        )
    k_all, rec = k_arena
    row0, fresh = rec["row0"], rec["fresh"]
    # every layer's slice and write-back below name THIS value
    # (models/nemotron_h.py says what the edge saves)
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
    # a decode step's live rows, ONCE for all the mixer layers: a layer
    # that is masked has none of them (``apply``)
    rows_live = None if prefill else live_rows(jnp.broadcast_to(
        jnp.asarray(wv) if moe_live is None else moe_live & jnp.asarray(wv),
        (B, S),
    ))
    carry = (h, k_all, v_arena, s_in, c_in)
    for run in stage_runs(cfg, layers):

        def apply(p, i, valid, carry, run=run):
            h, k_a, v_a, s_all, c_all = carry
            l = i + run.stack_first  # the layer's slot in its kind's state
            gate = jnp.asarray(wv) & valid
            live = jnp.broadcast_to(
                gate if moe_live is None else moe_live & gate, (B, S)
            )
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
            else:
                h_new, s_all, c_all = mixer_block(
                    cfg, p, h, s_all, c_all, (l, row0), live,
                    None if prefill else (
                        rows_live[0], jnp.where(valid, rows_live[1], 0)
                    ),
                    backend, zero=fresh & gate if prefill else None,
                )
            h_new = mlp_block(cfg, p, h_new)
            return (
                jnp.where(valid, h_new, h), k_a, v_a, s_all, c_all
            ), None

        carry, _ = scan_run(
            run, layers[run.kind],
            layer_mask[run.slot_first:run.slot_first + run.count],
            carry, apply,
        )
    h, k_all, v_all, s_all, c_all = carry
    s_all, c_all = jax.lax.optimization_barrier((s_all, c_all))
    rec = {"ssm": s_all, "conv": c_all, "row0": row0, "fresh": fresh}
    return h, (k_all, rec), v_all, None, None, None


def forward_full(cfg: ModelConfig, params: Params, token_ids: jnp.ndarray,
                 backend: str = "xla"):
    """The whole model over whole sequences from an empty state, with the
    SYSTEM's operations (the scan in time of ``ops/ssm.py``, the quantised
    matmuls) and plain causal attention: logits ``[B, S, V]`` and the
    recurrent state the sequences leave (``[L_mamba, B, ...]``). The tier-1
    tests hold it to the reference (``benchmark/blocks/jamba.py``)."""
    from ..ops.attention import cached_attention

    B, S = token_ids.shape
    h = embed(params, token_ids)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    live = jnp.ones((B, S), bool)
    seen = dict.fromkeys(params["layers"], 0)
    rec = zero_recurrent(cfg, cfg.layer_kinds.count("mamba"), B)
    s_all, tails = rec["ssm"], []
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
        else:
            h, s_all, c = mamba_mixer(
                cfg, p, h, s_all, (i, 0), rec["conv"][i], live, backend
            )
            tails.append(c)
        h = mlp_block(cfg, p, h)
    return final_logits(cfg, params, h), {
        "ssm": s_all, "conv": jnp.stack(tails)
    }
