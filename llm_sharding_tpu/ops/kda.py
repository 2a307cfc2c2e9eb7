"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): a linear-attention
mixer whose matrix state is CORRECTED before it is written — the recurrence of
``models/solar_open2.py``'s ``kda`` layers. Per head, ``S [d_k, d_v]`` (key x
value), float32::

    S' = Diag(α_t) S_{t-1}                  α_t = exp(g_t) ∈ (0, 1)^{d_k}: a decay
    u_t = v_t − S'ᵀ k_t                      per key CHANNEL, with NO lower bound
    S_t = S' + β_t k_t u_tᵀ                 β_t ∈ (0, 2): the write strength
    o_t = S_tᵀ q_t

Every update ``ops/ssm.py`` has is elementwise in the state (``S ← a ⊙ S +
b``); this one first READS the state against the key (``S'ᵀ k``, a
matrix-vector product a head) and writes a rank-one correction of what it
found there. With ``β`` up to 2 the correction can OVERSHOOT (``I − β k kᵀ`` has
an eigenvalue in (−1, 1) along a unit key): nothing here assumes a state that
only shrinks. The causal conv and its tail are ``ops/ssm.py``'s (``conv_step`` /
``conv_chunk``); the rule for pads is its too, in this update's terms: a
position that is no real token has ``g = 0`` and ``β = 0`` (the caller forces
both), so ``S' = S`` and the correction is ``0 · k uᵀ``: the state stays EXACTLY
what it was. float32 throughout, products that feed the state at ``highest``.

**One position a row** (``kda_step``): the four lines as written. The time
scan of it (``kda_scan``) is the tests' oracle for the chunk form.

**One position of a slot's LIVE rows, where the state lies**
(``kda_step_rows``: the paged decode step) takes the whole carried ``[L_kda,
rows, heads, d_k, d_v]`` array and advances the live rows of one layer's slot
inside it; ``backend`` as ``ops/ssm.ssm_step_rows``'s. ``kernel`` is
``kda_rows_tpu``, ONE Pallas call a layer: grid ``(live rows, head tiles)``
with a traced first extent, a tile's ``[heads, d_k, d_v]`` block read once,
decayed, read against ``k``, corrected, read out against ``q`` and written
once over itself (the output aliases the input: a block the grid does not
visit — a dead row's 4 MB — is neither read nor written). Inside it ``α``,
``k`` and ``q`` meet a head's slab as COLUMNS (a value a key channel, on
sublanes) and ``v``, ``u`` and ``o`` as ROWS (a value a value channel, on
lanes), so both reductions run over sublanes and nothing is transposed but
the three ``[heads, d_k]`` operands, once a step. ``xla`` is a ``fori_loop``
over the live rows through ``kda_step`` (the CPU path and the kernel's
reference), ``interpret`` the kernel emulated.

**A chunk of positions** (``kda_chunk``: chunked prefill) is the chunkwise WY /
UT form over chunks of ``CHUNK`` = 64 positions, the row's stored state the
carry in and out. With ``G_t = Σ_{s<=t} g_s`` inside a chunk (per key channel,
<= 0) and ``S_0`` the state entering it, the pseudo-values ``w_s = β_s u_s``
solve a unit lower-triangular system::

    (I + Diag(β) A) W = Diag(β) (V − K̃ S_0)
    A[s, r] = Σ_c k_s[c] k_r[c] exp(G_s[c] − G_r[c])   (r < s),  K̃_s = k_s ⊙ exp(G_s)

so ``W = U − W_k S_0`` with ``[U | W_k] = (I + Diag(β) A)^-1 Diag(β) [V | K̃]``
made for every chunk AT ONCE (one triangular solve a head and chunk), and only
``W``, the read-out ``O = Q̃ S_0 + B W`` (``B[t, s] = Σ_c q_t[c] k_s[c] exp(G_t[c]
− G_s[c])``, ``s <= t``) and ``S_C = Diag(exp(G_C)) S_0 + K̂ᵀ W`` (``K̂_s = k_s ⊙
exp(G_C − G_s)``) run chunk after chunk — matrix products inside a chunk, the
recurrence only from chunk to chunk. No token-by-token scan.

**Every exponent formed is <= 0.** ``A`` and ``B`` as ONE product ``(k ⊙ e^G)(k ⊙
e^-G)ᵀ`` would need ``exp(−G)`` of a whole chunk, and the gate has no lower
bound (``g = −exp(A_log) · softplus(…)``): no split may lean on one. A chunk is
cut into sub-chunks of ``SUB`` = 16. A pair in DIFFERENT sub-chunks is split at
the later sub-chunk's first position ``r``: ``exp(G_t − G_r) · exp(G_r − G_s)``,
``s < r <= t``, both factors at most 1, each side a matrix product. A pair
INSIDE a sub-chunk takes ``exp(G_t − G_s)`` for ``s <= t`` directly, a channel
at a time (16 x 16 x ``d_k`` values a head and sub-chunk, summed over the
channels: no product to split). A factor that underflows to 0 is then the true
value to float32, and nothing overflows whatever the gate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssm import _STATE_BLOCK_BYTES, _resolve, _visited

_HI = jax.lax.Precision.HIGHEST
f32 = jnp.float32

#: positions of a chunk of the WY form, and of the sub-chunks its decays are
#: split at
CHUNK = 64
SUB = 16


# ------------------------------------------------------------ one position

def kda_step(state, q, k, v, log_a, beta):
    """One position a row. ``state [B, nh, dk, dv]`` f32, ``q`` (scaled),
    ``k``, ``log_a [B, nh, dk]`` (``g`` <= 0; 0 for a row that must not advance),
    ``v [B, nh, dv]``, ``beta [B, nh]`` (0 likewise) → ``(o [B, nh, dv] f32,
    state)``."""
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    s = state * jnp.exp(log_a.astype(f32))[..., None]
    u = v - jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HI)
    s = s + (beta.astype(f32)[..., None] * k)[..., None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI), s


def kda_scan(state, q, k, v, log_a, beta):
    """``S`` positions as ``lax.scan`` over ``kda_step``, the state the
    carry: ``q``, ``k``, ``log_a [B, S, nh, dk]``, ``v [B, S, nh, dv]``, ``beta
    [B, S, nh]`` → ``(o [B, S, nh, dv] f32, state)``. The oracle of
    ``kda_chunk``."""
    def step(s, t):
        o, s = kda_step(s, *t)
        return s, o

    state, o = jax.lax.scan(
        step, state,
        tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, log_a, beta)),
    )
    return jnp.swapaxes(o, 0, 1), state


# ------------------------------------------- the live rows, where the state lies

def head_tile(nh: int, dk: int, dv: int) -> int:
    """Heads a grid step of ``kda_rows_tpu`` advances: the most that divide
    the heads evenly, are whole sublane tiles (8) and whose float32 block
    stays within ``ops/ssm._STATE_BLOCK_BYTES`` (what a grid step holds one
    way: PR 44's finding for ``ssm_rows_tpu``) — at the published widths (64
    heads of 128 x 128) 16 heads, 1 MiB a block, four steps a row. All the
    heads where nothing fits (a tiny model)."""
    fits = [
        t for t in range(8, nh + 1, 8)
        if nh % t == 0 and t * dk * dv * 4 <= _STATE_BLOCK_BYTES
    ]
    return max(fits, default=nh)


def kernel_eligible(nh: int, dk: int, dv: int) -> bool:
    """Whether Mosaic tiles a head's ``[d_k, d_v]`` float32 slab as it lies
    and a step's ``[heads of the tile, d_k]`` operands: whole (8, 128) tiles."""
    return (
        dk % 128 == 0 and dv % 128 == 0 and nh % 8 == 0
        and head_tile(nh, dk, dv) % 8 == 0
    )


def _rows_kernel(lyr, row0, order, nlive, beta, s_ref, q_ref, k_ref, a_ref,
                 v_ref, so_ref, o_ref, qt_ref, kt_ref, at_ref):
    """One live row's head tile: ``s_ref`` / ``so_ref [ht, dk, dv]`` the state
    block in and out, ``q_ref``, ``k_ref``, ``a_ref [ht, dk]`` the tile's
    query, key and decay ``α``, ``v_ref`` / ``o_ref [ht, dv]`` its values and
    read-out, ``beta [B, heads]`` in scalar memory. ``q``, ``k`` and ``α`` meet
    a head's slab a value a ROW of it, so they pass through ``[dk, ht]``
    scratch — the key channel on sublanes, a column a head — transposed once a
    step; ``v``, ``u`` and ``o`` are rows of lanes as they come."""
    i, j = pl.program_id(0), pl.program_id(1)
    ht = s_ref.shape[0]

    @pl.when(i < nlive[0])
    def _advance():
        b = order[i]
        qt_ref[...] = q_ref[...].T
        kt_ref[...] = k_ref[...].T
        at_ref[...] = a_ref[...].T
        for h in range(ht):
            kc = kt_ref[:, h:h + 1]  # [dk, 1]
            s = s_ref[h] * at_ref[:, h:h + 1]  # S' = Diag(α) S
            u = v_ref[h:h + 1, :] - jnp.sum(s * kc, axis=0, keepdims=True)
            s = s + kc * (u * beta[b, j * ht + h])
            so_ref[h] = s
            o_ref[h:h + 1, :] = jnp.sum(
                s * qt_ref[:, h:h + 1], axis=0, keepdims=True
            )

    @pl.when(i >= nlive[0])
    def _none_live():
        # the ONE step of a grid with no live row: its block goes back as
        # it came
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_rows_tpu(s_all, at, order, n_live, q, k, v, a, beta, *,
                 interpret: bool = False):
    """The Pallas state update of a slot's live rows in the carried array:
    grid ``(max(n_live, 1), head tiles)``, the first extent TRACED; step ``(i,
    j)`` holds head tile ``j`` of row ``row0 + order[i]`` of layer ``layer``
    (``at``, ``order``, ``n_live`` and ``beta`` scalar-prefetched). With no
    live row the grid is ONE step that writes one block of the first row back
    as it was read. ``q``, ``k``, ``a [B, nh, dk]`` (``a = exp(log α)``), ``v
    [B, nh, dv]``, ``beta [B, nh]`` → ``(o [B, nh, dv]`` — rows before
    ``n_live`` in ``order`` WRITTEN, the others not —, ``s_all)``."""
    _, _, nh, dk, dv = s_all.shape
    B = q.shape[0]
    ht = head_tile(nh, dk, dv)
    n = jnp.reshape(n_live, (1,)).astype(jnp.int32)

    def row(i, order):
        return order[jnp.minimum(i, B - 1)]

    def state_map(i, j, lyr, row0, order, nl, beta):
        return (lyr[0], row0[0] + row(i, order), j, 0, 0)

    def heads_map(i, j, lyr, row0, order, nl, beta):
        return (row(i, order), j, 0)

    state = pl.BlockSpec((None, None, ht, dk, dv), state_map)
    keys = pl.BlockSpec((None, ht, dk), heads_map)
    values = pl.BlockSpec((None, ht, dv), heads_map)
    s_all, o = pl.pallas_call(
        _rows_kernel,
        out_shape=[
            jax.ShapeDtypeStruct(s_all.shape, f32),
            jax.ShapeDtypeStruct((B, nh, dv), f32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(jnp.maximum(n[0], 1), jnp.where(n[0] > 0, nh // ht, 1)),
            in_specs=[state, keys, keys, keys, values],
            out_specs=[state, values],
            scratch_shapes=[pltpu.VMEM((dk, ht), f32)] * 3,
        ),
        input_output_aliases={5: 0},  # the carried state, over itself
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="kda_rows",
    )(
        *(jnp.reshape(x, (1,)).astype(jnp.int32) for x in at),
        order.astype(jnp.int32), n, beta.astype(f32), s_all, q, k, a, v,
    )
    return o, s_all


def kda_step_rows(s_all, at, order, n_live, q, k, v, log_a, beta,
                  backend: str = "auto"):
    """One position of a slot's LIVE rows with the state advanced WHERE IT
    LIES: ``s_all [L_kda, rows, nh, dk, dv]`` the whole carried state, ``at =
    (layer, first row of the slot)``, ``order [B]`` the slot's rows with the
    live ones first and ``n_live`` their count; the operands as ``kda_step``'s
    → ``(o [B, nh, dv]`` f32 — ZERO for a row that is not live —, ``s_all)``.
    A row that is not live is neither read nor written (module docstring: the
    backends)."""
    _, _, nh, dk, dv = s_all.shape
    backend = _resolve(backend, kernel_eligible(nh, dk, dv))
    if backend == "xla":
        l, row0 = at

        def advance(i, carry):
            # ONE live row: its state sliced out of the carried array,
            # advanced, written back (``ops/ssm.ssm_step_rows``'s loop)
            s_all, o_all = carry
            b = order[i]
            where = (l, row0 + b, 0, 0, 0)
            s = jax.lax.dynamic_slice(
                s_all, where, (1, 1, *s_all.shape[2:])
            )[0]

            def row(x):
                return jax.lax.dynamic_slice_in_dim(x, b, 1, axis=0)

            o, s = kda_step(
                s, row(q), row(k), row(v), row(log_a), row(beta)
            )
            return (
                jax.lax.dynamic_update_slice(s_all, s[None], where),
                jax.lax.dynamic_update_slice_in_dim(o_all, o, b, axis=0),
            )

        s_all, o = jax.lax.fori_loop(
            0, n_live, advance, (s_all, jnp.zeros(v.shape, f32))
        )
        return o, s_all
    o, s_all = kda_rows_tpu(
        s_all, at, order, n_live, q.astype(f32), k.astype(f32),
        v.astype(f32), jnp.exp(log_a.astype(f32)), beta,
        interpret=backend == "interpret",
    )
    live = _visited(order, n_live)
    return jnp.where(live[:, None, None], o, 0.0), s_all


# ------------------------------------------------------------------- a chunk

def _pairs(x, y, G, inclusive: bool):
    """``M[t, s] = Σ_c x_t[c] y_s[c] exp(G_t[c] − G_s[c])`` for ``s < t`` (``s
    <= t`` with ``inclusive``), 0 elsewhere — over one chunk of ``n`` sub-chunks
    of ``SUB`` positions: ``x``, ``y``, ``G [..., n, SUB, dk]`` (``G`` the
    inclusive cumulative gate of the CHUNK, decreasing) → ``[..., n·SUB,
    n·SUB]``. Every exponent is <= 0 (module docstring)."""
    n, dk = G.shape[-3], G.shape[-1]
    C = n * SUB
    # a pair inside a sub-chunk: exp(G_t − G_s) itself, a channel at a time
    at = jnp.arange(SUB)
    keep = at[:, None] >= at[None, :] if inclusive else at[:, None] > at[None, :]
    gap = jnp.minimum(G[..., :, None, :] - G[..., None, :, :], 0.0)
    inside = jnp.sum(
        jnp.where(
            keep[..., None],
            x[..., :, None, :] * y[..., None, :, :] * jnp.exp(gap), 0.0,
        ),
        axis=-1,
    )  # [..., n, SUB, SUB]
    # sub-chunk i against the positions of EARLIER sub-chunks, split at i's
    # first position: exp(G_t − G_first_i) <= 1, exp(G_first_i − G_s) <= 1
    first = G[..., 0, :]  # [..., n, dk]
    up = jnp.exp(G - first[..., None, :])
    flat = (*G.shape[:-3], C, dk)
    down = jnp.exp(jnp.minimum(
        first[..., :, None, :] - G.reshape(flat)[..., None, :, :], 0.0
    ))  # [..., n, C, dk]
    earlier = (jnp.arange(C)[None, :] // SUB) < jnp.arange(n)[:, None]
    down = jnp.where(earlier[..., None], down, 0.0)
    across = jnp.einsum(
        "...itc,...isc->...its", x * up,
        y.reshape(flat)[..., None, :, :] * down, precision=_HI,
    )  # [..., n, SUB, C]
    eye = jnp.eye(n, dtype=f32)
    full = across.reshape(*across.shape[:-1], n, SUB) + (
        inside[..., :, :, None, :] * eye[:, None, :, None]
    )
    return full.reshape(*full.shape[:-4], C, C)


def kda_chunk(state, q, k, v, log_a, beta):
    """A chunk in the chunkwise WY form. ``state [B, nh, dk, dv]`` f32 (the
    carry in), ``q`` (scaled), ``k``, ``log_a [B, S, nh, dk]`` and ``beta [B,
    S, nh]`` (``log_a`` and ``beta`` 0 at every position that is no real
    token), ``v [B, S, nh, dv]`` → ``(o [B, S, nh, dv] f32, state)``. ``S`` is
    padded up to whole chunks with such positions."""
    Bn, S, nh, dk = k.shape
    dv = v.shape[-1]
    pad = -S % CHUNK
    if pad:
        def padded(x):
            return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

        q, k, v, log_a, beta = map(padded, (q, k, v, log_a, beta))
    nc, n = (S + pad) // CHUNK, CHUNK // SUB

    def heads_first(x):  # [B, S', nh, d] -> [B, nh, nc, CHUNK, d]
        x = x.astype(f32).reshape(Bn, nc, CHUNK, nh, -1)
        return jnp.transpose(x, (0, 3, 1, 2, 4))

    q, k, v, log_a = map(heads_first, (q, k, v, log_a))
    beta = heads_first(beta[..., None])  # [B, nh, nc, CHUNK, 1]
    G = jnp.cumsum(log_a, axis=3)  # inside a chunk, inclusive

    def subs(x):
        return x.reshape(Bn, nh, nc, n, SUB, dk)

    A = _pairs(subs(k), subs(k), subs(G), inclusive=False)  # [B,nh,nc,C,C]
    Bm = _pairs(subs(q), subs(k), subs(G), inclusive=True)
    # [U | W_k] = (I + Diag(β) A)^-1 Diag(β) [V | K̃], every chunk at once
    system = jnp.eye(CHUNK, dtype=f32) + beta * A
    total = G[..., -1:, :]  # [B, nh, nc, 1, dk] a chunk's whole log-decay
    rhs = beta * jnp.concatenate([v, k * jnp.exp(G)], axis=-1)
    solved = jax.lax.linalg.triangular_solve(
        system, rhs, left_side=True, lower=True, unit_diagonal=True
    )
    U, Wk = solved[..., :dv], solved[..., dv:]
    q_in = q * jnp.exp(G)  # Q̃: what a query reads of the entering state
    k_out = k * jnp.exp(total - G)  # K̂: a key's write, decayed to the end
    through = jnp.exp(total)[..., 0, :, None]  # the entering state's decay

    def chunk(s, c):
        U_c, Wk_c, B_c, q_c, k_c, through_c = c
        w = U_c - jnp.einsum("bhtk,bhkv->bhtv", Wk_c, s, precision=_HI)
        o = jnp.einsum("bhtk,bhkv->bhtv", q_c, s, precision=_HI) + jnp.einsum(
            "bhts,bhsv->bhtv", B_c, w, precision=_HI
        )
        s = s * through_c + jnp.einsum(
            "bhtk,bhtv->bhkv", k_c, w, precision=_HI
        )
        return s, o

    state, o = jax.lax.scan(
        chunk, state.astype(f32),
        tuple(
            jnp.moveaxis(x, 2, 0) for x in (U, Wk, Bm, q_in, k_out, through)
        ),
    )
    # [nc, B, nh, CHUNK, dv] -> [B, S, nh, dv]
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(Bn, nc * CHUNK, nh, dv)
    return o[:, :S], state
