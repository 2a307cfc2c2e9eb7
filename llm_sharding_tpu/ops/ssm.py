"""State-space operations of a Mamba-2 mixer (``models/nemotron_h.py``): the
causal depthwise conv with its tail, the selective state update in its two
forms, and the gated grouped norm. float32 throughout: a request's recurrent
state is a float32 array of FIXED size (``[heads, head_dim, state]`` a layer,
beside the conv's last ``K - 1`` inputs) that thousands of decode steps
multiply through, so nothing here rounds it to a narrower type. Everything
is plain XLA but ONE Pallas kernel, the paged decode step's state update
(``ssm_step_rows``, below).

The recurrence, per head ``h`` (``A_h < 0`` a scalar, ``D_h`` a skip gain;
``B_t``, ``C_t [state]`` shared by the heads of a group)::

    S_t = exp(dt_t A) S_{t-1} + dt_t (x_t ⊗ B_t)        S [head_dim, state]
    y_t = S_t C_t + D x_t

**One position a row** (``ssm_step``, ``conv_step``: a decode step) is that
line as written — elementwise over the state, which is read and written once.
``ssm_step`` takes the rows' states as an array of their own: the unpaged
forward (``mamba_block``'s ``S == 1`` branch, the tests' oracle) calls it.

**One position of a slot's LIVE rows, where the state lies**
(``ssm_step_rows``: the paged decode step, ``mamba_decode_rows``) takes the
whole carried ``[L_mamba, rows, heads, head_dim, state]`` array and advances
the live rows of one layer's slot inside it. ``backend`` follows
``ops/moe.resolve_backend`` (``auto`` | ``kernel`` | ``xla`` | ``interpret``):
``kernel`` is ``ssm_rows_tpu``, ONE Pallas call whose grid is ``(live rows,
head tiles)`` with a traced first extent — each live row's state is read
once, advanced and read out in the same pass, and written once over itself
(the output aliases the input: a block the grid does not visit is what it
was); ``xla`` is a ``fori_loop`` over the live rows through ``ssm_step`` (the
CPU path and the kernel's reference); ``interpret`` emulates the kernel (what
the tier-1 tests run). A shape Mosaic cannot tile (``kernel_eligible``) runs
``xla`` on the chip too, and ``rows_backend`` says which one a caller got.

**A chunk of positions** (``ssm_chunk``, ``conv_chunk``: chunked prefill) is
the BLOCK form over blocks of ``block`` positions (the published
``chunk_size``): with ``a_t = dt_t A`` and ``c_i = Σ_{t<=i} a_t`` inside a
block, the block's own positions see each other through one masked product
``y_i += Σ_{j<=i} exp(c_i - c_j) (C_i·B_j) dt_j x_j``, the state entering the
block adds ``exp(c_i) C_i·S_in``, and the state leaving it is ``exp(c_last)
S_in + Σ_j exp(c_last - c_j) dt_j x_j ⊗ B_j`` — matrix products inside a
block, the recurrence only from block to block, the row's stored state the
carry in and out. No token-by-token scan.

**Pads.** A position that is no real token has ``dt = 0`` (the caller forces
it): ``exp(0) = 1`` and ``0·(x ⊗ B) = 0``, so it leaves the state EXACTLY as
it was, and a right-padded chunk ends in the state of its last real token.
The conv's tail is taken at the row's last real position (``n_real``), not at
the chunk's end.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
f32 = jnp.float32


# ------------------------------------------------------------------- the conv

def conv_step(tail, x, w, b):
    """One position a row. ``tail [B, K-1, C]`` (the last inputs, oldest
    first), ``x [B, C]``, ``w [K, C]`` (tap ``k`` meets the input ``K-1-k``
    positions back), ``b [C]`` → ``(silu(conv) [B, C], new tail)``."""
    win = jnp.concatenate([tail, x[:, None].astype(f32)], axis=1)  # [B, K, C]
    y = jnp.sum(win * w.astype(f32)[None], axis=1) + b.astype(f32)
    return jax.nn.silu(y), win[:, 1:]


def conv_chunk(tail, x, n_real, w, b):
    """A chunk. ``x [B, S, C]``; the row's first ``n_real [B]`` positions are
    real. Returns ``(silu(conv) [B, S, C], new tail)``: the tail is the
    ``K - 1`` inputs that END at the row's last real position (the old tail's
    where the chunk holds fewer; unchanged where it holds none)."""
    K = w.shape[0]
    S = x.shape[1]
    xin = jnp.concatenate([tail, x.astype(f32)], axis=1)  # [B, S + K - 1, C]
    wf = w.astype(f32)
    y = b.astype(f32)
    for k in range(K):
        y = y + xin[:, k:k + S] * wf[k]
    at = n_real[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]  # [B, K-1]
    new_tail = jnp.take_along_axis(xin, at[:, :, None], axis=1)
    return jax.nn.silu(y), new_tail


# ------------------------------------------------------------ the state update

def ssm_step(state, x, dt, A, Bm, Cm, D):
    """One position a row. ``state [B, nh, hd, ds]`` f32, ``x [B, nh, hd]``,
    ``dt [B, nh]`` (after softplus; 0 for a row that must not advance), ``A``,
    ``D [nh]``, ``Bm``, ``Cm [B, g, ds]`` → ``(y [B, nh, hd] f32, state)``."""
    Bn, nh, hd, ds = state.shape
    g = Bm.shape[1]
    r = nh // g
    x, dt = x.astype(f32), dt.astype(f32)
    dA = jnp.exp(dt * A.astype(f32))  # [B, nh]
    s = state.reshape(Bn, g, r, hd, ds)
    xdt = (x * dt[..., None]).reshape(Bn, g, r, hd)
    s = s * dA.reshape(Bn, g, r, 1, 1) + (
        xdt[..., None] * Bm.astype(f32)[:, :, None, None, :]
    )
    y = jnp.sum(s * Cm.astype(f32)[:, :, None, None, :], axis=-1)  # [B,g,r,hd]
    y = y.reshape(Bn, nh, hd) + D.astype(f32)[None, :, None] * x
    return y, s.reshape(Bn, nh, hd, ds)


# ------------------------------------------- the live rows, where the state lies

#: float32 bytes of state one grid step of ``ssm_rows_tpu`` holds ONE way (in
#: VMEM four times: in and out, each double buffered). On the chip 1 MiB
#: blocks advance four live rows in 63.4 us where 512 KiB blocks take 68.9
#: (one row: 19.2 / 19.5) and 2 MiB blocks leave more of the first read and
#: the last write uncovered (``PERF.md`` §6, PR 44)
_STATE_BLOCK_BYTES = 1024 * 1024


def head_tile(nh: int, g: int, hd: int, ds: int) -> int:
    """Heads a grid step of ``ssm_rows_tpu`` advances: a whole number of
    groups' heads that divides the heads evenly, the most whose float32
    block stays within ``_STATE_BLOCK_BYTES`` (one group's at least). At
    the published widths (128 heads of 64 x 128 in 8 groups) TWO groups: 32
    heads, 1 MiB a block, four steps a row."""
    r = nh // g
    fits = [
        k for k in range(1, g + 1)
        if g % k == 0 and k * r * hd * ds * 4 <= _STATE_BLOCK_BYTES
    ]
    return r * max(fits, default=1)


def kernel_eligible(nh: int, g: int, hd: int, ds: int) -> bool:
    """Whether Mosaic tiles a head's ``[head_dim, state]`` float32 slab as it
    lies and a step's ``[heads of the tile, head_dim]`` operands: whole (8,
    128) tiles, the heads whole groups."""
    return (
        nh % g == 0 and hd % 8 == 0 and ds % 128 == 0
        and head_tile(nh, g, hd, ds) % 8 == 0
    )


def rows_backend(backend: str, nh: int, g: int, hd: int, ds: int) -> str:
    """The path ``ssm_step_rows`` takes for ``backend`` at these shapes:
    ``ops/moe.resolve_backend``'s answer, and ``xla`` where that is the
    compiled kernel and the shapes are not ``kernel_eligible``."""
    from .moe import resolve_backend

    backend = resolve_backend(backend)
    if backend == "kernel" and not kernel_eligible(nh, g, hd, ds):
        return "xla"
    return backend


def _rows_kernel(lyr, row0, order, nlive, da, s_ref, x_ref, b_ref, c_ref,
                 so_ref, y_ref, xt_ref, yt_ref, *, r):
    """One live row's head tile: ``s_ref`` / ``so_ref [ht, hd, ds]`` the
    state block in and out, ``x_ref [ht, hd]`` the tile's ``x dt``, ``b_ref``
    / ``c_ref [g, ds]`` the row's ``B`` and ``C``, ``da [B, heads]`` in scalar
    memory; ``y_ref [ht, hd]`` the read-out ``Σ_state S C``. A head's ``x dt``
    meets its ``[hd, ds]`` slab row for row and its read-out leaves the slab
    a value a row, so both pass through ``[hd, ht]`` scratch — the head value
    on SUBLANES, a column a head — transposed once a step."""
    i, j = pl.program_id(0), pl.program_id(1)
    ht = s_ref.shape[0]

    @pl.when(i < nlive[0])
    def _advance():
        b = order[i]
        xt_ref[...] = x_ref[...].T
        for k in range(ht // r):  # the tile's groups: B and C once each
            group = pl.ds(j * (ht // r) + k, 1)
            bv, cv = b_ref[group, :], c_ref[group, :]
            for h in range(k * r, (k + 1) * r):
                s = s_ref[h] * da[b, j * ht + h] + xt_ref[:, h:h + 1] * bv
                so_ref[h] = s
                yt_ref[:, h:h + 1] = jnp.sum(s * cv, axis=-1, keepdims=True)
        y_ref[...] = yt_ref[...].T

    @pl.when(i >= nlive[0])
    def _none_live():
        # the ONE step of a grid with no live row: its block goes back as
        # it came
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_rows_tpu(s_all, at, order, n_live, dA, xdt, Bm, Cm, *,
                 interpret: bool = False):
    """The Pallas state update of a slot's live rows in the carried array:
    grid ``(max(n_live, 1), head tiles)``, the first extent TRACED (as
    ``ops/moe.expert_tiles_tpu``'s); step ``(i, j)`` holds head tile ``j`` of
    row ``row0 + order[i]`` of layer ``layer`` (``at``, ``order`` and
    ``n_live`` scalar-prefetched; index maps are evaluated a step ahead, so
    they hold ``i`` inside ``order``). With no live row the grid is ONE step
    that writes one block of the first row back as it was read. ``dA [B,
    nh]``, ``xdt [B, nh, hd]``, ``Bm``, ``Cm [B, g, ds]`` → ``(y [B, nh, hd]``
    — rows before ``n_live`` in ``order`` WRITTEN, the others not —,
    ``s_all)``."""
    _, _, nh, hd, ds = s_all.shape
    B, g = Bm.shape[:2]
    ht = head_tile(nh, g, hd, ds)
    n = jnp.reshape(n_live, (1,)).astype(jnp.int32)

    def row(i, order):
        return order[jnp.minimum(i, B - 1)]

    def state_map(i, j, lyr, row0, order, nl, da):
        return (lyr[0], row0[0] + row(i, order), j, 0, 0)

    def heads_map(i, j, lyr, row0, order, nl, da):
        return (row(i, order), j, 0)

    def groups_map(i, j, lyr, row0, order, nl, da):
        return (row(i, order), 0, 0)

    state = pl.BlockSpec((None, None, ht, hd, ds), state_map)
    heads = pl.BlockSpec((None, ht, hd), heads_map)
    groups = pl.BlockSpec((None, g, ds), groups_map)
    s_all, y = pl.pallas_call(
        functools.partial(_rows_kernel, r=nh // g),
        out_shape=[
            jax.ShapeDtypeStruct(s_all.shape, f32),
            jax.ShapeDtypeStruct((B, nh, hd), f32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(jnp.maximum(n[0], 1), jnp.where(n[0] > 0, nh // ht, 1)),
            in_specs=[state, heads, groups, groups],
            out_specs=[state, heads],
            scratch_shapes=[pltpu.VMEM((hd, ht), f32)] * 2,
        ),
        input_output_aliases={5: 0},  # the carried state, over itself
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="ssm_rows",
    )(
        *(jnp.reshape(a, (1,)).astype(jnp.int32) for a in at),
        order.astype(jnp.int32), n, dA, s_all, xdt, Bm, Cm,
    )
    return y, s_all


def ssm_step_rows(s_all, at, order, n_live, x, dt, A, Bm, Cm, D,
                  backend: str = "auto"):
    """One position of a slot's LIVE rows with the state advanced WHERE IT
    LIES: ``s_all [L_mamba, rows, nh, hd, ds]`` the whole carried state, ``at
    = (layer, first row of the slot)``, ``order [B]`` the slot's rows with
    the live ones first and ``n_live`` their count; ``x [B, nh, hd]``, ``dt
    [B, nh]``, ``Bm``, ``Cm [B, g, ds]`` as ``ssm_step``'s → ``(y [B, nh, hd]``
    f32 — ZERO for a row that is not live —, ``s_all)``. A row that is not
    live is neither read nor written (module docstring: the backends)."""
    _, _, nh, hd, ds = s_all.shape
    B = x.shape[0]
    backend = rows_backend(backend, nh, Bm.shape[1], hd, ds)
    if backend == "xla":
        l, row0 = at

        def advance(i, carry):
            # ONE live row: its state sliced out of the carried array,
            # advanced, written back — a loop's carried buffer is updated
            # in place (a ``lax.cond`` a row copied the whole state)
            s_all, y_all = carry
            b = order[i]
            where = (l, row0 + b, 0, 0, 0)
            s = jax.lax.dynamic_slice(
                s_all, where, (1, 1, *s_all.shape[2:])
            )[0]

            def row(a):
                return jax.lax.dynamic_slice_in_dim(a, b, 1, axis=0)

            y, s = ssm_step(s, row(x), row(dt), A, row(Bm), row(Cm), D)
            return (
                jax.lax.dynamic_update_slice(s_all, s[None], where),
                jax.lax.dynamic_update_slice_in_dim(y_all, y, b, axis=0),
            )

        s_all, y = jax.lax.fori_loop(
            0, n_live, advance, (s_all, jnp.zeros(x.shape, f32))
        )
        return y, s_all
    x, dt = x.astype(f32), dt.astype(f32)
    y, s_all = ssm_rows_tpu(
        s_all, at, order, n_live, jnp.exp(dt * A.astype(f32)),
        x * dt[..., None], Bm.astype(f32), Cm.astype(f32),
        interpret=backend == "interpret",
    )
    # the rows the grid visited: the first ``n_live`` of ``order``
    at_i = jnp.arange(B, dtype=jnp.int32)
    live = jnp.any(
        (order[None, :] == at_i[:, None]) & (at_i[None, :] < n_live), axis=1
    )
    y = y + D.astype(f32)[None, :, None] * x
    return jnp.where(live[:, None, None], y, 0.0), s_all


def ssm_chunk(state, x, dt, A, Bm, Cm, D, block: int):
    """A chunk in block form. ``state [B, nh, hd, ds]`` f32 (the carry in),
    ``x [B, S, nh, hd]``, ``dt [B, S, nh]`` (0 at every position that is no
    real token), ``Bm``, ``Cm [B, S, g, ds]`` → ``(y [B, S, nh, hd] f32,
    state)``. ``S`` is padded up to whole blocks with ``dt = 0``."""
    Bn, S, nh, hd = x.shape
    g, ds = Bm.shape[2], Bm.shape[3]
    r = nh // g
    pad = -S % block
    if pad:
        def padded(a):
            return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

        x, dt, Bm, Cm = padded(x), padded(dt), padded(Bm), padded(Cm)
    nc, Q = (S + pad) // block, block
    x = x.astype(f32).reshape(Bn, nc, Q, g, r, hd)
    dt = dt.astype(f32).reshape(Bn, nc, Q, g, r)
    Bm = Bm.astype(f32).reshape(Bn, nc, Q, g, ds)
    Cm = Cm.astype(f32).reshape(Bn, nc, Q, g, ds)
    a = dt * A.astype(f32).reshape(g, r)  # [B, nc, Q, g, r]  (<= 0)
    cum = jnp.cumsum(a, axis=2)
    xdt = x * dt[..., None]
    # inside a block: position i sees j <= i through exp(c_i - c_j) (C_i·B_j)
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cm, Bm, precision=_HI)
    diff = cum[:, :, :, None] - cum[:, :, None]  # [B, nc, i, j, g, r]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    y = jnp.einsum(
        "bcgij,bcijgr,bcjgrp->bcigrp", scores, decay, xdt, precision=_HI
    )
    # what each block adds to the state that leaves it
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, nc, Q, g, r]
    added = jnp.einsum(
        "bcjgr,bcjgrp,bcjgn->bcgrpn", to_end, xdt, Bm, precision=_HI
    )
    total = jnp.exp(cum[:, :, -1])  # [B, nc, g, r] a block's whole decay
    from_in = jnp.exp(cum)  # [B, nc, Q, g, r]
    s = state.reshape(Bn, g, r, hd, ds)
    y_in = []
    for c in range(nc):  # the recurrence, block to block
        y_in.append(jnp.einsum(
            "bign,bgrpn->bigrp", Cm[:, c], s, precision=_HI
        ) * from_in[:, c][..., None])
        s = s * total[:, c][..., None, None] + added[:, c]
    y = y + jnp.stack(y_in, axis=1)
    y = y + D.astype(f32).reshape(g, r)[..., None] * x
    y = y.reshape(Bn, nc * Q, nh, hd)[:, :S]
    return y, s.reshape(Bn, nh, hd, ds)


def gated_group_norm(y, z, gain, groups: int, eps: float):
    """The mixer's output norm, gate FIRST: ``RMSNorm_per_group(y ·
    silu(z)) · gain`` over ``groups`` groups of the last dim, in float32."""
    v = y.astype(f32) * jax.nn.silu(z.astype(f32))
    shape = v.shape
    v = v.reshape(*shape[:-1], groups, shape[-1] // groups)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return v.reshape(shape) * gain.astype(f32)
