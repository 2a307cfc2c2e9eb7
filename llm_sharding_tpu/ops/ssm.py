"""State-space operations of a model with a recurrent state: the causal
depthwise conv with its tail, the selective state update of BOTH Mamba
families in its two forms each, and Mamba-2's gated grouped norm. float32
throughout: a request's recurrent state is a float32 array of FIXED size (a
layer's state beside the conv's last ``K - 1`` inputs) that thousands of
decode steps multiply through, so nothing here rounds it to a narrower type.
Everything is plain XLA but THREE Pallas kernels: Mamba-2's paged decode update
(``ssm_rows_tpu``), Mamba-1's scan in time (``scan_rows_tpu``: a prefill
chunk, and the state update of a decode step in its split form) and
Mamba-1's whole mixer step between its projections (``mixer_step_tpu``: a
decode step in its fused form). The families share ``conv_step`` /
``conv_chunk``, the rule for pads, and ONE entry for the decode step's state
update (``ssm_step_rows``) with ONE question a server asks of it
(``rows_backend``); they share NO logic past that — Mamba-1's fused step
(``mixer_step_rows``, ``mixer_step_path``) is its own.

**Mamba-2** (``models/nemotron_h.py``): the recurrence, per head ``h`` (``A_h <
0`` a scalar, ``D_h`` a skip gain; ``B_t``, ``C_t [state]`` shared by the heads
of a group)::

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

**Mamba-1** (``models/jamba.py``): the decay differs per channel ``c`` AND
per state value ``n`` (``A [state, channels] < 0``; ``dt_t`` a step a CHANNEL;
``B_t``, ``C_t [state]`` shared by all channels)::

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = (Σ_n S_t[n, c] C_t[n] + D[c] x_t[c]) · silu(z_t[c])

With a decay per ``(n, c)`` no block form exists (the masked product would
cost ``S² · channels · state`` and be no matrix product): a chunk is a SCAN IN
TIME, sequential over positions and parallel over ``channels × state``.
``scan_rows`` is both forms — ``S`` positions of a slot's LIVE rows advanced
where the state lies, ``S = 1`` a decode step (through ``ssm_step_rows``),
``S`` a chunk's positions in a prefill chunk. ``kernel`` is ``scan_rows_tpu``,
ONE Pallas call a layer: grid ``(live rows, channel tiles, position blocks)``,
a tile's state ``[state, 8, 128]`` resident for the row's whole chunk while
the positions loop INSIDE the kernel, ``x``, ``dt``, ``z`` read once, ``B`` and
``C`` as scalars, ``y`` written once, the stored state the carry in and out
(aliased) — nothing of shape ``[positions, channels, state]`` reaches HBM.
``xla`` is ``lax.scan`` over positions with the state as the carry (the CPU
path and the kernel's oracle), ``interpret`` the kernel emulated. The state
lies as ``[state, 8, channels / 8]`` (``ModelConfig.recurrent_shapes``).

**A decode step of a Mamba-1 mixer, fused** (``mixer_step_rows``): what lies
between ``w_in`` and ``w_out`` — the conv step over the row's tail, ``[δ | B |
C] = x w_x``, the three norms, ``dt = softplus(δ w_dt + b_dt)``, the update
and the gated read-out — is latency, not bytes or arithmetic, when it is
fifteen XLA operations a layer (a ``[1, 5120] x [5120, 192]`` product, 82 K
state values); ``mixer_step_tpu`` is ONE Pallas call: the carried state AND
the carried conv tails aliased over themselves, the stack's leaves read
through a scalar-prefetched layer index (a per-layer slice of a scanned stack
handed to a Pallas call is copied first: ``models/stack.MAMBA1_WHOLE_KEYS``),
grid ``(live rows, 2 x channel tiles)`` — ``w_x`` reduces over ALL channels,
so a row runs PHASE 1 over every tile (conv, the shifted tail written back,
the product accumulated, the conv's output kept in VMEM) before PHASE 2 runs
over them (norms once, then ``w_dt``, softplus, update and read-out a tile).
Phase 2 works on ``[1, di / 8]`` rows — one sublane of the state ``[state, 8,
di / 8]`` at a time — because both products make channels along LANES. A row
that is not live is neither read nor written, its state and tail bit for
bit what they were. ``mixer_step_path`` says whether a step takes it
(``fused``) or the operations above (``split``): the backend, the shapes
(``mixer_eligible``), plain ``w_x`` / ``w_dt`` — nothing else chooses.

**Pads.** A position that is no real token has ``dt = 0`` (the caller forces
it): ``exp(0) = 1`` and ``0·(x ⊗ B) = 0``, so it leaves the state EXACTLY as
it was, and a right-padded chunk ends in the state of its last real token.
The conv's tail is taken at the row's last real position (``n_real``), not at
the chunk's end.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
f32 = jnp.float32


# ------------------------------------------------------------------- the conv

def conv_step(tail, x, w, b=None):
    """One position a row. ``tail [B, K-1, C]`` (the last inputs, oldest
    first), ``x [B, C]``, ``w [K, C]`` (tap ``k`` meets the input ``K-1-k``
    positions back), ``b [C]`` (None: a conv without a bias, KDA's) →
    ``(silu(conv) [B, C], new tail)``."""
    win = jnp.concatenate([tail, x[:, None].astype(f32)], axis=1)  # [B, K, C]
    y = jnp.sum(win * w.astype(f32)[None], axis=1)
    if b is not None:
        y = y + b.astype(f32)
    return jax.nn.silu(y), win[:, 1:]


def conv_chunk(tail, x, n_real, w, b=None):
    """A chunk. ``x [B, S, C]``; the row's first ``n_real [B]`` positions are
    real. Returns ``(silu(conv) [B, S, C], new tail)``: the tail is the
    ``K - 1`` inputs that END at the row's last real position (the old tail's
    where the chunk holds fewer; unchanged where it holds none)."""
    K = w.shape[0]
    S = x.shape[1]
    xin = jnp.concatenate([tail, x.astype(f32)], axis=1)  # [B, S + K - 1, C]
    wf = w.astype(f32)
    y = 0.0 if b is None else b.astype(f32)
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


def _resolve(backend: str, eligible: bool) -> str:
    """``ops/moe.resolve_backend``'s answer, and ``xla`` where that is the
    compiled kernel and the shapes are not ``eligible``."""
    from .moe import resolve_backend

    backend = resolve_backend(backend)
    return "xla" if backend == "kernel" and not eligible else backend


def rows_backend(backend: str, cfg) -> str:
    """The path the state update of ``cfg``'s mixers takes for ``backend``
    (``ssm_step_rows``; a Mamba-1 chunk's ``scan_rows`` too): ``kernel``,
    ``interpret`` or ``xla`` — the one question a server asks, whatever the
    family, from ``cfg.recurrent_shapes`` (a KDA mixer's matrix state:
    ``ops/kda.py``'s kernel)."""
    if "kda" in cfg.recurrent_shapes:
        from . import kda

        return _resolve(
            backend, kda.kernel_eligible(*cfg.recurrent_shapes["kda"])
        )
    shape = cfg.recurrent_shapes["ssm"]
    if cfg.ssm_dt_rank:
        return _resolve(backend, scan_eligible(*shape))
    nh, hd, ds = shape
    return _resolve(backend, kernel_eligible(nh, cfg.ssm_groups, hd, ds))


def scan_path(backend: str, cfg) -> str:
    """The path a prefill chunk's scan of ``cfg``'s mixers takes: ``block``
    (matrix products inside blocks of positions: Mamba-2's block form,
    ``ssm_chunk``; KDA's chunkwise WY form, ``ops/kda.kda_chunk``) or,
    Mamba-1, ``rows_backend``'s answer (``scan_rows``)."""
    return rows_backend(backend, cfg) if cfg.ssm_dt_rank else "block"


def _visited(order, n_live):
    """``[B]`` bool: the rows a kernel's grid visited — the first ``n_live``
    of ``order``."""
    at = jnp.arange(order.shape[0], dtype=jnp.int32)
    return jnp.any(
        (order[None, :] == at[:, None]) & (at[None, :] < n_live), axis=1
    )


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
                  backend: str = "auto", z=None):
    """One position of a slot's LIVE rows with the state advanced WHERE IT
    LIES: ``s_all [L_mamba, rows, ...]`` the whole carried state, ``at =
    (layer, first row of the slot)``, ``order [B]`` the slot's rows with the
    live ones first and ``n_live`` their count. Mamba-2 (``A [nh]``): ``s_all
    [.., nh, hd, ds]``, ``x [B, nh, hd]``, ``dt [B, nh]``, ``Bm``, ``Cm [B, g,
    ds]`` as ``ssm_step``'s → ``(y [B, nh, hd]`` f32 — ZERO for a row that is
    not live —, ``s_all)``. Mamba-1 (``A [ds, di]``: a decay per channel and
    state value): ``s_all [.., ds, 8, di / 8]``, ``x``, ``dt``, the gate ``z
    [B, di]``, ``Bm``, ``Cm [B, ds]`` → ``y [B, di]``, gated (``scan_rows`` at
    ONE position). A row that is not live is neither read nor written (module
    docstring: the backends)."""
    if A.ndim == 2:
        y, s_all = scan_rows(
            s_all, at, order, n_live, x[:, None], dt[:, None], z[:, None], A,
            Bm[:, None], Cm[:, None], D, backend=backend,
        )
        return y[:, 0], s_all
    _, _, nh, hd, ds = s_all.shape
    backend = _resolve(backend, kernel_eligible(nh, Bm.shape[1], hd, ds))
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
    live = _visited(order, n_live)
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


# ------------------------------------------------- Mamba-1: the scan in time

#: positions a grid step of ``scan_rows_tpu`` holds of ``x``, ``dt``, ``z``
#: and ``y`` (512 KiB each at a tile of 8 x 128 channels)
_SCAN_POSITIONS = 128


def scan_eligible(ds: int, sub: int, lanes: int) -> bool:
    """Whether a state value's channels ``[8, channels / 8]`` split into
    whole (8, 128) tiles, as ``scan_rows_tpu`` advances them."""
    return sub == 8 and lanes % 128 == 0


def scan_step(state, x, dt, z, A, Bm, Cm, D):
    """One position a row, as the equations. ``state [B, ds, di]`` f32, ``x``,
    ``dt`` (after softplus; 0 for a row that must not advance), ``z [B, di]``,
    ``A [ds, di]``, ``Bm``, ``Cm [B, ds]``, ``D [di]`` → ``(y [B, di]`` f32,
    gated, ``state)``."""
    x, dt, z = x.astype(f32), dt.astype(f32), z.astype(f32)
    Bm, Cm = Bm.astype(f32), Cm.astype(f32)
    dA = jnp.exp(dt[:, None, :] * A.astype(f32)[None])  # [B, ds, di]
    state = state * dA + (dt * x)[:, None, :] * Bm[:, :, None]
    y = jnp.sum(state * Cm[:, :, None], axis=1) + D.astype(f32) * x
    return y * jax.nn.silu(z), state


def scan_chunk(state, x, dt, z, A, Bm, Cm, D):
    """A chunk as ``lax.scan`` over its positions, the state the carry:
    ``state [B, ds, di]``, ``x``, ``dt``, ``z [B, S, di]``, ``Bm``, ``Cm [B, S,
    ds]`` → ``(y [B, S, di] f32, state)``. The XLA twin of ``scan_rows_tpu``."""
    def step(s, t):
        xt, dtt, zt, bt, ct = t
        y, s = scan_step(s, xt, dtt, zt, A, bt, ct, D)
        return s, y

    state, y = jax.lax.scan(
        step, state, tuple(jnp.swapaxes(a, 0, 1) for a in (x, dt, z, Bm, Cm))
    )
    return jnp.swapaxes(y, 0, 1), state


def _scan_kernel(lyr, row0, order, nlive, b_ref, c_ref, s_ref, x_ref, dt_ref,
                 z_ref, a_ref, d_ref, so_ref, y_ref, *, S):
    """One live row's channel tile over one block of positions: ``s_ref`` /
    ``so_ref [ds, 8, 128]`` the tile's state in and out (``so_ref`` stays
    resident over the row's position blocks), ``x_ref``, ``dt_ref``, ``z_ref``,
    ``y_ref [P, 8, 128]``, ``a_ref [ds, 8, 128]``, ``d_ref [8, 128]``; ``b_ref``,
    ``c_ref [B * S * ds]`` in scalar memory (row, position, state value): a
    state value's ``B`` and ``C`` meet its ``[8, 128]`` channels as scalars.
    The positions loop in here, the state in registers."""
    i, k = pl.program_id(0), pl.program_id(2)
    ds, P = s_ref.shape[0], x_ref.shape[0]

    @pl.when(i < nlive[0])
    def _advance():
        @pl.when(k == 0)
        def _first_block():
            so_ref[...] = s_ref[...]

        A = [a_ref[n] for n in range(ds)]
        D = d_ref[...]
        first = (order[i] * S + k * P) * ds  # the block's first scalar

        def position(t, s):
            x, dt, z = x_ref[t], dt_ref[t], z_ref[t]
            xdt = x * dt
            y = D * x
            new = []
            for n in range(ds):
                at = first + t * ds + n
                sn = s[n] * jnp.exp(dt * A[n]) + xdt * b_ref[at]
                y = y + sn * c_ref[at]
                new.append(sn)
            y_ref[t] = y * z * jax.nn.sigmoid(z)
            return tuple(new)

        s = jax.lax.fori_loop(
            0, P, position, tuple(so_ref[n] for n in range(ds))
        )
        for n in range(ds):
            so_ref[n] = s[n]

    @pl.when(i >= nlive[0])
    def _none_live():
        # the ONE step of a grid with no live row: its block goes back as
        # it came
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scan_rows_tpu(s_all, at, order, n_live, x, dt, z, A, Bm, Cm, D, *,
                  interpret: bool = False):
    """The Pallas scan of a slot's live rows in the carried array: ``s_all
    [L, rows, ds, 8, T]``; ``x``, ``dt``, ``z [B, S, 8, T]``, ``A [ds, 8, T]``,
    ``D [8, T]``, ``Bm``, ``Cm [B * S * ds]`` (all float32; these two whole in
    scalar memory, 64 KiB each at 4 rows of 256 positions) → ``(y [B, S, 8, T]``
    — rows before ``n_live`` in ``order`` WRITTEN, the others not —,
    ``s_all)``. Grid ``(max(n_live, 1), T / 128, S / P)`` with the first
    extent TRACED (as ``ssm_rows_tpu``'s); with no live row it is ONE step
    that writes one block of the first row back as it was read."""
    _, _, ds, sub, T = s_all.shape
    B, S = x.shape[:2]
    P = _SCAN_POSITIONS if S % _SCAN_POSITIONS == 0 else S
    W = 128 if T % 128 == 0 else T  # (a tiny model's, emulated: all of them)
    n = jnp.reshape(n_live, (1,)).astype(jnp.int32)

    def row(i, order):
        return order[jnp.minimum(i, B - 1)]

    state = pl.BlockSpec(
        (None, None, ds, sub, W),
        lambda i, j, k, lyr, row0, order, *_: (
            lyr[0], row0[0] + row(i, order), 0, 0, j
        ),
    )
    acts = pl.BlockSpec(
        (None, P, sub, W),
        lambda i, j, k, lyr, row0, order, *_: (row(i, order), k, 0, j),
    )
    s_all, y = pl.pallas_call(
        functools.partial(_scan_kernel, S=S),
        out_shape=[
            jax.ShapeDtypeStruct(s_all.shape, f32),
            jax.ShapeDtypeStruct(x.shape, f32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(
                jnp.maximum(n[0], 1), jnp.where(n[0] > 0, T // W, 1),
                jnp.where(n[0] > 0, S // P, 1),
            ),
            in_specs=[
                state, acts, acts, acts,
                pl.BlockSpec((ds, sub, W), lambda i, j, k, *_: (0, 0, j)),
                pl.BlockSpec((sub, W), lambda i, j, k, *_: (0, j)),
            ],
            out_specs=[state, acts],
        ),
        input_output_aliases={6: 0},  # the carried state, over itself
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="ssm_scan",
    )(
        *(jnp.reshape(a, (1,)).astype(jnp.int32) for a in at),
        order.astype(jnp.int32), n, Bm, Cm, s_all, x, dt, z, A, D,
    )
    return y, s_all


def scan_rows(s_all, at, order, n_live, x, dt, z, A, Bm, Cm, D,
              backend: str = "auto"):
    """``S`` positions of a slot's LIVE rows with the state advanced WHERE IT
    LIES: ``s_all [L_mamba, rows, ds, 8, di / 8]`` the whole carried state,
    ``at = (layer, first row of the slot)``, ``order [B]`` the slot's rows
    with the live ones first and ``n_live`` their count; ``x``, ``dt`` (0 at
    every position that is no real token), ``z [B, S, di]``, ``A [ds, di]``,
    ``Bm``, ``Cm [B, S, ds]``, ``D [di]`` → ``(y [B, S, di]`` f32, gated —
    ZERO for a row that is not live —, ``s_all)``. A row that is not live is
    neither read nor written (module docstring: the backends)."""
    _, _, ds, sub, T = s_all.shape
    B, S, di = x.shape
    backend = _resolve(backend, scan_eligible(ds, sub, T))
    x, dt, z = x.astype(f32), dt.astype(f32), z.astype(f32)
    A, D, Bm, Cm = A.astype(f32), D.astype(f32), Bm.astype(f32), Cm.astype(f32)
    if backend == "xla":
        l, row0 = at

        def advance(i, carry):
            # ONE live row (``ssm_step_rows``'s loop says why a loop)
            s_all, y_all = carry
            b = order[i]
            where = (l, row0 + b, 0, 0, 0)
            s = jax.lax.dynamic_slice(s_all, where, (1, 1, ds, sub, T))[0]

            def row(a):
                return jax.lax.dynamic_slice_in_dim(a, b, 1, axis=0)

            y, s = scan_chunk(
                s.reshape(1, ds, di), row(x), row(dt), row(z), A, row(Bm),
                row(Cm), D,
            )
            return (
                jax.lax.dynamic_update_slice(
                    s_all, s.reshape(1, 1, ds, sub, T), where
                ),
                jax.lax.dynamic_update_slice_in_dim(y_all, y, b, axis=0),
            )

        s_all, y = jax.lax.fori_loop(
            0, n_live, advance, (s_all, jnp.zeros(x.shape, f32))
        )
        return y, s_all

    def tiles(a):  # [.., di] → [.., 8, di / 8], as the state lies
        return a.reshape(*a.shape[:-1], sub, T)

    y, s_all = scan_rows_tpu(
        s_all, at, order, n_live, tiles(x), tiles(dt), tiles(z), tiles(A),
        Bm.reshape(B * S * ds), Cm.reshape(B * S * ds), tiles(D),
        interpret=backend == "interpret",
    )
    live = _visited(order, n_live)
    return jnp.where(live[:, None, None], y.reshape(B, S, di), 0.0), s_all


# --------------------------------- Mamba-1: a decode step's mixer in one pass

#: bytes of ``w_x`` one grid step of ``mixer_step_tpu`` holds ONE way (in VMEM
#: twice: double buffered, its lanes padded to whole tiles). At the published
#: widths (5,120 x 192 bf16: 2.6 MB padded) HALF of it: two channel tiles, four
#: grid steps a row. In the decode program a layer call takes 6.9-7.3 us so
#: against 7.2-7.6 with ONE tile (a tile's fetch overlaps the tile before's
#: work); in a program whose stacks lie in fast memory already 4.8 against
#: 4.3, and 5.6 / 7.4 at 4 / 8 tiles: ~0.4 us a grid step (``PERF.md`` §6,
#: PR 47)
_W_X_BLOCK_BYTES = 2 * 1024 * 1024


def mixer_tiles(di: int, width: int, itemsize: int) -> int:
    """Channel tiles ``mixer_step_tpu`` cuts a live row's two phases into:
    the fewest of 1, 2, 4, 8 (whole sublanes of the state ``[state, 8, di /
    8]``) whose ``[di / tiles, width]`` block of ``w_x`` stays within
    ``_W_X_BLOCK_BYTES``."""
    padded = -(-width // 128) * 128 * itemsize
    fits = [t for t in (1, 2, 4, 8) if di // t * padded <= _W_X_BLOCK_BYTES]
    return min(fits, default=8)


def mixer_eligible(ds: int, sub: int, lanes: int, rank: int) -> bool:
    """Whether Mosaic tiles ``mixer_step_tpu``'s blocks: the state as
    ``scan_rows_tpu`` takes it, and whole sublane tiles of the step's rank
    and of the state values (``w_dt``'s rows, the norms' slices)."""
    return scan_eligible(ds, sub, lanes) and rank % 8 == 0 and ds % 8 == 0


def _mixer_leaves_plain(layers) -> bool:
    """Whether every ``w_x`` / ``w_dt`` leaf of a tree of leaves by name is
    a plain array (no ``ops/quant.QTensor``)."""
    if not isinstance(layers, dict):
        return True
    return all(
        isinstance(v, jax.Array) if k in ("w_x", "w_dt")
        else _mixer_leaves_plain(v)
        for k, v in layers.items()
    )


def mixer_step_path(backend: str, cfg, layers=None) -> str:
    """The form a Mamba-1 mixer's decode step takes for ``backend``:
    ``fused`` — ONE Pallas call a layer between ``w_in`` and ``w_out``
    (``mixer_step_rows``) — or ``split`` (``conv_step``, the ``w_x`` path in
    XLA, ``ssm_step_rows``): the CPU's ``xla``, a shape Mosaic cannot tile,
    quantised ``w_x`` / ``w_dt`` among ``layers`` (a tree of leaves by name:
    a layer's, a stage's; None asks for the shapes alone)."""
    ds, sub, lanes = cfg.recurrent_shapes["ssm"]
    eligible = mixer_eligible(ds, sub, lanes, cfg.ssm_dt_rank)
    plain = _mixer_leaves_plain(layers)
    return "fused" if plain and _resolve(backend, eligible) != "xla" else "split"


def _softplus(v):
    return jnp.maximum(v, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(v)))


def _mixer_kernel(lyr, row0, order, nlive, x_ref, z_ref, c_ref, s_ref, cw_ref,
                  cb_ref, wx_ref, gdt_ref, gb_ref, gc_ref, wdt_ref, bdt_ref,
                  d_ref, a_ref, co_ref, so_ref, y_ref, xc_ref, acc_ref, dl_ref,
                  bc_ref, *, NT, R, eps):
    """Step ``(i, j)`` of live row ``order[i]``: ``j < NT`` is PHASE 1 on
    channel tile ``j`` — the conv step over the row's tail (``c_ref`` /
    ``co_ref [K-1, W]``, the shifted tail written back), its ``silu`` kept in
    ``xc_ref [NT, 1, W]``, its share of ``x w_x`` added into ``acc_ref [1,
    rank + 2 state]`` —; ``j >= NT`` is PHASE 2 on channel tile ``j - NT`` —
    at its first step the three norms (``dl_ref [1, rank]``, ``bc_ref [2,
    state]``), then ``dt = softplus(δ w_dt + b_dt)`` of the tile, the update
    of the tile's state rows (``s_ref`` / ``so_ref [state, 8, T]``, resident
    for the row: tile ``t`` holds its sublanes ``t W / T …``) and the gated
    read-out into ``y_ref [B, d_inner]`` (resident for the call, zeroed at
    its first step). ``x_ref`` / ``z_ref [B, W]`` are the tile's columns of
    ``xz``; a per-layer vector comes as the block of stack rows that holds
    the layer's (``pick``)."""
    i, j = pl.program_id(0), pl.program_id(1)
    B, W = x_ref.shape
    ds, _, T = s_ref.shape
    K = cw_ref.shape[0]
    l = lyr[0]
    b = order[jnp.minimum(i, B - 1)]
    live = i < nlive[0]

    def pick(ref, r):  # row ``r`` of a small block → [1, N] float32
        v = ref[...].astype(f32)
        rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        return jnp.sum(jnp.where(rows == r, v, 0.0), axis=0, keepdims=True)

    def vector(ref):  # the layer's row of a stack's block of rows
        return pick(ref, l % ref.shape[0])

    @pl.when((i == 0) & (j == 0))
    def _first_step():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(jnp.logical_not(live))
    def _none_live():
        # the ONE step of a grid with no live row: its blocks go back as
        # they came
        so_ref[...] = s_ref[...]
        co_ref[...] = c_ref[...]

    @pl.when(live & (j < NT))
    def _conv_and_w_x():
        x = pick(x_ref, b)  # [1, W]
        tail = c_ref[...]
        w = cw_ref[...].astype(f32)
        conv = tail[0:1] * w[0:1]
        for t in range(1, K - 1):
            conv = conv + tail[t:t + 1] * w[t:t + 1]
            co_ref[t - 1:t, :] = tail[t:t + 1]
        conv = conv + x * w[K - 1:K] + vector(cb_ref)
        co_ref[K - 2:K - 1, :] = x
        xc = conv * jax.nn.sigmoid(conv)
        xc_ref[j] = xc
        part = jnp.dot(
            xc.astype(wx_ref.dtype), wx_ref[...], preferred_element_type=f32
        )

        @pl.when(j == 0)
        def _first_tile():
            acc_ref[...] = part

        @pl.when(j > 0)
        def _next_tile():
            acc_ref[...] += part

    @pl.when(live & (j == NT))
    def _norms():
        v = acc_ref[...]

        def norm(u, g_ref):
            u = u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
            return u * pick(g_ref, l)

        dl_ref[...] = norm(v[:, :R], gdt_ref)
        bc_ref[0:1, :] = norm(v[:, R:R + ds], gb_ref)
        bc_ref[1:2, :] = norm(v[:, R + ds:], gc_ref)

    @pl.when(live & (j >= NT))
    def _update():
        t = j - NT
        dt = _softplus(jnp.dot(
            dl_ref[...].astype(wdt_ref.dtype), wdt_ref[...],
            preferred_element_type=f32,
        ) + vector(bdt_ref))  # [1, W]
        xc, z, D = xc_ref[t], pick(z_ref, b), vector(d_ref)
        bm, cm = bc_ref[0:1, :], bc_ref[1:2, :]
        for r in range(W // T):  # the tile's sublanes of the state
            at = pl.ds(t * (W // T) + r, 1)
            lanes = slice(r * T, (r + 1) * T)
            dtr, xr, zr = dt[:, lanes], xc[:, lanes], z[:, lanes]
            xdt = dtr * xr
            y = D[:, lanes] * xr
            for n in range(ds):
                sn = (
                    s_ref[n, at, :] * jnp.exp(dtr * a_ref[n, at, :])
                    + xdt * bm[:, n:n + 1]
                )
                so_ref[n, at, :] = sn
                y = y + sn * cm[:, n:n + 1]
            col = t * W + r * T
            if T % 128 == 0:
                col = pl.multiple_of(col, 128)
            y_ref[pl.ds(b, 1), pl.ds(col, T)] = y * zr * jax.nn.sigmoid(zr)


@functools.partial(jax.jit, static_argnames=("eps", "tiles", "interpret"))
def mixer_step_tpu(s_all, c_all, at, order, n_live, xz, leaves, A, *,
                   eps: float, tiles: Optional[int] = None,
                   interpret: bool = False):
    """The Pallas mixer step of a slot's live rows (``mixer_step_rows``:
    the operands): grid ``(max(n_live, 1), 2 tiles)`` with the first extent
    TRACED (as ``scan_rows_tpu``'s); a row's steps run phase 1 over its
    ``tiles`` channel tiles (``mixer_tiles`` unless given: the tests walk
    them all), then phase 2 over them — ``w_x`` reduces over
    ALL channels, so every channel's conv output exists (in VMEM) before any
    channel's ``dt`` does. ``s_all`` and ``c_all`` are aliased over
    themselves; the stacks of ``leaves`` are indexed by the scalar-prefetched
    layer in their ``BlockSpec``s (a ``[L, N]`` vector by the block of 8 or 16
    stack rows that holds the layer's). With no live row the grid is ONE step
    that writes a state block and a tail tile of the first row back as they
    were read. ``A [ds, 8, T]`` float32."""
    L, _, ds, sub, T = s_all.shape
    K1, di = c_all.shape[2:]
    B = xz.shape[0]
    p = leaves
    R = p["w_dt"].shape[1]
    NT = tiles or mixer_tiles(di, R + 2 * ds, p["w_x"].dtype.itemsize)
    W = di // NT
    n = jnp.reshape(n_live, (1,)).astype(jnp.int32)

    def row(i, order):
        return order[jnp.minimum(i, B - 1)]

    def p1(j):  # phase 1's tile at step j (phase 2 holds its last)
        return jnp.minimum(j, NT - 1)

    def p2(j):  # phase 2's tile (phase 1 holds its first)
        return jnp.maximum(j - NT, 0)

    def vector(a, tile):
        # the block of stack rows that holds the layer's: a whole sublane
        # tile of ``a``'s type (8 rows of float32, 16 of bf16)
        rb = min(L, 8 * 4 // a.dtype.itemsize)
        return pl.BlockSpec(
            (rb, W), lambda i, j, lyr, *_: (lyr[0] // rb, tile(j))
        )

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i, j, *_: (0,) * a.ndim)

    tail = pl.BlockSpec(
        (None, None, K1, W),
        lambda i, j, lyr, row0, order, nl: (
            lyr[0], row0[0] + row(i, order), 0, p1(j)
        ),
    )
    state = pl.BlockSpec(
        (None, None, ds, sub, T),
        lambda i, j, lyr, row0, order, nl: (
            lyr[0], row0[0] + row(i, order), 0, 0, 0
        ),
    )
    in_specs = [
        pl.BlockSpec((B, W), lambda i, j, *_: (0, p1(j))),  # x of xz
        pl.BlockSpec((B, W), lambda i, j, *_: (0, NT + p2(j))),  # z of xz
        tail, state,
        pl.BlockSpec(
            (None, K1 + 1, W), lambda i, j, lyr, *_: (lyr[0], 0, p1(j))
        ),
        vector(p["conv_b"], p1),
        pl.BlockSpec(
            (None, W, R + 2 * ds), lambda i, j, lyr, *_: (lyr[0], p1(j), 0)
        ),
        whole(p["dt_norm"]), whole(p["b_norm"]), whole(p["c_norm"]),
        pl.BlockSpec((None, R, W), lambda i, j, lyr, *_: (lyr[0], 0, p2(j))),
        vector(p["dt_bias"], p2), vector(p["D"], p2),
        whole(A),
    ]
    c_all, s_all, y = pl.pallas_call(
        functools.partial(_mixer_kernel, NT=NT, R=R, eps=eps),
        out_shape=[
            jax.ShapeDtypeStruct(c_all.shape, f32),
            jax.ShapeDtypeStruct(s_all.shape, f32),
            jax.ShapeDtypeStruct((B, di), f32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(jnp.maximum(n[0], 1), jnp.where(n[0] > 0, 2 * NT, 1)),
            in_specs=in_specs,
            out_specs=[
                tail, state, pl.BlockSpec((B, di), lambda i, j, *_: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((NT, 1, W), f32),
                pltpu.VMEM((1, R + 2 * ds), f32),
                pltpu.VMEM((1, R), f32),
                pltpu.VMEM((2, ds), f32),
            ],
        ),
        # the carried tail and state, each over itself
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="ssm_mixer",
    )(
        *(jnp.reshape(a, (1,)).astype(jnp.int32) for a in at),
        order.astype(jnp.int32), n, xz, xz, c_all, s_all, p["conv_w"],
        p["conv_b"], p["w_x"], p["dt_norm"], p["b_norm"], p["c_norm"],
        p["w_dt"], p["dt_bias"], p["D"], A,
    )
    return y, s_all, c_all


def mixer_step_rows(s_all, c_all, at, order, n_live, xz, leaves, A, eps,
                    backend: str = "auto"):
    """A Mamba-1 mixer's decode step between its two projections, ONE Pallas
    call, with the state AND the conv's tail advanced where they lie:
    ``s_all [L_mamba, rows, ds, 8, di / 8]`` and ``c_all [L_mamba, rows, K-1,
    di]`` the whole carried arrays, ``at = (layer, first row of the slot)``,
    ``order [B]`` the slot's rows with the live ones first and ``n_live``
    their count, ``xz [B, 2 di]`` what ``w_in`` made, ``leaves`` the stack's
    leaves of ``models/stack.MAMBA1_WHOLE_KEYS`` WHOLE (``[L_mamba, ...]``,
    plain arrays), ``A [ds, di] = -exp(A_log).T`` of the layer → ``(y [B,
    di]`` f32, gated — ZERO for a row that is not live —, ``s_all, c_all)``. Inside the call, per live
    row: the conv step over its tail, ``[δ | B | C] = x w_x``, the three
    norms, ``dt = softplus(δ w_dt + b_dt)``, ``S ← exp(dt A) S + dt B x``,
    ``y = (S·C + D x) · silu(z)`` — the two products on operands of the
    weights' type with float32 accumulation, everything else float32. A row
    that is not live is neither read nor written. ``backend`` must resolve
    to ``kernel`` or ``interpret`` (``mixer_step_path`` says ``fused``)."""
    _, _, ds, sub, T = s_all.shape
    backend = _resolve(
        backend, mixer_eligible(ds, sub, T, leaves["w_dt"].shape[1])
    )
    assert backend != "xla", "mixer_step_path: this shape takes the split path"
    return mixer_step_tpu(
        s_all, c_all, at, order, n_live, xz, leaves,
        A.astype(f32).reshape(ds, sub, T), eps=eps,
        interpret=backend == "interpret",
    )
