"""Sparse experts: the router and the expert product of a mixture-of-experts
MLP (OLMoE: ``E`` gated MLPs of width ``F`` a layer, ``k`` a token).

A layer's experts are ONE block-sparse MLP of width ``E·F``: ``we_gate``,
``we_up`` ``[H, E·F]`` and ``we_down`` ``[E·F, H]``, expert ``e`` being
columns (rows) ``e·F … (e+1)·F`` — plain 2-D matmul leaves, so quantisation,
stacking over layers, the shard store and the ring split treat them like any
other weight (``ops/quant.QTensor``: int8 codes, one scale per output
channel). What is new is that a step reads a SUBSET of them chosen at run
time: a decode step of four rows touches 8-32 of 64 experts, and reading all
of them would cost five times the bytes.

**The router** (``route``): logits ``x · router`` multiplied out in float32
(a bf16 router flips the top-k between near ties), softmax over ALL experts,
the ``k`` largest kept as they are — renormalised to sum 1 only under
``norm_topk_prob``. With a correction ``bias`` (``longcat_flash``) the CHOICE
is the ``k`` largest of ``p + bias`` and the weights stay the UNbiased ``p``
there, times a ``scale``; the router's width may then be ``E + Z``: ``E``
real experts and, after them, ``Z`` zero-compute ones (below).

**The expert product** (``expert_mlp``): ``y = (silu(x · Wg_e) ⊙ (x · Wu_e))
· Wd_e`` for each chosen expert, weighed and summed a row. Two regimes, chosen
by the static row count, each with a kernel of its own — they want opposite
things:

- *decode* (a handful of rows, ``N <= DECODE_ROWS_MAX``): every DISTINCT
  expert the live rows chose is read once, multiplied with ALL the rows, and
  its output added to the rows' sum with their router weights (zero where a
  row did not choose it). ONE Pallas call that fetches by hand
  (``expert_decode_tpu``): every operand stays in HBM; the body walks the
  experts that have a pair in ascending id and copies each block — a chunk
  of the expert's width (``decode_chunk``) of the three leaves and the same
  columns of the sublane tile of scale rows around the layer's — into a ring
  of VMEM slots, the next block's copies in flight while this one is multiplied, the
  walk's first copies started before anything else. The weighted sum runs in
  the kernel, in float32, in that one order, into one resident ``[N, H]``
  block that ``we_down``'s scale row multiplies at the end. Beside the call
  XLA masks the dead rows' ids and counts the pairs an expert has (the
  counters need them anyway). Until PR 63 this regime shared the grouped
  kernel: two XLA slices of the scale stacks before it, a sort over ``E`` and
  two gathers to list the tiles, a ``[NT, 8, H]`` tile output and an
  ``einsum`` after it — 11 us a call beside a 54 us kernel on the chip.
- *prefill* (hundreds of positions): (token, expert) pairs sorted by expert
  and padded per expert to whole tiles of ``TILE_ROWS`` — a grouped matmul;
  consecutive tiles of one expert reuse the fetched weights, which the
  pipeline's revisit rule gives for nothing (``expert_tiles_tpu``).

The GROUPED kernel takes the whole LAYER-STACKED weights and reads the
``(H, F)`` / ``(F, H)`` int8 tiles of expert ``tile_expert[i]`` of layer
``layer`` through scalar-prefetched indices — the stack is never sliced.
Its grid is ``(live tiles, slices of an expert's width)`` and the FIRST
extent is traced: a call walks its ``n_live`` tiles and ends where they do
(a grid step costs the pipeline's bookkeeping for seven operands even when
nothing is fetched: a tile is skipped by not being in the grid). Two rules
follow. The pipeline evaluates the index maps one step AHEAD of the one it
runs, so ``Tiles.expert`` and ``Tiles.row`` hold one entry more than the
most tiles there can be. And a tile past ``n_live`` is NOT WRITTEN: the
combine selects the live pairs before it weighs them — zero times what a
buffer happened to hold is not zero. The decode call has no such output: it
walks nothing when no expert has a pair and returns zeros; where the leaves
hold a SHARE of the experts most calls of a row or two are such calls, and
the decode regime branches around the kernel for them (``lax.cond``: a call
launched for nothing still costs its launch). The XLA path (``backend="xla"``:
the CPU tests, and the dense-cache oracle paths) gathers the same experts and
does the same arithmetic, one tile per distinct expert in the decode regime.

**Dead rows and pad positions route nowhere** (``live``): they form no pair,
are neither read for nor counted, and get a zero MLP output. No token is
dropped and there is no capacity factor.

``stats``: ``expert_tokens [E]`` — (live token, expert) pairs per expert —
and ``experts_read`` — distinct experts the layer read (scalar).

**A chip's share of the experts** (``expert_mlp(held=(first, count))``;
``deepseek_v3``, ``models/deepseek_v3.py``): the leaves hold ``count`` of the
layer's ``E`` experts, ids ``first … first + count - 1``. The router scores
and normalises over ALL ``E`` (``route_noaux_tc``: sigmoid scores, a
correction bias used for the choice only, groups of which the best are
kept); a pair that falls on an expert held elsewhere forms no tile, is not
read for, and adds nothing — what the absent experts would add is left out,
and nothing stands in for the chips that hold them. ``expert_tokens`` stays
``[E]`` (every pair routed, so pairs held = its slice over the held ids),
``experts_read`` counts held experts only. The SOFTMAX router scores and
normalises over all of them just the same (``route``; ``models/
longcat_flash.py``).

**Experts without weights** (``expert_mlp(zero_from=E)``; ``num_experts`` is
then the router's width ``E + Z``): an id ``>= zero_from`` is a zero-compute
expert that returns its input. Such a pair forms no tile in EITHER regime, is
never fetched for, and is not an absent expert either: it adds ``w · x``, so
a token gets ``(Σ w over such picks) · x`` — the weights summed in float32,
the product added to the experts' float32 sum before the one cast to the
output's dtype. Of a token's ``k`` picks 0 to ``k`` are real: its compute
varies. ``expert_tokens`` is ``[E + Z]`` wide, so the pairs that cost nothing
are COUNTED (its slice from ``E``); ``experts_read`` counts held real experts
only. Under a share the term needs no weights and is computed where the
token lives: what every chip computes alike, counted once when shares are
added up.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant import QTensor

#: rows of one grouped-matmul tile in the prefill regime
TILE_ROWS = 128
#: at most this many rows (B·S) run the decode regime (one tile per
#: distinct expert); more are grouped by expert
DECODE_ROWS_MAX = 32
#: columns of an expert's width one kernel step handles (VMEM: three int8
#: blocks of H x F_CHUNK, double-buffered, plus their converted forms)
F_CHUNK = 512
_VMEM_LIMIT = 64 * 1024 * 1024


def f_chunk(hidden: int) -> int:
    """``F_CHUNK`` while an ``H x F_CHUNK`` int8 block is at most 2 MiB (H up
    to 4,096), else the largest multiple of 128 columns that keeps it so:
    256 at H 7,168, where 512 would put three 3.5 MiB blocks, double
    buffered, and their converted forms past the VMEM limit."""
    if hidden * F_CHUNK <= 2 << 20:
        return F_CHUNK
    return max(128, ((2 << 20) // hidden) // 128 * 128)


def f_tile(hidden: int, width: int) -> int:
    """Columns of an expert's ``width`` one kernel step handles:
    ``f_chunk(hidden)`` (or the whole width under it) where that divides the
    width — every gated model's — else the largest multiple of 128 that does
    and keeps an ``H x tile`` int8 block at most 2 MiB: 896 for a width of
    2,688 = 21·128 at ``H`` 1,024 (``nemotron_h``'s latent experts), three
    steps an expert where 384 would take seven."""
    fc = min(width, f_chunk(hidden))
    if width % fc == 0:
        return fc
    most = (2 << 20) // hidden
    for n in range(min(most, width) // 128, 0, -1):
        if width % (128 * n) == 0:
            return 128 * n
    raise ValueError(
        f"no tile of whole 128 columns divides the expert width {width}"
    )


#: the expert product's activation (static): ``silu`` — gated, ``silu(x Wg) ⊙
#: (x Wu)`` — or ``relu2`` — NOT gated, ONE up matrix, ``relu(x Wu)²``
#: (``nemotron_h``; ``we_gate`` is then None)
ACTS = ("silu", "relu2")

BACKENDS = ("auto", "kernel", "xla", "interpret")


class MoeStats(NamedTuple):
    expert_tokens: jax.Array  # [E] int32 (live token, expert) pairs
    experts_read: jax.Array  # scalar int32 distinct experts read


def route(x, router, top_k: int, renormalize: bool = False, bias=None,
          scale: float = 1.0):
    """``x [N, H]``, ``router [H, E]`` → ``(weights [N, k] f32, ids [N, k])``:
    float32 softmax over all experts, the ``top_k`` largest kept as they are
    (renormalised only when asked). With ``bias [E]`` (``longcat_flash``) the
    CHOICE is the ``top_k`` largest of ``p + bias`` and the weights are the
    UNbiased ``p`` there; ``scale`` multiplies the kept weights."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        w, ids = jax.lax.top_k(probs, top_k)
    else:
        _, ids = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(probs, ids, axis=-1)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if scale != 1.0:
        w = w * scale
    return w, ids.astype(jnp.int32)


def route_noaux_tc(x, router, bias, top_k: int, n_group: int,
                   topk_group: int, scale: float):
    """The ``deepseek_v3`` router (HF ``DeepseekV3TopkRouter``), ``x [N, H]``,
    ``router [H, E]``, ``bias [E]`` → ``(weights [N, k] f32, ids [N, k])``:
    ``s = sigmoid(x · router)`` in float32; the CHOICE is made on ``s +
    bias``: a group's score is the sum of its two largest, the ``topk_group``
    best groups are kept and the others' scores set to 0.0 (as
    ``transformers`` masks them), the ``top_k`` largest name the experts;
    their weights are the UNbiased ``s`` there, divided by their sum (+1e-20)
    and multiplied by ``scale``."""
    N, E = x.shape[0], router.shape[-1]
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    s = jax.nn.sigmoid(logits)
    choice = s + bias.astype(jnp.float32)
    if n_group > 1:  # (one group, mimo_v2: no group step)
        grouped = choice.reshape(N, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, topk_group)
        keep = jnp.any(
            kept[:, :, None] == jnp.arange(n_group, dtype=kept.dtype), axis=1
        )  # [N, n_group]
        choice = jnp.where(keep[:, :, None], grouped, 0.0).reshape(N, E)
    _, ids = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return w, ids.astype(jnp.int32)


# ------------------------------------------------------------------ tiles

class Tiles(NamedTuple):
    """Row tiles for the expert product."""

    x: jax.Array  # [R, tm, H] distinct row tiles
    # one entry more than the most tiles (NT): the kernel's index maps are
    # evaluated one grid step ahead of the step that runs
    row: jax.Array  # [NT + 1] int32 which row tile each tile reads
    expert: jax.Array  # [NT + 1] int32 the tile's expert
    n_live: jax.Array  # scalar int32 tiles before this index are real


def _decode_tiles(x, w, ids, live, E):
    """The XLA path's decode regime: one tile per distinct expert of the live
    rows, all rows in each. Returns ``(tiles, combine [NT, N] f32, counts
    [E])``. An id of ``E`` (an expert held elsewhere, ``expert_mlp(held=)``)
    matches no tile."""
    N, H = x.shape
    k = ids.shape[1]
    onehot = (ids[:, :, None] == jnp.arange(E, dtype=jnp.int32)) & live[
        :, None, None
    ]  # [N, k, E]
    comb = jnp.sum(jnp.where(onehot, w[:, :, None], 0.0), axis=1)  # [N, E]
    counts = jnp.sum(onehot, axis=(0, 1)).astype(jnp.int32)  # [E]
    hit = counts > 0
    n_live = jnp.sum(hit).astype(jnp.int32)
    NT = min(E, N * k)
    # the hit experts first, in ascending order (a stable sort of ~hit)
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)
    expert = order[jnp.minimum(jnp.arange(NT + 1, dtype=jnp.int32), E - 1)]
    cw = comb.T[expert[:NT]]  # [NT, N]; no row chose a tile past n_live: 0
    tiles = Tiles(x[None], jnp.zeros((NT + 1,), jnp.int32), expert, n_live)
    return tiles, cw, counts


def _grouped_tiles(x, ids, live, E, tm):
    """(token, expert) pairs sorted by expert, each expert's run padded to
    whole tiles of ``tm`` rows. Returns ``(tiles, pos [N, k], counts [E])``:
    ``pos`` is each pair's row in the flattened tile output (dead pairs
    point at a row the caller does not select)."""
    N, H = x.shape
    k = ids.shape[1]
    P = N * k
    flat_e = jnp.where(live[:, None], ids, E).reshape(P)  # dead pairs last
    flat_tok = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    sorted_e = flat_e[order]
    counts = jnp.sum(
        flat_e[:, None] == jnp.arange(E, dtype=jnp.int32), axis=0
    ).astype(jnp.int32)
    tiles_per = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_per)
    tile_start = tile_end - tiles_per
    group_start = jnp.cumsum(counts) - counts
    NT = -(-P // tm) + E  # sum of ceil(c_e / tm) never exceeds this
    n_live = tile_end[-1].astype(jnp.int32)
    # padded row of each sorted pair: its expert's first tile + its rank
    e_safe = jnp.minimum(sorted_e, E - 1)
    rank = jnp.arange(P, dtype=jnp.int32) - group_start[e_safe]
    pos_sorted = jnp.where(
        sorted_e < E, tile_start[e_safe] * tm + rank, NT * tm
    )  # dead pairs scatter out of range (dropped)
    src = jnp.full((NT * tm,), N, jnp.int32).at[pos_sorted].set(
        flat_tok[order], mode="drop"
    )
    x_ext = jnp.concatenate([x, jnp.zeros((1, H), x.dtype)], axis=0)
    xt = x_ext[src].reshape(NT, tm, H)
    pos = jnp.zeros((P,), jnp.int32).at[order].set(
        jnp.minimum(pos_sorted, NT * tm - 1)
    ).reshape(N, k)
    ahead = jnp.arange(NT + 1, dtype=jnp.int32)
    expert = jnp.minimum(
        jnp.searchsorted(tile_end, ahead, side="right").astype(jnp.int32),
        E - 1,
    )
    tiles = Tiles(xt, jnp.minimum(ahead, NT - 1), expert, n_live)
    return tiles, pos, counts


# --------------------------------------------------------------- the product

def _leaf(w, layer):
    """``(codes or raw weight, scale)`` of a maybe-quantised leaf, both
    layer-stacked: a leaf handed over already sliced (``layer`` None — the
    block called on one layer's params) becomes a stack of one, and a raw
    weight's scale is one."""
    q, s = (w.q, w.scale) if isinstance(w, QTensor) else (w, None)
    if layer is None:
        q, s = q[None], None if s is None else s[None]
    if s is None:
        s = jnp.ones((q.shape[0], q.shape[-1]), jnp.float32)
    return q, s


def _tiles_xla(tiles: Tiles, layer, wg, wu, wd, sg, su, E, out_dtype,
               act: str = "silu"):
    """The kernel's arithmetic in plain XLA: gather each tile's expert."""
    row, expert = tiles.row[:-1], tiles.expert[:-1]  # the NT tiles
    x = tiles.x[row]  # [NT, tm, H]
    H = x.shape[-1]
    F = wu.shape[-1] // E

    def cols(w, s):  # [L, H, E·F] → the tiles' [NT, H, F]
        wl = jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
        wt = jnp.moveaxis(wl.reshape(H, E, F), 1, 0)[expert]
        sl = jax.lax.dynamic_index_in_dim(s, layer, keepdims=False)
        return wt.astype(x.dtype), sl.reshape(E, F)[expert][:, None, :]

    if act != "relu2":
        g_w, g_s = cols(wg, sg)
    u_w, u_s = cols(wu, su)
    d_w = jax.lax.dynamic_index_in_dim(wd, layer, keepdims=False).reshape(
        E, F, H
    )[expert].astype(x.dtype)
    f32 = jnp.float32
    u = jnp.einsum("jth,jhf->jtf", x, u_w, preferred_element_type=f32) * u_s
    if act == "relu2":
        a = jnp.square(jax.nn.relu(u)).astype(x.dtype)
    else:
        g = jnp.einsum(
            "jth,jhf->jtf", x, g_w, preferred_element_type=f32
        ) * g_s
        a = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.einsum("jtf,jfh->jth", a, d_w, preferred_element_type=f32)
    alive = jnp.arange(y.shape[0], dtype=jnp.int32) < tiles.n_live
    return jnp.where(alive[:, None, None], y, 0.0).astype(out_dtype)


def _expert_kernel(lyr, texp, trow, nlive, x_ref, wg_ref, wu_ref, wd_ref,
                   sg_ref, su_ref, o_ref, acc_ref, *, n_f):
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nlive[0])
    def _tile():
        x = x_ref[...]
        f32 = jnp.float32
        g = jnp.dot(
            x, wg_ref[...].astype(x.dtype), preferred_element_type=f32
        ) * sg_ref[...].astype(f32)
        u = jnp.dot(
            x, wu_ref[...].astype(x.dtype), preferred_element_type=f32
        ) * su_ref[...].astype(f32)
        a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(
            a, wd_ref[...].astype(x.dtype), preferred_element_type=f32
        )

    @pl.when(f == n_f - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _expert_kernel_relu2(lyr, texp, trow, nlive, x_ref, wu_ref, wd_ref,
                         su_ref, o_ref, acc_ref, *, n_f):
    """``_expert_kernel`` for an expert that is NOT gated: ``relu(x Wu)² Wd``."""
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nlive[0])
    def _tile():
        x = x_ref[...]
        f32 = jnp.float32
        u = jnp.dot(
            x, wu_ref[...].astype(x.dtype), preferred_element_type=f32
        ) * su_ref[...].astype(f32)
        u = jnp.maximum(u, 0.0)
        acc_ref[...] += jnp.dot(
            (u * u).astype(x.dtype), wd_ref[...].astype(x.dtype),
            preferred_element_type=f32,
        )

    @pl.when(f == n_f - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("E", "out_dtype", "interpret", "act")
)
def expert_tiles_tpu(tiles: Tiles, layer, wg, wu, wd, sg, su, *, E: int,
                     out_dtype, interpret: bool = False, act: str = "silu"):
    """The Pallas expert product: grid ``(max(n_live, 1), F / F_CHUNK)``, the
    first extent TRACED (Mosaic takes a traced extent on either axis; two
    axes keep a step's tile and slice the grid's own indices), the second
    axis accumulating a tile's output over slices of the expert's width.
    Returns ``[NT, tm, H]`` of which only the tiles before ``n_live`` are
    WRITTEN (with none live, one tile of zeros). Weights are the
    layer-stacked ``[L, H, E·F]`` / ``[L, E·F, H]`` arrays, read where they
    lie."""
    R, tm, H = tiles.x.shape
    NT = tiles.row.shape[0] - 1
    gated = act != "relu2"
    F = wu.shape[-1] // E
    fc = f_tile(H, F)
    n_f = F // fc
    # the layer's row of each scale stack, sliced here: 128 KB, where handing
    # the kernel a [L, 1, E·F] view of the stack made XLA re-lay the whole
    # 2 MiB stack out per layer and step (18 us each, a third of this scope)
    sg2, su2 = (
        None if s is None
        else jax.lax.dynamic_index_in_dim(s, layer, keepdims=True)
        for s in (sg, su)
    )
    def fblock(i, f, texp):  # slice f of tile i's expert
        return texp[i] * n_f + f

    def col_map(i, f, lyr, texp, trow, nlive):
        return (lyr[0], 0, fblock(i, f, texp))

    def scale_map(i, f, lyr, texp, trow, nlive):
        return (0, fblock(i, f, texp))

    def row_map(i, f, lyr, texp, trow, nlive):
        return (lyr[0], fblock(i, f, texp), 0)

    n_live = jnp.reshape(tiles.n_live, (1,))
    col, scale = pl.BlockSpec((None, H, fc), col_map), pl.BlockSpec(
        (1, fc), scale_map
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jnp.maximum(n_live[0], 1), n_f),
        in_specs=[
            pl.BlockSpec(
                (None, tm, H),
                lambda i, f, lyr, texp, trow, nlive: (trow[i], 0, 0),
            ),
            *((col, col) if gated else (col,)),
            pl.BlockSpec((None, fc, H), row_map),
            *((scale, scale) if gated else (scale,)),
        ],
        out_specs=pl.BlockSpec(
            (None, tm, H),  # (the step looked at ahead may be tile NT)
            lambda i, f, lyr, texp, trow, nlive: (
                jnp.minimum(i, NT - 1), 0, 0
            ),
        ),
        scratch_shapes=[pltpu.VMEM((tm, H), jnp.float32)],
    )
    operands = (
        (wg, wu, wd, sg2, su2) if gated else (wu, wd, su2)
    )
    return pl.pallas_call(
        functools.partial(
            _expert_kernel if gated else _expert_kernel_relu2, n_f=n_f
        ),
        out_shape=jax.ShapeDtypeStruct((NT, tm, H), out_dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_experts",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), tiles.expert, tiles.row,
        n_live, tiles.x, *operands,
    )


#: VMEM slots of the decode call's ring: a block being multiplied and the
#: next one's copies in flight (a third slot gave nothing on the chip: a
#: block's copies take longer than its conversion and dots, so one block in
#: flight keeps the queue fed)
DECODE_SLOTS = 2
#: bytes of one int8 leaf's part of a block of that ring
DECODE_BLOCK_BYTES = 512 * 1024


def decode_chunk(hidden: int, width: int) -> int:
    """Columns of an expert's ``width`` one block of the decode call holds:
    the largest multiple of 128 that divides the width and keeps an ``H x
    chunk`` int8 block at most ``DECODE_BLOCK_BYTES`` — 256 at ``H`` 2,048
    (OLMoE's 1,024 in four blocks, Keye's 768 in three), 384 of ``nemotron_h``'s
    2,688 at ``H`` 1,024 — and at least 128 (``H`` 4,096 and over); the
    whole width where that is no multiple of 128 (toy shapes). Finer than
    the grouped kernel's ``f_tile``: what nothing overlaps in a call is its
    LAST block's conversion and dots, and a by-hand copy costs next to
    nothing to start (the kernel alone on the chip, 8 experts met, two slots:
    Keye 67.5 us at 768 columns, 65.8 at 256; OLMoE 85.1 at 512, 81.6 at
    256; GigaChat's 6 of 16 held 386.1 at 256, 366.7 at 128: ``PERF.md``,
    PR 63)."""
    most = max(128, DECODE_BLOCK_BYTES // hidden // 128 * 128)
    for n in range(min(most, width) // 128, 0, -1):
        if width % (128 * n) == 0:
            return 128 * n
    return width


#: rows of a sublane tile of an array in HBM: a copy slices rows by whole
#: tiles (Mosaic: "slice shape must be aligned to tiling (8)")
SUBLANES = 8


def _decode_kernel(lyr_ref, cnt_ref, x_hbm, w_hbm, ids_hbm, *refs, gated: bool,
                   n_f: int):
    """The decode regime's expert product, ONE invocation: walk the experts
    that have a pair (``cnt_ref [E]`` > 0) in ascending id, an expert's width
    in ``n_f`` chunks; fetch each block BY HAND — the chunk's ``(H, fc)``
    columns of the gate and up leaves, its ``(fc, H)`` rows of the down leaf
    and the same columns of a few rows of the scale stacks around the
    layer's, all read where they lie in HBM — into a ring of VMEM slots, the
    next blocks' copies in flight while this one is multiplied; weigh an
    expert's output by the rows' router weights for it and add it to the one
    resident ``[rows, H]`` float32 block, which ``we_down``'s scale row
    multiplies at the end."""
    n_w = 2 if gated else 1  # leaves read by columns: (gate,) up
    cols, wd_hbm = refs[:n_w], refs[n_w]
    scales, sd_hbm = refs[n_w + 1:2 * n_w + 1], refs[2 * n_w + 1]
    out_ref = refs[2 * n_w + 2]
    xbuf, wbuf, idbuf = refs[2 * n_w + 3:2 * n_w + 6]
    scratch = refs[2 * n_w + 6:]
    colbufs, dbuf = scratch[:n_w], scratch[n_w]
    sbufs, sdbuf = scratch[n_w + 1:2 * n_w + 1], scratch[2 * n_w + 1]
    acc_ref, sem, small_sem = scratch[2 * n_w + 2:]
    E = cnt_ref.shape[0]
    F = wd_hbm.shape[1] // E
    nslot, fc = dbuf.shape[0], dbuf.shape[1]
    f32 = jnp.float32
    layer = lyr_ref[0]

    # the sublane tile of rows the layer's row of a scale stack lies in: what
    # a copy brings of one, the row picked out of it in VMEM
    tile_row = pl.multiple_of(layer // SUBLANES * SUBLANES, SUBLANES)
    tile_rows = pl.ds(tile_row, SUBLANES)

    def pick(tile):
        """The layer's row of a fetched tile of scale rows, ``[1, n]`` f32
        (a select: the stack may end inside the tile, and what lies past its
        end is whatever the memory holds)."""
        t = tile.astype(f32)
        at = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
        return jnp.sum(
            jnp.where(at == layer - tile_row, t, 0.0), axis=0, keepdims=True
        )

    def next_hit(e):
        """The first expert at or after ``e`` that has a pair; ``E``: none."""
        def missed(r):
            return (r < E) & (cnt_ref[jnp.minimum(r, E - 1)] <= 0)
        return jax.lax.while_loop(missed, lambda r: r + 1, e)

    def advance(pos):
        """The block after ``pos = (expert, chunk)`` in the walk."""
        e, f = pos
        if n_f == 1:
            return next_hit(jnp.minimum(e + 1, E)), f
        last = f + 1 >= n_f  # (the walk over ``cnt_ref`` only then)
        return (
            jax.lax.cond(
                last, lambda: next_hit(jnp.minimum(e + 1, E)), lambda: e
            ),
            jnp.where(last, 0, f + 1),
        )

    def copies(slot, e=0, f=0):
        """``(columns' and scales' copies, the down rows' copy)`` of chunk
        ``f`` of expert ``e`` into ``slot`` (the defaults: the same copies
        to WAIT on — a wait reads its copy's size and semaphore only)."""
        c0 = e * F + f * fc
        if F % 128 == 0 and fc % 128 == 0 and not isinstance(c0, int):
            c0 = pl.multiple_of(c0, 128)
        first = [
            pltpu.make_async_copy(
                s.at[tile_rows, pl.ds(c0, fc)], b.at[slot],
                sem.at[slot, 0],
            ) for s, b in zip(scales, sbufs)
        ] + [
            pltpu.make_async_copy(
                w.at[layer, :, pl.ds(c0, fc)], b.at[slot], sem.at[slot, 0]
            ) for w, b in zip(cols, colbufs)
        ]
        down = pltpu.make_async_copy(
            wd_hbm.at[layer, pl.ds(c0, fc), :], dbuf.at[slot], sem.at[slot, 1]
        )
        return first, down

    def fetch(slot, pos):
        @pl.when(pos[0] < E)
        def _():
            first, down = copies(slot, *pos)
            for cp in (*first, down):
                cp.start()

    # the walk's first blocks are asked for before anything else happens
    ahead = [(next_hit(jnp.int32(0)), jnp.int32(0))]
    fetch(0, ahead[0])
    for slot in range(1, nslot - 1):
        ahead.append(advance(ahead[-1]))
        fetch(slot, ahead[-1])
    ahead.append(advance(ahead[-1]))  # the next block to ask for
    small = [
        pltpu.make_async_copy(src, dst, small_sem.at[i])
        for i, (src, dst) in enumerate((
            (x_hbm, xbuf), (w_hbm, wbuf), (ids_hbm, idbuf),
            (sd_hbm.at[tile_rows], sdbuf),
        ))
    ]
    for cp in small:
        cp.start()
    out_ref[...] = jnp.zeros_like(out_ref)
    for cp in small:
        cp.wait()

    def block(carry):
        """One block of the walk: ask for the block ``nslot - 1`` ahead (its
        slot is the one the block before this one was multiplied from), then
        wait for this one's copies and multiply."""
        slot, *flat = carry
        pos = list(zip(flat[0::2], flat[1::2]))
        e, f = pos[0]
        fetch((slot + nslot - 1) % nslot, pos[-1])
        first, down = copies(slot)
        for cp in first:
            cp.wait()
        x = xbuf[...]
        u = jnp.dot(
            x, colbufs[-1][slot].astype(x.dtype), preferred_element_type=f32
        ) * pick(sbufs[-1][slot])
        if gated:
            g = jnp.dot(
                x, colbufs[0][slot].astype(x.dtype),
                preferred_element_type=f32,
            ) * pick(sbufs[0][slot])
            a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        else:
            u = jnp.maximum(u, 0.0)
            a = (u * u).astype(x.dtype)
        down.wait()
        y = jnp.dot(
            a, dbuf[slot].astype(x.dtype), preferred_element_type=f32
        )

        def weighed(y):
            """``y`` times each row's router weight for expert ``e`` (zero
            for a row that did not choose it)."""
            return y * jnp.sum(
                jnp.where(idbuf[...] == e, wbuf[...], 0.0), axis=1,
                keepdims=True,
            )

        if n_f == 1:
            out_ref[...] += weighed(y)
        else:
            @pl.when(f == 0)
            def _first():
                acc_ref[...] = y

            @pl.when(f > 0)
            def _more():
                acc_ref[...] += y

            @pl.when(f == n_f - 1)
            def _whole():
                out_ref[...] += weighed(acc_ref[...])

        pos = pos[1:] + [advance(pos[-1])]
        return ((slot + 1) % nslot, *(v for p in pos for v in p))

    jax.lax.while_loop(
        lambda carry: carry[1] < E, block,
        (jnp.int32(0), *(v for p in ahead for v in p)),
    )
    # we_down's scale: one per output channel, every expert's alike
    out_ref[...] = out_ref[...] * pick(sdbuf[...])


@functools.partial(jax.jit, static_argnames=("interpret", "act"))
def expert_decode_tpu(x, w, ids, counts, layer, wg, wu, wd, sg, su, sd, *,
                      interpret: bool = False, act: str = "silu"):
    """The decode regime as ONE Pallas call (``_decode_kernel``): ``x [n,
    H]``, the rows' router weights ``w [n, k]`` f32 and ids ``[n, k]`` (``E``
    for a pair that adds nothing: a dead row's, an expert's held elsewhere),
    ``counts [E]`` the pairs an expert has → ``[n, H]`` float32: ``Σ_e w_e ·
    MLP_e(x) · scale_down`` over the experts with a pair, in ascending id.
    Every array operand stays in HBM (``memory_space=pl.ANY``) and the body
    copies what it reads: the layer-stacked leaves ``[L, H, E·F]`` / ``[L,
    E·F, H]`` and the ``[L, E·F]`` / ``[L, H]`` scale stacks are read where
    they lie, never sliced. A block of the ring holds ``decode_chunk``
    columns of an expert's width."""
    n, H = x.shape
    E = counts.shape[0]
    gated = act != "relu2"
    F = wu.shape[-1] // E
    fc, slots = decode_chunk(H, F), DECODE_SLOTS

    def whole_tiles(s):
        """A ``[L, n]`` scale stack as the body may slice it: by sublane
        tiles of rows. On the chip an array over four rows deep IS whole
        tiles of eight — the rows past a stack's depth are there, and never
        picked — so the stored stack goes in as it is; the interpreter's
        arrays end where the stack does, and an array of up to four rows (a
        stack of one: 2-D leaves) is tiled by 1, 2 or 4 on the chip: those
        are handed over padded."""
        short = -s.shape[0] % SUBLANES
        if short and (interpret or s.shape[0] <= SUBLANES // 2):
            s = jnp.pad(s, ((0, short), (0, 0)))
        return s

    col_leaves = (wg, wu) if gated else (wu,)
    col_scales = tuple(whole_tiles(s) for s in ((sg, su) if gated else (su,)))
    sd = whole_tiles(sd)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    n_in = 3 + 2 * len(col_leaves) + 2
    return pl.pallas_call(
        functools.partial(_decode_kernel, gated=gated, n_f=F // fc),
        out_shape=jax.ShapeDtypeStruct((n, H), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[hbm] * n_in,
            out_specs=pl.BlockSpec((n, H), lambda i, *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM(x.shape, x.dtype),
                pltpu.VMEM(w.shape, w.dtype),
                pltpu.VMEM(ids.shape, ids.dtype),
                *(pltpu.VMEM((slots, H, fc), q.dtype) for q in col_leaves),
                pltpu.VMEM((slots, fc, H), wd.dtype),
                *(
                    pltpu.VMEM((slots, SUBLANES, fc), s.dtype)
                    for s in col_scales
                ),
                pltpu.VMEM((SUBLANES, H), sd.dtype),
                pltpu.VMEM((n, H), jnp.float32),
                pltpu.SemaphoreType.DMA((slots, 2)),
                pltpu.SemaphoreType.DMA((4,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="moe_experts",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), counts, x, w, ids,
        *col_leaves, wd, *col_scales, sd,
    )


def resolve_backend(backend: str) -> str:
    """``auto`` follows ``PAGED_FORCE_KERNEL`` like the paged attention ops,
    then the platform: the kernel on a TPU, XLA elsewhere."""
    from .paged_attention import forced_backend

    if backend not in BACKENDS:
        raise ValueError(
            f"moe backend {backend!r}: expected one of {BACKENDS}"
        )
    if backend == "auto":
        backend = forced_backend() or (
            "kernel" if jax.default_backend() == "tpu" else "xla"
        )
    if backend == "kernel" and jax.default_backend() != "tpu":
        raise ValueError(
            "moe backend 'kernel' requires a TPU backend (got "
            f"{jax.default_backend()}); use backend='interpret' to emulate "
            "the kernel or 'xla'"
        )
    return backend


def live_pairs(ids, live, E):
    """What XLA computes before the decode call: ``(ids [N, k] with a dead
    row's pairs turned into id E — as a pair held elsewhere is: it matches no
    expert, so it is neither counted nor weighed —, counts [E] the pairs an
    expert has)``."""
    ids = jnp.where(live[:, None], ids, E)
    counts = jnp.sum(
        ids[:, :, None] == jnp.arange(E, dtype=jnp.int32), axis=(0, 1)
    ).astype(jnp.int32)
    return ids, counts


def _decode_product(x, weights, ids, live, lyr, wg, wu, wd, sg, su, sd, E,
                    backend, act, branch):
    """The decode regime: ``(Σ_e w_e · MLP_e(x) · scale_down [N, H] f32,
    counts [E])``. On the kernel backends one call (``expert_decode_tpu``)
    that is handed the live pairs an expert and the rows' weights and ids as
    they are; ``branch`` (the leaves hold a SHARE of the experts) puts a
    ``lax.cond`` around it — most calls of a row or two then meet none
    (without a share a live row always meets one), and a call launched for
    nothing still costs its launch; the branch costs a call that runs ~2
    us. The XLA path builds one tile per distinct expert and gathers."""
    N, H = x.shape
    if backend == "xla":
        tiles, cw, counts = _decode_tiles(x, weights, ids, live, E)
        y = _tiles_xla(tiles, lyr, wg, wu, wd, sg, su, E, jnp.float32, act)
        out = jnp.einsum(
            "jn,jnh->nh", cw, y, precision=jax.lax.Precision.HIGHEST
        )  # (the XLA tiles past n_live are zeros)
        return out * jax.lax.dynamic_index_in_dim(
            sd, lyr, keepdims=False
        ).astype(jnp.float32), counts
    ids, counts = live_pairs(ids, live, E)

    def product():
        return expert_decode_tpu(
            x, weights, ids, counts, lyr, wg, wu, wd, sg, su, sd,
            interpret=backend == "interpret", act=act,
        )

    if not branch:
        return product(), counts
    return jax.lax.cond(
        jnp.sum(counts > 0) > 0, product,  # (the counters' own sum)
        lambda: jnp.zeros((N, H), jnp.float32),
    ), counts


def expert_mlp(
    x,  # [N, H]
    weights,  # [N, k] f32 router weights of the chosen experts
    ids,  # [N, k] int32 the chosen experts
    we_gate, we_up, we_down,  # [H, E·F] / [E·F, H], or layer-stacked [L, ..]
    num_experts: int,
    live=None,  # [N] bool — rows that route; None = all
    layer=None,  # scalar int32 index into layer-stacked weights; None = 2-D
    backend: str = "auto",
    held=None,  # static (first, count): the leaves hold only these experts
    act: str = "silu",  # static, one of ``ACTS``; "relu2": ``we_gate`` None
    zero_from=None,  # static: ids from here to ``num_experts`` have NO weights
):
    """``Σ_k weights[n, k] · MLP_{ids[n, k]}(x[n])`` for the live rows (zero
    for the others) and the layer's ``MoeStats``. With ``held`` the sum is
    over the pairs whose expert is held here (the module docstring). With
    ``zero_from`` the ids ``zero_from … num_experts - 1`` are zero-compute
    experts that return their input: ``+ (Σ_{k: id >= zero_from} w_k) · x``."""
    N, H = x.shape
    E = num_experts
    if zero_from is not None and held is None:
        held = (0, zero_from)  # the real experts, all of them held
    if act not in ACTS or (act == "relu2") != (we_gate is None):
        raise ValueError(
            f"expert activation {act!r}: one of {ACTS}, and 'relu2' (not "
            "gated) goes with we_gate=None, 'silu' with a gate matrix"
        )
    backend = resolve_backend(backend)
    if live is None:
        live = jnp.ones((N,), bool)
    if zero_from is not None:
        # the pairs that cost nothing: no tile in either regime, nothing
        # fetched — their weights summed in float32, for the live rows
        with jax.named_scope("zero_expert"):
            zero_w = jnp.sum(
                jnp.where((ids >= zero_from) & live[:, None], weights, 0.0),
                axis=1,
            )  # [N]
    routed = None
    if held is not None and tuple(held) != (0, E):
        first, E = held
        # every pair routed, per expert of the WHOLE layer: the counters'
        # view; the tiles below see ids relative to the first held expert
        routed = jnp.sum(
            (ids[:, :, None] == jnp.arange(num_experts, dtype=jnp.int32))
            & live[:, None, None], axis=(0, 1),
        ).astype(jnp.int32)
        # an expert held elsewhere becomes id E with weight 0: a dead pair in
        # both regimes
        here = (ids >= first) & (ids < first + E)
        ids = jnp.where(here, ids - first, E)
        weights = jnp.where(here, weights, 0.0)
    # (the gate first: the gated path's program is operation for operation
    # what it was before an expert could be without one)
    wg, sg = (None, None) if we_gate is None else _leaf(we_gate, layer)
    (wu, su), (wd, sd) = (_leaf(w, layer) for w in (we_up, we_down))
    lyr = jnp.zeros((), jnp.int32) if layer is None else layer
    decode = N <= DECODE_ROWS_MAX
    with jax.named_scope("moe"):
        if decode:
            out, counts = _decode_product(
                x, weights, ids, live, lyr, wg, wu, wd, sg, su, sd, E,
                backend, act, branch=routed is not None,
            )
        else:
            tiles, pos, counts = _grouped_tiles(x, ids, live, E, TILE_ROWS)
            if backend == "xla":
                y = _tiles_xla(tiles, lyr, wg, wu, wd, sg, su, E, x.dtype, act)
            else:
                y = expert_tiles_tpu(
                    tiles, lyr, wg, wu, wd, sg, su, E=E, out_dtype=x.dtype,
                    interpret=backend == "interpret", act=act,
                )
            # the kernel leaves the tiles past n_live unwritten: select what
            # is live, THEN weigh it (0 x whatever the buffer held is not 0)
            picked = y.reshape(-1, H)[pos].astype(jnp.float32)
            paired = (live[:, None] & (ids < E))[:, :, None]  # in a live tile
            out = jnp.sum(
                jnp.where(paired, picked * weights[:, :, None], 0.0), axis=1
            )  # [N, k, H] → [N, H]
            # we_down's scale: one per output channel, every expert's alike
            out = out * jax.lax.dynamic_index_in_dim(
                sd, lyr, keepdims=False
            ).astype(jnp.float32)
        if zero_from is not None:
            with jax.named_scope("zero_expert"):
                out = out + zero_w[:, None] * x.astype(jnp.float32)
        stats = MoeStats(
            counts if routed is None else routed,
            jnp.sum(counts > 0).astype(jnp.int32),
        )
        return out.astype(x.dtype), stats
