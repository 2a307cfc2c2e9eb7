"""The paged kernels' three arguments a windowed model brought: a LOWER
bound on the walk (cells behind the window are not in the grid, the cell the
edge cuts is masked), a per-head sink logit in the softmax's denominator and
a value narrower than its key — interpret mode against a plain numpy
attention over the same arena."""

import numpy as np
import pytest
import jax.numpy as jnp

import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
from llm_sharding_tpu.models.cache import POS_SENTINEL
from llm_sharding_tpu.ops import paged_attention as pa

from paged_arena import window as logical_window

L, NB, BS, T = 2, 40, 4, 16


def plain(q, k, v, qpos, kvpos, scale, window, sink):
    """q [S, Nh, D], k [W, Nkv, D], v [W, Nkv, Dv] of one row, float64."""
    S, Nh, _ = q.shape
    G = Nh // k.shape[1]
    out = np.zeros((S, Nh, v.shape[-1]))
    for s in range(S):
        if qpos[s] >= POS_SENTINEL:
            continue
        keep = kvpos <= qpos[s]
        if window:
            keep &= kvpos > qpos[s] - window
        for h in range(Nh):
            sc = (k[keep, h // G] @ q[s, h]) * scale
            logits = sc if sink is None else np.append(sc, sink[h])
            p = np.exp(logits - logits.max())
            p = p / p.sum()
            out[s, h] = p[: sc.shape[0]] @ v[keep, h // G]
    return out


def arena(rng, Nkv, D, Dv):
    k = jnp.asarray(rng.normal(size=(L, NB, Nkv, BS, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, NB, Nkv, BS, Dv)), jnp.float32)
    return k, v


def rows(rng, lengths, freed):
    """Tables and key positions of rows holding ``lengths`` tokens, the
    first ``freed`` entries of each given back to the pool (trash)."""
    B = len(lengths)
    tbl = np.zeros((B, T), np.int32)
    kvpos = np.full((B, T * BS), POS_SENTINEL, np.int32)
    free = list(rng.permutation(np.arange(1, NB)))
    for b, (n, f) in enumerate(zip(lengths, freed)):
        for t in range(f, -(-n // BS)):
            tbl[b, t] = free.pop()
        kvpos[b, :n] = np.arange(n)
    return tbl, kvpos


@pytest.mark.parametrize("window, use_sink, Dv, bps", [
    (0, False, 8, None), (6, False, 16, None), (0, True, 16, None),
    (6, True, 8, None), (13, True, 8, None),
    # cells narrower than the table: a windowed row's walk starts at a cell
    # past the table's first (``first`` 6 of 8, 3 of 4), its first copies
    # started by the row before it, and the window's edge cuts a cell
    (6, True, 8, 2), (13, False, 16, 4), (13, True, 8, 2), (0, True, 8, 4),
])
def test_decode_kernel_lower_bound_sink_and_value_width(
        window, use_sink, Dv, bps):
    """The decode kernel's copies by hand (the table's blocks a shuffle of
    the pool) under each of the three arguments, alone and together, the
    whole table one cell (``bps`` None: what the shapes give) and in cells
    of 2 and 4 blocks."""
    rng = np.random.default_rng(3)
    Nkv, G, D = 2, 2, 16
    Nh = Nkv * G
    k_a, v_a = arena(rng, Nkv, D, Dv)
    lengths = [37, 9, 0, 64]
    # blocks wholly behind the window are gone from a windowed row's table
    freed = [max((n - window) // BS, 0) if window else 0 for n in lengths]
    tbl, kvpos = rows(rng, lengths, freed)
    qpos = np.asarray([[n - 1] if n else [POS_SENTINEL] for n in lengths],
                      np.int32)
    q = jnp.asarray(rng.normal(size=(4, 1, Nh, D)), jnp.float32)
    sink = rng.normal(size=(Nh,)).astype(np.float32) if use_sink else None
    kw = dict(window=window, sink=None if sink is None else jnp.asarray(sink))
    got = pa.paged_attention_tpu(
        q, k_a, v_a, 1, jnp.asarray(tbl), jnp.asarray(qpos),
        jnp.asarray(kvpos), 0.25, interpret=True, blocks_per_step=bps, **kw,
    )
    if window and bps:
        first = pa._first_blocks(
            jnp.asarray(tbl), jnp.asarray(qpos), jnp.asarray(kvpos), window,
            pa._live_blocks(jnp.asarray(tbl), jnp.asarray(qpos),
                            jnp.asarray(kvpos)))
        assert int(first[3]) // bps > 0
    xla = pa.paged_attention_xla(
        q, k_a, v_a, 1, jnp.asarray(tbl), jnp.asarray(qpos),
        jnp.asarray(kvpos), 0.25, **kw,
    )
    kw_, vw_ = logical_window(k_a, 1, tbl), logical_window(v_a, 1, tbl)
    for b, n in enumerate(lengths):
        want = plain(np.asarray(q[b], np.float64), kw_[b], vw_[b], qpos[b],
                     kvpos[b], 0.25, window, sink)
        np.testing.assert_allclose(got[b], want, atol=2e-5)
        if n:
            np.testing.assert_allclose(xla[b], want, atol=2e-5)
    assert got.shape == (4, 1, Nh, Dv)


def test_decode_walk_starts_at_the_windows_first_cell():
    """Cells behind the window are not in the grid: the walk of a long row
    is as long as the window, whatever the context."""
    rng = np.random.default_rng(5)
    tbl, kvpos = rows(rng, [61, 61], [0, 0])
    qpos = np.asarray([[60], [60]], np.int32)
    nlive = pa._live_blocks(jnp.asarray(tbl), jnp.asarray(qpos),
                            jnp.asarray(kvpos))
    first = pa._first_blocks(jnp.asarray(tbl), jnp.asarray(qpos),
                             jnp.asarray(kvpos), 8, nlive)
    assert list(np.asarray(nlive)) == [16, 16]
    assert list(np.asarray(first)) == [13, 13]  # keys 53..60: entries 13-15


@pytest.mark.parametrize("window, use_sink", [(0, True), (6, False), (10, True)])
def test_prefill_kernel_lower_bound_sink_and_value_width(window, use_sink):
    rng = np.random.default_rng(7)
    Nkv, G, D, Dv, S = 2, 2, 16, 8, 8
    Nh = Nkv * G
    k_a, v_a = arena(rng, Nkv, D, Dv)
    # a chunk of 8 queries at the end of what is written; row 1 is short
    # (its chunk half padding), row 2 dead
    written = [40, 13, 0]
    tbl, kvpos = rows(rng, written, [0, 0, 0])
    qpos = np.full((3, S), POS_SENTINEL, np.int32)
    qpos[0] = np.arange(32, 40)
    qpos[1, :5] = np.arange(8, 13)
    q = jnp.asarray(rng.normal(size=(3, S, Nh, D)), jnp.float32)
    sink = rng.normal(size=(Nh,)).astype(np.float32) if use_sink else None
    kw = dict(window=window, sink=None if sink is None else jnp.asarray(sink))
    got = pa.paged_prefill_tpu(
        q, k_a, v_a, 0, jnp.asarray(tbl), jnp.asarray(qpos),
        jnp.asarray(kvpos), 0.25, interpret=True, blocks_per_step=2, **kw,
    )
    kw_, vw_ = logical_window(k_a, 0, tbl), logical_window(v_a, 0, tbl)
    for b in range(3):
        want = plain(np.asarray(q[b], np.float64), kw_[b], vw_[b], qpos[b],
                     kvpos[b], 0.25, window, sink)
        np.testing.assert_allclose(got[b], want, atol=2e-5)
    if window:
        walk = pa.prefill_walk(
            jnp.asarray(tbl), jnp.asarray(qpos), jnp.asarray(kvpos),
            q_heads=Nh, kv_heads=Nkv, window=window, blocks_per_step=2,
        )
        full = pa.prefill_walk(
            jnp.asarray(tbl), jnp.asarray(qpos), jnp.asarray(kvpos),
            q_heads=Nh, kv_heads=Nkv, blocks_per_step=2,
        )
        assert int(walk.steps) < int(full.steps)


def test_cached_attention_window_and_sink_match_the_plain_form():
    from llm_sharding_tpu.ops.attention import cached_attention

    rng = np.random.default_rng(11)
    B, S, C, Nkv, G, D, Dv = 1, 5, 24, 2, 2, 8, 4
    q = rng.normal(size=(B, S, Nkv * G, D)).astype(np.float32)
    k = rng.normal(size=(B, C, Nkv, D)).astype(np.float32)
    v = rng.normal(size=(B, C, Nkv, Dv)).astype(np.float32)
    kvpos = np.arange(C, dtype=np.int32)[None]
    qpos = np.arange(19, 24, dtype=np.int32)[None]
    sink = rng.normal(size=(Nkv * G,)).astype(np.float32)
    got = cached_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(qpos),
        jnp.asarray(kvpos), 0.3, window=7, sink=jnp.asarray(sink),
    )
    want = plain(q[0].astype(np.float64), k[0], v[0], qpos[0], kvpos[0],
                 0.3, 7, sink)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
