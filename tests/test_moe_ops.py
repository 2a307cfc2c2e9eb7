"""``ops/moe.py``: the router and the expert product, decode and prefill
regimes, the Pallas kernel in interpret mode and the XLA path of the same
arithmetic, against the DENSE form (every expert computed, the unchosen
multiplied by zero) — over experts, k, rows with dead rows, experts repeated
across rows, raw and int8 weights, a layer of a stack that is not the first;
and over how many of a call's tiles are live (none, one, every one, a chunk
whose pairs all fall on experts held elsewhere): the kernel's grid ends where
the live tiles do and what lies past them is never written."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import llm_sharding_tpu.models  # noqa: F401 — ops import through models
from llm_sharding_tpu.ops import moe
from llm_sharding_tpu.ops.quant import QTensor, dequantize, quantize_tensor


def dense_form(x, weights, ids, wg, wu, wd, E, live, held=None):
    """Σ_e p_e · (silu(x Wg_e) ⊙ (x Wu_e)) Wd_e with p_e = 0 off the kept
    set, all experts computed; zero for dead rows. ``held = (first, count)``:
    the weights are those ``count`` of the ``E`` experts, the sum over them."""
    first, count = held or (0, E)
    N, F = x.shape[0], wg.shape[-1] // count
    mask = jnp.zeros((N, E), jnp.float32).at[
        jnp.arange(N)[:, None], ids].add(weights)[:, first:first + count]
    E = count
    hp = jax.lax.Precision.HIGHEST
    g = jnp.dot(x, wg, precision=hp).reshape(N, E, F)
    u = jnp.dot(x, wu, precision=hp).reshape(N, E, F)
    a = (jax.nn.silu(g) * u * mask[:, :, None]).reshape(N, E * F)
    return jnp.where(live[:, None], jnp.dot(a, wd, precision=hp), 0.0)


def make(seed, N, E, F, H, L, quant):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (N, H), jnp.float32)
    router = jax.random.normal(ks[1], (H, E), jnp.float32)
    wg = jax.random.normal(ks[2], (L, H, E * F), jnp.float32) * H ** -0.5
    wu = jax.random.normal(ks[3], (L, H, E * F), jnp.float32) * H ** -0.5
    wd = jax.random.normal(ks[4], (L, E * F, H), jnp.float32) * F ** -0.5
    live = jax.random.bernoulli(ks[5], 0.6, (N,)).at[N // 2].set(True)
    given = (wg, wu, wd)
    if quant:
        given = tuple(quantize_tensor(w) for w in given)
        wg, wu, wd = (dequantize(w) for w in given)
    return x, router, live, given, (wg, wu, wd)


CASES = [
    # N rows, E experts, k, F, H, layers, int8
    (1, 8, 2, 32, 64, 2, False),    # one live row: k experts read
    (4, 8, 2, 32, 64, 2, False),    # a slot with dead rows
    (4, 8, 8, 32, 64, 1, False),    # k = E: every row repeats every expert
    (3, 16, 4, 128, 128, 3, True),  # int8, a stack of three
    (8, 64, 8, 16, 64, 2, True),    # OLMoE's E and k, N·k = E tiles
    (100, 8, 2, 32, 64, 2, False),  # grouped: more rows than DECODE_ROWS_MAX
    (300, 16, 4, 128, 128, 2, True),  # grouped, several tiles an expert
    (40, 64, 8, 16, 64, 1, False),  # grouped, most experts under one tile
    # how many tiles are live — (rows that route, the experts they may
    # choose, the experts held here, the experts read); the kernel's grid is
    # the live tiles
    (4, 8, 2, 32, 64, 2, False, "none", None, None, 0),  # decode: no tile
    (4, 8, 1, 32, 64, 2, True, "all", (5, 6), None, 1),  # decode: ONE tile
    (4, 8, 2, 32, 64, 2, True, "all", None, None, 8),  # decode: all N·k tiles
    (4, 16, 8, 32, 64, 1, False, "all", None, None, 16),  # decode: all E
    (4, 16, 2, 32, 64, 2, False, "some", (0, 8), (8, 8), 0),  # all elsewhere
    (4, 16, 4, 32, 64, 2, True, "all", None, (4, 8), 8),  # a share, all met
    (100, 16, 2, 32, 64, 2, True, "some", (0, 8), (8, 8), 0),  # grouped: same
    (100, 16, 4, 32, 64, 2, False, "some", None, (4, 8), None),  # a share
    (100, 8, 2, 32, 64, 1, False, "none", None, None, 0),  # grouped: no row
]


def routing(x, router, k, live, rows, among):
    """Router weights and ids with the rows that route and the experts they
    may choose pinned: ``among = (lo, hi)`` keeps every choice inside experts
    ``lo … hi - 1`` (the others' logits pushed far down); ``rows`` "all"
    without it has the rows choose every expert in turn."""
    N, E = x.shape[0], router.shape[1]
    if among is not None:
        inside = (jnp.arange(E) >= among[0]) & (jnp.arange(E) < among[1])
        x = x.at[:, 0].set(1.0)
        router = router.at[0].set(jnp.where(inside, 0.0, -1e4))
    w, ids = moe.route(x, router, k)
    if rows == "all" and among is None:
        ids = (jnp.arange(N * k, dtype=jnp.int32) % E).reshape(N, k)
    live = {"all": jnp.ones((N,), bool), "none": jnp.zeros((N,), bool),
            "some": live}[rows]
    return x, w, ids, live


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize(
    "N,E,k,F,H,L,quant,rows,among,held,read",
    [c if len(c) == 11 else c + ("some", None, None, None) for c in CASES],
)
def test_expert_product_equals_the_dense_form(
    N, E, k, F, H, L, quant, rows, among, held, read, backend
):
    first, count = held or (0, E)
    # the leaves hold the ``count`` experts of ``held``; the router scores E
    x, _, live, given, plain = make(N + E, N, count, F, H, L, quant)
    router = jax.random.normal(jax.random.key(N * E), (H, E), jnp.float32)
    layer = L - 1
    x, w, ids, live = routing(x, router, k, live, rows, among)
    out, stats = moe.expert_mlp(
        x, w, ids, *given, num_experts=E, live=live,
        layer=jnp.int32(layer), backend=backend, held=held,
    )
    want = dense_form(x, w, ids, *(a[layer] for a in plain), E, live, held)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    # the counters: pairs of live rows only, each distinct HELD expert once
    counts = np.zeros(E, int)
    for n in np.flatnonzero(np.asarray(live)):
        counts[np.asarray(ids[n])] += 1
    here = counts[first:first + count]
    assert np.array_equal(np.asarray(stats.expert_tokens), counts)
    assert int(stats.experts_read) == (here > 0).sum() <= min(E, k * live.sum())
    assert counts.sum() == k * int(live.sum())
    if read is not None:  # the case pins how many tiles are live
        assert int(stats.experts_read) == read
        assert np.asarray(out).any() == (read > 0)


@pytest.mark.parametrize("N", [4, 100])  # decode tiles, grouped tiles
@pytest.mark.parametrize("poison", [np.nan, np.inf, 3e38])
def test_what_lies_past_the_live_tiles_never_reaches_the_output(
    N, poison, monkeypatch
):
    """The kernel writes the live tiles only. Whatever the rest of its
    output buffer holds — here every unwritten tile is overwritten with
    ``poison`` between the kernel and the combine, over whatever interpret
    mode left there — the output is finite and the dense form's."""
    E, k = 16, 2
    x, router, live, given, plain = make(N, N, E, 32, 64, 2, False)
    w, ids = moe.route(x, router, k)
    kernel, seen = moe.expert_tiles_tpu, []

    def poisoned(tiles, *args, **kw):
        y = kernel(tiles, *args, **kw)
        dead = jnp.arange(y.shape[0]) >= tiles.n_live
        seen.append((int(tiles.n_live), y.shape[0]))
        return jnp.where(dead[:, None, None], poison, y).astype(y.dtype)

    monkeypatch.setattr(moe, "expert_tiles_tpu", poisoned)
    out, _ = moe.expert_mlp(
        x, w, ids, *given, num_experts=E, live=live, layer=jnp.int32(1),
        backend="interpret",
    )
    (n_live, n_tiles), = seen
    assert 0 < n_live < n_tiles  # there WERE unwritten tiles
    assert np.isfinite(np.asarray(out)).all()
    want = dense_form(x, w, ids, *(a[1] for a in plain), E, live)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_unstacked_weights_and_no_live_mask(backend):
    """A layer handed over already sliced (2-D leaves, ``layer=None``) and no
    mask: every row routes."""
    x, router, _, given, plain = make(3, 5, 8, 32, 64, 2, True)
    one = tuple(QTensor(g.q[1], g.scale[1]) for g in given)
    w, ids = moe.route(x, router, 2)
    out, stats = moe.expert_mlp(x, w, ids, *one, num_experts=8, backend=backend)
    want = dense_form(x, w, ids, *(a[1] for a in plain), 8, jnp.ones(5, bool))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    assert int(stats.expert_tokens.sum()) == 10


def test_no_live_row_reads_and_counts_nothing():
    x, router, _, given, _ = make(9, 4, 8, 32, 64, 1, False)
    w, ids = moe.route(x, router, 2)
    for backend in ("xla", "interpret"):
        out, stats = moe.expert_mlp(
            x, w, ids, *given, num_experts=8, live=jnp.zeros(4, bool),
            layer=jnp.int32(0), backend=backend,
        )
        assert not np.asarray(out).any()
        assert int(stats.experts_read) == 0 and not np.asarray(stats.expert_tokens).any()


def test_router_is_float32_topk_unnormalised_unless_asked():
    ks = jax.random.split(jax.random.key(0), 2)
    x = jax.random.normal(ks[0], (64, 32), jnp.float32)
    router = jax.random.normal(ks[1], (32, 16), jnp.float32)
    w, ids = moe.route(x, router, 4)
    p = jax.nn.softmax(jnp.dot(x, router, precision="highest"), -1)
    order = np.argsort(-np.asarray(p), axis=-1)[:, :4]
    assert np.array_equal(np.asarray(ids), order)
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(np.asarray(p), order, -1), rtol=1e-6)
    assert float(w.sum(-1).max()) < 1.0  # kept as they are
    wn, idn = moe.route(x, router, 4, renormalize=True)
    assert np.array_equal(np.asarray(idn), np.asarray(ids))
    np.testing.assert_allclose(np.asarray(wn.sum(-1)), 1.0, rtol=1e-6)
    # bf16 inputs are multiplied out in float32 all the same
    wb, _ = moe.route(x.astype(jnp.bfloat16), router.astype(jnp.bfloat16), 4)
    assert wb.dtype == jnp.float32


def test_a_hand_built_routing_counts_exactly():
    """Rows 0 and 2 live and choosing {1, 3} and {3, 5}; row 1 dead, choosing
    {0, 7}: three distinct experts read, expert 3 twice."""
    E, F, H = 8, 16, 32
    x, _, _, given, plain = make(1, 3, E, F, H, 1, False)
    ids = jnp.asarray([[1, 3], [0, 7], [3, 5]], jnp.int32)
    w = jnp.asarray([[0.5, 0.25], [0.9, 0.05], [0.4, 0.3]], jnp.float32)
    live = jnp.asarray([True, False, True])
    for backend in ("xla", "interpret"):
        out, stats = moe.expert_mlp(
            x, w, ids, *given, num_experts=E, live=live,
            layer=jnp.int32(0), backend=backend,
        )
        assert np.asarray(stats.expert_tokens).tolist() == [0, 1, 0, 2, 0, 1, 0, 0]
        assert int(stats.experts_read) == 3
        want = dense_form(x, w, ids, *(a[0] for a in plain), E, live)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_backend_names():
    with pytest.raises(ValueError, match="expected one of"):
        moe.resolve_backend("pallas")
    with pytest.raises(ValueError, match="requires a TPU"):
        moe.resolve_backend("kernel")
    assert moe.resolve_backend("xla") == "xla"


# ---- an expert that is NOT gated: relu(x Wu)² Wd (``act="relu2"``) ----------

def plain_loop_relu2(x, weights, ids, wu, wd, E, live, held=None):
    """Σ over the chosen experts, one expert at a time, in plain numpy."""
    first, count = held or (0, E)
    x, wu, wd = (np.asarray(a, np.float64) for a in (x, wu, wd))
    F = wu.shape[-1] // count
    out = np.zeros((x.shape[0], wd.shape[-1]))
    for n in np.flatnonzero(np.asarray(live)):
        for w, e in zip(np.asarray(weights[n]), np.asarray(ids[n])):
            if first <= e < first + count:
                j = e - first
                a = np.maximum(x[n] @ wu[:, j * F:(j + 1) * F], 0.0) ** 2
                out[n] += w * (a @ wd[j * F:(j + 1) * F])
    return out


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("N,E,k,F,H,L,quant,held", [
    (1, 8, 3, 32, 64, 2, False, None),     # one live row
    (4, 8, 3, 24, 32, 2, True, None),      # a slot, int8, a width of 24
    (4, 16, 6, 32, 64, 3, True, (4, 4)),   # a share: 4 held of 16
    (100, 8, 3, 32, 64, 2, False, None),   # grouped tiles
    (70, 16, 6, 32, 64, 1, True, (8, 4)),  # grouped, a share
])
def test_the_relu2_kernel_is_the_xla_path_is_the_plain_loop(
        N, E, k, F, H, L, quant, held, backend):
    first, count = held or (0, E)
    x, _, live, given, plain = make(N + E + 1, N, count, F, H, L, quant)
    router = jax.random.normal(jax.random.key(N * E), (H, E), jnp.float32)
    layer = L - 1
    x, w, ids, live = routing(x, router, k, live, "some", None)
    out, stats = moe.expert_mlp(
        x, w, ids, None, given[1], given[2], num_experts=E, live=live,
        layer=jnp.int32(layer), backend=backend, held=held, act="relu2",
    )
    want = plain_loop_relu2(
        x, w, ids, plain[1][layer], plain[2][layer], E, live, held)
    np.testing.assert_allclose(np.asarray(out), want, atol=5e-5)
    counts = np.zeros(E, int)
    for n in np.flatnonzero(np.asarray(live)):
        counts[np.asarray(ids[n])] += 1
    assert np.array_equal(np.asarray(stats.expert_tokens), counts)
    assert int(stats.experts_read) == (counts[first:first + count] > 0).sum()


def test_the_gated_path_is_bitwise_what_it_was_without_the_argument():
    """``act="silu"`` is the default and the program it was: the same bits
    with and without the keyword, on both paths."""
    x, router, live, given, _ = make(5, 4, 8, 32, 64, 2, True)
    x, w, ids, live = routing(x, router, 2, live, "some", None)
    for backend in ("xla", "interpret"):
        kw = dict(num_experts=8, live=live, layer=jnp.int32(1), backend=backend)
        a, _ = moe.expert_mlp(x, w, ids, *given, **kw)
        b, _ = moe.expert_mlp(x, w, ids, *given, act="silu", **kw)
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_an_activation_goes_with_its_leaves():
    x, router, live, given, _ = make(5, 4, 8, 32, 64, 2, False)
    x, w, ids, live = routing(x, router, 2, live, "some", None)
    with pytest.raises(ValueError, match="relu2"):
        moe.expert_mlp(x, w, ids, *given, num_experts=8, act="relu2")
    with pytest.raises(ValueError, match="relu2"):
        moe.expert_mlp(x, w, ids, None, given[1], given[2], num_experts=8)
    with pytest.raises(ValueError, match="gelu"):
        moe.expert_mlp(x, w, ids, *given, num_experts=8, act="gelu")


@pytest.mark.parametrize("hidden, width, tile", [
    (2048, 1024, 512), (4096, 2048, 512), (7168, 2048, 256),  # as before
    (64, 32, 32), (4096, 1408, 128),
    (1024, 2688, 896),  # nemotron_h's latent experts: 21 x 128 = 3 x 896
    (4096, 2688, 384),
])
def test_the_tile_of_an_experts_width(hidden, width, tile):
    assert moe.f_tile(hidden, width) == tile and width % tile == 0
    if width % min(width, moe.f_chunk(hidden)) == 0:
        assert tile == min(width, moe.f_chunk(hidden))
