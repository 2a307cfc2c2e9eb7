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


@pytest.mark.parametrize("N", [100])  # grouped tiles (decode: the ring, below)
@pytest.mark.parametrize("poison", [np.nan, np.inf, 3e38])
def test_what_lies_past_the_live_tiles_never_reaches_the_output(
    N, poison, monkeypatch
):
    """The grouped kernel writes the live tiles only. Whatever the rest of
    its output buffer holds — here every unwritten tile is overwritten with
    ``poison`` between the kernel and the combine, over whatever interpret
    mode left there — the output is finite and the dense form's. (The decode
    call has no tile output since PR 63: what it must not read is its ring
    of VMEM buffers as the call before left them —
    ``test_the_decode_call_reads_nothing_its_ring_held_before``.)"""
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
    return plain_loop(
        x, weights, ids, None, wu, wd, live, held or (0, E), "relu2", None)


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


@pytest.mark.parametrize("hidden, width, chunk", [
    (2048, 1024, 256), (2048, 768, 256), (7168, 2048, 128), (4096, 2048, 128),
    (6144, 2048, 128), (1024, 2688, 384), (4096, 256, 128),
    (64, 32, 32), (64, 1024, 1024), (32, 24, 24),  # toy shapes: one block
])
def test_the_chunk_of_a_decode_block(hidden, width, chunk):
    """An ``H x chunk`` int8 block of the decode call's ring is at most half a
    MiB where 128 columns allow it, and whole chunks make up the width."""
    assert moe.decode_chunk(hidden, width) == chunk and width % chunk == 0
    assert hidden * chunk <= max(moe.DECODE_BLOCK_BYTES, hidden * 128)


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


# ---- the decode call (PR 63): one kernel that fetches by hand ---------------

def plain_loop(x, weights, ids, wg, wu, wd, live, held, act, zero_from):
    """Σ over a live row's chosen experts, one expert at a time, in float64
    numpy: a held expert's MLP (``act``), the row itself for an id from
    ``zero_from``, nothing for an expert held elsewhere."""
    first, count = held
    x, wu, wd = (np.asarray(a, np.float64) for a in (x, wu, wd))
    wg = None if wg is None else np.asarray(wg, np.float64)
    F = wu.shape[-1] // count
    out = np.zeros((x.shape[0], wd.shape[-1]))
    for n in np.flatnonzero(np.asarray(live)):
        for w, e in zip(np.asarray(weights[n]), np.asarray(ids[n])):
            if zero_from is not None and e >= zero_from:
                out[n] += w * x[n]
            elif first <= e < first + count:
                cols = slice((e - first) * F, (e - first + 1) * F)
                u = x[n] @ wu[:, cols]
                if act == "relu2":
                    a = np.maximum(u, 0.0) ** 2
                else:
                    g = x[n] @ wg[:, cols]
                    a = g / (1.0 + np.exp(-g)) * u
                out[n] += w * (a @ wd[cols])
    return out


#: a decode call: rows, the router's width, k, an expert's width, hidden, the
#: stack's depth and the layer read (None: 2-D leaves), the activation, the
#: share held, where the zero-compute ids start, which rows route and among
#: which experts, int8
DECODE = dict(N=4, E=16, k=4, F=32, H=64, L=2, layer=1, act="silu", held=None,
              zero=None, rows="some", among=None, quant=True, read=None)
DECODE_CASES = {
    "one-row": dict(N=1),
    "four-rows": dict(),
    "32-rows": dict(N=32, k=2),
    "32-rows-every-expert": dict(N=32, k=8, rows="all", read=16),
    "no-tile": dict(rows="none", read=0),
    "one-tile": dict(k=1, rows="all", among=(5, 6), read=1),
    "every-tile": dict(rows="all", read=16),
    "all-held-elsewhere": dict(k=2, among=(0, 8), held=(8, 8), read=0),
    "a-share": dict(held=(4, 8), rows="all", read=8),
    "relu2-one-row": dict(N=1, act="relu2", k=3),
    "relu2-a-share": dict(act="relu2", k=6, held=(4, 4), L=3, layer=2),
    "relu2-32-rows": dict(N=32, act="relu2", k=3),
    "relu2-width-24": dict(act="relu2", F=24, H=32, E=8, k=3),
    "zero-experts": dict(zero=12, k=6),
    "zero-experts-a-share": dict(zero=12, held=(4, 4), k=6),
    "zero-experts-only": dict(zero=12, k=2, among=(12, 16)),
    # an expert's width in two blocks: decode_chunk(4096, 256) = 128
    "two-chunks": dict(H=4096, F=256, E=4, k=2),
    "two-chunks-relu2": dict(H=4096, F=256, E=4, k=2, act="relu2", N=3),
    "raw-weights": dict(quant=False),
    "unstacked": dict(layer=None),
    "unstacked-raw": dict(layer=None, quant=False, N=5),
    # stacks whose depth is not whole sublane tiles of scale rows
    "depth-2-first": dict(L=2, layer=0),
    "depth-12-first": dict(L=12, layer=0),
    "depth-12-middle": dict(L=12, layer=5),
    "depth-12-last": dict(L=12, layer=11),
    "depth-17-first": dict(L=17, layer=0),
    "depth-17-middle": dict(L=17, layer=8),
    "depth-17-last": dict(L=17, layer=16, N=32, k=2),
    "depth-16-last": dict(L=16, layer=15),  # whole tiles
}


def decode_case(name):
    """A case's operands: ``(call(backend) -> (out, stats), the plain loop's
    output, the counters' (pairs per expert, held experts read))``."""
    c = dict(DECODE, **DECODE_CASES[name])
    N, E, k, L, layer = c["N"], c["E"], c["k"], c["L"], c["layer"]
    real = c["zero"] or E  # the experts with weights, of the router's E
    held = c["held"] or (0, real)
    x, _, live, given, plain = make(
        N + E + k, N, held[1], c["F"], c["H"], L, c["quant"])
    router = jax.random.normal(jax.random.key(N * E), (c["H"], E), jnp.float32)
    x, w, ids, live = routing(x, router, k, live, c["rows"], c["among"])
    if layer is None:  # the last layer's leaves, handed over 2-D
        given = tuple(
            QTensor(g.q[-1], g.scale[-1]) if c["quant"] else g[-1]
            for g in given)
    at = L - 1 if layer is None else layer
    relu2 = c["act"] == "relu2"

    def call(backend):
        return moe.expert_mlp(
            x, w, ids, None if relu2 else given[0], given[1], given[2],
            num_experts=E, live=live,
            layer=None if layer is None else jnp.int32(layer),
            backend=backend, held=c["held"], act=c["act"],
            zero_from=c["zero"],
        )

    want = plain_loop(
        x, w, ids, None if relu2 else plain[0][at], plain[1][at],
        plain[2][at], live, held, c["act"], c["zero"])
    counts = np.zeros(E, int)
    for n in np.flatnonzero(np.asarray(live)):
        counts[np.asarray(ids[n])] += 1
    read = int((counts[held[0]:held[0] + held[1]] > 0).sum())
    assert c["read"] in (None, read), (c["read"], read)
    return call, want, (counts, read)


@pytest.mark.parametrize("name", sorted(DECODE_CASES))
def test_the_decode_call_is_the_xla_path_is_the_plain_loop(name):
    """The decode regime's ONE kernel (emulated) against the XLA path of the
    same arithmetic and a plain loop over the pairs: rows 1 / 4 / 32, no
    tile, one, every one, all held elsewhere, both activations, a share,
    zero-compute ids, an expert's width in two chunks, and the layer's scale
    rows picked at the first, a middle and the LAST layer of stacks 2, 12, 16
    and 17 deep (a copy brings whole sublane tiles of rows: the last tile of
    a stack 12 or 17 deep ends past the stack)."""
    call, want, (counts, read) = decode_case(name)
    got, stats = call("interpret")
    ref, stats_x = call("xla")
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
    for st in (stats, stats_x):
        assert np.array_equal(np.asarray(st.expert_tokens), counts)
        assert int(st.experts_read) == read
    if read == 0 and DECODE_CASES[name].get("zero") is None:
        assert not np.asarray(got).any()


@pytest.mark.parametrize("poison", [np.nan, np.inf, 3e38])
@pytest.mark.parametrize(
    "name", ["four-rows", "raw-weights", "two-chunks", "depth-12-last"])
def test_the_decode_call_reads_nothing_its_ring_held_before(
        name, poison, monkeypatch):
    """Every VMEM buffer of the decode call — the ring of weight blocks and
    scale tiles, the rows, the accumulator — starts as ``poison`` (the
    interpreter hands a kernel its scratch as ``uninitialized_value`` says:
    the chip hands it what the call before left) and the output is bit for
    bit what it is over the interpreter's own fill, finite, and the plain
    loop's: nothing is read that this call did not fetch."""
    from jax._src.pallas import primitives

    call, want, _ = decode_case(name)
    clean, _ = call("interpret")
    fill, planted = primitives.uninitialized_value, []

    def poisoned(shape, dtype):
        if jnp.issubdtype(dtype, jnp.floating):
            planted.append(tuple(shape))
            return jnp.full(shape, poison, dtype)
        if dtype == jnp.int8:  # a ring of int8 codes
            planted.append(tuple(shape))
            return jnp.full(shape, 127, dtype)
        return fill(shape, dtype)

    monkeypatch.setattr(primitives, "uninitialized_value", poisoned)
    # the fill is read when the kernel is traced: trace it anew, outside the
    # jit cache that holds the clean call's program
    monkeypatch.setattr(
        moe, "expert_decode_tpu", moe.expert_decode_tpu.__wrapped__)
    got, _ = call("interpret")
    slots = moe.DECODE_SLOTS
    assert any(len(sh) == 3 and sh[0] == slots for sh in planted), planted
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(clean))
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)
