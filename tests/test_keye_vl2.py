"""Keye-VL-2.0's language block on the program's paths (``tiny_keye_vl2``-sized:
2 layers, hidden 64, GQA 4 / 2 heads of 16 with a per-head q/k norm, 8 experts,
an indexer of 4 heads x 8 over ONE 8-wide index key a token, ``topk`` 16),
against the ONE plain reference the repo has for it —
``benchmark/blocks/KeyeVL2.py``, through ``benchmark.blocks.load`` — in float32:

- prefill in chunks of 32, then decode one token at a time through the paged
  arenas (K, V and the index keys beside them), contexts to 96: ``topk`` is
  crossed INSIDE the first chunk and again between chunks; LOGITS at every
  position equal the reference's full forward, on both backends (XLA; the
  paged kernels in interpret mode);
- while the context is no longer than ``topk`` the layer equals the llama
  path on the same leaves (the selection is everything);
- the positions a decode step chooses are the reference's;
- a wrong ``theta``, a dropped LayerNorm bias of the index key, a dropped
  ``wI``, the most recent ``topk`` keys in place of the indexer's choice and
  no selection at all each move a logit by far more than the tolerance.

The tolerance: program and reference are both float32 on the CPU and differ in
the order of their sums, 5e-5 on logits of unit scale. The two sides score the
index keys in the same precision here, so their choices agree at every
position but exact ties (none with these weights); on the chip the program's
scores are bf16 products and a few keys at the edge of the top-k differ
(``benchmark/tests/test_keye_vl2_block.py`` counts them).
"""

import dataclasses
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from benchmark import blocks, reference, weights
from llm_sharding_tpu.models import llama
from llm_sharding_tpu.models.cache import POS_SENTINEL, paged_arena_shape
from llm_sharding_tpu.models.config import (
    ModelConfig, tiny_keye_vl2, tiny_keye_vl2_keys,
)
from llm_sharding_tpu.ops import paged_attention as pa

MODEL = tiny_keye_vl2_keys(eos_token_id=1 << 20)  # no reply ends early
CFG = ModelConfig.from_hf_config(MODEL)
BLOCK = blocks.load("KeyeVL2")
TOL = 5e-5
S = 96
IDS = np.random.default_rng(0).integers(0, 255, (S,)).astype(np.int32)
BS, T, B = 8, 16, 2  # blocks of 8 tokens, 16 a row: a window of 128 columns


@pytest.fixture(scope="module")
def params():
    """The block's seeded float32 weights, in the engine's layout."""
    return weights.make_params(BLOCK, MODEL, 5, "f32", jax.devices()[:1])


def ref_logits(params, ids, **wrong):
    tables = {t.name: params[t.name] for t in BLOCK.tables(MODEL)}
    h = reference.hidden_states(
        BLOCK, MODEL, lambda l: jax.tree.map(lambda a: a[l], params["layers"]),
        tables, [ids], **wrong,
    )[0][: len(ids)]
    with jax.default_matmul_precision("highest"):
        return np.asarray(BLOCK.logits(h, tables, **BLOCK.head_static(MODEL)))


def test_the_preset_is_the_models_config():
    cfg = tiny_keye_vl2()
    assert cfg.model_type == "llama"  # flags of the llama block, no new type
    assert cfg.sparse_attn and cfg.qk_norm_per_head and cfg.norm_topk_prob
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (4, 8, 16)
    assert cfg.intermediate_size == 32 and cfg.num_experts == 8
    theirs = jax.eval_shape(
        lambda: llama.init_layer_params(CFG, jax.random.key(0), 1))
    assert {k: v.shape[1:] for k, v in theirs.items()} == {
        l.name: l.shape for l in BLOCK.layer_leaves(MODEL)}


@pytest.mark.parametrize("what, keys, match", [
    ("a second index key a token",
     dict(sa_config=dict(MODEL["sa_config"], indexer_num_kv_heads=2)),
     "ONE index key"),
    ("no topk", dict(sa_config={"indexer_num_heads": 4, "indexer_head_dim": 8}),
     "lacks 'topk'"),
    ("dense MLP layers", dict(mlp_only_layers=[0]), "mlp_only_layers"),
    ("a sliding window", dict(use_sliding_window=True), "sliding-window"),
    ("a scaled rotation", dict(rope_scaling={"rope_type": "yarn", "factor": 4}),
     "rope_scaling"),
])
def test_what_the_block_cannot_honour_is_refused_by_name(what, keys, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_config(dict(MODEL, **keys))


@functools.partial(jax.jit, static_argnames=("cfg", "backend", "prefill"))
def _layers(cfg, params, tokens, k, v, table, cols, kv_pos, positions, live,
            *, backend, prefill):
    with jax.default_matmul_precision("highest"):
        h = llama.embed(params, tokens)
        h, k, v, _, _, _ = llama.forward_layers_paged(
            cfg, params["layers"], h, k, v, table, cols, kv_pos, positions,
            backend=backend, prefill=prefill, moe_live=live,
        )
        return llama.final_logits(cfg, params, h)[0], k, v


class Arenas:
    """K, V and the index keys of ``B`` rows of ``T`` blocks, and one row's
    key positions, driven a chunk or a token at a time."""

    def __init__(self, cfg, params, backend):
        self.cfg, self.params, self.backend = cfg, params, backend
        shape = paged_arena_shape(cfg, 1 + B * T, BS)
        self.k = jnp.zeros(shape, jnp.float32)
        self.v = jnp.zeros(shape, jnp.float32)
        self.idx = jnp.zeros((*shape[:2], 1, BS, cfg.index_cache_dim or 1),
                             jnp.float32)
        self.table = jnp.asarray(1 + np.arange(B * T).reshape(B, T), jnp.int32)
        self.kv_pos = np.full((B, T * BS), POS_SENTINEL, np.int32)

    def step(self, tokens, positions, cols, prefill):
        """Row 0 is live, row 1 dead; returns row 0's logits."""
        real = positions[0] != POS_SENTINEL
        self.kv_pos[0, cols[0][real]] = positions[0][real]
        k_in = (self.k, self.idx) if self.cfg.sparse_attn else self.k
        live = np.zeros(positions.shape, bool)
        live[0] = real
        logits, k_out, self.v = _layers(
            self.cfg, self.params, jnp.asarray(tokens), k_in, self.v,
            self.table, jnp.asarray(cols), jnp.asarray(self.kv_pos),
            jnp.asarray(positions), jnp.asarray(live), backend=self.backend,
            prefill=prefill,
        )
        self.k, self.idx = k_out if self.cfg.sparse_attn else (k_out, self.idx)
        return np.asarray(logits)


def run(cfg, params, backend, ids, prompt, chunk=32):
    """Prefill ``ids[:prompt]`` in chunks (the last padded), then decode the
    rest one token at a time: the logits at every position."""
    a = Arenas(cfg, params, backend)
    logits = []
    with jax.default_matmul_precision("highest"):
        for c0 in range(0, prompt, chunk):
            n = min(chunk, prompt - c0)
            tokens = np.zeros((B, chunk), np.int32)
            tokens[0, :n] = ids[c0:c0 + n]
            positions = np.full((B, chunk), POS_SENTINEL, np.int32)
            positions[0, :n] = np.arange(c0, c0 + n)
            cols = np.broadcast_to(
                c0 + np.arange(chunk, dtype=np.int32), (B, chunk)).copy()
            logits.append(a.step(tokens, positions, cols, True)[:n])
        col = -(-prompt // chunk) * chunk  # decode columns follow the chunks
        for t in range(prompt, len(ids)):
            tok = np.asarray([[ids[t]], [0]], np.int32)
            pos = np.asarray([[t], [POS_SENTINEL]], np.int32)
            cols = np.asarray([[col], [0]], np.int32)
            logits.append(a.step(tok, pos, cols, False)[:1])
            col += 1
    return np.concatenate(logits), a


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_prefill_then_decode_through_the_paged_arenas(params, backend):
    """A 72-token prompt prefilled in chunks of 32 (``topk`` 16 is crossed
    inside the first chunk; the third chunk is 8 tokens and 24 pads; a dead
    second row), then 24 tokens decoded one at a time through the arenas:
    every position's logits equal the reference's full forward pass."""
    got, arenas = run(CFG, params, backend, IDS, 72)
    np.testing.assert_allclose(got, ref_logits(params, IDS), atol=TOL)
    # the index keys landed beside K, in the same blocks, for every layer:
    # the three chunks' 96 columns (the last chunk's 24 pads are written as
    # K's are, at the sentinel position) and the 24 decoded ones behind them
    written = np.abs(np.asarray(arenas.idx)).sum(axis=(2, 4)) > 0  # [L, NB, BS]
    assert written[:, 1:1 + T].reshape(2, -1).sum(axis=1).tolist() == [120, 120]


def test_a_decode_only_request_crosses_topk(params):
    """A 5-token prompt (one padded chunk) and 40 decoded tokens: the
    selection starts mid-decode (position 16 is the first to leave a key
    out), on the XLA path."""
    ids = IDS[:45]
    got, _ = run(CFG, params, "xla", ids, 5)
    np.testing.assert_allclose(got, ref_logits(params, ids), atol=TOL)


def test_while_the_context_fits_topk_the_layer_is_the_llama_path(params):
    """``context <= topk``: the selection is everything, and the layer with
    its indexer equals the llama block on the same leaves without one (the
    olmoe path with a per-head norm) — and the reference with no selection."""
    wide = dataclasses.replace(CFG, index_topk=10_000)
    got, _ = run(wide, params, "xla", IDS, 72)
    plain_cfg = dataclasses.replace(
        CFG, index_topk=0, index_heads=0, index_head_dim=0)
    layers = {k: v for k, v in params["layers"].items()
              if k not in ("wq_idx", "wk_idx", "w_idx", "k_idx_norm",
                           "k_idx_bias")}
    want, _ = run(plain_cfg, dict(params, layers=layers), "xla", IDS, 72)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(
        got, ref_logits(params, IDS, select="all"), atol=TOL)
    # ... and under topk 16 it is NOT: the selection does something
    assert np.abs(got - ref_logits(params, IDS)).max() > 100 * TOL


def test_a_decode_step_chooses_the_references_positions(params):
    """Layer 0's choice at the last position (95): the program's list of
    columns, mapped to positions, is the reference's kept set (no two scores
    tie with these weights)."""
    _, a = run(CFG, params, "xla", IDS, 72)
    p = jax.tree.map(lambda w: w[0], params["layers"])
    x = np.asarray(llama.embed(params, jnp.asarray(IDS)[None]))[0]
    with jax.default_matmul_precision("highest"):
        xn = BLOCK.rms_norm(jnp.asarray(x), p["input_norm"], CFG.rms_norm_eps)
        qi = BLOCK.rotary((xn @ p["wq_idx"]).reshape(S, 4, 8), CFG.rope_theta)
        ki = BLOCK.rotary(BLOCK.layer_norm(
            xn @ p["wk_idx"], p["k_idx_norm"], p["k_idx_bias"],
            CFG.rms_norm_eps)[:, None], CFG.rope_theta)[:, 0]
        wi = (xn @ p["w_idx"]) * 32 ** -0.5
        score = jnp.einsum("h,ht->t", wi[-1], jax.nn.relu(
            jnp.einsum("hd,td->ht", qi[-1], ki)))
        want = np.flatnonzero(np.asarray(BLOCK.keep_topk(score[None], 16))[0])
        # the program, from its arenas: the same index keys, the same query
        sel = pa.Selection(qi[-1][None, None], wi[-1][None, None],
                           jnp.asarray(a.idx), 16)
        tbl, kv = a.table[:1], jnp.asarray(a.kv_pos[:1])
        ok = pa._attendable(tbl, jnp.asarray([[S - 1]]), kv, BS)
        cols, real = pa.select_tokens(
            pa.index_scores(sel, 0, tbl, None, None, ok)[:, 0], 16)
    assert bool(np.asarray(real).all())
    got = np.sort(a.kv_pos[0][np.asarray(cols)[0]])
    assert got.tolist() == want.tolist() and len(want) == 16


def test_a_stale_index_key_is_never_scored(params):
    """The trash block and a block another request left behind hold index
    keys of ANY value (here huge ones): they sit at the sentinel position or
    in a block the row does not own, and the served logits do not move."""
    want, clean = run(CFG, params, "xla", IDS[:60], 40)
    poisoned = Arenas(CFG, params, "xla")
    poisoned.idx = jnp.full_like(poisoned.idx, 1e4)  # every block, trash too
    poisoned.k = jnp.full_like(poisoned.k, 1e4)
    real_step = Arenas.step
    logits = []
    with jax.default_matmul_precision("highest"):
        for c0 in (0, 32):
            n = min(32, 40 - c0)
            tokens = np.zeros((B, 32), np.int32)
            tokens[0, :n] = IDS[c0:c0 + n]
            positions = np.full((B, 32), POS_SENTINEL, np.int32)
            positions[0, :n] = np.arange(c0, c0 + n)
            cols = np.broadcast_to(c0 + np.arange(32, dtype=np.int32),
                                   (B, 32)).copy()
            logits.append(real_step(poisoned, tokens, positions, cols, True)[:n])
        for i, t in enumerate(range(40, 60)):
            logits.append(real_step(
                poisoned, np.asarray([[IDS[t]], [0]], np.int32),
                np.asarray([[t], [POS_SENTINEL]], np.int32),
                np.asarray([[64 + i], [0]], np.int32), False)[:1])
    np.testing.assert_allclose(np.concatenate(logits), want, atol=TOL)


# ---- the selection as a mask over the decode kernel's walk (PR 50) ----------
# ``selected_attention`` at the level of the op, on arenas built by hand: 2 rows
# of 16 blocks of 8 tokens (128 columns), GQA 4 / 2 heads of 16, an indexer of 4
# heads x 8 over a stored key of 128 lanes, ``topk`` 16, float32.

_W, _TOPK, _NH, _NKV, _D, _HI, _DI = T * BS, 16, 4, 2, 16, 4, 8


def _selecting_case(case):
    """``(contexts, q_pos, logical index keys [B, W, Di], poison)`` of a case:
    a row's context is the columns it has written (position = column), ``q_pos``
    its query's position (the sentinel: a dead row)."""
    rng = np.random.default_rng(len(case))
    ctx, poison = [40, 50], False
    ki = rng.standard_normal((2, _W, _DI)).astype(np.float32)
    qi = rng.standard_normal((2, _HI, _DI)).astype(np.float32)
    wi = rng.uniform(0.5, 1.5, (2, _HI)).astype(np.float32)
    if case == "many scores tied at 0 across the topk-th place":
        # every product of a key with a query is negative but for five keys a
        # row: relu makes the others' scores exactly 0, on every backend
        qi, ki = np.abs(qi), -np.abs(ki)
        for b in range(2):
            ki[b, rng.choice(ctx[b], 5, replace=False)] *= -1
    elif case == "a dead row beside a live one":
        ctx = [0, 50]
    elif case == "a row under topk beside a row past it":
        ctx = [10, 50]
    elif case == "stale keys in a freed block and in the trash block":
        ctx, poison = [44, 50], True  # 44: four stale columns in the last block
    elif case == "non-finite index keys in the trash block":
        ctx, poison = [44, 50], "nan"
    elif case == "the query's own token not among the chosen":
        qi, ki = np.abs(qi), np.abs(ki)
        for b in range(2):
            ki[b, ctx[b] - 1] *= -1  # the newest key scores 0, the others > 0
    elif case == "the chosen in two blocks, the other blocks whole unchosen":
        # columns 8-23 (table entries 1 and 2) outscore every other: the
        # kernel's copies bring five blocks of which no column is kept
        qi, ki = np.abs(qi), np.abs(ki)
        ki[:, BS:3 * BS] *= 100.0
    else:
        assert case == "no ties"
    q_pos = [c - 1 if c else POS_SENTINEL for c in ctx]
    return ctx, q_pos, qi, wi, ki, poison


SELECTING = [
    "no ties", "many scores tied at 0 across the topk-th place",
    "a dead row beside a live one", "a row under topk beside a row past it",
    "stale keys in a freed block and in the trash block",
    "non-finite index keys in the trash block",
    "the query's own token not among the chosen",
    "the chosen in two blocks, the other blocks whole unchosen",
]


def _selecting_arenas(case):
    """A case's logical ``k``, ``v`` ``[B, W, Nkv, D]`` and queries ``[B, 1,
    Nh, D]``, and layer 1 of the three arenas holding them (and the case's
    index keys) through the table: a row's blocks in order, the rest of its
    table the trash block; every slot nothing wrote holds the case's poison
    (the trash block's index keys ``inf`` and ``nan`` where it is "nan")."""
    ctx, _, _, _, ki, poison = _selecting_case(case)
    rng = np.random.default_rng(7)
    k = rng.standard_normal((2, _W, _NKV, _D)).astype(np.float32)
    v = rng.standard_normal((2, _W, _NKV, _D)).astype(np.float32)
    q = rng.standard_normal((2, 1, _NH, _D)).astype(np.float32)
    fill = 1e4 if poison else 0.0
    L, NB = 2, 1 + 2 * T
    ka = np.full((L, NB, _NKV, BS, _D), fill, np.float32)
    va = np.full((L, NB, _NKV, BS, _D), fill, np.float32)
    ia = np.full((L, NB, 1, BS, 128), fill, np.float32)
    if poison == "nan":
        ia[:, 0, :, ::2], ia[:, 0, :, 1::2] = np.inf, np.nan
    table = np.zeros((2, T), np.int32)
    kv_pos = np.full((2, _W), POS_SENTINEL, np.int32)
    for b in range(2):
        nb = -(-ctx[b] // BS)
        table[b, :nb] = 1 + b * T + np.arange(nb)  # the rest: the trash block
        kv_pos[b, :ctx[b]] = np.arange(ctx[b])
        for c in range(ctx[b]):
            blk, slot = table[b, c // BS], c % BS
            ka[1, blk, :, slot], va[1, blk, :, slot] = k[b, c], v[b, c]
            ia[1, blk, 0, slot] = 0.0
            ia[1, blk, 0, slot, :_DI] = ki[b, c]
    return k, v, q, ka, va, ia, table, kv_pos


@pytest.mark.parametrize("width", [1, 2, 8, 16])
@pytest.mark.parametrize("case", SELECTING)
def test_the_score_kernels_scores_choose_the_list_at_every_cell_width(
        case, width):
    """The score kernel (``index_scores_tpu``, interpret mode) over a case's
    index arena at one, two, eight and sixteen blocks a cell — sixteen, eight,
    two cells and one a row: its scores on the attendable columns are the
    ones computed here in numpy, and ``select_mask`` over them keeps the very
    columns ``select_tokens`` lists of the numpy scores (ties at the
    ``topk``-th score included: a ``relu``'s zeros are exact on every
    path)."""
    ctx, q_pos, qi, wi, ki, _ = _selecting_case(case)
    *_, ia, table, kv_pos = _selecting_arenas(case)
    table, kv_pos = jnp.asarray(table), jnp.asarray(kv_pos)
    q_pos = jnp.asarray(q_pos, jnp.int32)[:, None]
    raw = pa.index_scores_tpu(
        jnp.pad(jnp.asarray(qi), [(0, 0), (0, 0), (0, 128 - _DI)]),
        jnp.asarray(wi), jnp.asarray(ia), 1, table, q_pos, kv_pos,
        interpret=True, blocks_per_cell=width)
    assert np.isfinite(np.asarray(raw)).all()
    ok = pa._attendable(table, q_pos, kv_pos, BS)[:, 0]
    keep = np.asarray(pa.select_mask(jnp.where(ok, raw, -jnp.inf), _TOPK))
    for b in range(2):
        score = np.full(_W, -np.inf, np.float32)
        score[:ctx[b]] = np.einsum(
            "h,hw->w", wi[b], np.maximum(qi[b] @ ki[b, :ctx[b]].T, 0.0))
        np.testing.assert_allclose(
            np.asarray(raw)[b, :ctx[b]], score[:ctx[b]], rtol=1e-6, atol=TOL)
        assert not np.asarray(ok)[b, ctx[b]:].any()
        cols, real = (np.asarray(a) for a in pa.select_tokens(
            jnp.asarray(score), _TOPK))
        if case == "no ties" or case.startswith("many scores tied"):
            assert sorted(np.flatnonzero(keep[b])) == sorted(cols[real])
        else:  # the sums' order may move a score by a digit: the count
            assert keep[b].sum() == real.sum()


@pytest.mark.parametrize("case", SELECTING)
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_a_selecting_decode_step_attends_the_list_select_tokens_gives(
        backend, case):
    """``selected_attention`` — the mask as key positions, through
    ``paged_attention`` — against a plain softmax over the LIST
    ``select_tokens`` makes of scores computed here in numpy: the same set
    (ties at the ``topk``-th score go to the lowest columns), nothing of a
    column outside it, whatever a block the row does not own holds."""
    ctx, q_pos, qi, wi, ki, _ = _selecting_case(case)
    k, v, q, ka, va, ia, table, kv_pos = _selecting_arenas(case)
    select = pa.Selection(
        jnp.asarray(qi)[:, None], jnp.asarray(wi)[:, None], jnp.asarray(ia),
        _TOPK)
    got = np.asarray(pa.selected_attention(
        jnp.asarray(q), jnp.asarray(ka), jnp.asarray(va), 1,
        jnp.asarray(table), jnp.asarray(q_pos, jnp.int32)[:, None],
        jnp.asarray(kv_pos), select, backend=backend))
    assert np.isfinite(got).all()
    for b in range(2):
        if not ctx[b]:
            continue  # a dead row: whatever it reads is nobody's
        score = np.full(_W, -np.inf, np.float32)
        score[:ctx[b]] = np.einsum(
            "h,hw->w", wi[b], np.maximum(qi[b] @ ki[b, :ctx[b]].T, 0.0))
        cols, real = (np.asarray(a) for a in pa.select_tokens(
            jnp.asarray(score), _TOPK))
        cols = cols[real]
        assert len(cols) == min(ctx[b], _TOPK)
        if case.startswith("many scores tied"):
            assert (score[cols] == 0).sum() == _TOPK - 5
        if case.startswith("the query's own"):
            assert ctx[b] - 1 not in cols
        if case.startswith("the chosen in two blocks"):
            assert sorted(cols) == list(range(BS, 3 * BS))
        for h in range(_NH):
            logit = k[b, cols, h // 2] @ q[b, 0, h] * _D ** -0.5
            p = jax.nn.softmax(jnp.asarray(logit))
            np.testing.assert_allclose(
                got[b, 0, h], np.asarray(p) @ v[b, cols, h // 2], atol=TOL)


@pytest.mark.parametrize("side", ["chosen", "everything"])
@pytest.mark.parametrize("case", SELECTING)
def test_a_selecting_decode_step_stores_its_entry_in_the_attention_call(
        case, side):
    """``paged_attention_write(select=)`` on the kernel path (interpreted):
    the step's fresh K/V rides into the ONE ``paged_decode`` after
    ``selected_attention``'s ``cond`` and is stored there, on both of the
    ``cond``'s sides (``chosen``: a row's context is past ``topk``;
    ``everything``: every context cut to twelve tokens, no score taken) —
    the output the same kernel's over the arena ``write_block_kv`` leaves,
    bit for bit, and both arenas that scatter's over every owned block. Also
    where the selection keeps no column of the fresh entry's block (the
    query's own token not chosen; the chosen in two early blocks): the
    kernel's walk is stretched to that block, which adds nothing to the
    softmax, so that the entry lands all the same."""
    ctx, q_pos, qi, wi, ki, _ = _selecting_case(case)
    _, _, q, ka, va, ia, table, kv_pos = _selecting_arenas(case)
    if side == "everything":
        ctx = [min(c, 12) for c in ctx]
        q_pos = [c - 1 if c else POS_SENTINEL for c in ctx]
        for b in range(2):
            kv_pos[b, ctx[b]:] = POS_SENTINEL
            table[b, -(-ctx[b] // BS):] = 0
    rng = np.random.default_rng(61)
    k_new, v_new = (jnp.asarray(
        rng.standard_normal((2, 1, _NKV, _D)), jnp.float32) for _ in "kv")
    # the fresh entry is the query's own token; a dead row's lands in trash
    cols = jnp.asarray([[max(c - 1, 0)] for c in ctx], jnp.int32)
    select = pa.Selection(
        jnp.asarray(qi)[:, None], jnp.asarray(wi)[:, None], jnp.asarray(ia),
        _TOPK)
    args = (jnp.asarray(table), jnp.asarray(q_pos, jnp.int32)[:, None],
            jnp.asarray(kv_pos))
    ok = pa._attendable(*args, BS)
    assert bool(jnp.any(jnp.sum(ok, axis=-1) > _TOPK)) == (side == "chosen")
    out, k, v, _, _ = pa.paged_attention_write(
        jnp.asarray(q), k_new, v_new, jnp.asarray(ka), jnp.asarray(va), 1,
        args[0], cols, *args[1:], backend="interpret", select=select)
    k_w, v_w = pa.write_block_kv(
        jnp.asarray(ka), jnp.asarray(va), 1, args[0], cols, k_new, v_new)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(
        pa.selected_attention(
            jnp.asarray(q), k_w, v_w, 1, *args, select, backend="interpret")))
    np.testing.assert_allclose(np.asarray(out), np.asarray(
        pa.selected_attention(
            jnp.asarray(q), k_w, v_w, 1, *args, select, backend="xla")),
        atol=TOL)
    for got, want, new in ((k, k_w, k_new), (v, v_w, v_new)):
        np.testing.assert_array_equal(
            np.asarray(got)[:, 1:], np.asarray(want)[:, 1:])
        for b in range(2):
            if ctx[b]:
                np.testing.assert_array_equal(
                    np.asarray(got)[
                        1, table[b, (ctx[b] - 1) // BS], :, (ctx[b] - 1) % BS],
                    np.asarray(new)[b, 0])


def sorted_mask(scores, topk):
    """``select_mask`` as it was before PR 51, from ``select_tokens``' sorted
    list: above the list's last score, and of the columns that tie with it
    (IEEE ``==``) the lowest, by a running count."""
    cols, _ = pa.select_tokens(scores, topk)
    kth = jnp.take_along_axis(scores, cols[..., -1:], axis=-1)
    above = scores > kth
    tie = (scores == kth) & (scores > -jnp.inf)
    left = cols.shape[-1] - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= left))


def _random_scores(shape, ties):
    """Random scores, a third of them forced to tie at 0 (a sum of ``relu``s)
    or queries with fewer attendable keys than ``topk``."""
    rng = np.random.default_rng(len(ties) + len(shape))
    scores = rng.standard_normal(shape).astype(np.float32)
    if ties == "at the topk-th place":
        scores = np.maximum(scores, 0.0)  # about half the columns tie at 0
        scores[..., :7] = 3.0  # ... and seven tie above them
    scores[..., 12 if ties == "fewer keys than topk" else 80:] = -np.inf
    return scores, 60 if ties == "at the topk-th place" else 24


def _signed_zeros():
    # a sum of relus under a NEGATIVE index weight: -0.0 and +0.0 mixed, the
    # largest scores of the row, more of them than topk
    rng = np.random.default_rng(51)
    scores = -np.abs(rng.standard_normal((3, 96))).astype(np.float32)
    zero = rng.random((3, 96)) < 0.5
    scores[zero] = np.where(rng.random(zero.sum()) < 0.5, 0.0, -0.0)
    scores[..., 90:] = -np.inf
    return scores, 24


def _negative_at_the_threshold():
    rng = np.random.default_rng(52)
    scores = rng.standard_normal((2, 5, 96)).astype(np.float32)
    scores[..., rng.random(96) < 0.4] = -0.75  # the topk-th score, tied
    return scores, 70


def _a_dead_row():
    scores = np.random.default_rng(53).standard_normal((4, 96))
    scores = np.round(scores.astype(np.float32), 1)  # ties everywhere
    scores[[0, 2, 3]] = -np.inf
    return scores, 24


def _the_cells_slot():
    # keye_vl2_30b_a3b.longgen: a slot of four rows over a 9,216-column
    # window, one of them live at a context of 2,900, a third of its columns
    # 0: under 2,048 are positive, so the zeros tie across the topk-th place
    rng = np.random.default_rng(54)
    scores = np.full((4, 9216), -np.inf, np.float32)
    scores[1, :2900] = np.abs(rng.standard_normal(2900))
    scores[1, :2900][rng.random(2900) < 1 / 3] = 0.0
    return scores, 2048


MASK_CASES = {
    **{f"ties {ties}, {len(shape)} dims": (
        lambda s=shape, t=ties: _random_scores(s, t))
       for shape in [(3, 96), (2, 5, 96)]
       for ties in ["at the topk-th place", "none", "fewer keys than topk"]},
    "-0.0 and +0.0 tied across the topk-th place": _signed_zeros,
    "negative scores at the threshold": _negative_at_the_threshold,
    "all scores equal": lambda: (np.full((3, 96), 0.5, np.float32), 24),
    "a dead row beside a live one": _a_dead_row,
    "topk >= W": lambda: _random_scores((3, 96), "none")[:1] + (96,),
    "topk past W": lambda: _random_scores((3, 96), "none")[:1] + (200,),
    "topk 1": lambda: (np.round(np.random.default_rng(55).standard_normal(
        (3, 96)).astype(np.float32), 1), 1),
    # the ``recent`` control's input: the column index as float32
    "distinct increasing scores": lambda: (
        np.tile(np.arange(96, dtype=np.float32), (2, 5, 1)), 24),
    "the cell's slot": _the_cells_slot,
}


@pytest.mark.parametrize("case", list(MASK_CASES))
def test_the_mask_is_the_set_of_the_list(case):
    """``select_mask`` keeps exactly the columns ``select_tokens`` lists
    (those that are real choices), with and without a leading query dim: the
    search (PR 51) against the sort. Scores tie by IEEE ``==`` — the oracle
    reads the two zeros as one — and the set is the one the sort-based mask
    kept, bit for bit."""
    scores, topk = MASK_CASES[case]()
    keep = np.asarray(pa.select_mask(jnp.asarray(scores), topk))
    cols, real = (np.asarray(a) for a in pa.select_tokens(
        jnp.asarray(np.where(scores == 0, np.float32(0), scores)), topk))
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.where(real, cols, cols[..., :1]), True, axis=-1)
    want &= scores > -np.inf  # a dead row's first column is no choice
    assert keep.sum(axis=-1).tolist() == real.sum(axis=-1).tolist()
    assert (keep == want).all()
    live = (scores > -np.inf).sum(axis=-1)
    assert (keep.sum(axis=-1) == np.minimum(topk, live)).all()
    assert (keep == np.asarray(sorted_mask(jnp.asarray(scores), topk))).all()


def _random_slot(shape, live=None, seed=58):
    scores = np.random.default_rng(seed).standard_normal(shape)
    scores = scores.astype(np.float32)
    if live is not None:
        scores[[b for b in range(shape[0]) if b != live]] = -np.inf
    return scores


def _zeros_of_both_signs(shape=(2, 256), topk=64):
    # a tenth of the columns positive, the others zeros of either sign: the
    # topk-th score is a zero and far more columns tie with it than are left
    rng = np.random.default_rng(59)
    scores = np.where(rng.random(shape) < 0.5, 0.0, -0.0).astype(np.float32)
    few = rng.random(shape) < 0.1
    scores[few] = np.abs(rng.standard_normal(few.sum())) + 1e-3
    return scores, topk


KERNEL_CASES = {
    "random scores at the cell's [4, 9216]": lambda: (
        _random_slot((4, 9216)), 2048),
    "random scores at a small [2, 256]": lambda: (_random_slot((2, 256)), 64),
    "one live row and three of -inf": lambda: (
        _random_slot((4, 256), live=2), 64),
    "fewer finite columns than topk": lambda: (
        np.where(np.arange(256) < 40, _random_slot((2, 256)), -np.inf), 64),
    "all columns equal": lambda: (np.full((2, 256), 0.5, np.float32), 64),
    "zeros of both signs tying across the threshold": _zeros_of_both_signs,
    "negative scores only": lambda: (-np.abs(_random_slot((2, 256))) - 1, 64),
    "topk >= W": lambda: (_random_slot((2, 256)), 256),
    "topk past W": lambda: (_random_slot((2, 128)), 200),
    "ties at the topk-th place, sixteen rows": lambda: (
        np.round(_random_slot((16, 128)), 1), 24),
    # the ``recent`` control's input: the column index as float32
    "distinct increasing scores": lambda: (
        np.tile(np.arange(256, dtype=np.float32), (2, 1)), 64),
    "the cell's slot": _the_cells_slot,
}


def _the_lists_set(scores, topk):
    """``select_tokens``' real choices as a mask (the two zeros made one for
    the sort, which reads -0.0 below +0.0)."""
    cols, real = (np.asarray(a) for a in pa.select_tokens(
        jnp.asarray(np.where(scores == 0, np.float32(0), scores)), topk))
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.where(real, cols, cols[..., :1]), True, axis=-1)
    return want & (scores > -np.inf)  # a dead row's first column is no choice


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_kernels_mask_is_the_set_of_the_list(case):
    """The search as ONE Pallas call (``select_topk_tpu``, PR 58: what a
    decode step's few queries run on the chip), emulated: exactly the columns
    ``select_tokens`` lists, and the XLA search's mask bit for bit — above
    the ``topk``-th score, the LOWEST columns of a tie with it, never a
    ``-inf`` column, a dead row all False."""
    scores, topk = KERNEL_CASES[case]()
    keep = np.asarray(pa.select_topk_tpu(
        jnp.asarray(scores), topk, interpret=True))
    assert keep.dtype == bool and keep.shape == scores.shape
    assert (keep == _the_lists_set(scores, topk)).all()
    assert (keep == np.asarray(pa.select_mask(jnp.asarray(scores), topk))).all()
    live = (scores > -np.inf).sum(axis=-1)
    assert (keep.sum(axis=-1) == np.minimum(topk, live)).all()


def test_the_tying_cases_reach_the_second_search():
    """More columns tie with the ``topk``-th score than are left to keep in
    the cases that say so: the kernel's second search (the last column kept)
    runs there, and only there."""
    for case, many in (
        ("zeros of both signs tying across the threshold", True),
        ("all columns equal", True), ("the cell's slot", True),
        ("random scores at a small [2, 256]", False),
    ):
        scores, topk = KERNEL_CASES[case]()
        kth = np.sort(scores, axis=-1)[:, -topk][:, None]
        ties = ((scores == kth) & (scores > -np.inf)).sum(axis=-1)
        left = topk - (scores > kth).sum(axis=-1)
        assert (ties > left).any() == many, case


@pytest.mark.parametrize("bits", [1, 2, 4])
def test_the_kernels_mask_at_every_width_of_a_pass(bits):
    """1, 2 and 4 bits a pass (``chip_smoke.py --select`` times them beside
    the 3 the kernel takes) find the same set: the passes tile the key's 32
    bits and a column's from bit 0 up, and a digit that reaches past the top
    bit holds nothing."""
    for case in ("zeros of both signs tying across the threshold",
                 "one live row and three of -inf", "negative scores only"):
        scores, topk = KERNEL_CASES[case]()
        keep = np.asarray(pa.select_topk_tpu(
            jnp.asarray(scores), topk, bits=bits, interpret=True))
        assert (keep == _the_lists_set(scores, topk)).all(), case


def test_the_search_takes_the_form_its_call_can_observe(monkeypatch):
    """``select_mask`` chooses from the shape and the backend alone: the
    kernel for a decode step's few queries over whole lane tiles that VMEM
    holds — emulated where ``PAGED_FORCE_KERNEL`` says so, leading dims and
    all — and the XLA search for a chunk's many queries, a window that is no
    whole number of lane tiles, and every call off the TPU."""
    assert pa.select_path((4,), 9216) == "xla"  # the CPU
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "interpret")
    assert pa.select_path((4,), 9216) == "interpret"
    assert pa.select_path((1, 16), 256) == "interpret"
    assert pa.select_path((1, 256), 9216) == "xla"  # a chunk's queries
    assert pa.select_path((4,), 96) == "xla"  # no whole lane tile
    assert pa.select_path((16,), 1 << 16) == "xla"  # past what VMEM holds
    assert pa.select_path((0,), 128) == "xla"
    calls = []
    kernel = pa.select_topk_tpu
    monkeypatch.setattr(
        pa, "select_topk_tpu",
        lambda s, k, **kw: calls.append((s.shape, kw)) or kernel(s, k, **kw))
    scores, topk = _negative_at_the_threshold()  # [2, 5, 96]
    scores = np.pad(scores, [(0, 0), (0, 0), (0, 32)], constant_values=-np.inf)
    keep = np.asarray(pa.select_mask(jnp.asarray(scores), topk))
    assert calls == [((10, 128), {"interpret": True})]
    assert (keep == _the_lists_set(scores, topk)).all()
    monkeypatch.setenv("PAGED_FORCE_KERNEL", "xla")
    assert pa.select_path((4,), 9216) == "xla"
    assert (keep == np.asarray(pa.select_mask(jnp.asarray(scores), topk))).all()
    assert len(calls) == 1


WRONG = {
    "a wrong theta": dict(theta=1e6),
    "the index key's LayerNorm bias dropped": dict(index_bias=False),
    "wI dropped": dict(index_weights=False),
    "the most recent topk keys": dict(select="recent"),
    "no selection": dict(select="all"),
}


@pytest.mark.parametrize("what", list(WRONG))
def test_a_wrong_model_reads_not_correct(params, what):
    """Each of these is a model the program must NOT be: its logits lie far
    outside the tolerance the program is held to (4 x and much more)."""
    err = np.abs(
        ref_logits(params, IDS, **WRONG[what]) - ref_logits(params, IDS)
    ).max()
    assert err > 100 * TOL, (what, err)


def test_the_dense_cache_path_refuses_by_name(params):
    from llm_sharding_tpu.models.cache import init_cache

    cache = init_cache(CFG, 1, capacity=32, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="paged arenas only"):
        llama.forward(CFG, params, jnp.asarray(IDS[:8])[None], cache,
                      jnp.arange(8)[None])


def test_the_converter_refuses_a_keye_checkpoint_by_name():
    """The names of its tensors are in no file here: nothing is guessed."""
    from llm_sharding_tpu.utils import convert

    with pytest.raises(NotImplementedError, match="KeyeVL2.*names of a Keye"):
        convert.params_from_hf(CFG, {}, jnp.float32)
