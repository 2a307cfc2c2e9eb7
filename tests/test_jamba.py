"""``jamba`` on the CPU at tiny widths (``tiny_jamba``: five Mamba-1 mixers of
128 channels — a state of 4 a channel, a step rank of 3 — and one attention
layer INSIDE the period of 4, four query heads over ONE key/value head; a
gated MLP in every layer; a tied head): the program's LOGITS over the whole
forward against the plain float32 reference of ``benchmark/blocks/jamba.py``
(the recurrence position by position); the controls that must FAIL that
tolerance; the scan in time (XLA and the kernel, interpreted) against the
step applied position by position across one, two and more chunks; a
right-padded chunk leaving the state of its last real token and the conv's
tail at its last real inputs; a dead row's state bit-for-bit untouched by a
decode step; the parameter count at the published widths; what the
configuration reads and refuses, by name. The engine and the server:
``tests/test_jamba_serve.py``."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_sharding_tpu.models import jamba
from llm_sharding_tpu.models.config import (
    JAMBA_KEYS_NOT_READ, ModelConfig, jamba2_3b, jamba2_3b_keys, tiny_jamba,
    tiny_jamba_keys,
)
from llm_sharding_tpu.models.stack import zero_recurrent
from llm_sharding_tpu.ops import ssm

KEYS = tiny_jamba_keys()
CFG = tiny_jamba()
# float32 on both sides, matmuls at ``highest``: the two differ by the order
# of their sums only (7.7e-6 read here over logits of ~3); the other models'
# 3e-4. A bf16 state reads 2.7e-2, a dropped norm 2.2, a dropped skip 5.3
TOL = 3e-4


@pytest.fixture(scope="module")
def params():
    p = jamba.init_params(CFG, jax.random.key(3), jnp.float32)
    k = jax.random.key(4)
    for kind, stack in p["layers"].items():  # gains off one
        for i, name in enumerate(sorted(stack)):
            if name.endswith("norm"):
                stack[name] = stack[name] + 0.2 * jax.random.normal(
                    jax.random.fold_in(k, i), stack[name].shape)
    return p


def reference_logits(params, ids, keys=KEYS, **overrides):
    """The benchmark's plain reference over one sequence."""
    from benchmark import blocks, reference, weights

    block = blocks.load("jamba")
    kinds = blocks.kinds(block, keys)
    tables = {k: params[k] for k in ("embed", "final_norm")}
    hidden = reference.hidden_states(
        block, keys, lambda l: weights.take_layer(params["layers"], kinds, l),
        tables, [ids], **overrides,
    )[0][:len(ids)]
    return np.asarray(block.logits(hidden, tables, **block.head_static(keys)))


def system_logits(params, ids, cfg=CFG, backend="xla"):
    with jax.default_matmul_precision("highest"):
        logits, rec = jamba.forward_full(
            cfg, params, jnp.asarray([ids]), backend
        )
    return np.asarray(logits[0]), rec


IDS = np.random.default_rng(5).integers(0, 250, size=43).astype(np.int32)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_logits_match_the_plain_reference(params, backend):
    got, _ = system_logits(params, IDS, backend=backend)
    want = reference_logits(params, IDS)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("wrong", [
    {"state_round": jnp.bfloat16},
    {"use_conv_bias": False},
    {"use_skip": False},
    {"use_dt_norm": False},
    {"use_b_norm": False},
    {"use_c_norm": False},
    {"use_dt_bias": False},
    {"use_gate": False},
])
def test_a_wrong_model_fails_the_tolerance(params, wrong):
    got, _ = system_logits(params, IDS)
    assert np.abs(got - reference_logits(params, IDS, **wrong)).max() > 4 * TOL


def test_the_head_is_the_embedding_table(params):
    """No ``lm_head`` leaf: the logits contract against ``embed``; an untied
    configuration of the same widths gets its own table."""
    assert CFG.tie_word_embeddings and "lm_head" not in params
    untied = tiny_jamba(tie_word_embeddings=False)
    assert "lm_head" in jamba.init_params(untied, jax.random.key(0))


def _mixer_inputs(seed, B, S, ds=4, di=128):
    k = jax.random.split(jax.random.key(seed), 8)
    return dict(
        x=jax.random.normal(k[0], (B, S, di)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, S, di)) - 1.0),
        z=jax.random.normal(k[2], (B, S, di)),
        A=-jnp.exp(jax.random.normal(k[3], (ds, di))),
        Bm=jax.random.normal(k[4], (B, S, ds)),
        Cm=jax.random.normal(k[5], (B, S, ds)),
        D=jax.random.normal(k[6], (di,)),
        s0=jax.random.normal(k[7], (B, ds, di)),
    )


def _sequential(m, dt=None, upto=None):
    dt = m["dt"] if dt is None else dt
    s, ys = m["s0"], []
    for t in range(m["x"].shape[1] if upto is None else upto):
        y, s = ssm.scan_step(s, m["x"][:, t], dt[:, t], m["z"][:, t], m["A"],
                             m["Bm"][:, t], m["Cm"][:, t], m["D"])
        ys.append(y)
    return (jnp.stack(ys, 1) if ys else None), s


def _rows(s):  # [B, ds, di] → the carried array [1, B, ds, 8, di / 8]
    return s.reshape(1, *s.shape[:2], 8, s.shape[2] // 8)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("chunks", [(1,), (16,), (16, 16), (16, 16, 16, 5)])
def test_the_scan_in_time_is_the_step_applied_position_by_position(
        chunks, backend):
    """Across one position (a decode step), one chunk, two chunks and more
    (the last ragged): the stored state is the carry from chunk to chunk."""
    S = sum(chunks)
    m = _mixer_inputs(len(chunks), 2, S)
    want_y, want_s = _sequential(m)
    s_all, ys, at = _rows(m["s0"]), [], 0
    order, n = jnp.arange(2), jnp.int32(2)
    for c in chunks:
        sl = slice(at, at + c)
        y, s_all = ssm.scan_rows(
            s_all, (0, 0), order, n, m["x"][:, sl], m["dt"][:, sl],
            m["z"][:, sl], m["A"], m["Bm"][:, sl], m["Cm"][:, sl], m["D"],
            backend=backend,
        )
        ys.append(y)
        at += c
    assert np.abs(jnp.concatenate(ys, 1) - want_y).max() < 2e-5
    assert np.abs(s_all.reshape(want_s.shape) - want_s).max() < 2e-5


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_the_decode_entry_is_the_scan_at_one_position(backend):
    """``ssm_step_rows`` — the entry Mamba-2's decode step takes — advances a
    Mamba-1 state (``A [state, channels]``) by ``scan_step``."""
    m = _mixer_inputs(9, 3, 1)
    want_y, want_s = _sequential(m)
    y, s_all = ssm.ssm_step_rows(
        _rows(m["s0"]), (0, 0), jnp.arange(3), jnp.int32(3), m["x"][:, 0],
        m["dt"][:, 0], m["A"], m["Bm"][:, 0], m["Cm"][:, 0], m["D"],
        backend=backend, z=m["z"][:, 0],
    )
    assert np.abs(y - want_y[:, 0]).max() < 2e-5
    assert np.abs(s_all.reshape(want_s.shape) - want_s).max() < 2e-5


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("n_real", [0, 1, 7, 13, 16])
def test_a_right_padded_chunk_leaves_the_state_of_its_last_real_token(
        params, n_real, backend):
    """Through the mixer itself (``mamba_mixer``): the state AND the conv's
    tail after a chunk of 16 whose first ``n_real`` positions are real are
    those after ``n_real`` single steps — exactly the old ones at 0."""
    p = jax.tree.map(lambda a: a[0], params["layers"]["mamba"])
    k = jax.random.split(jax.random.key(n_real), 3)
    h = jax.random.normal(k[0], (2, 16, CFG.hidden_size))
    rec = zero_recurrent(CFG, 1, 2)
    s0 = jax.random.normal(k[1], rec["ssm"].shape)
    c0 = jax.random.normal(k[2], rec["conv"].shape)[0]
    live = jnp.broadcast_to(jnp.arange(16)[None] < n_real, (2, 16))
    _, s_pad, c_pad = jamba.mamba_mixer(
        CFG, p, h, s0, (0, 0), c0, live, backend
    )
    s, c = s0, c0
    one = jnp.ones((2, 1), bool)
    for t in range(n_real):
        _, s, c = jamba.mamba_mixer(
            CFG, p, h[:, t:t + 1], s, (0, 0), c, one, backend
        )
    if n_real == 0:
        assert np.array_equal(np.asarray(s_pad), np.asarray(s0))
        assert np.array_equal(np.asarray(c_pad), np.asarray(c0))
    assert np.abs(s_pad - s).max() < 2e-5
    assert np.abs(c_pad - c).max() < 1e-6


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_a_dead_rows_state_is_untouched_by_a_decode_step(params, backend):
    """Bit for bit: rows 1 and 3 of a slot of four are live; the others'
    state — and every other layer's and slot's — is what it was, and their
    conv tails too."""
    p = jax.tree.map(lambda a: a[1], params["layers"]["mamba"])
    k = jax.random.split(jax.random.key(11), 3)
    rec = zero_recurrent(CFG, 3, 6)
    s0 = jax.random.normal(k[0], rec["ssm"].shape)
    c0 = jax.random.normal(k[1], (4, *rec["conv"].shape[2:]))
    h = jax.random.normal(k[2], (4, 1, CFG.hidden_size))
    live = jnp.array([False, True, False, True])[:, None]
    h1, s1, c1 = jamba.mamba_mixer(CFG, p, h, s0, (1, 2), c0, live, backend)
    s0, s1 = np.asarray(s0), np.asarray(s1)
    changed = np.zeros(s0.shape[:2], bool)
    changed[1, [3, 5]] = True  # layer 1, rows 2 + 1 and 2 + 3
    assert np.array_equal(s1[~changed], s0[~changed])
    assert np.abs(s1[changed] - s0[changed]).max() > 1e-3
    assert np.array_equal(np.asarray(c1)[[0, 2]], np.asarray(c0)[[0, 2]])
    # a dead row's read-out is zero: its hidden state is the residual alone
    assert np.array_equal(np.asarray(h1)[[0, 2]], np.asarray(h)[[0, 2]])


# ---- a decode step's mixer as ONE kernel (``ssm.mixer_step_rows``) ----------

def whole_leaves(stack):
    from llm_sharding_tpu.models.stack import MAMBA1_WHOLE_KEYS

    return {k: stack[k] for k in MAMBA1_WHOLE_KEYS}


def split_step(stack, l, row0, live, s_all, c_all, xz_h):
    """The oracle: ``_mixer_in`` and ``ssm.scan_step`` over the slot's rows,
    each array of their own — ``(y, state, tail)`` of the slot."""
    B = live.shape[0]
    p = jax.tree.map(lambda a: a[l], stack)
    x, z, dt, A, Bm, Cm, tail = jamba._mixer_in(
        CFG, p, xz_h, c_all[l, row0:row0 + B], live[:, None]
    )
    s = s_all[l, row0:row0 + B]
    y, s1 = ssm.scan_step(
        s.reshape(B, CFG.ssm_state_size, -1), x[:, 0], dt[:, 0], z[:, 0], A,
        Bm[:, 0], Cm[:, 0], p["D"],
    )
    return jnp.where(live[:, None], y, 0.0), s1.reshape(s.shape), tail


def fused_step(stack, l, row0, order, n_live, s_all, c_all, h, tiles=None):
    """``ssm.mixer_step_rows``; with ``tiles``, the kernel at that tiling."""
    p = jax.tree.map(lambda a: a[l], stack)
    xz = jamba._w_in(CFG, p, h)[:, 0]
    args = (
        s_all, c_all, (jnp.int32(l), jnp.int32(row0)), jnp.asarray(order),
        jnp.int32(n_live), xz, whole_leaves(stack),
    )
    if tiles is None:
        return ssm.mixer_step_rows(
            *args, jamba._decay(p), CFG.rms_norm_eps, backend="interpret"
        )
    return ssm.mixer_step_tpu(
        *args, jamba._decay(p).reshape(CFG.recurrent_shapes["ssm"]),
        eps=CFG.rms_norm_eps, tiles=tiles, interpret=True,
    )


def carried(seed, rows=12):
    stack_layers = CFG.layer_kinds.count("mamba")
    k = jax.random.split(jax.random.key(seed), 3)
    rec = zero_recurrent(CFG, stack_layers, rows)
    return (
        jax.random.normal(k[0], rec["ssm"].shape),
        jax.random.normal(k[1], rec["conv"].shape),
        jax.random.normal(k[2], (4, 1, CFG.hidden_size)),
    )


def check_fused_step(stack, live, order, n_live, **kw):
    """Layer 3 of the stack's 5, rows 4-7 of 12: the fused call against the
    oracle, and everything it must not touch bit for bit."""
    l, row0 = 3, 4
    s0, c0, h = carried(21)
    counted = jnp.asarray(live, bool) & (n_live > 0)
    y_w, s_w, c_w = split_step(stack, l, row0, counted, s0, c0, h)
    y, s1, c1 = fused_step(stack, l, row0, order, n_live, s0, c0, h, **kw)
    at = np.asarray(counted)
    assert np.abs(np.asarray(y) - np.asarray(y_w)).max() < 2e-5
    assert np.array_equal(np.asarray(y)[~at], np.zeros_like(y)[~at])
    slot_s, slot_c = np.asarray(s1[l, row0:row0 + 4]), np.asarray(c1[l, row0:row0 + 4])
    assert np.abs(slot_s[at] - np.asarray(s_w)[at]).max(initial=0) < 2e-6
    assert np.array_equal(slot_c[at], np.asarray(c_w)[at])
    still = np.ones(s0.shape[:2], bool)
    still[l, row0:row0 + 4] = ~at
    assert np.array_equal(np.asarray(s1)[still], np.asarray(s0)[still])
    assert np.array_equal(np.asarray(c1)[still], np.asarray(c0)[still])
    if at.any():
        assert np.abs(slot_s[at] - np.asarray(s0[l, row0:row0 + 4])[at]).max() > 1e-3


@pytest.mark.parametrize("live, order, n_live", [
    ([0, 0, 0, 0], [0, 1, 2, 3], 0),
    ([0, 0, 1, 0], [2, 0, 1, 3], 1),
    ([1, 0, 0, 1], [3, 0, 2, 1], 2),
    ([1, 0, 0, 1], [0, 3, 1, 2], 2),
    ([1, 1, 1, 1], [2, 0, 3, 1], 4),
    ([1, 1, 1, 1], [2, 0, 3, 1], 0),  # a masked layer: live rows, none counted
])
def test_the_fused_mixer_step_is_the_split_steps(params, live, order, n_live):
    """``ssm.mixer_step_rows`` (interpreted) against ``_mixer_in`` +
    ``ssm.scan_step``: 0, 1, 2 and 4 live rows of a slot of 4 in a shuffled
    ``order``, a first row that is not 0, a layer that is not the stack's
    first, a masked layer; a dead row's state AND tail, every other layer and
    slot bit for bit what they were; a dead row's ``y`` zero."""
    check_fused_step(params["layers"]["mamba"], live, order, n_live)


@pytest.mark.parametrize("tiles", [1, 2, 4, 8])
def test_the_fused_mixer_step_at_every_channel_tiling(params, tiles):
    """The two phases over 1, 2, 4 and 8 channel tiles a row: the same step
    (``w_x`` reduces over all channels whatever the tiling)."""
    check_fused_step(
        params["layers"]["mamba"], [1, 0, 1, 1], [3, 0, 2, 1], 3, tiles=tiles
    )


def test_sixty_four_chained_fused_steps_hold_the_tolerance(params):
    """64 decode steps of one layer, the state and the tail carried by each
    side for itself: ``y`` of every step and the state at the end within the
    float32 logits tolerance (sound ~1e-5)."""
    stack = params["layers"]["mamba"]
    l, row0 = 2, 4
    live = jnp.array([True, False, True, True])
    order, n_live = jamba.live_rows(live[:, None])
    s_f, c_f, _ = carried(22)
    s_w, c_w = s_f, c_f
    fused = jax.jit(lambda s, c, h: fused_step(
        stack, l, row0, order, n_live, s, c, h))

    @jax.jit
    def split(s_all, c_all, h):
        y, s, c = split_step(stack, l, row0, live, s_all, c_all, h)
        sel = live[:, None, None]
        return (
            y,
            s_all.at[l, row0:row0 + 4].set(
                jnp.where(sel[..., None], s, s_all[l, row0:row0 + 4])),
            c_all.at[l, row0:row0 + 4].set(c),
        )

    worst = 0.0
    for t in range(64):
        h = jax.random.normal(jax.random.key(100 + t), (4, 1, CFG.hidden_size))
        y_f, s_f, c_f = fused(s_f, c_f, h)
        y_w, s_w, c_w = split(s_w, c_w, h)
        worst = max(worst, float(jnp.abs(y_f - y_w).max()))
    assert worst < TOL
    assert float(jnp.abs(s_f - s_w).max()) < TOL
    assert np.array_equal(np.asarray(c_f), np.asarray(c_w))
    assert float(jnp.abs(s_f[l, row0] - carried(22)[0][l, row0]).max()) > 1e-2


def whole_layer(stack, l):
    """A layer's leaves as the serve programs' scan hands them."""
    from llm_sharding_tpu.models.stack import join_whole, split_whole

    scanned, whole = split_whole(stack)
    return join_whole(jax.tree.map(lambda a: a[l], scanned), whole, jnp.int32(l))


def test_a_dead_rows_state_and_tail_are_untouched_by_the_fused_step(params):
    """``mixer_block`` with the leaves handed whole, interpreted: the fused
    form is taken, it is ``mamba_mixer``'s step, and rows 0 and 2 of the slot
    — their state AND their conv tails — and every other layer and slot
    come back bit for bit."""
    stack = params["layers"]["mamba"]
    s0, c0, h = carried(23, rows=6)
    p = whole_layer(stack, 1)
    assert jamba.mixer_step_fused(CFG, p, "interpret")
    assert not jamba.mixer_step_fused(CFG, p, "xla")
    live = jnp.array([False, True, False, True])[:, None]
    at = (jnp.int32(1), jnp.int32(2))
    h1, s1, c1 = jamba.mixer_block(CFG, p, h, s0, c0, at, live,
                                   backend="interpret")
    h2, s2, c2 = jamba.mixer_block(
        CFG, jax.tree.map(lambda a: a[1], stack), h, s0, c0, at, live,
        backend="interpret",
    )
    changed = np.zeros(s0.shape[:2], bool)
    changed[1, [3, 5]] = True
    for got, was in ((s1, s0), (c1, c0)):
        got, was = np.asarray(got), np.asarray(was)
        assert np.array_equal(got[~changed], was[~changed])
        assert np.abs(got[changed] - was[changed]).max() > 1e-3
    assert np.array_equal(np.asarray(h1)[[0, 2]], np.asarray(h)[[0, 2]])
    assert np.abs(np.asarray(h1) - np.asarray(h2)).max() < 2e-5
    assert np.abs(np.asarray(s1) - np.asarray(s2)).max() < 2e-6
    assert np.array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.parametrize("what", ["quantised", "shape", "xla", "sliced"])
def test_what_the_fused_step_cannot_take_falls_back(params, what, monkeypatch):
    """The form is chosen from what the code can see: int8 ``w_x`` / ``w_dt``,
    a shape Mosaic cannot tile under the compiled kernel, the XLA backend and
    leaves that come as a layer's slices each take the split path — and the
    quantised stack still runs through ``mixer_block``."""
    from llm_sharding_tpu.ops.quant import quantize_params

    stack = params["layers"]["mamba"]
    if what == "quantised":
        q = quantize_params({"layers": {"mamba": stack}})["layers"]["mamba"]
        assert not isinstance(q["w_x"], jax.Array)
        p = whole_layer(q, 1)
        assert ssm.mixer_step_path("interpret", CFG, q) == "split"
        assert not jamba.mixer_step_fused(CFG, p, "interpret")
        s0, c0, h = carried(24, rows=4)
        live = jnp.ones((4, 1), bool)
        at = (jnp.int32(1), jnp.int32(0))
        h1, s1, c1 = jamba.mixer_block(CFG, p, h, s0, c0, at, live,
                                       backend="interpret")
        # ... as the same leaves do when they come as the layer's slices
        h2, s2, c2 = jamba.mixer_block(
            CFG, jax.tree.map(lambda a: a[1], q), h, s0, c0, at, live,
            backend="interpret",
        )
        for got, want in ((h1, h2), (s1, s2), (c1, c2)):
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
        assert np.abs(np.asarray(s1) - np.asarray(s0)).max() > 1e-3
    elif what == "shape":
        monkeypatch.setattr(ssm.jax, "default_backend", lambda: "tpu")
        assert ssm.mixer_step_path("kernel", CFG) == "split"
        assert ssm.mixer_step_path("kernel", jamba2_3b()) == "fused"
        assert not ssm.mixer_eligible(16, 8, 640, 4)
    elif what == "xla":
        assert ssm.mixer_step_path("xla", CFG) == "split"
        assert ssm.mixer_step_path("xla", jamba2_3b()) == "split"
        assert ssm.mixer_step_path("interpret", CFG) == "fused"
    else:
        p = jax.tree.map(lambda a: a[1], stack)
        assert not jamba.mixer_step_fused(CFG, p, "interpret")


def test_only_a_mamba_1_stack_hands_its_mixer_leaves_whole(params):
    """The whole-leaf rule is keyed by ``w_x``: Jamba's mixer stack is split
    (the nine leaves the kernel reads through the layer index), its attention
    stack is not, and ``nemotron_h``'s Mamba-2 stack — which HAS leaves called
    ``conv_w``, ``conv_b``, ``A_log``, ``D`` — comes back as it is."""
    from llm_sharding_tpu.models import nemotron_h
    from llm_sharding_tpu.models.config import tiny_nemotron_h
    from llm_sharding_tpu.models.stack import MAMBA1_WHOLE_KEYS, split_whole

    scanned, whole = split_whole(params["layers"]["mamba"])
    assert set(whole) == set(MAMBA1_WHOLE_KEYS)
    assert not set(scanned) & set(whole)
    assert {"w_in", "w_out", "A_log", "norm", "w_gate"} <= set(scanned)
    attn = params["layers"]["attn"]
    assert split_whole(attn) == (attn, None)
    cfg2 = tiny_nemotron_h()
    for kind, stack in nemotron_h.init_layer_params(
            cfg2, jax.random.key(0), 2, jnp.float32).items():
        scanned, whole = split_whole(stack)
        if kind == "mamba":
            assert {"conv_w", "conv_b", "A_log", "D"} <= set(stack)
            assert scanned is stack and whole is None
        else:
            assert not set(whole or ()) & set(MAMBA1_WHOLE_KEYS)


def test_the_parameter_count_at_the_published_widths():
    """3.03 B from the layer equations: 26 mixer layers of 104.16 M (the
    mixer and its norm 41.24 M), 2 attention layers of 76.68 M (13.77 M), the
    MLP and its norm 62.92 M in each, one tied table of 167.8 M; 6.06 GB of
    bfloat16."""
    from benchmark import blocks

    block = blocks.load("jamba")
    model = jamba2_3b_keys()
    mlp = 3 * 2560 * 8192 + 2560
    assert block.layer_params(model, "mamba") - mlp == 41_244_352
    assert block.layer_params(model, "attn") - mlp == 13_765_120
    total = block.total_params(model)
    assert total == 26 * 104_161_472 + 2 * 76_682_240 + 65536 * 2560 + 2560
    assert round(total / 1e9, 2) == 3.03
    # the program's tree holds the same count
    cfg = jamba2_3b()
    shapes = jax.eval_shape(
        lambda: jamba.init_params(cfg, jax.random.key(0), jnp.bfloat16)
    )
    assert sum(a.size for a in jax.tree.leaves(shapes)) == total
    assert cfg.recurrent_row_bytes == 389_120
    assert block.state_bytes_per_row_layer(model) == 778_240
    assert block.arena_bytes_per_token_layer(model) == 512


def test_the_configuration_reads_the_published_keys():
    cfg = jamba2_3b()
    assert cfg.layer_pattern == "MMMMMMM*MMMMMMMMMMMMM*MMMMMM"
    assert cfg.layer_kinds.count("mamba") == 26
    assert [l for l, k in enumerate(cfg.layer_kinds) if k == "attn"] == [7, 21]
    assert (cfg.ssm_inner, cfg.ssm_state_size, cfg.ssm_dt_rank,
            cfg.conv_kernel, cfg.conv_dim) == (5120, 16, 160, 4, 5120)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim_) == (20, 1, 128)
    assert cfg.tie_word_embeddings and cfg.recurrent and not cfg.windowed
    assert cfg.num_experts == 0 and cfg.rms_norm_eps == 1e-6
    assert cfg.recurrent_shapes == {"ssm": (16, 8, 640), "conv": (3, 5120)}
    assert ModelConfig.from_json(cfg.to_json()) == cfg
    # what it keeps and does not read changes nothing
    other = jamba2_3b(**{k: 7 for k in JAMBA_KEYS_NOT_READ})
    assert other == cfg
    assert tiny_jamba(mamba_dt_rank="auto").ssm_dt_rank == 4
    assert CFG.layer_pattern == "MM*MMM"


@pytest.mark.parametrize("kw, word", [
    ({"num_experts": 16}, "num_experts=16 is not supported"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias=True"),
    ({"sliding_window": 4096}, "sliding_window=4096"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias=False"),
    ({"hidden_act": "gelu"}, "hidden_act='gelu'"),
    ({"attn_layer_offset": 14}, "attn_layer_offset 14 is not in 0..13"),
    ({"mamba_d_state": None}, "lacks 'mamba_d_state'"),
    ({"hidden_size": 2562, "num_attention_heads": 21}, "do not split into 8"),
])
def test_what_the_configuration_cannot_honour_is_refused(kw, word):
    with pytest.raises(ValueError, match=word):
        jamba2_3b(**kw)


def test_the_dense_cache_path_and_tp_are_refused(params):
    from llm_sharding_tpu.models.family import refuse_axes

    with pytest.raises(NotImplementedError, match="dense KV cache"):
        jamba.forward_layers(CFG, params["layers"], None, None, None)
    with pytest.raises(NotImplementedError, match="tensor / context"):
        refuse_axes(CFG, "tensor")
    from llm_sharding_tpu.parallel.pipeline import model_fns

    with pytest.raises(NotImplementedError, match="over jamba"):
        model_fns(CFG, tp_axis="tensor")
    assert model_fns(CFG).prefill_walks is jamba.prefill_walks
