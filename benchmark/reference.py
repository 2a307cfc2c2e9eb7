"""The comparison that decides ``correct``, shared by every block.

The plain reference itself — one decoder layer over a whole sequence, the
embedding, the final norm and logits, and the two thresholds — is the
block's (``blocks/<model_type>.py``: ``layer_forward``, ``embed``, ``logits``,
``DELTA_MEAN``, ``DELTA_MAX``; layers are walked in order, ``passes`` times,
and a block with ``layer_kinds`` is told each layer's ``kind``; a block with
``passes`` closes each pass itself, ``close_pass``, and its ``logits`` is
handed every pass's closed state). It reads nothing from the
program under test; weights come in as plain arrays (an int8 weight as its
``(q, scale)`` pair, dequantised here as ``q · scale``), one layer at a time.

Departure from the published descriptions: a sequence is padded at its END to
a multiple of ``PAD_TO`` so that a handful of shapes compile; under a causal
mask the padding cannot reach an earlier position.

The comparison. The server returns token ids, not logits, so the served
sequence is scored teacher-forced: the reference runs once over prompt +
served tokens, and at every output position t the *margin*

    m_t = max(ref_logits_t) − ref_logits_t[served_t]          (≥ 0)

says how far below the reference's best the served token lies. A rounding
flip between near-ties gives a small margin and does not compound. A run is
correct when the mean margin is at most the block's ``DELTA_MEAN`` and no
margin exceeds its ``DELTA_MAX``.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from benchmark import blocks

PAD_TO = 256


def dequant(leaf) -> jnp.ndarray:
    """A weight as float32: plain arrays are cast, ``(q, scale)`` pairs are
    multiplied out per output channel."""
    if isinstance(leaf, tuple):
        q, scale = leaf
        return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None, :]
    return leaf.astype(jnp.float32)


def round_kv(x, kv_round):
    """Keys or values x: [S, N, D] as a cache of lower precision would hold
    them. Only the benchmark's tests pass ``kv_round``: they show that serving
    from such a cache under a bf16 label fails the comparison."""
    if kv_round is None:
        return x
    if kv_round in ("int8", "int4"):  # symmetric, one scale per head
        qmax = 127.0 if kv_round == "int8" else 7.0
        scale = jnp.max(jnp.abs(x), axis=(0, 2), keepdims=True) / qmax
        return jnp.round(x / scale) * scale
    return x.astype(kv_round).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("logits", "kw"))
def margins_from_hidden(h, tables, served, *, logits, kw):
    """h: [rows, H] hidden states at the positions that predict ``served`` —
    for a block with ``passes`` [T, rows, H], the closed state of every pass,
    among which its ``logits`` chooses; ``logits`` is the block's, ``kw`` its
    static keywords as sorted items."""
    with jax.default_matmul_precision("highest"):
        out = logits(h, tables, **dict(kw))
        best = jnp.max(out, axis=-1)
        got = jnp.take_along_axis(out, served[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(out, axis=-1)


def _as_ref_layer(layer: dict) -> dict:
    """Containers to plain data: any ``(q, scale)`` named tuple becomes a
    plain tuple, so the reference needs no type of the program's."""
    return {
        k: (tuple(v) if isinstance(v, tuple) else v) for k, v in layer.items()
    }


def hidden_states(block, model: dict, get_layer, tables: dict,
                  sequences: list, **overrides) -> list:
    """Final hidden states [S_padded, H] of each id sequence. ``get_layer(l)``
    gives layer l's leaves on the device (of whatever kind layer l is); only
    one layer is resident at a time. Layers are the outer loop, so each is
    fetched once for all sequences. ``overrides`` (tests only) replace a
    keyword of the block's ``layer_forward``, e.g. a wrong ``theta``.

    A block with ``passes`` has the SAME layers walked that many times, each
    fetched again in every pass and told nothing of the pass (a whole
    sequence needs no cache, so a pass attends its own keys). The block's
    ``close_pass`` closes each pass: its result enters the next one and is
    that pass's closed state. All of them are returned, [T, S_padded, H] a
    sequence."""
    kinds = blocks.kinds(block, model)
    head_kw = block.head_static(model)
    hidden = []
    for ids in sequences:
        ids = np.asarray(ids, np.int32)
        padded = jnp.asarray(np.pad(ids, (0, -len(ids) % PAD_TO)))
        hidden.append(block.embed(tables, padded, **head_kw))
    looped = blocks.looped(block)
    close = getattr(block, "close_pass", None) if looped else None
    closed = []  # of a block with ``passes``: every pass's closed states
    for step in range(blocks.passes(block, model)):
        for l in range(block.dims(model)["layers"]):
            # a block with ``layer_kinds`` is told which kind layer l is
            kw = dict(blocks.static_of(block, model, kinds, l), **overrides)
            p = _as_ref_layer(get_layer(l))
            hidden = [block.layer_forward(h, p, **kw) for h in hidden]
            del p
        if close is not None:
            hidden = [close(h, tables, step=step, **head_kw) for h in hidden]
        closed.append(hidden)
    if not looped:
        return hidden
    return [jnp.stack(of_seq) for of_seq in zip(*closed)]


def score(block, model: dict, get_layer, tables: dict, samples: list) -> dict:
    """Teacher-forced margins of ``samples`` — ``(prompt_ids, served_ids)``
    pairs — under the block's reference."""
    kw = tuple(sorted(block.head_static(model).items()))
    seqs = [(len(p), len(s)) for p, s in samples]
    hidden = hidden_states(
        block, model, get_layer, tables,
        [np.concatenate([p, s]) for p, s in samples],
    )
    margins, agree = [], []
    for (n_prompt, n_out), h, (_, served) in zip(seqs, hidden, samples):
        rows = h[..., n_prompt - 1 : n_prompt - 1 + n_out, :]
        m, best = margins_from_hidden(
            rows, tables, jnp.asarray(np.asarray(served, np.int32)),
            logits=block.logits, kw=kw,
        )
        margins.append(np.asarray(m))
        agree.append(np.asarray(best) == np.asarray(served))
    m = np.concatenate(margins)
    a = np.concatenate(agree)
    return {
        "positions": int(m.size),
        "samples": len(samples),
        "margin_mean": float(m.mean()),
        "margin_max": float(m.max()),
        "margin_p99": float(np.percentile(m, 99)),
        "argmax_share": float(a.mean()),
    }


def verdict(scored: dict, block) -> bool:
    return (
        scored["positions"] > 0
        and scored["margin_mean"] <= block.DELTA_MEAN
        and scored["margin_max"] <= block.DELTA_MAX
    )
