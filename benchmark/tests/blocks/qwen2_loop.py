"""A block whose layers run several times for one token, for the benchmark's
tests: the rehearsal of what a looped model (Ouro-2.6B: ``total_ut_steps`` 4
passes over the SAME 48 layers, the final norm after every pass, an exit gate
over the passes' states) brings — ONE block file with ``passes`` and
``close_pass`` — at tiny widths, on the CPU, with no configuration under
``configs/`` and no cell.

The layer, its leaves, the first three tables and the thresholds are the Qwen2
block's own (``blocks/qwen2.py``), so a model of ONE pass whose close is the
identity is the Qwen2 model of the same seed to the last digit. The toy
configuration's keys, named as Ouro's published configuration names them:

- ``total_ut_steps``: T, how many times the stack runs (``passes``);
- ``early_exit_threshold``: the exit gate's threshold (``head_static``). Pass
  ``t``'s closed state ``s_t`` gives ``g_t = sigmoid(s_t . exit_gate +
  exit_bias)``; ``p_t = g_t prod_{u<t} (1 - g_u)`` and the last pass takes what
  is left; a position's logits are the head of the FIRST pass at which the
  running sum of ``p`` reaches the threshold, else of the last. At 1 that is
  the last pass always; at 0.5 about half of the positions leave at pass 0;
- ``loop_close`` (the toy's own): ``"final_norm"`` — the block's final RMSNorm
  after EVERY pass, whose result enters the next pass, and the head alone in
  ``logits``, as Ouro does it — or ``"identity"``, where a pass ends as its
  last layer left it and ``logits`` is Qwen2's (norm, then head).

The gate is two tables more (``exit_gate`` ``[H]``, ``exit_bias`` ``[1]``),
drawn after the Qwen2 block's three, whose keys they leave alone. The layers
are drawn ONCE: ``dims()["layers"]`` is L, whatever T.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import blocks, roofline
from benchmark.weights import Leaf

QWEN2 = blocks.load("qwen2")

dims, layer_leaves = QWEN2.dims, QWEN2.layer_leaves
layer_static, layer_forward = QWEN2.layer_static, QWEN2.layer_forward
DELTA_MEAN, DELTA_MAX = QWEN2.DELTA_MEAN, QWEN2.DELTA_MAX


def passes(model: dict) -> int:
    """T: the published key, as it is (``blocks.passes`` refuses T < 1)."""
    return int(model["total_ut_steps"])


def gate_in(x):
    return x * x.shape[-1] ** -0.5


def tables(model: dict) -> tuple:
    H = model["hidden_size"]
    return QWEN2.tables(model) + (
        Leaf("exit_gate", (H,), gate_in), Leaf("exit_bias", (1,), QWEN2.bias),
    )


def head_static(model: dict) -> dict:
    """The keywords of ``embed``, ``close_pass`` and ``logits``."""
    return dict(
        QWEN2.head_static(model),
        exit_threshold=float(model["early_exit_threshold"]),
        close=str(model.get("loop_close", "final_norm")),
    )


def embed(tables: dict, ids, **_head_static):
    return QWEN2.embed(tables, ids)


@functools.partial(
    jax.jit, static_argnames=("step", "eps", "exit_threshold", "close"))
def close_pass(h, tables: dict, *, step, eps, exit_threshold, close):
    """What closes pass ``step`` over a whole sequence h: [S, H]: the final
    norm, the same after every pass (``step`` is there for a block whose
    passes close differently)."""
    if close == "identity":
        return h
    return QWEN2.rms_norm(h, tables["final_norm"].astype(jnp.float32), eps)


def exit_pass(h, tables: dict, exit_threshold: float):
    """h: [T, rows, H] closed states → [rows] the pass each position's logits
    are taken from."""
    T = h.shape[0]
    g = jax.nn.sigmoid(
        h @ tables["exit_gate"].astype(jnp.float32)
        + tables["exit_bias"].astype(jnp.float32))  # [T, rows]
    stayed = jnp.cumprod(1.0 - g, axis=0)
    before = jnp.concatenate([jnp.ones_like(g[:1]), stayed[:-1]])
    p = jnp.concatenate([(g * before)[:-1], before[-1:]])
    reached = jnp.cumsum(p, axis=0) >= exit_threshold
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0), T - 1)


def logits(h, tables: dict, *, eps, exit_threshold, close):
    """h: [T, rows, H], every pass's closed state at the scored rows → [rows,
    V]: the gate chooses a pass a position, here and not in the shared code."""
    at = exit_pass(h, tables, exit_threshold)
    chosen = jnp.take_along_axis(h, at[None, :, None], axis=0)[0]
    if close == "identity":
        return QWEN2.logits(chosen, tables, eps=eps)
    return chosen @ tables["lm_head"].astype(jnp.float32)


def decode_step_bytes(model: dict, weight_dtype: str, stages: int,
                      live_tokens: float, rec=None, kv_bytes: int = 2) -> float:
    """A looped block counts its passes itself: a decode microstep reads the
    layers T times and the live K/V of T passes (each pass keeps keys and
    values of its own), the head once."""
    T = passes(model)
    return roofline.decode_step_bytes(
        dims(model), T * QWEN2.layer_weight_bytes(model, weight_dtype), stages,
        T * live_tokens, kv_bytes,
    )
