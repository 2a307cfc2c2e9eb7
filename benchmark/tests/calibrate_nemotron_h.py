#!/usr/bin/env python3
"""What the margins of ``blocks/nemotron_h.py`` read when the program holds a
lower precision than the configuration states, or drops a term. Run ON THE
CHIP when ``DELTA_MEAN`` is set; every other argument is ``run.py``'s:

    python3 benchmark/tests/calibrate_nemotron_h.py bf16_state \
        --workload nemotron3_super_120b_a12b.think --seed 7 --seconds 50

It changes the program in memory (nothing on disk, no option of the program)
and then runs the cell as ``run.py`` does — same traffic, same window, same
sample of scored requests, so the reading stands beside a sound run's at the
same count of positions. The result line's ``correct`` is the verdict under
the limits as they stand. Modes:

- ``sound``: nothing changed (the control).
- ``bf16_state``: the recurrent state rounded to bfloat16 after every update
  (a decode step's and a prefill chunk's), under the float32 label (the array
  stays float32). The nearest precision below the float32 the configuration
  states for it; over 4,096 decode steps the rounding compounds.
- ``int4_weights``: every matmul weight the ENGINE is given — ``w_in``,
  ``w_out``, the experts, the latent and attention projections — rounded to
  the 15 levels of symmetric int4 under the int8 label and scales; the
  reference scores the served tokens under the int8 weights the configuration
  states (the check makes its own copy). The cheat that would pay: a decode
  step is bound by the weights it reads.
- ``no_conv_bias``: the mixer's conv without its bias.
- ``no_skip``: the mixer without its ``D x`` skip term.
"""

import functools
import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

MODES = ("sound", "bf16_state", "int4_weights", "no_conv_bias", "no_skip")


def patch(mode: str) -> None:
    import jax
    import jax.numpy as jnp
    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.ops import ssm

    step, chunk = ssm.ssm_step, ssm.ssm_chunk
    if mode == "bf16_state":
        def rounded(fn):
            def low(*args, **kw):
                y, s = fn(*args, **kw)
                # (``reduce_precision``, not a cast there and back: the chip's
                # compiler drops such a pair — ``xla_allow_excess_precision``
                # — and the control then reads the sound run's digits)
                return y, jax.lax.reduce_precision(
                    s, exponent_bits=8, mantissa_bits=7)
            return low

        ssm.ssm_step, ssm.ssm_chunk = rounded(step), rounded(chunk)
    elif mode == "no_skip":
        def without(fn):
            def low(state, x, dt, A, Bm, Cm, D, *rest):
                return fn(state, x, dt, A, Bm, Cm, jnp.zeros_like(D), *rest)
            return low

        ssm.ssm_step, ssm.ssm_chunk = without(step), without(chunk)
    elif mode == "no_conv_bias":
        conv_step, conv_chunk = ssm.conv_step, ssm.conv_chunk
        ssm.conv_step = lambda tail, x, w, b: conv_step(
            tail, x, w, jnp.zeros_like(b))
        ssm.conv_chunk = lambda tail, x, n, w, b: conv_chunk(
            tail, x, n, w, jnp.zeros_like(b))
    elif mode == "int4_weights":
        from benchmark import weights
        from llm_sharding_tpu.ops.quant import QTensor

        make, calls = weights.make_params, []

        @functools.partial(jax.jit, donate_argnums=0)  # in place, fused
        def round4(q):
            q4 = jnp.round(q.astype(jnp.float32) * (7.0 / 127.0))
            return jnp.round(q4 * (127.0 / 7.0)).astype(jnp.int8)

        def int4(leaf):
            if not isinstance(leaf, QTensor):
                return leaf
            return QTensor(q=round4(leaf.q), scale=leaf.scale)

        def low(*args, **kw):
            params = make(*args, **kw)
            calls.append(None)
            if len(calls) > 1:  # the check's own copy: as stated
                return params
            return jax.tree.map(
                int4, params, is_leaf=lambda x: isinstance(x, QTensor))

        weights.make_params = low
    elif mode != "sound":
        raise SystemExit(f"mode {mode!r}: one of {MODES}")


if __name__ == "__main__":
    mode = sys.argv.pop(1)
    patch(mode)
    print("calibrate_nemotron_h:", mode, flush=True)
    runpy.run_path(os.path.join(BENCH, "run.py"), run_name="__main__")
