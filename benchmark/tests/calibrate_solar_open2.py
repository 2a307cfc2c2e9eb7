#!/usr/bin/env python3
"""What the margins of ``blocks/solar_open2.py`` read when the program holds a
lower precision than the configuration states, or runs another model. Run ON
THE CHIP when ``DELTA_MEAN`` is set; every other argument is ``run.py``'s:

    python3 benchmark/tests/calibrate_solar_open2.py bf16_state \
        --workload solar_open2_250b.cot --seed 7 --seconds 50

It changes the program in memory (nothing on disk, no option of the program)
and then runs the cell as ``run.py`` does — same traffic, same window, same
sample of scored requests, so the reading stands beside a sound run's at the
same count of positions. The result line's ``correct`` is the verdict under
the limits as they stand. Modes:

- ``sound``: nothing changed (the control).
- ``bf16_state``: the recurrent state rounded to bfloat16 after every update
  (a decode step's, inside its kernel, and a prefill chunk's), under the
  float32 label (the array stays float32). The nearest precision below the
  float32 the configuration states for it; over 4,096 decode steps the
  rounding compounds.
- ``beta_01``: the write strength NOT doubled, ``β = sigmoid(·)`` in (0, 1):
  ``kda_allow_neg_eigval`` ignored.
- ``no_delta``: ``S = S' + β k vᵀ`` — the write without the read that corrects
  it: gated linear attention with KDA's decay. Both forms run the time scan of
  the changed step (the kernel and the chunkwise form are the sound
  recurrence's).
- ``no_gate``: the attention layers' output gate dropped (``y = W_o o``).
- ``int4_weights``: every matmul weight the ENGINE is given rounded to the 15
  levels of symmetric int4 under the int8 label and scales; the reference
  scores the served tokens under the int8 weights the configuration states
  (the check makes its own copy). The cheat that would pay: a decode step is
  bound by the weights it reads.
"""

import functools
import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

MODES = ("sound", "bf16_state", "beta_01", "no_delta", "no_gate",
         "int4_weights")


def patch(mode: str) -> None:
    import jax
    import jax.numpy as jnp
    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.models import solar_open2 as so
    from llm_sharding_tpu.ops import kda

    if mode == "bf16_state":
        def low(s):
            # (``reduce_precision``, not a cast there and back: the chip's
            # compiler drops such a pair — ``xla_allow_excess_precision``)
            return jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)

        def rounded(fn):
            def lowered(*args, **kw):
                o, s = fn(*args, **kw)
                return o, low(s)
            return lowered

        kernel = kda._rows_kernel

        def rows_kernel(*refs):
            # the decode kernel's state block rounded WHERE it is written, so
            # that the step keeps its one pass and its speed, and a run scores
            # as many positions as a sound run (Mosaic keeps the cast pair)
            kernel(*refs)
            so_ref = refs[10]
            so_ref[...] = so_ref[...].astype(jnp.bfloat16).astype(jnp.float32)

        kda._rows_kernel = rows_kernel
        kda.kda_step = rounded(kda.kda_step)  # the loop in XLA, off the chip
        kda.kda_chunk = rounded(kda.kda_chunk)
    elif mode == "beta_01":
        mixer_in = so._mixer_in

        def halved(cfg, p, h, tail, live):
            q, k, v, g, beta, z, tail = mixer_in(cfg, p, h, tail, live)
            return q, k, v, g, beta / cfg.kda_beta_scale, z, tail

        so._mixer_in = halved
    elif mode == "no_delta":
        f32, hi = jnp.float32, jax.lax.Precision.HIGHEST

        def step(state, q, k, v, log_a, beta):
            q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
            s = state * jnp.exp(log_a.astype(f32))[..., None]
            s = s + (beta.astype(f32)[..., None] * k)[..., None] * v[..., None, :]
            return jnp.einsum("bhkv,bhk->bhv", s, q, precision=hi), s

        kda.kda_step = step
        kda._resolve = lambda backend, eligible: "xla"  # the loop over kda_step
        kda.kda_chunk = kda.kda_scan  # ... and the time scan of it
    elif mode == "no_gate":
        block = so.gqa_block

        def ungated(cfg, p, h, attend):
            return block(
                cfg, {k: v for k, v in p.items() if k != "w_gate"}, h, attend)

        so.gqa_block = ungated
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
    print("calibrate_solar_open2:", mode, flush=True)
    runpy.run_path(os.path.join(BENCH, "run.py"), run_name="__main__")
