#!/usr/bin/env python3
"""What the margins of ``blocks/jamba.py`` read when the program holds a lower
precision than the configuration states, or drops a term. Run ON THE CHIP when
``DELTA_MEAN`` is set; every other argument is ``run.py``'s:

    python3 benchmark/tests/calibrate_jamba.py bf16_state \
        --workload jamba2_3b.agent --seed 7 --seconds 50

It changes the program in memory (nothing on disk, no option of the program)
and then runs the cell as ``run.py`` does — same traffic, same window, same
sample of scored requests, so the reading stands beside a sound run's at the
same count of positions. The result line's ``correct`` is the verdict under
the limits as they stand. Modes:

- ``sound``: nothing changed (the control).
- ``bf16_state``: the recurrent state rounded to bfloat16 after every update
  (a decode step's and a prefill chunk's), under the float32 label (the array
  stays float32). The nearest precision below the float32 the configuration
  states for it; over 512 decode steps the rounding compounds.
- ``int8_weights``: every matmul weight the ENGINE is given rounded to the
  255 levels of symmetric per-channel int8 and multiplied out again, under
  the bf16 label (the arrays stay bfloat16); the reference scores the served
  tokens under the bf16 weights the configuration states (the check makes its
  own copy). The nearest precision below for the weights, and the cheat that
  would pay: a decode step is bound by the weights it reads.
- ``no_dt_norm`` / ``no_b_norm`` / ``no_c_norm``: a mixer without one of the
  three norms Jamba adds to Mamba (the projection's output used as it is).
- ``no_skip``: the mixer without its ``D x`` skip term.
"""

import functools
import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

MODES = ("sound", "bf16_state", "int8_weights", "no_dt_norm", "no_b_norm",
         "no_c_norm", "no_skip")


def patch(mode: str) -> None:
    import jax
    import jax.numpy as jnp
    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.models import jamba
    from llm_sharding_tpu.ops import ssm

    scan = ssm.scan_rows
    if mode == "bf16_state":
        def low(s_all, at, *args, **kw):
            y, s_all = scan(s_all, at, *args, **kw)
            # the layer's rows as bfloat16 would hold them (``reduce_precision``,
            # not a cast there and back: the chip's compiler drops such a pair)
            l = at[0]
            rows = jax.lax.dynamic_index_in_dim(s_all, l, keepdims=True)
            rows = jax.lax.reduce_precision(
                rows, exponent_bits=8, mantissa_bits=7)
            return y, jax.lax.dynamic_update_index_in_dim(s_all, rows[0], l, 0)

        ssm.scan_rows = low
    elif mode == "no_skip":
        def low(s_all, at, order, n, x, dt, z, A, Bm, Cm, D, **kw):
            return scan(s_all, at, order, n, x, dt, z, A, Bm, Cm,
                        jnp.zeros_like(D), **kw)

        ssm.scan_rows = low
    elif mode in ("no_dt_norm", "no_b_norm", "no_c_norm"):
        norm = jamba.rms_norm
        drop = ("no_dt_norm", "no_b_norm", "no_c_norm").index(mode)
        seen = []

        def low(x, w, eps, *rest):
            # ``_mixer_in`` calls, in order: the layer's norm [hidden], then
            # the step's, B's and C's; ``mlp_block``: [hidden]
            if x.shape[-1] == w.shape[-1] and w.shape[-1] < 1024:
                seen.append(None)
                if (len(seen) - 1) % 3 == drop:
                    return x
            return norm(x, w, eps, *rest)

        jamba.rms_norm = low
    elif mode == "int8_weights":
        from benchmark import weights

        make, calls = weights.make_params, []

        @functools.partial(jax.jit, donate_argnums=0)  # in place, fused
        def round8(w):
            w32 = w.astype(jnp.float32)
            scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
            return (jnp.round(w32 / jnp.maximum(scale, 1e-12)) * scale).astype(
                w.dtype)

        def low(block, model, *args, **kw):
            params = make(block, model, *args, **kw)
            calls.append(None)
            if len(calls) > 1:  # the check's own copy: as stated
                return params
            matmuls = {
                kind: {leaf.name for leaf in leaves if leaf.matmul}
                for kind, leaves in block.layer_leaves(model).items()
            }
            layers = {
                kind: {name: round8(a) if name in matmuls[kind] else a
                       for name, a in stack.items()}
                for kind, stack in params["layers"].items()
            }
            return dict(params, layers=layers)

        weights.make_params = low
    elif mode != "sound":
        raise SystemExit(f"mode {mode!r}: one of {MODES}")


if __name__ == "__main__":
    mode = sys.argv.pop(1)
    patch(mode)
    print("calibrate_jamba:", mode, flush=True)
    runpy.run_path(os.path.join(BENCH, "run.py"), run_name="__main__")
