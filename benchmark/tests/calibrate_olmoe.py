#!/usr/bin/env python3
"""What the margins of ``blocks/olmoe.py`` read when the NEW code of the
expert layer computes in a lower precision than it says. Run ON THE CHIP when
``DELTA_MEAN`` is set; every other argument is ``run.py``'s:

    python3 benchmark/tests/calibrate_olmoe.py bf16_router \
        --workload olmoe_1b_7b.backlog --seed 7 --seconds 50

It changes the program in memory (nothing on disk, no option of the program)
and then runs the cell as ``run.py`` does — same traffic, same window, same
sample of scored requests, so the reading stands beside a sound run's at the
same count of positions. The result line's ``correct`` is the verdict under
the limits as they stand. Modes:

- ``sound``: nothing changed (the control).
- ``bf16_router``: the router's logits multiplied out in the activation dtype
  (bfloat16) instead of float32; softmax and top-k as they are.
- ``fp8_experts``: everything that enters an expert matmul — the rows, the
  weights' int8 codes, the gated activation — rounded to the three mantissa
  bits of fp8 e4m3 first (accumulation stays float32, as an fp8 MXU's does).
  The arena, the attention half and the router stay as they are. A v5e has no
  fp8 MXU: this reads the precision, never the speed.
"""

import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

MODES = ("sound", "bf16_router", "fp8_experts")


def round_e4m3(v):
    """float32 values rounded (half up) to 3 mantissa bits; e4m3's range is
    not modelled: what enters an expert matmul is far inside it."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    u = (u + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def patch(mode: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.ops import moe

    if mode == "bf16_router":

        def route(x, router, top_k, renormalize=False):
            logits = jnp.dot(x, router.astype(x.dtype))  # bf16 in, bf16 out
            probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            w, ids = jax.lax.top_k(probs, top_k)
            if renormalize:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            return w, ids.astype(jnp.int32)

        moe.route = route
    elif mode == "fp8_experts":

        def kernel(lyr, texp, trow, nlive, x_ref, wg_ref, wu_ref, wd_ref,
                   sg_ref, su_ref, o_ref, acc_ref, *, n_f):
            i, f = pl.program_id(0), pl.program_id(1)
            f32 = jnp.float32

            @pl.when(f == 0)
            def _init():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            @pl.when(i < nlive[0])
            def _tile():
                dt = x_ref.dtype
                low = lambda ref: round_e4m3(ref[...].astype(f32)).astype(dt)
                x = low(x_ref)
                g = jnp.dot(x, low(wg_ref), preferred_element_type=f32)
                u = jnp.dot(x, low(wu_ref), preferred_element_type=f32)
                g = g * sg_ref[...].astype(f32)
                u = u * su_ref[...].astype(f32)
                a = round_e4m3(g * jax.nn.sigmoid(g) * u).astype(dt)
                acc_ref[...] += jnp.dot(
                    a, low(wd_ref), preferred_element_type=f32
                )

            @pl.when(f == n_f - 1)
            def _finish():
                o_ref[...] = acc_ref[...].astype(o_ref.dtype)

        moe._expert_kernel = kernel
    elif mode != "sound":
        raise SystemExit(f"mode {mode!r}: one of {MODES}")


if __name__ == "__main__":
    mode = sys.argv.pop(1)
    patch(mode)
    print("calibrate_olmoe:", mode, flush=True)
    runpy.run_path(os.path.join(BENCH, "run.py"), run_name="__main__")
