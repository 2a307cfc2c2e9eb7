#!/usr/bin/env python3
"""What the margins of ``blocks/mimo_v2.py`` read when the program holds a
lower precision than the configuration states: its KV state (bf16 stated),
or its matmul weights (int8 stated). Run ON THE CHIP when ``DELTA_MEAN`` is
set; every other argument is ``run.py``'s:

    python3 benchmark/tests/calibrate_mimo_v2.py fp8_kv \
        --workload mimo_v25.reason --seed 7 --seconds 50

It changes the program in memory (nothing on disk, no option of the program)
and then runs the cell as ``run.py`` does — same traffic, same window, same
sample of scored requests, so the reading stands beside a sound run's at the
same count of positions. The result line's ``correct`` is the verdict under
the limits as they stand. Modes:

- ``sound``: nothing changed (the control).
- ``fp8_kv``: every key and value entry rounded to the three mantissa bits of
  fp8 e4m3 before it is written to its arena — both kinds of layer — under
  the bf16 label (the arrays stay bfloat16: the type check cannot see it, the
  margins must). The nearest precision below the bf16 the configuration
  states. A v5e reads such an arena no faster: this reads the precision,
  never the speed.
- ``int4_weights``: every matmul weight the ENGINE is given rounded to the 15
  levels of symmetric int4 (its int8 values / 127 x 7, rounded, and back),
  under the int8 label and scales; the reference scores the served tokens
  under the int8 weights the configuration states (the check makes its own
  copy). The nearest precision below the int8 the configuration states, and
  the cheat that would pay: a decode step is bound by the weights it reads.
"""

import functools
import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

MODES = ("sound", "fp8_kv", "int4_weights")


def round_e4m3(v):
    """Values rounded (half up) to 3 mantissa bits, in their own dtype;
    e4m3's range is not modelled: keys and values lie far inside it."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    u = (u + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(v.dtype)


def patch(mode: str) -> None:
    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.ops import paged_attention as pa

    if mode == "fp8_kv":
        write = pa.write_block_kv

        def low(k_arena, v_arena, layer, table, cols, k_new, v_new, **kw):
            return write(k_arena, v_arena, layer, table, cols,
                         round_e4m3(k_new), round_e4m3(v_new), **kw)

        pa.write_block_kv = low
    elif mode == "int4_weights":
        import jax
        import jax.numpy as jnp
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
    print("calibrate_mimo_v2:", mode, flush=True)
    runpy.run_path(os.path.join(BENCH, "run.py"), run_name="__main__")
