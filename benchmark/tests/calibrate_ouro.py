#!/usr/bin/env python3
"""What the margins of ``blocks/ouro.py`` read when the program runs another
model than the looped one, or holds a lower precision than the configuration
states. Run ON THE CHIP when ``DELTA_MEAN`` is set; every other argument is
``run.py``'s:

    python3 benchmark/tests/calibrate_ouro.py shared_slots \
        --workload ouro_2p6b.ponder --seed 7 --seconds 50

It changes the program in memory (nothing on disk, no option of the program)
and then runs the cell as ``run.py`` does — same traffic, same window, same
sample of scored requests, so the reading stands beside a sound run's at the
same count of positions. The result line's ``correct`` is the verdict under
the limits as they stand. Every mode also prints each scored request's own
margins on standard error (``request 2: ...``, in the order the cycle sent
them): a run scores as many requests as the program finishes, and the limit
has to hold whatever that count is. Modes:

- ``sound``: nothing changed (the control of the controls).
- ``shared_slots``: every pass reads and writes pass 0's arena slots (the
  offset ``t * L`` dropped): a prefill's later passes overwrite the earlier
  ones' keys and a decode step's first pass attends the last pass's.
- ``fewer_passes``: the program runs ``total_ut_steps - 1`` passes (and sizes
  its arena for them); the reference runs what the configuration states.
- ``close_last_only``: no norm between passes, only after the last — on the
  REFERENCE's side (``blocks/ouro.close_pass`` is the identity before the
  last pass; the program is sound): the distance between the two models is
  the same from either side, and the program's loop is one traced body whose
  close no patch of one line turns off for some passes only.
- ``no_out_norm``: the program is handed layers WITHOUT ``mlp_out_norm`` (the
  block applies the output norms by key presence); the reference keeps it.
- ``head_norm``: the program's head norms once more (T + 1 final norms).
- ``fp8_kv``: every key and value rounded to the three mantissa bits of fp8
  e4m3 before it is written to its arena, under the bf16 label (the arrays
  stay bfloat16: the type check cannot see it, the margins must).
- ``int8_weights``: the ENGINE is given int8 matmul weights (per-channel
  scales) under the bf16 label; the reference scores under the bf16 weights
  the configuration states (the check makes its own copy). The cheat that
  would pay: a step is 19.9 GB of weight reads.
"""

import dataclasses
import json
import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

MODES = ("sound", "shared_slots", "fewer_passes", "close_last_only",
         "no_out_norm", "head_norm", "fp8_kv", "int8_weights")


def served_once(change):
    """``weights.make_params`` changed for the ENGINE's call alone: the
    check's own copy (the second call on one chip) stays as stated."""
    from benchmark import weights

    make, calls = weights.make_params, []

    def low(block, model, seed, weight_dtype, devices):
        calls.append(None)
        if len(calls) > 1:
            return make(block, model, seed, weight_dtype, devices)
        return change(make, block, model, seed, weight_dtype, devices)

    weights.make_params = low


def patch(mode: str) -> None:
    import jax

    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.models import stack

    if mode == "shared_slots":
        stack._slot = lambda i, first_layer: i
    elif mode == "fewer_passes":
        from benchmark import harness

        config = harness.model_config

        def fewer(cfg_file):
            cfg = config(cfg_file)
            return dataclasses.replace(cfg, passes=cfg.passes - 1)

        harness.model_config = fewer
    elif mode == "close_last_only":
        from benchmark import blocks

        with open(os.path.join(BENCH, "configs", "ouro_2p6b.json")) as f:
            last = int(json.load(f)["total_ut_steps"]) - 1
        block = blocks.load("ouro")  # (one module object a file: run.py's)
        close = block.close_pass
        block.close_pass = lambda h, tables, *, step, **kw: (
            close(h, tables, step=step, **kw) if step == last else h)
    elif mode == "no_out_norm":
        def dropped(make, *args):
            params = make(*args)
            layers = {k: v for k, v in params["layers"].items()
                      if k != "mlp_out_norm"}
            return dict(params, layers=layers)

        served_once(dropped)
    elif mode == "head_norm":
        from llm_sharding_tpu.ops.norms import rms_norm
        from llm_sharding_tpu.parallel import head

        logits = head._local_logits

        def again(cfg, hd, h_last):
            return logits(cfg, hd, rms_norm(
                h_last, hd["final_norm"], cfg.rms_norm_eps, cfg.norm_offset))

        head._local_logits = again
    elif mode == "fp8_kv":
        from llm_sharding_tpu.ops import paged_attention as pa

        # (``reduce_precision``, not a cast there and back: the chip's
        # compiler drops such a pair — ``xla_allow_excess_precision``)
        low = lambda v: jax.lax.reduce_precision(v, 4, 3)
        write, chunk = pa.paged_attention_write, pa.write_chunk_kv

        def low_write(q, k_new, v_new, *a, **kw):
            return write(q, low(k_new), low(v_new), *a, **kw)

        def low_chunk(k_arena, v_arena, layer, table, col0, k_new, v_new, **kw):
            return chunk(k_arena, v_arena, layer, table, col0, low(k_new),
                         low(v_new), **kw)

        pa.paged_attention_write, pa.write_chunk_kv = low_write, low_chunk
    elif mode == "int8_weights":
        served_once(lambda make, block, model, seed, _dtype, devices: make(
            block, model, seed, "int8", devices))
    elif mode != "sound":
        raise SystemExit(f"mode {mode!r}: one of {MODES}")


if __name__ == "__main__":
    from calibrate_keye_vl2 import print_by_request  # (beside this file)

    mode = sys.argv.pop(1)
    patch(mode)
    print_by_request()
    print("calibrate_ouro:", mode, flush=True)
    runpy.run_path(os.path.join(BENCH, "run.py"), run_name="__main__")
