#!/usr/bin/env python3
"""What the margins of ``blocks/KeyeVL2.py`` read when the program selects
other keys than the model's, holds a lower precision than the configuration
states, or prefills a prompt longer than ``topk``. Run ON THE CHIP when
``DELTA_MEAN`` is set; every other argument is ``run.py``'s:

    python3 benchmark/tests/calibrate_keye_vl2.py recent \
        --workload keye_vl2_30b_a3b.longgen --seed 7 --seconds 50

It changes the program in memory (nothing on disk, no option of the program)
and then runs the cell as ``run.py`` does — same traffic, same window, same
sample of scored requests, so the reading stands beside a sound run's at the
same count of positions. The result line's ``correct`` is the verdict under
the limits as they stand. Every mode also prints each scored request's own
margins on standard error (``request 2: ...``, in the order the cycle sent
them, with the mean over the first k beside it): a run scores as many requests
as the program finishes — one at PR 49's speed, three since PR 51 — and the
limit has to hold whatever that count is (``--seconds 92`` finishes the
cycle's first five at PR 58's speed). Modes:

- ``sound``: nothing changed (the control of the controls).
- ``recent``: a query keeps the most recent ``topk`` keys in place of the
  indexer's choice — a sliding window under the model's name. Must read NOT
  correct: it is what ``correct`` has to see of the mechanism.
- ``all``: no selection at all (every query attends its whole context, the
  llama block on the same leaves). Must read NOT correct.
- ``bf16_scores``: the index scores rounded to bfloat16's eight bits after
  they are summed (the program sums bf16 products in float32): more keys at
  the edge of the top-k fall on the other side than a sound run's. A reading
  beside the limits, as ISSUE 49 asks.
- ``fp8_kv``: every key and value rounded to the three mantissa bits of fp8
  e4m3 before it is written to its arena, under the bf16 label (the arrays
  stay bfloat16: the type check cannot see it, the margins must). The nearest
  precision below the bf16 the configuration states.
- ``int4_weights``: every matmul weight the ENGINE is given rounded to the 15
  levels of symmetric int4 under the int8 label and scales (the reference
  scores under the int8 weights the configuration states): the nearest
  precision below on the axis that would PAY — a step reads 0.7 GB of weights.
- ``prefill N [config.json]`` (no ``run.py`` arguments but ``--seed``): ONE prompt of ``N``
  tokens (4,096: twice ``topk``) prefilled in chunks through the same server
  (``harness.build_server``), 256 tokens decoded after it, and those scored
  under the reference — the one place a selecting PREFILL is judged, since
  ``harness.CHECK_MAX_PROMPT`` keeps every cell's scored prompt at 512 tokens
  (every chunk past the eighth masks keys out of its dense product).
"""

import json
import os
import runpy
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

MODES = ("sound", "recent", "all", "bf16_scores", "fp8_kv", "int4_weights",
         "prefill")


def round_e4m3(v):
    """Values rounded to 3 mantissa bits, in their own dtype (a cast there and
    back is dropped by the chip's compiler: ``reduce_precision`` is not)."""
    import jax

    return jax.lax.reduce_precision(v, 4, 3)


def by_position(scores):
    """Scores that rank a row's attendable columns by their order (a row's
    columns are in position order), for the ``recent`` control."""
    import jax.numpy as jnp

    ok = scores > -jnp.inf
    order = jnp.arange(scores.shape[-1], dtype=jnp.float32)
    return jnp.where(ok, order, -jnp.inf)


def patch(mode: str) -> None:
    import jax
    import jax.numpy as jnp

    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.ops import paged_attention as pa

    tokens, mask, scores = pa.select_tokens, pa.select_mask, pa.index_scores
    if mode == "recent":
        pa.select_tokens = lambda s, topk: tokens(by_position(s), topk)
        pa.select_mask = lambda s, topk: mask(by_position(s), topk)
    elif mode == "all":
        # the unselected kernels as they are (the llama block on the same
        # leaves): the index keys are still written and never read
        def decode(q, k_arena, v_arena, layer, table, q_pos, kv_pos, select,
                   scale=None, backend="auto"):
            return pa.paged_attention(
                q, k_arena, v_arena, layer, table, q_pos, kv_pos, scale,
                backend=backend)

        def chunk(q, k_arena, v_arena, layer, table, q_pos, kv_pos, select,
                  scale=None, backend="auto", walk=None):
            return pa.paged_prefill(
                q, k_arena, v_arena, layer, table, q_pos, kv_pos, scale,
                backend=backend, walk=walk)

        pa.selected_attention, pa.selected_prefill = decode, chunk
    elif mode == "bf16_scores":
        pa.index_scores = lambda *a, **kw: jax.lax.reduce_precision(
            scores(*a, **kw), 8, 7)
    elif mode == "fp8_kv":
        write, chunk = pa.paged_attention_write, pa.write_chunk_kv

        def low_write(q, k_new, v_new, *a, **kw):
            return write(q, round_e4m3(k_new), round_e4m3(v_new), *a, **kw)

        def low_chunk(k_arena, v_arena, layer, table, col0, k_new, v_new, **kw):
            if v_arena.shape[-1] == 0:  # the index keys: as stated
                return chunk(k_arena, v_arena, layer, table, col0, k_new,
                             v_new, **kw)
            return chunk(k_arena, v_arena, layer, table, col0,
                         round_e4m3(k_new), round_e4m3(v_new), **kw)

        pa.paged_attention_write, pa.write_chunk_kv = low_write, low_chunk
    elif mode == "int4_weights":
        import functools

        from benchmark import weights
        from llm_sharding_tpu.ops.quant import QTensor

        make, calls = weights.make_params, []

        @functools.partial(jax.jit, donate_argnums=0)  # in place, fused
        def round4(q):
            q4 = jnp.round(q.astype(jnp.float32) * (7.0 / 127.0))
            return jnp.round(q4 * (127.0 / 7.0)).astype(jnp.int8)

        def low(*args, **kw):
            params = make(*args, **kw)
            calls.append(None)
            if len(calls) > 1:  # the check's own copy: as stated
                return params
            return jax.tree.map(
                lambda leaf: QTensor(q=round4(leaf.q), scale=leaf.scale)
                if isinstance(leaf, QTensor) else leaf,
                params, is_leaf=lambda x: isinstance(x, QTensor))

        weights.make_params = low
    elif mode not in ("sound", "prefill"):
        raise SystemExit(f"mode {mode!r}: one of {MODES}")


def print_by_request() -> None:
    """Each scored request's margins beside the harness's pooled reading:
    ``reference.score`` scores the sample in the order it was submitted."""
    import numpy as np

    from benchmark import reference

    margins, seen = reference.margins_from_hidden, []

    def each(h, tables, served, **kw):
        m, best = margins(h, tables, served, **kw)
        seen.append(np.asarray(m))
        agree = np.asarray(best) == np.asarray(served)
        print(f"request {len(seen)}: positions {seen[-1].size} margin_mean "
              f"{seen[-1].mean():.6f} margin_max {seen[-1].max():.4f} argmax "
              f"{agree.mean():.4f}; the first {len(seen)}: margin_mean "
              f"{np.concatenate(seen).mean():.6f}", file=sys.stderr, flush=True)
        return m, best

    reference.margins_from_hidden = each


def long_prefill(n_prompt: int, seed: int, config: str, new: int = 256) -> None:
    """One prompt of ``n_prompt`` tokens through the cell's own server, the
    ``new`` tokens decoded after it scored under the reference. ``config``:
    the configuration's file (another one only to rehearse this on the CPU)."""
    import numpy as np
    import jax

    from benchmark import blocks, harness, reference

    with open(config) as f:
        cfg_file = json.load(f)
    block = blocks.load(cfg_file["model_type"])
    devices = jax.devices()[:1]
    marks = {}
    on_chip = devices[0].platform == "tpu"
    server, engine, host = harness.build_server(
        cfg_file, block, devices, seed, "kernel" if on_chip else "auto", marks)
    vocab = block.dims(harness.model_keys(cfg_file))["vocab"]
    prompt = np.random.default_rng(seed).integers(
        0, vocab, size=n_prompt, dtype=np.int32)
    t = time.perf_counter()
    new = min(new, int(cfg_file["serve"]["capacity"]) // 4)
    req = server.submit(prompt, new)
    server.run_until_idle()
    took = time.perf_counter() - t
    served = np.asarray(req.tokens)
    server.close()
    del server, engine
    scored = harness.check(
        cfg_file, block, seed, devices, host, [(prompt, served)])
    print(json.dumps({
        "mode": f"prefill {n_prompt}", "seed": seed, "served": len(served),
        "seconds": round(took, 1), "reference": scored,
        "correct": bool(reference.verdict(scored, block)),
        "limits": [block.DELTA_MEAN, block.DELTA_MAX],
        "device": devices[0].device_kind,
    }), flush=True)


if __name__ == "__main__":
    mode = sys.argv.pop(1)
    patch(mode)
    print("calibrate_keye_vl2:", mode, flush=True)
    if mode == "prefill":
        n = int(sys.argv.pop(1))
        config = os.path.join(BENCH, "configs", "keye_vl2_30b_a3b.json")
        if len(sys.argv) > 1 and sys.argv[1].endswith(".json"):
            config = sys.argv.pop(1)
        seed = int(sys.argv[sys.argv.index("--seed") + 1])
        long_prefill(n, seed, config)
    else:
        print_by_request()
        runpy.run_path(os.path.join(BENCH, "run.py"), run_name="__main__")
