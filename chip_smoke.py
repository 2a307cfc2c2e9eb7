#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serve path still starts on the chip.

One command, run from the root of a checkout on a machine with a TPU:

    python3 chip_smoke.py            # Qwen2.5-7B widths, int8, one stage

It drives the system's main path once, through the entry points a user
calls: a ``convert``-format shard store written by the product's own
streaming writer from a seeded random tensor source, then
``python -m llm_sharding_tpu serve`` (PipelineEngine → PipelineServer over
the paged arena with the Pallas kernels → HTTP ingress) answering a few
``POST /v1/completions`` requests, then SIGTERM → "drained; exiting 0".
Before the daemon, every ``pallas_call`` the CLI can select is compiled by
Mosaic and compared with the XLA path at the daemon's geometry.

A chip belongs to one process at a time, so THIS process never imports jax
(nor anything that does): it runs the store writer as a child on
``JAX_PLATFORMS=cpu`` and, one after the other, the two children that hold
the chip. It exits non-zero — and prints no result line — unless the device
did the work: the children must report ``platform == "tpu"``, every request
must come back 200 with the asked number of tokens, nothing may have failed,
health must be SERVING, and ``/metrics`` must show the paged decode and
prefill KERNELS dispatched with blocks read through both. On success the
last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything it writes (the store, child logs, ``result.json``) lands in
``.smoke/`` inside the checkout, which ``.gitignore`` lists. The four-chip
forms are the same script: ``--stages 4``, ``--weights bf16``,
``--data-parallel 2 --stages 2``. ``--layers N`` cuts depth, never width.
``--moe`` runs one check only and no daemon: the expert kernels of
``ops/moe.py`` (``moe_experts``) against their XLA path at OLMoE-1B-7B's
published widths, in the decode regime (4 rows) and the grouped prefill
regime (1,024 positions), a third of the rows dead, on a stack of nine
layers read at the last; then it TIMES a decode call (one live row of four)
that meets 0, 1 and 8 experts at OLMoE's, GigaChat's and Keye's shapes,
microseconds a call: the call's fixed part and what one more expert costs —
and, at 8 met, the call's PARTS each alone (``parts_us``: the loop around
nothing, what runs before the kernel, the kernel, and the two XLA parts the
kernel took in with PR 63). The last line is then ``{"ok": true, "moe":
[...], "moe_us_per_call": [{"shape", "us_per_call", "parts_us"}, ...],
"device": {...}}``.
``--kv-write`` likewise runs one check only: a prefill chunk's K/V write
(``ops/paged_attention.write_chunk_kv``) at the arena shapes of the
benchmark's cells, microseconds a layer call with the arenas carried as the
layer scan carries them — the whole-block tiles the chunk program writes
(``tile``), the row-wise scatter they replaced (``rows``) and the two other
whole-block forms that were timed against them (a loop of
``dynamic_update_slice``, a copy kernel) — each first held bit for bit to
the row-wise write outside block 0. The last line, also left in
``chiprun_out/kv_write.json``: ``{"ok": true, "kv_write": [...], "device":
{...}}``.
``--kv-decode`` likewise: a DECODE step's K/V write with the attention it
feeds at OLMoE's, the 7B's, MiMo's window, Keye's (at three contexts: the
walk's nanoseconds a token) and Ouro's shapes, one and four live rows of
four — the one op a layer calls (``paged_attention_write``) against the
pair it replaced (``write_block_kv`` then ``paged_attention``): the arenas
bit for bit, and the DEVICE time of one layer call of each from a profiler
trace, with the operations it is made of. The last line, also left in
``chiprun_out/kv_decode.json``: ``{"ok": true, "kv_decode": [...], "device":
{...}}``. With ``--beside FILE`` (the ``kv_decode.json`` that this script
left when run from ANOTHER tree, the parent commit's: copy this file over
that tree's and run it there first, in the same chip call) form ``fused``
of both trees is printed side by side and kept under ``"beside"``.
``--index-scores`` likewise: a selecting decode step's SCORE call alone
(``ops/paged_attention.index_scores_tpu``) at Keye's shape (16 index heads,
128 lanes, block 32, table 288, four rows of which one live) at 2.5 / 5 /
8.7 k of context: DEVICE microseconds a layer call from a profiler trace,
nanoseconds a token of context, the share of its bytes' roofline, the same
at every cell width the tree's kernel takes, and a hash of the scores of
the attendable columns — the same seed gives the same hash on every tree
whose kernel scores a column as this one does. The last line, also left in
``chiprun_out/index_scores.json``: ``{"ok": true, "index_scores": [...],
"device": {...}}``; exit 1 where the kernel's scores are not the XLA
branch's.
``--select`` likewise: a selecting decode step's SEARCH alone
(``ops/paged_attention.select_mask`` with the fusion that reads its mask) at
Keye's shape (a slot's ``[4, 9216]`` float32 scores, ``topk`` 2,048, 12
layers), one live row and four, at 2.5 / 5 / 8.7 k of context, plus an input
whose zeros of both signs tie across the ``topk``-th score: the search in XLA
(``PAGED_FORCE_KERNEL=xla``) beside the search as the tree runs it on the
chip (the kernel ``select_topk`` where it has one, at 1, 2 and 3 bits a
pass), each mask held to ``select_tokens``' set, DEVICE microseconds a layer
call from a profiler trace with the operations it is made of; then the mask
alone at slots of 1, 2, 8 and 16 rows. The last line,
also left in ``chiprun_out/select.json``: ``{"ok": true, "select": [...],
"device": {...}}``; exit 1 where a mask is not the oracle's set.
``--ssm`` likewise: a decode step's state update of a recurrent-state model
(``ops/ssm.ssm_step_rows``) at Nemotron-3-Super's published mixer shape (128
heads of 64, a state of 128, 8 groups; 8 layers x 4 rows of float32 state
carried), 1 and 4 live rows of the slot's 4: the kernel (``ssm_rows``)
against the XLA loop — the largest difference of the read-out and of the new
state, a row that is not live bit for bit — and microseconds a layer call of
each. Then a Mamba-1 mixer's decode step at AI21-Jamba2-3B's published mixer
widths (5,120 channels, a state of 16, a rank of 160; 26 layers x 4 rows
carried; projections of a hidden size of 128, so that what lies between them
is what is timed): the FUSED step (``ops/ssm.mixer_step_rows``, the kernel
``ssm_mixer``) beside the SPLIT path through ``models/jamba.mixer_block`` at 1
and 4 live rows — the differences, a dead row's state and tail bit for bit,
DEVICE microseconds a layer call of each from a profiler trace with the
operations it is made of, and ``mixer_step_path``: what
``server_recurrent_mixer_step`` reads on this chip. The last line, also left
in ``chiprun_out/ssm.json``: ``{"ok": true, "ssm": [...], "device": {...}}``;
exit 1 where a kernel and its reference disagree.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import functools
import http.client
import json
import os
import resource
import shutil
import signal
import socket
import subprocess
import sys
import time
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".smoke")

#: The full-size smoke. ``serve`` is the daemon's geometry; the kernel
#: check derives its shapes from the same numbers, so what it compiles is
#: what the daemon dispatches. Block size 32 is kernel-eligible for the
#: 2-byte AND the 1-byte arena; capacity 2048 leaves room for a prompt
#: several prefill chunks long plus a radix-hit suffix that still needs
#: chunking.
FULL = {
    "preset": "qwen25_7b",
    "overrides": {},
    "seed": 20260926,
    "dtype": "bf16",
    "quantize": True,  # int8 layer weights, bf16 vocab tables
    "serve": {
        "capacity": 2048,
        "batch_per_slot": 4,
        "kv_block_size": 32,
        "kv_blocks": 513,
        "prefill_chunk": 128,
    },
    "max_tokens": 16,
    "short_prompt": 24,  # < one block: an exact resubmit cannot radix-hit
    "long_prompt": 500,  # bucket 512 = four prefill chunks
    "shared_prefix": 384,  # whole blocks of the long prompt, resubmitted
    "shared_suffix": 200,  # suffix bucket 256 > chunk: chunked from offset
}

#: max |kernel - XLA| on unit-variance inputs with bf16 outputs. The two
#: paths round in different places (the kernel keeps a running softmax in
#: f32 and casts p to bf16 per block); ~1.6e-2 was normal for the flash
#: kernel at S=C=2048.
KERNEL_TOL = 2.5e-2


# --------------------------------------------------------------- children
# Everything below this line that imports jax or llm_sharding_tpu runs in a
# child process (``--child``) or under pytest — never in the smoke's parent.


def model_config(spec: dict):
    import dataclasses

    from llm_sharding_tpu.models import config as config_mod

    cfg = getattr(config_mod, spec["preset"])()
    return dataclasses.replace(cfg, **spec["overrides"])


def tensor_source(cfg, seed: int):
    """A seeded stand-in for a checkpoint: HF tensor name → float32 array,
    made on demand, one tensor at a time (what ``save_shards_streaming``
    asks of a safetensors reader). Every tensor has its own stream, keyed
    by its name, so the store is the same whatever order it is read in.
    Values are uniform with the usual 0.02 standard deviation (numpy draws
    uniforms ~9x faster than normals, and 7.6 G of them are drawn); a
    projection comes as the transpose of a row-major array, the layout the
    converter's own ``.T`` turns back into a contiguous one."""
    import numpy as np

    H, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    q_out = cfg.num_attention_heads * cfg.head_dim_
    kv_out = cfg.num_key_value_heads * cfg.head_dim_
    shapes = {
        "self_attn.q_proj.weight": (q_out, H),
        "self_attn.k_proj.weight": (kv_out, H),
        "self_attn.v_proj.weight": (kv_out, H),
        "self_attn.o_proj.weight": (H, q_out),
        "mlp.gate_proj.weight": (F, H),
        "mlp.up_proj.weight": (F, H),
        "mlp.down_proj.weight": (H, F),
        "input_layernorm.weight": (H,),
        "post_attention_layernorm.weight": (H,),
    }
    if cfg.attention_bias:  # the qwen2 family biases q/k/v, not o
        shapes.update({
            "self_attn.q_proj.bias": (q_out,),
            "self_attn.k_proj.bias": (kv_out,),
            "self_attn.v_proj.bias": (kv_out,),
        })
    top = {
        "model.embed_tokens.weight": (V, H),
        "model.norm.weight": (H,),
        "lm_head.weight": (V, H),
    }

    def get(name: str):
        if name in top:
            shape = top[name]
        else:
            shape = shapes.get(name.split(".", 3)[-1]) if name.startswith(
                "model.layers."
            ) else None
            if shape is None:
                raise KeyError(name)
        rng = np.random.default_rng([seed, zlib.crc32(name.encode())])
        w = rng.random(shape[::-1], dtype=np.float32).T
        w -= 0.5
        if name.endswith("norm.weight"):  # RMSNorm gains sit around one
            w *= 0.2
            w += 1.0
        else:
            w *= 0.02 * 12 ** 0.5  # U(-a, a) with std 0.02
        return w

    return get


def write_store(spec: dict, out_dir: str) -> dict:
    """Write the smoke's shard store with the product's streaming writer.
    A store already there with the same spec is kept (the second smoke of
    one chip call measures a warm start, not a second write)."""
    import jax.numpy as jnp

    from llm_sharding_tpu.utils.shard_store import save_shards_streaming

    marker = os.path.join(out_dir, "smoke_spec.json")
    want = {k: spec[k] for k in ("preset", "overrides", "seed", "dtype",
                                 "quantize")}
    t0 = time.time()
    reused = False
    if os.path.exists(marker):
        with open(marker) as f:
            reused = json.load(f) == want
    if not reused:
        shutil.rmtree(out_dir, ignore_errors=True)
        cfg = model_config(spec)
        save_shards_streaming(
            cfg, tensor_source(cfg, spec["seed"]), out_dir,
            dtype={"bf16": jnp.bfloat16, "f32": jnp.float32}[spec["dtype"]],
            quantize=spec["quantize"],
        )
        with open(marker, "w") as f:
            json.dump(want, f)
    return {
        "reused": reused,
        "seconds": round(time.time() - t0, 1),
        "bytes": store_bytes(out_dir),
    }


#: The decode kernel's work follows each row's written frontier, so its
#: cases carry a STATE: ``ragged`` (every row live, each at its own
#: length), ``one_row_eighth`` (one live row at 1/8 of the table beside
#: dead rows: what a lightly loaded server decodes) and ``all_rows_full``
#: (every row at the full table: where the walk saves nothing and the
#: kernel must still be no slower than before).
DECODE_STATES = ("ragged", "one_row_eighth", "all_rows_full")


def kernel_cases(spec: dict) -> list[dict]:
    """Every pallas_call the CLI can select, at the daemon's geometry: the
    paged decode kernel (S = 1, in each of ``DECODE_STATES``) and the
    chunked-prefill kernel (S = the prefill chunk) over bf16, int8 and fp8
    arenas, and the flash kernel the one-shot admission uses (S = the short
    prompt's bucket over the full window)."""
    sv = spec["serve"]
    cases = [
        {"kernel": "paged_decode", "kv_dtype": kvd, "state": state}
        for kvd in ("bf16", "int8", "fp8") for state in DECODE_STATES
    ] + [
        {"kernel": "paged_prefill", "kv_dtype": kvd, "state": "ragged"}
        for kvd in ("bf16", "int8", "fp8")
    ]
    cases.append({"kernel": "flash", "kv_dtype": "bf16", "state": "ragged"})
    bucket = 8
    while bucket < spec["short_prompt"]:
        bucket *= 2
    for c in cases:
        c.update(
            rows=sv["batch_per_slot"], block_size=sv["kv_block_size"],
            table_width=-(-sv["capacity"] // sv["kv_block_size"]),
            q_len={"paged_decode": 1, "paged_prefill": sv["prefill_chunk"],
                   "flash": bucket}[c["kernel"]],
        )
    return cases


def kernel_inputs(cfg, case: dict, seed: int = 0):
    """One kernel case's operands on a seeded random arena: ``(args,
    scales, nlive)`` — for the paged kernels the ops' positional arguments
    over a head-major stack of three layers with other contents in each,
    attended at a layer that is not the first (a kernel that ignored its
    layer operand would read layer 0), the scale keywords of a quantized
    arena, and the blocks covering each row's written frontier; for the
    flash kernel its ``(q, k, v, q_positions, kv_positions)`` alone."""
    import numpy as np
    import jax.numpy as jnp

    import llm_sharding_tpu.models  # noqa: F401 — ops import through models
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops.quant import kv_qmax, kv_storage_dtype

    rng = np.random.default_rng([seed, zlib.crc32(json.dumps(
        case, sort_keys=True).encode())])
    B, BS, T, S = (case["rows"], case["block_size"], case["table_width"],
                   case["q_len"])
    Nh, Nkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim_)
    W = T * BS
    dt = jnp.bfloat16
    # tokens already in the window behind each row's S query positions
    if case["state"] == "all_rows_full":
        ctx = np.full(B, W - S)
    elif case["state"] == "one_row_eighth":
        ctx = np.zeros(B, np.int64)
        ctx[0] = W // 8 - S
    else:  # ragged rows
        ctx = rng.integers(W // 4, W - S, B)
    qpos = (ctx[:, None] + np.arange(S)[None]).astype(np.int32)
    cols = np.arange(W)[None]
    kvpos = np.where(cols < (ctx + S)[:, None], cols, int(POS_SENTINEL))
    q = jnp.asarray(rng.standard_normal((B, S, Nh, D), np.float32), dt)

    if case["kernel"] == "flash":
        k = jnp.asarray(rng.standard_normal((B, W, Nkv, D), np.float32), dt)
        v = jnp.asarray(rng.standard_normal((B, W, Nkv, D), np.float32), dt)
        return ((q, k, v, jnp.asarray(qpos), jnp.asarray(kvpos, jnp.int32)),
                {}, None)
    # blocks covering the written frontier; a row with nothing behind its
    # query is dead: its table stays all trash
    nlive = np.where(ctx > 0, -(-(ctx + S) // BS), 0)
    NB = int(nlive.sum()) + 1  # + the trash block 0
    ids = rng.permutation(np.arange(1, NB))
    table = np.zeros((B, T), np.int32)
    at = 0
    for b in range(B):
        table[b, : nlive[b]] = ids[at: at + nlive[b]]
        at += nlive[b]
    store = kv_storage_dtype(case["kv_dtype"], dt)
    L = 3
    layer = int(rng.integers(1, L))
    vals = rng.standard_normal((2, L, NB, Nkv, BS, D), np.float32)
    scales = {}
    if case["kv_dtype"] == "bf16":
        k_arena, v_arena = (jnp.asarray(a, store) for a in vals)
    else:  # codes spanning the code range + per-block-per-head scales
        qmax = kv_qmax(store)
        codes = np.clip(vals * (qmax / 3.0), -qmax, qmax)
        if case["kv_dtype"] == "int8":
            codes = np.round(codes)
        k_arena, v_arena = (jnp.asarray(a, store) for a in codes)
        sc = rng.uniform(0.5, 1.5, (2, L, NB, Nkv)) * (3.0 / qmax)
        scales = {"k_scale": jnp.asarray(sc[0], jnp.float32),
                  "v_scale": jnp.asarray(sc[1], jnp.float32)}
    args = (q, k_arena, v_arena, layer, jnp.asarray(table),
            jnp.asarray(qpos), jnp.asarray(kvpos, jnp.int32))
    return args, scales, nlive


def check_kernel(cfg, case: dict, backend: str, seed: int = 0) -> float:
    """Run one kernel variant (``backend`` = "kernel" on the chip,
    "interpret" under pytest) and the XLA path on the same operands
    (``kernel_inputs``); return max |difference|."""
    import numpy as np
    import jax.numpy as jnp

    from llm_sharding_tpu.ops import attention, flash_attention
    from llm_sharding_tpu.ops import paged_attention as pa

    args, scales, nlive = kernel_inputs(cfg, case, seed)
    if case["kernel"] == "flash":
        got = flash_attention.flash_attention(
            *args, interpret=(backend == "interpret")
        )
        want = attention.cached_attention(*args)
    else:
        if case["kernel"] == "paged_decode":
            got = pa.paged_attention(*args, backend=backend, **scales)
        else:
            got = pa.paged_prefill(
                *args, backend=backend, nlive=jnp.asarray(nlive, jnp.int32),
                **scales,
            )
        want = pa.paged_attention_xla(*args, **scales)
    got = np.asarray(got.astype(jnp.float32))
    if not np.isfinite(got).all():
        raise AssertionError(f"{case}: non-finite kernel output")
    return float(np.abs(got - np.asarray(want.astype(jnp.float32))).max())


def best_of_three(run, operands) -> float:
    """Seconds of the fastest of three calls of a program that is warm."""
    import jax

    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*operands))
        best = min(best, time.perf_counter() - t0)
    return best


def time_decode(cfg, case: dict, backend: str, calls: int = 64) -> dict:
    """Milliseconds per call of the paged decode op through ``backend`` and
    through the XLA path on one case's operands: ``calls`` calls inside ONE
    program (a loop over the stack's layers, so no host dispatch is timed),
    warmed up, best of three."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_tpu.ops import paged_attention as pa

    args, scales, _ = kernel_inputs(cfg, case)
    q, k_arena, v_arena, _, table, qpos, kvpos = args
    layers = jnp.arange(calls, dtype=jnp.int32) % k_arena.shape[0]

    def timed(how):
        @jax.jit
        def run(q, k_arena, v_arena, table, qpos, kvpos, scales):
            def one(total, layer):
                out = pa.paged_attention(
                    q, k_arena, v_arena, layer, table, qpos, kvpos,
                    backend=how, **scales,
                )
                return total + out.astype(jnp.float32).sum(), None
            return jax.lax.scan(one, jnp.float32(0), layers)[0]

        operands = (q, k_arena, v_arena, table, qpos, kvpos, scales)
        run(*operands).block_until_ready()
        return round(best_of_three(run, operands) / calls * 1e3, 4)

    return {"kernel_ms": timed(backend), "xla_ms": timed("xla")}


#: the expert kernel's two regimes: a decode step's rows, a prefill chunk's
#: positions (``batch_per_slot`` rows x ``prefill_chunk``)
MOE_ROWS = (4, 1024)


def check_moe_kernel(cfg, rows: int, backend: str, seed: int = 0) -> float:
    """The expert product of ``ops/moe.py`` through ``backend`` ("kernel" on
    the chip, "interpret" under pytest) and through its XLA path on the same
    seeded int8 experts — a stack of ``MOE_DEPTH`` layers read at the last, a third
    of the rows dead; max |difference| relative to the output's scale."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    import llm_sharding_tpu.models  # noqa: F401 — ops import through models
    from llm_sharding_tpu.ops import moe
    from llm_sharding_tpu.ops.quant import QTensor

    H, F = cfg.hidden_size, cfg.intermediate_size
    E, k, L = cfg.num_experts, cfg.num_experts_per_tok, MOE_DEPTH
    ks = jax.random.split(jax.random.key(seed * 7919 + rows), 9)
    dt = jnp.bfloat16

    def codes(key, *shape):
        return jax.random.randint(key, shape, -127, 128, jnp.int8)

    def scales(key, n, fan):
        return (jax.random.uniform(key, (L, n), jnp.float32, 0.5, 1.5)
                * (fan ** -0.5 / 64.0)).astype(dt)

    wg = QTensor(codes(ks[0], L, H, E * F), scales(ks[1], E * F, H))
    wu = QTensor(codes(ks[2], L, H, E * F), scales(ks[3], E * F, H))
    wd = QTensor(codes(ks[4], L, E * F, H), scales(ks[5], H, F))
    x = jax.random.normal(ks[6], (rows, H), jnp.float32).astype(dt)
    router = jax.random.normal(ks[7], (H, E), jnp.float32).astype(dt)
    live = jax.random.uniform(ks[8], (rows,)) > 1 / 3

    # the weights are arguments: closed over, 0.8 GB of them would be baked
    # into each program as constants
    @functools.partial(jax.jit, static_argnames="how")
    def run(x, live, router, wg, wu, wd, how):
        w, ids = moe.route(x, router, k, cfg.norm_topk_prob)
        return moe.expert_mlp(
            x, w, ids, wg, wu, wd, E, live=live, layer=jnp.int32(L - 1),
            backend=how,
        )

    got, st = run(x, live, router, wg, wu, wd, how=backend)
    want, st_x = run(x, live, router, wg, wu, wd, how="xla")
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    if not np.isfinite(got).all():
        raise AssertionError(f"rows={rows}: non-finite expert output")
    if not np.array_equal(st.expert_tokens, st_x.expert_tokens):
        raise AssertionError(f"rows={rows}: the two paths count differently")
    return float(np.abs(got - want).max() / np.abs(want).max())


#: a decode call's shapes, timed: the hidden and expert widths, the experts
#: the router scores, the share of them held here (None: all)
MOE_TIMED = (
    {"name": "olmoe_1b_7b", "H": 2048, "F": 1024, "E": 64, "held": None},
    {"name": "gigachat31_702b_a36b", "H": 7168, "F": 2048, "E": 256,
     "held": (0, 16)},
    {"name": "keye_vl2_30b_a3b", "H": 2048, "F": 768, "E": 128, "held": None},
)
MOE_MET = (0, 1, 8)
#: layers of the stacks the expert calls are checked and timed on: NOT whole
#: sublane tiles of scale rows, as the benchmark's stages are not (12, 9, ..)
MOE_DEPTH = 9


def time_moe(shape: dict, met: int, backend: str, calls: int = 64,
             k: int = 8) -> float:
    """Microseconds per call of ``expert_mlp`` through ``backend`` with ONE
    live row of four whose ``k`` choices meet ``met`` distinct held experts
    (the rest of them repeat the first, or fall on experts held elsewhere;
    0: no row routes) — the tiles' building, the kernel and the combine, as
    a decode step pays them. ``calls`` calls inside ONE program over a stack
    of ``MOE_DEPTH`` layers, each call's input hanging on the one before, warmed up,
    best of three."""
    import jax
    import jax.numpy as jnp

    import llm_sharding_tpu.models  # noqa: F401 — ops import through models
    from llm_sharding_tpu.ops import moe
    from llm_sharding_tpu.ops.quant import QTensor

    H, F, E, L = shape["H"], shape["F"], shape["E"], MOE_DEPTH
    first, count = shape["held"] or (0, E)
    ks = jax.random.split(jax.random.key(met), 5)
    dt = jnp.bfloat16

    def leaf(key, rows, cols):
        return QTensor(
            jax.random.randint(key, (L, rows, cols), -127, 128, jnp.int8),
            jnp.full((L, cols), rows ** -0.5 / 64.0, dt),
        )

    wg, wu = leaf(ks[0], H, count * F), leaf(ks[1], H, count * F)
    wd = leaf(ks[2], count * F, H)
    x = jax.random.normal(ks[3], (4, H), jnp.float32).astype(dt)
    # a row's choices: ``met`` distinct held experts, the rest a repeat of
    # the first (every expert is held here) or an expert held elsewhere
    other = first if shape["held"] is None else first + count
    ids = jnp.asarray(
        [[first + j if j < met else other for j in range(k)]] * 4, jnp.int32
    )
    w = jnp.full((4, k), 1.0 / k, jnp.float32)
    live = jnp.asarray([met > 0, False, False, False])
    layers = jnp.arange(calls, dtype=jnp.int32) % L

    @jax.jit
    def run(x, w, ids, live, wg, wu, wd):
        def one(carry, layer):
            total, read = carry
            # hangs on the call before: nothing is hoisted out of the loop
            tied = (total * 0.0).astype(jnp.int32)
            out, st = moe.expert_mlp(
                x + tied.astype(dt), w, ids + tied, wg, wu, wd, E, live=live,
                layer=layer, backend=backend, held=shape["held"],
            )
            total = total + out.astype(jnp.float32).sum()
            return (total, read + st.experts_read), None
        return jax.lax.scan(one, (jnp.float32(0), jnp.int32(0)), layers)[0]

    operands = (x, w, ids, live, wg, wu, wd)
    _, read = run(*operands)
    if int(read) != met * calls:
        raise AssertionError(
            f"{shape['name']}: {int(read)} experts read in {calls} calls "
            f"that should meet {met} each"
        )
    return round(best_of_three(run, operands) / calls * 1e6, 2)


def time_moe_parts(shape: dict, backend: str, calls: int = 64,
                   k: int = 8, met: int = 8) -> dict:
    """Microseconds per call of the PARTS of the decode call ``time_moe``
    times whole (one live row of four that meets ``met`` held experts), each
    alone in a loop of its own, ``calls`` calls in one program, each call's
    input hanging on the one before; ``loop`` is that loop around nothing,
    what every other part's reading holds of it. ``tiles`` — what
    ``expert_mlp`` computes before its kernel (``moe.live_pairs``: the dead
    rows' ids masked, the pairs an expert has); ``kernel`` — ``expert_decode_tpu`` on counts
    built once outside the loop. And what the call paid BESIDE its kernel
    until PR 63, which the kernel now does inside, as the plain XLA they
    were: ``scales`` — two ``dynamic_index_in_dim`` slices of ``[L, E·F]``
    scale stacks; ``combine`` — the ``where`` over the live tiles of a ``[NT,
    8, H]`` float32 output, the ``einsum`` with the router weights and
    ``we_down``'s scale."""
    import jax
    import jax.numpy as jnp

    import llm_sharding_tpu.models  # noqa: F401 — ops import through models
    from llm_sharding_tpu.ops import moe

    H, F, E, L = shape["H"], shape["F"], shape["E"], MOE_DEPTH
    first, count = shape["held"] or (0, E)
    ks = jax.random.split(jax.random.key(met), 5)
    dt, f32 = jnp.bfloat16, jnp.float32

    def codes(key, rows, cols):
        return jax.random.randint(key, (L, rows, cols), -127, 128, jnp.int8)

    wg, wu = codes(ks[0], H, count * F), codes(ks[1], H, count * F)
    wd = codes(ks[2], count * F, H)
    sg = jnp.full((L, count * F), H ** -0.5 / 64.0, dt)
    su = jnp.full((L, count * F), H ** -0.5 / 32.0, dt)
    sd = jnp.full((L, H), F ** -0.5 / 64.0, dt)
    x = jax.random.normal(ks[3], (4, H), f32).astype(dt)
    ids = jnp.asarray(
        [[j if j < met else (0 if shape["held"] is None else count)
          for j in range(k)]] * 4, jnp.int32
    )  # (relative to the first held expert; ``count``: held elsewhere)
    w = jnp.where(ids < count, 1.0 / k, 0.0).astype(f32)
    live = jnp.asarray([met > 0, False, False, False])
    layers = jnp.arange(calls, dtype=jnp.int32) % L

    masked, counts = moe.live_pairs(ids, live, count)
    tiles, cw, _ = jax.jit(moe._decode_tiles, static_argnums=4)(
        x, w, ids, live, count
    )
    y = jax.random.normal(ks[4], (cw.shape[0], 8, H), f32)
    # (the weights are arguments: closed over they would be baked into each
    # program as constants)
    operands = (wg, wu, wd, sg, su, sd, y)

    # each part: (carry, layer, operands) -> f32 that hangs on what the part
    # computed
    def part_loop(tied, layer, _):
        return (layer + tied.astype(jnp.int32)).astype(f32)

    def part_tiles(tied, layer, _):
        m, c = moe.live_pairs(ids + tied.astype(jnp.int32), live, count)
        return (m.sum() + c.sum()).astype(f32)

    def part_kernel(tied, layer, ops):
        return moe.expert_decode_tpu(
            x + tied.astype(dt), w, masked, counts, layer, *ops[:6],
            interpret=backend == "interpret",
        ).sum()

    def part_scales(tied, layer, ops):
        lyr = layer + tied.astype(jnp.int32)
        rows = jax.lax.optimization_barrier(tuple(
            jax.lax.dynamic_index_in_dim(s, lyr, keepdims=True)
            for s in ops[3:5]
        ))
        return sum(r[0, 0].astype(f32) for r in rows)

    def part_combine(tied, layer, ops):
        sd, y = ops[5:]
        alive = jnp.arange(cw.shape[0], dtype=jnp.int32) < tiles.n_live
        out = jnp.einsum(
            "jn,jnh->nh", cw + tied,
            jnp.where(alive[:, None, None], y[:, :4], 0.0),
            precision=jax.lax.Precision.HIGHEST,
        ) * jax.lax.dynamic_index_in_dim(sd, layer, keepdims=False).astype(f32)
        return out.astype(dt).astype(f32).sum()

    def timed(part):
        @jax.jit
        def run(layers, ops):
            def one(total, layer):
                return total + part(total * 0.0, layer, ops), None
            return jax.lax.scan(one, f32(0), layers)[0]

        run(layers, operands).block_until_ready()
        return round(
            best_of_three(run, (layers, operands)) / calls * 1e6, 2
        )

    return {"loop": timed(part_loop), "tiles": timed(part_tiles),
            "kernel": timed(part_kernel), "scales": timed(part_scales),
            "combine": timed(part_combine)}


def child_moe(spec: dict, out_path: str) -> None:
    import jax

    from llm_sharding_tpu.utils.compile_cache import enable_persistent_cache
    from llm_sharding_tpu.utils.device_report import device_report

    platform = jax.devices()[0].platform
    require_tpu(platform, "the expert kernel check")
    enable_persistent_cache(platform)
    cfg = model_config(spec)
    results = []
    for rows in MOE_ROWS:
        err = check_moe_kernel(cfg, rows, "kernel")
        results.append({"kernel": "moe_experts", "rows": rows,
                        "max_rel_err": err, "ok": err <= KERNEL_TOL})
        print(f"[moe] moe_experts rows={rows:<5d} max rel err={err:.3e} "
              f"{'ok' if err <= KERNEL_TOL else 'OVER ' + str(KERNEL_TOL)}",
              flush=True)
    timed = []
    for shape in MOE_TIMED:
        us = {met: time_moe(shape, met, "kernel") for met in MOE_MET}
        parts = time_moe_parts(shape, "kernel")
        timed.append({"shape": shape["name"], "us_per_call": us,
                      "parts_us": parts})
        print(f"[moe] moe call {shape['name']}: "
              + ", ".join(f"{m} met {u} us" for m, u in us.items())
              + "; of 8 met: "
              + ", ".join(f"{p} {u} us" for p, u in parts.items()),
              flush=True)
    with open(out_path, "w") as f:
        json.dump({"device": device_report(), "kernels": results,
                   "moe_us_per_call": timed}, f)
    if not all(r["ok"] for r in results):
        raise SystemExit("chip_smoke: the expert kernel disagrees with XLA")


#: a prefill chunk's K/V write at the shapes the benchmark's cells have: the
#: key/value heads of the arena, the lanes of a stored key and of a value
#: (0: a latent arena holds none), the pool's blocks and the stage's layers.
#: The chunk is ``batch_per_slot`` 4 rows x ``prefill_chunk`` 256 over
#: ``kv_block_size`` 32 everywhere.
KV_WRITE_SHAPES = (
    {"name": "olmoe_1b_7b", "heads": 16, "dk": 128, "dv": 128,
     "blocks": 1025, "layers": 16},
    {"name": "qwen25_7b", "heads": 4, "dk": 128, "dv": 128,
     "blocks": 1921, "layers": 28},
    {"name": "qwen25_14b_pp4", "heads": 8, "dk": 128, "dv": 128,
     "blocks": 2305, "layers": 12},
    {"name": "gigachat31_702b_a36b", "heads": 1, "dk": 640, "dv": 0,
     "blocks": 4097, "layers": 9},
    # a key of 192 is stored in 256 lanes (models/mimo_v2.py)
    {"name": "mimo_v25.swa", "heads": 8, "dk": 256, "dv": 128,
     "blocks": 53, "layers": 9},
    {"name": "mimo_v25.full", "heads": 4, "dk": 256, "dv": 128,
     "blocks": 2049, "layers": 3},
)
#: ``tile``: ``write_chunk_kv`` as the chunk program calls it; ``rows``:
#: the row-wise ``write_block_kv`` it replaced; ``dus`` and ``kernel``: the
#: two other whole-block forms, kept here for the timing alone
KV_WRITE_FORMS = ("tile", "rows", "dus", "kernel")
KV_CHUNK = {"rows": 4, "chunk": 256, "block_size": 32}


def _write_tiles_dus(arena, layer, blk, tiles):
    """One ``dynamic_update_slice`` a tile, in a loop."""
    import jax

    B, nb = blk.shape

    def one(i, arena):
        b, j = i // nb, i % nb
        return jax.lax.dynamic_update_slice(
            arena, tiles[b, j][None, None], (layer, blk[b, j], 0, 0, 0)
        )
    return jax.lax.fori_loop(0, B * nb, one, arena)


def _write_tiles_kernel(arena, layer, blk, tiles, interpret=False):
    """A copy kernel: one grid step a tile, the table scalar-prefetched, the
    arena aliased in and out so that the blocks no step names are kept."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nb, Nkv, BS, D = tiles.shape

    def copy(layer_ref, blk_ref, tile_ref, arena_ref, out_ref):
        out_ref[...] = tile_ref[...]

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * nb,),
        in_specs=[
            pl.BlockSpec((None, Nkv, BS, D), lambda i, l, t: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (None, None, Nkv, BS, D), lambda i, l, t: (l[0], t[i], 0, 0, 0)
        ),
    )
    return pl.pallas_call(
        copy, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        input_output_aliases={3: 0}, interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), blk.reshape(-1),
      tiles.reshape(B * nb, Nkv, BS, D), arena)


def kv_write_form(form: str, interpret: bool = False):
    """``write(k_arena, v_arena, layer, table, col0, k_new, v_new)`` →
    ``(k_arena, v_arena)`` in one of ``KV_WRITE_FORMS``."""
    import jax.numpy as jnp

    import llm_sharding_tpu.models  # noqa: F401 — ops import through models
    from llm_sharding_tpu.ops import paged_attention as pa

    if form == "tile":
        return pa.write_chunk_kv
    if form == "rows":
        def rows(k_arena, v_arena, layer, table, col0, k_new, v_new):
            B, Sc = k_new.shape[:2]
            cols = jnp.broadcast_to(
                col0 + jnp.arange(Sc, dtype=jnp.int32)[None, :], (B, Sc)
            )
            return pa.write_block_kv(
                k_arena, v_arena, layer, table, cols, k_new, v_new
            )
        return rows
    one = {"dus": _write_tiles_dus,
           "kernel": functools.partial(_write_tiles_kernel,
                                       interpret=interpret)}[form]

    def whole_blocks(k_arena, v_arena, layer, table, col0, k_new, v_new):
        B, Sc, Nkv = k_new.shape[:3]
        BS = k_arena.shape[3]
        nb = Sc // BS
        blk = jnp.take(
            table, col0 // BS + jnp.arange(nb, dtype=jnp.int32), axis=1
        )

        def put(arena, new):
            if not arena.shape[-1]:
                return arena
            t = new.astype(arena.dtype).reshape(B, nb, BS, Nkv, -1)
            return one(arena, layer, blk, jnp.transpose(t, (0, 1, 3, 2, 4)))
        return put(k_arena, k_new), put(v_arena, v_new)
    return whole_blocks


def kv_write_inputs(shape: dict, seed: int = 0, blocks: int = None):
    """Arenas, a table that maps each of the chunk's rows to blocks of its
    own, and a chunk's fresh keys and values at ``shape``."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    B, Sc, BS = KV_CHUNK["rows"], KV_CHUNK["chunk"], KV_CHUNK["block_size"]
    NB = blocks or shape["blocks"]
    L, Nkv, dt = shape["layers"], shape["heads"], jnp.bfloat16
    T = 2 * Sc // BS  # room for a chunk at a later column
    rng = np.random.default_rng(seed)
    # blocks of its own for every row where the pool has that many; MiMo's
    # window pool (53 blocks) maps what a row can hold, the rest to block 0
    own = min(T, (NB - 1) // B)
    table = np.zeros((B, T), np.int32)
    table[:, T - own:] = (
        1 + rng.permutation(NB - 1)[: B * own].reshape(B, own)
    )
    ks = jax.random.split(jax.random.key(seed), 4)
    k_arena = jnp.zeros((L, NB, Nkv, BS, shape["dk"]), dt)
    v_arena = jnp.zeros((L, NB, Nkv, BS, shape["dv"]), dt)
    k_new = jax.random.normal(ks[0], (B, Sc, Nkv, shape["dk"]), dt)
    v_new = jax.random.normal(ks[1], (B, Sc, Nkv, shape["dv"]), dt)
    col0 = jnp.asarray(Sc, jnp.int32)
    return k_arena, v_arena, jnp.asarray(table), col0, k_new, v_new


def check_kv_write(shape: dict, form: str, interpret: bool = False,
                   blocks: int = None) -> bool:
    """Whether ``form`` leaves both arenas, outside block 0, bit for bit as
    the row-wise write leaves them, one layer of the stack written."""
    import jax
    import jax.numpy as jnp

    k_arena, v_arena, table, col0, k_new, v_new = kv_write_inputs(
        shape, seed=1, blocks=blocks
    )
    layer = shape["layers"] - 1
    got = jax.jit(kv_write_form(form, interpret))(
        k_arena, v_arena, layer, table, col0, k_new, v_new)
    want = jax.jit(kv_write_form("rows"))(
        k_arena, v_arena, layer, table, col0, k_new, v_new)
    return all(
        bool(jnp.array_equal(g[:, 1:], w[:, 1:])) for g, w in zip(got, want)
    )


def time_kv_write(shape: dict, form: str, calls: int = 64,
                  interpret: bool = False, blocks: int = None) -> float:
    """Microseconds per layer call of a chunk's K/V write in ``form``: the
    arenas are carried through ``calls`` calls of ONE program as the layer
    scan carries them (donated, so the write is in place or shows that it is
    not), each call's entries hanging on the arena the call before left,
    warmed up, best of three."""
    import jax
    import jax.numpy as jnp

    write = kv_write_form(form, interpret)
    k_arena, v_arena, table, col0, k_new, v_new = kv_write_inputs(
        shape, blocks=blocks
    )
    layers = jnp.arange(calls, dtype=jnp.int32) % shape["layers"]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(k_arena, v_arena, table, col0, k_new, v_new):
        def one(carry, layer):
            ka, va = carry
            # hangs on the call before: nothing is hoisted out of the loop
            tied = ka[layer, 0, 0, 0, :1] * 0
            return write(
                ka, va, layer, table, col0, k_new + tied, v_new + tied
            ), None
        return jax.lax.scan(one, (k_arena, v_arena), layers)[0]

    best = float("inf")
    for _ in range(4):  # the first call compiles
        t0 = time.perf_counter()
        k_arena, v_arena = jax.block_until_ready(
            run(k_arena, v_arena, table, col0, k_new, v_new)
        )
        best = min(best, time.perf_counter() - t0)
    return round(best / calls * 1e6, 2)


def child_kv_write(spec: dict, out_path: str) -> None:
    import jax

    from llm_sharding_tpu.utils.compile_cache import enable_persistent_cache
    from llm_sharding_tpu.utils.device_report import device_report

    platform = jax.devices()[0].platform
    require_tpu(platform, "the chunk's K/V write")
    enable_persistent_cache(platform)
    results = []
    for shape in KV_WRITE_SHAPES:
        same = {  # at a pool that leaves room for two copies of it
            f: check_kv_write(shape, f, blocks=min(shape["blocks"], 129))
            for f in KV_WRITE_FORMS if f != "rows"
        }
        us = {f: time_kv_write(shape, f) for f in KV_WRITE_FORMS}
        results.append({"shape": shape["name"], "us_per_layer_call": us,
                        "same_as_rows": same})
        print(f"[kv-write] {shape['name']}: "
              + ", ".join(f"{f} {u} us" for f, u in us.items())
              + f"; same as rows: {same}", flush=True)
    with open(out_path, "w") as f:
        json.dump({"device": device_report(), "kv_write": results}, f)
    if not all(all(r["same_as_rows"].values()) for r in results):
        raise SystemExit(
            "chip_smoke: a whole-block write differs from the row-wise one"
        )


#: A DECODE step's K/V write with the attention it feeds, at four of the
#: cells' shapes: ``group`` query heads a key/value head, ``table`` entries a
#: row (capacity / 32), each live row ``context`` tokens long, once a context
#: of ``contexts``; MiMo's window layers keep the blocks their window reaches
#: and a sink logit; Keye's rows grow to 8.7 k tokens inside one reply, so
#: its three contexts read the walk's slope (PERF.md, PR 54).
KV_DECODE_SHAPES = (
    {"name": "olmoe_1b_7b", "heads": 16, "group": 1, "dk": 128, "dv": 128,
     "blocks": 1025, "layers": 16, "table": 128, "contexts": (1024,)},
    {"name": "qwen25_7b", "heads": 4, "group": 7, "dk": 128, "dv": 128,
     "blocks": 1921, "layers": 28, "table": 128, "contexts": (1024,)},
    {"name": "mimo_v25.swa", "heads": 8, "group": 8, "dk": 256, "dv": 128,
     "blocks": 53, "layers": 9, "table": 256, "contexts": (4096,),
     "window": 128},
    # a looped step calls its 48 layers four times, 192 attention layer
    # calls: a pass's 48 arena slots of the 192 are enough to time one
    {"name": "ouro_2p6b", "heads": 16, "group": 1, "dk": 128, "dv": 128,
     "blocks": 161, "layers": 48, "table": 64, "contexts": (448,)},
    {"name": "keye_vl2_30b_a3b", "heads": 4, "group": 8, "dk": 128,
     "dv": 128, "blocks": 2305, "layers": 12, "table": 288,
     "contexts": (2560, 5120, 8704)},
)
#: ``scatter``: ``write_block_kv`` then ``paged_attention``, the pair a layer
#: called before; ``fused``: ``paged_attention_write``, the one op it calls
KV_DECODE_FORMS = ("scatter", "fused")
KV_DECODE_LIVE = (1, 4)


def kv_decode_form(form: str, backend: str, window: int = 0):
    """``step(q, k_new, v_new, k_arena, v_arena, layer, table, cols, qpos,
    kvpos, sink)`` → ``(out, k_arena, v_arena)`` in one of
    ``KV_DECODE_FORMS``."""
    import llm_sharding_tpu.models  # noqa: F401 — ops import through models
    from llm_sharding_tpu.ops import paged_attention as pa

    def scatter(q, k_new, v_new, k_arena, v_arena, layer, table, cols, qpos,
                kvpos, sink):
        k_arena, v_arena = pa.write_block_kv(
            k_arena, v_arena, layer, table, cols, k_new, v_new
        )
        return pa.paged_attention(
            q, k_arena, v_arena, layer, table, qpos, kvpos, backend=backend,
            window=window, sink=sink,
        ), k_arena, v_arena

    def fused(q, k_new, v_new, k_arena, v_arena, layer, table, cols, qpos,
              kvpos, sink):
        return pa.paged_attention_write(
            q, k_new, v_new, k_arena, v_arena, layer, table, cols, qpos,
            kvpos, backend=backend, window=window, sink=sink,
        )[:3]

    return {"scatter": scatter, "fused": fused}[form]


def kv_decode_inputs(shape: dict, live: int, seed: int = 0):
    """What one decode layer call takes at ``shape``: the first ``live`` of
    four rows ``context`` tokens long with the step's entry at the next
    column (a window layer's rows hold only the blocks the window reaches),
    the others dead — table all trash, position at the sentinel."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from llm_sharding_tpu.models.cache import POS_SENTINEL

    B, BS = KV_CHUNK["rows"], KV_CHUNK["block_size"]
    L, NB, Nkv, T = (shape[k] for k in ("layers", "blocks", "heads", "table"))
    col, window = shape["context"], shape.get("window", 0)
    first = max(col - window + 1, 0) // BS if window else 0
    own = col // BS + 1 - first
    rng = np.random.default_rng(seed)
    table = np.zeros((B, T), np.int32)
    table[:live, first:first + own] = (
        1 + rng.permutation(NB - 1)[: live * own].reshape(live, own)
    )
    kvpos = np.full((B, T * BS), POS_SENTINEL, np.int32)
    kvpos[:live, : col + 1] = np.arange(col + 1)
    at = np.where(np.arange(B) < live, col, 0).astype(np.int32)[:, None]
    qpos = np.where(np.arange(B)[:, None] < live, at, POS_SENTINEL)
    ks = jax.random.split(jax.random.key(seed), 6)
    dt = jnp.bfloat16
    normal = lambda k, *s: jax.random.normal(k, s, dt)
    return dict(
        q=normal(ks[0], B, 1, Nkv * shape["group"], shape["dk"]),
        k_new=normal(ks[1], B, 1, Nkv, shape["dk"]),
        v_new=normal(ks[2], B, 1, Nkv, shape["dv"]),
        k_arena=normal(ks[3], L, NB, Nkv, BS, shape["dk"]),
        v_arena=normal(ks[4], L, NB, Nkv, BS, shape["dv"]),
        table=jnp.asarray(table), cols=jnp.asarray(at),
        qpos=jnp.asarray(qpos.astype(np.int32)), kvpos=jnp.asarray(kvpos),
        sink=jax.random.normal(ks[5], (Nkv * shape["group"],)) if window
        else None,
    )


def _kv_decode_program(shape: dict, form: str, backend: str):
    """Every layer of ``shape`` called once, the arenas carried (donated) as
    the layer scan carries them."""
    import jax
    import jax.numpy as jnp

    step = kv_decode_form(form, backend, shape.get("window", 0))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(k_arena, v_arena, q, k_new, v_new, table, cols, qpos, kvpos,
            sink):
        def one(carry, layer):
            ka, va = carry
            out, ka, va = step(
                q, k_new, v_new, ka, va, layer, table, cols, qpos, kvpos,
                sink,
            )
            return (ka, va), out
        return jax.lax.scan(
            one, (k_arena, v_arena),
            jnp.arange(shape["layers"], dtype=jnp.int32),
        )

    def call(inp):
        rest = [inp[k] for k in (
            "q", "k_new", "v_new", "table", "cols", "qpos", "kvpos", "sink")]
        (inp["k_arena"], inp["v_arena"]), out = run(
            inp["k_arena"], inp["v_arena"], *rest)
        return out

    return call


def check_kv_decode(shape: dict, live: int, backend: str) -> dict:
    """The fused call against the pair it replaced on the same inputs:
    whether both arenas are the same bit for bit outside block 0, and the
    largest difference of the outputs (the same kernel over the same bytes:
    none)."""
    import jax.numpy as jnp

    got = {}
    for form in KV_DECODE_FORMS:
        inp = kv_decode_inputs(shape, live, seed=1)
        out = _kv_decode_program(shape, form, backend)(inp)
        got[form] = (out, inp["k_arena"], inp["v_arena"])
    (o_s, k_s, v_s), (o_f, k_f, v_f) = got["scatter"], got["fused"]
    return {
        "arenas_same": bool(jnp.array_equal(k_s[:, 1:], k_f[:, 1:]))
        and bool(jnp.array_equal(v_s[:, 1:], v_f[:, 1:])),
        "max_err": float(jnp.max(jnp.abs(
            o_s.astype(jnp.float32) - o_f.astype(jnp.float32)))),
    }


def device_op_us(trace_dir: str) -> dict:
    """Microseconds on the first TPU by operation name, from the profiler
    trace under ``trace_dir``. A loop's own event spans its body's: names
    that start with ``while`` are left out so the body counts once."""
    import collections
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    us = collections.defaultdict(float)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = ev.name.split(" = ", 1)[0].lstrip("%")
                if not name.startswith("while"):
                    us[name] += ev.duration_ns / 1e3
    return dict(us)


def time_kv_decode(shape: dict, live: int, form: str, backend: str = "kernel",
                   runs: int = 8) -> dict:
    """DEVICE microseconds of ONE layer call in ``form``, from a profiler
    trace of ``runs`` executions of the program that calls every layer once
    (a clock around a loop of calls reads the loop's glue: PERF.md, PR 44
    and PR 45): the sum over the operations and the largest of them."""
    import jax

    call = _kv_decode_program(shape, form, backend)
    inp = kv_decode_inputs(shape, live)
    jax.block_until_ready(call(inp))  # compiles
    trace_dir = os.path.join(WORK, "trace_kv_decode")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(runs):
        out = call(inp)
    jax.block_until_ready((out, inp["k_arena"], inp["v_arena"]))
    jax.profiler.stop_trace()
    calls = runs * shape["layers"]
    ops = sorted(device_op_us(trace_dir).items(), key=lambda kv: -kv[1])
    return {
        "us_per_layer_call": round(sum(u for _, u in ops) / calls, 2),
        "ops_us": [[n, round(u / calls, 2)] for n, u in ops[:8]],
    }


def fused_beside(parent: list, change: list) -> list:
    """Form ``fused`` of two trees' ``kv_decode`` results, a row a (shape,
    live rows, context) both timed: DEVICE microseconds a layer call and
    the operations they are made of."""
    at = lambda r: (r["shape"], r["live"], r["context"])
    theirs = {at(r): r["fused"] for r in parent}
    rows = []
    for r in change:
        if at(r) not in theirs:
            continue
        old, new = theirs[at(r)], r["fused"]
        rows.append({
            "shape": r["shape"], "live": r["live"], "context": r["context"],
            "parent_us": old["us_per_layer_call"],
            "change_us": new["us_per_layer_call"],
            "delta_us": round(
                new["us_per_layer_call"] - old["us_per_layer_call"], 2),
            "parent_ops_us": old["ops_us"], "change_ops_us": new["ops_us"],
        })
    return rows


def walk_slope(walk: list, live: int) -> float:
    """Nanoseconds a token walked between the shortest and the longest of
    ``walk``'s ``(context, us a layer call)`` readings, ``live`` rows each
    ``context`` long."""
    (c0, us0), (c1, us1) = walk[0], walk[-1]
    return round(1e3 * (us1 - us0) / (live * (c1 - c0)), 2)


def child_kv_decode(spec: dict, out_path: str) -> None:
    import jax

    from llm_sharding_tpu.utils.compile_cache import enable_persistent_cache
    from llm_sharding_tpu.utils.device_report import device_report

    platform = jax.devices()[0].platform
    require_tpu(platform, "the decode step's K/V write")
    enable_persistent_cache(platform)
    results = []
    for shape in KV_DECODE_SHAPES:
        for live in KV_DECODE_LIVE:
            walk = []  # (context, the decode kernel's us a layer call)
            for context in shape["contexts"]:
                at = {**shape, "context": context}
                same = check_kv_decode(at, live, "kernel")
                timed = {f: time_kv_decode(at, live, f)
                         for f in KV_DECODE_FORMS}
                results.append({"shape": shape["name"], "live": live,
                                "context": context, **same, **timed})
                walk.append((context, next(
                    us for name, us in timed["fused"]["ops_us"]
                    if name.startswith("paged_decode"))))
                print(f"[kv-decode] {shape['name']} at {context} live {live}: "
                      + ", ".join(
                          f"{f} {t['us_per_layer_call']} us {t['ops_us']}"
                          for f, t in timed.items())
                      + f"; {same}", flush=True)
            if len(walk) > 1:
                results[-1]["walk_ns_per_token"] = walk_slope(walk, live)
                print(f"[kv-decode] {shape['name']} live {live}: "
                      f"paged_decode {walk} (context, us a layer call): "
                      f"{results[-1]['walk_ns_per_token']} ns a token of "
                      "context", flush=True)
    with open(out_path, "w") as f:
        json.dump({"device": device_report(), "kv_decode": results}, f)
    if not all(r["arenas_same"] and r["max_err"] <= KERNEL_TOL
               for r in results):
        raise SystemExit(
            "chip_smoke: the fused decode write differs from the scatter's"
        )


#: a selecting decode step's score call at Keye-VL-2.0-30B-A3B's shape: the
#: slot's four rows of which one is live, 12 selecting layers' index arena
INDEX_SHAPE = {"name": "keye_vl2_30b_a3b", "rows": 4, "live": 1, "heads": 16,
               "lanes": 128, "block_size": 32, "table": 288, "blocks": 2305,
               "layers": 12, "contexts": (2560, 5120, 8704)}
#: blocks a cell the sweep tries beside the rule's own choice (None)
INDEX_WIDTHS = (None, 8, 16, 32, 48, 64, 96, 144)
#: one v5e chip's HBM bytes a second (Google Cloud documentation, "TPU v5e")
HBM_BYTES_PER_S = 819e9


def index_inputs(shape: dict, context: int, table: int, seed: int = 0):
    """What the score call of one layer takes: the first ``live`` rows
    ``context`` tokens long, the query at the last of them, the others dead
    (table all trash, position at the sentinel); block 0 holds ``inf``."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from llm_sharding_tpu.models.cache import POS_SENTINEL

    B, live, BS = shape["rows"], shape["live"], shape["block_size"]
    L, NB, Hi, lanes = (shape[k] for k in ("layers", "blocks", "heads",
                                           "lanes"))
    own = -(-context // BS)
    rng = np.random.default_rng(seed)
    tbl = np.zeros((B, table), np.int32)
    tbl[:live, :own] = (
        1 + rng.permutation(NB - 1)[: live * own].reshape(live, own)
    )
    kvpos = np.full((B, table * BS), POS_SENTINEL, np.int32)
    kvpos[:live, :context] = np.arange(context)
    qpos = np.where(np.arange(B)[:, None] < live, context - 1, POS_SENTINEL)
    ks = jax.random.split(jax.random.key(seed), 3)
    arena = jax.random.normal(ks[0], (L, NB, 1, BS, lanes), jnp.bfloat16)
    return dict(
        qi=jax.random.normal(ks[1], (B, Hi, lanes), jnp.bfloat16),
        wi=jax.random.uniform(ks[2], (B, Hi), jnp.float32, 0.5, 1.5),
        arena=arena.at[:, 0].set(jnp.inf),
        table=jnp.asarray(tbl), qpos=jnp.asarray(qpos.astype(np.int32)),
        kvpos=jnp.asarray(kvpos),
    )


def _index_program(shape: dict, width, interpret: bool = False):
    """Every layer's score call once, ``[L, B, W]``: ``width`` blocks a cell
    (None: the kernel's own rule), or the XLA branch (``"xla"``)."""
    import jax
    import jax.numpy as jnp
    import llm_sharding_tpu.models  # noqa: F401 — ops import through models
    from llm_sharding_tpu.ops import paged_attention as pa

    kw = {} if width is None else {"blocks_per_cell": width}
    if interpret:
        kw["interpret"] = True

    @jax.jit
    def run(qi, wi, arena, table, qpos, kvpos):
        ok = pa._attendable(table, qpos, kvpos, shape["block_size"])

        def one(_, layer):
            if width == "xla":
                sel = pa.Selection(qi[:, None], wi[:, None], arena, 0)
                return None, pa.index_scores(
                    sel, layer, table, qpos, kvpos, ok)[:, 0]
            score = pa.index_scores_tpu(
                qi, wi, arena, layer, table, qpos, kvpos, **kw)
            return None, jnp.where(ok[:, 0], score, -jnp.inf)
        return jax.lax.scan(
            one, None, jnp.arange(shape["layers"], dtype=jnp.int32))[1]

    return lambda inp: run(*(inp[k] for k in (
        "qi", "wi", "arena", "table", "qpos", "kvpos")))


def index_widths() -> list:
    """The cell widths this tree's score kernel can be asked for: its own
    (None) alone where the width is not the caller's to give."""
    import inspect
    from llm_sharding_tpu.ops import paged_attention as pa

    fn = inspect.unwrap(pa.index_scores_tpu)
    if "blocks_per_cell" not in inspect.signature(fn).parameters:
        return [None]
    return list(INDEX_WIDTHS)


def time_index_scores(shape: dict, context: int, width, want: dict,
                      runs: int = 8, interpret: bool = False) -> dict:
    """DEVICE microseconds of ONE layer's score call from a profiler trace
    (``time_kv_decode`` says why a trace): the kernel alone and with what
    XLA puts around it, and a hash of the attendable columns' scores. A
    width that does not divide the table gets a table padded up to it;
    ``want`` keeps the XLA branch's scores by ``(context, table)``."""
    import numpy as np
    import jax

    table = shape["table"]
    if width is not None:
        table = -(-table // width) * width
    inp = index_inputs(shape, context, table)
    call = _index_program(shape, width, interpret)
    scores = np.asarray(jax.block_until_ready(call(inp)))  # compiles
    trace_dir = os.path.join(WORK, "trace_index_scores")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(runs):
        out = call(inp)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    calls = runs * shape["layers"]
    ops = sorted(device_op_us(trace_dir).items(), key=lambda kv: -kv[1])
    kernel = sum(u for n, u in ops if n.startswith("index_scores")) / calls
    if (context, table) not in want:
        want[context, table] = np.asarray(_index_program(shape, "xla")(inp))
    want = want[context, table]
    seen = want > -np.inf  # the attendable columns
    return {
        "kernel_us": round(kernel, 2),
        "us_per_layer_call": round(sum(u for _, u in ops) / calls, 2),
        "ops_us": [[n, round(u / calls, 2)] for n, u in ops[:6]],
        "max_err": float(np.max(np.abs(scores[seen] - want[seen]))),
        "masked_same": bool(np.array_equal(scores > -np.inf, seen)),
        "hash": "%08x" % zlib.crc32(scores[seen].tobytes()),
    }


def child_index_scores(spec: dict, out_path: str) -> None:
    import jax

    from llm_sharding_tpu.utils.compile_cache import enable_persistent_cache
    from llm_sharding_tpu.utils.device_report import device_report

    platform = jax.devices()[0].platform
    require_tpu(platform, "the index score call")
    enable_persistent_cache(platform)
    shape, results, want = INDEX_SHAPE, [], {}
    live = shape["live"]
    for width in index_widths():
        walk = []
        for context in shape["contexts"]:
            got = time_index_scores(shape, context, width, want)
            # what the call must read: the live rows' index keys
            floor_us = 1e6 * live * context * shape["lanes"] * 2 / (
                HBM_BYTES_PER_S)
            got["roofline_pct"] = round(100 * floor_us / got["kernel_us"], 1)
            results.append({"shape": shape["name"], "width": width,
                            "context": context, **got})
            walk.append((context, got["kernel_us"]))
            print(f"[index-scores] width {width} at {context}: {got}",
                  flush=True)
        results[-1]["walk_ns_per_token"] = walk_slope(walk, live)
        print(f"[index-scores] width {width}: {walk} (context, us a layer "
              f"call): {results[-1]['walk_ns_per_token']} ns a token of "
              "context", flush=True)
    with open(out_path, "w") as f:
        json.dump({"device": device_report(), "index_scores": results}, f)
    # bf16 products summed in float32: the two paths differ in the order
    if not all(r["masked_same"] and r["max_err"] <= KERNEL_TOL
               for r in results):
        raise SystemExit(
            "chip_smoke: the score kernel differs from the XLA branch"
        )


#: a selecting decode step's search at Keye-VL-2.0-30B-A3B's shape: a slot's
#: four rows over the table's 288 blocks of 32, of which one row is live in
#: the cell (and four once admission goes by row), 2,048 tokens kept
SELECT_SHAPE = {"name": "keye_vl2_30b_a3b", "rows": 4, "width": 9216,
                "topk": 2048, "layers": 12, "lives": (1, 4),
                "contexts": (2560, 5120, 8704)}
#: the forms timed: the search in XLA, the search as the tree's
#: ``select_mask`` resolves it on this backend, the kernel at other widths of
#: a pass
SELECT_FORMS = ("xla", "own", 1, 2, 4)
#: slots of other sizes whose mask is checked (not timed): the kernel re-lays
#: a row out of tiles that hold as many sublanes as the slot has rows
SELECT_ROWS_CHECKED = (1, 2, 8, 16)


def select_inputs(shape: dict, live: int, context: int, ties: bool = False,
                  seed: int = 0) -> dict:
    """Every layer's scores ``[L, B, W]`` as ``index_scores`` hands them —
    the first ``live`` rows ``context`` attendable columns, ``-inf`` past
    them and in the dead rows — and the key positions the mask is read
    into. ``ties``: a live row's columns are zeros of both signs but for
    ``topk // 2`` positive ones, so the ``topk``-th score is a zero and far
    more columns tie with it than are left to keep."""
    import numpy as np
    import jax.numpy as jnp
    from llm_sharding_tpu.models.cache import POS_SENTINEL

    L, B, W = shape["layers"], shape["rows"], shape["width"]
    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((L, B, W)).astype(np.float32)
    if ties:
        zeros = np.where(rng.random((L, B, W)) < 0.5, 0.0, -0.0)
        few = rng.random((L, B, W)) < shape["topk"] / 2 / context
        scores = np.where(few, np.abs(scores) + 1e-3, zeros).astype(np.float32)
    seen = (np.arange(B)[:, None] < live) & (np.arange(W)[None] < context)
    return dict(
        scores=jnp.asarray(np.where(seen[None], scores, -np.inf)),
        kvpos=jnp.asarray(np.where(
            seen, np.arange(W)[None], POS_SENTINEL).astype(np.int32)),
    )


def select_forms() -> list:
    """The forms this tree can be timed in: the kernel's other widths of a
    pass only where the tree has the kernel."""
    import llm_sharding_tpu.models  # noqa: F401 — ops import through models
    from llm_sharding_tpu.ops import paged_attention as pa

    return [f for f in SELECT_FORMS
            if isinstance(f, str) or hasattr(pa, "select_topk_tpu")]


def _select_program(shape: dict, form, interpret: bool = False):
    """Every layer's search once, the mask read into the key positions as
    ``selected_attention`` reads it: ``[L, B, W]`` int32, the sentinel where
    a column is not kept. ``form``: ``"xla"`` — ``select_mask`` under
    ``PAGED_FORCE_KERNEL=xla``, the search every tree has —, ``"own"`` —
    ``select_mask`` as the tree resolves it here (``interpret``: under
    ``PAGED_FORCE_KERNEL=interpret``) —, or the kernel at that many bits a
    pass."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import llm_sharding_tpu.models  # noqa: F401 — ops import through models
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops import paged_attention as pa

    forced = "xla" if form == "xla" else "interpret" if interpret else ""

    @jax.jit
    def run(scores, kvpos):
        def one(_, s):
            if isinstance(form, int):
                keep = pa.select_topk_tpu(
                    s, shape["topk"], bits=form, interpret=interpret)
            else:
                keep = pa.select_mask(s, shape["topk"])
            return None, jnp.where(keep, kvpos, POS_SENTINEL)
        return jax.lax.scan(one, None, scores)[1]

    def call(inp):
        # the path is read where the program is traced: its first call
        with mock.patch.dict(os.environ, PAGED_FORCE_KERNEL=forced):
            return run(inp["scores"], inp["kvpos"])

    return call


def time_select(shape: dict, live: int, context: int, form,
                ties: bool = False, runs: int = 8,
                interpret: bool = False) -> dict:
    """DEVICE microseconds of ONE layer's search in ``form`` from a profiler
    trace (``time_kv_decode`` says why a trace): everything between the
    scores and the masked key positions, the kernel's own share of it, and
    whether the kept columns are ``select_tokens``' set (``lax.top_k``,
    stable: the lower column of a tie; the two zeros made one for it).
    ``runs=0``: the mask alone, no trace."""
    import numpy as np
    import jax
    from llm_sharding_tpu.models.cache import POS_SENTINEL
    from llm_sharding_tpu.ops import paged_attention as pa

    inp = select_inputs(shape, live, context, ties)
    call = _select_program(shape, form, interpret)
    kept = np.asarray(jax.block_until_ready(call(inp))) != POS_SENTINEL
    ops = []
    if runs:
        trace_dir = os.path.join(WORK, "trace_select")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        for _ in range(runs):
            out = call(inp)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        ops = sorted(device_op_us(trace_dir).items(), key=lambda kv: -kv[1])
    calls = max(runs, 1) * shape["layers"]
    scores = np.asarray(inp["scores"])
    # the sort reads -0.0 below +0.0; the selection ties them (IEEE ==)
    cols, real = (np.asarray(a) for a in jax.jit(
        pa.select_tokens, static_argnums=1)(
            np.where(scores == 0, np.float32(0), scores), shape["topk"]))
    want = np.zeros_like(kept)
    np.put_along_axis(want, np.where(real, cols, cols[..., :1]), True, axis=-1)
    want &= scores > -np.inf  # a dead row's first column is no choice
    return {
        "us_per_layer_call": round(sum(u for _, u in ops) / calls, 2),
        "kernel_us": round(
            sum(u for n, u in ops if n.startswith("select_topk")) / calls, 2),
        "ops_us": [[n, round(u / calls, 2)] for n, u in ops[:6]],
        "kept": int(kept.sum()), "oracle_set": bool((kept == want).all()),
    }


def child_select(spec: dict, out_path: str) -> None:
    import jax

    from llm_sharding_tpu.utils.compile_cache import enable_persistent_cache
    from llm_sharding_tpu.utils.device_report import device_report

    platform = jax.devices()[0].platform
    require_tpu(platform, "the selection's search")
    enable_persistent_cache(platform)
    shape, results = SELECT_SHAPE, []
    cases = [(live, context, False) for live in shape["lives"]
             for context in shape["contexts"]]
    cases += [(live, shape["contexts"][-1], True) for live in shape["lives"]]
    for live, context, ties in cases:
        for form in select_forms():
            got = time_select(shape, live, context, form, ties)
            results.append({"shape": shape["name"], "live": live,
                            "context": context, "ties": ties,
                            "form": form, **got})
            print(f"[select] live {live} at {context}"
                  f"{' (zeros tie)' if ties else ''}, {form}: {got}",
                  flush=True)
    for rows in SELECT_ROWS_CHECKED:
        slot = dict(shape, rows=rows, layers=2)
        got = time_select(slot, -(-rows // 2), shape["contexts"][0], "own",
                          ties=rows > 2, runs=0)
        results.append({"shape": shape["name"], "rows": rows, "form": "own",
                        **{k: got[k] for k in ("kept", "oracle_set")}})
        print(f"[select] a slot of {rows} rows: {results[-1]}", flush=True)
    with open(out_path, "w") as f:
        json.dump({"device": device_report(), "select": results}, f)
    if not all(r["oracle_set"] for r in results):
        raise SystemExit(
            "chip_smoke: a search's mask is not select_tokens' set"
        )


#: the one-step state update at Nemotron-3-Super-120B-A12B's published mixer
#: shape, carried as the cell carries it: 8 mixer layers x 4 rows of float32
SSM_SHAPE = {"layers": 8, "rows": 4, "heads": 128, "head_dim": 64,
             "state": 128, "groups": 8}
SSM_LIVE = (1, 4)
#: float32 against float32: only the order of the sum over ``state`` differs
SSM_TOL = 1e-4


def ssm_inputs(shape: dict, live: int, seed: int = 0) -> tuple:
    """``(s_all, alive, x, dt, A, Bm, Cm, D)``: a carried state and one
    position of a slot whose LAST ``live`` rows are live (so ``order`` is no
    identity)."""
    import jax
    import jax.numpy as jnp

    L, R, nh, hd, ds, g = (shape[k] for k in (
        "layers", "rows", "heads", "head_dim", "state", "groups"))
    k = jax.random.split(jax.random.key(seed), 7)
    alive = jnp.arange(R) >= R - live
    dt = jax.nn.softplus(jax.random.normal(k[2], (R, nh)) - 2.0)
    return (
        jax.random.normal(k[0], (L, R, nh, hd, ds)), alive,
        jax.random.normal(k[1], (R, nh, hd)),
        jnp.where(alive[:, None], dt, 0.0),
        -jnp.exp(jax.random.uniform(k[3], (nh,), minval=0.0, maxval=2.77)),
        jax.random.normal(k[4], (R, g, ds)),
        jax.random.normal(k[5], (R, g, ds)),
        jax.random.uniform(k[6], (nh,), minval=0.5, maxval=1.5),
    )


def ssm_rows_call(backend: str):
    """``(s_all, layer, alive, x, dt, A, Bm, Cm, D) -> (y, s_all)``: the
    slot's update as ``models/nemotron_h.mamba_decode_rows`` asks for it."""
    import jax.numpy as jnp

    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.ops import ssm

    def call(s_all, layer, alive, x, dt, A, Bm, Cm, D):
        return ssm.ssm_step_rows(
            s_all, (layer, jnp.int32(0)), jnp.argsort(~alive),
            jnp.sum(alive.astype(jnp.int32)), x, dt, A, Bm, Cm, D,
            backend=backend,
        )
    return call


def check_ssm_rows(shape: dict, live: int, backend: str = "kernel") -> dict:
    """``backend`` against the XLA loop, one layer of the stack advanced: the
    largest difference of ``y`` and of the new state (relative to the largest
    value, 1 at least), and whether every row that is not live — and every other layer —
    came back bit for bit."""
    import jax
    import jax.numpy as jnp

    s_all, alive, *ops = ssm_inputs(shape, live, seed=1)
    layer = jnp.int32(shape["layers"] - 1)
    y, s = jax.jit(ssm_rows_call(backend))(s_all, layer, alive, *ops)
    y_want, s_want = jax.jit(ssm_rows_call("xla"))(s_all, layer, alive, *ops)
    still = jnp.ones(s_all.shape[:2], bool).at[layer].set(~alive)
    def err(got, want):  # (with no live row ``y`` is all zeros)
        return float(
            jnp.abs(got - want).max() / jnp.maximum(jnp.abs(want).max(), 1.0)
        )

    return {
        "y_err": err(y, y_want), "s_err": err(s, s_want),
        "dead_rows_untouched": bool(
            jnp.array_equal(s[still], s_all[still])
            & jnp.array_equal(y[~alive], y_want[~alive])
        ),
    }


def time_ssm_rows(shape: dict, live: int, backend: str,
                  calls: int = 64) -> float:
    """Microseconds per layer call: the state is carried through ``calls``
    calls of ONE program as the layer scan carries it (donated: the update
    is in place or shows that it is not), warmed up, best of three."""
    import jax
    import jax.numpy as jnp

    call = ssm_rows_call(backend)
    s_all, alive, x, *ops = ssm_inputs(shape, live)
    layers = jnp.arange(calls, dtype=jnp.int32) % shape["layers"]

    @functools.partial(jax.jit, donate_argnums=0)
    def run(s_all, alive, x, *ops):
        def one(carry, layer):
            s_all, y = carry
            # hangs on the call before: nothing is hoisted out of the loop
            return call(s_all, layer, alive, x + y * 1e-9, *ops)[::-1], None
        return jax.lax.scan(one, (s_all, jnp.zeros_like(x)), layers)[0]

    best = float("inf")
    for _ in range(4):  # the first call compiles
        t0 = time.perf_counter()
        s_all, _ = jax.block_until_ready(run(s_all, alive, x, *ops))
        best = min(best, time.perf_counter() - t0)
    return round(best / calls * 1e6, 2)


#: a Mamba-1 mixer at AI21-Jamba2-3B's published mixer widths (5,120
#: channels, a state of 16, a step rank of 160, 4 taps) between projections
#: of a hidden size of 128, so that what lies BETWEEN them is what is timed;
#: the carried state of 26 mixer layers x 4 rows, leaves in bf16 as the cell's
MIXER_KEYS = dict(hidden_size=128, mamba_expand=40, intermediate_size=128,
                  num_attention_heads=1, num_key_value_heads=1, vocab_size=256)
MIXER_LAYERS, MIXER_ROWS = 26, 4
#: bf16 operands into the two products on both paths; the split path rounds
#: the products to bf16, the fused one keeps their float32 sums
MIXER_TOL = 2e-2


def mixer_inputs(live: int, seed: int = 0):
    """``(cfg, stack, h, s_all, c_all, alive)``: a stack of Mamba-1 mixers
    (bf16 leaves) and one decode step of a slot whose LAST ``live`` rows are
    live."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_tpu.models import jamba
    from llm_sharding_tpu.models.config import ModelConfig, jamba2_3b_keys
    from llm_sharding_tpu.models.stack import zero_recurrent

    cfg = ModelConfig.from_hf_config(jamba2_3b_keys(**MIXER_KEYS))
    k = jax.random.split(jax.random.key(seed), 4)
    stack = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        jamba.init_layer_params(cfg, k[0], MIXER_LAYERS, kind="mamba"),
    )
    rec = zero_recurrent(cfg, MIXER_LAYERS, MIXER_ROWS)
    return (
        cfg, stack,
        jax.random.normal(k[1], (MIXER_ROWS, 1, cfg.hidden_size), jnp.bfloat16),
        rec["ssm"] + jax.random.normal(k[2], rec["ssm"].shape),
        rec["conv"] + jax.random.normal(k[3], rec["conv"].shape),
        jnp.arange(MIXER_ROWS) >= MIXER_ROWS - live,
    )


def mixer_program(cfg, form: str, calls: int, backend: str = "kernel"):
    """``(stack, h, s_all, c_all, alive) -> (h, s_all, c_all)``: ``calls``
    mixer layers of a decode step as the layer scan runs them
    (``models/jamba.mixer_block``, the live rows counted once): ``fused``
    hands the stack's leaves WHOLE beside the layer's index, as the serve
    programs' scan does, ``split`` a layer's slices of them."""
    import jax
    import jax.numpy as jnp

    from llm_sharding_tpu.models import jamba
    from llm_sharding_tpu.models.stack import join_whole, split_whole

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def run(stack, h, s_all, c_all, alive):
        live = alive[:, None]
        rows = jamba.live_rows(live)
        scanned, whole = (
            (stack, None) if form == "split" else split_whole(stack)
        )

        def one(carry, l):
            p = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False),
                scanned,
            )
            return jamba.mixer_block(
                cfg, join_whole(p, whole, l), *carry, (l, jnp.int32(0)), live,
                rows, backend,
            ), None
        layers = jnp.arange(calls, dtype=jnp.int32) % MIXER_LAYERS
        return jax.lax.scan(one, (h, s_all, c_all), layers)[0]
    return run


def check_mixer_step(live: int, backend: str = "kernel") -> dict:
    """The fused decode step against the split path, every layer once: the
    largest difference of ``h``, of the state and of the conv's tail
    (relative to the largest value), and whether every row that is not live
    came back bit for bit."""
    import jax.numpy as jnp

    got = {}
    for form in ("split", "fused"):
        cfg, stack, h, s_all, c_all, alive = mixer_inputs(live, seed=1)
        got[form] = mixer_program(cfg, form, MIXER_LAYERS, backend)(
            stack, h, s_all, c_all, alive)
    _, _, _, s_all, c_all, alive = mixer_inputs(live, seed=1)

    def err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.abs(a - b).max() / jnp.maximum(jnp.abs(b).max(), 1.0))

    (h_s, s_s, c_s), (h_f, s_f, c_f) = got["split"], got["fused"]
    return {
        "h_err": err(h_f, h_s), "s_err": err(s_f, s_s), "c_err": err(c_f, c_s),
        "dead_rows_untouched": bool(
            jnp.array_equal(s_f[:, ~alive], s_all[:, ~alive])
            & jnp.array_equal(c_f[:, ~alive], c_all[:, ~alive])
        ),
    }


def time_mixer_step(live: int, form: str, runs: int = 8,
                    calls: int = 32) -> dict:
    """DEVICE microseconds of ONE mixer layer call in ``form`` (the tiny
    projections included), from a profiler trace (``time_kv_decode`` says
    why): the sum over the operations and the largest of them."""
    import jax

    cfg, stack, h, s_all, c_all, alive = mixer_inputs(live)
    run = mixer_program(cfg, form, calls)
    h, s_all, c_all = jax.block_until_ready(
        run(stack, h, s_all, c_all, alive))  # compiles
    trace_dir = os.path.join(WORK, "trace_mixer_step")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(runs):
        h, s_all, c_all = run(stack, h, s_all, c_all, alive)
    jax.block_until_ready((h, s_all, c_all))
    jax.profiler.stop_trace()
    n = runs * calls
    ops = sorted(device_op_us(trace_dir).items(), key=lambda kv: -kv[1])
    return {
        "us_per_layer_call": round(sum(u for _, u in ops) / n, 2),
        "ops_us": [[name, round(u / n, 2)] for name, u in ops[:8]],
    }


def child_ssm(spec: dict, out_path: str) -> None:
    import jax

    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.ops import ssm
    from llm_sharding_tpu.utils.compile_cache import enable_persistent_cache
    from llm_sharding_tpu.utils.device_report import device_report

    platform = jax.devices()[0].platform
    require_tpu(platform, "the state update's kernel check")
    enable_persistent_cache(platform)
    results = []
    for live in SSM_LIVE:
        got = check_ssm_rows(SSM_SHAPE, live)
        got["ok"] = (
            got["dead_rows_untouched"]
            and max(got["y_err"], got["s_err"]) <= SSM_TOL
        )
        us = {b: time_ssm_rows(SSM_SHAPE, live, b) for b in ("kernel", "xla")}
        results.append({"kernel": "ssm_rows", "live_rows": live, **got,
                        "us_per_layer_call": us})
        print(f"[ssm] ssm_rows {live} live of {SSM_SHAPE['rows']}: y err "
              f"{got['y_err']:.2e}, state err {got['s_err']:.2e}, dead rows "
              f"untouched {got['dead_rows_untouched']}; kernel "
              f"{us['kernel']} us, xla {us['xla']} us a layer call",
              flush=True)
    # Mamba-1: a mixer's decode step as ONE kernel beside the split path
    path = ssm.mixer_step_path("kernel", mixer_inputs(1)[0])
    for live in SSM_LIVE:
        got = check_mixer_step(live)
        got["ok"] = path == "fused" and got["dead_rows_untouched"] and max(
            got["h_err"], got["s_err"], got["c_err"]) <= MIXER_TOL
        timed = {f: time_mixer_step(live, f) for f in ("fused", "split")}
        results.append({"kernel": "ssm_mixer", "live_rows": live,
                        "mixer_step_path": path, **got, **timed})
        print(f"[ssm] ssm_mixer {live} live of {MIXER_ROWS} (path {path}): "
              f"h err {got['h_err']:.2e}, state err {got['s_err']:.2e}, tail "
              f"err {got['c_err']:.2e}, dead rows untouched "
              f"{got['dead_rows_untouched']}; device us a layer call: fused "
              f"{timed['fused']['us_per_layer_call']} "
              f"{timed['fused']['ops_us'][:3]}, split "
              f"{timed['split']['us_per_layer_call']} "
              f"{timed['split']['ops_us'][:4]}", flush=True)
    with open(out_path, "w") as f:
        json.dump({"device": device_report(), "ssm": results}, f)
    if not all(r["ok"] for r in results):
        raise SystemExit(
            "chip_smoke: a state-space kernel disagrees with its reference"
        )


def require_tpu(platform: str, who: str) -> None:
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: {who} found no TPU: jax reports platform "
            f"{platform!r}. The smoke measures the serve path on the chip "
            "and never falls back to another backend."
        )


def child_kernels(spec: dict, out_path: str) -> None:
    import jax

    from llm_sharding_tpu.utils.compile_cache import enable_persistent_cache
    from llm_sharding_tpu.utils.device_report import device_report

    platform = jax.devices()[0].platform
    require_tpu(platform, "the kernel check")
    enable_persistent_cache(platform)
    cfg = model_config(spec)
    results = []
    for case in kernel_cases(spec):
        err = check_kernel(cfg, case, "kernel")
        times = (
            time_decode(cfg, case, "kernel")
            if case["kernel"] == "paged_decode" else {}
        )
        results.append(
            {**case, "max_err": err, "ok": err <= KERNEL_TOL, **times}
        )
        print(f"[kernels] {case['kernel']:13s} {case['kv_dtype']:4s} "
              f"{case['state']:14s} S={case['q_len']:<4d} "
              f"max|err|={err:.3e} "
              f"{'ok' if err <= KERNEL_TOL else 'OVER ' + str(KERNEL_TOL)}"
              + "".join(f" {k}={v}" for k, v in times.items()),
              flush=True)
    with open(out_path, "w") as f:
        json.dump({"device": device_report(), "kernels": results}, f)
    if not all(r["ok"] for r in results):
        raise SystemExit("chip_smoke: a kernel disagrees with the XLA path")


def child_store(spec: dict, out_path: str) -> None:
    with open(out_path, "w") as f:
        json.dump(write_store(spec, os.path.join(WORK, "store")), f)


# ----------------------------------------------------------------- parent
# stdlib only from here on.


def store_bytes(store: str) -> dict:
    """On-disk bytes of a store by unit kind — uncompressed npz, so also
    what each unit weighs on a device. A unit may span several files
    (``block_3.npz``, ``block_3.part1.npz``, ...): the writer keeps every
    file under ``shard_store.MAX_FILE_BYTES``."""
    units: dict[str, int] = {}
    largest = 0
    for name in os.listdir(store):
        if not name.endswith(".npz"):
            continue
        size = os.path.getsize(os.path.join(store, name))
        unit = name.split(".", 1)[0]
        units[unit] = units.get(unit, 0) + size
        largest = max(largest, size)
    blocks = [n for u, n in units.items() if u.startswith("block_")]
    return {"block": max(blocks), "blocks": len(blocks),
            "head": sum(units.values()) - sum(blocks),
            "largest_file": largest}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 60) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def run_child(mode: str, spec: dict, env: dict, log_name: str) -> dict:
    """Start ``chip_smoke.py --child MODE``; returns a handle ``wait_child``
    turns into the child's JSON result."""
    os.makedirs(os.path.join(WORK, "logs"), exist_ok=True)
    out_path = os.path.join(WORK, f"{mode}.json")
    if os.path.exists(out_path):
        os.remove(out_path)
    log_path = os.path.join(WORK, "logs", log_name)
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", mode,
         "--spec", json.dumps(spec), "--out", out_path],
        stdout=log, stderr=subprocess.STDOUT, env=env, cwd=HERE,
    )
    return {"proc": proc, "log": log, "log_path": log_path,
            "out_path": out_path, "mode": mode, "t0": time.time()}


def wait_child(h: dict) -> dict:
    rc = h["proc"].wait()
    h["log"].close()
    if rc != 0:
        raise SystemExit(
            f"chip_smoke: the {h['mode']} child exited {rc}:\n"
            + tail(h["log_path"])
        )
    with open(h["out_path"]) as f:
        res = json.load(f)
    res["wall_s"] = round(time.time() - h["t0"], 1)
    return res


def metric(text: str, name: str, **labels) -> float:
    """Sum of the series of ``name`` whose labels include ``labels`` in a
    Prometheus text page (0.0 when there is none)."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name) or line[len(name):len(name) + 1] not in (
            "{", " "
        ):
            continue
        head, _, value = line.rpartition(" ")
        if all(f'{k}="{v}"' in head for k, v in labels.items()):
            total += float(value)
    return total


class Daemon:
    """``python -m llm_sharding_tpu serve`` as a child with the chip: stdin
    held open (the daemon exits on stdin EOF), stderr to a log, stopped on
    the way out whatever happened."""

    def __init__(self, store: str, serve_args: list[str], env: dict):
        self.http_port, self.metrics_port = free_port(), free_port()
        os.makedirs(os.path.join(WORK, "logs"), exist_ok=True)
        self.log_path = os.path.join(WORK, "logs", "daemon.log")
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "llm_sharding_tpu", "serve", store,
             *serve_args, "--http-port", str(self.http_port),
             "--metrics-port", str(self.metrics_port)],
            stdin=subprocess.PIPE, stdout=self._log, stderr=self._log,
            env=env, cwd=HERE,
        )

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()

    def _request(self, port, method, path, body=None, timeout=900.0):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        try:
            conn.request(
                method, path,
                body=None if body is None else json.dumps(body),
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            return resp.status, resp.read().decode()
        finally:
            conn.close()

    def wait_ready(self, timeout_s: float) -> None:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise SystemExit(
                    f"chip_smoke: the daemon exited {self.proc.returncode} "
                    "before serving:\n" + tail(self.log_path)
                )
            try:
                status, _ = self._request(
                    self.http_port, "GET", "/healthz", timeout=2.0
                )
                if status == 200:
                    return
            except OSError:
                pass  # not listening yet: the model is still loading
            time.sleep(0.5)
        raise SystemExit("chip_smoke: the daemon never became ready:\n"
                         + tail(self.log_path))

    def complete(self, prompt: list[int], max_tokens: int, **knobs) -> list:
        """One ``POST /v1/completions``; returns the generated ids. Fails
        unless it is a 200 with exactly ``max_tokens`` tokens."""
        body = {"prompt": prompt, "max_tokens": max_tokens, **knobs}
        status, text = self._request(
            self.http_port, "POST", "/v1/completions", body
        )
        if status != 200:
            raise SystemExit(f"chip_smoke: POST answered {status}: {text[:400]}")
        if knobs.get("stream"):
            events = [json.loads(l[6:]) for l in text.splitlines()
                      if l.startswith("data: ") and l != "data: [DONE]"]
            if not text.rstrip().endswith("data: [DONE]"):
                raise SystemExit("chip_smoke: stream ended without [DONE]")
            ids = [t for e in events for t in e["choices"][0]["token_ids"]]
            reason = events[-1]["choices"][0]["finish_reason"]
        else:
            choice = json.loads(text)["choices"][0]
            ids, reason = choice["token_ids"], choice["finish_reason"]
        if len(ids) != max_tokens:
            raise SystemExit(
                f"chip_smoke: asked {max_tokens} tokens, got {len(ids)} "
                f"(finish_reason {reason!r}) for a {len(prompt)}-token prompt"
            )
        return ids

    def metrics(self) -> str:
        return self._request(self.metrics_port, "GET", "/metrics")[1]

    def statz(self) -> dict:
        return json.loads(self._request(self.metrics_port, "GET", "/statz")[1])

    def healthz(self) -> int:
        return self._request(self.metrics_port, "GET", "/healthz")[0]

    def drain(self, timeout_s: float = 180.0) -> None:
        """SIGTERM → the daemon finishes what is in flight and exits 0."""
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=timeout_s)
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            drained = "drained; exiting 0" in f.read()
        if rc != 0 or not drained:
            raise SystemExit(
                f"chip_smoke: the daemon exited {rc} on SIGTERM without a "
                "clean drain:\n" + tail(self.log_path, 40)
            )


def prompts(spec: dict, vocab: int) -> dict:
    """Token-id prompts from the spec's seed (a plain LCG: the parent has
    no numpy). Ids stay below ``vocab`` and clear of the low special ids."""
    state = spec["seed"]

    def ids(n):
        nonlocal state
        out = []
        for _ in range(n):
            state = (state * 6364136223846793005 + 1442695040888963407) % 2**64
            out.append(10 + (state >> 33) % (vocab - 10))
        return out

    long = ids(spec["long_prompt"])
    return {
        "short": ids(spec["short_prompt"]),
        "long": long,
        "shared": long[: spec["shared_prefix"]] + ids(spec["shared_suffix"]),
        "burst": [ids(spec["short_prompt"]) for _ in range(3)],
        "stream": ids(spec["short_prompt"]),
        "sampled": ids(spec["short_prompt"]),
    }


def drive(d: Daemon, spec: dict, vocab: int, routed: bool = False) -> dict:
    """The smoke's traffic. Returns what came back plus phase times; any
    request that is not a 200 with the asked tokens ends the run.

    ``routed`` (a replica router in front): the shared-prefix hit is
    reported, not required. The router's cluster index knows a cached
    prompt only at its node boundary, so a prefix that ends mid-node is
    invisible to it and the request goes by load — to the replica that
    holds the prefix or to the other (PERF.md, PR 21)."""
    p = prompts(spec, vocab)
    n = spec["max_tokens"]
    sent = 0
    t0 = time.time()
    first = d.complete(p["short"], n)  # compiles admit + decode programs
    t_first = time.time() - t0
    again = d.complete(p["short"], n)
    sent += 2
    if first != again:
        raise SystemExit(
            f"chip_smoke: the same greedy prompt gave different ids:\n"
            f"{first}\n{again}"
        )
    # a prompt several prefill chunks long; others join while it runs
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        long_f = pool.submit(d.complete, p["long"], n)
        time.sleep(0.2)
        futs = [pool.submit(d.complete, q, n) for q in p["burst"]]
        futs.append(pool.submit(d.complete, p["stream"], n, stream=True))
        long_ids = long_f.result()
        burst = [f.result() for f in futs]
    sent += 1 + len(burst)
    # the long prompt's leading blocks, resubmitted under a new suffix
    hit0 = metric(d.metrics(), "server_prefix_cache_hit_tokens_total")
    shared = d.complete(p["shared"], n)
    hit1 = metric(d.metrics(), "server_prefix_cache_hit_tokens_total")
    sent += 1
    if not routed and hit1 - hit0 < spec["shared_prefix"]:
        raise SystemExit(
            f"chip_smoke: a resubmitted {spec['shared_prefix']}-token prefix "
            f"raised the hit-token counter by {hit1 - hit0}"
        )
    # sampling with a seed is reproducible (top-k/top-p: the full-vocab sort)
    knobs = dict(temperature=0.8, top_k=50, top_p=0.9, seed=7)
    s1 = d.complete(p["sampled"], n, **knobs)
    s2 = d.complete(p["sampled"], n, **knobs)
    sent += 2
    if s1 != s2:
        raise SystemExit(
            f"chip_smoke: one seed sampled two completions:\n{s1}\n{s2}"
        )
    return {
        "sent": sent, "first_request_s": round(t_first, 1),
        "rest_s": round(time.time() - t0 - t_first, 1),
        "prefix_hit_tokens": hit1 - hit0,
        "ids": {"greedy": first, "long": long_ids, "burst": burst,
                "shared": shared, "sampled": s1},
    }


def check_served(d: Daemon, sent: int, expect: dict) -> dict:
    """The assertions that make a pass mean the device did the work."""
    st, text = d.statz(), d.metrics()
    dev = st["device"]
    failures = []
    if dev["platform"] != expect["platform"]:
        failures.append(f"the daemon ran on {dev['platform']!r}")
    c = st["counters"]
    if c["requests_failed"] or c["requests_completed"] != sent:
        failures.append(f"requests: {c} (sent {sent})")
    if d.healthz() != 200:
        failures.append("/healthz is not SERVING")
    impl = {b: metric(text, "server_attn_backend", backend=b)
            for b in ("kernel", "interpret", "xla", "dense")}
    if impl[expect["attn_backend"]] < 1 or sum(impl.values()) != impl[
        expect["attn_backend"]
    ]:
        failures.append(f"decode attention resolved to {impl}")
    if metric(text, "server_prefill_path", path="kernel") != 1:
        failures.append("chunked prefill did not dispatch the kernel")
    blocks = {k: metric(text, f"server_{k}_blocks_read_total")
              for k in ("attn", "prefill")}
    if min(blocks.values()) <= 0:
        failures.append(f"blocks read through the kernels: {blocks}")
    if failures:
        raise SystemExit("chip_smoke: " + "; ".join(failures))
    return {
        "device": dev, "counters": c, "blocks_read": blocks,
        "arena_bytes": metric(text, "server_arena_bytes"),
    }


def check_memory(dev: dict, sizes: dict, arena_bytes: float, stages: int,
                 replicas: int, slack: float) -> list[float]:
    """No device may ever have held more than its own stage's layers, its
    head slice and its arena share (plus ``slack`` for activations and
    compiler scratch): the weights went disk → host → their stage's chip.
    Returns the peak GiB of each device in use."""
    busy = [m for m in dev["memory"] if m["bytes_in_use"] > 0]
    if len(busy) != stages * replicas:
        raise SystemExit(
            f"chip_smoke: {len(busy)} devices hold data; {stages} stages x "
            f"{replicas} replicas were asked for"
        )
    layers = -(-sizes["blocks"] // stages)
    share = (layers * sizes["block"] + sizes["head"] / stages
             + arena_bytes / len(busy))
    peaks = [m["peak_bytes_in_use"] for m in busy]
    if max(peaks) > share + slack:
        raise SystemExit(
            f"chip_smoke: a device peaked at {max(peaks) / 2**30:.2f} GiB; "
            f"its stage's share is {share / 2**30:.2f} GiB"
        )
    if min(m["bytes_in_use"] for m in busy) < 0.5 * layers * sizes["block"]:
        raise SystemExit(
            "chip_smoke: a device holds less than half its stage's layers: "
            f"{[m['bytes_in_use'] for m in busy]}"
        )
    return [round(p / 2**30, 2) for p in peaks]


def serve_args(spec: dict, stages: int, data_parallel: int) -> list[str]:
    sv = spec["serve"]
    args = [
        # explicit: with no --stages the engine takes one stage per visible
        # device, and a four-chip host would silently serve pp4
        "--stages", str(stages), "--dtype", spec["dtype"],
        "--capacity", str(sv["capacity"]),
        "--batch-per-slot", str(sv["batch_per_slot"]),
        "--kv-block-size", str(sv["kv_block_size"]),
        "--kv-blocks", str(sv["kv_blocks"]),
        "--prefill-chunk", str(sv["prefill_chunk"]),
        "--prefix-cache", "hbm",
    ]
    if data_parallel > 1:
        args += ["--data-parallel", str(data_parallel)]
    return args


def cache_entries(path) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--stages", type=int, default=1)
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--weights", choices=("int8", "bf16"), default="int8")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut depth (never width)")
    ap.add_argument("--moe", action="store_true",
                    help="only check the expert kernel (ops/moe.py) against "
                         "its XLA path at OLMoE-1B-7B's published widths")
    ap.add_argument("--kv-write", action="store_true",
                    help="only time a prefill chunk's K/V write (ops/"
                         "paged_attention.py) at the cells' arena shapes: "
                         "whole-block tiles beside the row-wise scatter")
    ap.add_argument("--kv-decode", action="store_true",
                    help="only check and time a decode step's K/V write "
                         "with the attention it feeds (ops/paged_attention."
                         "py): the one fused op beside the scatter pair")
    ap.add_argument("--beside",
                    help="with --kv-decode: the kv_decode.json this script "
                         "left in another tree (the parent's): form fused "
                         "of both, side by side")
    ap.add_argument("--index-scores", action="store_true",
                    help="only check and time a selecting decode step's "
                         "score call (ops/paged_attention.index_scores_tpu) "
                         "at Keye's shape, at every cell width it takes")
    ap.add_argument("--select", action="store_true",
                    help="only check and time a selecting decode step's "
                         "search (ops/paged_attention.select_mask) at "
                         "Keye's shape: the kernel beside the XLA search")
    ap.add_argument("--ssm", action="store_true",
                    help="only check and time a decode step's state update "
                         "(ops/ssm.py) at Nemotron-3-Super's mixer shape, the "
                         "kernel beside the XLA loop, and a Mamba-1 mixer's "
                         "fused decode step beside the split path at "
                         "Jamba2-3B's")
    ap.add_argument("--child",
                    choices=("kernels", "store", "moe", "kv_write",
                             "kv_decode", "index_scores", "select", "ssm"))
    ap.add_argument("--spec")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.child:
        spec = json.loads(args.spec)
        {"kernels": child_kernels, "store": child_store,
         "moe": child_moe, "kv_write": child_kv_write,
         "kv_decode": child_kv_decode,
         "index_scores": child_index_scores, "select": child_select,
         "ssm": child_ssm}[args.child](spec, args.out)
        return 0
    # one check, its line left behind too
    for mode in ("ssm", "kv_write", "kv_decode", "index_scores", "select"):
        if getattr(args, mode):
            os.makedirs(WORK, exist_ok=True)
            got = wait_child(run_child(
                mode, {}, dict(os.environ, PYTHONPATH=HERE), mode + ".log"))
            report = {"ok": True, mode: got[mode], "device": got["device"]}
            if mode == "kv_decode" and args.beside:
                with open(args.beside) as f:
                    report["beside"] = fused_beside(
                        json.load(f)["kv_decode"], got[mode])
                for r in report["beside"]:
                    print(f"[kv-decode] fused, {r['shape']} at "
                          f"{r['context']} live {r['live']}: parent "
                          f"{r['parent_us']} us {r['parent_ops_us'][:3]}, "
                          f"change {r['change_us']} us "
                          f"{r['change_ops_us'][:3]}: {r['delta_us']:+} us")
            line = json.dumps(report)
            os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
            with open(os.path.join(HERE, "chiprun_out", mode + ".json"),
                      "w") as f:
                f.write(line + "\n")
            print(line)
            return 0
    if args.moe:
        os.makedirs(WORK, exist_ok=True)
        got = wait_child(run_child(
            "moe", {"preset": "olmoe_1b_7b", "overrides": {}},
            dict(os.environ, PYTHONPATH=HERE), "moe.log"))
        print(json.dumps({"ok": True, "moe": got["kernels"],
                          "moe_us_per_call": got["moe_us_per_call"],
                          "device": got["device"]}))
        return 0

    spec = json.loads(json.dumps(FULL))
    spec["quantize"] = args.weights == "int8"
    if args.layers:
        spec["overrides"]["num_hidden_layers"] = args.layers
    t_start = time.time()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    cpu_env = dict(env, JAX_PLATFORMS="cpu")
    cpu_env.pop("JAX_COMPILATION_CACHE_DIR", None)  # no XLA:CPU cache

    # the store is written on the CPU while the kernel check holds the chip
    store_h = run_child("store", spec, cpu_env, "store.log")
    try:
        kern = wait_child(run_child("kernels", spec, env, "kernels.log"))
        store = wait_child(store_h)
    finally:
        if store_h["proc"].poll() is None:
            store_h["proc"].kill()
            store_h["proc"].wait()
    for r in kern["kernels"]:
        print(f"kernel {r['kernel']:13s} {r['kv_dtype']:4s} {r['state']:14s}"
              f" S={r['q_len']:<4d} compiled by Mosaic, max|err| vs XLA "
              f"{r['max_err']:.2e}"
              + (f", {r['kernel_ms']} ms a call (XLA {r['xla_ms']})"
                 if "kernel_ms" in r else ""))

    with open(os.path.join(WORK, "store", "config.json")) as f:
        vocab = json.load(f)["vocab_size"]
    t_load = time.time()
    with Daemon(os.path.join(WORK, "store"),
                serve_args(spec, args.stages, args.data_parallel), env) as d:
        d.wait_ready(900.0)
        load_s = round(time.time() - t_load, 1)
        loaded = d.statz()["device"]
        entries0 = cache_entries(loaded["compile_cache_dir"])
        traffic = drive(d, spec, vocab, routed=args.data_parallel > 1)
        served = check_served(
            d, traffic["sent"], {"platform": "tpu", "attn_backend": "kernel"}
        )
        d.drain()
    dev = served["device"]
    if (dev["platform"], dev["kind"]) != (
        kern["device"]["platform"], kern["device"]["kind"]
    ):
        raise SystemExit("chip_smoke: the two chip children disagree on "
                         f"the device: {kern['device']} vs {dev}")
    mem = (store["bytes"], served["arena_bytes"], args.stages,
           args.data_parallel)
    after_load = check_memory(loaded, *mem, slack=2**30)
    peaks = check_memory(dev, *mem, slack=3 * 2**30)
    report = {
        "device": {k: dev[k] for k in ("platform", "kind", "count")},
        "versions": {k: dev[k] for k in ("jax", "jaxlib", "libtpu")},
        "model": {**{k: spec[k] for k in ("preset", "overrides")},
                  "weights": args.weights, "stages": args.stages,
                  "data_parallel": args.data_parallel},
        "attn_impl": "kernel",  # check_served passed: nothing else is live
        "kernels": kern["kernels"],
        "requests": {"sent": traffic["sent"],
                     "succeeded": served["counters"]["requests_completed"],
                     "failed": served["counters"]["requests_failed"]},
        "blocks_read": served["blocks_read"],
        "prefix_hit_tokens": traffic["prefix_hit_tokens"],
        # the writer's per-file cap at work, beside the limit this machine
        # puts on a file (-1 = none)
        "store": {**store["bytes"],
                  "rlimit_fsize": resource.getrlimit(resource.RLIMIT_FSIZE)[0]},
        "peak_gib_after_load": after_load,
        "peak_gib": peaks,
        "compile_cache": {
            "dir": dev["compile_cache_dir"], "entries_before": entries0,
            "entries_after": cache_entries(dev["compile_cache_dir"]),
        },
        "wall_s": {
            "store_write": store["seconds"], "store_reused": store["reused"],
            "kernel_check": kern["wall_s"], "load": load_s,
            "first_request": traffic["first_request_s"],
            "rest": traffic["rest_s"],
            "total": round(time.time() - t_start, 1),
        },
        "ids": traffic["ids"],
    }
    with open(os.path.join(WORK, "result.json"), "w") as f:
        json.dump(report, f, indent=1)
    for k in ("device", "versions", "model", "attn_impl", "requests",
              "blocks_read", "prefix_hit_tokens", "store",
              "peak_gib_after_load",
              "peak_gib", "compile_cache", "wall_s"):
        print(f"{k}: {json.dumps(report[k])}")
    print("daemon: drained; exiting 0")
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
