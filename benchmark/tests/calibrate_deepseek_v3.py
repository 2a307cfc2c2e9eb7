#!/usr/bin/env python3
"""Chip tool: where the margins of ``gigachat31_702b_a36b`` come from, and
what they read when the NEW code computes in a lower precision than it says.
Run ON THE CHIP when ``DELTA_MEAN`` is set:

    python3 benchmark/tests/calibrate_deepseek_v3.py layers [seed ...]
    python3 benchmark/tests/calibrate_deepseek_v3.py chain [seed ...]
    python3 benchmark/tests/calibrate_deepseek_v3.py kernels
    python3 benchmark/tests/calibrate_deepseek_v3.py fp8_latent \
        --workload gigachat31_702b_a36b.stream --seed 7 --seconds 50

``layers`` makes the configuration's weights per seed, draws one sequence from
the vocabulary slice and runs ONE LAYER AT A TIME of the program's block
(``models/deepseek_v3``: bf16 activations, int8 weights, dense-cache
attention) on the REFERENCE's float32 hidden state of the layer below, so no
error accumulates. It prints the per-token relative error of what the layer
adds: rounding reads alike on every token; a token whose HELD experts differ
from the reference's (a near-tie of the router flipped by the bf16 input)
carries the whole weight of one expert, 2.5 / 8 of the routed sum. It is no
cell's run and prints no result line.

``chain`` runs the same layers CHAINED (each on the program's own output of
the layer below, so the error accumulates as in a served prefill), once in
bfloat16 and once with float32 activations at ``highest``, and scores the
program's next-token choice at every position under the reference's logits:
the margins a prefill ALONE reads, to stand beside a served run's (prefill,
then decode through the arena). ``kernels`` holds the two latent Pallas
kernels to a float32 attention over the same bf16 operands, at the
configuration's widths and with scores as wide as the seeded model's were
before ``wq_b`` was softened (deviation 2.8). The three run on the CPU too
(``chain`` in ~8 minutes at the published widths; ``kernels interpret``), and
``CALIBRATE_CONFIG=<file>`` names another configuration of the block.

Every other mode changes the program in memory (nothing on disk, no option of
the program) and then runs the cell as ``run.py`` does, every other argument
being ``run.py``'s — same traffic, same window, same sample of scored
requests, so the reading stands beside a sound run's at the same count of
positions; ``correct`` in the result line is the verdict under the limits as
they stand:

- ``sound``: nothing changed (the control).
- ``fp8_latent``: the latent entry ``[c_kv | k_pe]`` rounded to the three
  mantissa bits of fp8 e4m3 before it is written (an fp8 arena under the bf16
  label; the arena's type check cannot see it, its arrays stay bfloat16).
- ``fp8_experts``: what enters a routed expert's matmuls rounded likewise
  (``calibrate_olmoe.py``'s kernel: the two models share it).
- ``bf16_router``: the router's logits multiplied out in bfloat16; sigmoid,
  bias, groups and top-k as they are.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import blocks, harness, reference, weights  # noqa: E402

S = 256


def program_layer(cfg, p, h):
    """One layer of the program over h [S, H] (bf16), dense-cache attention."""
    from llm_sharding_tpu.models import deepseek_v3 as ds
    from llm_sharding_tpu.ops.flash_attention import attention_step
    from llm_sharding_tpu.ops.rope import rope_cos_sin

    n = h.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    cos, sin = rope_cos_sin(pos, cfg, dtype=jnp.float32)
    r, scale = cfg.kv_lora_rank, ds.softmax_scale(cfg)

    def attend(q_full, entry):
        return attention_step(
            q_full, entry, entry[..., :r], pos, pos, jnp.zeros((), jnp.int32),
            scale,
        ), None

    out, _, stats = ds.mla_block(cfg, p, h[None], cos, sin, attend)
    return out[0], stats


def setup():
    name = os.environ.get("CALIBRATE_CONFIG", os.path.join(
        ROOT, "benchmark/configs/gigachat31_702b_a36b.json"))
    with open(name) as f:
        cfg_file = json.load(f)
    model = harness.model_keys(cfg_file)
    block = blocks.load(cfg_file["model_type"])
    return (cfg_file, model, block, harness.model_config(cfg_file),
            blocks.kinds(block, model))


def main(seeds):
    cfg_file, model, block, cfg, kinds = setup()
    dev = jax.devices()[:1]
    for seed in seeds:
        params = weights.make_params(block, model, seed, "int8", dev)
        tables = {t.name: params[t.name] for t in block.tables(model)}
        ids = np.random.default_rng(seed).integers(
            0, model["vocab_size"], S).astype(np.int32)
        h = block.embed(tables, jnp.asarray(ids), **block.head_static(model))
        run = jax.jit(program_layer, static_argnums=0)
        for l in range(block.dims(model)["layers"]):
            p = weights.take_layer(params["layers"], kinds, l)
            kw = blocks.static_of(block, model, kinds, l)
            want = block.layer_forward(h, reference._as_ref_layer(p), **kw)
            got, stats = run(cfg, p, h.astype(jnp.bfloat16))
            got = np.asarray(got, np.float32)
            d = np.linalg.norm(got - np.asarray(want), axis=-1)
            rel = d / np.linalg.norm(np.asarray(want) - np.asarray(h), axis=-1)
            q = np.percentile(rel, [50, 90, 99, 100])
            big = int((rel > 5 * q[0]).sum())
            print(f"seed {seed} layer {l} {kinds[l]}: error of the layer's "
                  f"own addition, relative: p50 {q[0]:.4f} p90 {q[1]:.4f} "
                  f"p99 {q[2]:.4f} max {q[3]:.4f}; tokens over 5 x p50: "
                  f"{big} of {S}", flush=True)
            h = want
        del params
    print("done")


def chain(seeds):
    cfg_file, model, block, cfg, kinds = setup()
    from llm_sharding_tpu.models import deepseek_v3 as ds

    dev = jax.devices()[:1]
    run = jax.jit(program_layer, static_argnums=0)
    for seed in seeds:
        params = weights.make_params(block, model, seed, "int8", dev)
        tables = {t.name: params[t.name] for t in block.tables(model)}
        ids = np.random.default_rng(seed).integers(
            0, model["vocab_size"], S).astype(np.int32)
        h_ref = block.embed(tables, jnp.asarray(ids), **block.head_static(model))
        hs = {"bf16": h_ref.astype(jnp.bfloat16), "f32": h_ref}
        for l in range(block.dims(model)["layers"]):
            p = weights.take_layer(params["layers"], kinds, l)
            kw = blocks.static_of(block, model, kinds, l)
            h_ref = block.layer_forward(h_ref, reference._as_ref_layer(p), **kw)
            hs["bf16"] = run(cfg, p, hs["bf16"])[0]
            with jax.default_matmul_precision("highest"):
                hs["f32"] = run(cfg, p, hs["f32"])[0]
            for name, h in hs.items():
                d = np.linalg.norm(np.asarray(h, np.float32) - np.asarray(h_ref), axis=-1)
                rel = d / np.linalg.norm(np.asarray(h_ref), axis=-1)
                q = np.percentile(rel, [50, 99, 100])
                print(f"seed {seed} after layer {l} {name}: error of the hidden "
                      f"state, relative: p50 {q[0]:.4f} p99 {q[1]:.4f} max "
                      f"{q[2]:.4f}; tokens over 5 x p50: "
                      f"{int((rel > 5 * q[0]).sum())} of {S}", flush=True)
        want = np.asarray(block.logits(h_ref, tables, **block.head_static(model)))
        for name, h in hs.items():
            with jax.default_matmul_precision(
                    "highest" if name == "f32" else "default"):
                got = np.asarray(ds.final_logits(cfg, tables, h[None])[0], np.float32)
            served = got.argmax(-1)
            m = want.max(-1) - want[np.arange(S), served]
            print(f"seed {seed} chained {name}: margin mean {m.mean():.5f} max "
                  f"{m.max():.4f} p99 {np.percentile(m, 99):.4f}; program argmax "
                  f"= reference argmax at {100 * (m == 0).mean():.1f}% of {S}; "
                  f"logits std {want.std():.3f}", flush=True)
        del params
    print("done")


def kernels(backend="kernel"):
    """Both latent kernels against float32 attention over the SAME bf16
    operands: what the kernels' own arithmetic adds. Scores are drawn as wide
    as the seeded model's (std ~2.8 after the scale)."""
    from llm_sharding_tpu.models import deepseek_v3 as ds
    from llm_sharding_tpu.ops.paged_attention import (
        paged_attention, paged_prefill,
    )

    cfg = setup()[3]
    r, dr, Dk, Nh = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.cache_k_dim, cfg.num_attention_heads
    BS, T, L, B = 32, 64, 2, 4
    scale = ds.softmax_scale(cfg)
    ks = jax.random.split(jax.random.key(5), 6)
    bf = jnp.bfloat16
    lens = np.array([300, 700, 33, 1500])

    def entries(key, shape_lead, s_lat, s_pe):
        a, b = jax.random.split(key)
        e = jnp.concatenate([
            s_lat * jax.random.normal(a, (*shape_lead, r)),
            s_pe * jax.random.normal(b, (*shape_lead, dr)),
            jnp.zeros((*shape_lead, Dk - r - dr)),
        ], -1)
        return e.astype(bf)

    NB = B * T + 1
    k_arena = entries(ks[0], (L, NB, 1, BS), 1.0, 2.0)
    v_arena = jnp.zeros((L, NB, 1, BS, 0), bf)
    table = jnp.arange(1, NB, dtype=jnp.int32).reshape(B, T)
    cols = np.arange(T * BS)[None]
    kv_pos = jnp.asarray(np.where(cols < lens[:, None], cols, 2**30), jnp.int32)

    def exact(q, qpos):
        with jax.default_matmul_precision("highest"):
            k = k_arena[1][table].reshape(B, T * BS, Dk).astype(jnp.float32)
            sc = jnp.einsum("bshd,btd->bhst", q.astype(jnp.float32), k) * scale
            ok = kv_pos[:, None, None, :] <= qpos[:, None, :, None]
            p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
            return jnp.einsum("bhst,btd->bshd", p, k[..., :r])

    def report(what, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want)
        rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
        print(f"{what}: error of o_lat per query and head, relative: p50 "
              f"{np.median(rel):.4f} p99 {np.percentile(rel, 99):.4f} max "
              f"{rel.max():.4f}", flush=True)

    q = entries(ks[1], (B, 1, Nh), 0.5, 1.0)
    qpos = jnp.asarray(lens[:, None] - 1, jnp.int32)
    want = exact(q, qpos)
    for be in (backend, "xla"):
        report(f"decode {be}", paged_attention(
            q, k_arena, v_arena, 1, table, qpos, kv_pos, scale, backend=be,
            latent_v=r), want)
    Sc = 256
    q = entries(ks[2], (B, Sc, Nh), 0.5, 1.0)
    start = np.maximum(lens - Sc, 0)
    qpos = jnp.asarray(np.minimum(start[:, None] + np.arange(Sc)[None],
                                  lens[:, None] - 1), jnp.int32)
    want = exact(q, qpos)
    nlive = jnp.asarray(-(-lens // BS), jnp.int32)
    for be in (backend, "xla"):
        report(f"prefill {be}", paged_prefill(
            q, k_arena, v_arena, 1, table, qpos, kv_pos, scale, backend=be,
            nlive=nlive, latent_v=r), want)
    print("done")


MODES = ("layers", "chain", "kernels", "sound", "fp8_latent", "fp8_experts", "bf16_router")


def patch(mode: str) -> None:
    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.models import deepseek_v3 as ds
    from llm_sharding_tpu.ops import moe

    sys.path.insert(0, HERE)
    import calibrate_olmoe

    if mode == "fp8_latent":
        block = ds.mla_block

        def low(cfg, p, h, cos, sin, attend, *a, **kw):
            def rounded(q_full, entry):
                e = calibrate_olmoe.round_e4m3(entry).astype(entry.dtype)
                return attend(q_full, e)
            return block(cfg, p, h, cos, sin, rounded, *a, **kw)

        ds.mla_block = low
    elif mode == "fp8_experts":
        calibrate_olmoe.patch("fp8_experts")
    elif mode == "bf16_router":
        route = moe.route_noaux_tc

        def low(x, router, bias, *a, **kw):
            logits = jnp.dot(x, router.astype(x.dtype))  # bf16 in, bf16 out
            # hand the float32 code logits that are already rounded: a
            # one-hot "router" applied to them reproduces them exactly
            eye = jnp.eye(router.shape[-1], dtype=jnp.float32)
            return route(logits.astype(jnp.float32), eye, bias, *a, **kw)

        moe.route_noaux_tc = low
    elif mode != "sound":
        raise SystemExit(f"mode {mode!r}: one of {MODES}")


if __name__ == "__main__":
    mode = sys.argv.pop(1) if len(sys.argv) > 1 else "layers"
    if mode in ("layers", "chain"):
        {"layers": main, "chain": chain}[mode](
            [int(s) for s in sys.argv[1:]] or [11])
    elif mode == "kernels":
        kernels(*sys.argv[1:2])
    else:
        import runpy

        patch(mode)
        print("calibrate_deepseek_v3:", mode, flush=True)
        runpy.run_path(os.path.join(ROOT, "benchmark", "run.py"),
                       run_name="__main__")
