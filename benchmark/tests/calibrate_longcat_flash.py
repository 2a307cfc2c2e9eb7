#!/usr/bin/env python3
"""What the margins of ``blocks/longcat_flash.py`` read when the program holds
a lower precision than the configuration states, or runs another model. Run ON
THE CHIP when ``DELTA_MEAN`` is set; every other argument is ``run.py``'s:

    python3 benchmark/tests/calibrate_longcat_flash.py no_zero \
        --workload longcat_flash_omni.draft --seed 7 --seconds 50

It changes the program in memory (nothing on disk, no option of the program)
and then runs the cell as ``run.py`` does — same traffic, same window, same
sample of scored requests, so the reading stands beside a sound run's at the
same count of positions. The result line's ``correct`` is the verdict under
the limits as they stand. Modes:

- ``sound``: nothing changed (the control).
- ``no_zero``: the zero-compute term dropped — a pick on an expert without
  weights adds nothing where it adds ``w · x`` (its weight set to zero before
  the product; the counters still count the pick).
- ``moe_late``: the experts fed the SECOND sub-layer's post-attention norm and
  joined where they are computed: an ordinary expert layer, not the shortcut.
- ``no_lora_scale``: ``s_q = s_kv = 1`` (``mla_scale_q_lora`` /
  ``mla_scale_kv_lora`` ignored).
- ``renorm``: the twelve kept weights divided by their sum before the scale
  of 6 (``norm_topk_prob`` true).
- ``fp8_latent``: every latent entry rounded to e4m3's three mantissa bits
  before it is written, under the bf16 label — the arena one precision down.
- ``int4_weights``: every matmul weight the ENGINE is given rounded to the 15
  levels of symmetric int4 under the int8 label and scales; the reference
  scores the served tokens under the int8 weights the configuration states
  (the check makes its own copy). The cheat that would pay: a decode step is
  bound by the weights it reads.
"""

import dataclasses
import functools
import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

MODES = ("sound", "no_zero", "moe_late", "no_lora_scale", "renorm",
         "fp8_latent", "int4_weights")


def patch(mode: str) -> None:
    import jax
    import jax.numpy as jnp
    import llm_sharding_tpu.models  # noqa: F401  (import cycle: models first)
    from llm_sharding_tpu.models import longcat_flash as lf
    from llm_sharding_tpu.ops import moe

    def with_cfg(**changes):
        block = lf.layer_block

        def changed(cfg, *a, **kw):
            return block(dataclasses.replace(cfg, **changes), *a, **kw)

        lf.layer_block = changed

    if mode == "no_zero":
        product = moe.expert_mlp

        def no_zero(x, weights, ids, *a, zero_from=None, **kw):
            if zero_from is not None:
                weights = jnp.where(ids >= zero_from, 0.0, weights)
            return product(x, weights, ids, *a, zero_from=zero_from, **kw)

        moe.expert_mlp = no_zero
    elif mode == "moe_late":
        from llm_sharding_tpu.ops.norms import rms_norm

        def late(cfg, p, h, cos, sin, attend, cache, moe_live=None,
                 moe_backend="auto"):
            B, S, H = h.shape
            eps = cfg.rms_norm_eps
            p0, p1 = lf.sub_layer(p, 0), lf.sub_layer(p, 1)
            scales = dict(q_scale=cfg.mla_q_scale, kv_scale=cfg.mla_kv_scale)
            h, cache = lf.mla_attention(
                cfg, p0, h, cos, sin, functools.partial(attend, 0, cache),
                **scales)
            x1 = rms_norm(h, p0["post_norm"], eps)
            h = h + lf.gated_mlp(x1, p0["w_gate"], p0["w_up"], p0["w_down"])
            h, cache = lf.mla_attention(
                cfg, p1, h, cos, sin, functools.partial(attend, 1, cache),
                **scales)
            x = rms_norm(h, p1["post_norm"], eps)
            x2 = x.reshape(B * S, H)  # NOT the shortcut: the second norm
            weights, ids = moe.route(
                x2, p["router"], cfg.num_experts_per_tok, cfg.norm_topk_prob,
                bias=p["router_bias"], scale=cfg.routed_scaling_factor)
            m, stats = moe.expert_mlp(
                x2, weights, ids, p["we_gate"], p["we_up"], p["we_down"],
                cfg.router_experts,
                live=None if moe_live is None else moe_live.reshape(B * S),
                layer=p.get("layer"), backend=moe_backend,
                held=cfg.held_experts_, zero_from=cfg.num_experts)
            h = h + lf.gated_mlp(x, p1["w_gate"], p1["w_up"], p1["w_down"])
            return h + m.reshape(B, S, H), cache, stats

        lf.layer_block = late
    elif mode == "no_lora_scale":
        with_cfg(mla_q_scale=1.0, mla_kv_scale=1.0)
    elif mode == "renorm":
        with_cfg(norm_topk_prob=True)
    elif mode == "fp8_latent":
        attention = lf.mla_attention

        def low(cfg, p, h, cos, sin, attend, **kw):
            def rounded(q_full, entry):
                # (``reduce_precision``, not a cast there and back: the chip's
                # compiler drops such a pair — ``xla_allow_excess_precision``)
                e = jax.lax.reduce_precision(
                    entry, exponent_bits=4, mantissa_bits=3)
                return attend(q_full, e)
            return attention(cfg, p, h, cos, sin, rounded, **kw)

        lf.mla_attention = low
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
    print("calibrate_longcat_flash:", mode, flush=True)
    runpy.run_path(os.path.join(BENCH, "run.py"), run_name="__main__")
