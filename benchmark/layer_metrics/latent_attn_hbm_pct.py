"""Kernels and step: the latent decode kernel's share of its memory roofline:
the live latents of the rows in a decode microstep × what the arena holds of
one token and layer (the block's ``arena_bytes_per_token_layer``: each latent
entry is read ONCE, its value being a slice of its key) × this chip's layers
÷ peak bytes/s ÷ the ``attn`` scope's own device time per decode microstep,
%. It reads LOW where a step has one live row of a few hundred tokens: the
kernel is then bound by its latency (a grid step a cell, the absorbed
query's 64 x 640 tile), not by the bytes — that is what it is here to show.
It cannot read over 100: every byte counted is one the kernel must read.
None for a block without a latent arena, without the scope or the trace."""
from benchmark import blocks, samples, span_reduce
from benchmark.harness import model_keys
from benchmark.layer_metrics.decode_hbm_pct import live_tokens_per_slot


def read(rec):
    sp = span_reduce.spans(rec)
    steps = samples.decode_step_s(rec)
    if not sp or not steps or not rec.get("peaks") or not rec.get("traced"):
        return None
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "arena_bytes_per_token_layer"):
        return None
    attn_s = sp["scopes"].get(span_reduce.DECODE_MODULE, {}).get("attn")
    live = live_tokens_per_slot(rec, *rec["traced"])
    if not attn_s or not live:
        return None
    model = model_keys(rec["config"])
    layers = block.dims(model)["layers"] / rec["chips"]
    need = live * layers * block.arena_bytes_per_token_layer(model)
    attn_step_s = attn_s / len(steps)
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / attn_step_s
