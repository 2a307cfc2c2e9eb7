"""Kernels and step: the decode attention kernel's share of its memory
roofline in a model with a KV state per kind of layer: the keys and values a
decode microstep's attention MUST read — per kind of attention layer, the
live tokens a query of that kind reaches (a full layer the whole context, a
window layer at most its window) × what that kind's arena holds of one token
and layer × that kind's layers (the block's ``attn_kv_bytes``, from the
records' contexts over the traced slice) ÷ peak bytes/s ÷ the ``attn``
scope's own device time per decode microstep, %. The bytes are what a sound
program must read: a kernel that walked behind the window, or read blocks
the window's edge does not cut, takes longer over the same count and reads
LOWER; it cannot read over 100. None for a block without ``attn_kv_bytes``,
without the scope or without the trace."""
from benchmark import blocks, samples, span_reduce
from benchmark.harness import model_keys


def read(rec):
    sp = span_reduce.spans(rec)
    steps = samples.decode_step_s(rec)
    if not sp or not steps or not rec.get("peaks") or not rec.get("traced"):
        return None
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "attn_kv_bytes"):
        return None
    attn_s = sp["scopes"].get(span_reduce.DECODE_MODULE, {}).get("attn")
    need = block.attn_kv_bytes(model_keys(rec["config"]), rec, *rec["traced"])
    if not attn_s or not need:
        return None
    attn_step_s = attn_s / len(steps)
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / attn_step_s
