"""Kernels and step: roofline share of the expert product of a decode
microstep, memory bound: the bytes of the experts it had to read — the mean
distinct experts read per layer per decode microstep over the traced slice
(the program's counter) × one expert's bytes (the block's ``expert_bytes``) ×
this chip's layers — ÷ peak bytes/s ÷ the ``moe`` scope's own device time per
decode microstep, %. Each distinct expert counts once, so a kernel that reads
more reads under 100%, never over. None without the counter or the scope."""
from benchmark import blocks, samples, span_reduce
from benchmark.harness import model_keys


def read(rec):
    sp = span_reduce.spans(rec)
    steps = samples.decode_step_s(rec)
    if not sp or not steps or not rec.get("peaks"):
        return None
    moe_s = sp["scopes"].get(span_reduce.DECODE_MODULE, {}).get("moe")
    block = blocks.load(rec["config"]["model_type"])
    if not moe_s or not hasattr(block, "expert_bytes"):
        return None
    n = block.experts_read_per_layer(rec)
    if n is None:
        return None
    model = model_keys(rec["config"])
    layers = block.dims(model)["layers"] / rec["chips"]
    need = n * layers * block.expert_bytes(
        model, rec["config"]["deployment"]["weight_dtype"])
    # a chip runs executions x stages microsteps: as many as ``steps`` has
    # entries (one per execution and chip); the scope's seconds are a chip's
    moe_step_s = moe_s / len(steps)
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / moe_step_s
