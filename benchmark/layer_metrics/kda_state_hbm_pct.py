"""Kernels and step: the KDA decode update's share of its memory roofline:
the rows in flight in a decode microstep × this chip's KDA layers × what a
step moves of one row's recurrent state in one layer (the block's
``state_bytes_per_row_layer``: the float32 ``[heads, 128, 128]`` state and the
conv's tail, each read AND written) ÷ peak bytes/s ÷ the ``conv`` + ``kda``
scopes' own device time per decode microstep, %. Counted from the records and
the shapes whatever implements the update, so a loop in XLA reads LOW, not
absent; the ``kda`` scope also holds the decay, the L2 norms and the gated
norm, so the kernel alone stands higher than this reads; it cannot read over
100: every byte counted is one the update must move. None for a block without
a KDA state, without the scopes or the trace."""
from benchmark import blocks, samples, span_reduce
from benchmark.harness import model_keys

SCOPES = ("conv", "kda")


def read(rec):
    sp = span_reduce.spans(rec)
    steps = samples.decode_step_s(rec)
    if not sp or not steps or not rec.get("peaks") or not rec.get("traced"):
        return None
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "kda_state_bytes"):
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not scopes.get("kda"):
        return None
    kda_s = sum(scopes.get(s) or 0.0 for s in SCOPES)
    need = block.kda_state_bytes(model_keys(rec["config"]), rec, *rec["traced"])
    if not need:
        return None
    # the scopes' seconds are a chip's over the slice; ``steps`` has one
    # entry per execution and chip
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / (kda_s / len(steps))
