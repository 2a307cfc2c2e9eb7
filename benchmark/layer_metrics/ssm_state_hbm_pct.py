"""Kernels and step: the one-step state update's share of its memory
roofline: the rows in flight in a decode microstep × this chip's mixer layers
× what a step moves of one row's recurrent state in one layer (the block's
``state_bytes_per_row_layer``: the float32 state and the conv's tail, each
read AND written) ÷ peak bytes/s ÷ the ``conv`` + ``ssm`` scopes' own device
time per decode microstep, %. It reads LOW where the program updates the
state of every row of a slot and one is live (the bytes counted are the live
rows'), or where the step is bound by its latency; it cannot read over 100:
every byte counted is one the update must move. None for a block without a
recurrent state, without the scopes or the trace."""
from benchmark import blocks, samples, span_reduce
from benchmark.harness import model_keys

SCOPES = ("conv", "ssm")


def read(rec):
    sp = span_reduce.spans(rec)
    steps = samples.decode_step_s(rec)
    if not sp or not steps or not rec.get("peaks") or not rec.get("traced"):
        return None
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "ssm_state_bytes"):
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    ssm_s = sum(scopes.get(s) or 0.0 for s in SCOPES)
    need = block.ssm_state_bytes(model_keys(rec["config"]), rec, *rec["traced"])
    if not ssm_s or not need:
        return None
    # the scopes' seconds are a chip's over the slice; ``steps`` has one
    # entry per execution and chip
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / (ssm_s / len(steps))
