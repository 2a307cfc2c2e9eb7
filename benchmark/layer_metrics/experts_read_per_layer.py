"""Expert layer: mean distinct experts read per layer per decode microstep in
the window (the program's counter, ``StepRecord.experts_read`` ÷
``expert_steps``): k at one live row, at most k × the live rows, never above
the layer's experts. None where the records carry no such counter."""
from benchmark import blocks


def read(rec):
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "experts_read_per_layer"):
        return None
    return block.experts_read_per_layer(rec, *rec["window"])
