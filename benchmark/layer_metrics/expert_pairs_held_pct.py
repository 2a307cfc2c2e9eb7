"""Kernels and step: of the (token, expert) pairs the router chose in the window,
the share that fell on experts this chip HOLDS, % — from the program's
counter (``StepRecord.expert_tokens``, pairs per expert of the WHOLE layer)
and the block's ``held_experts``. An even router reads held / total (6.25 at
16 of 256): the canary of routing under a chip's share — a router that
normalised over the held experts only, or ids counted relative to the share,
reads 100. None where the block has no share or the records no counter."""
from benchmark import blocks, samples
from benchmark.harness import model_keys


def read(rec):
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "held_experts"):
        return None
    first, held = block.held_experts(model_keys(rec["config"]))
    routed = kept = 0
    for st in samples.steps_in_window(rec):
        tokens = st.get("expert_tokens")
        if not tokens:
            continue
        routed += sum(tokens)
        kept += sum(tokens[first:first + held])
    return 100.0 * kept / routed if routed else None
