"""Kernels and step: of the (live token, expert) pairs the router chose in the
window, the share that fell on ZERO-COMPUTE experts — experts without weights
that return their input —, % — from the program's counter
(``StepRecord.expert_tokens``, pairs per output of the WHOLE router) past the
block's ``real_experts``. An even router reads zero / (real + zero) (33.3 at
256 of 768); 0 says the router scored the real experts only, 100 that ids are
counted from the wrong end. None for a block without zero-compute experts or
records without the counter."""
from benchmark import blocks, samples
from benchmark.harness import model_keys


def read(rec):
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "real_experts"):
        return None
    model = model_keys(rec["config"])
    if not block.zero_experts(model):
        return None
    real = block.real_experts(model)
    routed = zero = 0
    for st in samples.steps_in_window(rec):
        tokens = st.get("expert_tokens")
        if not tokens:
            continue
        routed += sum(tokens)
        zero += sum(tokens[real:])
    return 100.0 * zero / routed if routed else None
