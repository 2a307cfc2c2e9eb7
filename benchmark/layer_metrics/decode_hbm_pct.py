"""Kernels and step: roofline share of the decode microstep, memory bound:
the bytes one chip must read for it (its layers, its share of the head, the
live KV of the rows in the step; the block's decode_step_bytes, which is
handed the records too) ÷ peak bytes/s ÷ decode_step_ms, %."""
from benchmark import blocks, samples
from benchmark.harness import model_keys


def live_tokens_per_slot(rec, lo, hi):
    """Mean over the steps in [lo, hi] of the summed context lengths of the
    requests in flight, ÷ the slots (a microstep serves one slot)."""
    steps = samples.steps_in_window(rec, lo, hi)
    if not steps:
        return None
    total = 0.0
    for st in steps:
        t = st["t"]
        for r in rec["requests"]:
            started = r["server_started_at"]
            if started is None or started > t:
                continue
            if r["finished"] is not None and r["finished"] < t:
                continue
            total += r["prompt_len"] + sum(1 for s in r["stamps"] if s <= t)
    return total / len(steps) / rec["chips"]


def read(rec):
    steps = samples.decode_step_s(rec)
    if not steps or not rec.get("peaks"):
        return None
    step_ms = samples.percentile(steps, 50) * 1e3
    ta, tb = rec["traced"]
    live = live_tokens_per_slot(rec, ta, tb)
    if live is None:
        return None
    need = blocks.load(rec["config"]["model_type"]).decode_step_bytes(
        model_keys(rec["config"]), rec["config"]["deployment"]["weight_dtype"],
        rec["chips"], live, rec,
    )
    least_ms = 1e3 * need / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_ms / step_ms
