"""Admission: share of the token positions the window's prefill dispatches
computed that no prompt needed, %, by the program's own count: 1 − Σ
prompt_tokens ÷ Σ prefill_positions over the window's step records (fed
where serve_admit / serve_prefill_chunk are dispatched)."""
from benchmark import samples


def read(rec):
    steps = samples.steps_in_window(rec)
    real = sum(st.get("prompt_tokens", 0) for st in steps)
    computed = sum(st.get("prefill_positions", 0) for st in steps)
    return 100.0 * (1.0 - real / computed) if computed else None
