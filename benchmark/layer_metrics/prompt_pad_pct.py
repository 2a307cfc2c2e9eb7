"""Admission: share of the token positions that the window's prefills
computed and no prompt needed, %. One admission prefills batch_per_slot rows
at its bucket (the next power of two over the longest prompt), whatever the
rows hold: 1 − real prompt tokens ÷ (rows x bucket), summed over admissions."""
from benchmark import samples


def read(rec):
    rows = rec["config"]["serve"]["batch_per_slot"]
    real = padded = 0
    for group in samples.admissions(rec):
        real += sum(r["prompt_len"] for r in group)
        padded += rows * samples.bucket(max(r["prompt_len"] for r in group))
    return 100.0 * (1.0 - real / padded) if padded else None
