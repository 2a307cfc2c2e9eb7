"""Host loop: `queue_empty_lo_pct`'s upper bound, % — each empty stretch
counted from the last poll that still saw the device busy (or the previous
program's own enqueue) instead of the stamp that found it landed
(`StepRecord.dispatches[*].starved_hi_s`)."""
from benchmark import path_reduce


def read(rec):
    return path_reduce.queue_empty_pct(rec, "hi")
