"""KV manager: peak over the window of arena blocks in use ÷ blocks in all
(the program's gauges, sampled after every step), %."""
from benchmark import samples


def read(rec):
    s = [100.0 * used / total for t, used, total in rec["kv_samples"]
         if samples.in_window(rec, t) and total > 0]
    return max(s) if s else None
