"""Scheduler: the longest time to first token from the due time among the
first tokens that became visible in the window, overdue requests at their
worst (samples.overdue), ms. The worst of a handful, named as that."""
from benchmark import samples


def read(rec):
    s = samples.ttft_s(rec)
    return max(s) * 1e3 if s else None
