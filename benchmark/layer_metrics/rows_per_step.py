"""Scheduler: mean rows in flight per productive step of the window (the
program's step records)."""
from benchmark import samples


def read(rec):
    s = [st["rows"] for st in samples.steps_in_window(rec)]
    return sum(s) / len(s) if s else None
