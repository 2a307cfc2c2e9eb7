"""Host loop: mean host work per step of the window, ms — the sum of the
step record's host phases, time blocked on the device excluded."""
from benchmark import samples


def read(rec):
    s = [st["host_s"] for st in samples.steps_in_window(rec)]
    return 1e3 * sum(s) / len(s) if s else None
