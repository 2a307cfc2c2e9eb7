"""Step programs: programs built (compiled or loaded) during set-up — the
count of `setup.compile` spans before the window."""
from benchmark import setup_reduce


def read(rec):
    spans = setup_reduce.compiles(rec)
    return None if spans is None else len(spans)
