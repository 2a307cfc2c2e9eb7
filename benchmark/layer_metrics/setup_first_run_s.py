"""Step programs: seconds of set-up from a program's first dispatch to its
log's landing, less the compiles inside (`setup.first_run`, summed)."""
from benchmark import setup_reduce


def read(rec):
    return setup_reduce.first_run_seconds(rec)
