"""Step programs: share of `setup_s` outside the harness's own phases and
every top-level set-up span of the program, % — imports, backend start, the
warm-up requests' own steps, whatever has no span yet."""
from benchmark import setup_reduce


def read(rec):
    return setup_reduce.unaccounted_pct(rec)
