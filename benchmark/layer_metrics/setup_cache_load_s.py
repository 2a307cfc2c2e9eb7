"""Step programs: seconds of set-up spent on programs the compile cache
had — tracing + lowering + the cache's load, over the `setup.compile` spans
with `cache == hit`."""
from benchmark import setup_reduce


def read(rec):
    return setup_reduce.built_seconds(rec, from_cache=True)
