"""Step programs: seconds of set-up spent building programs the compile
cache did not have — tracing + lowering + the backend's compile, over the
`setup.compile` spans with `cache` other than `hit`."""
from benchmark import setup_reduce


def read(rec):
    return setup_reduce.built_seconds(rec, from_cache=False)
