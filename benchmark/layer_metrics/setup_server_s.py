"""KV manager (the server): seconds of set-up in the server's constructor —
arenas, tables and state ready on the chips, pools, mirrors, threads
(`setup.server`, whole)."""
from benchmark import setup_reduce


def read(rec):
    return setup_reduce.seconds(rec, setup_reduce.SERVER)
