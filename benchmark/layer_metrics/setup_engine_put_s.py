"""Ring (the engine): seconds of set-up from the first host → chips put to
the last array ready on its chips (`setup.engine.put`, and `.quant` where
the tree is re-laid), less the host staging that runs inside it."""
from benchmark import setup_reduce


def read(rec):
    return setup_reduce.seconds(rec, setup_reduce.PUT, less=setup_reduce.HOST)
