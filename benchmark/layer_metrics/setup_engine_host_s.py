"""Ring (the engine): seconds of set-up the engine spends on the host —
pulling the parameter tree to numpy (`setup.engine.host_pull`) and stacking /
padding the stages and the head (`setup.engine.stack`), summed."""
from benchmark import setup_reduce


def read(rec):
    return setup_reduce.seconds(rec, setup_reduce.HOST)
