"""Step programs: device time of one decode microstep, ms — each serve_chunk
execution in the trace ÷ its cycles (one per stage of the ring), median over
executions and chips."""
from benchmark import samples


def read(rec):
    s = samples.decode_step_s(rec)
    return samples.percentile(s, 50) * 1e3 if s else None
