"""Step programs: 95th percentile of the time between the landings of two
decode logs next to each other in the device's queue, both stamped while the
host waited (`StepRecord.logs[*].landed`, `.exact`, `.n`), ms — the device's
pace as the host sees it, tail included: the gap a client would see if the
emit lag were constant."""
from benchmark import path_reduce, samples


def read(rec):
    s = path_reduce.landing_gaps_s(rec)
    return samples.percentile(s, 95) * 1e3 if s else None
