"""95th percentile of the gaps between successive visible tokens of one
request, pooled over requests, zero gaps included, ms."""
from benchmark import samples


def read(rec):
    s = samples.gaps_s(rec)
    return samples.percentile(s, 95) * 1e3 if s else None
