"""Host loop: 95th percentile of the same lag (`token_emit_lag_p50_ms`), ms —
its distance to the median is the host's jitter on a token's gap."""
from benchmark import path_reduce, samples


def read(rec):
    s = path_reduce.emit_lags_s(rec)
    return samples.percentile(s, 95) * 1e3 if s else None
