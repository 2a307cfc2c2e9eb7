"""Kernels and step: share of the decode program's own device time under the
scope ``ssm_x`` — a Mamba-1 mixer's path to ``dt``, ``B`` and ``C``: ``w_x``,
the three norms, ``w_dt`` and its softplus, which Mamba-2 has no counterpart
of: small products and norms at one row, bound by their latency, %. None
where the program has no such scope."""
from benchmark import span_reduce

SCOPES = ("ssm_x",)


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not any(s in scopes for s in SCOPES):
        return None
    return span_reduce.scope_share(rec, (span_reduce.DECODE_MODULE,), SCOPES)
