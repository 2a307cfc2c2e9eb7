"""Kernels and step: share of the decode program's own device time under the
scopes of a KDA mixer — ``kda_proj`` (its projections, the two low-rank pairs
and ``wo``), ``conv`` (the causal conv over ``[q | k | v]`` and its tail) and
``kda`` (the decay, the L2 norms, the state update, its read-out, the gated
norm), %. None where the program has no such scope (a model without KDA
layers, or a program from before the scopes existed)."""
from benchmark import span_reduce

SCOPES = ("kda_proj", "conv", "kda")


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not any(s in scopes for s in ("kda_proj", "kda")):
        return None
    return span_reduce.scope_share(rec, (span_reduce.DECODE_MODULE,), SCOPES)
