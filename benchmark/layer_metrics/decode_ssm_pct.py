"""Kernels and step: share of the decode program's own device time under the
scopes of a Mamba-2 mixer — ``ssm_proj`` (its two projections), ``conv`` (the
causal conv and its tail) and ``ssm`` (the state update, its read-out, the
gated norm), %. None where the program has no such scope (a model without
recurrent layers, or a program from before the scopes existed)."""
from benchmark import span_reduce

SCOPES = ("ssm_proj", "conv", "ssm")


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not any(s in scopes for s in SCOPES):
        return None
    return span_reduce.scope_share(rec, (span_reduce.DECODE_MODULE,), SCOPES)
