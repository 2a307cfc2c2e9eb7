"""Kernels and step: share of the decode program's own device time under the
scope ``zero_expert`` — the zero-compute experts' term (the float32 sum of the
chosen identity experts' weights a token, times the expert path's input) and
the add that joins a shortcut layer's expert output to the residual stream at
the layer's end —, %: that an expert without weights costs what its name
says. None where the program has no such scope (a model without zero-compute
experts, or a program from before the scope existed)."""
from benchmark import span_reduce

SCOPES = ("zero_expert",)


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not any(s in scopes for s in SCOPES):
        return None
    return span_reduce.scope_share(rec, (span_reduce.DECODE_MODULE,), SCOPES)
