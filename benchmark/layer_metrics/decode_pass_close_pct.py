"""Kernels and step: share of the decode program's own device time under the
scope ``pass_close`` — what closes a pass of a LOOPED stack (the final norm
whose result enters the next pass, the exit gate's product and sigmoid, the
running exit choice: ``models/stack.run_passes``), T times a decode step —, %:
that running the layers T times costs the layers T times and the closes next
to nothing. None where the program has no such scope (a model whose layers
run once, or a program from before the scope existed)."""
from benchmark import span_reduce

SCOPES = ("pass_close",)


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not any(s in scopes for s in SCOPES):
        return None
    return span_reduce.scope_share(rec, (span_reduce.DECODE_MODULE,), SCOPES)
