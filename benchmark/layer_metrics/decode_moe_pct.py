"""Kernels and step: share of the decode program's own device time under the
scopes of a sparse MLP — ``router`` (float32 logits, softmax, top-k) and
``moe`` (tiles, the expert kernel, the weighted sum, the counters), %. None
where the program has no such scope (a model without experts, or a program
from before the scopes existed)."""
from benchmark import span_reduce

SCOPES = ("router", "moe")


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not any(s in scopes for s in SCOPES):
        return None
    return span_reduce.scope_share(rec, (span_reduce.DECODE_MODULE,), SCOPES)
