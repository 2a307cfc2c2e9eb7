"""Step programs: share of the decode program's own device time under no
scope of the vocabulary, % — copies the compiler made on its own and scopes
lost on the way to the trace. What the other shares cannot account for."""
from benchmark import span_reduce


def read(rec):
    return span_reduce.scope_share(
        rec, (span_reduce.DECODE_MODULE,), (span_reduce.UNSCOPED,))
