"""Kernels: share of the decode program's own device time under the scope
attn (the paged decode kernel and its GQA fold; the kv_layout transposes
inside it count as KV copies, not here), %."""
from benchmark import span_reduce


def read(rec):
    return span_reduce.scope_share(
        rec, (span_reduce.DECODE_MODULE,), ("attn",))
