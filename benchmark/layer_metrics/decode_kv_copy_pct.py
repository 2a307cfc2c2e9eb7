"""Step programs: share of the decode program's own device time spent moving
the KV arena around the attention kernel, % — the operations of serve_chunk
under the scopes kv_take (a layer sliced out of the arena), kv_layout (the
head-major transposes of the kernel's K/V operands) and kv_put (the layer
written back); span_reduce's per-scope table."""
from benchmark import span_reduce


def read(rec):
    return span_reduce.scope_share(
        rec, (span_reduce.DECODE_MODULE,), ("kv_take", "kv_layout", "kv_put"))
