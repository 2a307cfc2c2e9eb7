"""Kernels: share of the prefill programs' own device time (serve_admit,
serve_prefill_chunk, serve_admit_finish) under the scope attn — the chunked
prefill kernel or the dense flash/XLA attention, %."""
from benchmark import span_reduce


def read(rec):
    return span_reduce.scope_share(
        rec, span_reduce.PREFILL_MODULES, ("attn",))
