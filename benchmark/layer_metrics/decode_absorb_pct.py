"""Kernels and step: share of the decode program's own device time under the
scope ``absorb`` — a latent-attention layer's two absorbed products (the
query's nope part into the latent space before the kernel, the latent output
out of it after), %. None where the program has no such scope (a model
without latent attention, or a program from before the scope existed)."""
from benchmark import span_reduce

SCOPES = ("absorb",)


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not any(s in scopes for s in SCOPES):
        return None
    return span_reduce.scope_share(rec, (span_reduce.DECODE_MODULE,), SCOPES)
