"""Kernels and step: share of the chunked-prefill program's own device time
(``serve_prefill_chunk``) under the scopes ``conv`` + ``ssm`` — a mixer's
causal conv and its scan over the chunk's positions with the gate (Mamba-2's
block form, Mamba-1's scan in time), %. Informational: it decides how soon a
reply starts, which no end-to-end metric judges yet. None where the program
has no such scope or the slice holds no chunk."""
from benchmark import span_reduce

MODULE = "serve_prefill_chunk"
SCOPES = ("conv", "ssm")


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(MODULE, {})
    if not any(s in scopes for s in SCOPES):
        return None
    return span_reduce.scope_share(rec, (MODULE,), SCOPES)
