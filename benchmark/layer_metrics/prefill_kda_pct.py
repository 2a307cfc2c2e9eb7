"""Kernels and step: share of the chunked-prefill program's own device time
(``serve_prefill_chunk``) under the scopes ``conv`` + ``kda`` — a KDA mixer's
causal conv and its chunkwise WY form over the chunk's positions with the
decay and the gated norm, %. Informational: it decides how soon a reply
starts, which no end-to-end metric judges yet. None where the program has no
such scope or the slice holds no chunk."""
from benchmark import span_reduce

MODULE = "serve_prefill_chunk"
SCOPES = ("conv", "kda")


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    if "kda" not in sp["scopes"].get(MODULE, {}):
        return None
    return span_reduce.scope_share(rec, (MODULE,), SCOPES)
