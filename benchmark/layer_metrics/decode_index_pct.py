"""Kernels and step: share of the decode program's own device time under the
scopes of a token-selecting model's indexer — ``indexer`` (its three
projections, the index key's LayerNorm and rotary, the scores of a query
against the row's live index keys) and ``select`` (the top-k over them and what
turns it into the attention's list), %. What the selection costs a step beyond
the bytes it saves: the chosen tokens' attention itself stays under ``attn``.
None where the program has no such scope (a model that selects nothing, or a
program from before the scopes existed)."""
from benchmark import span_reduce

SCOPES = ("indexer", "select")


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not any(s in scopes for s in SCOPES):
        return None
    return span_reduce.scope_share(rec, (span_reduce.DECODE_MODULE,), SCOPES)
