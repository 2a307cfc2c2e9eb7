"""Kernels and step: share of the decode program's own device time under the
scope ``moe_latent`` — a LatentMoE's two projections, into the experts'
latent space and out of it: what the latent space costs beside what it saves
in expert bytes, %. None where the program has no such scope."""
from benchmark import span_reduce

SCOPES = ("moe_latent",)


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp:
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    if not any(s in scopes for s in SCOPES):
        return None
    return span_reduce.scope_share(rec, (span_reduce.DECODE_MODULE,), SCOPES)
