"""Kernels and step: the indexer's share of its memory roofline: the bytes a
decode microstep's indexer MUST read — every layer's indexer weights and the
index keys of the live tokens it scores (the block's ``index_bytes``, from the
records' contexts over the traced slice) ÷ peak bytes/s ÷ the ``indexer`` +
``select`` scopes' own device time per decode microstep, %. It reads LOW where
the scores and the top-k are bound by their latency and not by their bytes; it
cannot read over 100: every byte counted is one the indexer must read. None for
a block without ``index_bytes``, without the scopes or without the trace."""
from benchmark import blocks, samples, span_reduce
from benchmark.harness import model_keys

SCOPES = ("indexer", "select")


def read(rec):
    sp = span_reduce.spans(rec)
    steps = samples.decode_step_s(rec)
    if not sp or not steps or not rec.get("peaks") or not rec.get("traced"):
        return None
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "index_bytes"):
        return None
    scopes = sp["scopes"].get(span_reduce.DECODE_MODULE, {})
    index_s = sum(scopes.get(s) or 0.0 for s in SCOPES)
    need = block.index_bytes(
        model_keys(rec["config"]),
        rec["config"]["deployment"]["weight_dtype"], rec, *rec["traced"],
    )
    if not index_s or not need:
        return None
    # the scopes' seconds are a chip's over the slice; ``steps`` has one
    # entry per execution and chip
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / (
        index_s / len(steps))
