"""Kernels and step: a prefill chunk's state-space scan's share of its
memory roofline: the bytes a ONE-PASS scan must move for the prompt tokens
the step records say went through it in the traced slice
(``scan_positions["real"]``, the program's counter: a pad need not be moved,
and a row without a token is not visited) × this chip's mixer layers × the
block's ``scan_bytes`` (``x``, ``dt``, ``z`` in and ``y`` out a channel, ``B``
and ``C`` a state value, float32; the state once) ÷ peak bytes/s ÷ the
``ssm`` scope's own device time in ``serve_prefill_chunk`` over the slice,
%. Counted from the counter and the block's shapes, whatever implements the
scan: a scan that writes ``[positions, channels, state]`` to HBM or passes
over it several times takes longer over the same count and reads LOW, not
absent; it cannot read over 100 (every byte counted is one the scan must
move), and a scan bound by its arithmetic — 16 exponentials a channel and
position — reads low however it is written. Informational (no end-to-end
metric judges how soon a reply starts). None for a block without
``scan_bytes``, a program without the counter or the scope, or a slice that
holds no chunk."""
from benchmark import blocks, span_reduce
from benchmark.harness import model_keys

MODULE = "serve_prefill_chunk"


def read(rec):
    sp = span_reduce.spans(rec)
    if not sp or not rec.get("peaks") or not rec.get("traced"):
        return None
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "scan_bytes") or not hasattr(block, "kind_layers"):
        return None
    scan_s = sp["scopes"].get(MODULE, {}).get("ssm")
    lo, hi = rec["traced"]
    real = sum(
        (st.get("scan_positions") or {}).get("real", 0)
        for st in rec.get("steps", ()) if lo <= st["t"] <= hi
    )
    if not scan_s or not real:
        return None
    model = model_keys(rec["config"])
    need = block.kind_layers(model)["mamba"] * block.scan_bytes(model, real)
    # the scope's seconds are a chip's over the slice, as the bytes are
    return 100.0 * need / rec["peaks"]["hbm_bytes_per_s"] / scan_s
