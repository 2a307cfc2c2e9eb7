"""Host loop: median, over the window's logs that carried tokens, of the time
from the log's landing on the host (`StepRecord.logs[*].landed`, the
program's `_Prefetched.done_at`) to the end of the step that applied it, ms —
what the host adds between the device's answer and the token's stamp."""
from benchmark import path_reduce, samples


def read(rec):
    s = path_reduce.emit_lags_s(rec)
    return samples.percentile(s, 50) * 1e3 if s else None
