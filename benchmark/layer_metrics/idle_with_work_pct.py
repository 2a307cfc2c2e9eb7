"""Host loop: share of the time the server held work in which chip 0 ran
nothing, % — chip 0's idle intervals cut to the runs of serve.step
annotations that began with rows, queued requests or un-applied logs, over
those runs' length. The idle share the program answers for: an empty server
is not in it. rec["spans"]["idle"]["by"] splits it by serve.<phase>."""
from benchmark import span_reduce


def read(rec):
    sp = span_reduce.spans(rec)
    idle = sp and sp["idle"]
    if not idle or not idle["work_s"]:
        return None
    return 100.0 * idle["idle_with_work_s"] / idle["work_s"]
