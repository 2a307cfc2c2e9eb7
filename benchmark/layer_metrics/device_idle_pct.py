"""Device: 1 − (union of the intervals in which an operation ran on the
chip) ÷ traced window, mean over the cell's chips, %."""


def read(rec):
    tr = rec.get("trace")
    return tr["idle_pct"] if tr else None
