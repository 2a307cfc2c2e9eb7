"""Process start to window start, s (taken by the harness)."""


def read(rec):
    return rec["setup_s"]
