"""Host loop: of the window's steps that applied a decode log, the share
that did not have to wait for it (`StepRecord.logs[*].waited` false: the
device had finished before the host came for the log), %."""
from benchmark import path_reduce


def read(rec):
    return path_reduce.host_bound_pct(rec)
