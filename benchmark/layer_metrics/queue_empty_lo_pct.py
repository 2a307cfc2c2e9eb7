"""Host loop: of the time the server held work in the window, the part in
which the host had left the device's queue empty, % — on the HOST's clock,
counted by the program at every enqueue with nothing un-landed before it,
from the stamp that found the previous program landed
(`StepRecord.dispatches[*].starved_lo_s`): the lower bound. Not the device's
idle time: a frozen process stamps nothing, so a stall of the machine is in
`queue_empty_hi_pct` at most, and the trace's `idle_with_work_pct` holds it."""
from benchmark import path_reduce


def read(rec):
    return path_reduce.queue_empty_pct(rec, "lo")
