"""Kernels and step: of the tokens applied in the window, the share whose
logits were read from the LAST pass of a looped stack, % — from the program's
counter (``StepRecord.exit_passes``, tokens by the pass the exit gate chose:
what ``server_exit_pass_total`` counts). 100 at a threshold of 1 (the
published one): that the gate ran and chose what the equations say; lower
where a configuration lets tokens leave early. None where the records carry no
such counter (a model whose layers run once, a program from before it)."""
from benchmark import samples


def read(rec):
    last = total = 0
    for st in samples.steps_in_window(rec):
        got = st.get("exit_passes")
        if got:
            last += got[-1]
            total += sum(got)
    return 100.0 * last / total if total else None
