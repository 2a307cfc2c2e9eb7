"""Kernels and step: of the live context tokens of the rows in the window's
decode steps, the share whose K/V the attention read, % — from the program's
counters (``StepRecord.sparse_tokens``: ``read`` ÷ ``live``, each summed over
rows and layers at a decode dispatch). 100 for a program that reads every live
token; a model that keeps 2,048 keys reads 100 up to a context of 2,048 and
2,048 ÷ context from there on (57 over a reply that runs from ~200 to 8.7 k).
None where the records carry no such counter (a model that selects nothing, a
program from before it)."""
from benchmark import samples


def read(rec):
    read_ = live = 0
    for st in samples.steps_in_window(rec):
        got = st.get("sparse_tokens")
        if got:
            read_ += got["read"]
            live += got["live"]
    return 100.0 * read_ / live if live else None
