"""KV manager: of the bytes the live rows' keys and values would hold with
EVERY layer keeping its whole context, the share the two arenas really hold,
%, over the window's decode steps — from the program's per-kind counters
(``StepRecord.kv_kinds``: the blocks each kind's pool holds at a decode
dispatch) and the block's layers and entry bytes per kind:

    (full blocks × full layers × full entry + window blocks × window layers
     × window entry) ÷ (full blocks × (full layers × full entry + window
     layers × window entry))

A window layer that freed nothing behind its window holds as many blocks as a
full one and reads 100; with 9 window layers of 12 holding a window of 128
under contexts of thousands it reads 15-20. None where the records carry no
such counter (a model with one kind of KV state, a program from before it)."""
from benchmark import blocks, samples
from benchmark.harness import model_keys


def read(rec):
    block = blocks.load(rec["config"]["model_type"])
    if not hasattr(block, "attn_layers"):
        return None
    model = model_keys(rec["config"])
    layers = block.attn_layers(model)
    cost = {a: layers[a] * block.arena_bytes_per_token_layer(model, a)
            for a in layers}
    held = whole = 0.0
    for st in samples.steps_in_window(rec):
        kinds = st.get("kv_kinds")
        if not kinds or not kinds["full"]["blocks_in_use"]:
            continue
        full, swa = (kinds[a]["blocks_in_use"] for a in ("full", "swa"))
        held += full * cost["full"] + swa * cost["swa"]
        whole += full * (cost["full"] + cost["swa"])
    return 100.0 * held / whole if whole else None
