"""Ring: time of collective operations in the trace per decode microstep,
mean over chips, ms. Only a ring has any (the reader returns nothing on one
chip)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or rec["chips"] < 2 or "serve_chunk" not in tr["modules"]:
        return None
    steps = sum(len(chip) for chip in tr["modules"]["serve_chunk"])
    steps = steps / len(tr["modules"]["serve_chunk"]) * rec["chips"]
    return 1e3 * tr["collective_s"] / steps if steps else None
