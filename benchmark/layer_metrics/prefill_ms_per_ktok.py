"""Step programs: device time of the prefill programs (serve_admit,
serve_prefill_chunk, serve_admit_finish modules, mean over chips) in the
traced window ÷ thousands of real prompt tokens admitted in it, ms."""
from benchmark import samples

PREFILL_MODULES = ("serve_admit", "serve_prefill_chunk", "serve_admit_finish")


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec.get("traced"):
        return None
    ta, tb = rec["traced"]
    tokens = sum(r["prompt_len"] for g in samples.admissions(rec, ta, tb)
                 for r in g)
    per_chip = [sum(sum(d) for d in tr["modules"].get(m, []))
                for m in PREFILL_MODULES]
    chips = max(len(tr["chips"]), 1)
    return 1e3 * sum(per_chip) / chips / (tokens / 1e3) if tokens else None
