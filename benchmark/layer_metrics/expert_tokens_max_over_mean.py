"""Expert layer: over the window's steps, the tokens of the busiest expert ÷
the mean tokens per expert (``StepRecord.expert_tokens``, live rows and
prompt positions, summed over layers): 1 is even load. None where the records
carry no such counter.

INFORMATIONAL today: with the benchmark's seeded router it reads a property of
the seed (1.35-1.47 on the chip, PR 27), which no change to the program moves
— its ``moves`` names the metric uneven load WOULD move. It is the baseline a
skewed-routing cell will be read against (PERF.md section 7); until that cell
exists, judge nothing by it."""
from benchmark import samples


def read(rec):
    total = None
    for st in samples.steps_in_window(rec):
        tokens = st.get("expert_tokens")
        if tokens:
            total = tokens if total is None else [
                a + b for a, b in zip(total, tokens)]
    if not total or not sum(total):
        return None
    return max(total) * len(total) / sum(total)
