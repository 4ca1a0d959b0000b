"""The share of the router's picks that fell on identity (zero-computation)
experts, over the window: the step records' ``experts.zero`` over all
pairs. Uniform routing gives identity experts / outputs (256 / 768 = 33%
for LongCat-Flash); a router that leans on them costs less per token."""

from benchmark.layer_metrics._experts import records

NAME, UNIT, LAYER = "zero_expert_pick_share", "%", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    recs = records(run)
    pairs = sum(e["held"] + e["zero"] + e["absent"] for e in recs)
    return 100.0 * sum(e["zero"] for e in recs) / pairs if pairs else None
