"""The share of the window's expert-layer calls (forward passes x layers)
whose dispatch overflowed a held expert's slots and took the exact slow
path of ``ops/moe.py`` ``held_expert_ffn`` — every token through every held
expert: the step records' ``experts.overflow`` over ``passes`` x layers.
The slots are eight times a held expert's load under even routing, so
anything over 0 says the router piles tokens on one expert; ``expert_ffn_ms``
and the prefill programs' time then hold the slow path's product."""

from benchmark.layer_metrics._experts import records

NAME, UNIT, LAYER = "expert_overflow_share", "%", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    recs = [e for e in records(run) if "overflow" in e]
    calls = sum(e["passes"] for e in recs) * run["model"].get("num_layers", 0)
    return 100.0 * sum(e["overflow"] for e in recs) / calls if calls else None
