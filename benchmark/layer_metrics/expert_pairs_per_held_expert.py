"""Token-expert pairs a decode pass puts on one held expert of one layer,
mean over the window's pure decode dispatches: the step records'
``experts.held`` over ``passes`` x layers x experts held, of the records
whose counts came from the block's decode programs alone. How near the
cell's expert load is to a deployment's: a chip of the deployment sees its
own batch's pairs from all of its expert-parallel group, this cell only its
own rows'."""

from benchmark.layer_metrics._experts import records

NAME, UNIT, LAYER = "expert_pairs_per_held_expert", "rows", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    decode = {name.removeprefix("jit_") for name in getattr(run["block"].bytes, "PROGRAMS", {})}
    recs = [e for e in records(run) if e["programs"] and set(e["programs"]) <= decode]
    passes = sum(e["passes"] for e in recs)
    model = run["model"]
    if not passes or "n_experts_held" not in model:
        return None
    return sum(e["held"] for e in recs) / (passes * model["num_layers"] * model["n_experts_held"])
