"""Of the held experts of an expert layer, the share a speculative ROUND
touches (at least one token-expert pair of a live row's two positions),
mean over the window's ``_decode_spec`` dispatches: the step records'
``experts.touched`` over ``passes`` (the rounds) x expert layers (the
module's included: the block's ``bytes.stacks``) x experts held, of the
records whose counts came from ``_decode_spec`` alone. The held experts'
matrices are read whole whatever the routing (``ops/moe.py``
``held_expert_ffn``); this is the size of what a grouped product over the
experts touched would read instead, where ``expert_touched_share`` reads
it for one-position passes. Nothing to read in a model without the module."""

from benchmark.layer_metrics._experts import records

NAME, UNIT, LAYER = "spec_expert_touched_share", "%", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    model = run["model"]
    if "n_experts_held" not in model or not model.get("num_nextn_predict_layers"):
        return None
    recs = [e for e in records(run) if e["programs"] == ["_decode_spec"]]
    rounds = sum(e["passes"] for e in recs)
    if not rounds:
        return None
    expert_layers = run["block"].bytes.stacks(model)[1]
    return 100.0 * sum(e["touched"] for e in recs) / (rounds * expert_layers * model["n_experts_held"])
