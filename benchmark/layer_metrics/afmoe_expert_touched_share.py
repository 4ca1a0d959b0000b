"""Of the held experts of an expert layer of a model with window layers, the
share a decode pass touches (at least one token-expert pair of a live row),
mean over the window's pure decode dispatches: the step records'
``experts.touched`` over ``passes`` x expert layers x experts held, of the
records whose counts came from the block's decode programs alone. The held
experts' matrices are read whole whatever the routing (``ops/moe.py``
``held_expert_ffn``); this is the size of what a grouped product over the
experts touched would read instead: at six rows a pass, 8 picks of 128 and 16
held, about a third. Nothing to read in another model."""

from benchmark.layer_metrics._experts import records

NAME, UNIT, LAYER = "afmoe_expert_touched_share", "%", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    model = run["model"]
    if "n_experts_held" not in model or "sliding_window" not in model:
        return None
    decode = {name.removeprefix("jit_") for name in getattr(run["block"].bytes, "PROGRAMS", {})}
    recs = [e for e in records(run) if e["programs"] and set(e["programs"]) <= decode]
    passes = sum(e["passes"] for e in recs)
    layers = model["num_hidden_layers"] - model["num_dense_layers"]
    if not passes or not layers:
        return None
    return 100.0 * sum(e["touched"] for e in recs) / (passes * layers * model["n_experts_held"])
