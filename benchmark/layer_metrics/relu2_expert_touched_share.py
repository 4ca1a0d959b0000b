"""Of the held two-matrix experts of an expert layer, the share a decode
pass touches (at least one token-expert pair of a live row), mean over the
window's pure decode dispatches: the step records' ``experts.touched`` over
``passes`` x EXPERT layers of the pattern x experts held, of the records
whose counts came from the block's decode programs alone. The held experts'
matrices are read whole whatever the routing (``ops/moe.py``
``held_expert_ffn``: one batched product over all of them); this is the size
of what a grouped product over the experts touched would read instead.
``expert_touched_share`` divides by every layer of a model whose layers all
hold experts; here 23 of 52 do. Nothing to read in a model without the
pattern."""

from benchmark.layer_metrics._experts import records

NAME, UNIT, LAYER = "relu2_expert_touched_share", "%", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    model = run["model"]
    if "n_experts_held" not in model or "hybrid_override_pattern" not in model:
        return None
    decode = {name.removeprefix("jit_") for name in getattr(run["block"].bytes, "PROGRAMS", {})}
    recs = [e for e in records(run) if e["programs"] and set(e["programs"]) <= decode]
    passes = sum(e["passes"] for e in recs)
    layers = model["hybrid_override_pattern"].count("E")
    if not passes or not layers:
        return None
    return 100.0 * sum(e["touched"] for e in recs) / (passes * layers * model["n_experts_held"])
