"""Device time a decode pass spends in the held experts' products of a model
with window layers (three-matrix SwiGLU experts, ``models/afmoe.py``), with
the gather that feeds them and the weighted scatter-add that combines them:
the ``conditional`` of ``held_expert_ffn`` (its fast path or its exact slow
path, whichever ran) on the "XLA Ops" line of the traced slice — the ones
whose result is ``f32[rows, hidden]`` at the decode programs' ``rows`` — over
the passes the decode programs made there (the block's ``bytes.PROGRAMS``),
all 30 expert layers summed. The router, the slot arithmetic before it and
the shared expert are left out. No share of a peak: the products are XLA's
batched dot over all 16 held experts, read whole whatever the routing
(``afmoe_expert_touched_share`` is the size of what a grouped product would
read). ``expert_ffn_ms`` and ``relu2_expert_ffn_ms`` read the same
conditional in their own cells. Nothing to read in another model."""

import re

from benchmark.layer_metrics._common import decode_steps_traced, events_matching

NAME, UNIT, LAYER = "afmoe_expert_ffn_ms", "ms", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    model = run["model"]
    if "n_experts_held" not in model or "sliding_window" not in model:
        return None
    pattern = re.compile(rf"^%cond[\w.]* = \(?f32\[{run['llm']['max_batch_slots']},"
                         rf"{model['hidden_size']}\]\)? conditional\(")
    passes, _ = decode_steps_traced(run)
    _, seconds = events_matching(run, "ops", pattern)
    return seconds * 1e3 / passes if passes and seconds else None
