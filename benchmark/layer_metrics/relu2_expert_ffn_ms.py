"""Device time a decode pass spends in the held TWO-MATRIX experts' products
(``relu(u W_up)^2 W_down``: ``ops/moe.py`` ``expert_ffn``'s second form), with
the gather that feeds them and the weighted scatter-add that combines them:
the ``conditional`` of ``held_expert_ffn`` (its fast path or its exact slow
path, whichever ran) on the "XLA Ops" line of the traced slice — the ones
whose result is ``f32[rows, hidden]`` at the decode programs' ``rows`` — over
the passes the decode programs made there (the block's ``bytes.PROGRAMS``),
every expert layer of the pattern summed. The router, the slot arithmetic
before it and the shared expert are left out. No share of a peak: the
products are XLA's batched dot over all held experts, not a kernel that
reads only the experts touched (``relu2_expert_touched_share`` is the size
of what such a kernel would read). ``expert_ffn_ms`` reads the same
conditional of the three-matrix form, in its own cell. Nothing to read in
a model whose experts are not of this form."""

import re

from benchmark.layer_metrics._common import decode_steps_traced, events_matching

NAME, UNIT, LAYER = "relu2_expert_ffn_ms", "ms", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    model = run["model"]
    if "n_experts_held" not in model or "hybrid_override_pattern" not in model:
        return None
    pattern = re.compile(rf"^%cond[\w.]* = \(?f32\[{run['llm']['max_batch_slots']},"
                         rf"{model['hidden_size']}\]\)? conditional\(")
    passes, _ = decode_steps_traced(run)
    _, seconds = events_matching(run, "ops", pattern)
    return seconds * 1e3 / passes if passes and seconds else None
