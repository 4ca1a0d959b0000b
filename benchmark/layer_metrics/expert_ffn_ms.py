"""Device time a decode pass spends in the held experts' products, with the
gather that feeds them and the weighted scatter-add that combines them:
the ``conditional`` of ``ops/moe.py`` ``held_expert_ffn`` (its fast path or
its exact slow path, whichever ran) on the "XLA Ops" line of the traced
slice — the one whose result is ``f32[rows, hidden]`` at the decode
programs' ``rows`` — over the passes the decode programs made there (the
block's ``bytes.PROGRAMS``). The router and the slot arithmetic before it
are left out. No share of a peak: the products are XLA's batched dot over
all held experts, not a kernel that reads only the experts touched."""

import re

from benchmark.layer_metrics._common import decode_steps_traced, events_matching

NAME, UNIT, LAYER = "expert_ffn_ms", "ms", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    model = run["model"]
    if "n_experts_held" not in model:
        return None
    pattern = re.compile(rf"^%conditional[.\d]* = \(?f32\[{run['llm']['max_batch_slots']},"
                         rf"{model['hidden_size']}\]")
    steps, _ = decode_steps_traced(run)
    _, seconds = events_matching(run, "ops", pattern)
    return seconds * 1e3 / steps if steps and seconds else None
