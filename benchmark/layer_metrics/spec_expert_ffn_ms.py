"""Device time a speculative ROUND spends in the held experts' products,
with the gather that feeds them and the weighted scatter-add that combines
them: the ``conditional`` of ``ops/moe.py`` ``held_expert_ffn`` (its fast
path or its exact slow path, whichever ran) on the "XLA Ops" line of the
traced slice — the ones whose result is ``f32[2 x slots, hidden]``, a
round's two positions a row, in the trunk's expert layers and in the
module's — over the rounds ``jit__decode_spec`` made there (the block's
``bytes.PROGRAMS``). ``expert_ffn_ms`` reads the one-position passes of
``_decode_multi``, which a model that drafts for itself never runs for a
greedy row; this is the same quantity where a pass is a round. The router
and the slot arithmetic before it are left out. No share of a peak: the
products are XLA's batched dot over all held experts, not a kernel that
reads only the experts touched. Nothing to read in a model without the
module."""

import re

from benchmark.layer_metrics._common import decode_steps_traced, events_matching

NAME, UNIT, LAYER = "spec_expert_ffn_ms", "ms", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    model = run["model"]
    if "n_experts_held" not in model or not model.get("num_nextn_predict_layers"):
        return None
    pattern = re.compile(rf"^%cond[\w.]* = \(?f32\[{2 * run['llm']['max_batch_slots']},"
                         rf"{model['hidden_size']}\]\)? conditional\(")
    rounds, _ = decode_steps_traced(run)
    _, seconds = events_matching(run, "ops", pattern)
    return seconds * 1e3 / rounds if rounds and seconds else None
