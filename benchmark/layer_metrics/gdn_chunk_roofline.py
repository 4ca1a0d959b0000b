"""The chunked gated delta rule in the prefill and mixed programs of the
traced slice: its share of its roofline — the larger of its operations over
the peak bf16 rate and its bytes over the peak bytes per second
(``kernels/gdn_chunk.py``), for the prompt tokens a program of the slice
prefilled on average, over the mean device time of a call (one
linear-attention layer of one such program). The rule runs its products in
float32 at the highest precision, several bf16 passes each, and a mixed
step lays every prefill row out as a run of the whole chunk budget: both
keep the share low, and both are the next kernel's to take. Nothing to read
in a model with no recurrent state."""

from benchmark.kernels import gdn_chunk as kernel
from benchmark.layer_metrics._common import matching, traced_delta

NAME, UNIT, LAYER = "gdn_chunk_roofline", "%", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"
PROGRAMS = ("jit__prefill_step", "jit__mixed_step")


def read(run: dict):
    model = run["model"]
    if "linear_num_value_heads" not in model or run["peaks"] is None:
        return None
    heads, dk, dv = (model["linear_num_value_heads"], model["linear_key_head_dim"],
                     model["linear_value_head_dim"])
    sizes = (run["llm"]["max_batch_slots"], heads, dk, dv)
    seconds = sum(s for name, _, s in matching(run, "ops", kernel.pattern(*sizes))
                  if kernel.is_event(name, *sizes))
    modules = (run["trace"] or {"modules": {}})["modules"]
    programs = sum(modules[p]["count"] for p in PROGRAMS if p in modules)
    tokens = traced_delta(run, "prefill_tokens")
    if not seconds or not programs or not tokens:
        return None
    linear = model["num_hidden_layers"] - model["num_hidden_layers"] // model["full_attention_interval"]
    per_program = tokens / programs
    conv = 2 * model["linear_num_key_heads"] * dk + heads * dv
    need = max(kernel.ops_per_call(per_program, heads, dk, dv) / run["peaks"]["bf16_flops"],
               kernel.bytes_per_call(per_program, 1.0, heads, dk, dv, conv)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need / (seconds / (programs * linear))
