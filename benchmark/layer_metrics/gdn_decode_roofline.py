"""The gated delta rule's recurrent step in the traced slice: its share of
the HBM roofline — the bytes a call must move for the slice's live rows
(state read and written, convolution tail, a row's inputs:
``kernels/gdn_step.py``) over the peak bytes per second, over the mean
device time of a call. A call is one linear-attention layer of one forward
pass: the decode programs' passes and the mixed steps, times the linear
layers. The step as written reads and writes EVERY slot's state, live or
free, so the share cannot pass live rows over slots. Nothing to read in a
model with no recurrent state, or from a program that has none."""

from benchmark.kernels import gdn_step as kernel
from benchmark.layer_metrics._common import decode_steps_traced, live_in_trace, matching

NAME, UNIT, LAYER = "gdn_decode_roofline", "%", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    model = run["model"]
    if "linear_num_value_heads" not in model or run["peaks"] is None:
        return None
    heads, dk, dv = (model["linear_num_value_heads"], model["linear_key_head_dim"],
                     model["linear_value_head_dim"])
    sizes = (run["llm"]["max_batch_slots"], heads, dk, dv)
    seconds = sum(s for name, _, s in matching(run, "ops", kernel.pattern(*sizes))
                  if kernel.is_event(name, *sizes))
    passes, _ = decode_steps_traced(run)
    mixed = ((run["trace"] or {"modules": {}})["modules"].get("jit__mixed_step")
             or {"count": 0})["count"]
    linear = model["num_hidden_layers"] - model["num_hidden_layers"] // model["full_attention_interval"]
    calls = (passes + mixed) * linear
    live = live_in_trace(run)
    if not seconds or not calls or live is None:
        return None
    conv = 2 * model["linear_num_key_heads"] * dk + heads * dv
    need = (kernel.bytes_per_call(live[0], heads, dk, dv, conv, model["linear_conv_kernel_dim"])
            / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need / (seconds / calls)
