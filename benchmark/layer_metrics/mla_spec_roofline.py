"""The latent attention inside ``jit__decode_spec`` — two query positions a
row, one call an attention block and round, the prediction module's block
included: its share of its roofline over the traced slice. The larger of
the bytes a call must read (the live latent cache of one block,
``kernels/mla_spec.py``; contexts counted as prompt tokens, a lower bound)
over the peak bytes per second and the operations it must do over the peak
bf16 rate, over the mean device time of a call (the page-walk ``while``, or
the call named ``mla_spec_walk``, on the "XLA Ops" line). Nothing to read in
a program that runs no rounds."""

from benchmark.kernels import mla_spec as kernel
from benchmark.layer_metrics._common import events_matching, live_in_trace

NAME, UNIT, LAYER = "mla_spec_roofline", "%", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    model = run["model"]
    if "kv_lora_rank" not in model or run["peaks"] is None:
        return None
    heads, rank, rope = (model["num_attention_heads"], model["kv_lora_rank"],
                         model["qk_rope_head_dim"])
    calls, seconds = events_matching(
        run, "ops", kernel.pattern(run["llm"]["max_batch_slots"], heads, rank))
    live = live_in_trace(run)
    if not calls or live is None:
        return None
    need = max(kernel.bytes_per_call(live[1], rank, rope) / run["peaks"]["hbm_bytes_per_s"],
               kernel.ops_per_call(live[1], heads, rank, rope) / run["peaks"]["bf16_flops"])
    return 100.0 * need / (seconds / calls)
