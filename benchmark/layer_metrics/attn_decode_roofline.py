"""The decode programs' Pallas paged attention: its share of its (memory)
roofline over the traced slice — the live keys and values of the pass's
rows (``kernels/paged_attention_decode.py``; contexts counted as prompt
tokens, a lower bound) over the peak bytes per second, per call, over the
kernels' mean device time per call on the "XLA Ops" line."""

from benchmark.kernels import paged_attention_decode as kernel
from benchmark.layer_metrics._common import events_matching, live_in_trace

NAME, UNIT, LAYER = "attn_decode_roofline", "%", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    calls, seconds = events_matching(
        run, "ops", kernel.pattern(run["llm"]["max_batch_slots"]))
    live = live_in_trace(run)
    if not calls or live is None or run["peaks"] is None:
        return None
    model = run["model"]
    need = kernel.bytes_per_call(live[1], model["n_kv_heads"],
                                 model["dim"] // model["n_heads"])
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (seconds / calls)
