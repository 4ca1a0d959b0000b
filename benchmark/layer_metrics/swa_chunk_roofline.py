"""The window layers' chunk walk in the prefill and mixed programs of the
traced slice: its share of its roofline — the larger of its operations over
the peak bf16 rate and its bytes over the peak bytes per second
(``kernels/swa_chunk.py``), for the chunks and decode rows a program of the
slice carried on average (the step records' ``window.chunk_pairs``,
``chunk_rows_seen`` and, of a mixed step, ``rows_seen``), over the mean
device time of a call (``%swa_chunk_walk`` on the "XLA Ops" line: one
sliding layer of one such program). A mixed step walks its chunk a block of
eight queries a grid step, each fetching its own copy of its window: that
keeps the share low, and is the next kernel's to take. Nothing to read in a
model with no window."""

from benchmark.kernels import swa_chunk as kernel
from benchmark.layer_metrics import _window
from benchmark.layer_metrics._common import events_matching

NAME, UNIT, LAYER = "swa_chunk_roofline", "%", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    model = run["model"]
    if "sliding_window" not in model or run["peaks"] is None:
        return None
    calls, seconds = events_matching(run, "ops", kernel.EVENT)
    recs = _window.traced(run, _window.CHUNK)
    if not calls or not seconds or not recs:
        return None
    mixed = [s["window"]["rows_seen"] if "_mixed_step" in s["program"] else 0 for s in recs]
    pairs = sum(s["window"]["chunk_pairs"] + m for s, m in zip(recs, mixed)) / len(recs)
    rows = sum(s["window"]["chunk_rows_seen"] + m for s, m in zip(recs, mixed)) / len(recs)
    heads, kv, hd = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    need = max(kernel.ops_per_call(pairs, heads, hd) / run["peaks"]["bf16_flops"],
               kernel.bytes_per_call(rows, kv, hd) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need / (seconds / calls)
