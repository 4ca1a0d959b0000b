"""The window layers' decode walk in the traced slice: its share of the HBM
roofline — the keys and values inside the window of a decode pass's rows
(``kernels/swa_decode.py``; the rows from the step records' ``window.rows_seen``,
a dispatch weighed by its passes) over the peak bytes per second, over the
mean device time of a call (``%swa_decode_walk`` on the "XLA Ops" line: one
sliding layer of one pass). The walk fetches whole groups of pages, up to a
group past the window, and a grid step a slot whether the slot is live: both
keep the share low. Nothing to read in a model with no window."""

from benchmark.kernels import swa_decode as kernel
from benchmark.layer_metrics import _window
from benchmark.layer_metrics._common import events_matching

NAME, UNIT, LAYER = "swa_decode_roofline", "%", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    model = run["model"]
    if "sliding_window" not in model or run["peaks"] is None:
        return None
    calls, seconds = events_matching(run, "ops", kernel.EVENT)
    recs = _window.traced(run, _window.DECODE)
    passes = sum(s["k"] or 1 for s in recs)
    if not calls or not seconds or not passes:
        return None
    rows = sum((s["k"] or 1) * s["window"]["rows_seen"] for s in recs) / passes
    need = (kernel.bytes_per_call(rows, model["num_key_value_heads"], model["head_dim"])
            / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need / (seconds / calls)
