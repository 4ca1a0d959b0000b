"""A decode step's share of the HBM roofline: the bytes one step must read
(``kernels/decode_step.py``) over the peak bytes per second, over the
device time of one decode step — the pure decode programs on the trace's
"XLA Modules" line, divided by the steps each run makes. Bound by memory:
at 16 rows the step's matmuls are far under the MXU's peak."""

from benchmark.kernels import decode_step
from benchmark.layer_metrics._common import decode_steps_traced, live_in_trace

NAME, UNIT, LAYER = "decode_hbm_roofline", "%", "model step"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    steps, seconds = decode_steps_traced(run)
    live = live_in_trace(run)
    if not steps or live is None or run["peaks"] is None:
        return None
    need = decode_step.step_bytes(run["model"], live[1]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / (seconds / steps)
