"""A decode step's share of the HBM roofline: the bytes one step must read
(the run's block says how many: ``bytes.step_bytes``) over the peak bytes
per second, over the device time of one decode step — the block's pure
decode programs on the trace's "XLA Modules" line, divided by the steps
each run makes. Bound by memory: at 16 rows the step's matmuls are far
under the MXU's peak."""

from benchmark.layer_metrics._common import decode_steps_traced, live_in_trace

NAME, UNIT, LAYER = "decode_hbm_mfu", "%", "model step"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    step_bytes = getattr(run["block"].bytes, "step_bytes", None)
    steps, seconds = decode_steps_traced(run)
    live = live_in_trace(run)
    if step_bytes is None or not steps or live is None or run["peaks"] is None:
        return None
    need = step_bytes(run["model"], live[1]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / (seconds / steps)
