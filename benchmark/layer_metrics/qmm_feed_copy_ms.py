"""Device time, per pass over the weights, of the copies that feed the
Pallas int8 matmul: one layer's int8 matrix sliced out of the stacked
array before each call ("XLA Ops" line of the traced slice, over the
passes the decode programs made there). The kernel's own time
(``qmm_kernel_ms``) comes after it, not under it."""

from benchmark.kernels import qmm_pallas
from benchmark.layer_metrics._common import decode_steps_traced, events_matching

NAME, UNIT, LAYER = "qmm_feed_copy_ms", "ms", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    steps, _ = decode_steps_traced(run)
    _, seconds = events_matching(run, "ops", qmm_pallas.FEED)
    return seconds * 1e3 / steps if steps and seconds else None
