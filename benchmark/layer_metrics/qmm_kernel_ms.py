"""Device time of the Pallas int8 matmul per pass over the weights: its
events on the "XLA Ops" line of the traced slice, over the passes the
decode programs made there (``kernels/decode_step.PROGRAMS``; in this
benchmark's mixes only they call the kernel). No share of a peak: in some
programs the kernel reads its matrix from on-chip memory, where the copy
beside it (``qmm_feed_copy_ms``) has put it (``kernels/qmm_pallas.py``)."""

from benchmark.kernels import qmm_pallas
from benchmark.layer_metrics._common import decode_steps_traced, events_matching

NAME, UNIT, LAYER = "qmm_kernel_ms", "ms", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    steps, _ = decode_steps_traced(run)
    _, seconds = events_matching(run, "ops", qmm_pallas.PATTERN)
    return seconds * 1e3 / steps if steps and seconds else None
