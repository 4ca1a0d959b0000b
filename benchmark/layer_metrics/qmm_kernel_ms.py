"""Device time of the Pallas int8 matmul per pass over the weights: its
events on the "XLA Ops" line of the traced slice, over the passes the
decode programs made there (the block's ``bytes.PROGRAMS``; in this
benchmark's mixes only they call the kernel). No share of a peak yet:
until PR 30 the kernel read its matrix from on-chip memory in some
programs, where a copy in front of it had put it (``kernels/qmm_pallas.py``);
it now reads the stacked array in place, and a roofline by shape is a
later PR's (PERF.md section 7)."""

from benchmark.kernels import qmm_pallas
from benchmark.layer_metrics._common import decode_steps_traced, events_matching

NAME, UNIT, LAYER = "qmm_kernel_ms", "ms", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    steps, _ = decode_steps_traced(run)
    _, seconds = events_matching(run, "ops", qmm_pallas.PATTERN)
    return seconds * 1e3 / steps if steps and seconds else None
