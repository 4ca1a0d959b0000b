"""Sequences preempted for want of KV pages during the window."""

from benchmark.layer_metrics._common import delta

NAME, UNIT, LAYER = "kv_preemptions", "count", "KV manager"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    return delta(run, "preemptions")
