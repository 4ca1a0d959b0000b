"""Submission-to-admission wait inside the engine, 90th percentile: the
``queue_wait_s`` of every request FIRST admitted from the window's start
to the end of its drain (``admitted`` of the step records: the value
``runbook_queue_wait_seconds`` observes, unrounded and one a request,
where ``queue_wait_p90_ms`` interpolates inside a histogram bucket over
the same requests)."""

from benchmark.layer_metrics import _steps

NAME, UNIT, LAYER = "engine_queue_wait_p90_ms", "ms", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    waits = [a[1] for s in _steps.span_steps(run) if s["t_end"] >= run["t0"]
             for a in s["admitted"]]
    return _steps.percentile_ms(waits, 90)
