"""Submission-to-admission wait inside the engine: the 90th percentile of
the window's requests, from the bucket deltas of the server's
``runbook_queue_wait_seconds`` histogram — coarse (bucket bounds 5 ms,
10, 25, 50, 100, 250…), linear inside a bucket, until the tracing issue
gives a span."""

from benchmark import metrics

NAME, UNIT, LAYER = "queue_wait_p90_ms", "ms", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"
HISTOGRAM = "runbook_queue_wait_seconds"


def read(run: dict):
    q = metrics.histogram_quantile(
        metrics.parse_histogram(run["hist_before"], HISTOGRAM),
        metrics.parse_histogram(run["hist_after"], HISTOGRAM), 0.9)
    return None if q is None else q * 1e3
