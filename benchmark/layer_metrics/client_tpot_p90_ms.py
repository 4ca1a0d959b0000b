"""The tail of the time per output token: per request (last token − first
token) / (tokens − 1), 90th percentile over the window's requests with 8
tokens or more. Per layer and not end to end: seven requests decode side
by side, so ONE stall of the host lifts eight of a window's 66 requests
over the 90th percentile (read: 100.7 ms against 82.0; PERF.md section 2)."""

from benchmark import metrics

NAME, UNIT, LAYER = "client_tpot_p90_ms", "ms", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "host_clock"


def read(run: dict):
    tpots = [v for v in map(metrics.tpot_ms, run["reqs"]) if v is not None]
    return metrics.finite(metrics.percentile(tpots, 90)) if tpots else None
