"""Client-side time to the first streamed token, from the instant the
request was DUE: 90th percentile over the window's requests, a failed one
infinite. Per layer and not end to end: with every seed's work the same,
the engine's step cadence locks to the fixed arrivals in one of a few
phases, and the tail reads at levels 8% apart (PERF.md section 2)."""

from benchmark import metrics

NAME, UNIT, LAYER = "client_ttft_p90_ms", "ms", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "host_clock"


def read(run: dict):
    ttfts = [metrics.ttft_ms(r) for r in run["reqs"]]
    return metrics.finite(metrics.percentile(ttfts, 90)) if ttfts else None
