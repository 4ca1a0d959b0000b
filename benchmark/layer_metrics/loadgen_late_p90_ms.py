"""How late the load generator sent: send time minus due time, 90th
percentile over the window's requests. A starved generator must not be
read as a fast server."""

from benchmark import metrics

NAME, UNIT, LAYER = "loadgen_late_p90_ms", "ms", "load generator"
MOVES, SOURCE = "tpot_p50_ms", "host_clock"


def read(run: dict):
    late = [(r["sent"] - r["due"]) * 1e3 for r in run["reqs"] if r["sent"] is not None]
    return metrics.percentile(late, 90) if late else None
