"""Share of the window's admitted prompt tokens that the prefix cache
served: cached / (cached + computed), from the ``/healthz`` counters."""

from benchmark.layer_metrics._common import delta

NAME, UNIT, LAYER = "prefix_hit_share", "%", "KV manager"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    cached, computed = delta(run, "cached_prefix_tokens"), delta(run, "prefill_tokens")
    total = cached + computed
    return 100.0 * cached / total if total > 0 else None
