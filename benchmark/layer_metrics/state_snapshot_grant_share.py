"""Of the prompt tokens the window's admissions matched by page hash, the
share they were GRANTED: a model with recurrent layers may use a page hit
only up to a boundary whose state snapshot is at hand
(``engine/kv_cache.py`` ``StateSnapshots``). From the ``/healthz`` counters
``state_hash_tokens_granted`` over ``state_hash_tokens_matched``; a program
without them (a model whose state is all pages; the parent of the PR that
added them) has nothing to read."""

from benchmark.layer_metrics._common import delta

NAME, UNIT, LAYER = "state_snapshot_grant_share", "%", "KV manager"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    if "state_hash_tokens_matched" not in run["health_after"]["metrics"]:
        return None
    matched = delta(run, "state_hash_tokens_matched")
    return 100.0 * delta(run, "state_hash_tokens_granted") / matched if matched > 0 else None
