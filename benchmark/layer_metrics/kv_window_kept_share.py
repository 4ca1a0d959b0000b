"""Of the token rows the live sequences' contexts would take in the window
layers if every position were kept, the share the KV manager keeps: the sum
of the step records' ``window.rows_kept`` over the sum of their
``rows_context``, over the measured window's pure decode steps. A sequence
keeps the window, the chunk being written and a page's slack at either end
(``engine/kv_cache.py`` ``WindowSpec``), so the share falls as contexts
grow: 100 for contexts under the window, or for a manager that gives
nothing back. Nothing to read in a model with no window."""

from benchmark.layer_metrics import _window

NAME, UNIT, LAYER = "kv_window_kept_share", "%", "KV manager"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    recs = _window.records(run, _window.DECODE, run["t0"], run["t0"] + run["seconds"])
    context = sum(s["window"]["rows_context"] for s in recs)
    return 100.0 * sum(s["window"]["rows_kept"] for s in recs) / context if context else None
