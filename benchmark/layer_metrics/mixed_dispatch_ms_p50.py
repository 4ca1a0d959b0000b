"""What one mixed step takes, a CALL: the median, over the ``_mixed_step``
dispatches made ready inside the window, of the dispatch's device-side
interval on the host's clock (``_dispatches``). ``prefill_dev_ms_per_ktok``
divides the slice's seconds by its prompt tokens and moves with the steps
a slice happens to hold; a call's time does not. The host's view, an
upper bound, as ``decode_pass_ms_p50`` says of itself."""

from benchmark.layer_metrics import _dispatches

NAME, UNIT, LAYER = "mixed_dispatch_ms_p50", "ms", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    rows = _dispatches.window_intervals(run)
    if rows is None:
        return None
    return _dispatches.median_ms([seconds for d, seconds, _ in rows
                                  if d["program"] == "_mixed_step"])
