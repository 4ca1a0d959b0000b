"""A decode pass (or round) as the whole window's rows and contexts make
it: the median, over the pure decode dispatches made ready inside the
window, of the dispatch's device-side interval on the host's clock
(``_dispatches``) over its ``k``. The slice's ``decode_hbm_mfu``
holds the first seconds' pass against its bytes; this is every pass of
the window, batch grown and contexts long. The interval is the HOST's
view, an upper bound: under the overlapped pipeline device idle time
behind a late issue lies inside the dispatch before it, so a slower host
lengthens this "pass" too (``_dispatches``' docstring; ``unwaited`` names
the dispatches it can have touched)."""

from benchmark.layer_metrics import _dispatches

NAME, UNIT, LAYER = "decode_pass_ms_p50", "ms", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    rows = _dispatches.window_intervals(run)
    if rows is None:
        return None
    return _dispatches.median_ms([seconds / d["k"] for d, seconds, _ in rows
                                  if d["program"] in _dispatches.DECODE])
