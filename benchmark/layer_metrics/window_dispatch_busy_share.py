"""The share of the window with a dispatch OUTSTANDING, as the host saw
it: the seconds inside some dispatch's interval (``_dispatches``; each cut
to the window, the one in flight at its end included) over the window's.
The rest is ``between``: the engine had handed the device nothing. A
dispatch whose predecessor's record was lost has no interval and counts
as ``between``.

A HOST metric (admission and batching), not the device's busy share:
under the overlapped pipeline ``between`` is 0 by construction, so device
idle time behind a late issue lies inside the dispatch before it and reads
as busy here (``_dispatches``' docstring). It falls when the pipeline
DRAINS (an empty engine, synchronous rounds, first-token fetches); it
cannot fall because a host was slow to issue the next dispatch. The
device's idle share is the slice's ``device_idle_share``, from the trace."""

from benchmark.layer_metrics import _dispatches

NAME, UNIT, LAYER = "window_dispatch_busy_share", "%", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    under = _dispatches.seconds_under_a_dispatch(run)
    return None if under is None else 100.0 * under / run["seconds"]
