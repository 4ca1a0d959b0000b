"""Time to first token as the program sees it, 90th percentile: from the
HTTP handler's start (``t_received``) to the engine's fetch of the first
token (``t_first_token``), over the requests received in the window; one
that retired with no token counts as infinitely slow, as at the client.
What ``client_ttft_p90_ms`` has on top of it is the load generator's
lateness, the connection, and the way back out (``front_door_ttft_p50_ms``)."""

import math

from benchmark.layer_metrics import _steps

NAME, UNIT, LAYER = "engine_ttft_p90_ms", "ms", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    ttfts = [math.inf if f["t_first_token"] is None
             else f["t_first_token"] - f["t_received"]
             for f in _steps.window_requests(run)]
    return _steps.percentile_ms(ttfts, 90)
