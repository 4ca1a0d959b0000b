"""What time to first token spends in the process OUTSIDE the engine,
median over the window's streamed requests: on the way in, HTTP handler
start to ``EngineCore.submit`` (body read, chat template, tokenise, tenant
admission, the hop to the engine's loop and its wait for the step lock:
``t_enqueued - t_received``); on the way out, the engine's first-token
fetch to the first content chunk flushed to the socket (the hop back, the
SSE write: ``t_first_write - t_first_token``)."""

from benchmark.layer_metrics import _steps

NAME, UNIT, LAYER = "front_door_ttft_p50_ms", "ms", "front door"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    outside = [(f["t_enqueued"] - f["t_received"])
               + (f["t_first_write"] - f["t_first_token"])
               for f in _steps.window_requests(run)
               if f["t_first_write"] is not None
               and f["t_first_token"] is not None]
    return _steps.percentile_ms(outside, 50)
