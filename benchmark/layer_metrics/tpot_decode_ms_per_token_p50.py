"""The floor of a row's time a token: the median, over the window's
requests with eight tokens or more and no preemption, of the seconds the
request's ``rode`` books behind the pure decode programs over the tokens
those gave it. What ``tpot_p50_ms`` holds above this is the time behind
mixed and prefill steps (``tpot_mixed_share``) and ``between``."""

from benchmark.layer_metrics import _dispatches

NAME, UNIT, LAYER = "tpot_decode_ms_per_token_p50", "ms", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    life = _dispatches.ridden(run)
    if life is None:
        return None
    per_token = [_dispatches.rode_seconds(f["rode"], _dispatches.DECODE) / tokens
                 for f in life
                 if (tokens := _dispatches.rode_tokens(f["rode"], _dispatches.DECODE))]
    return _dispatches.median_ms(per_token)
