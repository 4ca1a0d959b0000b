"""The share of a request's decoding time spent behind somebody's prompt:
the median, over the window's requests with eight tokens or more and no
preemption, of the seconds its ``rode`` books behind ``_mixed_step`` and
``_prefill_step`` over ``t_finished - t_first_token``."""

from benchmark import metrics
from benchmark.layer_metrics import _dispatches

NAME, UNIT, LAYER = "tpot_mixed_share", "%", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    life = _dispatches.ridden(run)
    if life is None:
        return None
    shares = [100.0 * _dispatches.rode_seconds(f["rode"], _dispatches.PREFILL)
              / (f["t_finished"] - f["t_first_token"])
              for f in life if f["t_finished"] > f["t_first_token"]]
    return metrics.percentile(shares, 50) if shares else None
