"""Device time of the prefill and mixed programs per 1,000 prompt tokens
computed, over the traced slice: the programs' time on the "XLA Modules"
line over the ``prefill_tokens`` counter's delta between the slice's two
``/healthz`` readings."""

import re

from benchmark.layer_metrics._common import events_matching, traced_delta

NAME, UNIT, LAYER = "prefill_dev_ms_per_ktok", "ms", "model step"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"
PATTERN = re.compile(r"^jit__(prefill|mixed)_step$")


def read(run: dict):
    tokens = traced_delta(run, "prefill_tokens")
    _, seconds = events_matching(run, "modules", PATTERN)
    if not tokens or not seconds:
        return None
    return seconds * 1e3 / (tokens / 1e3)
