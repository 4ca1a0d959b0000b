"""Of the sampler's calls by dispatched programs, the share that had a row
whose temperature is over 0 and so sorted the vocabulary: the growth of
``sampler_sorted_calls`` over that of ``sampler_calls`` (``/healthz``)
across the window. The sorted path (top-p / top-k, the soft-max, the draw)
runs behind a device-side condition inside the one program; a call whose
rows are all greedy takes the argmax. Every cell's traffic is temperature
0, so this reads 0 in each: it is reported so that a cell with sampling
rows, or a pad row that carries a temperature, shows. Nothing to read where
no call was counted (a program without the counters)."""

from benchmark.layer_metrics._common import delta

NAME, UNIT, LAYER = "sampler_sorted_call_share", "%", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    calls = delta(run, "sampler_calls")
    return 100.0 * delta(run, "sampler_sorted_calls") / calls if calls else None
