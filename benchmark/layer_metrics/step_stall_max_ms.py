"""The longest a decoding batch waited for its next step: the largest
``t_end``-to-``t_end`` interval between two steps next to each other by
step number that both dispatched decode rows (``rows`` > 0), inside the
window. A steady batch reads one dispatch's device time; a stall (a
compile, a blocked fetch, a starved host) reads as itself, and the later
record's ``phases`` and ``compile_s`` say which. A pair with a lost record
between them is not a pair."""

from benchmark.layer_metrics import _steps

NAME, UNIT, LAYER = "step_stall_max_ms", "ms", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    steps = _steps.window_steps(run)
    gaps = [b["t_end"] - a["t_end"] for a, b in zip(steps, steps[1:])
            if b["step"] == a["step"] + 1 and a["rows"] > 0 and b["rows"] > 0]
    return _steps.ms(max(gaps)) if gaps else None
