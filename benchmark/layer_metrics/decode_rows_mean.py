"""Mean rows (sequences) per decode or mixed dispatch over the window,
from the flight recorder (``/debug/steps``, polled during the window
because it keeps 512 steps)."""

NAME, UNIT, LAYER = "decode_rows_mean", "rows", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    rows = [s["batch"] for s in run["steps"]
            if s["kind"] in ("decode", "mixed", "decode_spec") and s["batch"] > 0]
    return sum(rows) / len(rows) if rows else None
