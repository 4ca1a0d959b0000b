"""What the host spends on a step when it is not waiting for the device:
the median, over the window's steps that dispatched a program, of
``wall_s`` less the ``fetch`` phase (the blocking device-to-host reads)."""

from benchmark.layer_metrics import _steps

NAME, UNIT, LAYER = "step_host_ms_p50", "ms", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_span"


def read(run: dict):
    host = [s["wall_s"] - s["phases"]["fetch"]
            for s in _steps.window_steps(run) if s["program"]]
    return _steps.percentile_ms(host, 50)
