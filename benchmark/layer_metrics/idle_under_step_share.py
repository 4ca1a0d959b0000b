"""Of the device's idle time in the traced slice, the share that no span
of the program explains: gaps whose innermost covering span of the ENGINE
is ``engine.step`` itself (somewhere in the step, outside every phase) or
that no engine span covers. The rest lies under a named span: a phase of
the step (admit, build, a dispatch being issued, a fetch, the emit loop,
drafting) or ``engine.loop``, the way from one step to the next. Reads
the newest ``.xplane.pb`` of the run itself, for the names
``trace_reduce`` is not given (``_program_spans``). A slice with no idle
time at all has none unexplained: 0. The seconds behind the share, by
span: ``python3 -m benchmark.tools.join_steps``."""

from benchmark import serving, trace_reduce
from benchmark.layer_metrics import _program_spans

NAME, UNIT, LAYER = "idle_under_step_share", "%", "device"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    if run["trace"] is None:
        return None
    trace_dir = serving.RUN_DIR / "trace"
    xplane = trace_reduce.newest_xplane(trace_dir)
    if xplane is None:
        raise FileNotFoundError(
            f"the run reduced a trace, and {trace_dir} holds no .xplane.pb")
    idle = _program_spans.idle_by_span(xplane)
    total = sum(idle.values())
    if total <= 0:
        return 0.0
    unexplained = idle.get(_program_spans.STEP, 0.0) + idle.get("between steps", 0.0)
    return 100.0 * unexplained / total
