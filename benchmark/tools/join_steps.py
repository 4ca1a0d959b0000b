"""Check the join of the profiler's ``engine.step`` spans to the flight
records by step number: for each span that carries a ``step``, the record
of that number and the device programs that started between its ends.

    python3 -m benchmark.tools.join_steps <dir or .xplane.pb> <steps.json> [out.json]

``steps.json`` is ``GET /debug/steps`` as served, or its ``steps`` list.
Prints a summary, with what lies behind ``idle_under_step_share``: the
device's idle seconds by engine span, and the same inside the steps and
between them for each step of the slice; and on the records' clock, over
ALL the steps given, the interval from one step to the next (``t_start``
less the ``t_end`` before it, where the step numbers follow on). Exits 1
if a span's step has no record or a record's ``program`` differs from
the step programs (``spans.json`` ``step_programs``) on the device; the
small eager programs around them (a key split, a slice) are in no record.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from benchmark import metrics, serving, trace_reduce
from benchmark.layer_metrics import _program_spans, _steps


def matches(row: dict, step_programs: list[str]) -> bool:
    """``_decode_multi`` in the record, ``jit__decode_multi`` on the device."""
    if row["record_program"] is None:
        return False
    known = {"jit_" + p for p in step_programs}
    return (sorted("jit_" + p for p in row["record_program"])
            == sorted(m for m in row["device_programs"] if m in known))


def idle_per_step(idle: dict[str, float], step_spans: int) -> dict:
    """The idle seconds by span as ms a step of the slice: inside the
    steps' phases, under ``engine.step`` outside every phase, on the way
    to the next step (``engine.loop``), and under no span at all."""
    per_step = 1e3 / max(1, step_spans)
    apart = (_program_spans.STEP, _program_spans.LOOP, "between steps")
    return {
        "in_phases_ms": per_step * sum(v for k, v in idle.items()
                                       if k not in apart),
        "in_step_unnamed_ms": per_step * idle.get(_program_spans.STEP, 0.0),
        "loop_ms": per_step * idle.get(_program_spans.LOOP, 0.0),
        "no_span_ms": per_step * idle.get("between steps", 0.0)}


def between_steps_ms(steps: list[dict]) -> dict | None:
    """On the records' clock: from a step's end to the next one's start."""
    spans = sorted((s for s in steps if "t_end" in s), key=lambda s: s["step"])
    gaps = [1e3 * (b["t_start"] - a["t_end"]) for a, b in zip(spans, spans[1:])
            if b["step"] == a["step"] + 1 and a["program"] and b["program"]]
    if not gaps:
        return None
    return {"pairs": len(gaps), "p50": metrics.percentile(gaps, 50),
            "p90": metrics.percentile(gaps, 90), "max": max(gaps),
            "sum": sum(gaps)}


def main(argv: list[str]) -> int:
    path = Path(argv[1])
    xplane = path if path.is_file() else trace_reduce.newest_xplane(path)
    steps = _steps.load_steps(Path(argv[2]))
    loaded = _program_spans.load(xplane)
    rows = _program_spans.join_steps(loaded, steps)
    # The slice cuts its first and last step in two: their programs may
    # have started outside it.
    inner = rows[1:-1]
    step_programs = serving.load_json(serving.BENCH / "spans.json")["step_programs"]
    bad = [r for r in inner if not matches(r, step_programs)]
    idle = _program_spans.idle_by_span(xplane) if loaded["modules"] else {}
    out = {"file": str(xplane), "step_spans": len(rows),
           "without_record": sum(r["record_program"] is None for r in rows),
           "checked": len(inner), "mismatched": len(bad),
           "first_mismatches": bad[:5],
           "idle_by_span_s": idle,
           "idle_per_step": idle_per_step(idle, len(rows)),
           "between_steps_ms": between_steps_ms(steps),
           "rows": rows}
    if len(argv) > 3:
        Path(argv[3]).parent.mkdir(parents=True, exist_ok=True)
        Path(argv[3]).write_text(json.dumps(out, indent=1))
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))
    return 1 if bad or out["without_record"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
