"""Run one cell as ``benchmark.run`` runs it and KEEP what it polled of
``/debug/steps`` (a run holds the records in memory and writes none):

    python3 -m benchmark.tools.keep_steps <out.json> --workload <name> --seed <n> --seconds <s> --trace 1

``out.json`` gets ``{"t0", "seconds", "steps"}``: the window's start on
the records' clock, its length, and every polled record by step number,
which with the run's trace (``.benchmark_run/trace``) is what
``benchmark.tools.dispatches``, ``join_steps`` and ``front_door`` take.
The run's own lines and exit code are unchanged.

A STOPGAP: it wraps two names of ``benchmark.run`` (``serving.Http.steps``,
``run.wait_started``) and raises rather than write a file if a run did not
start exactly one window through them. ``run.py`` should write what it
polled itself (ROADMAP C16, a ``benchmark`` PR's); this file then goes.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from benchmark import run, serving

# The load generator's file of the measured window (``run.run_loadgen``'s
# tag "window"); a warm-up's is named otherwise and is waited for elsewhere.
WINDOW_RECORDS = "records.window.jsonl"


def main(argv: list[str]) -> int:
    out, kept = Path(argv[1]), {}
    polled = serving.Http.steps
    window: dict = {}
    started = run.wait_started

    def steps(self, n: int = 512) -> list[dict]:
        got = polled(self, n)
        kept.update((s["step"], s) for s in got)
        return got

    def wait_started(proc, out_path) -> float:
        t0 = started(proc, out_path)
        if Path(out_path).name == WINDOW_RECORDS:
            if "t0" in window:
                raise RuntimeError("benchmark.run started a second window")
            window["t0"] = t0
        return t0

    serving.Http.steps, run.wait_started = steps, wait_started
    args = argv[2:]
    code = run.main(args)
    if "t0" not in window or not kept:
        if code:
            return code  # the run ended before its window: its own verdict
        raise RuntimeError(
            f"nothing kept: benchmark.run polled no step (it polls under "
            f"--trace 1 only) or did not wait for {WINDOW_RECORDS} through "
            "run.wait_started")
    seconds = float(args[args.index("--seconds") + 1])
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"t0": window["t0"], "seconds": seconds,
                               "steps": [kept[k] for k in sorted(kept)]}))
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv))
