"""What the step-record readers share. ``run["steps"]`` is ``/debug/steps``
as polled through the window and its drain, one dict a step, by step
number. A step of a program with the step span carries ``t_start`` /
``t_end`` (``time.monotonic()``, the load generator's clock, so they
compare with ``run["t0"]``), ``phases``, ``program``, ``rows``,
``admitted`` and ``finished``; a program without it (the parent of the PR
that added these readers) carries none of them, and every reader here then
returns None. Records the ring lost between two polls are simply not
there: each reader reports over what it has.
"""

from __future__ import annotations

import json
from pathlib import Path

from benchmark import metrics


def load_steps(path: Path) -> list[dict]:
    """A saved ``GET /debug/steps``, as served or its ``steps`` list (what
    the tools beside a trace are given; a run holds its own in memory)."""
    steps = json.loads(Path(path).read_text())
    return steps["steps"] if isinstance(steps, dict) else steps


def span_steps(run: dict) -> list[dict]:
    """The polled steps that are spans, by step number."""
    return [s for s in run["steps"] if "t_end" in s and "phases" in s]


def in_window(run: dict, t: float | None) -> bool:
    return t is not None and run["t0"] <= t <= run["t0"] + run["seconds"]


def window_steps(run: dict) -> list[dict]:
    """Steps that began and ended inside the measured window."""
    return [s for s in span_steps(run)
            if in_window(run, s["t_start"]) and in_window(run, s["t_end"])]


def window_requests(run: dict) -> list[dict]:
    """Lifecycle records of the requests the front door received inside
    the window, wherever they retired (the drain included)."""
    return [f for s in span_steps(run) for f in s["finished"]
            if in_window(run, f["t_received"])]


def ms(seconds: float) -> float:
    return seconds * 1e3


def percentile_ms(seconds: list[float], q: float) -> float | None:
    """``metrics.percentile`` of a list of seconds, in ms; None if empty.
    An infinite entry (a request that never got that far) prints as the
    benchmark's ``INF_MS``."""
    if not seconds:
        return None
    return metrics.finite(ms(metrics.percentile(seconds, q)))
