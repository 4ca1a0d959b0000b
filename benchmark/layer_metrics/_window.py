"""What the window readers share: the step records' ``window`` field
(``engine/flight_recorder.py`` ``OPTIONAL_STEP_FIELDS``) — a model with
sliding-window layers reports in every step, over the sequences that live,
the token rows its window pool holds (``rows_kept``) beside their contexts
(``rows_context``), the rows given back, and the work its walks must do
(``rows_seen``, ``chunk_pairs``, ``chunk_rows_seen``). A program without the
field (a model with no window; the parent of the PR that added these
readers) has none, and every reader here then returns None."""

from __future__ import annotations

DECODE = ("_decode_step", "_decode_multi")
CHUNK = ("_mixed_step", "_prefill_step")


def records(run: dict, programs: tuple, t_from: float, t_to: float) -> list[dict]:
    """The steps with a ``window`` that dispatched one of ``programs`` and
    no other kind, begun in ``[t_from, t_to]`` (``time.monotonic()``)."""
    return [s for s in run["steps"]
            if s.get("window") and s.get("program") and "t_start" in s
            and t_from <= s["t_start"] <= t_to
            and any(p in programs for p in s["program"])
            and all(p in programs for p in s["program"])]


def traced(run: dict, programs: tuple) -> list[dict]:
    t = run["traced"]
    if "t_stop" not in t:
        return []
    return records(run, programs, t["t_start"], t["t_stop"])
