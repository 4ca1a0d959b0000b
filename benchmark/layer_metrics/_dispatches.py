"""The dispatch as a span, read from ``/debug/steps``: what the readers of
the ``dispatches`` field and of a lifecycle record's ``rode`` share, and
the join of a dispatch to the device's own event of it in a ``.xplane.pb``.
This file imports nothing of the program's (a parent that lacks the field
must still run): the definition below is a COPY of
``engine/flight_recorder.py`` ``DispatchLedger``'s, and
``tests/benchmark/test_dispatch_metrics.py`` holds the two to the same
seconds.

An entry of a step record's ``dispatches`` is one dispatch of a step
program: ``n`` (the engine's running number), ``program``, ``k`` (its
passes or rounds), ``rows``, ``kv_pages_live``, ``prefill_tokens``,
``t_issued`` (the jitted call returned), ``t_ready`` (the first
``device_get`` of its result returned; both ``time.monotonic()``, the load
generator's clock) and ``tokens``. The device runs an engine's dispatches
in the order issued, so dispatch ``n``'s DEVICE-SIDE INTERVAL is

    t_ready(n) - max(t_issued(n), t_ready(n - 1))

and what lies before ``t_issued(n)``, if anything, is ``between``: the
device had nothing of this engine's. A dispatch whose predecessor's record
was lost between two polls has no interval, and every reader leaves it
out. A record without the field (a program from before it) gives None.

Both stamps are the HOST's, so the interval is what the host saw of the
dispatch: an upper bound of the device's time in it, not the device's own.
Under the overlapped pipeline a step issues ``n + 1`` and only then
fetches ``n``, so ``t_ready(n) > t_issued(n + 1)`` always and ``between``
is 0 BY CONSTRUCTION: where a late host issued ``n + 1`` after the device
had finished ``n``, the device's idle time lies inside ``n``'s interval
and reads as a longer dispatch. ``between`` sees a drained pipeline only:
an empty engine, a synchronous dispatch (rounds, a lone prefill), the
first-token fetch of a mixed step. The device's idle share is the
trace's (``device_idle_share``); whether a fetch WAITED for its dispatch
(the device was still in it when the host came, so none of this applies)
is in the step record's ``phases.fetch`` (``unwaited``).
"""

from __future__ import annotations

from pathlib import Path

from benchmark import metrics, trace_reduce
from benchmark.layer_metrics import _steps

DECODE = ("_decode_step", "_decode_multi", "_decode_spec")
PREFILL = ("_mixed_step", "_prefill_step")
# The dispatch annotation on the profiler's clock, by step program.
ANNOTATION = {"_prefill_step": "prefill", "_mixed_step": "mixed",
              "_decode_step": "decode", "_decode_multi": "decode",
              "_decode_spec": "decode_spec"}
FETCH = "engine.fetch_tokens"
# The device plane's clock reads EARLY against the host plane's by some
# hundreds of microseconds (a program on an idle device read as started
# 0.42 ms before the annotation around its call began in the tiny chip
# recording, 0.54 ms in a dense cell's slice): far under the tens of
# milliseconds by which a dispatch's neighbours on the device miss its
# call and its fetch.
CLOCK_SLACK_S = 2e-3
# A slice opens on at most the dispatch the device is in and those queued
# behind it, whose annotations the trace does not hold.
BEFORE_THE_SLICE = 8


def entries(steps: list[dict]) -> list[dict] | None:
    """Every dispatch of the steps, by number; None where no step has the
    field."""
    if not any("dispatches" in s for s in steps):
        return None
    out = {d["n"]: d for s in steps for d in s.get("dispatches", ())}
    return [out[n] for n in sorted(out)]


def intervals(entries_: list[dict]) -> list[tuple[dict, float, float]]:
    """(entry, its interval's seconds, ``between`` seconds before it) of
    every dispatch whose predecessor is there too."""
    out = []
    for prev, d in zip(entries_, entries_[1:]):
        if d["n"] != prev["n"] + 1:
            continue  # a record lost between polls: the stamp before is unknown
        start = max(d["t_issued"], prev["t_ready"])
        out.append((d, d["t_ready"] - start, start - prev["t_ready"]))
    return out


def window_intervals(run: dict) -> list[tuple[dict, float, float]] | None:
    """The intervals of the dispatches made ready inside the window (their
    predecessors taken from every polled step, the warm-up's too)."""
    es = entries(_steps.span_steps(run))
    if es is None:
        return None
    return [row for row in intervals(es) if _steps.in_window(run, row[0]["t_ready"])]


def seconds_under_a_dispatch(run: dict) -> float | None:
    """The window's seconds that lie inside some dispatch's interval: each
    interval cut to the window, the one the window opens in and the one in
    flight at its end included (the drain's polls hold that one)."""
    es = entries(_steps.span_steps(run))
    if es is None:
        return None
    t0, t1 = run["t0"], run["t0"] + run["seconds"]
    return sum(max(0.0, min(d["t_ready"], t1) - max(d["t_ready"] - seconds, t0))
               for d, seconds, _ in intervals(es))


def unwaited(steps: list[dict], wait_s: float = 1e-3) -> list[dict] | None:
    """The dispatches whose interval may hold device idle time no record
    names (the blind spot above): stamped ready in the step that lists
    them, by a fetch phase that waited under ``wait_s`` in all. The device
    had finished before the host came for the result; when, no stamp
    says. One stamped in an EARLIER step was fetched synchronously where
    it was issued (a first token), and what followed it is ``between``."""
    if not any("dispatches" in s for s in steps):
        return None
    return [d for s in steps for d in s.get("dispatches", ())
            if d["t_ready"] >= s["t_start"] and s["phases"].get("fetch", 0.0) < wait_s]


def median_ms(seconds: list[float]) -> float | None:
    return _steps.ms(metrics.percentile(seconds, 50)) if seconds else None


def ridden(run: dict) -> list[dict] | None:
    """Lifecycle records of the window's requests that can be taken apart:
    eight tokens or more, never preempted (a preempted request's interval
    holds time it did not ride), with a ``rode``. None where no lifecycle
    record has the field."""
    life = _steps.window_requests(run)
    if not any("rode" in f for s in _steps.span_steps(run) for f in s["finished"]):
        return None
    return [f for f in life if f.get("rode") and f["generated"] >= 8
            and not f["preemptions"]]


def rode_seconds(rode: dict, programs: tuple[str, ...]) -> float:
    return sum(rode[p][2] for p in programs if p in rode)


def rode_tokens(rode: dict, programs: tuple[str, ...]) -> int:
    return sum(rode[p][1] for p in programs if p in rode)


def partition(entries_: list[dict], t0: float, t1: float) -> dict | None:
    """A ``rode`` over ``(t0, t1]`` made anew from the dispatches, by the
    rule above: ``{program: [dispatches made ready, seconds], ...,
    "between": seconds}`` (no tokens: an entry holds no request's). The
    check of the engine's sums against this file's copy of the definition.
    None where the dispatches do not cover the interval: none was ready by
    ``t0``, or a record was lost inside it. After the last dispatch there
    is, nothing was in flight: ``between``."""
    def clipped(lo: float, hi: float) -> float:
        return max(0.0, min(hi, t1) - max(lo, t0))

    if not entries_ or entries_[0]["t_ready"] > t0:
        return None
    out: dict = {"between": 0.0}
    covered = t0
    for prev, d in zip(entries_, entries_[1:]):
        if d["t_ready"] <= t0:
            continue
        if d["n"] != prev["n"] + 1:
            return None
        start = max(d["t_issued"], prev["t_ready"])
        out["between"] += clipped(prev["t_ready"], start)
        if start < t1:
            row = out.setdefault(d["program"], [0, 0.0])
            row[0] += d["t_ready"] <= t1
            row[1] += clipped(start, d["t_ready"])
        covered = d["t_ready"]
        if covered >= t1:
            return out
    out["between"] += t1 - covered
    return out


# ---- on the profiler's clock -------------------------------------------------


def load(xplane: Path, step_programs: list[str]) -> dict:
    """{"issued": the dispatch annotations that carry a ``dispatch`` stat,
    [(n, name, start_s, end_s)] by number, "fetched": the fetch spans that
    carry one, likewise, "modules": the first device's step programs
    [(program, start_s, end_s)] by start}; "modules" is empty for a trace
    with no device plane."""
    from jax.profiler import ProfileData

    known = {"jit_" + p: p for p in step_programs}
    issued, fetched, modules = [], [], None
    for plane in ProfileData.from_file(str(xplane)).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            if modules is None:
                modules = [(known[trace_reduce.base_name(name)], s, e)
                           for line in plane.lines
                           if line.name == trace_reduce.MODULE_LINE
                           for name, s, e in trace_reduce._events(line)
                           if trace_reduce.base_name(name) in known]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name != FETCH and e.name not in ANNOTATION.values():
                        continue
                    n = dict(e.stats).get("dispatch")
                    if n is None:
                        continue
                    row = (int(n), e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                    (fetched if e.name == FETCH else issued).append(row)
    return {"issued": sorted(issued), "fetched": sorted(fetched),
            "modules": modules or []}


def join(loaded: dict) -> list[dict]:
    """One row a dispatch annotation of the slice whose device event the
    slice holds too: by NUMBER and ORDER, held to the two ends the host's
    spans give. The device takes an engine's dispatches up in the order
    issued, so the annotations by number and the device's step programs
    by start pair off one for one from SOME event on: the slice opens on
    a few events whose annotations came before it. Which one is decided,
    not assumed: a row ``agrees`` if the event is of the annotation's
    program, did not start before the annotation did, and ended by the
    end of the fetch that consumed its result (``fetch_end_s``: the
    record's ``t_ready`` on this clock; both within ``CLOCK_SLACK_S``). A
    pairing one event off breaks one of the two ends at nearly every row
    (the neighbours are a dispatch away), so a dropped host span or a
    step program without its stat cannot shift the pairs unseen: the
    first offset at which EVERY row agrees is taken; if there is none,
    the rows of the offset that got furthest, up to and with the first
    that does not agree (the tool says so and exits 1)."""
    issued, modules = loaded["issued"], loaded["modules"]
    fetch_end: dict[int, float] = {}
    for n, _, _, end in loaded["fetched"]:
        fetch_end.setdefault(n, end)

    def rows_from(offset: int) -> list[dict]:
        rows = []
        for (n, name, t_call, t_issued), (program, start, end) in zip(
                issued, modules[offset:]):
            fetched = fetch_end.get(n)
            rows.append({
                "n": n, "annotation": name, "program": program,
                "agrees": (ANNOTATION[program] == name
                           and start >= t_call - CLOCK_SLACK_S
                           and (fetched is None or end <= fetched + CLOCK_SLACK_S)),
                "call_s": t_call, "issued_s": t_issued, "start_s": start,
                "end_s": end, "device_s": end - start, "fetch_end_s": fetched})
            if not rows[-1]["agrees"]:
                break
        return rows

    best: list[dict] = []
    for offset in range(min(len(modules), BEFORE_THE_SLICE + 1) if issued else 0):
        rows = rows_from(offset)
        if rows[-1]["agrees"]:
            return rows
        if len(rows) > len(best):
            best = rows
    return best


def against_records(rows: list[dict], entries_: list[dict]) -> dict:
    """By program, over the joined dispatches that have an interval in the
    records: the record's device-side interval against the device's own
    event, as (record - device) / device — dispatches, and the median and
    the largest magnitude of that difference. The slice's first and last
    joined dispatch are left out: the device plane begins and ends inside
    their events (a 153 ms program read 31 ms at a slice's start)."""
    by_n = {d["n"]: (seconds, d) for d, seconds, _ in intervals(entries_)}
    diffs: dict[str, list[float]] = {}
    pairs = []
    for r in rows[1:-1]:
        if r["n"] not in by_n or not r["agrees"]:
            continue
        seconds, d = by_n[r["n"]]
        if d["program"] != r["program"]:
            raise ValueError(f"dispatch {r['n']}: the record says "
                             f"{d['program']}, the device {r['program']}")
        rel = (seconds - r["device_s"]) / r["device_s"]
        diffs.setdefault(d["program"], []).append(rel)
        pairs.append({**{k: d[k] for k in ("n", "program", "k", "rows",
                                           "kv_pages_live", "tokens")},
                      "record_ms": seconds * 1e3,
                      "device_ms": r["device_s"] * 1e3, "relative": rel})
    return {"pairs": pairs, "by_program": {
        p: {"dispatches": len(v), "median": metrics.percentile(v, 50),
            "worst": max(v, key=abs)} for p, v in sorted(diffs.items())}}
