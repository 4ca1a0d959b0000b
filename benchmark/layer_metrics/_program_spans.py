"""The program's own spans on the profiler's clock, read from a
``.xplane.pb`` with nothing but JAX: the host events the program emits
(``utils/trace.annotate``) by name, and their joins to what
``/debug/steps`` holds: a step's span to its flight record through the
``step`` stat, a request's ``server.parse`` span to its lifecycle record
through the ``request`` stat.

The engine's step thread emits ``engine.step`` (stat ``step``: the number
of the step's record) and, inside it, ``engine.admit``, ``engine.build``,
the dispatch (``prefill`` / ``mixed`` / ``decode`` / ``decode_spec``),
``engine.fetch_tokens``, ``engine.emit`` and ``engine.draft``; from the
end of a step that leaves work to the start of the next lies
``engine.loop`` (``AsyncEngine``: the hops between the event loop and the
step thread, the wait for the step lock). The handler threads emit
``server.parse`` (handler start to the engine hand-off) and
``server.write`` (each SSE write), both with stat ``request`` = the
request's ``x-request-id``, a lifecycle record's ``trace_id``. A traced
benchmark run also wraps ``step`` / ``_admit`` / ``_fetch_tokens`` from
outside under the same names (``spans.json`` ``wrap``); such a span
encloses the program's and has no ``step`` stat.
"""

from __future__ import annotations

from pathlib import Path

from benchmark import metrics, trace_reduce

STEP, LOOP = "engine.step", "engine.loop"
ENGINE_SPANS = (STEP, LOOP, "engine.admit", "engine.build",
                "engine.fetch_tokens", "engine.emit", "engine.draft",
                "prefill", "mixed", "decode", "decode_spec")
PARSE, WRITE = "server.parse", "server.write"


def load(xplane: Path) -> dict:
    """{"spans": the engine's, [(name, start_s, end_s, step or None)] by
    start, "server": the handler threads', [(name, start_s, end_s,
    request)] by start, "modules": the first device's programs as (name,
    start_s, end_s)}; "modules" is empty for a trace with no device plane
    (a CPU's)."""
    from jax.profiler import ProfileData

    spans, server, modules = [], [], None
    for plane in ProfileData.from_file(str(xplane)).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            if modules is None:  # the first device, as the gaps are taken
                modules = [
                    (trace_reduce.base_name(name), s, e)
                    for line in plane.lines
                    if line.name == trace_reduce.MODULE_LINE
                    for name, s, e in trace_reduce._events(line)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name not in ENGINE_SPANS + (PARSE, WRITE):
                        continue
                    stats = dict(e.stats)
                    ends = (e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                    if e.name in ENGINE_SPANS:
                        step = stats.get("step")
                        spans.append((e.name, *ends,
                                      None if step is None else int(step)))
                    else:
                        server.append((e.name, *ends, stats.get("request")))
    spans.sort(key=lambda t: t[1])
    server.sort(key=lambda t: t[1])
    return {"spans": spans, "server": server, "modules": modules or []}


def idle_by_span(xplane: Path) -> dict[str, float]:
    """Seconds of device idle in the slice by the innermost engine span
    that covers the gap's middle, "between steps" where none does (the
    engine had no work, or the program is one from before ``engine.loop``):
    the benchmark's own reduction (``trace_reduce``), given the engine's
    span names instead of ``spans.json``'s."""
    return trace_reduce.reduce_trace(xplane, list(ENGINE_SPANS))["idle_gaps"]


def join_steps(loaded: dict, steps: list[dict]) -> list[dict]:
    """One row for each ``engine.step`` span that carries a ``step``: the
    record of that number (None if the polls missed it), and the device
    programs that STARTED between the span's ends. Under the overlapped
    pipeline a program runs on past the end of the step that dispatched
    it, but the device takes it up when the window before it ends, which
    is inside that step's fetch: its start is what lies in the span."""
    by_number = {s["step"]: s for s in steps}
    rows = []
    for name, t0, t1, step in loaded["spans"]:
        if name != STEP or step is None:
            continue
        rec = by_number.get(step)
        rows.append({
            "step": step, "span_s": t1 - t0,
            "record_program": None if rec is None else rec.get("program"),
            "device_programs": [m for m, s, _ in loaded["modules"]
                                if t0 <= s <= t1]})
    return rows


def front_door(loaded: dict, steps: list[dict]) -> dict:
    """What ``front_door_ttft_p50_ms`` adds up, taken apart: one row for
    each request whose ``server.parse`` span lies whole in the trace and
    whose lifecycle record the steps hold (joined by ``request`` =
    ``trace_id``), in ms. On the way in, ``parse`` (the span: body read,
    chat template, tokenise, tenant admission) and ``handoff`` (the rest
    of ``t_enqueued - t_received``: response headers and the role chunk,
    the hop to the engine's loop, ``submit``'s wait for the step lock);
    on the way out, ``first_write`` (``t_first_write - t_first_token``;
    None for a request that never streamed); and its ``server.write``
    spans inside the trace, their count, sum and longest. Durations only,
    so the two clocks need no offset."""
    life = {f["trace_id"]: f for s in steps for f in s.get("finished", ())
            if f.get("trace_id") is not None}
    writes: dict[str, list[float]] = {}
    for name, t0, t1, request in loaded["server"]:
        if name == WRITE:
            writes.setdefault(request, []).append((t1 - t0) * 1e3)
    rows = []
    for name, t0, t1, request in loaded["server"]:
        f = life.get(request)
        if name != PARSE or f is None or f["t_enqueued"] is None:
            continue
        parse = (t1 - t0) * 1e3
        streamed = None not in (f["t_first_write"], f["t_first_token"])
        w = writes.get(request, [])
        rows.append({
            "request": request, "parse_ms": parse,
            "handoff_ms": (f["t_enqueued"] - f["t_received"]) * 1e3 - parse,
            "first_write_ms": ((f["t_first_write"] - f["t_first_token"]) * 1e3
                               if streamed else None),
            "writes": len(w), "write_ms_sum": sum(w),
            "write_ms_max": max(w, default=None)})

    def p50(key: str):
        values = [r[key] for r in rows if r[key] is not None]
        return metrics.percentile(values, 50) if values else None

    every = [ms for w in writes.values() for ms in w]
    return {"requests": len(rows), "parse_ms_p50": p50("parse_ms"),
            "handoff_ms_p50": p50("handoff_ms"),
            "first_write_ms_p50": p50("first_write_ms"),
            "writes": len(every),
            "write_ms_p50": metrics.percentile(every, 50) if every else None,
            "write_ms_max": max(every, default=None), "rows": rows}
