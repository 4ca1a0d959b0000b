"""Request-lifecycle timelines stitched from trace JSONL.

The tracer (:mod:`runbookai_tpu.utils.trace`) writes flat span/event
records; this module joins them back into ONE request's story — enqueue →
router placement → admit → prefill chunks → decode windows → finish/abort
— keyed by the correlation ids the serving stack already propagates:

- the caller's ``x-request-id`` rides as ``ctx.request_id`` on server
  spans, ``meta.trace_id`` on the engine's lifecycle events
  (``engine.enqueue`` / ``engine.admit`` / ``engine.request``) and on the
  fleet router's ``router.place`` / ``router.shed`` /
  ``router.page_pull`` events;
- the engine-internal request id (``r{i}-…`` when fleeted) appears as
  ``meta.request`` on lifecycle events and inside ``meta.requests`` on
  dispatch spans (``engine.prefill`` / ``engine.decode`` /
  ``engine.decode_spec`` / ``engine.mixed``) — a dp fleet's retries each
  contribute their own engine request, so a timeline shows the aborted
  attempt AND the replica that finally served it.

``runbook timeline <request-id> --trace <file>`` renders the tree;
:func:`lifecycle_summary` powers the queue-wait / router-placement block
of ``runbook metrics --trace``.
"""

from __future__ import annotations

from typing import Any, Optional

# Dispatch spans that carry a meta.requests attribution list.
DISPATCH_SPANS = ("engine.prefill", "engine.decode", "engine.decode_spec",
                  "engine.mixed")

# Fleet-wide incident markers (obs/incident.py): not owned by any one
# request, but stitched into every timeline they overlap — a dp retry
# during an incident must be visible in one view.
INCIDENT_EVENTS = ("incident.open", "incident.resolve")
_DISPATCH_LABEL = {
    "engine.prefill": "prefill chunk",
    "engine.decode": "decode window",
    "engine.decode_spec": "decode window (spec-verify)",
    "engine.mixed": "mixed dispatch",
}


def _meta(rec: dict[str, Any]) -> dict[str, Any]:
    meta = rec.get("meta")
    return meta if isinstance(meta, dict) else {}


def _ctx(rec: dict[str, Any]) -> dict[str, Any]:
    ctx = rec.get("ctx")
    return ctx if isinstance(ctx, dict) else {}


def _start_ts(rec: dict[str, Any]) -> float:
    """When a record STARTED. New records carry ``t0``, time.monotonic()
    at the start — the clock of the engine's step records; a file from
    before that has only ``ts``, the wall clock at CLOSE, less ``ms``.
    One file is all of one kind, so differences within it hold."""
    if "t0" in rec:
        return float(rec["t0"])
    return float(rec.get("ts", 0.0)) - float(rec.get("ms", 0.0)) / 1e3


def resolve_engine_requests(spans: list[dict[str, Any]],
                            request_id: str) -> set[str]:
    """Engine-internal request ids owned by ``request_id``.

    The query id may itself BE an engine id (tests trace without a
    server in front), or an ``x-request-id`` that one or more engine
    requests carried as ``trace_id`` (fleet retries → several)."""
    rids = {request_id}
    for rec in spans:
        meta = _meta(rec)
        if meta.get("trace_id") == request_id and "request" in meta:
            rids.add(str(meta["request"]))
    return rids


def build_timeline(spans: list[dict[str, Any]],
                   request_id: str) -> Optional[dict[str, Any]]:
    """Stitch one request's records into an ordered event list.

    Returns None when no record references the id. Each event carries
    ``rel_ms`` (offset from the request's first record), the raw span
    name, duration, and the interesting meta fields."""
    rids = resolve_engine_requests(spans, request_id)
    picked: list[dict[str, Any]] = []
    for rec in spans:
        name = str(rec.get("name", ""))
        meta = _meta(rec)
        owns = (
            _ctx(rec).get("request_id") == request_id
            or meta.get("trace_id") == request_id
            or str(meta.get("request")) in rids
            or (name in DISPATCH_SPANS
                and any(str(r) in rids
                        for r in (meta.get("requests") or ())))
        )
        if owns:
            picked.append(rec)
    if not picked:
        return None
    picked.sort(key=_start_ts)
    t0 = _start_ts(picked[0])
    events: list[dict[str, Any]] = []
    finish: Optional[dict[str, Any]] = None
    replicas: set[int] = set()
    for rec in picked:
        name = str(rec.get("name", ""))
        meta = _meta(rec)
        ev: dict[str, Any] = {
            "name": name,
            "rel_ms": round((_start_ts(rec) - t0) * 1e3, 3),
            "ms": float(rec.get("ms", 0.0)),
        }
        if "replica" in meta:
            ev["replica"] = meta["replica"]
            replicas.add(int(meta["replica"]))
        if name in DISPATCH_SPANS:
            ev["label"] = _DISPATCH_LABEL[name]
            for key in ("batch", "tokens", "k", "prefill_rows"):
                if key in meta:
                    ev[key] = meta[key]
        elif name == "engine.enqueue":
            ev["label"] = "enqueue"
            ev["request"] = meta.get("request")
            ev["prompt_tokens"] = meta.get("prompt_tokens")
        elif name == "engine.admit":
            ev["label"] = "admit"
            ev["request"] = meta.get("request")
            ev["cached_tokens"] = meta.get("cached_tokens")
            ev["queue_ms"] = meta.get("queue_ms")
            ev["cls"] = meta.get("cls")
        elif name == "router.place":
            hit = meta.get("affinity")
            ev["label"] = (f"router.place → replica {meta.get('replica')}"
                           + (" (affinity hit)" if hit else ""))
            ev["affinity"] = hit
        elif name == "router.page_pull":
            # Cross-replica KV pull / prefill→decode handoff: the span
            # that proves the request rode staged pages instead of a
            # re-prefill (replica = destination, src = the page source).
            ev["label"] = (f"page pull ← replica {meta.get('src')} "
                           f"({meta.get('pages')} pages, "
                           f"{meta.get('pull_ms')} ms)")
            ev["src"] = meta.get("src")
            ev["pages"] = meta.get("pages")
            ev["pull_ms"] = meta.get("pull_ms")
        elif name == "router.shed":
            ev["label"] = "router.shed (all replicas saturated)"
        elif name == "engine.request":
            ev["label"] = f"finish: {meta.get('reason')}"
            ev["request"] = meta.get("request")
            ev["reason"] = meta.get("reason")
            ev["generated"] = meta.get("generated")
            if "ttft_ms" in meta:
                ev["ttft_ms"] = meta["ttft_ms"]
            finish = ev
        elif name == "server.request":
            ev["label"] = (f"server.request {_meta(rec).get('route', '')}"
                           .strip())
        else:
            ev["label"] = name
        events.append(ev)
    last = max(ev["rel_ms"] + ev["ms"] for ev in events)
    # Incident span band: fleet-wide incident.open/resolve markers
    # overlapping this request's window ride into the timeline (with a
    # small slack so an open that preceded the request by a beat still
    # shows), labeled so the operator sees the request's dispatches AND
    # the incident they ran inside in one view.
    incidents: set[str] = set()
    t_end = t0 + last / 1e3
    for rec in spans:
        name = str(rec.get("name", ""))
        if name not in INCIDENT_EVENTS:
            continue
        ts = _start_ts(rec)
        if not (t0 - 1.0 <= ts <= t_end + 1.0):
            continue
        meta = _meta(rec)
        inc_id = str(meta.get("incident", "?"))
        incidents.add(inc_id)
        ev = {
            "name": name,
            "rel_ms": round((ts - t0) * 1e3, 3),
            "ms": 0.0,
            "incident": inc_id,
            "signal": meta.get("signal"),
        }
        if name == "incident.open":
            ev["label"] = (f"⚠ incident open: {meta.get('signal')} "
                           f"({inc_id}, {meta.get('severity', '?')})")
        else:
            dur = meta.get("duration_s")
            ev["label"] = (f"✓ incident resolve: {meta.get('signal')} "
                           f"({inc_id}"
                           + (f", {dur}s" if dur is not None else "")
                           + ")")
        events.append(ev)
    events.sort(key=lambda e: e["rel_ms"])
    return {
        "request_id": request_id,
        "engine_requests": sorted(rids - {request_id}),
        "replicas": sorted(replicas),
        "incidents": sorted(incidents),
        "total_ms": round(last, 3),
        "finish": ({"reason": finish.get("reason"),
                    "generated": finish.get("generated"),
                    "ttft_ms": finish.get("ttft_ms")}
                   if finish else None),
        "events": events,
    }


def render_timeline(tl: dict[str, Any], max_events: int = 60) -> str:
    """ASCII span tree of a built timeline (``runbook timeline``).

    Long decode phases collapse: when the event list exceeds
    ``max_events``, the middle dispatch windows are elided into one
    summary line so the enqueue/placement/admit head and the finish tail
    stay readable."""
    head = [f"request {tl['request_id']} — {tl['total_ms']:.1f} ms total"]
    if tl["engine_requests"]:
        head.append(f"  engine ids: {', '.join(tl['engine_requests'])}")
    if tl["replicas"]:
        head.append("  replicas: "
                    + ", ".join(str(r) for r in tl["replicas"]))
    if tl.get("incidents"):
        head.append("  incidents: " + ", ".join(tl["incidents"]))
    events = tl["events"]
    shown: list[Any] = list(events)
    if len(events) > max_events:
        keep_head = max_events // 2
        keep_tail = max_events - keep_head
        elided = events[keep_head:-keep_tail]
        dispatch_ms = sum(e["ms"] for e in elided)
        shown = (events[:keep_head]
                 + [{"_elided": len(elided), "_ms": dispatch_ms}]
                 + events[-keep_tail:])
    lines = head
    for i, ev in enumerate(shown):
        branch = "└─" if i == len(shown) - 1 else "├─"
        if "_elided" in ev:
            lines.append(f"{branch} … {ev['_elided']} more dispatch "
                         f"windows ({ev['_ms']:.1f} ms)")
            continue
        extras = []
        for key in ("k", "batch", "tokens", "prefill_rows", "generated",
                    "cached_tokens", "queue_ms", "cls", "prompt_tokens",
                    "ttft_ms"):
            if ev.get(key) is not None:
                extras.append(f"{key}={ev[key]}")
        if ev.get("replica") is not None and "router" not in ev["name"]:
            extras.append(f"replica={ev['replica']}")
        dur = f" {ev['ms']:.1f}ms" if ev["ms"] else ""
        suffix = f"  [{', '.join(extras)}]" if extras else ""
        lines.append(f"{branch} +{ev['rel_ms']:9.1f}ms  "
                     f"{ev['label']}{dur}{suffix}")
    return "\n".join(lines)


def lifecycle_summary(spans: list[dict[str, Any]]) -> dict[str, Any]:
    """Population view of the lifecycle events for
    ``runbook metrics --trace``: queue-wait distribution (from
    ``engine.admit``'s ``queue_ms``) and router placement counts — both
    previously invisible in the per-span duration summary (events have
    ``ms=0`` so their latency story lives in meta, not duration)."""
    from runbookai_tpu.utils.trace import _percentile

    queue_ms: list[float] = []
    by_class: dict[str, list[float]] = {}
    placements: dict[str, int] = {}
    affinity_hits = 0
    sheds = 0
    admits = 0
    for rec in spans:
        name = str(rec.get("name", ""))
        meta = _meta(rec)
        if name == "engine.admit":
            admits += 1
            if meta.get("queue_ms") is not None:
                queue_ms.append(float(meta["queue_ms"]))
                # Per-priority-class breakdown (the admit event carries
                # its class since the sched/ layer landed): the
                # starvation picture — batch may legitimately wait,
                # interactive must not.
                cls = str(meta.get("cls") or "unknown")
                by_class.setdefault(cls, []).append(float(meta["queue_ms"]))
        elif name == "router.place":
            replica = str(meta.get("replica", "?"))
            placements[replica] = placements.get(replica, 0) + 1
            if meta.get("affinity"):
                affinity_hits += 1
        elif name == "router.shed":
            sheds += 1
    queue_ms.sort()

    def _dist(values: list[float]) -> dict[str, Any]:
        values = sorted(values)
        return {
            "count": len(values),
            "p50": round(_percentile(values, 50), 3),
            "p95": round(_percentile(values, 95), 3),
            "max": round(values[-1], 3) if values else 0.0,
        }

    out: dict[str, Any] = {
        "admissions": admits,
        "queue_wait_ms": _dist(queue_ms),
    }
    if by_class:
        out["queue_wait_ms_by_class"] = {
            cls: _dist(values) for cls, values in sorted(by_class.items())}
    if placements or sheds:
        total = sum(placements.values())
        out["router"] = {
            "placements": {k: placements[k] for k in sorted(placements)},
            "affinity_hits": affinity_hits,
            "affinity_hit_ratio": (round(affinity_hits / total, 4)
                                   if total else 0.0),
            "sheds": sheds,
        }
    return out
