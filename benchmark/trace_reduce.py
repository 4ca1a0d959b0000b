"""From a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read — with nothing but JAX (``ProfileData``).

A device plane (``/device:TPU:<n>``) carries a line of whole programs
("XLA Modules": ``jit__decode_multi(...)``…) and a line of single
operations ("XLA Ops": fusions, copies, the Pallas kernels by their
names). Busy time is the union of the operations' intervals; a gap is
what lies between them, and is attributed to the host span
(``TraceAnnotation``: the names in ``spans.json``) that covers its
middle, or to "between steps". Host and device events share the
profiler's clock.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


def newest_xplane(trace_dir: Path) -> Path | None:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def _events(line) -> list[tuple[str, float, float]]:
    """(name, start_s, end_s) of a line's events, by start."""
    out = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
           for e in line.events]
    out.sort(key=lambda t: t[1])
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def base_name(name: str) -> str:
    """A program ``jit__decode_multi(1234567)`` -> ``jit__decode_multi``.
    An operation is named by the trace with its whole HLO instruction
    (``%fusion.2 = bf16[16,3584]{...} fusion(...)``): kept, with its
    shapes, less the layout braces, cut to 200 characters."""
    if " = " not in name:
        return name.split("(")[0].strip()
    return re.sub(r"\{[^{}]*\}", "", name)[:200]


def own_name(op: str) -> str:
    """``%qmm_pallas.82 = bf16[16,18944] custom-call(...)`` -> ``qmm_pallas``."""
    return re.sub(r"\.\d+$", "", op.split(" = ")[0].lstrip("%"))


# Operations that only contain others (their time is their bodies' time).
CONTAINERS = ("while", "conditional", "call")


def totals(events: list[tuple[str, float, float]]) -> dict[str, dict]:
    out: dict[str, dict] = {}
    for name, s, e in events:
        t = out.setdefault(base_name(name), {"count": 0, "seconds": 0.0})
        t["count"] += 1
        t["seconds"] += e - s
    return out


def reduce_trace(xplane: Path, span_names: list[str]) -> dict:
    """See the module text. Raises when the file holds no device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(xplane))
    devices, host_spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines
                     if line.name in (MODULE_LINE, OPS_LINE)}
            devices.append({"plane": plane.name,
                            "modules": lines.get(MODULE_LINE, []),
                            "ops": lines.get(OPS_LINE, [])})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [ev for ev in _events(line) if ev[0] in span_names]
    if not devices:
        raise ValueError(f"{xplane}: no /device:TPU plane "
                         f"(planes: {[p.name for p in data.planes]})")
    edges = [t for d in devices for ev in d["ops"] + d["modules"] for t in ev[1:]]
    edges += [t for ev in host_spans for t in ev[1:]]
    if not edges:
        raise ValueError(f"{xplane}: no event on a device plane")
    t_lo, t_hi = min(edges), max(edges)
    busy_each, gaps = [], {}
    for d in devices:
        busy = union([(s, e) for _, s, e in (d["ops"] or d["modules"])])
        busy_each.append(sum(e - s for s, e in busy))
        if d is not devices[0]:
            continue  # gaps are attributed on the first device
        holes = [(a, b) for a, b in zip([t_lo] + [e for _, e in busy],
                                        [s for s, _ in busy] + [t_hi]) if b > a]
        for a, b in holes:
            mid = (a + b) / 2
            cover = [ev for ev in host_spans if ev[1] <= mid <= ev[2]]
            name = (min(cover, key=lambda ev: ev[2] - ev[1])[0]
                    if cover else "between steps")
            gaps[name] = gaps.get(name, 0.0) + (b - a)
    first = devices[0]
    return {
        "window_s": t_hi - t_lo,
        "busy_s": sum(busy_each) / len(busy_each),
        "devices": len(devices),
        "modules": totals(first["modules"]),
        "ops": totals(first["ops"]),
        "idle_gaps": gaps,
        "host_spans": totals(host_spans),
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time and the longest idle gaps by what the host was doing."""
    ops = sorted(((n, t["seconds"]) for n, t in reduced["ops"].items()
                  if own_name(n) not in CONTAINERS), key=lambda x: -x[1])[:top]
    gaps = sorted(reduced["idle_gaps"].items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
