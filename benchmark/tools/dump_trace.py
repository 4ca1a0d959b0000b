"""Look at one trace by hand: planes, lines, the heaviest event names and
a few events' statistics.

    python3 -m benchmark.tools.dump_trace <dir or .xplane.pb> [out.json]
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from benchmark import trace_reduce


def dump(xplane: Path, top: int = 40) -> dict:
    from jax.profiler import ProfileData

    out = {"file": str(xplane), "bytes": xplane.stat().st_size, "planes": []}
    for plane in ProfileData.from_file(str(xplane)).planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            by_name: dict[str, list[float]] = {}
            for e in events:
                t = by_name.setdefault(e.name, [0, 0.0])
                t[0] += 1
                t[1] += e.duration_ns * 1e-9
            heavy = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]
            sample = max(events, key=lambda e: e.duration_ns)
            lines.append({
                "line": line.name, "events": len(events),
                "first_start_ns": min(e.start_ns for e in events),
                "heaviest": [[n, c, s] for n, (c, s) in heavy],
                "longest_event_stats": {str(k): str(v)[:300] for k, v in sample.stats}})
        out["planes"].append({"plane": plane.name, "lines": lines})
    return out


def main(argv: list[str]) -> int:
    path = Path(argv[1])
    xplane = path if path.is_file() else trace_reduce.newest_xplane(path)
    text = json.dumps(dump(xplane), indent=1)
    if len(argv) > 2:
        Path(argv[2]).parent.mkdir(parents=True, exist_ok=True)
        Path(argv[2]).write_text(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
