"""Take the front door's share of time to first token apart, from a
profiler trace and the flight records of the same minutes: for each
request whose ``server.parse`` span the trace holds and whose lifecycle
record the steps hold, the parse, the hand-off to the engine, and the way
out to the first write; and every ``server.write`` in the trace
(``layer_metrics/_program_spans.front_door``).

    python3 -m benchmark.tools.front_door <dir or .xplane.pb> <steps.json> [out.json]

``steps.json`` is ``GET /debug/steps`` as served, or its ``steps`` list.
Prints the medians; the rows go to ``out.json``. Exits 1 if no request of
the trace has a record.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from benchmark import trace_reduce
from benchmark.layer_metrics import _program_spans, _steps


def main(argv: list[str]) -> int:
    path = Path(argv[1])
    xplane = path if path.is_file() else trace_reduce.newest_xplane(path)
    out = _program_spans.front_door(_program_spans.load(xplane),
                                    _steps.load_steps(Path(argv[2])))
    if len(argv) > 3:
        Path(argv[3]).parent.mkdir(parents=True, exist_ok=True)
        Path(argv[3]).write_text(json.dumps(out, indent=1))
    print(json.dumps({k: v for k, v in out.items() if k != "rows"}))
    return 0 if out["requests"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
