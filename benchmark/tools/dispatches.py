"""The dispatches of a run, one line each, from its two files: the polled
``/debug/steps`` (as served, its ``steps`` list, or ``keep_steps``'s file)
and the slice's ``.xplane.pb`` (or ``.xplane.pb.gz``, or the directory
that holds it).

    python3 -m benchmark.tools.dispatches <trace> <steps.json> [out.json]

Prints a table — n, program, k, rows, ``kv_pages_live``, the record's
device-side interval (``layer_metrics/_dispatches``) and, for a dispatch
inside the slice, the device's own event of it joined by the ``dispatch``
stat — and a last JSON line: by program, the record against the device
(dispatches, median and worst relative difference); the decode dispatches
of the window (or of the file) by thirds in time (rows, pages, ms a pass);
the share of the records' clock under a dispatch; ``unwaited``, the
dispatches whose fetch did not wait and their seconds (an interval is the
host's view: only in these can it hold device idle time that no record
names, ``_dispatches.unwaited``). Exits 1 if an annotation disagrees with
the device's event at its place (another program's, or outside the host's
two ends of the dispatch: a pair shifted by a missing span), or a
program's median differs by more than 3%.
"""

from __future__ import annotations

import gzip
import json
import sys
import tempfile
from pathlib import Path

from benchmark import metrics, serving, trace_reduce
from benchmark.layer_metrics import _dispatches, _steps

LIMIT = 0.03


def open_xplane(path: Path, scratch: Path) -> Path:
    """The ``.xplane.pb`` a path means: itself, the newest under a
    directory, or a ``.gz`` unpacked into ``scratch``."""
    if path.is_dir():
        return trace_reduce.newest_xplane(path)
    if path.suffix != ".gz":
        return path
    unpacked = scratch / path.name[:-3]
    unpacked.write_bytes(gzip.decompress(path.read_bytes()))
    return unpacked


def thirds(rows: list[tuple[dict, float, float]]) -> list[dict]:
    """The decode dispatches in three equal runs by number: how the batch
    and the contexts grew, and what a pass then took."""
    decode = [(d, s) for d, s, _ in rows if d["program"] in _dispatches.DECODE]
    out = []
    for i in range(3):
        part = decode[len(decode) * i // 3: len(decode) * (i + 1) // 3]
        if part:
            out.append({
                "dispatches": len(part),
                "rows_mean": sum(d["rows"] for d, _ in part) / len(part),
                "kv_pages_live_mean": sum(d["kv_pages_live"] for d, _ in part) / len(part),
                "pass_ms_p50": 1e3 * metrics.percentile(
                    [s / d["k"] for d, s in part], 50)})
    return out


def main(argv: list[str]) -> int:
    saved = json.loads(Path(argv[2]).read_text())
    steps = saved["steps"] if isinstance(saved, dict) else saved
    entries = _dispatches.entries(steps)
    if entries is None:
        print("the records hold no `dispatches`: a program from before the field")
        return 1
    step_programs = serving.load_json(serving.BENCH / "spans.json")["step_programs"]
    with tempfile.TemporaryDirectory() as scratch:
        xplane = open_xplane(Path(argv[1]), Path(scratch))
        rows = _dispatches.join(_dispatches.load(xplane, step_programs))
    compared = _dispatches.against_records(rows, entries)
    device_ms = {p["n"]: p["device_ms"] for p in compared["pairs"]}
    every = _dispatches.intervals(entries)
    if isinstance(saved, dict) and saved.get("t0") is not None:
        run = {"t0": saved["t0"], "seconds": saved["seconds"]}
        every = [r for r in every if _steps.in_window(run, r[0]["t_ready"])]
    print(f"{'n':>6} {'program':<14} {'k':>2} {'rows':>4} {'pages':>6} "
          f"{'record ms':>10} {'device ms':>10} {'between ms':>10}")
    for d, seconds, between in every:
        dev = device_ms.get(d["n"])
        print(f"{d['n']:>6} {d['program']:<14} {d['k']:>2} {d['rows']:>4} "
              f"{d['kv_pages_live']:>6} {seconds * 1e3:>10.3f} "
              f"{'' if dev is None else format(dev, '.3f'):>10} {between * 1e3:>10.3f}")
    span = (every[-1][0]["t_ready"] - every[0][0]["t_ready"]) if len(every) > 1 else 0.0
    unwaited = {d["n"] for d in _dispatches.unwaited(steps)}
    unwaited_s = [s for d, s, _ in every if d["n"] in unwaited]
    out = {"file": str(xplane), "dispatches": len(every), "joined": len(rows),
           "disagree": [r for r in rows if not r["agrees"]],
           "unwaited": {"dispatches": len(unwaited_s), "seconds": sum(unwaited_s)},
           "by_program": compared["by_program"], "thirds": thirds(every),
           "under_a_dispatch_share": (sum(s for _, s, _ in every[1:]) / span
                                      if span else None),
           "between_s": sum(b for _, _, b in every[1:])}
    if len(argv) > 3:
        Path(argv[3]).parent.mkdir(parents=True, exist_ok=True)
        Path(argv[3]).write_text(json.dumps({**out, "pairs": compared["pairs"]}, indent=1))
    print(json.dumps(out))
    off = [p for p, v in compared["by_program"].items() if abs(v["median"]) > LIMIT]
    return 1 if out["disagree"] or off else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
