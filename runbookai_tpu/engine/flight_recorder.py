"""Engine flight recorder: a bounded in-memory ring of per-step records.

PR 1's histograms answer "how slow is the tail"; this module answers the
question that follows — "what was the engine *doing* on the slow steps?"
(FlashInfer-Bench's thesis: a serving stack improves only when every
measured run leaves a machine-readable record of what actually executed.)

One :class:`StepRecord`-shaped dict is appended per
:meth:`EngineCore.step`: step index, dispatch kind (the PR 4 counters:
prefill / decode / mixed — plus ``prefill+decode`` for a split step that
ran both, and ``idle`` for a drain-only step), real tokens this dispatch,
batch occupancy (total AND per priority class — the scheduler-fairness
picture), queue depth, KV-pool free pages, the dispatch/host/overlap wall
split, preemptions, and the replica index when fleeted. The record is
also the program's span of the step: its two ends on ``time.monotonic()``,
the seconds of each phase (:class:`OpenStep`), the step program(s) it
dispatched, seconds the process spent compiling in it, and the requests
first admitted and retired in it (``docs/observability.md``).

Design constraints (pinned by ``tests/test_observability.py``):

- **O(1) append, no lock**: the buffer is preallocated and the writer is
  the engine step thread (already serialized by the AsyncEngine lock);
  a slot assignment + cursor bump is the entire hot-path cost. Readers
  (``/debug/steps`` scrapes) snapshot under that same engine lock — or
  tolerate a one-record tear when they cannot afford to wait, exactly
  like the scrape gauges.
- **Bounded**: ``capacity`` records, oldest overwritten. A 1800s soak at
  ~50 steps/s stays a few MB regardless of run length.
- **Dumpable**: :meth:`snapshot` (newest-last dicts) for ``/debug/steps``
  and the AsyncFleet aggregation, :meth:`dump_jsonl` for offline diffing,
  :meth:`summary` for a window's step-level roll-up.
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Any, Optional

from runbookai_tpu.utils.trace import _percentile

# The per-step record keys, in emission order (documentation + the
# /debug/steps shape test import this so the wire contract is pinned).
STEP_RECORD_FIELDS = (
    "step", "ts", "kind", "classes", "tokens", "batch", "occupancy",
    "queue_depth", "kv_free_pages", "kv_utilization", "dispatch_s",
    "host_s", "overlap_s", "wall_s", "preemptions", "kv_imported",
    "kv_exported", "replica",
    # The record as a span (docs/observability.md): its two ends on
    # time.monotonic(), where its seconds went, what it dispatched, and
    # the requests that entered and left the engine in it.
    "t_start", "t_end", "phases", "program", "k", "rows", "kv_pages_live",
    "prefill_tokens", "decode_tokens", "compile_s", "admitted", "finished",
)
# Keys a record has only in some steps. ``experts``: where the model has an
# expert share (models/longcat.py), the expert counts this step FETCHED (a
# decode window's arrive with its tokens, one step after its dispatch):
# token-expert pairs on ``held``, identity (``zero``) and ``absent``
# experts, held experts ``touched`` and expert layers whose dispatch took
# the slow path (``overflow``), summed over layers, from ``passes`` forward
# passes of ``programs``. ``state``: where the model has recurrent layers
# (models/qwen3_next.py), in every step: ``slots_live`` (slots of the state
# pool that hold a sequence), and this step's ``snapshots_taken``,
# ``snapshots_restored``, ``snapshot_evictions``, ``hash_tokens_matched``
# and ``hash_tokens_granted`` (engine/kv_cache.py ``StateSnapshots``).
# ``spec``: in a step whose ``_decode_spec`` dispatch ran ROUNDS drafted by
# the model's own prediction module (models/joyai.py): ``rounds`` a row in
# the dispatch (also the record's ``k``), drafts verified (``drafted``: one
# a round and row, up to the row's stop), those whose second token was
# served (``accepted``), and the ``rows`` dispatched. Such a step's
# ``decode_tokens`` is ``drafted + accepted``: a row takes ``rounds`` to
# ``2 * rounds`` tokens of it.
OPTIONAL_STEP_FIELDS = ("experts", "state", "spec")

# ``phases`` keys besides "other" (= wall_s less their sum), and the
# profiler span that marks the same boundaries on the device trace's
# clock ("issue" is the dispatch annotation: prefill / mixed / decode /
# decode_spec).
STEP_PHASES = ("admit", "build", "issue", "fetch", "emit", "draft")
PHASE_SPANS = {"admit": "engine.admit", "build": "engine.build",
               "fetch": "engine.fetch_tokens", "emit": "engine.emit",
               "draft": "engine.draft"}

# One entry of ``finished``: a request's lifecycle, built once, in
# EngineCore._retire. Its times are on one clock, CLOCK_MONOTONIC:
# ``t_received``, ``t_enqueued`` and ``t_first_write`` read
# time.monotonic(); the others are the perf_counter() stamps the engine's
# histograms already took (EngineRequest says why).
LIFECYCLE_FIELDS = (
    "id", "trace_id", "t_received", "t_enqueued", "t_admitted",
    "t_first_token", "t_first_write", "t_finished", "prompt_tokens",
    "cached_tokens", "generated", "preemptions", "reason",
    "max_emit_gap_s",
)


class OpenStep:
    """The step being run, until it becomes a record: seconds per phase,
    and what was dispatched. A phase entered inside another pauses the
    outer one, so no interval is counted twice however the engine's
    drains nest. Written by the step thread only."""

    __slots__ = ("t_start", "phases", "programs", "k", "rows",
                 "kv_pages_live", "experts", "spec", "_stack", "_t")

    def __init__(self):
        self.phases = dict.fromkeys(STEP_PHASES, 0.0)
        self.programs: list[str] = []
        self.experts: Optional[dict[str, Any]] = None
        self.spec: Optional[dict[str, int]] = None  # self-drafted rounds
        self.k = 0  # decode steps in the dispatch
        self.rows = 0  # decode rows dispatched
        self.kv_pages_live = 0  # KV pages the dispatch's rows held
        self._stack: list[str] = []
        self._t = 0.0
        self.t_start = time.monotonic()

    def enter(self, phase: str) -> None:
        now = time.monotonic()
        if self._stack:
            self.phases[self._stack[-1]] += now - self._t
        self._stack.append(phase)
        self._t = now

    def exit(self) -> None:
        now = time.monotonic()
        self.phases[self._stack.pop()] += now - self._t
        self._t = now

    def dispatched(self, program: str, k: int = 0, rows: int = 0,
                   kv_pages_live: int = 0) -> None:
        self.programs.append(program)
        if k:
            self.k, self.rows, self.kv_pages_live = k, rows, kv_pages_live

    def fetched_experts(self, program: str, passes: int, held: int,
                        zero: int, absent: int, touched: int,
                        overflow: int) -> None:
        """Add one dispatch's expert counts, as they reach the host."""
        e = self.experts
        if e is None:
            e = self.experts = {"held": 0, "zero": 0, "absent": 0,
                                "touched": 0, "overflow": 0, "passes": 0,
                                "programs": []}
        e["held"] += held
        e["zero"] += zero
        e["absent"] += absent
        e["touched"] += touched
        e["overflow"] += overflow
        e["passes"] += passes
        if program not in e["programs"]:
            e["programs"].append(program)


class FlightRecorder:
    """Preallocated ring of the last ``capacity`` step records."""

    __slots__ = ("capacity", "_buf", "_next")

    def __init__(self, capacity: int):
        self.capacity = max(0, int(capacity))
        self._buf: list[Optional[dict[str, Any]]] = [None] * self.capacity
        self._next = 0  # monotonically increasing step cursor

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    @property
    def total_steps(self) -> int:
        """Steps recorded since construction (including overwritten ones)."""
        return self._next

    def __len__(self) -> int:
        return min(self._next, self.capacity)

    def append(self, rec: dict[str, Any]) -> None:
        """O(1), allocation-free beyond the caller's dict; no lock (the
        engine step thread is the only writer)."""
        if not self.capacity:
            return
        rec["step"] = self._next
        self._buf[self._next % self.capacity] = rec
        self._next += 1

    def reset(self) -> None:
        """Drop every record and restart the step cursor
        (``EngineCore.reset_metrics``: a measured window's records must
        exclude the warm-up's)."""
        self._buf = [None] * self.capacity
        self._next = 0

    def snapshot(self, last_n: Optional[int] = None) -> list[dict[str, Any]]:
        """Oldest→newest copies of the retained records (at most
        ``last_n``). Each record is shallow-copied so callers can JSON-
        serialize outside the engine lock without racing the writer."""
        n = len(self)
        if last_n is not None:
            n = min(n, max(0, int(last_n)))
        start = self._next - n
        return [dict(self._buf[i % self.capacity])
                for i in range(start, self._next)
                if self._buf[i % self.capacity] is not None]

    def dump_jsonl(self, path: str | Path) -> int:
        """Write the retained records as JSONL; returns the record count."""
        records = self.snapshot()
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        with open(p, "w") as fh:
            for rec in records:
                fh.write(json.dumps(rec) + "\n")
        return len(records)

    @staticmethod
    def merge_summaries(summaries: list[dict[str, Any]]) -> dict[str, Any]:
        """Fleet-wide roll-up of per-replica :meth:`summary` blocks:
        dispatch kinds and tokens sum, pressure peaks take the max, and
        occupancy percentiles report the worst replica (the one whose
        batch ran fullest — the capacity-planning signal)."""
        kinds: dict[str, int] = {}
        classes: dict[str, int] = {}
        merged: dict[str, Any] = {
            "steps_recorded": 0, "steps_total": 0, "capacity": 0,
            "tokens": 0, "prefill_tokens": 0, "decode_tokens": 0,
            "decode_row_steps": 0, "occupancy_p50": 0.0, "occupancy_p95": 0.0,
            "kv_utilization_peak": 0.0, "queue_depth_peak": 0,
        }
        for s in summaries:
            for kind, count in s.get("dispatch_kinds", {}).items():
                kinds[kind] = kinds.get(kind, 0) + count
            for cls, count in s.get("class_slot_steps", {}).items():
                classes[cls] = classes.get(cls, 0) + count
            for key in ("steps_recorded", "steps_total", "capacity",
                        "tokens", "prefill_tokens", "decode_tokens",
                        "decode_row_steps"):
                merged[key] += s.get(key, 0)
            for key in ("occupancy_p50", "occupancy_p95",
                        "kv_utilization_peak", "queue_depth_peak"):
                merged[key] = max(merged[key], s.get(key, 0))
        merged["dispatch_kinds"] = dict(sorted(kinds.items()))
        merged["class_slot_steps"] = dict(sorted(classes.items()))
        return merged

    def summary(self) -> dict[str, Any]:
        """Step-level provenance for a measured run:
        per-dispatch-kind step counts, tokens by
        side against the decode row-steps dispatched, occupancy p50/p95,
        and the KV-pressure peak over the retained window."""
        records = self.snapshot()
        kinds: dict[str, int] = {}
        classes: dict[str, int] = {}
        occ: list[float] = []
        kv_peak = 0.0
        queue_peak = 0
        tokens = prefill_tokens = decode_tokens = decode_row_steps = 0
        for rec in records:
            kinds[str(rec.get("kind", "?"))] = (
                kinds.get(str(rec.get("kind", "?")), 0) + 1)
            for cls, n in (rec.get("classes") or {}).items():
                # Slot-steps per priority class: who actually occupied
                # the decode batch over the window (the scheduler's
                # fairness evidence, tests/test_sched.py).
                classes[str(cls)] = classes.get(str(cls), 0) + int(n)
            occ.append(float(rec.get("occupancy", 0.0)))
            kv_peak = max(kv_peak, float(rec.get("kv_utilization", 0.0)))
            queue_peak = max(queue_peak, int(rec.get("queue_depth", 0)))
            tokens += int(rec.get("tokens", 0))
            prefill_tokens += int(rec.get("prefill_tokens", 0))
            decode_tokens += int(rec.get("decode_tokens", 0))
            decode_row_steps += int(rec.get("rows", 0)) * int(rec.get("k", 0))
        occ.sort()
        return {
            "steps_recorded": len(records),
            "steps_total": self.total_steps,
            "capacity": self.capacity,
            "dispatch_kinds": dict(sorted(kinds.items())),
            "class_slot_steps": dict(sorted(classes.items())),
            "tokens": tokens,
            # ``tokens`` by side, and the decode side's denominator: row x
            # step pairs dispatched (``rows`` * ``k``). decode_tokens over
            # it is the share of dispatched decode work that became a
            # token (a window runs on past a row's stop or length limit).
            "prefill_tokens": prefill_tokens,
            "decode_tokens": decode_tokens,
            "decode_row_steps": decode_row_steps,
            "occupancy_p50": round(_percentile(occ, 50), 4),
            "occupancy_p95": round(_percentile(occ, 95), 4),
            "kv_utilization_peak": round(kv_peak, 4),
            "queue_depth_peak": queue_peak,
        }
