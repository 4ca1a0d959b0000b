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
first admitted and retired in it (``docs/observability.md``). A DISPATCH
is a span of its own (:class:`DispatchLedger`): under the overlapped
pipeline a step issues one dispatch and fetches another, so a record
lists the dispatches that came back in it, and a retired request's
lifecycle record says which it rode.

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
  and the AsyncFleet aggregation, :meth:`dump_jsonl` for offline diffing.
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Any, Optional

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
    # The dispatches whose last tokens reached the host since the record
    # before (``DISPATCH_FIELDS``, ``DispatchLedger``): under the
    # overlapped pipeline NOT the ones ``program`` names, which this step
    # issued and a later one fetches.
    "dispatches",
    # Calls of the sampler by the programs this step DISPATCHED (``k`` for
    # a ``_decode_multi``, two for a mixed step, one for a ``_decode_step``
    # or a prefill's first tokens) and, of them, those with a row that
    # samples, which sort the vocabulary (``ops/sampling.py``
    # ``needs_sort``): {"calls", "sorted"}.
    "sampler",
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
# ``2 * rounds`` tokens of it. ``window``: where the model has window layers
# (models/afmoe.py), in every step, over the sequences that live:
# ``rows_kept`` (token rows the window layers' pool holds for them),
# ``rows_context`` (their contexts: what the rows would be with no window),
# ``rows_kept_max`` (the most one of them holds: never over
# ``WindowSpec.rows_bound``), ``rows_released`` (rows this step gave back
# behind the window), ``rows_seen`` (over the DECODING sequences, the rows
# inside the window: ``min(context, window)`` each, what one layer's decode
# walk must read), and of the step's prefill chunks ``chunk_pairs`` (query-key
# pairs inside the window) and ``chunk_rows_seen`` (the distinct key rows
# their queries see).
OPTIONAL_STEP_FIELDS = ("experts", "state", "spec", "window")

# ``phases`` keys besides "other" (= wall_s less their sum), and the
# profiler span that marks the same boundaries on the device trace's
# clock ("issue" is the dispatch annotation: prefill / mixed / decode /
# decode_spec).
STEP_PHASES = ("admit", "build", "issue", "fetch", "emit", "draft")
PHASE_SPANS = {"admit": "engine.admit", "build": "engine.build",
               "fetch": "engine.fetch_tokens", "emit": "engine.emit",
               "draft": "engine.draft"}

# One entry of ``dispatches``: a dispatch of a step program as a span of
# its own (``DispatchLedger``). ``n`` numbers the engine's dispatches
# since the ledger began; ``program``, ``k``, ``rows`` and
# ``kv_pages_live`` are what ``OpenStep.dispatched`` got, ``prefill_tokens``
# the prompt tokens it computed; ``t_issued`` and ``t_ready`` its two
# stamps on time.monotonic(); ``tokens`` what it gave its rows, after
# stops (rounds: ``drafted + accepted``).
DISPATCH_FIELDS = ("n", "program", "k", "rows", "kv_pages_live",
                   "prefill_tokens", "t_issued", "t_ready", "tokens")

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
    # What the request waited behind over (t_first_token, t_finished]
    # (``DispatchLedger.rode``); None for one that had no first token.
    "rode",
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


class DispatchLedger:
    """The engine's dispatches as spans, and the one definition of a
    dispatch's device-side interval on the host's clock.

    A dispatch has two stamps: ``t_issued``, read when its jitted call
    returned, and ``t_ready``, read when the first ``device_get`` that
    consumed its result returned. The device runs one engine's dispatches
    in the order they were issued, so the time from the ready stamp before
    this one's (``n - 1``'s) to ``t_ready`` falls in two parts::

        (t_ready(n - 1), max(t_issued(n), t_ready(n - 1))]   "between"
        (max(t_issued(n), t_ready(n - 1)), t_ready(n)]       the dispatch's

    Queued behind ``n - 1`` the device goes from one to the next and the
    whole of it is ``n``'s; where the host issued late, the part before
    ``t_issued`` is time the device had nothing of this engine's. The
    intervals tile the clock from the ledger's start to the newest ready
    stamp. The ledger keeps their sums (dispatches and seconds by
    program, and ``between``), so that what a request waited behind is two
    reads of them (:meth:`at`) and a subtraction (:meth:`rode`), nothing a
    row a step.

    A stamp is the HOST's, so the interval is what the host saw of the
    dispatch, an UPPER bound of the device's time in it: ``t_ready`` is
    late by whatever kept the step thread from its fetch, and that time
    goes to the dispatch, not to the one after it. Under the overlapped
    pipeline a step issues ``n + 1`` and only then fetches ``n``, so
    ``t_ready(n) > t_issued(n + 1)`` always and ``between`` is 0 by
    construction: where the host issued ``n + 1`` after the device had
    finished ``n``, the device's idle time is inside ``n``'s interval.
    ``between`` sees a DRAINED pipeline only (an empty engine, a
    synchronous dispatch, a first-token fetch). The device's own idle
    share is the profiler's to give, not this ledger's.

    Written by the step thread only; it lives and dies with the
    recorder's ring (:meth:`FlightRecorder.reset`). A dispatch in flight
    across a reset is of the ledger that went: this one stamps, books and
    logs nothing of it (:meth:`mine`)."""

    __slots__ = ("n", "t0", "t_ready", "in_flight", "count", "seconds",
                 "between", "log")

    def __init__(self):
        self.n = 0  # the next dispatch's number
        # The ledger's start, and the newest ready stamp (until there is
        # one, the start).
        self.t0 = self.t_ready = time.monotonic()
        # Issued and not ready, by number: (entry, an array of its result
        # to wait on, whether tokens will be emitted from it).
        self.in_flight: list[tuple[dict[str, Any], Any, bool]] = []
        self.count: dict[str, int] = {}
        self.seconds: dict[str, float] = {}
        self.between = 0.0
        # Entries the next step record takes: ready, their tokens emitted.
        self.log: list[dict[str, Any]] = []

    def open(self, program: str, k: int, rows: int, kv_pages_live: int,
             prefill_tokens: int) -> dict[str, Any]:
        """The entry of the dispatch about to be issued (``n`` is also the
        ``dispatch`` stat of its annotation on the profiler's clock)."""
        entry = {"n": self.n, "program": program, "k": k, "rows": rows,
                 "kv_pages_live": kv_pages_live,
                 "prefill_tokens": prefill_tokens, "t_issued": None,
                 "t_ready": None, "tokens": 0}
        self.n += 1
        return entry

    def issued(self, entry: dict[str, Any], result: Any, emits: bool) -> None:
        """The jitted call returned. ``emits``: the engine will call
        :meth:`emitted` once the dispatch's last token is out; a dispatch
        that gives no token (a prefill chunk short of its prompt's end) is
        logged as soon as it is ready."""
        entry["t_issued"] = time.monotonic()
        self.in_flight.append((entry, result, emits))

    def before(self, entry: dict[str, Any]) -> list[tuple[dict[str, Any], Any]]:
        """Dispatches issued before ``entry`` and not ready: the engine
        waits on each, in order, before it fetches ``entry``'s result, so
        that every dispatch has a ready stamp of its own."""
        return [(e, result) for e, result, _ in self.in_flight
                if e["n"] < entry["n"]]

    def mine(self, entry: dict[str, Any]) -> bool:
        """Whether this ledger issued the dispatch (one issued before the
        ledger began is of the ledger a reset replaced)."""
        return entry["t_issued"] is not None and entry["t_issued"] >= self.t0

    def ready(self, entry: dict[str, Any]) -> None:
        """The first fetch of the dispatch's result returned: stamp it and
        book its interval (a second fetch of it changes nothing)."""
        if entry["t_ready"] is not None or not self.mine(entry):
            return
        now = entry["t_ready"] = time.monotonic()
        start = max(entry["t_issued"], self.t_ready)
        self.between += start - self.t_ready
        program = entry["program"]
        self.count[program] = self.count.get(program, 0) + 1
        self.seconds[program] = self.seconds.get(program, 0.0) + now - start
        self.t_ready = now
        for i, (e, _, emits) in enumerate(self.in_flight):
            if e is entry:
                del self.in_flight[i]
                if not emits:
                    self.log.append(entry)
                break

    def emitted(self, entry: dict[str, Any]) -> None:
        if self.mine(entry):
            self.log.append(entry)

    def take_log(self) -> list[dict[str, Any]]:
        """The entries for the step record being made, by number (a
        prefill's first token is fetched and emitted ahead of the window
        that was in flight when it was issued)."""
        log, self.log = self.log, []
        log.sort(key=lambda entry: entry["n"])
        return log

    def at(self, t: float) -> tuple[dict[str, int], dict[str, float], float]:
        """(dispatches, seconds by program, ``between``) as they stand at
        ``t``, a time not before the newest ready stamp: the sums, and the
        open interval up to ``t`` by the same rule (the dispatch the
        device is in, where one is in flight)."""
        seconds, between = dict(self.seconds), self.between
        if self.in_flight:
            head = self.in_flight[0][0]
            start = min(max(head["t_issued"], self.t_ready), t)
            program = head["program"]
            seconds[program] = seconds.get(program, 0.0) + max(0.0, t - start)
        else:
            start = t
        between += max(0.0, start - self.t_ready)
        return dict(self.count), seconds, between

    def rode(self, mark: tuple, t: float,
             tokens: dict[str, int]) -> dict[str, Any]:
        """A lifecycle record's ``rode``: ``{program: [dispatches made
        ready, tokens they gave this request, seconds], ..., "between":
        seconds}`` from ``mark`` (:meth:`at` the request's first token) to
        ``t``. The seconds add up to ``t`` less the mark's time."""
        count0, seconds0, between0 = mark
        count, seconds, between = self.at(t)
        out: dict[str, Any] = {}
        for program in sorted(set(seconds) | set(tokens)):
            row = [count.get(program, 0) - count0.get(program, 0),
                   tokens.get(program, 0),
                   seconds.get(program, 0.0) - seconds0.get(program, 0.0)]
            if any(row):
                out[program] = row
        out["between"] = between - between0
        return out


class FlightRecorder:
    """Preallocated ring of the last ``capacity`` step records."""

    __slots__ = ("capacity", "_buf", "_next", "dispatches")

    def __init__(self, capacity: int):
        self.capacity = max(0, int(capacity))
        self._buf: list[Optional[dict[str, Any]]] = [None] * self.capacity
        self._next = 0  # monotonically increasing step cursor
        # None with the recorder off: no dispatch is numbered or stamped.
        self.dispatches = DispatchLedger() if self.capacity else None

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
        if self.dispatches is not None:
            self.dispatches = DispatchLedger()

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
