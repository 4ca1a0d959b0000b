"""Tracing: host-side span JSONL + device (XProf) profiling hooks.

SURVEY.md §5.1 — the reference has no tracer; its only "trace" is per-tool
``durationMs`` plus the scratchpad JSONL. The TPU build adds the real thing:

- :class:`Tracer` — nested host spans appended as JSONL (one object per
  span: ts, t0, name, ms, depth, meta). ``ts`` is the wall clock at CLOSE;
  ``t0`` is ``time.monotonic()`` at the START: the clock of the engine's
  step and lifecycle records and of the load generator. Cheap enough to
  leave on in production; a disabled tracer costs one ``if``.
- :func:`annotate` — ``jax.profiler.TraceAnnotation`` passthrough so the
  engine's step, its phases and its dispatches show up on the
  XProf/TensorBoard timeline, on the device trace's clock, by name.
- :func:`device_trace` — context manager around
  ``jax.profiler.start_trace``/``stop_trace`` for capturing a device profile
  of any region (``RUNBOOK_DEVICE_TRACE=<logdir>`` wraps a whole CLI run).

Enable globally with ``RUNBOOK_TRACE=<file.jsonl>`` (or ``1`` for the
default ``.runbook/trace/<pid>.jsonl``) or by passing a Tracer explicitly.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import warnings
from pathlib import Path
from typing import Any, Iterator, Optional


# Default byte cap per trace file before rotation: a 1800s soak at full
# span volume stays bounded on disk instead of growing the JSONL forever.
# One rotated generation (<file>.1) is kept; RUNBOOK_TRACE_MAX_MB
# overrides (0 = unbounded).
DEFAULT_TRACE_MAX_BYTES = 256 * 1024 * 1024


class Tracer:
    """Appends nested span records to a JSONL file.

    Thread-safe: the process-wide tracer is shared across server request
    threads and the engine loop, so span depth is tracked per-thread and
    each record is written whole under a lock.

    Size-bounded: when a write would push the file past ``max_bytes``,
    the current file rotates to ``<path>.1`` (replacing any previous
    generation) and a fresh file begins — at most ~2× the cap on disk,
    with the rotation counted in ``runbook_trace_rotations_total`` so a
    soak run's dashboards see the trail turning over.
    """

    def __init__(self, path: Optional[str | Path], enabled: bool = True,
                 max_bytes: Optional[int] = DEFAULT_TRACE_MAX_BYTES):
        self.enabled = enabled and path is not None
        self.path = Path(path) if path else None
        self.max_bytes = max_bytes if max_bytes else None
        self._local = threading.local()
        self._lock = threading.Lock()
        self._fh = None
        self._bytes = 0
        self._rotations = 0
        self._warned = False
        if self.enabled:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            self._fh = open(self.path, "a", buffering=1)  # line-buffered
            try:
                self._bytes = self.path.stat().st_size
            except OSError:
                self._bytes = 0

    @property
    def _depth(self) -> int:
        return getattr(self._local, "depth", 0)

    @_depth.setter
    def _depth(self, value: int) -> None:
        self._local.depth = value

    # -------------------------------------------------------------- context

    def set_context(self, **fields: Any) -> None:
        """Attach per-thread fields to every span/event this thread writes
        until :meth:`clear_context` — e.g. the server sets
        ``request_id=<x-request-id>`` for the handler thread so a JSONL
        trace line can be joined to its request's metrics."""
        ctx = getattr(self._local, "ctx", None)
        if ctx is None:
            ctx = self._local.ctx = {}
        ctx.update(fields)

    def clear_context(self) -> None:
        self._local.ctx = {}

    def _ctx(self) -> Optional[dict[str, Any]]:
        ctx = getattr(self._local, "ctx", None)
        return dict(ctx) if ctx else None

    def _write(self, rec: dict[str, Any]) -> None:
        try:
            line = json.dumps(rec) + "\n"
            rotated = False
            with self._lock:
                if self._fh is None:
                    return  # closed deliberately: silence, not a warning
                if (self.max_bytes is not None and self._bytes > 0
                        and self._bytes + len(line) > self.max_bytes):
                    # Rotate the live file to ``<path>.1`` (replacing any
                    # previous generation) and start fresh — the swap must
                    # be atomic against the other writer threads, and it
                    # runs once per ``max_bytes`` of trace volume, so the
                    # bounded stall is the price of a bounded footprint.
                    self._fh.flush()
                    self._fh.close()
                    os.replace(self.path,
                               self.path.with_name(self.path.name + ".1"))
                    self._fh = self.path.open("a", buffering=1)
                    self._bytes = 0
                    self._rotations += 1
                    rotated = True
                self._fh.write(line)
                self._bytes += len(line)
            if rotated:
                # Metric outside the write lock (RBK003: the registry has
                # its own lock and scrape callbacks must not nest under
                # the tracer's).
                from runbookai_tpu.utils import metrics as metrics_mod

                metrics_mod.get_registry().counter(
                    "runbook_trace_rotations_total",
                    "Trace JSONL rotations at the byte cap").inc()
        except (OSError, ValueError) as e:
            # Disk gone / fh poisoned: stop tracing, keep serving — but
            # never silently (operators must learn their trail went dark).
            # Disable under the same lock close() takes (the with-block
            # above already released it on the exception path), so the
            # enabled flag has one consistent writer discipline.
            with self._lock:
                self.enabled = False
            if not self._warned:
                self._warned = True
                warnings.warn(
                    f"tracing disabled: could not write {self.path} "
                    f"({type(e).__name__}: {e})", RuntimeWarning,
                    stacklevel=3)

    @contextlib.contextmanager
    def span(self, name: str, **meta: Any) -> Iterator[None]:
        if not self.enabled:
            yield
            return
        t0 = time.monotonic()
        self._depth += 1
        depth = self._depth
        try:
            yield
        finally:
            self._depth -= 1
            rec = {"ts": time.time(), "t0": t0, "name": name, "depth": depth,
                   "ms": round((time.monotonic() - t0) * 1e3, 3)}
            ctx = self._ctx()
            if ctx:
                rec["ctx"] = ctx
            if meta:
                rec["meta"] = meta
            self._write(rec)

    def event(self, name: str, **meta: Any) -> None:
        """Zero-duration marker."""
        if not self.enabled:
            return
        rec = {"ts": time.time(), "t0": time.monotonic(), "name": name,
               "depth": self._depth + 1, "ms": 0.0}
        ctx = self._ctx()
        if ctx:
            rec["ctx"] = ctx
        if meta:
            rec["meta"] = meta
        self._write(rec)

    def close(self) -> None:
        """Flush and release the line-buffered handle; tracing stays off."""
        with self._lock:
            if self._fh:
                self._fh.flush()
                self._fh.close()
                self._fh = None
                self.enabled = False


_NULL = Tracer(None, enabled=False)
_global: Optional[Tracer] = None


def get_tracer() -> Tracer:
    """Process-wide tracer, configured from ``RUNBOOK_TRACE`` on first use."""
    global _global
    if _global is None:
        env = os.environ.get("RUNBOOK_TRACE", "")
        if not env:
            _global = _NULL
        else:
            path = (Path(".runbook") / "trace" / f"{os.getpid()}.jsonl"
                    if env == "1" else Path(env))
            max_bytes: Optional[int] = DEFAULT_TRACE_MAX_BYTES
            cap_env = os.environ.get("RUNBOOK_TRACE_MAX_MB", "")
            if cap_env:
                try:
                    mb = float(cap_env)
                    max_bytes = int(mb * 1024 * 1024) if mb > 0 else None
                except ValueError:
                    pass  # malformed cap keeps the default
            try:
                _global = Tracer(path, max_bytes=max_bytes)
            except OSError:
                _global = _NULL
    return _global


def set_tracer(tracer: Optional[Tracer]) -> None:
    global _global
    _global = tracer if tracer is not None else _NULL


def annotate(name: str, **meta: Any):
    """Named region on the profiler's host timeline (no-op off-profile).
    ``meta`` rides as the event's stats: ``annotate("engine.step",
    step=n)`` reads back as name ``engine.step``, stat ``step``."""
    import jax

    return jax.profiler.TraceAnnotation(name, **meta)


@contextlib.contextmanager
def device_trace(logdir: str | Path) -> Iterator[None]:
    """Capture an XProf device profile of the enclosed region."""
    import jax

    jax.profiler.start_trace(str(logdir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def try_device_trace(logdir: str | Path) -> Iterator[bool]:
    """:func:`device_trace` for callers that ask for a profile on whatever
    backend they run on (``runbook profile``):
    yields True when the capture started. On the CPU a ``jax.profiler``
    that cannot start yields False and the enclosed work runs unprofiled —
    dependency-free CI has nothing to trace. On a TPU the device trace is
    what was asked for, so a profiler that will not start (or stop)
    raises."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    started = False
    try:
        jax.profiler.start_trace(str(logdir))
        started = True
    except Exception:  # noqa: BLE001 — CPU: any capture failure means "skip"
        if on_tpu:
            raise
    try:
        yield started
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001 — CPU: the work already ran
                if on_tpu:
                    raise


def read_spans(path: str | Path) -> list[dict[str, Any]]:
    """Load a span JSONL (for tooling/tests)."""
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def _percentile(sorted_ms: list[float], q: float) -> float:
    """Exact nearest-rank-with-interpolation percentile of a sorted list."""
    if not sorted_ms:
        return 0.0
    pos = (len(sorted_ms) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(sorted_ms) - 1)
    return sorted_ms[lo] + (sorted_ms[hi] - sorted_ms[lo]) * (pos - lo)


# Span name -> dispatch-kind counter. One engine span = one device
# dispatch of that kind, so a trace JSONL alone reconstructs the PR-4
# counters (`runbook_prefill_dispatch_total` / `runbook_decode_dispatch_
# total` / `runbook_mixed_dispatch_total`) — engine.decode_spec is a
# decode dispatch that happened to verify a speculative draft.
_DISPATCH_SPANS = {
    "engine.prefill": "prefill_steps",
    "engine.decode": "decode_dispatches",
    "engine.decode_spec": "decode_dispatches",
    "engine.mixed": "mixed_steps",
}


def dispatch_counters(spans: list[dict[str, Any]]) -> dict[str, int]:
    """Dispatch-kind counts recovered from a span JSONL — lets a tune
    run's measured refinement be sanity-checked
    from its trace alone: a config that claims mixed dispatch but traces
    zero ``engine.mixed`` spans did not serve the config it claims."""
    out = {"prefill_steps": 0, "decode_dispatches": 0, "mixed_steps": 0}
    for rec in spans:
        key = _DISPATCH_SPANS.get(str(rec.get("name", "")))
        if key is not None:
            out[key] += 1
    return out


def summarize_spans(spans: list[dict[str, Any]]) -> dict[str, dict[str, Any]]:
    """Per-span-name latency summary: count, p50/p95/max/total ms.

    The analysis half of ``runbook metrics --trace``: joins with the
    Prometheus side through span names (engine.decode, server.request, ...)
    and per-record ``ctx.request_id``.
    """
    by_name: dict[str, list[float]] = {}
    for rec in spans:
        by_name.setdefault(str(rec.get("name", "?")), []).append(
            float(rec.get("ms", 0.0)))
    out: dict[str, dict[str, Any]] = {}
    for name in sorted(by_name):
        ms = sorted(by_name[name])
        out[name] = {
            "count": len(ms),
            "p50_ms": round(_percentile(ms, 50), 3),
            "p95_ms": round(_percentile(ms, 95), 3),
            "max_ms": round(ms[-1], 3),
            "total_ms": round(sum(ms), 3),
        }
    return out
