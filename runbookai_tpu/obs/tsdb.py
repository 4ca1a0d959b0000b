"""Embedded telemetry time-series store: give every signal a history.

Every ``runbook_*`` series the platform exports is scrape-time-only —
incident bundles freeze a single instant, the detector and the feedback
controller re-derive trends ad hoc, and the ROADMAP's autoscaler /
retune-governor items both need saturation and drift *over time* before
they can act. :class:`MetricsTSDB` closes that gap in-process: a
bounded, injected-clock sampler walks the live metrics registry
(:mod:`runbookai_tpu.utils.metrics`) every ``llm.obs.tsdb.interval_s``
seconds and appends each exposed sample — counters, gauges, and every
histogram ``_bucket``/``_sum``/``_count`` series — into a per-series
ring pruned to ``retention_s`` seconds (and hard-capped in count), with
at most ``max_series`` distinct series process-wide.

Contracts:

- **absence-not-zero is preserved end to end**: the sampler stores what
  ``metric.samples()`` exposes and nothing else, so a series the
  registry drops (a labeled callback raising — the ``runbook_slo_*``
  contract) stores NO sample for that tick, never a zero. Queries over
  an absent window return an empty result, not zeros.
- **bounded**: ring retention + count caps, a ``max_series`` cap on
  distinct series (new series past the cap are dropped and counted),
  and self-accounting through ``runbook_tsdb_series`` /
  ``runbook_tsdb_samples_total`` / ``runbook_tsdb_memory_bytes``.
- **deterministic**: the clock is injected and ``sample_once(now)`` /
  ``ingest(now, ...)`` are public, so tests drive the store
  without threads or sleeps; the query evaluator on top
  (:mod:`runbookai_tpu.obs.query`) is a pure function of (store
  contents, query, now).

Surfaces: ``GET /debug/query`` + the ``/healthz`` ``history`` block
(server/openai_api.py), ``runbook query`` (cli/main.py), incident-bundle
lookback history + store-derived detector readings (obs/incident.py),
and the soak gate's query-expressed invariants (chaos/soak.py).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Optional

from runbookai_tpu.utils import metrics as metrics_mod

# Estimated bytes per stored (ts, value) sample and per series ring —
# a deterministic accounting model (tuple of two floats + deque slot),
# not a profiler reading; runbook_tsdb_memory_bytes documents itself as
# an estimate.
_SAMPLE_BYTES = 16
_SERIES_OVERHEAD_BYTES = 160

# A series ring never holds more than this many samples regardless of
# retention math: callers may drive sample_once() faster than
# interval_s (the incident monitor aligns a sample to every poll), and
# the count cap keeps that bounded instead of trusting time pruning
# alone.
_RING_SLACK = 4


class MetricsTSDB:
    """Bounded in-process history over the live metrics registry."""

    def __init__(self, *, interval_s: float = 1.0,
                 retention_s: float = 600.0, max_series: int = 2048,
                 registry: Optional[metrics_mod.MetricsRegistry] = None,
                 clock: Callable[[], float] = time.time):
        if interval_s <= 0 or retention_s <= 0:
            raise ValueError("interval_s and retention_s must be > 0")
        self.interval_s = float(interval_s)
        self.retention_s = float(retention_s)
        self.max_series = max(1, int(max_series))
        self._registry = (registry if registry is not None
                          else metrics_mod.get_registry())
        self._clock = clock
        self._ring_cap = max(64, int(self.retention_s / self.interval_s)
                             * _RING_SLACK)
        # name -> labels-tuple -> ring of (ts, value). Guarded by
        # self._lock; the registry walk in sample_once runs OUTSIDE it
        # (scrape callbacks read live engine state and the store's own
        # self-metrics — holding the lock across them would deadlock
        # the sampler against its own accounting).
        self._series: dict[
            str, dict[tuple[tuple[str, str], ...],
                      deque[tuple[float, float]]]] = {}
        self._dropped_series = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        reg = self._registry
        g_series = reg.gauge(
            "runbook_tsdb_series",
            "Distinct series held by the embedded time-series store "
            "(obs/tsdb.py; bounded by llm.obs.tsdb.max_series)")
        g_series.set_function(lambda: float(self._count_series()))
        self._c_samples = reg.counter(
            "runbook_tsdb_samples_total",
            "Samples appended to the embedded time-series store "
            "(registry sweeps + direct ingests; drops past the series "
            "cap are not counted)")
        g_mem = reg.gauge(
            "runbook_tsdb_memory_bytes",
            "Estimated bytes held by the embedded time-series store's "
            "rings (accounting model, not a profiler reading)")
        g_mem.set_function(lambda: float(self._estimate_bytes()))

    # ----------------------------------------------------------- lifecycle

    def start(self) -> "MetricsTSDB":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="tsdb-sampler")
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self.sample_once()
            except Exception:  # noqa: BLE001 — history must survive a
                import logging  # scrape hiccup; the next tick retries

                logging.getLogger(__name__).exception("tsdb sample failed")
            self._stop.wait(self.interval_s)

    # ------------------------------------------------------------ sampling

    def sample_once(self, now: Optional[float] = None) -> int:
        """One registry sweep at ``now`` (public — tests drive
        the store deterministically without the thread). Returns the
        number of samples appended. A series the registry exposes
        nothing for this tick stores nothing — absence, never zero."""
        now = float(self._clock() if now is None else now)
        scraped: list[tuple[str, tuple[tuple[str, str], ...], float]] = []
        for metric in self._registry:
            for suffix, labels, value in metric.samples():
                scraped.append((metric.name + suffix, labels, value))
        appended = 0
        with self._lock:
            for name, labels, value in scraped:
                if self._append_locked(now, name, labels, value):
                    appended += 1
        if appended:
            self._c_samples.inc(appended)
        return appended

    def ingest(self, now: float, name: str,
               labels: Any = (), value: float = 0.0) -> bool:
        """Append one sample directly (series the registry does not
        carry: the incident monitor's per-poll detector readings, test
        fixtures). ``labels`` is a dict or an iterable of (k, v)."""
        items = labels.items() if isinstance(labels, dict) else labels
        key = tuple(sorted((str(k), str(v)) for k, v in items))
        with self._lock:
            ok = self._append_locked(float(now), str(name), key,
                                     float(value))
        if ok:
            self._c_samples.inc()
        return ok

    def _append_locked(self, now: float, name: str,
                       labels: tuple[tuple[str, str], ...],
                       value: float) -> bool:
        labels = tuple(sorted(labels))
        by_labels = self._series.get(name)
        if by_labels is None:
            by_labels = self._series[name] = {}
        ring = by_labels.get(labels)
        if ring is None:
            if self._count_series_locked() >= self.max_series:
                self._dropped_series += 1
                if not by_labels:
                    del self._series[name]
                return False
            ring = by_labels[labels] = deque(maxlen=self._ring_cap)
        ring.append((now, float(value)))
        floor = now - self.retention_s
        while ring and ring[0][0] < floor:
            ring.popleft()
        return True

    # ------------------------------------------------------------- reading

    def select(self, name: str, start: Optional[float] = None,
               end: Optional[float] = None,
               ) -> list[tuple[dict[str, str],
                               list[tuple[float, float]]]]:
        """Every series named ``name`` restricted to the CLOSED window
        ``[start, end]`` — ``(labels, samples)`` pairs sorted by
        canonical labels; series with no sample in the window are
        omitted (absence-not-zero, end to end)."""
        out: list[tuple[dict[str, str], list[tuple[float, float]]]] = []
        with self._lock:
            for labels, ring in self._series.get(name, {}).items():
                pts = [(ts, v) for ts, v in ring
                       if (start is None or ts >= start)
                       and (end is None or ts <= end)]
                if pts:
                    out.append((dict(labels), pts))
        out.sort(key=lambda row: sorted(row[0].items()))
        return out

    def series_names(self) -> list[str]:
        with self._lock:
            return sorted(n for n, d in self._series.items() if d)

    def _count_series_locked(self) -> int:
        return sum(len(d) for d in self._series.values())

    def _count_series(self) -> int:
        with self._lock:
            return self._count_series_locked()

    def _estimate_bytes(self) -> int:
        with self._lock:
            n_series = self._count_series_locked()
            n_samples = sum(len(r) for d in self._series.values()
                            for r in d.values())
        return n_samples * _SAMPLE_BYTES + n_series * _SERIES_OVERHEAD_BYTES

    def snapshot(self) -> dict[str, Any]:
        """The ``/healthz`` ``history`` block: store accounting, never
        sample payloads (those are what ``/debug/query`` is for)."""
        with self._lock:
            n_series = self._count_series_locked()
            n_samples = 0
            oldest: Optional[float] = None
            newest: Optional[float] = None
            for by_labels in self._series.values():
                for ring in by_labels.values():
                    if not ring:
                        continue
                    n_samples += len(ring)
                    first, last = ring[0][0], ring[-1][0]
                    oldest = first if oldest is None else min(oldest, first)
                    newest = last if newest is None else max(newest, last)
            dropped = self._dropped_series
        return {
            "enabled": True,
            "interval_s": self.interval_s,
            "retention_s": self.retention_s,
            "max_series": self.max_series,
            "series": n_series,
            "samples": n_samples,
            "dropped_series": dropped,
            "memory_bytes": (n_samples * _SAMPLE_BYTES
                             + n_series * _SERIES_OVERHEAD_BYTES),
            "oldest_ts": None if oldest is None else round(oldest, 3),
            "newest_ts": None if newest is None else round(newest, 3),
        }

    def clock(self) -> float:
        """The store's injected clock — evaluation 'now' defaults to it
        so queries and samples share one time base."""
        return float(self._clock())

    # ------------------------------------------------------------ factory

    @classmethod
    def from_config(cls, llm_cfg: Any,
                    registry: Optional[metrics_mod.MetricsRegistry] = None,
                    ) -> Optional["MetricsTSDB"]:
        """Build from ``llm.obs.tsdb``; None when the obs layer or the
        store is disabled — zero ``runbook_tsdb_*`` series, and every
        surface on top (``/debug/query``, the ``/healthz`` history
        block, bundle lookback history) reports itself absent."""
        obs_cfg = getattr(llm_cfg, "obs", None)
        if obs_cfg is None or not getattr(obs_cfg, "enabled", False):
            return None
        tsdb_cfg = getattr(obs_cfg, "tsdb", None)
        if tsdb_cfg is None or not getattr(tsdb_cfg, "enabled", True):
            return None
        return cls(
            interval_s=getattr(tsdb_cfg, "interval_s", 1.0),
            retention_s=getattr(tsdb_cfg, "retention_s", 600.0),
            max_series=getattr(tsdb_cfg, "max_series", 2048),
            registry=registry)


__all__ = ["MetricsTSDB"]
