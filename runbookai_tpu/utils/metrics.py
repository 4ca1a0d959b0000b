"""Serving-grade metrics: Counters, Gauges, fixed-bucket Histograms, and
Prometheus text exposition — dependency-free (no prometheus_client in the
image), thread-safe, one process-wide registry.

Production LLM serving treats per-request latency histograms and cache/pool
gauges as the control signals for routing and autoscaling (AIBrix,
arXiv:2504.03648); this module is the in-tree layer every subsystem reports
through:

- engine (``engine/engine.py``): TTFT/TPOT/e2e/queue-wait histograms, KV-pool
  and scheduler gauges, and the legacy step-counter dict re-exported as
  counters (scrape-time callbacks — the dict stays the ``/healthz`` contract
  and the single source of truth; nothing is double-counted).
- server (``server/openai_api.py``): per-route request/latency metrics and
  the ``GET /metrics`` exposition endpoint.
- agent (``agent/parallel_executor.py``, ``agent/agent.py``): per-tool
  latency/error counters and LLM token-usage counters.

Contracts (enforced here, pinned by ``tests/test_metrics.py``):

- every metric name matches ``^runbook_[a-z0-9_]+$`` (no dashboard drift);
- histograms declare explicit, strictly increasing buckets;
- registration is get-or-create: re-registering a name returns the existing
  metric (engines are rebuilt freely in tests) but a type/label mismatch is
  an error, never silent aliasing.
"""

from __future__ import annotations

import bisect
import math
import re
import threading
from typing import Callable, Iterable, Optional, Sequence

METRIC_NAME_RE = re.compile(r"^runbook_[a-z0-9_]+$")
_LABEL_NAME_RE = re.compile(r"^[a-z_][a-z0-9_]*$")

# Shared bucket layouts (seconds). Callers may pass their own; these keep the
# in-tree instrumentation consistent so PromQL templates transfer.
TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                10.0, 30.0, 60.0)
TPOT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5)
E2E_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
               120.0, 300.0, 600.0)
QUEUE_WAIT_BUCKETS = TTFT_BUCKETS
REQUEST_LATENCY_BUCKETS = E2E_BUCKETS
TOOL_LATENCY_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                        10.0, 30.0, 60.0, 120.0)
# Token counts per mixed prefill+decode dispatch (powers of two up to the
# largest plausible mixed_token_budget) — a count histogram, not seconds.
MIXED_TOKENS_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0,
                        512.0, 1024.0, 2048.0, 4096.0)


def percentile_from_counts(bounds: Sequence[float],
                           counts: Sequence[float],
                           q: float) -> Optional[float]:
    """q-th percentile (0–100) from per-bucket observation counts — the
    ONE bucket-interpolation implementation. ``bounds`` are a
    histogram's finite upper bounds; ``counts`` carries one entry per
    finite bucket plus the trailing ``+Inf`` overflow (the
    :meth:`Histogram.bucket_counts` layout). Linear interpolation inside
    the winning bucket; the overflow clamps to the last finite bound.
    None when the window is empty.

    Every windowed-percentile consumer goes through here: lifetime and
    windowed :class:`Histogram` percentiles, the sched/feedback burn
    windows and obs/incident queue-wait readings (via
    :class:`HistogramWindow`), and obs/query's ``histogram_quantile()``
    over stored bucket snapshots — pinned by the parity test in
    tests/test_tsdb.py so the implementations cannot re-diverge.
    """
    counts = list(counts)
    total = sum(counts)
    if total == 0:
        return None
    target = max(1.0, math.ceil(q / 100.0 * total))
    cum = 0.0
    lower = 0.0
    for i, upper in enumerate(bounds):
        c = counts[i]
        if cum + c >= target:
            return lower + (upper - lower) * ((target - cum) / c)
        cum += c
        lower = upper
    return float(bounds[-1])


class HistogramWindow:
    """Bucket-snapshot-diff windowing over one :class:`Histogram`
    labelset — the shared spelling of "percentile of the observations
    since the last decision point" (previously hand-rolled in parallel
    by ``sched/feedback.MixedBudgetController.burn`` and
    ``obs/incident.IncidentMonitor``).

    Semantics, chosen so both call sites keep their behavior:

    - the mark advances only when a window is CONSUMED (``advance``
      returned counts), so sparse traffic accumulates until it carries
      at least ``min_obs`` observations instead of being dropped;
    - a histogram reset under us (any bucket count going backwards —
      a warm-up's reset, tests) resyncs the mark and yields None rather than
      a garbage negative window;
    - ``prime_zero=True`` makes the first window read everything
      observed so far (the feedback controller's first decision);
      the default primes at the current counts, so the first call only
      sets the mark (the incident monitor's first poll is absent).
    """

    def __init__(self, hist: "Histogram", key: tuple[str, ...] = (), *,
                 prime_zero: bool = False):
        self.hist = hist
        self.key = tuple(key)
        self._mark: Optional[list[float]] = None
        self._prime_zero = bool(prime_zero)

    def advance(self, min_obs: int = 1) -> Optional[list[float]]:
        """Per-bucket counts of the observations since the last consumed
        window, or None (too few, reset, or an unprimed first call)."""
        counts = self.hist.bucket_counts(self.key)
        if self._mark is None:
            if self._prime_zero:
                self._mark = [0.0] * len(counts)
            else:
                self._mark = counts
                return None
        if any(now < then for now, then in zip(counts, self._mark)):
            self._mark = counts
            return None
        window = [now - then for now, then in zip(counts, self._mark)]
        if sum(window) < max(1, int(min_obs)):
            return None
        self._mark = counts
        return window

    def percentile(self, q: float,
                   min_obs: int = 1) -> Optional[float]:
        """``advance()`` + interpolate in one call (the incident
        monitor's queue-wait reading)."""
        window = self.advance(min_obs)
        if window is None:
            return None
        return percentile_from_counts(self.hist.buckets, window, q)


def _escape_label_value(value: str) -> str:
    return (value.replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _escape_help(text: str) -> str:
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def _format_value(v: float) -> str:
    if v != v:  # NaN
        return "NaN"
    if v in (float("inf"), float("-inf")):
        return "+Inf" if v > 0 else "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class _Child:
    """Bound (metric, labelset) handle: ``metric.labels(route="x").inc()``."""

    __slots__ = ("_metric", "_key")

    def __init__(self, metric: "_Metric", key: tuple[str, ...]):
        self._metric = metric
        self._key = key

    def inc(self, amount: float = 1.0) -> None:
        self._metric._inc(self._key, amount)

    def dec(self, amount: float = 1.0) -> None:
        self._metric._inc(self._key, -amount)

    def set(self, value: float) -> None:
        self._metric._set(self._key, value)

    def observe(self, value: float) -> None:
        self._metric._observe(self._key, value)

    def set_function(self, fn: Callable[[], float]) -> "_Child":
        """Sample ``fn()`` at scrape time for THIS labelset (the labeled
        twin of ``_Metric.set_function`` — per-replica engine gauges bind
        one callback per replica label). Re-binding a labelset replaces
        its previous callback."""
        self._metric._set_key_function(self._key, fn)
        return self

    @property
    def value(self) -> float:
        """Stored value of THIS labelset (the labeled twin of
        ``Counter.value`` — fleet health snapshots read their own
        model's series, never a cross-group total)."""
        with self._metric._lock:
            return self._metric._values.get(self._key, 0.0)


class _Metric:
    type = "untyped"

    def __init__(self, name: str, help_text: str,
                 labels: Sequence[str] = ()):
        if not METRIC_NAME_RE.match(name):
            raise ValueError(
                f"metric name {name!r} must match {METRIC_NAME_RE.pattern}")
        for label in labels:
            if not _LABEL_NAME_RE.match(label) or label == "le":
                raise ValueError(f"bad label name {label!r} for {name}")
        self.name = name
        self.help = help_text
        self.labelnames = tuple(labels)
        self._lock = threading.Lock()
        self._values: dict[tuple[str, ...], float] = {}
        self._fn: Optional[Callable[[], float]] = None
        # Per-labelset scrape-time callbacks (labeled set_function): each
        # key's callback shadows any stored value for that key.
        self._key_fns: dict[tuple[str, ...], Callable[[], float]] = {}

    # ------------------------------------------------------------- labelling

    def labels(self, *values, **kv) -> _Child:
        if values and kv:
            raise ValueError("pass label values positionally or by name")
        if kv:
            try:
                values = tuple(kv[name] for name in self.labelnames)
            except KeyError as e:
                raise ValueError(
                    f"{self.name} expects labels {self.labelnames}") from e
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name} expects {len(self.labelnames)} label values")
        return _Child(self, tuple(str(v) for v in values))

    def _check_unlabeled(self) -> None:
        if self.labelnames:
            raise ValueError(
                f"{self.name} has labels {self.labelnames}; use .labels()")

    def set_function(self, fn: Callable[[], float]) -> "_Metric":
        """Sample ``fn()`` at scrape time instead of storing a value.

        Re-binding replaces the previous callback (an engine rebuilt in the
        same process takes over its gauges; the old engine is released).
        Unlabeled metrics only.
        """
        self._check_unlabeled()
        self._fn = fn
        return self

    def _set_key_function(self, key: tuple[str, ...],
                          fn: Callable[[], float]) -> None:
        if len(key) != len(self.labelnames):
            raise ValueError(
                f"{self.name} expects {len(self.labelnames)} label values")
        with self._lock:
            self._key_fns[key] = fn

    def clear_functions(self) -> None:
        """Drop every scrape-time callback (labeled and unlabeled). A
        rebuilt fleet calls this before re-binding so replica labelsets
        from a larger previous fleet don't keep scraping dead engines."""
        self._fn = None
        with self._lock:
            self._key_fns.clear()

    # ---------------------------------------------------------------- values

    def _inc(self, key: tuple[str, ...], amount: float) -> None:
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def _set(self, key: tuple[str, ...], value: float) -> None:
        with self._lock:
            self._values[key] = float(value)

    def _observe(self, key: tuple[str, ...], value: float) -> None:
        raise ValueError(f"{self.name} ({self.type}) does not observe()")

    def _callback_value(self) -> Optional[float]:
        if self._fn is None:
            return None
        try:
            return float(self._fn())
        except Exception:  # noqa: BLE001 — a dead engine must not 500 /metrics
            return None

    # -------------------------------------------------------------- sampling

    def samples(self) -> list[tuple[str, tuple[tuple[str, str], ...], float]]:
        """``(name_suffix, ((label, value), ...), value)`` triples."""
        out: list[tuple[str, tuple[tuple[str, str], ...], float]] = []
        cb = self._callback_value()
        if cb is not None:
            out.append(("", (), cb))
        with self._lock:
            items = sorted(self._values.items())
            key_fns = sorted(self._key_fns.items())
        # Callbacks run OUTSIDE the metric lock: they read live engine
        # state and must never deadlock a scrape against an engine step.
        seen: set[tuple[str, ...]] = set()
        for key, fn in key_fns:
            # A bound callback owns its labelset even when it raises: the
            # series is dropped, never replaced by a stale stored value
            # masquerading as live data.
            seen.add(key)
            try:
                value = float(fn())
            except Exception:  # noqa: BLE001 — dead engine must not 500 /metrics
                continue
            out.append(("", tuple(zip(self.labelnames, key)), value))
        for key, value in items:
            if key in seen:
                continue  # the callback shadows any stored value
            out.append(("", tuple(zip(self.labelnames, key)), value))
        if not out and not self.labelnames:
            out.append(("", (), 0.0))
        return out

    def reset(self) -> None:
        with self._lock:
            self._values.clear()


class Counter(_Metric):
    type = "counter"

    def inc(self, amount: float = 1.0) -> None:
        self._check_unlabeled()
        if amount < 0:
            raise ValueError("counters only increase")
        self._inc((), amount)

    def _inc(self, key: tuple[str, ...], amount: float) -> None:
        if amount < 0:
            raise ValueError("counters only increase")
        super()._inc(key, amount)

    def _set(self, key, value) -> None:
        raise ValueError(f"{self.name} is a counter; use inc()")

    @property
    def value(self) -> float:
        cb = self._callback_value()
        if cb is not None:
            return cb
        with self._lock:
            return self._values.get((), 0.0)

    def total(self) -> float:
        """Sum across every label set (equals ``value`` when unlabeled) —
        the public 'how many in all' accessor, so callers never read the
        private per-labelset storage."""
        cb = self._callback_value()
        if cb is not None:
            return cb
        with self._lock:
            return float(sum(self._values.values()))


class Gauge(_Metric):
    type = "gauge"

    def set(self, value: float) -> None:
        self._check_unlabeled()
        self._set((), value)

    def inc(self, amount: float = 1.0) -> None:
        self._check_unlabeled()
        self._inc((), amount)

    def dec(self, amount: float = 1.0) -> None:
        self._check_unlabeled()
        self._inc((), -amount)

    @property
    def value(self) -> float:
        cb = self._callback_value()
        if cb is not None:
            return cb
        with self._lock:
            return self._values.get((), 0.0)


class Histogram(_Metric):
    """Fixed-bucket histogram: cumulative ``le`` buckets + sum + count.

    Buckets are upper bounds in ascending order; an implicit ``+Inf`` bucket
    is always appended. Explicit buckets are REQUIRED — a histogram whose
    buckets are implied defaults drifts silently when the library changes.
    """

    type = "histogram"

    def __init__(self, name: str, help_text: str,
                 buckets: Sequence[float], labels: Sequence[str] = ()):
        super().__init__(name, help_text, labels)
        buckets = tuple(float(b) for b in buckets)
        if not buckets:
            raise ValueError(f"{name}: histograms require explicit buckets")
        if any(b != b or b in (float("inf"), float("-inf")) for b in buckets):
            raise ValueError(f"{name}: buckets must be finite")
        if any(b2 <= b1 for b1, b2 in zip(buckets, buckets[1:])):
            raise ValueError(f"{name}: buckets must be strictly increasing")
        self.buckets = buckets
        # key -> [per-bucket counts..., +Inf count, sum]
        self._hist: dict[tuple[str, ...], list[float]] = {}

    def observe(self, value: float) -> None:
        self._check_unlabeled()
        self._observe((), value)

    def _observe(self, key: tuple[str, ...], value: float) -> None:
        value = float(value)
        idx = bisect.bisect_left(self.buckets, value)
        with self._lock:
            state = self._hist.get(key)
            if state is None:
                state = self._hist[key] = [0.0] * (len(self.buckets) + 2)
            state[idx] += 1
            state[-1] += value

    def _inc(self, key, amount) -> None:
        raise ValueError(f"{self.name} is a histogram; use observe()")

    def _set(self, key, value) -> None:
        raise ValueError(f"{self.name} is a histogram; use observe()")

    def _set_key_function(self, key, fn) -> None:
        raise ValueError(f"{self.name} is a histogram; use observe()")

    def _state(self, key: tuple[str, ...] = ()) -> tuple[list[float], float, float]:
        with self._lock:
            state = list(self._hist.get(key)
                         or [0.0] * (len(self.buckets) + 2))
        counts = state[:-1]
        return counts, sum(counts), state[-1]

    @property
    def count(self) -> float:
        return self._state()[1]

    @property
    def sum(self) -> float:
        return self._state()[2]

    def percentile(self, q: float,
                   key: tuple[str, ...] = ()) -> Optional[float]:
        """Approximate q-th percentile (linear interpolation inside the
        bucket; the ``+Inf`` bucket clamps to the last finite bound).
        Accuracy is bounded by bucket width — good enough for tail-latency
        tracking, not for exact SLO math."""
        return self._interpolate(self._state(key)[0], q)

    def _interpolate(self, counts: list[float], q: float) -> Optional[float]:
        return percentile_from_counts(self.buckets, counts, q)

    def bucket_counts(self, key: tuple[str, ...] = ()) -> list[float]:
        """Per-bucket observation counts (finite buckets + the ``+Inf``
        overflow) — a snapshot for windowed percentiles."""
        with self._lock:
            state = list(self._hist.get(key)
                         or [0.0] * (len(self.buckets) + 2))
        return state[:-1]

    def percentile_since(self, q: float, baseline: Sequence[float],
                         key: tuple[str, ...] = ()) -> Optional[float]:
        """q-th percentile of the observations made SINCE ``baseline``
        (a prior :meth:`bucket_counts` snapshot) — the windowed view a
        feedback controller needs: a process-lifetime percentile takes
        hours of bad samples to move after a day of good ones. None when
        the window is empty (or the histogram was reset under us)."""
        counts = [max(0.0, now - then)
                  for now, then in zip(self.bucket_counts(key), baseline)]
        return self._interpolate(counts, q)

    def samples(self):
        out = []
        with self._lock:
            items = sorted(self._hist.items())
        for key, state in items:
            base = tuple(zip(self.labelnames, key))
            cum = 0.0
            for i, upper in enumerate(self.buckets):
                cum += state[i]
                out.append(("_bucket",
                            base + (("le", _format_value(upper)),), cum))
            cum += state[len(self.buckets)]
            out.append(("_bucket", base + (("le", "+Inf"),), cum))
            out.append(("_sum", base, state[-1]))
            out.append(("_count", base, cum))
        if not items and not self.labelnames:
            for upper in self.buckets:
                out.append(("_bucket", (("le", _format_value(upper)),), 0.0))
            out.append(("_bucket", (("le", "+Inf"),), 0.0))
            out.append(("_sum", (), 0.0))
            out.append(("_count", (), 0.0))
        return out

    def reset(self) -> None:
        with self._lock:
            self._hist.clear()


class MetricsRegistry:
    """Named metric store with get-or-create registration and exposition."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: dict[str, _Metric] = {}

    def _register(self, cls, name: str, help_text: str,
                  labels: Sequence[str], **kw) -> _Metric:
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if not isinstance(existing, cls):
                    raise ValueError(
                        f"{name} already registered as {existing.type}")
                if existing.labelnames != tuple(labels):
                    raise ValueError(
                        f"{name} already registered with labels "
                        f"{existing.labelnames}")
                want = kw.get("buckets")
                if want is not None and tuple(
                        float(b) for b in want) != existing.buckets:
                    raise ValueError(
                        f"{name} already registered with buckets "
                        f"{existing.buckets}")
                return existing
            metric = cls(name, help_text, labels=labels, **kw)
            self._metrics[name] = metric
            return metric

    def counter(self, name: str, help_text: str,
                labels: Sequence[str] = ()) -> Counter:
        return self._register(Counter, name, help_text, labels)

    def gauge(self, name: str, help_text: str,
              labels: Sequence[str] = ()) -> Gauge:
        return self._register(Gauge, name, help_text, labels)

    def histogram(self, name: str, help_text: str, *,
                  buckets: Sequence[float],
                  labels: Sequence[str] = ()) -> Histogram:
        return self._register(Histogram, name, help_text, labels,
                              buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def __iter__(self) -> Iterable[_Metric]:
        with self._lock:
            return iter(sorted(self._metrics.values(),
                               key=lambda m: m.name))

    def render(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        lines: list[str] = []
        for metric in self:
            lines.append(f"# HELP {metric.name} {_escape_help(metric.help)}")
            lines.append(f"# TYPE {metric.name} {metric.type}")
            for suffix, labels, value in metric.samples():
                if labels:
                    body = ",".join(
                        f'{k}="{_escape_label_value(v)}"' for k, v in labels)
                    lines.append(f"{metric.name}{suffix}{{{body}}} "
                                 f"{_format_value(value)}")
                else:
                    lines.append(
                        f"{metric.name}{suffix} {_format_value(value)}")
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """Flat JSON-friendly view (``/healthz`` extensions, tooling).

        Counters/gauges map to numbers (labeled children keyed by
        ``name{a=b,...}``); histograms map to {count, sum, p50, p95, p99}.
        """
        out: dict = {}
        for metric in self:
            if isinstance(metric, Histogram):
                keys = {()} if not metric.labelnames else set()
                with metric._lock:
                    keys |= set(metric._hist)
                for key in sorted(keys):
                    counts, total, s = metric._state(key)
                    name = metric.name
                    if key:
                        body = ",".join(f"{k}={v}" for k, v
                                        in zip(metric.labelnames, key))
                        name = f"{name}{{{body}}}"
                    out[name] = {
                        "count": total, "sum": round(s, 6),
                        "p50": metric.percentile(50, key),
                        "p95": metric.percentile(95, key),
                        "p99": metric.percentile(99, key),
                    }
                continue
            for _suffix, labels, value in metric.samples():
                name = metric.name
                if labels:
                    body = ",".join(f"{k}={v}" for k, v in labels)
                    name = f"{name}{{{body}}}"
                out[name] = value
        return out

    def reset(self) -> None:
        """Zero every metric's stored state (tests, a warm-up)."""
        for metric in self:
            metric.reset()


REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry every in-tree subsystem reports through."""
    return REGISTRY
