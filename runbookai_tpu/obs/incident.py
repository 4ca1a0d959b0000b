"""Live incident monitoring + black-box capture: the fleet writes its
own postmortems.

:class:`IncidentMonitor` is the live half of the detection story
(:mod:`runbookai_tpu.obs.detect` is the pure half): a poll loop folds
the signals the platform already exports — SLO burn, workload drift,
replica health, supervisor states, router sheds / stale pull
rejections, queue-wait percentiles — into :class:`IncidentDetector`
readings, and on every **open** preserves the evidence while the
incident is still happening: a bounded, schema-versioned,
content-hashed **incident bundle** written to a rotated on-disk
directory (``llm.obs.incident_dir``, oldest pruned past
``incident_max_bundles``). A bundle carries per-replica flight-recorder
tails, the ``/healthz`` body, the live workload fingerprint + drift
breakdown, the supervisor/chaos blocks (fault provenance — WAS a fault
injected when this opened), a trace JSONL tail and a full metrics
scrape — everything the reference system's incident investigator would
ask a human to paste, captured at detection time instead.

Surfaces (everywhere the platform already looks):

- ``GET /debug/incidents`` and the ``/healthz`` ``incidents`` block
  (server/openai_api.py);
- ``runbook incident list|show [--bundle]`` (cli/main.py) — works
  against a live server or straight off the bundle directory;
- ``runbook_incident_open{signal}`` (**absent** when no incident of
  that signal is open — the ``runbook_slo_*`` absence contract),
  ``runbook_incident_total{signal}`` (materialized at 0 so ``rate()``
  works from the first incident) and
  ``runbook_incident_duration_seconds{signal}`` (resolved open→resolve
  durations). Labels are pre-created over the
  :data:`~runbookai_tpu.obs.detect.INCIDENT_SIGNALS` literal tuple —
  zero noqa sites, pinned by ``tests/test_lint.py``;
- ``incident.open`` / ``incident.resolve`` tracer events, stitched into
  ``runbook timeline`` as a span band (utils/timeline.py) so a dp retry
  during an incident is visible in one view;
- the soak gate's (``chaos/soak.py``) detection-coverage invariant:
  every injected fault window must overlap a detected incident of a
  matching signal class, and the chaos-free baseline pass must open
  zero incidents (the false-positive gate).

Threading: one daemon poll thread (``poll_once`` public for
deterministic drivers — tests, fixtures). Detector state mutates only
under ``self._lock``; bundle writes, tracer events and metric bumps run
OUTSIDE it (blocking I/O under a lock is exactly what ``runbook lint``
RBK003 exists to catch).
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
from pathlib import Path
from typing import Any, Callable, Optional, Sequence

from runbookai_tpu.obs.detect import (
    INCIDENT_SIGNALS,
    IncidentDetector,
    default_policies,
)
from runbookai_tpu.obs.query import bucket_quantile, counter_increase
from runbookai_tpu.utils import metrics as metrics_mod
from runbookai_tpu.utils.trace import get_tracer

BUNDLE_SCHEMA_VERSION = 1

# The bundle `history` section's own version: lookback payload shape
# may evolve independently of the bundle envelope.
HISTORY_SCHEMA_VERSION = 1

# The store series the monitor writes each poll: the detector's input
# readings, one labelset per INCIDENT_SIGNALS entry. Store-only (never
# registered in the registry) — registering it as a gauge would make
# absent signals linger at their last stored value, breaking the
# absence contract the readings carry.
SIGNAL_SERIES = "runbook_incident_signal"

# Resolved-incident durations: seconds from open to resolve.
INCIDENT_DURATION_BUCKETS = (1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
                             300.0, 600.0, 1800.0, 3600.0)

# Resolved incidents kept in the in-memory feed (bundles persist more).
_RECENT_MAX = 32


# ------------------------------------------------------------- bundles


def bundle_hash(doc: dict[str, Any]) -> str:
    """Content hash over the canonical JSON of everything BUT the hash
    field itself — ``verify_bundle`` recomputes exactly this."""
    body = {k: v for k, v in doc.items() if k != "content_hash"}
    canonical = json.dumps(body, sort_keys=True,
                           separators=(",", ":"), default=str)
    return "sha256:" + hashlib.sha256(canonical.encode()).hexdigest()


def write_bundle(directory: str | Path, doc: dict[str, Any],
                 max_bundles: int = 16) -> Path:
    """Write one incident bundle (stamping schema version + content
    hash) and prune the oldest past ``max_bundles`` — the black box is
    bounded like the flight ring and the trace JSONL.

    Filenames lead with the capture timestamp (ms) so they sort
    chronologically ACROSS process restarts: detector ids restart at
    inc-0001 per process, and a restarted server pointed at the same
    persistent ``incident_dir`` must neither overwrite the previous
    run's postmortems nor prune the wrong "oldest"."""
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    doc = dict(doc)
    doc["schema_version"] = BUNDLE_SCHEMA_VERSION
    doc["content_hash"] = bundle_hash(doc)
    inc = doc.get("incident") or {}
    stamp = max(0, int(float(doc.get("captured_ts") or 0.0) * 1000))
    name = (f"{stamp:013d}-{inc.get('id', 'inc-0000')}"
            f"-{inc.get('signal', 'unknown')}.json")
    path = directory / name
    # The same serialization laxity as the hash (default=str): an
    # evidence value that is stringifiable but not JSON-native must not
    # desync the written bytes from the hash input — or kill the write.
    path.write_text(json.dumps(doc, indent=2, sort_keys=True,
                               default=str) + "\n")
    for stale in sorted(directory.glob("*.json"))[:-max(1, max_bundles)]:
        stale.unlink(missing_ok=True)
    return path


def load_bundle(path: str | Path) -> dict[str, Any]:
    return json.loads(Path(path).read_text())


def verify_bundle(path: str | Path) -> tuple[bool, str, str]:
    """Recompute the content hash: ``(ok, expected, actual)``. A bundle
    that fails is corrupt or hand-edited — either way not evidence."""
    doc = load_bundle(path)
    stored = str(doc.get("content_hash", ""))
    actual = bundle_hash(doc)
    return stored == actual, stored, actual


def list_bundles(directory: str | Path) -> list[Path]:
    """Bundles oldest→newest (the timestamp-prefixed names sort
    chronologically even across process restarts)."""
    directory = Path(directory)
    if not directory.is_dir():
        return []
    return sorted(directory.glob("*.json"))


# ------------------------------------------------------------- monitor


class IncidentMonitor:
    """Poll-loop incident detection over live fleets + monitors."""

    def __init__(self, fleets: Sequence[Any] = (), *,
                 cores: Optional[Sequence[Any]] = None,
                 slo_monitor: Any = None, workload_monitor: Any = None,
                 detector: Optional[IncidentDetector] = None,
                 bundle_dir: Optional[str | Path] = None,
                 max_bundles: int = 16,
                 poll_interval_s: float = 1.0,
                 flight_tail: int = 32, trace_tail: int = 64,
                 tsdb: Any = None, history_lookback_s: float = 60.0,
                 clock: Callable[[], float] = time.time,
                 registry: Optional[metrics_mod.MetricsRegistry] = None):
        self.fleets = list(fleets)
        if cores is not None:
            self.cores = list(cores)
        else:
            self.cores = [c for fleet in self.fleets
                          for c in getattr(fleet, "cores", ())]
        self.slo_monitor = slo_monitor
        self.workload_monitor = workload_monitor
        # Embedded time-series store (obs/tsdb.py). When attached, the
        # derivative-shaped readings (router sheds / stale pulls /
        # queue-wait p95) come from the STORE's samples instead of
        # hand-rolled snapshot diffs, every poll's readings are
        # ingested as the SIGNAL_SERIES history, and bundles embed a
        # pre-open lookback window. None = the PR-15 snapshot-diff
        # paths, unchanged.
        self.tsdb = tsdb
        self.history_lookback_s = float(history_lookback_s)
        self.bundle_dir = Path(bundle_dir) if bundle_dir else None
        self.max_bundles = max(1, int(max_bundles))
        self.poll_interval_s = float(poll_interval_s)
        self.flight_tail = int(flight_tail)
        self.trace_tail = int(trace_tail)
        self._clock = clock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Guards the detector + recent feed + counter baselines against
        # snapshot() readers (HTTP threads). Never held across bundle
        # writes, tracer events or metric bumps.
        self._lock = threading.Lock()
        self._detector = detector if detector is not None \
            else IncidentDetector()
        self._recent: list[dict[str, Any]] = []
        # Counter baselines for delta-shaped signals (sheds, stale
        # pulls) and the queue-wait bucket-snapshot window (the shared
        # utils/metrics.HistogramWindow) — the tsdb-off fallback paths.
        self._prev_counts: dict[str, float] = {}
        self._queue_window: Optional[metrics_mod.HistogramWindow] = None
        # End of the previous poll's store window (tsdb path): each
        # poll's derivative readings diff the store samples over
        # [previous poll, this poll].
        self._last_poll_now: Optional[float] = None
        reg = registry or metrics_mod.get_registry()
        g_open = reg.gauge(
            "runbook_incident_open",
            "Open incidents per signal class; a signal with no open "
            "incident scrapes as ABSENCE, never 0 (the runbook_slo_* "
            "contract)", labels=("signal",))
        # A rebuilt monitor takes over the scrape; stale callbacks from
        # a torn-down fleet's monitor must not keep reporting.
        g_open.clear_functions()
        c_total = reg.counter(
            "runbook_incident_total",
            "Incidents opened, by signal class (materialized at 0 so "
            "rate() works from the first incident)", labels=("signal",))
        h_duration = reg.histogram(
            "runbook_incident_duration_seconds",
            "Open-to-resolve duration of resolved incidents, by signal",
            labels=("signal",), buckets=INCIDENT_DURATION_BUCKETS)
        self._m_total = {}
        self._m_duration = {}
        for signal in INCIDENT_SIGNALS:
            g_open.labels(signal=signal).set_function(
                lambda s=signal: self._open_count_or_raise(s))
            child = c_total.labels(signal=signal)
            child.inc(0.0)
            self._m_total[signal] = child
            self._m_duration[signal] = h_duration.labels(signal=signal)

    def _open_count_or_raise(self, signal: str) -> float:
        with self._lock:
            n = sum(1 for i in self._detector.open_incidents()
                    if i["signal"] == signal)
        if n == 0:
            raise LookupError(f"{signal}: no open incident")
        return float(n)

    # ----------------------------------------------------------- lifecycle

    def start(self) -> "IncidentMonitor":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="incident-monitor")
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self.poll_once()
            except Exception:  # noqa: BLE001 — detection must survive a
                import logging  # poll hiccup; the next tick retries

                logging.getLogger(__name__).exception(
                    "incident poll failed")
            self._stop.wait(self.poll_interval_s)

    # ------------------------------------------------------------ readings

    def _max_burn(self) -> Optional[float]:
        """Worst objective's lifetime burn, WITHOUT the violation-counter
        side effect ``SLOMonitor.evaluate`` has."""
        slo = self.slo_monitor
        if slo is None or not getattr(slo, "objectives", None):
            return None
        burns = []
        for key, obj in slo.objectives.items():
            current = slo.current_ms(key)
            if current is not None:
                burns.append(current / obj["target_ms"])
        return max(burns) if burns else None

    def _max_drift(self) -> Optional[float]:
        monitor = self.workload_monitor
        if monitor is None:
            return None
        drifts = [monitor.drift(m) for m in monitor.fingerprinters]
        drifts = [d for d in drifts if d is not None]
        return max(drifts) if drifts else None

    def _min_health(self) -> Optional[float]:
        monitor = self.workload_monitor
        if monitor is None:
            return None
        healths = [monitor.replica_health(core, model)
                   for model, fp in monitor.fingerprinters.items()
                   for core in fp.cores]
        return min(healths) if healths else None

    def _unhealthy_replicas(self) -> list[Any]:
        """Global replica ids the supervisors hold in failed/rebuilding/
        rejoining — both the replica_failure reading and the context an
        opened incident carries."""
        out = []
        for fleet in self.fleets:
            sup = getattr(fleet, "supervisor", None)
            if sup is None:
                continue
            for i in range(fleet.dp):
                if sup.state_of(i) in ("failed", "rebuilding", "rejoining"):
                    out.append(fleet.replica_ids[i])
        return out

    def _counter_delta(self, key: str, total: float) -> float:
        prev = self._prev_counts.get(key)
        self._prev_counts[key] = total
        return 0.0 if prev is None else max(0.0, total - prev)

    def _queue_wait_p95(self) -> Optional[float]:
        """p95 of the queue-wait observations since the LAST poll
        (bucket-snapshot diff via the shared
        utils/metrics.HistogramWindow — the sched/feedback windowing
        idiom) — None when no request was admitted this window
        (absence)."""
        hist = metrics_mod.get_registry().get("runbook_queue_wait_seconds")
        if not isinstance(hist, metrics_mod.Histogram):
            return None
        if self._queue_window is None or self._queue_window.hist is not hist:
            self._queue_window = metrics_mod.HistogramWindow(hist)
        return self._queue_window.percentile(95)

    def _trend_readings_from_store(self, readings: dict[str, Any],
                                   now: float) -> None:
        """The derivative-shaped signals from the STORE's samples over
        [previous poll, now] — sheds / stale pulls as reset-aware
        counter increases, queue-wait p95 as a bucket-snapshot quantile
        (obs/query math, so detection and ``/debug/query`` cannot
        disagree). First poll (no window yet) and windows with no
        samples stay absent."""
        start = self._last_poll_now
        self._last_poll_now = now
        if start is None or start >= now:
            return
        for signal, metric in (
                ("router_shed", "runbook_router_shed_total"),
                ("router_stale", "runbook_router_xreplica_stale_total")):
            increases = [inc for _, pts in self.tsdb.select(
                             metric, start, now)
                         if (inc := counter_increase(pts)) is not None]
            if increases:
                readings[signal] = float(sum(increases))
        rows = bucket_quantile(
            self.tsdb.select("runbook_queue_wait_seconds_bucket",
                             start, now), 0.95)
        if rows:
            readings["queue_wait"] = max(v for _, v in rows)

    def collect(self, now: Optional[float] = None) -> dict[str, Any]:
        """One reading for the detector: every signal with live evidence
        (missing keys are the absence contract). Runs WITHOUT the
        monitor lock — every source has its own synchronization story
        (scrape-gauge torn-read tolerance)."""
        readings: dict[str, Any] = {}
        burn = self._max_burn()
        if burn is not None:
            readings["slo_burn"] = burn
        drift = self._max_drift()
        if drift is not None:
            readings["workload_drift"] = drift
        health = self._min_health()
        if health is not None:
            readings["replica_health"] = health
        if any(getattr(f, "supervisor", None) is not None
               for f in self.fleets):
            readings["replica_failure"] = float(
                len(self._unhealthy_replicas()))
        if self.tsdb is not None:
            self._trend_readings_from_store(
                readings, float(self._clock() if now is None else now))
            return readings
        sheds = [f.shed_total() for f in self.fleets
                 if hasattr(f, "shed_total")]
        if sheds:
            readings["router_shed"] = self._counter_delta(
                "router_shed", float(sum(sheds)))
        stale = [f.stale_rejections() for f in self.fleets
                 if hasattr(f, "stale_rejections")]
        if stale:
            readings["router_stale"] = self._counter_delta(
                "router_stale", float(sum(stale)))
        queue_p95 = self._queue_wait_p95()
        if queue_p95 is not None:
            readings["queue_wait"] = queue_p95
        return readings

    # ---------------------------------------------------------- detection

    def poll_once(self, now: Optional[float] = None) -> list[tuple[str, dict]]:
        """One detection fold (public so tests can drive the
        machine deterministically without the thread). Side effects —
        bundle capture, tracer events, metric bumps — run outside the
        state lock."""
        now = self._clock() if now is None else float(now)
        if self.tsdb is not None:
            # Aligned sweep: the derivative readings diff the store's
            # samples at consecutive polls, so every poll contributes
            # exactly one window endpoint (the sampler thread's own
            # cadence only adds resolution in between).
            self.tsdb.sample_once(now)
        readings = self.collect(now)
        if self.tsdb is not None:
            # The detector's input readings become first-class history:
            # what the bundle lookback and `runbook incident show`
            # render. Absent signals ingest nothing.
            for signal, value in sorted(readings.items()):
                self.tsdb.ingest(now, SIGNAL_SERIES,
                                 (("signal", signal),), float(value))
        with self._lock:
            events = self._detector.observe(now, readings)
            for kind, inc in events:
                if kind == "open":
                    inc["context"] = self._context(readings)
                elif kind == "resolve":
                    self._recent.append(dict(inc))
                    del self._recent[:-_RECENT_MAX]
            # Copies for the unlocked side-effect phase: the docs keep
            # mutating under later folds.
            emitted = [(kind, dict(inc)) for kind, inc in events]
        for kind, inc in emitted:
            self._emit(kind, inc)
        return emitted

    def _context(self, readings: dict[str, Any]) -> dict[str, Any]:
        """What was true the instant the incident opened: the replicas
        involved, the chaos windows active RIGHT NOW (fault provenance —
        an incident during an injected fault says so), and the full
        reading that tripped the detector."""
        chaos_active = []
        for fleet in self.fleets:
            chaos = getattr(fleet, "chaos", None)
            if chaos is not None:
                chaos_active.extend(chaos.active_windows())
        return {
            "replicas": self._unhealthy_replicas(),
            "chaos_active": chaos_active,
            "reading": {k: round(float(v), 6)
                        for k, v in sorted(readings.items())},
        }

    def _emit(self, kind: str, inc: dict[str, Any]) -> None:
        tracer = get_tracer()
        if kind == "open":
            self._m_total[inc["signal"]].inc()
            if tracer.enabled:
                tracer.event("incident.open", incident=inc["id"],
                             signal=inc["signal"],
                             severity=inc["severity"],
                             value=inc["value_at_open"],
                             replicas=inc["context"].get("replicas", []))
            if self.bundle_dir is not None:
                self.capture_bundle(inc)
        elif kind == "resolve":
            self._m_duration[inc["signal"]].observe(inc["duration_s"])
            if tracer.enabled:
                tracer.event("incident.resolve", incident=inc["id"],
                             signal=inc["signal"],
                             duration_s=inc["duration_s"])

    # ------------------------------------------------------------ capture

    def _trace_tail(self) -> list[dict[str, Any]]:
        tracer = get_tracer()
        if not tracer.enabled or tracer.path is None:
            return []
        try:
            lines = tracer.path.read_text().splitlines()[-self.trace_tail:]
        except OSError:
            return []
        out = []
        for line in lines:
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # the writer's in-flight partial last line
        return out

    def evidence(self) -> dict[str, Any]:
        """The black-box payload: bounded snapshots of every live
        surface, taken while the incident is still happening."""
        body: dict[str, Any] = {}
        healthz = {}
        flight = {}
        for fi, fleet in enumerate(self.fleets):
            snap_fn = getattr(fleet, "health_snapshot", None)
            scope = getattr(fleet, "model", None) or f"fleet{fi}"
            if snap_fn is not None:
                healthz[str(scope)] = snap_fn()
        for core in self.cores:
            recorder = getattr(core, "flight", None)
            if recorder is None or not recorder.enabled:
                continue
            rid = core.replica_idx if core.replica_idx is not None else 0
            flight[str(rid)] = recorder.snapshot(self.flight_tail)
        body["healthz"] = healthz
        body["flight"] = flight
        if self.workload_monitor is not None:
            body["workload"] = self.workload_monitor.snapshot()
        slo = self.slo_monitor
        if slo is not None and getattr(slo, "objectives", None):
            body["slo"] = slo.evaluate()
        body["trace_tail"] = self._trace_tail()
        body["metrics"] = metrics_mod.get_registry().render()
        return body

    def history_section(self,
                        now: Optional[float] = None,
                        ) -> Optional[dict[str, Any]]:
        """The bundle's pre-open lookback: every INCIDENT_SIGNALS entry
        with stored samples inside ``history_lookback_s`` of ``now``,
        as ``[ts, value]`` pairs from the SIGNAL_SERIES history the
        poll loop ingests. None when no store is attached (the bundle
        then carries no ``history`` key at all); a signal that was
        absent over the whole window is absent here too."""
        if self.tsdb is None:
            return None
        now = float(self._clock() if now is None else now)
        signals: dict[str, list[list[float]]] = {}
        for labels, pts in self.tsdb.select(
                SIGNAL_SERIES, now - self.history_lookback_s, now):
            name = labels.get("signal")
            if name in INCIDENT_SIGNALS:
                signals[name] = [[round(ts, 3), round(v, 6)]
                                 for ts, v in pts]
        return {"schema_version": HISTORY_SCHEMA_VERSION,
                "lookback_s": round(self.history_lookback_s, 3),
                "signals": dict(sorted(signals.items()))}

    def capture_bundle(self, inc: dict[str, Any]) -> Optional[Path]:
        """Write one incident's bundle (schema-versioned, content-hashed,
        rotation-pruned). Failures never propagate into the poll loop —
        a full disk must not stop detection."""
        doc: dict[str, Any] = {
            "captured_ts": round(self._clock(), 3),
            "incident": dict(inc),
            "evidence": self.evidence(),
        }
        history = self.history_section()
        if history is not None:
            # Inside the content-hash envelope: verify_bundle covers
            # the lookback exactly like every other evidence section.
            doc["history"] = history
        try:
            path = write_bundle(self.bundle_dir, doc,
                                max_bundles=self.max_bundles)
        except (OSError, TypeError, ValueError):
            # Full disk, or an evidence source emitting something even
            # default=str cannot serialize — detection keeps running.
            return None
        with self._lock:
            live = self._detector._open.get(inc["signal"])
            if live is not None and live["id"] == inc["id"]:
                live["bundle"] = path.name
        return path

    # ------------------------------------------------------------ surface

    def snapshot(self, full: bool = False) -> dict[str, Any]:
        """The ``/healthz`` ``incidents`` block (light) and the
        ``GET /debug/incidents`` body (``full=True`` adds the resolved
        feed and the on-disk bundle listing). ``totals`` carries only
        signals that HAVE opened incidents — absence, not a zero row per
        signal (the metric's materialized-zero lives on /metrics where
        rate() needs it)."""
        with self._lock:
            open_incidents = [dict(i)
                              for i in self._detector.open_incidents()]
            recent = [dict(i) for i in self._recent]
        totals: dict[str, int] = {}
        for inc in [*recent, *open_incidents]:
            totals[inc["signal"]] = totals.get(inc["signal"], 0) + 1
        body: dict[str, Any] = {
            "enabled": True,
            "open": open_incidents,
            "open_count": len(open_incidents),
            "totals": dict(sorted(totals.items())),
            "bundle_dir": (str(self.bundle_dir)
                           if self.bundle_dir is not None else None),
        }
        if full:
            body["recent"] = recent
            body["bundles"] = [p.name for p in list_bundles(self.bundle_dir)] \
                if self.bundle_dir is not None else []
        return body

    def incidents(self) -> list[dict[str, Any]]:
        with self._lock:
            return [dict(i) for i in self._detector.incidents()]

    # ------------------------------------------------------------ factory

    @classmethod
    def from_config(cls, llm_cfg: Any, *, fleets: Sequence[Any] = (),
                    cores: Optional[Sequence[Any]] = None,
                    slo_monitor: Any = None, workload_monitor: Any = None,
                    tsdb: Any = None,
                    ) -> Optional["IncidentMonitor"]:
        """Build from ``llm.obs`` (None when the obs layer or incident
        detection is disabled). The drift policy's open threshold tracks
        ``llm.obs.drift_threshold`` — the incident and
        ``runbook_plan_stale`` must agree on what "drifted" means."""
        obs_cfg = getattr(llm_cfg, "obs", None)
        if obs_cfg is None or not getattr(obs_cfg, "enabled", False) \
                or not getattr(obs_cfg, "incidents_enabled", True):
            return None
        detector = IncidentDetector(default_policies(
            drift_threshold=float(getattr(obs_cfg, "drift_threshold",
                                          0.35)),
            open_after_s=getattr(obs_cfg, "incident_open_s", 5.0),
            resolve_after_s=getattr(obs_cfg, "incident_resolve_s", 10.0)))
        tsdb_cfg = getattr(obs_cfg, "tsdb", None)
        return cls(
            fleets, cores=cores, slo_monitor=slo_monitor,
            workload_monitor=workload_monitor, detector=detector,
            bundle_dir=getattr(obs_cfg, "incident_dir", None),
            max_bundles=getattr(obs_cfg, "incident_max_bundles", 16),
            poll_interval_s=getattr(obs_cfg, "incident_poll_interval_s",
                                    1.0),
            tsdb=tsdb,
            history_lookback_s=getattr(tsdb_cfg, "lookback_s", 60.0)
            if tsdb_cfg is not None else 60.0)


__all__ = [
    "BUNDLE_SCHEMA_VERSION", "HISTORY_SCHEMA_VERSION",
    "INCIDENT_DURATION_BUCKETS", "SIGNAL_SERIES",
    "IncidentMonitor", "bundle_hash", "list_bundles", "load_bundle",
    "verify_bundle", "write_bundle",
]
