"""Streaming incident detection: the fleet decides "an incident is
happening" from the signals it already exports.

The serving stack measures SLO burn (utils/slo.py), workload drift and
replica health (obs/monitor.py), supervisor state transitions and chaos
fault provenance (runbookai_tpu/chaos), router sheds / stale rejections
(engine/fleet.py) and queue-wait percentiles (the PR 1 histograms) — but
until now nothing folded them into a verdict. This module is the PURE
half of that fold (AIBrix's self-healing-infrastructure argument and the
reference system's own incident-investigator framing both want the
serving layer to SAY when it is in an incident, not just export gauges):

- :data:`INCIDENT_SIGNALS` is the closed signal vocabulary — the
  ``signal`` metric label set, pre-created over this literal tuple
  (bounded-label contract, RBK010-clean with zero noqa sites).
- :class:`SignalPolicy` spells one signal's thresholds and hysteresis in
  both directions: a breach must PERSIST ``open_after_s`` before an
  incident opens (a one-poll blip is noise), and an open incident must
  stay CLEAR of ``resolve_at`` for ``resolve_after_s`` before it
  resolves (a reading inside the ``resolve_at``..``open_at`` band holds
  it open — flapping traffic cannot thrash open/resolve).
- :class:`IncidentDetector` folds ``(now, readings)`` observations into
  the incident lifecycle (open → update → resolve). Decisions are pure
  functions of the observed window: the clock is an input, readings are
  plain floats, ids are sequential — seeded fixtures replay to
  **byte-identical incident JSON** (:func:`incidents_json`, pinned by
  ``tests/test_incident.py``).

The live half — reading collection, bundle capture, metrics, the poll
thread — lives in :mod:`runbookai_tpu.obs.incident`; keeping it out of
this module is what makes detection replayable evidence.

Readings use the absence contract shared with ``runbook_slo_*`` /
``runbook_workload_*``: a signal with no evidence this poll (empty
histogram window, no workload monitor attached) is simply missing from
the reading — absence is never a breach, and for an OPEN incident it
counts toward resolution (the thing being measured went quiet).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Any, Optional, Sequence

# The closed signal vocabulary. Metric children are pre-created over this
# tuple (obs/incident.py) and fault-coverage checks validate against it.
INCIDENT_SIGNALS = (
    "slo_burn",          # worst objective's current/target ratio
    "workload_drift",    # worst group's fingerprint drift score
    "replica_health",    # worst replica's composite health (low = bad)
    "replica_failure",   # replicas in failed/rebuilding/rejoining
    "router_shed",       # requests shed per poll (all replicas saturated)
    "router_stale",      # stale/rejected cross-replica pulls per poll
    "queue_wait",        # p95 submission→admission wait (s) this poll
)

# Incident JSON schema version (the bundle schema references it too).
INCIDENT_SCHEMA_VERSION = 1

# Which signal classes each injected fault kind is expected to surface
# as — the detection-coverage invariant's mapping (chaos/soak.py:
# every injected fault window must overlap a detected
# incident of a matching class). Kinds in COVERAGE_REQUIRED_KINDS are
# GATED (their detection path — supervisor transitions — is
# deterministic); the rest are reported in the coverage table but a miss
# does not fail the gate (a 10 ms kv_pull_delay legitimately detects as
# nothing).
FAULT_SIGNAL_CLASSES = {
    "replica_crash": ("replica_failure",),
    "replica_wedge": ("replica_failure",),
    "kv_pull_corrupt": ("router_stale",),
    "kv_pull_delay": ("router_stale", "queue_wait", "slo_burn"),
    "spill_pressure": ("queue_wait", "slo_burn", "replica_health"),
    "tenant_flood": ("router_shed", "queue_wait", "slo_burn"),
}
COVERAGE_REQUIRED_KINDS = ("replica_crash", "replica_wedge")


@dataclass(frozen=True)
class SignalPolicy:
    """Thresholds + two-way hysteresis for one signal.

    ``mode="gte"``: a reading >= ``open_at`` breaches, < ``resolve_at``
    clears (``resolve_at`` <= ``open_at``; between the two is the
    hysteresis band that holds an open incident open).
    ``mode="lte"`` inverts both for low-is-bad signals (replica_health).
    """

    signal: str
    open_at: float
    resolve_at: float
    mode: str = "gte"
    open_after_s: float = 0.0
    resolve_after_s: float = 5.0
    severity: str = "major"

    def __post_init__(self) -> None:
        if self.signal not in INCIDENT_SIGNALS:
            raise ValueError(f"unknown incident signal {self.signal!r}; "
                             f"valid: {INCIDENT_SIGNALS}")
        if self.mode not in ("gte", "lte"):
            raise ValueError(f"{self.signal}: mode must be gte or lte")
        band_ok = (self.resolve_at <= self.open_at if self.mode == "gte"
                   else self.resolve_at >= self.open_at)
        if not band_ok:
            raise ValueError(
                f"{self.signal}: resolve_at must sit on the clear side of "
                f"open_at (hysteresis band, not an inversion)")

    def breached(self, value: float) -> bool:
        return (value >= self.open_at if self.mode == "gte"
                else value <= self.open_at)

    def cleared(self, value: float) -> bool:
        return (value < self.resolve_at if self.mode == "gte"
                else value > self.resolve_at)

    def worse(self, value: float, than: float) -> bool:
        return value > than if self.mode == "gte" else value < than


def default_policies(*, drift_threshold: float = 0.6,
                     open_after_s: float = 5.0,
                     resolve_after_s: float = 10.0,
                     ) -> tuple[SignalPolicy, ...]:
    """The stock policy set. ``open_after_s``/``resolve_after_s`` scale
    the level-signal hysteresis (``llm.obs.incident_open_s`` /
    ``incident_resolve_s``); event-shaped signals keep their own
    constants where a single observation IS the incident (a replica in
    ``failed`` needs no persistence proof — the supervisor already
    debounced it)."""
    return (
        # Sustained burn past 1.5x target; clears under 1.1x.
        SignalPolicy("slo_burn", 1.5, 1.1, open_after_s=open_after_s,
                     resolve_after_s=resolve_after_s, severity="major"),
        # The plan-staleness threshold, held long enough to be traffic
        # and not a window artifact. Minor: drift is a retune trigger,
        # not an outage.
        SignalPolicy("workload_drift", drift_threshold,
                     0.8 * drift_threshold, open_after_s=open_after_s,
                     resolve_after_s=resolve_after_s, severity="minor"),
        # A replica pinned near zero composite health.
        SignalPolicy("replica_health", 0.1, 0.25, mode="lte",
                     open_after_s=open_after_s,
                     resolve_after_s=resolve_after_s, severity="major"),
        # Any replica the supervisor holds in failed/rebuilding/
        # rejoining: open immediately (the supervisor's own state machine
        # is the debounce), resolve once the fleet is whole again.
        SignalPolicy("replica_failure", 1.0, 1.0, open_after_s=0.0,
                     resolve_after_s=resolve_after_s, severity="critical"),
        # Sheds sustained for a full second = real saturation; a single
        # raced shed is load-shedding doing its job.
        SignalPolicy("router_shed", 1.0, 1.0, open_after_s=1.0,
                     resolve_after_s=resolve_after_s, severity="major"),
        # A rejected (stale/corrupt) pull is incident-worthy on sight —
        # digest mismatches especially are evidence to preserve.
        SignalPolicy("router_stale", 1.0, 1.0, open_after_s=0.0,
                     resolve_after_s=resolve_after_s, severity="major"),
        # p95 queue wait in whole-seconds territory, sustained.
        SignalPolicy("queue_wait", 10.0, 5.0, open_after_s=open_after_s,
                     resolve_after_s=resolve_after_s, severity="minor"),
    )


@dataclass
class _SignalState:
    breach_since: Optional[float] = None
    clear_since: Optional[float] = None


class IncidentDetector:
    """Fold ``(now, readings)`` into the incident lifecycle.

    NOT thread-safe: the caller (obs/incident.IncidentMonitor) serializes
    ``observe`` under its own lock; fixtures drive it single-threaded.
    At most one open incident per signal — concurrent breaches of one
    signal are one incident with updates, which is what an operator wants
    paged about once.
    """

    def __init__(self, policies: Optional[Sequence[SignalPolicy]] = None):
        policies = tuple(policies) if policies is not None \
            else default_policies()
        self.policies = {p.signal: p for p in policies}
        if len(self.policies) != len(policies):
            raise ValueError("duplicate signal policies")
        self._state = {s: _SignalState() for s in self.policies}
        self._open: dict[str, dict[str, Any]] = {}
        self.resolved: list[dict[str, Any]] = []
        self._seq = 0

    # ------------------------------------------------------------- fold

    def observe(self, now: float, readings: dict[str, Any],
                ) -> list[tuple[str, dict[str, Any]]]:
        """One detection fold: returns ``[(event, incident), ...]`` where
        event is ``open`` / ``update`` / ``resolve``. Pure in
        ``(now, readings, prior folds)`` — same sequence in, same events
        and byte-identical incident docs out."""
        now = float(now)
        events: list[tuple[str, dict[str, Any]]] = []
        for signal, policy in self.policies.items():
            value = readings.get(signal)
            value = None if value is None else float(value)
            st = self._state[signal]
            inc = self._open.get(signal)
            breaching = value is not None and policy.breached(value)
            if inc is None:
                if not breaching:
                    st.breach_since = None
                    continue
                if st.breach_since is None:
                    st.breach_since = now
                if now - st.breach_since >= policy.open_after_s:
                    inc = self._open_incident(signal, policy, now, value,
                                              st.breach_since)
                    st.clear_since = None
                    events.append(("open", inc))
                continue
            # Open incident: track peak / last breach, or progress the
            # resolve hysteresis. A reading inside the band (cleared by
            # neither test) resets the resolve clock without counting as
            # a fresh breach.
            if breaching:
                st.clear_since = None
                inc["last_breach_ts"] = round(now, 3)
                if policy.worse(value, inc["peak"]):
                    inc["peak"] = round(value, 6)
                    events.append(("update", inc))
            elif value is None or policy.cleared(value):
                if st.clear_since is None:
                    st.clear_since = now
                if now - st.clear_since >= policy.resolve_after_s:
                    self._resolve(inc, now)
                    st.breach_since = None
                    st.clear_since = None
                    events.append(("resolve", inc))
            else:
                st.clear_since = None
        return events

    def _open_incident(self, signal: str, policy: SignalPolicy,
                       now: float, value: float,
                       breach_since: float) -> dict[str, Any]:
        self._seq += 1
        inc = {
            "schema_version": INCIDENT_SCHEMA_VERSION,
            "id": f"inc-{self._seq:04d}",
            "signal": signal,
            "severity": policy.severity,
            "status": "open",
            "threshold": round(policy.open_at, 6),
            "mode": policy.mode,
            "breach_started_ts": round(breach_since, 3),
            "opened_ts": round(now, 3),
            "value_at_open": round(value, 6),
            "peak": round(value, 6),
            "last_breach_ts": round(now, 3),
            "resolved_ts": None,
            "duration_s": None,
            "context": {},
        }
        self._open[signal] = inc
        return inc

    def _resolve(self, inc: dict[str, Any], now: float) -> None:
        inc["status"] = "resolved"
        inc["resolved_ts"] = round(now, 3)
        inc["duration_s"] = round(now - inc["opened_ts"], 3)
        del self._open[inc["signal"]]
        self.resolved.append(inc)

    # ---------------------------------------------------------- surface

    def open_incidents(self) -> list[dict[str, Any]]:
        """Open incidents, oldest first (id order)."""
        return sorted(self._open.values(), key=lambda i: i["id"])

    def incidents(self) -> list[dict[str, Any]]:
        """Every incident this detector ever opened, in id order."""
        return sorted([*self.resolved, *self._open.values()],
                      key=lambda i: i["id"])


def incidents_json(incidents: Sequence[dict[str, Any]]) -> str:
    """Canonical JSON of a detector's incident list — the byte-identity
    surface the determinism tests pin (fixed key order, fixed rounding
    already applied at emission)."""
    return json.dumps(list(incidents), sort_keys=True, indent=2) + "\n"


__all__ = [
    "COVERAGE_REQUIRED_KINDS", "FAULT_SIGNAL_CLASSES",
    "INCIDENT_SCHEMA_VERSION", "INCIDENT_SIGNALS", "IncidentDetector",
    "SignalPolicy", "default_policies", "incidents_json",
]
