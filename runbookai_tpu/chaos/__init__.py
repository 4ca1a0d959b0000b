"""Chaos hardening for the serving fleet (docs/robustness.md).

Two halves, composable but independent:

- :mod:`~runbookai_tpu.chaos.inject` — deterministic, seeded fault
  injection: :class:`FaultSchedule` (same seed ⇒ byte-identical plan)
  applied to a live fleet by :class:`ChaosInjector` through documented
  seams (``EngineCore.chaos_hook``, ``AsyncFleet.chaos_pull_hook``).
- :mod:`~runbookai_tpu.chaos.supervisor` — :class:`FleetSupervisor`:
  heartbeat-driven detection of dead/wedged replicas, in-flight
  failover through the router's retry path, online replica rebuild
  (``AsyncFleet.rebuild_replica``) and hysteresis-guarded rejoin.

The soak gate (:mod:`~runbookai_tpu.chaos.soak`; ``python -m
runbookai_tpu.chaos.soak``) drives both against the full composed stack
and returns a verdict per production invariant (zero lost requests
outside fault windows, TTFT bounds, fairness, RSS/fd bounds, seeded
digest determinism) — the serving twin of tier-1.
"""

from runbookai_tpu.chaos.inject import (
    FAULT_KINDS,
    ChaosInjector,
    ChaosReplicaCrash,
    FaultEvent,
    FaultSchedule,
)
from runbookai_tpu.chaos.supervisor import SUPERVISOR_STATES, FleetSupervisor

__all__ = [
    "FAULT_KINDS",
    "SUPERVISOR_STATES",
    "ChaosInjector",
    "ChaosReplicaCrash",
    "FaultEvent",
    "FaultSchedule",
    "FleetSupervisor",
]
