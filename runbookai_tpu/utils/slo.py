"""SLO monitor: configured latency objectives evaluated at scrape time.

AIBrix's lesson (PAPERS.md) is that SLO-aware routing/scheduling is only
as good as the live latency-vs-SLO signal underneath it; this module IS
that signal, computed from the PR 1 histograms the engine already
observes — no second measurement path, no per-request overhead.

Objectives are configured under ``llm.slo`` (docs/CONFIG.md) as
``<metric>_p<quantile>_ms`` targets over the engine histograms::

    llm:
      slo:
        ttft_p95_ms: 500
        tpot_p95_ms: 40
        e2e_p99_ms: 30000

Exported series (ONLY when at least one objective is configured — an
unconfigured deployment scrapes no ``runbook_slo_*`` at all):

- ``runbook_slo_target_ms{objective=...}`` — the configured target;
- ``runbook_slo_current_ms{objective=...}`` — the histogram's current
  percentile (bucket-interpolated; the series is absent until the
  histogram has observations);
- ``runbook_slo_burn_ratio{objective=...}`` — current / target; > 1 means
  the objective is burning. The sched/feedback.py controller consumes
  the same objective WINDOWED (bucket-snapshot diffs via
  :meth:`SLOMonitor.histogram`), not this lifetime gauge;
- ``runbook_slo_violations_total{objective=...}`` — evaluations (scrapes
  and ``/healthz`` probes) that observed the objective breached. A rate
  over it is "fraction of recent looks that saw a breach", not a request
  count.

All three gauges are scrape-time callbacks over the live histograms —
one source of truth, zero steady-state cost.
"""

from __future__ import annotations

import re
from typing import Any, Optional

from runbookai_tpu.utils import metrics as metrics_mod

# objective key = "<metric>_p<quantile>_ms" over these histograms.
OBJECTIVE_HISTOGRAMS = {
    "ttft": "runbook_ttft_seconds",
    "tpot": "runbook_tpot_seconds",
    "e2e": "runbook_e2e_seconds",
}
_OBJECTIVE_RE = re.compile(r"^(ttft|tpot|e2e)_p(\d{2})_ms$")


def parse_objective(key: str) -> tuple[str, float]:
    """``"ttft_p95_ms"`` -> ("runbook_ttft_seconds", 95.0); raises on an
    unknown spelling so a typo'd config fails at startup, not silently."""
    m = _OBJECTIVE_RE.match(key)
    if not m:
        raise ValueError(
            f"unknown SLO objective {key!r} (expected "
            f"<ttft|tpot|e2e>_p<quantile>_ms, e.g. ttft_p95_ms)")
    return OBJECTIVE_HISTOGRAMS[m.group(1)], float(m.group(2))


class SLOMonitor:
    """Evaluates ``{objective_key: target_ms}`` against the registry's
    latency histograms; registers the ``runbook_slo_*`` series on
    construction (never when ``targets`` is empty)."""

    def __init__(self, targets: dict[str, float],
                 registry: Optional[metrics_mod.MetricsRegistry] = None):
        self.registry = registry or metrics_mod.get_registry()
        self.objectives: dict[str, dict[str, Any]] = {}
        for key, target_ms in targets.items():
            hist_name, quantile = parse_objective(key)
            if target_ms is None:
                continue
            if float(target_ms) <= 0:
                raise ValueError(f"SLO target {key} must be > 0 ms")
            self.objectives[key] = {"hist": hist_name, "q": quantile,
                                    "target_ms": float(target_ms)}
        if not self.objectives:
            return  # no objectives -> no series, no registration
        reg = self.registry
        self._g_target = reg.gauge(
            "runbook_slo_target_ms",
            "Configured latency objective (llm.slo)", labels=("objective",))
        self._g_current = reg.gauge(
            "runbook_slo_current_ms",
            "Current bucket-interpolated percentile of the objective's "
            "histogram (absent until it has observations)",
            labels=("objective",))
        self._g_burn = reg.gauge(
            "runbook_slo_burn_ratio",
            "current/target per objective; > 1 means the objective is "
            "burning", labels=("objective",))
        self._c_violations = reg.counter(
            "runbook_slo_violations_total",
            "Evaluations (scrapes + /healthz probes) that observed the "
            "objective breached", labels=("objective",))
        for key in self.objectives:
            # runbook: noqa[RBK010] — objective label: regex-validated
            # <ttft|tpot|e2e>_p<q>_ms spellings from llm.slo, fixed at load.
            self._g_target.labels(objective=key).set_function(
                lambda k=key: self.objectives[k]["target_ms"])
            # Materialize the violation series at 0: "never breached" must
            # scrape as an explicit zero so rate() works from first breach.
            # runbook: noqa[RBK010] — objective label: regex-validated
            # <ttft|tpot|e2e>_p<q>_ms spellings from llm.slo, fixed at load.
            self._c_violations.labels(objective=key).inc(0.0)
            # current/burn raise (-> series dropped) while the histogram
            # is empty: "no data" must scrape as absence, not as 0 (a
            # burn_ratio of 0 would read as a comfortably-met SLO).
            # runbook: noqa[RBK010] — objective label: regex-validated
            # <ttft|tpot|e2e>_p<q>_ms spellings from llm.slo, fixed at load.
            self._g_current.labels(objective=key).set_function(
                lambda k=key: self._current_ms_or_raise(k))
            # runbook: noqa[RBK010] — objective label: regex-validated
            # <ttft|tpot|e2e>_p<q>_ms spellings from llm.slo, fixed at load.
            self._g_burn.labels(objective=key).set_function(
                lambda k=key: self._burn_or_raise(k))

    # ------------------------------------------------------------- internals

    def _histogram(self, key: str) -> Optional[metrics_mod.Histogram]:
        metric = self.registry.get(self.objectives[key]["hist"])
        return metric if isinstance(metric, metrics_mod.Histogram) else None

    def histogram(self, key: str) -> Optional[metrics_mod.Histogram]:
        """The live histogram behind an objective (None until the engine
        registers it). Public so consumers that need WINDOWED views —
        the sched/feedback controller diffs bucket snapshots per
        decision window — can reach the source series."""
        return self._histogram(key)

    def current_ms(self, key: str) -> Optional[float]:
        """The objective's live percentile in ms (None = no data yet)."""
        hist = self._histogram(key)
        if hist is None:
            return None
        value = hist.percentile(self.objectives[key]["q"])
        return None if value is None else value * 1e3

    def _current_ms_or_raise(self, key: str) -> float:
        value = self.current_ms(key)
        if value is None:
            raise LookupError(f"{key}: histogram empty")
        return value

    def _burn_or_raise(self, key: str) -> float:
        burn = self._current_ms_or_raise(key) / self.objectives[key]["target_ms"]
        if burn > 1.0:
            # runbook: noqa[RBK010] — objective label: regex-validated
            # <ttft|tpot|e2e>_p<q>_ms spellings from llm.slo, fixed at load.
            self._c_violations.labels(objective=key).inc()
        return burn

    # ------------------------------------------------------------------ API

    def evaluate(self) -> dict[str, dict[str, Any]]:
        """One evaluation pass for ``/healthz``: per objective,
        target, current, burn ratio, and breached (None current = the
        histogram has no observations yet). Counts breaches into
        ``runbook_slo_violations_total`` like a scrape does."""
        out: dict[str, dict[str, Any]] = {}
        for key, obj in self.objectives.items():
            current = self.current_ms(key)
            burn = (current / obj["target_ms"]
                    if current is not None else None)
            breached = burn is not None and burn > 1.0
            if breached:
                # runbook: noqa[RBK010] — objective label: regex-validated
                # <ttft|tpot|e2e>_p<q>_ms spellings from llm.slo, fixed at load.
                self._c_violations.labels(objective=key).inc()
            out[key] = {
                "target_ms": obj["target_ms"],
                "current_ms": round(current, 3) if current is not None else None,
                "burn_ratio": round(burn, 4) if burn is not None else None,
                "breached": breached,
            }
        return out

    @classmethod
    def from_config(cls, slo_cfg: Any,
                    registry: Optional[metrics_mod.MetricsRegistry] = None,
                    ) -> Optional["SLOMonitor"]:
        """Build from an ``llm.slo`` config block (utils/config.SLOConfig
        or any object with a ``targets()`` dict). None when no objective
        is set — the caller keeps serving with zero SLO surface."""
        if slo_cfg is None:
            return None
        targets = (slo_cfg.targets() if hasattr(slo_cfg, "targets")
                   else dict(slo_cfg))
        if not targets:
            return None
        return cls(targets, registry=registry)
