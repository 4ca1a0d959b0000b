"""Workload monitor: drift detection, plan-staleness, replica health.

:class:`WorkloadMonitor` owns one
:class:`~runbookai_tpu.obs.fingerprint.WorkloadFingerprinter` per served
model group and compares each live fingerprint against that group's
**reference descriptor** — the serving plan's provenance ``workload``
block when a plan is pinned (``llm.plan`` / ``llm.models[].plan``), the
``llm.obs.workload`` block otherwise, and the tuner's default
:class:`~runbookai_tpu.autotune.cost_model.Workload` as the last resort.
The comparison is the observation half of ROADMAP item 3's closed loop:
``runbook_workload_drift_score`` crossing ``llm.obs.drift_threshold``
(scraped as ``runbook_plan_stale``) is the retune trigger a future
governor subscribes to; this layer itself changes NOTHING — no plan is
swapped, no traffic moved, so byte-identity with an unmonitored engine
is structural.

Exported series (absent-not-zero, the ``runbook_slo_*`` contract: an
empty/warmup window drops the series rather than scraping drift=0):

- ``runbook_workload_{prompt_len_p50,output_len_p50,concurrency,
  guided_share,spec_hit_rate,prefix_cache_share,window_requests}{model}``
- ``runbook_workload_drift_score{model}`` / ``runbook_plan_stale{model}``
- ``runbook_replica_health{replica,model}`` — composite SLO-burn x queue
  x KV-pressure x drift score in [0, 1]; the admission signal ROADMAP
  item 2's autoscaler will consume (present whenever the monitor is on —
  health is computable before the first fingerprint).

Surfaces: ``GET /debug/workload`` and the ``/healthz`` ``workload``
block (per-group + merged fleet-wide, like ``debug_steps``), the
``runbook workload`` CLI, and a rotated on-disk
fingerprint history with window provenance (``llm.obs.history_dir``).
"""

from __future__ import annotations

import json
import threading
from pathlib import Path
from typing import Any, Callable, Optional, Sequence

from runbookai_tpu.obs.fingerprint import (
    DEFAULT_DRIFT_THRESHOLD,
    DESCRIPTOR_KEYS,
    WorkloadFingerprinter,
    build_fingerprint,
    drift_score,
)
from runbookai_tpu.utils import metrics as metrics_mod

# How long a computed fingerprint is reused across scrape callbacks: one
# /metrics scrape samples ~8 workload gauges per model, and each would
# otherwise re-fold the window.
_FINGERPRINT_MEMO_S = 1.0


class FingerprintHistory:
    """Rotated on-disk fingerprint trail with window provenance.

    One JSON file per recording (``fingerprint-<seq>.json``), oldest
    pruned past ``max_files`` — a soak's history is bounded like the
    flight ring and the trace JSONL. Each file carries the window span
    and sample counts the fingerprint was folded from, so a retune
    decision is auditable against the exact traffic that motivated it.
    """

    def __init__(self, directory: str | Path, max_files: int = 64):
        self.dir = Path(directory)
        self.max_files = max(1, int(max_files))

    def _existing(self) -> list[Path]:
        if not self.dir.is_dir():
            return []
        return sorted(self.dir.glob("fingerprint-*.json"))

    def record(self, entry: dict[str, Any]) -> Path:
        self.dir.mkdir(parents=True, exist_ok=True)
        existing = self._existing()
        seq = 0
        if existing:
            try:
                seq = int(existing[-1].stem.split("-")[-1]) + 1
            except ValueError:
                seq = len(existing)
        path = self.dir / f"fingerprint-{seq:08d}.json"
        path.write_text(json.dumps(entry, indent=2, sort_keys=True) + "\n")
        for stale in self._existing()[:-self.max_files]:
            stale.unlink(missing_ok=True)
        return path

    def entries(self) -> list[dict[str, Any]]:
        out = []
        for path in self._existing():
            try:
                out.append(json.loads(path.read_text()))
            except (OSError, json.JSONDecodeError):
                continue
        return out


def reference_descriptor(llm_cfg: Any,
                         plan_path: Optional[str] = None,
                         ) -> tuple[dict[str, Any], str]:
    """Resolve the descriptor a group's live fingerprint is judged
    against: plan provenance workload > ``llm.obs.workload`` > tuner
    defaults. Returns ``(descriptor, source)`` — the source string rides
    into ``/debug/workload`` so an operator can see WHICH yardstick the
    drift score measures."""
    from runbookai_tpu.autotune.cost_model import Workload

    if plan_path:
        try:
            from runbookai_tpu.autotune.plan import load_plan

            plan = load_plan(plan_path)
            wl = {k: plan.workload[k] for k in DESCRIPTOR_KEYS
                  if k in plan.workload}
            if wl:
                base = Workload().to_dict()
                base.update(wl)
                return base, f"plan:{plan.plan_id}"
        except ValueError:
            pass  # invalid plan already refused loudly at engine build
    obs_cfg = getattr(llm_cfg, "obs", None)
    configured = getattr(obs_cfg, "workload", None)
    if configured is not None:
        return dict(configured.to_descriptor()), "config:llm.obs.workload"
    return Workload().to_dict(), "default"


def replica_health(core: Any, *, burn: Optional[float] = None,
                   drift: Optional[float] = None) -> float:
    """Composite per-replica health in [0, 1]: the product of four
    normalized factors — SLO burn (1 while the worst objective is inside
    target, 1/burn past it), queue depth (vs one batch of slots), KV
    pressure (free-page headroom), and workload drift (1 - score). A
    replica at 1.0 is serving its tuned workload with headroom; the
    autoscaler-facing admission signal (ROADMAP item 2) degrades
    multiplicatively because any single exhausted axis makes the replica
    a bad placement regardless of the others."""
    slots = max(1, core.ecfg.max_batch_slots)
    queue = len(core.waiting) + len(core.prefilling)
    queue_factor = 1.0 / (1.0 + queue / slots)
    kv_factor = max(0.0, 1.0 - float(core.kv.utilization()))
    burn_factor = (1.0 if burn is None or burn <= 1.0
                   else 1.0 / max(burn, 1.0))
    drift_factor = 1.0 - min(1.0, drift or 0.0)
    return round(queue_factor * kv_factor * burn_factor * drift_factor, 4)


class WorkloadMonitor:
    """Per-model fingerprinters + drift scoring + the metric surface."""

    def __init__(self, fingerprinters: dict[str, WorkloadFingerprinter],
                 references: dict[str, tuple[dict[str, Any], str]], *,
                 drift_threshold: float = DEFAULT_DRIFT_THRESHOLD,
                 slo_monitor: Any = None, tenants: Any = None,
                 history: Optional[FingerprintHistory] = None,
                 history_interval_s: float = 60.0,
                 clock: Optional[Callable[[], float]] = None,
                 registry: Optional[metrics_mod.MetricsRegistry] = None):
        if not fingerprinters:
            raise ValueError("a workload monitor needs >= 1 fingerprinter")
        self.fingerprinters = dict(fingerprinters)
        # Injected clock seam (the supervisor's flap-damping pattern):
        # history-rotation intervals and the scrape memo are pure
        # functions of it, so interval tests drive a fake clock instead
        # of wall-clock sleeps. Defaults to the first fingerprinter's
        # clock so window math and rotation timing cannot disagree.
        self._clock = clock if clock is not None else \
            next(iter(self.fingerprinters.values()))._clock
        self.references = {name: references.get(name, ({}, "default"))
                           for name in fingerprinters}
        self.drift_threshold = float(drift_threshold)
        self.slo_monitor = slo_monitor
        self.tenants = tenants
        self.history = history
        self.history_interval_s = float(history_interval_s)
        self._history_last = 0.0
        self._memo: dict[str, tuple[float, Optional[dict]]] = {}
        self._memo_lock = threading.Lock()
        for fp in self.fingerprinters.values():
            fp.install_taps()
        self._install_metrics(registry or metrics_mod.get_registry())

    # ----------------------------------------------------------- folding

    def _fp(self, model: str) -> Optional[dict[str, Any]]:
        """Memoized fingerprint (one fold serves a whole scrape pass)."""
        now = self._clock()
        with self._memo_lock:
            cached = self._memo.get(model)
            if cached is not None and now - cached[0] < _FINGERPRINT_MEMO_S:
                return cached[1]
        fp = self.fingerprinters[model].fingerprint(now)
        with self._memo_lock:
            self._memo[model] = (now, fp)
        return fp

    @staticmethod
    def _drift_of(fp: dict[str, Any], reference: dict[str, Any]) -> float:
        # No step evidence in the window (recorder off / ring aged out):
        # the concurrency dimension is excluded rather than scored off a
        # floor value that would fabricate drift.
        skip = ("concurrency",) if fp.get("concurrency") is None else ()
        return drift_score(fp["workload"], reference, skip=skip)

    def drift(self, model: str) -> Optional[float]:
        fp = self._fp(model)
        if fp is None:
            return None
        return self._drift_of(fp, self.references[model][0])

    def plan_stale(self, model: str) -> Optional[bool]:
        d = self.drift(model)
        return None if d is None else d > self.drift_threshold

    # Memo key for the merged fold — cannot collide with a served model
    # name (config names never carry parentheses).
    _MERGED_KEY = "(fleet)"

    def merged_fingerprint(self, now: Optional[float] = None
                           ) -> Optional[dict[str, Any]]:
        """Fleet-wide fingerprint: every group's window folded together
        (the ``debug_steps`` merge contract — one traffic picture for
        the whole endpoint). Memoized like the per-model folds (snapshot
        is wired into /healthz, and a health probe must not re-sort 4k
        samples per call); a single-group monitor reuses that group's
        already-memoized fingerprint instead of folding the identical
        window twice."""
        if len(self.fingerprinters) == 1:
            fp = self._fp(next(iter(self.fingerprinters)))
            return None if fp is None else {**fp, "model": "fleet"}
        now = self._clock() if now is None else float(now)
        with self._memo_lock:
            cached = self._memo.get(self._MERGED_KEY)
            if cached is not None and now - cached[0] < _FINGERPRINT_MEMO_S:
                return cached[1]
        fps = list(self.fingerprinters.values())
        window_s = max(fp.window_s for fp in fps)
        t0 = now - window_s
        samples = [s for fp in fps for s in fp.samples()]
        steps = [r for fp in fps for r in fp._step_records(t0)]
        metrics: dict[str, float] = {}
        for fp in fps:
            for key, value in fp._metrics().items():
                metrics[key] = metrics.get(key, 0) + value
        merged = build_fingerprint(samples, steps, metrics, model="fleet",
                                   window=(t0, now))
        with self._memo_lock:
            self._memo[self._MERGED_KEY] = (now, merged)
        return merged

    # ----------------------------------------------------------- surface

    def snapshot(self) -> dict[str, Any]:
        """``GET /debug/workload`` / ``/healthz`` body: per-group
        fingerprint + drift + staleness, a merged fleet-wide view, and
        the cumulative per-tenant admission mix when tenancy is on."""
        models: dict[str, Any] = {}
        for name in self.fingerprinters:
            fp = self._fp(name)
            reference, source = self.references[name]
            d = self._drift_of(fp, reference) if fp is not None else None
            models[name] = {
                "fingerprint": fp,
                "drift_score": d,
                "plan_stale": (None if d is None
                               else d > self.drift_threshold),
                "reference": reference,
                "reference_source": source,
            }
        drifts = [m["drift_score"] for m in models.values()
                  if m["drift_score"] is not None]
        body: dict[str, Any] = {
            "enabled": True,
            "drift_threshold": self.drift_threshold,
            "models": models,
            "merged": self.merged_fingerprint(),
            # Fleet-wide staleness is the WORST group: one stale model on
            # a shared endpoint is a retune trigger even while siblings
            # still match their plans.
            "drift_score": max(drifts) if drifts else None,
            "plan_stale": (max(drifts) > self.drift_threshold
                           if drifts else None),
        }
        if self.tenants is not None:
            body["tenant_mix"] = self._tenant_mix()
        self._maybe_record(body)
        return body

    def _tenant_mix(self) -> dict[str, Any]:
        """Cumulative per-tenant admitted-request shares from the
        governor's counters (the workload's WHO axis; the fingerprint
        covers the WHAT)."""
        try:
            snap = self.tenants.snapshot()
        except Exception:  # noqa: BLE001 — observability never fails a scrape
            return {}
        counts = {name: int(row.get("admitted", 0))
                  for name, row in snap.get("tenants", {}).items()}
        total = sum(counts.values())
        return {name: {"admitted": n,
                       "share": round(n / total, 4) if total else 0.0}
                for name, n in sorted(counts.items())}

    def _maybe_record(self, body: dict[str, Any]) -> None:
        if self.history is None:
            return
        now = self._clock()
        if now - self._history_last < self.history_interval_s:
            return
        self._history_last = now
        entry = {
            "recorded_ts": round(now, 3),
            "drift_threshold": self.drift_threshold,
            "models": {
                name: {
                    "fingerprint": m["fingerprint"],
                    "drift_score": m["drift_score"],
                    "plan_stale": m["plan_stale"],
                    "reference_source": m["reference_source"],
                }
                for name, m in body["models"].items()
            },
        }
        try:
            self.history.record(entry)
        except OSError:
            pass  # a full disk must not fail the scrape that noticed it

    # ----------------------------------------------------------- health

    def _max_burn(self) -> Optional[float]:
        """Worst configured objective's lifetime burn ratio, WITHOUT the
        violation-counter side effect a gauge scrape has."""
        slo = self.slo_monitor
        if slo is None or not getattr(slo, "objectives", None):
            return None
        burns = []
        for key, obj in slo.objectives.items():
            current = slo.current_ms(key)
            if current is not None:
                burns.append(current / obj["target_ms"])
        return max(burns) if burns else None

    def replica_health(self, core: Any, model: str) -> float:
        return replica_health(core, burn=self._max_burn(),
                              drift=self.drift(model))

    # ----------------------------------------------------------- metrics

    def _install_metrics(self, reg: metrics_mod.MetricsRegistry) -> None:
        def fp_value(model: str, fn) -> float:
            fp = self._fp(model)
            if fp is None:
                raise LookupError(f"{model}: empty fingerprint window")
            return float(fn(fp))

        gauges = (
            ("runbook_workload_prompt_len_p50",
             "Live p50 prompt tokens over the fingerprint window",
             lambda fp: fp["prompt_tokens"]["p50"]),
            ("runbook_workload_output_len_p50",
             "Live p50 generated tokens over the fingerprint window",
             lambda fp: fp["output_tokens"]["p50"]),
            ("runbook_workload_concurrency",
             "Live offered concurrency (decode batch + queued backlog, "
             "mean over non-idle steps in the window)",
             lambda fp: fp["workload"]["concurrency"]),
            ("runbook_workload_guided_share",
             "Fraction of window requests that were grammar-guided",
             lambda fp: fp["guided_share"]),
            ("runbook_workload_spec_hit_rate",
             "Extra accepted speculative tokens per decode dispatch",
             lambda fp: fp["spec_hit_rate"]),
            ("runbook_workload_prefix_cache_share",
             "Prompt tokens served from the prefix cache over the window",
             lambda fp: fp["prefix_cache_share"]),
            ("runbook_workload_window_requests",
             "Completed requests inside the fingerprint window",
             lambda fp: fp["window"]["samples"]),
        )
        models = list(self.fingerprinters)
        for name, help_text, fn in gauges:
            metric = reg.gauge(name, help_text, labels=("model",))
            metric.clear_functions()
            for model in models:
                # runbook: noqa[RBK010] — model label: served-group
                # catalog names, fixed at monitor attach.
                metric.labels(model=model).set_function(
                    lambda m=model, f=fn: fp_value(m, f))

        def drift_or_raise(model: str) -> float:
            d = self.drift(model)
            if d is None:
                raise LookupError(f"{model}: empty fingerprint window")
            return d

        g_drift = reg.gauge(
            "runbook_workload_drift_score",
            "Bounded [0,1] distance between the live workload fingerprint "
            "and the serving plan's provenance workload (or the "
            "configured descriptor); absent until the window has samples",
            labels=("model",))
        g_stale = reg.gauge(
            "runbook_plan_stale",
            "1 when the live workload drift exceeds llm.obs."
            "drift_threshold — the serving plan no longer matches the "
            "traffic; absent until the window has samples",
            labels=("model",))
        g_drift.clear_functions()
        g_stale.clear_functions()
        for model in models:
            # runbook: noqa[RBK010] — model label: served-group
            # catalog names, fixed at monitor attach.
            g_drift.labels(model=model).set_function(
                lambda m=model: drift_or_raise(m))
            # runbook: noqa[RBK010] — model label: served-group
            # catalog names, fixed at monitor attach.
            g_stale.labels(model=model).set_function(
                lambda m=model: float(
                    drift_or_raise(m) > self.drift_threshold))

        g_health = reg.gauge(
            "runbook_replica_health",
            "Composite replica health in [0,1]: SLO burn x queue depth x "
            "KV pressure x workload drift (1.0 = serving its tuned "
            "workload with headroom)", labels=("replica", "model"))
        g_health.clear_functions()
        for model, fp in self.fingerprinters.items():
            for core in fp.cores:
                rid = core.replica_idx if core.replica_idx is not None else 0
                # runbook: noqa[RBK010] — replica/model labels: pinned
                # replica ids x served-group names, fixed at attach.
                g_health.labels(replica=str(rid), model=model).set_function(
                    lambda c=core, m=model: self.replica_health(c, m))

    # ------------------------------------------------------------ factory

    @classmethod
    def from_config(cls, llm_cfg: Any, *,
                    cores: Optional[Sequence[Any]] = None,
                    multi_model: Any = None, slo_monitor: Any = None,
                    tenants: Any = None) -> Optional["WorkloadMonitor"]:
        """Build from ``llm.obs`` (None when disabled). Multi-model
        fleets get one fingerprinter per group (each judged against its
        OWN plan's provenance workload); single-model deployments get
        one for the whole engine."""
        obs_cfg = getattr(llm_cfg, "obs", None)
        if obs_cfg is None or not getattr(obs_cfg, "enabled", False):
            return None
        window_s = float(getattr(obs_cfg, "window_s", 300.0))
        max_samples = int(getattr(obs_cfg, "max_samples", 4096))
        fingerprinters: dict[str, WorkloadFingerprinter] = {}
        references: dict[str, tuple[dict[str, Any], str]] = {}
        if multi_model is not None:
            for name, group in multi_model.groups.items():
                fingerprinters[name] = WorkloadFingerprinter(
                    group.cores, model=name, window_s=window_s,
                    max_samples=max_samples)
                group_plan = getattr(group.llm_cfg, "plan", None) \
                    if group.llm_cfg is not None else None
                references[name] = reference_descriptor(
                    llm_cfg, plan_path=group_plan)
        else:
            model = getattr(llm_cfg, "model", None) or "default"
            fingerprinters[model] = WorkloadFingerprinter(
                list(cores or []), model=model, window_s=window_s,
                max_samples=max_samples)
            references[model] = reference_descriptor(
                llm_cfg, plan_path=getattr(llm_cfg, "plan", None))
        history = None
        if getattr(obs_cfg, "history_dir", None):
            history = FingerprintHistory(
                obs_cfg.history_dir,
                max_files=getattr(obs_cfg, "history_max_files", 64))
        return cls(
            fingerprinters, references,
            drift_threshold=getattr(obs_cfg, "drift_threshold",
                                    DEFAULT_DRIFT_THRESHOLD),
            slo_monitor=slo_monitor, tenants=tenants, history=history,
            history_interval_s=getattr(obs_cfg, "history_interval_s",
                                       60.0))


__all__ = ["FingerprintHistory", "WorkloadMonitor", "reference_descriptor",
           "replica_health"]
