"""Live workload fingerprinting: what the fleet actually serves, in the
autotuner's own vocabulary.

The autotuner (PR 6) searches from a hand-written
:class:`~runbookai_tpu.autotune.cost_model.Workload` descriptor; the
flight recorder (PR 7) already observes the real traffic — this module is
the missing link of ROADMAP item 3's "virtuous cycle" (FlashInfer-Bench /
AIConfigurator, PAPERS.md): fold what the engine *observes* into what the
tuner *consumes*, continuously, so a serving plan's staleness becomes a
measured number instead of a slow throughput regression.

Three layers, deliberately separated so determinism is testable:

- **Pure functions** (``summarize_requests`` / ``summarize_steps`` /
  ``build_fingerprint`` / ``drift_score``): identical inputs produce
  byte-identical JSON (every float rounded at a fixed precision, keys
  emitted in one order) — flight-recorder fixtures double as fingerprint
  fixtures, pinned by ``tests/test_obs.py``.
- :class:`WorkloadFingerprinter`: the live accumulator. Engine request
  taps (``EngineCore.workload_tap`` — one O(1) deque append per finished
  request, never on the dispatch path) feed a bounded sliding window;
  ``fingerprint()`` joins the window's request samples with the flight
  recorder's step records and the engine metrics dict into one
  fingerprint whose ``workload`` block is a valid tuner descriptor.
- ``drift_score``: a bounded [0, 1] distance between a live descriptor
  and a reference one (the serving plan's provenance workload, or the
  configured descriptor when no plan is pinned). Scale dimensions
  (prompt/output length, concurrency) compare on a saturating log-ratio;
  share dimensions (guided, speculation) on absolute difference — so
  "2x the prompt length" and "guided traffic appeared" both move the
  score visibly while neither can swamp it past 1.

Empty/warmup windows fingerprint as ``None`` — absence, never a
reassuring drift of 0 (the same contract as ``runbook_slo_*``).
"""

from __future__ import annotations

import json
import math
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

from runbookai_tpu.utils.trace import _percentile

# Workload descriptor keys, in emission order (must stay exactly
# autotune.cost_model.Workload.to_dict()'s key set so an emitted
# descriptor feeds `runbook tune --workload` unchanged — pinned by test).
DESCRIPTOR_KEYS = ("prompt_len", "output_len", "concurrency",
                   "guided_share", "spec_hit_rate")

# Default "plan is stale" drift threshold (llm.obs.drift_threshold):
# roughly "one scale dimension doubled AND a share appeared", or any
# single dimension moving ~4x alone. Calibrated against the shift scenario
# of tests/test_obs.py (short-chat -> long-context/guided crosses it;
# steady traffic against its own descriptor stays well under).
DEFAULT_DRIFT_THRESHOLD = 0.35


@dataclass(frozen=True)
class RequestSample:
    """One finished engine request, as the tap records it."""

    ts: float
    prompt_tokens: int
    output_tokens: int
    cached_tokens: int = 0
    guided: bool = False
    forced_sync: bool = False
    aborted: bool = False

    def to_dict(self) -> dict[str, Any]:
        return {"ts": self.ts, "prompt_tokens": self.prompt_tokens,
                "output_tokens": self.output_tokens,
                "cached_tokens": self.cached_tokens,
                "guided": self.guided, "forced_sync": self.forced_sync,
                "aborted": self.aborted}


# ------------------------------------------------------------ pure layer


def _round(value: float, digits: int = 4) -> float:
    """One rounding rule for every emitted float: byte-stable JSON."""
    return round(float(value), digits)


def summarize_requests(samples: Sequence[RequestSample],
                       t0: float, t1: float) -> Optional[dict[str, Any]]:
    """Distribution summary of the window's COMPLETED requests (aborted
    ones count toward the mix, never toward length stats). None when the
    window holds no completed request — the absence contract."""
    window = [s for s in samples if t0 <= s.ts <= t1]
    done = [s for s in window if not s.aborted]
    if not done:
        return None
    prompts = sorted(float(s.prompt_tokens) for s in done)
    outputs = sorted(float(s.output_tokens) for s in done)
    n = len(done)
    prompt_total = sum(s.prompt_tokens for s in done)
    cached_total = sum(min(s.cached_tokens, s.prompt_tokens) for s in done)
    return {
        "samples": n,
        "aborted": len(window) - n,
        "prompt_tokens": {
            "mean": _round(sum(prompts) / n, 2),
            "p50": _round(_percentile(prompts, 50), 2),
            "p95": _round(_percentile(prompts, 95), 2),
        },
        "output_tokens": {
            "mean": _round(sum(outputs) / n, 2),
            "p50": _round(_percentile(outputs, 50), 2),
            "p95": _round(_percentile(outputs, 95), 2),
        },
        "guided_share": _round(sum(1 for s in done if s.guided) / n),
        "forced_sync_share": _round(
            sum(1 for s in done if s.forced_sync) / n),
        "prefix_cache_share": _round(
            cached_total / prompt_total if prompt_total else 0.0),
    }


def summarize_steps(steps: Sequence[dict[str, Any]],
                    t0: float, t1: float) -> dict[str, Any]:
    """Concurrency summary from flight-recorder step records in the
    window: live decode-batch occupancy plus the queued backlog is the
    offered-concurrency estimate the tuner's ``concurrency`` knob means.
    Idle drain steps are excluded — a quiet engine ticking over must not
    dilute the concurrency the busy windows actually saw."""
    live = [r for r in steps
            if t0 <= float(r.get("ts", 0.0)) <= t1
            and r.get("kind") != "idle"]
    if not live:
        return {"steps": 0, "concurrency": None, "occupancy_p50": None}
    conc = sorted(float(r.get("batch", 0)) + float(r.get("queue_depth", 0))
                  for r in live)
    occ = sorted(float(r.get("occupancy", 0.0)) for r in live)
    return {
        "steps": len(live),
        "concurrency": {
            "mean": _round(sum(conc) / len(conc), 2),
            "p95": _round(_percentile(conc, 95), 2),
        },
        "occupancy_p50": _round(_percentile(occ, 50)),
    }


def build_fingerprint(samples: Sequence[RequestSample],
                      steps: Sequence[dict[str, Any]],
                      metrics: Optional[dict[str, Any]] = None, *,
                      model: str = "default",
                      window: tuple[float, float]) -> Optional[dict[str, Any]]:
    """The pure core: request samples + step records + the engine metrics
    dict -> one fingerprint whose ``workload`` block is a valid
    :class:`~runbookai_tpu.autotune.cost_model.Workload` descriptor.

    Deterministic by construction (identical inputs -> byte-identical
    ``descriptor_json``): no clocks, no randomness, fixed rounding.
    Returns None for an empty/warmup window — series absence, never a
    fingerprint of zeros that would score drift 0 against any plan.
    """
    t0, t1 = window
    req = summarize_requests(samples, t0, t1)
    if req is None:
        return None
    step = summarize_steps(steps, t0, t1)
    metrics = metrics or {}
    # Speculation hit rate in the tuner's unit: extra accepted tokens per
    # decode dispatch (engine-lifetime counters — speculation acceptance
    # moves slowly and a windowed ratio over few dispatches would be
    # noise dressed as signal).
    dispatches = float(metrics.get("decode_dispatches", 0) or 0)
    spec_rate = (float(metrics.get("spec_accepted", 0)) / dispatches
                 if dispatches else 0.0)
    if step["concurrency"] is not None:
        concurrency = max(1, int(math.ceil(step["concurrency"]["mean"])))
    else:
        # No non-idle step records in the window (recorder disabled, or
        # the ring aged out): there is NO concurrency evidence. Emit the
        # floor (1) — never the window's request COUNT, which would
        # overestimate a sequential workload by orders of magnitude and
        # false-trip runbook_plan_stale — and leave ``concurrency: None``
        # on the fingerprint so drift scoring can EXCLUDE the dimension
        # (``drift_score(..., skip=("concurrency",))``).
        concurrency = 1
    descriptor = {
        "prompt_len": max(1, int(round(req["prompt_tokens"]["p50"]))),
        "output_len": max(1, int(round(req["output_tokens"]["p50"]))),
        "concurrency": concurrency,
        "guided_share": req["guided_share"],
        "spec_hit_rate": _round(spec_rate),
    }
    return {
        "model": model,
        "window": {
            "from_ts": _round(t0, 3), "to_ts": _round(t1, 3),
            "span_s": _round(t1 - t0, 3),
            "samples": req["samples"], "aborted": req["aborted"],
            "steps": step["steps"],
        },
        "prompt_tokens": req["prompt_tokens"],
        "output_tokens": req["output_tokens"],
        "concurrency": step["concurrency"],
        "occupancy_p50": step["occupancy_p50"],
        "guided_share": req["guided_share"],
        "forced_sync_share": req["forced_sync_share"],
        "prefix_cache_share": req["prefix_cache_share"],
        "spec_hit_rate": _round(spec_rate),
        "workload": descriptor,
    }


def descriptor_json(fingerprint: dict[str, Any]) -> str:
    """Canonical JSON of a fingerprint's tuner descriptor — the bytes
    ``runbook workload --emit-descriptor`` writes and ``runbook tune
    --workload`` reads back unchanged."""
    return json.dumps(fingerprint["workload"], sort_keys=True, indent=2) + "\n"


def _scale_dist(live: float, ref: float) -> float:
    """Saturating log-ratio distance for scale dimensions: 0 when equal,
    ~0.41 at 2x, ~0.58 at 4x, asymptotically 1 — a 100x shift cannot
    swamp the composite past its bound."""
    live = max(float(live), 1e-9)
    ref = max(float(ref), 1e-9)
    d = abs(math.log(live / ref))
    return d / (d + 1.0)


def _share_dist(live: float, ref: float) -> float:
    return min(1.0, abs(float(live) - float(ref)))


# Drift weights per descriptor dimension (sum to 1.0 so the score is a
# bounded [0, 1] convex combination).
DRIFT_WEIGHTS = {
    "prompt_len": 0.25,
    "output_len": 0.15,
    "concurrency": 0.20,
    "guided_share": 0.25,
    "spec_hit_rate": 0.15,
}


_DRIFT_DIMS = (
    ("prompt_len", _scale_dist, 1),
    ("output_len", _scale_dist, 1),
    ("concurrency", _scale_dist, 1),
    ("guided_share", _share_dist, 0.0),
    ("spec_hit_rate", _share_dist, 0.0),
)


def drift_score(live: dict[str, Any], reference: dict[str, Any], *,
                skip: tuple[str, ...] = ()) -> float:
    """Bounded [0, 1] distance between a live descriptor and the
    reference (plan-provenance or configured) one. Deterministic: same
    inputs, same 6-decimal score. ``skip`` drops dimensions the live
    fingerprint has no evidence for (e.g. concurrency with the flight
    recorder disabled) — remaining weights re-normalize so the score
    stays a [0, 1] convex combination."""
    total_weight = 0.0
    score = 0.0
    for dim, dist, default in _DRIFT_DIMS:
        if dim in skip:
            continue
        weight = DRIFT_WEIGHTS[dim]
        total_weight += weight
        score += weight * dist(live.get(dim, default),
                               reference.get(dim, default))
    if total_weight <= 0:
        return 0.0
    return round(min(1.0, score / total_weight * sum(
        DRIFT_WEIGHTS.values())), 6)


# ------------------------------------------------------------ live layer


class WorkloadFingerprinter:
    """Sliding-window accumulator over one served model's cores.

    ``observe_request`` is the engine tap target: O(1) bounded-deque
    append under a private lock (finish paths run under each core's
    engine lock; a multi-replica group funnels several cores into one
    fingerprinter, so the deque needs its own). ``fingerprint()`` reads
    the cores' flight recorders and metrics dicts lock-free — the same
    torn-read tolerance as the scrape gauges.
    """

    def __init__(self, cores: Sequence[Any] = (), *,
                 model: str = "default", window_s: float = 300.0,
                 max_samples: int = 4096,
                 clock: Callable[[], float] = time.time):
        self.cores = list(cores)
        self.model = model
        self.window_s = float(window_s)
        # Injected clock seam (the supervisor's flap-damping pattern):
        # window math is a pure function of it, so interval/rotation
        # tests drive a fake clock instead of sleeping wall time.
        self._clock = clock
        self._samples: deque[RequestSample] = deque(maxlen=max(16,
                                                               max_samples))
        self._lock = threading.Lock()

    def install_taps(self) -> None:
        """Point every core's ``workload_tap`` at this fingerprinter."""
        for core in self.cores:
            core.workload_tap = self.observe_request

    def observe_request(self, req: Any) -> None:
        """Engine tap: one sample per finished request (any outcome)."""
        from runbookai_tpu.engine.request import FinishReason

        sampling = req.sampling
        sample = RequestSample(
            ts=self._clock(),
            prompt_tokens=len(req.prompt_ids),
            output_tokens=req.num_generated,
            cached_tokens=req.cached_tokens,
            guided=bool(sampling.guided),
            forced_sync=bool(sampling.forced_sync),
            aborted=req.finish_reason is FinishReason.ABORTED,
        )
        with self._lock:
            self._samples.append(sample)

    def reset(self) -> None:
        """Drop every sample (phase boundaries, warmup exclusion)."""
        with self._lock:
            self._samples.clear()

    @property
    def sample_count(self) -> int:
        return len(self._samples)

    def samples(self) -> list[RequestSample]:
        with self._lock:
            return list(self._samples)

    def _step_records(self, t0: float) -> list[dict[str, Any]]:
        records: list[dict[str, Any]] = []
        for core in self.cores:
            flight = getattr(core, "flight", None)
            if flight is None or not flight.enabled:
                continue
            records.extend(r for r in flight.snapshot()
                           if float(r.get("ts", 0.0)) >= t0)
        return records

    def _metrics(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for core in self.cores:
            for key in ("spec_accepted", "spec_drafted",
                        "decode_dispatches"):
                out[key] = out.get(key, 0) + core.metrics.get(key, 0)
        return out

    def fingerprint(self, now: Optional[float] = None
                    ) -> Optional[dict[str, Any]]:
        """The window's fingerprint, or None while it is empty."""
        now = self._clock() if now is None else float(now)
        t0 = now - self.window_s
        return build_fingerprint(
            self.samples(), self._step_records(t0), self._metrics(),
            model=self.model, window=(t0, now))

    def descriptor(self, now: Optional[float] = None
                   ) -> Optional[dict[str, Any]]:
        fp = self.fingerprint(now)
        return None if fp is None else fp["workload"]


__all__ = [
    "DESCRIPTOR_KEYS", "DEFAULT_DRIFT_THRESHOLD", "DRIFT_WEIGHTS",
    "RequestSample", "WorkloadFingerprinter", "build_fingerprint",
    "descriptor_json", "drift_score", "summarize_requests",
    "summarize_steps",
]
