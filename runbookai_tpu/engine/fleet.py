"""Data-parallel engine fleet: N ``EngineCore`` replicas behind a
prefix-affinity router.

The serving comparison literature is unambiguous that above the engine, the
two highest-leverage pod-scale moves are (1) data-parallel replica scaling —
most of Gemma-on-TPU's pod throughput comes from replicas, not deeper model
sharding — and (2) prefix-cache-aware request routing across those replicas
(AIBrix, arXiv:2504.03648). This module is both:

- :func:`build_engine_fleet` constructs ``EngineConfig.dp_replicas``
  independent :class:`~runbookai_tpu.engine.engine.EngineCore` replicas,
  each pinned to a disjoint device slice of the dp axis
  (``parallel/mesh.replica_device_slices``). Replicas never communicate
  inside compiled programs — weights are replicated, KV pools are private —
  so the fleet scales the *data* axis of ``parallel/mesh.py`` without
  touching the TP/seq story within a replica. On CPU tier-1 the replicas
  land on the virtual mesh's devices (or share the default device when the
  platform exposes only one).

- :class:`AsyncFleet` fronts the replicas with the exact
  ``generate``/``generate_stream``/``start``/``stop``/``refresh_lora``
  surface of :class:`~runbookai_tpu.engine.async_engine.AsyncEngine`, so
  ``server/openai_api.py``, ``server/mcp.py``, the agent runtime and the
  eval suite all switch to a fleet behind the one-line config change
  ``EngineConfig.dp_replicas`` (``llm.dp_replicas`` in config files).

Routing policy (:meth:`AsyncFleet._route`): hash the prompt's full pages
once (``kv_cache.hash_blocks``) and probe every replica's
``KVCacheManager.match_prefix`` — requests sharing a system prompt land on
the replica already holding those pages, so agent iterations ride the
prefix cache instead of re-prefilling on a cold replica. Affinity is
load-guarded: a matching replica wins only while its live load stays
within ``affinity_load_slack`` of the least-loaded replica (a hot prefix
must not pile the whole pod onto one engine). With no usable match,
placement is least-loaded with a round-robin tiebreak. Overflow sheds
(``shed_queue_depth``) and a replica that aborts on pool pressure gets the
request retried on its siblings (``max_retries``).

Fleet-wide KV page sharing (``FleetConfig.kv_share``): KV pages are
location-addressable, not replica-private — when the placed replica holds
fewer of the prompt's prefix pages than a sibling, the router pulls the
missing pages from that sibling (host-staged copy on CPU; the same
export/import seam carries device-to-device transfers on TPU) instead of
re-prefilling them. Every pull is staleness-guarded per chain (the
export re-walks the planned chain with per-page token verification under
the source's engine lock) and digest-checked at import, so a pulled page
is byte-identical to recompute or it is not installed at all.

Prefill/decode disaggregation (``FleetConfig.disagg_prefill_replicas``):
the first N replicas form a prefill tier — prompts with enough full pages
prefill there via a 1-token warm request, the pages hand off to a
decode-tier replica through the same pull seam, and the request streams
entirely from the decode tier, so prompt bursts never sit in front of
decode dispatches (AIBrix, arXiv:2504.03648).

Per-request streams are byte-identical to the single-engine path: the
router only *chooses* a replica (and optionally pre-stages byte-identical
KV pages); the chosen ``AsyncEngine`` serves the request exactly as a
standalone engine would.
"""

from __future__ import annotations

import asyncio
import random
import threading
import time as _time
from contextvars import ContextVar
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

from runbookai_tpu.engine.async_engine import AsyncEngine
from runbookai_tpu.engine.engine import (
    LEGACY_COUNTER_EXPORTS,
    EngineConfig,
    EngineCore,
    export_expert_pairs,
)
from runbookai_tpu.engine.kv_cache import hash_blocks
from runbookai_tpu.engine.request import (
    EngineOutput,
    FinishReason,
    FleetSaturated,
    SamplingParams,
)
from runbookai_tpu.sched import class_label
from runbookai_tpu.utils import metrics as metrics_mod
from runbookai_tpu.utils.trace import get_tracer

# Per-asyncio-task eval-case attribution: the eval runner sets this around
# each case (AsyncFleet.begin_case/end_case) and contextvars flow through
# awaits, so every engine call a case makes — however deep in the agent
# stack — is attributed to it without plumbing ids through the orchestrator.
CURRENT_CASE: ContextVar[Optional[str]] = ContextVar(
    "runbook_fleet_case", default=None)

# Bound on the routed-case attribution map: entries are popped by
# case_routes(); a caller that never collects them must not leak memory.
_CASE_ROUTES_MAX = 4096


@dataclass
class FleetConfig:
    """Router policy knobs (docs/SERVING.md)."""

    # Prefix-affinity placement on/off (off = pure least-loaded).
    affinity: bool = True
    # A prefix-matching replica may exceed the least-loaded replica's live
    # load by at most this many requests and still win placement. None =
    # one batch's worth (the replica's max_batch_slots): affinity is worth
    # at most one slot-generation of queueing, never a pile-up.
    affinity_load_slack: Optional[int] = None
    # Shed (synthetic abort / FleetSaturated, no submission) when EVERY
    # replica's waiting queue is at least this deep. None = never shed.
    shed_queue_depth: Optional[int] = None
    # Cross-replica retries when a replica aborts a request on pool
    # pressure. None = up to every other replica once.
    max_retries: Optional[int] = None
    # Fleet-wide KV page sharing: when the placed replica holds fewer of
    # the prompt's prefix pages than a sibling, pull the missing pages
    # from that sibling (digest-checked, chain-reverified host-staged copy)
    # before submitting, instead of re-prefilling them. Implied on by
    # disaggregation (the prefill→decode handoff IS a pull).
    kv_share: bool = False
    # Minimum full-page deficit (sibling's match minus the placed
    # replica's) worth a pull — below it, recompute is cheaper than the
    # two lock acquisitions + copy.
    kv_share_min_pages: int = 1
    # Prefill/decode disaggregation: dedicate the FIRST this-many replicas
    # to a prefill tier. Prompts with at least ``disagg_min_prompt_pages``
    # full pages prefill there (a 1-token warm request), their pages hand
    # off to a decode-tier replica, and the request streams entirely from
    # the decode tier — prompt bursts never sit in front of decode
    # dispatches. 0 = symmetric fleet (the classic router).
    disagg_prefill_replicas: int = 0
    # Prompts below this many full pages skip the prefill tier (the warm
    # round-trip would cost more than the tail prefill it saves).
    disagg_min_prompt_pages: int = 1
    # Cross-replica retry backoff: attempt k (1-based) waits
    # min(max, base * 2**(k-1)) scaled by seeded jitter in [0.5, 1.0)
    # before re-placing — an aborting replica's siblings see a spread-out
    # retry wave, not a synchronized stampede. 0 disables (the historical
    # immediate re-place). The jitter stream is seeded per fleet so a
    # soak's retry schedule is reproducible run over run.
    retry_backoff_base: float = 0.05
    retry_backoff_max: float = 2.0
    retry_jitter_seed: int = 0


@dataclass
class _Placement:
    """One routing decision: the chosen replica plus an optional page-pull
    plan (source replica and how many blocks the destination already
    holds). The plan's staleness is handled by the export itself: it
    re-walks the chain with per-page token verification under the
    source's engine lock, so planned pages that vanished since the probe
    simply export nothing."""

    idx: Optional[int]
    hashes: Optional[list[int]] = None
    pull_src: Optional[int] = None
    pull_dst_blocks: int = 0
    # Full pages the probe planned to pull (source match minus the
    # destination's). An export that lands SHORT of this count was
    # truncated between probe and copy — the mid-pull-preemption signal
    # the stale reason label attributes.
    pull_pages: int = 0


def split_engine_budget(engine_cfg: EngineConfig, dp: int) -> EngineConfig:
    """Per-replica EngineConfig from a fleet-TOTAL slot/page budget.

    The split is exact, never rounded UP past the total (a floor that
    rounded the per-replica pool up would hand a dp arm more aggregate
    pages than dp=1 and fake a win via fewer preemptions — a
    fixed-total-budget A/B's contract; no caller is left in the package
    since the dp arm that made one went, ROADMAP C19). Plan artifacts
    and the autotuner's measured arms carry
    PER-REPLICA slot/page budgets already (the llm.*/EngineConfig
    contract) and must never pass through this split.
    Allocator minimums: 1 slot, 2 pages per replica.
    """
    import dataclasses

    dp = max(1, dp)
    slots_per = max(1, engine_cfg.max_batch_slots // dp)
    return dataclasses.replace(
        engine_cfg, dp_replicas=dp, max_batch_slots=slots_per,
        num_pages=max(2, engine_cfg.num_pages // dp),
        # The host spill tier is per replica too: an unsplit value would
        # hand the dp arm dp× the aggregate host bytes (and spill
        # readmits) of the dp=1 arm — the exact fake-win this split
        # exists to prevent. 0 stays 0 (tier disabled).
        kv_spill_pages=engine_cfg.kv_spill_pages // dp,
        prefill_batch=max(1, min(engine_cfg.prefill_batch, slots_per)))


def _agg_utilization(cores: Sequence[EngineCore]) -> float:
    usable = sum(c.kv.allocator.num_pages - 1 for c in cores)
    used = sum(c.kv.pages_in_use for c in cores)
    return used / usable if usable > 0 else 0.0


def _agg_prefix_hit_ratio(cores: Sequence[EngineCore]) -> float:
    cached = sum(c.metrics.get("cached_prefix_tokens", 0) for c in cores)
    total = cached + sum(c.metrics.get("prefill_tokens", 0) for c in cores)
    return cached / total if total else 0.0


def _agg_overlap_ratio(cores: Sequence[EngineCore]) -> float:
    host = sum(c.metrics.get("decode_host_time_s", 0.0) for c in cores)
    overlap = sum(c.metrics.get("decode_host_overlap_s", 0.0)
                  for c in cores)
    return overlap / host if host > 0 else 0.0


def install_fleet_aggregates(cores: Sequence[EngineCore]) -> None:
    """Re-bind the unlabeled engine metric names to aggregates over
    ``cores`` — the fleet-wide truth an existing single-engine dashboard
    keeps reading. Last bind wins: a single fleet binds its own replicas
    here; a multi-model fleet calls this once more with the union of
    every group's cores so the process-wide names cover all groups."""
    cores = list(cores)
    reg = metrics_mod.get_registry()
    reg.gauge("runbook_running_requests",
              "Requests holding a decode slot").set_function(
        lambda: sum(len(c.decoding) for c in cores))
    reg.gauge("runbook_waiting_requests",
              "Requests queued or prefilling").set_function(
        lambda: sum(len(c.waiting) + len(c.prefilling) for c in cores))
    g_cls_wait = reg.gauge(
        "runbook_sched_waiting_requests",
        "Requests queued or prefilling, per priority class",
        labels=("cls",))
    g_cls_wait.clear_functions()
    for label in ("interactive", "batch", "other"):
        g_cls_wait.labels(cls=label).set_function(
            lambda lb=label: float(sum(
                1 for c in cores
                for r in list(c.waiting) + list(c.prefilling)
                if class_label(r.priority) == lb)))
    reg.gauge("runbook_kv_pages_total", "KV pool size in pages"
              ).set_function(
        lambda: sum(c.kv.allocator.num_pages for c in cores))
    reg.gauge("runbook_kv_pages_in_use",
              "KV pages referenced by live sequences").set_function(
        lambda: sum(c.kv.pages_in_use for c in cores))
    reg.gauge("runbook_kv_pages_cached",
              "Retired-but-resident prefix-cache pages").set_function(
        lambda: sum(c.kv.allocator.cached_pages for c in cores))
    reg.counter("runbook_kv_spill_pages_total",
                "KV pages captured into the host spill tier at "
                "eviction time").set_function(
        lambda: float(sum(c.kv.spill.pages_spilled for c in cores
                          if c.kv.spill)))
    reg.counter("runbook_kv_spill_evictions_total",
                "Spill-tier pages dropped by its LRU bound"
                ).set_function(
        lambda: float(sum(c.kv.spill.evictions for c in cores
                          if c.kv.spill)))
    reg.gauge("runbook_kv_pool_utilization",
              "Fraction of allocatable KV pages held by live sequences"
              ).set_function(lambda: _agg_utilization(cores))
    reg.gauge("runbook_prefix_cache_hit_ratio",
              "Cached prompt tokens / (cached + prefilled) since start"
              ).set_function(lambda: _agg_prefix_hit_ratio(cores))
    reg.gauge("runbook_decode_overlap_ratio",
              "Fraction of host decode work hidden behind device "
              "execution by the lagged pipeline (0 in forced-sync mode)"
              ).set_function(lambda: _agg_overlap_ratio(cores))
    for key, name, help_text in LEGACY_COUNTER_EXPORTS:
        reg.counter(name, help_text).set_function(
            lambda k=key: float(sum(c.metrics.get(k, 0) for c in cores)))
    export_expert_pairs(
        reg, lambda k: float(sum(c.metrics.get(k, 0) for c in cores)))


def build_engine_fleet(
    model_cfg,
    params,
    tokenizer,
    engine_cfg: Optional[EngineConfig] = None,
    *,
    mask_fn=None,
    advance_fn=None,
    seed: int = 0,
    tracer=None,
    lora_registry=None,
    draft_worker_factory: Optional[Callable[[int], Any]] = None,
    devices: Optional[Sequence[Any]] = None,
    replica_indices: Optional[Sequence[int]] = None,
    pin_devices: bool = False,
) -> list[EngineCore]:
    """Construct the fleet's ``EngineCore`` replicas.

    Each replica ``i`` gets ``replica_idx=i`` (request-id namespace
    ``r{i}-``) and — when the host exposes enough devices — its own
    single-slice mesh with the params replicated onto it, so its compiled
    steps and KV pool live entirely on its slice of the dp axis. With too
    few devices (single-device CPU), replicas share the default device:
    N independent engines whose dispatch loops interleave on it.

    ``replica_indices`` restricts construction to a subset of the global
    fleet — each pod host passes ``multihost.local_replica_range(dp)`` with
    ``devices=jax.local_devices()`` so replicas never span hosts.
    ``draft_worker_factory(i)`` builds a per-replica draft worker (one
    worker cannot serve two cores — its slot state is per-engine).
    ``pin_devices`` pins params/mesh to the computed slice even for a
    single-replica build — a multi-model fleet's dp=1 groups must each
    own THEIR device, not all share the default one.
    """
    import jax

    from runbookai_tpu.parallel.mesh import (
        build_mesh,
        replica_device_slices,
        replicated,
    )

    ecfg = engine_cfg or EngineConfig()
    dp = max(1, ecfg.dp_replicas)
    indices = list(replica_indices if replica_indices is not None
                   else range(dp))
    # Slices are computed over the replicas built HERE (this host's
    # share), positioned within the caller's device list — a pod host
    # building replicas [4, 8) of a dp=8 fleet owns slices 0..3 of its
    # jax.local_devices(), not (nonexistent) global offsets 4..7.
    slices = replica_device_slices(len(indices), devices=devices)
    if (len(indices) > 1 and slices[0] is None
            and jax.default_backend() == "tpu"):
        # Single-device timesharing is the CPU tier-1 fleet. On the chip
        # it would be "dp=4" measured on one device with the others idle
        # (or absent), every replica's pool stacked in one HBM.
        raise ValueError(
            f"engine fleet: {len(indices)} replicas need {len(indices)} "
            f"devices, found "
            f"{len(devices) if devices is not None else len(jax.devices())}")
    cores: list[EngineCore] = []
    for pos, i in enumerate(indices):
        mesh_i = None
        params_i = params
        if (dp > 1 or pin_devices) and slices[pos] is not None:
            mesh_i = build_mesh(devices=slices[pos])
            # DP means replicated weights: each replica's slice holds its
            # own copy, placed once here so per-dispatch transfers never
            # pay for it.
            params_i = jax.device_put(params, replicated(mesh_i))
        cores.append(EngineCore(
            model_cfg, params_i, tokenizer, ecfg,
            mask_fn=mask_fn, advance_fn=advance_fn, seed=seed,
            tracer=tracer, mesh=mesh_i, lora_registry=lora_registry,
            draft_worker=(draft_worker_factory(i)
                          if draft_worker_factory else None),
            replica_idx=i,
        ))
    return cores


class AsyncFleet:
    """AsyncEngine-compatible facade over N replicas + the router.

    ``model_label`` names the served model this fleet's metric series
    carry (``runbook_router_*{model=...}`` / ``runbook_replica_*``) —
    a multi-model fleet (``runbookai_tpu/fleet``) builds one AsyncFleet
    per model group, so the label is what separates the groups on a
    dashboard. Default: the model config's own name. ``clear_labeled``
    controls whether construction drops every existing labelset callback
    first (the single-fleet rebuild behavior); a multi-model builder
    clears once for its first group so sibling groups' bindings survive.
    """

    def __init__(self, cores: Sequence[EngineCore],
                 fleet_cfg: Optional[FleetConfig] = None,
                 model_label: Optional[str] = None,
                 clear_labeled: bool = True,
                 replica_factory: Optional[Callable[[int], EngineCore]]
                 = None):
        if not cores:
            raise ValueError("a fleet needs at least one EngineCore")
        self.cores = list(cores)
        self.model = (model_label
                      or getattr(cores[0].cfg, "name", None) or "default")
        self.replicas = [AsyncEngine(core) for core in self.cores]
        self.dp = len(self.cores)
        # GLOBAL replica ids for everything operator-facing (metric
        # labels, health rows, eval attribution): on a pod host building
        # replicas [4, 8) these must match the r{idx}- request prefixes
        # and trace records, not local list positions 0..3.
        self.replica_ids = [c.replica_idx if c.replica_idx is not None
                            else i for i, c in enumerate(self.cores)]
        self.cfg = fleet_cfg or FleetConfig()
        self._page_size = self.cores[0].ecfg.page_size
        slack = self.cfg.affinity_load_slack
        self._slack = (slack if slack is not None
                       else self.cores[0].ecfg.max_batch_slots)
        # Disaggregated tiers: GLOBAL replica ids [0, n) form the prefill
        # tier, the rest decode (global, not local list positions — a pod
        # host building replicas [2, 4) of a dp=4 fleet with one prefill
        # replica must see zero local prefill replicas, not dedicate its
        # own replica 2). Every request STREAMS from a decode-tier
        # replica; the prefill tier only runs warm prefills whose pages
        # hand off. A split that leaves this fleet no decode tier is
        # refused — it would place every request nowhere.
        n_pf = max(0, self.cfg.disagg_prefill_replicas)
        self._prefill_tier = [i for i, g in enumerate(self.replica_ids)
                              if g < n_pf]
        self._decode_tier = [i for i, g in enumerate(self.replica_ids)
                             if g >= n_pf]
        if n_pf and not self._decode_tier:
            raise ValueError(
                f"disagg_prefill_replicas={n_pf} leaves no decode tier "
                f"in this fleet (replicas {self.replica_ids})")
        # The handoff IS a pull, so disaggregation forces page sharing on.
        self._kv_share = bool(self.cfg.kv_share or n_pf)
        # Router state below is mutated ONLY under this lock (routing runs
        # on event-loop threads and, for eval drivers, possibly
        # several of them).
        self._lock = threading.Lock()
        self._routed = [0] * self.dp
        self._rr = 0
        self._affinity_hits = 0
        self._case_routes: dict[str, dict[int, int]] = {}
        # Supervision (runbookai_tpu/chaos): quarantined LOCAL replica
        # positions are excluded from routing (placement AND pull
        # sources) until the supervisor rejoins them. Replaced as a
        # whole frozenset under self._lock; racy reads see either the
        # old or new set — the same one-step-stale contract as the load
        # reads.
        self._quarantined: frozenset[int] = frozenset()
        # Online rebuild: a caller-supplied factory (global replica id ->
        # fresh EngineCore on that replica's device slice); None falls
        # back to cloning the dead core's construction inputs.
        self.replica_factory = replica_factory
        # Hook re-running any wrapper's metric bindings after a rebuild
        # swaps a core (fleet/multimodel re-unions its rollups here).
        self._rebuild_listener: Optional[Callable[[], None]] = None
        # Attach points read by /healthz and `runbook chaos status`:
        # the fleet supervisor (chaos/supervisor.py) and the fault
        # injector (chaos/inject.py) publish their snapshots through
        # health_snapshot when present.
        self.supervisor = None
        self.chaos = None
        # Fault-injection seam on the page-pull path: applied to the
        # ExportedPages payload INSIDE the export worker thread (a delay
        # or corruption must never block the event loop).
        self.chaos_pull_hook = None
        # Seeded jitter stream for retry backoff (drawn under _lock).
        self._retry_rng = random.Random(self.cfg.retry_jitter_seed)
        self._install_metrics(clear=clear_labeled)

    # ------------------------------------------------------------- routing

    def _live_load(self, core: EngineCore) -> int:
        """Live slots + queue depth (racy read of the engine's pools —
        at worst one step stale, same contract as the scrape gauges)."""
        return (len(core.waiting) + len(core.prefilling)
                + len(core.decoding))

    def _hash_seed(self, adapter: Optional[str]) -> int:
        """Prefix-cache namespace of the request (LoRA adapter row)."""
        if adapter is None:
            return 0
        lora = self.cores[0].lora
        if lora is None:
            return 0
        try:
            return lora.index_of(adapter)
        except Exception:  # noqa: BLE001 — unknown adapter errors at submit
            return 0

    def _route(self, prompt_ids: list[int], hash_seed: int = 0,
               exclude: frozenset[int] = frozenset(),
               trace_id: Optional[str] = None) -> _Placement:
        """Pick a replica: prefix affinity under a load guard, else
        least-loaded with round-robin tiebreak. ``idx=None`` = shed.

        Placement is restricted to the decode tier under disaggregation;
        with kv_share on, every replica (both tiers) is additionally
        probed as a page-pull SOURCE, and a sibling holding at least
        ``kv_share_min_pages`` more of the prompt's prefix than the
        placed replica yields a pull plan the caller executes before
        submit. ``trace_id`` (the caller's x-request-id) rides into the
        ``router.place`` trace event so a request timeline can show
        WHERE the router put it and WHY (affinity vs least-loaded) —
        routing runs on the event-loop thread, where the server
        handler's per-thread tracer context is not visible."""
        probe = (self.cfg.affinity or self._kv_share) \
            and len(prompt_ids) >= self._page_size
        hashes = None
        if probe:
            hashes = hash_blocks(
                prompt_ids, self._page_size,
                max_blocks=(len(prompt_ids) - 1) // self._page_size,
                seed=hash_seed, lookahead=self.cores[0].kv.lookahead)
        # (idx, matched, load, queue_depth): load is the full live count
        # (waiting + prefilling + decoding); queue_depth is the not-yet-
        # decoding backlog — the tiebreak between equally-loaded replicas
        # (two replicas both at load 8 are NOT equal when one has 8
        # decoding and the other 8 queued behind a long prefill).
        candidates: list[tuple[int, int, int, int]] = []
        sources: list[tuple[int, int]] = []  # (idx, matched)
        quarantined = self._quarantined  # one racy read per decision
        for i, core in enumerate(self.cores):
            if i in exclude or i in quarantined:
                # Quarantined replicas (supervisor failover) serve
                # nothing: not placement, not pull sources — a dead
                # core's pages cannot be trusted mid-rebuild.
                continue
            matched = (core.kv.match_prefix(prompt_ids, hashes=hashes,
                                            hash_seed=hash_seed)
                       if hashes else 0)
            if i in self._decode_tier:
                depth = len(core.waiting) + len(core.prefilling)
                candidates.append((i, matched, self._live_load(core),
                                   depth))
                # The depth the router actually saw for this decision —
                # a stored gauge, so a dashboard can join placement
                # choices against the backlog they were made under.
                # runbook: noqa[RBK010] — model/replica labels: configured
                # group name + pinned replica ids, fixed at fleet build.
                self._m_depth.labels(
                    model=self.model,
                    replica=str(self.replica_ids[i])).set(depth)
            if self._kv_share and matched:
                sources.append((i, matched))
        if not candidates:
            return _Placement(idx=None)
        min_load = min(load for _, _, load, _ in candidates)
        if (self.cfg.shed_queue_depth is not None
                and all(len(self.cores[i].waiting) >= self.cfg.shed_queue_depth
                        for i, _, _, _ in candidates)):
            self._m_shed.inc()
            shed_meta = {"dp": self.dp}
            if trace_id is not None:
                shed_meta["trace_id"] = trace_id
            get_tracer().event("router.shed", **shed_meta)
            return _Placement(idx=None)
        # kv_share probes matches even with affinity routing off — the
        # matches then only plan pulls, never placement.
        affine = ([c for c in candidates
                   if c[1] >= self._page_size
                   and c[2] <= min_load + self._slack]
                  if self.cfg.affinity else [])
        with self._lock:
            if affine:
                pick, _matched, _load, _depth = max(
                    affine, key=lambda c: (c[1], -c[2]))
                self._affinity_hits += 1
                self._m_affinity.inc()
            else:
                # Queue-depth-aware least-loaded: load ties break on the
                # waiting+prefilling backlog first (the replica whose
                # live count is decode-heavy starts this request sooner
                # than one with the same count queued), then round-robin
                # so a cold fleet spreads a burst instead of flooding
                # replica 0.
                tied = [c for c in candidates if c[2] == min_load]
                min_depth = min(c[3] for c in tied)
                tied_ids = [c[0] for c in tied if c[3] == min_depth]
                pick = min(tied_ids, key=lambda i: (i - self._rr) % self.dp)
                self._rr = (pick + 1) % self.dp
            self._routed[pick] += 1
            case = CURRENT_CASE.get()
            if case is not None and (case in self._case_routes
                                     or len(self._case_routes)
                                     < _CASE_ROUTES_MAX):
                # The cap bounds NEW entries only: a case already being
                # tracked keeps counting, or its attribution would silently
                # undercount mid-flight.
                per = self._case_routes.setdefault(case, {})
                gid = self.replica_ids[pick]
                per[gid] = per.get(gid, 0) + 1
        # runbook: noqa[RBK010] — model/replica labels: configured
        # group name + pinned replica ids, fixed at fleet build.
        self._m_requests.labels(
            model=self.model, replica=str(self.replica_ids[pick])).inc()
        tracer = get_tracer()
        if tracer.enabled:
            meta = {"replica": self.replica_ids[pick],
                    "affinity": bool(affine)}
            if trace_id is not None:
                meta["trace_id"] = trace_id
            tracer.event("router.place", **meta)
        placement = _Placement(idx=pick, hashes=hashes)
        if sources:
            # Page-pull plan: the richest sibling beats the placed
            # replica's own match by at least kv_share_min_pages full
            # pages → pull the deficit before submit. The export
            # re-validates the chain under the source's engine lock, so
            # a plan outdated by eviction degrades to recompute there.
            dst_matched = next((m for i, m, _, _ in candidates
                                if i == pick), 0)
            src, src_matched = max(
                ((i, m) for i, m in sources if i != pick),
                key=lambda s: s[1], default=(None, 0))
            deficit = (src_matched - dst_matched) // self._page_size
            if src is not None and deficit >= max(
                    1, self.cfg.kv_share_min_pages):
                placement.pull_src = src
                placement.pull_dst_blocks = dst_matched // self._page_size
                placement.pull_pages = deficit
        return placement

    # -------------------------------------------------- page pull / disagg

    async def _execute_pull(self, placement: _Placement,
                            prompt_ids: list[int], hash_seed: int,
                            trace_id: Optional[str] = None) -> int:
        """Run a planned page pull: export from the source replica (under
        its engine lock, chain-reverified) and import into the placed
        replica (under its lock, digest-checked). Both halves run in
        worker threads — the event loop (and every live stream) stays
        free. A stale plan (pages evicted since the probe) or full
        destination pool degrades to recompute; the request is submitted
        either way. Returns pages pulled.

        Staleness is attributed per failure mode
        (``runbook_router_xreplica_stale_total{reason=}``):
        ``epoch_moved`` — the under-lock chain re-walk found NOTHING (the
        planned pages were evicted/re-registered since the probe);
        ``mid_pull_preempt`` — the export landed SHORT of the planned
        deficit (the chain truncated while the pull was in flight; the
        partial prefix still installs); ``digest_mismatch`` — the import
        rejected a corrupted payload block."""
        dst, src = placement.idx, placement.pull_src
        t0 = _time.perf_counter()
        exported = await self.replicas[src].run_locked(
            lambda: self.cores[src].export_kv_pages(
                prompt_ids, hashes=placement.hashes, hash_seed=hash_seed,
                skip_blocks=placement.pull_dst_blocks))
        if exported is None:
            self._m_stale["epoch_moved"].inc()
            return 0
        hook = self.chaos_pull_hook
        if hook is not None:
            # Fault injection on the in-transit payload (chaos/inject.py:
            # d2d delay / corruption). Runs in a worker thread with NO
            # engine lock held — a delayed pull stalls only this
            # request, never a step loop or the event loop.
            exported = await asyncio.to_thread(hook, exported)

        def _import() -> tuple[int, bool]:
            core = self.cores[dst]
            n = core.import_kv_pages(exported)
            # Both reads under the destination's engine lock: the flag
            # belongs to exactly this import call.
            return n, core.kv.last_import_digest_mismatch

        pulled, digest_bad = await self.replicas[dst].run_locked(_import)
        # ONE reason per pull (stale_rejections() sums the labels, so a
        # pull that both truncated AND hit a bad digest must not count
        # twice): corruption outranks truncation as the thing to page on.
        if digest_bad:
            self._m_stale["digest_mismatch"].inc()
        elif placement.pull_pages \
                and exported.num_pages < placement.pull_pages:
            self._m_stale["mid_pull_preempt"].inc()
        elapsed = _time.perf_counter() - t0
        if pulled:
            self._m_xreplica_hits.inc()
            self._m_xreplica_pages.inc(pulled)
            self._m_xreplica_seconds.inc(elapsed)
        tracer = get_tracer()
        if tracer.enabled:
            # The timeline's pull span: destination + SOURCE replica,
            # pages moved, the wall it cost, and the OWNING CHAIN id —
            # the tail block hash of the pulled prefix chain (chained
            # hashing makes it identify the whole prefix), so repeated
            # pulls of one hot conversation join up across timelines.
            chain = (exported.hashes[-1] if exported.hashes
                     else (placement.hashes[-1] if placement.hashes
                           else 0))
            meta = {"replica": self.replica_ids[dst],
                    "src": self.replica_ids[src], "pages": pulled,
                    "chain": f"{chain & 0xFFFFFFFFFFFFFFFF:016x}",
                    "pull_ms": round(elapsed * 1e3, 3)}
            if trace_id is not None:
                meta["trace_id"] = trace_id
            tracer.event("router.page_pull", **meta)
        return pulled

    def shed_total(self) -> int:
        """Requests this fleet shed (every replica over
        ``shed_queue_depth``) — the public accessor the incident
        detector's ``router_shed`` delta signal reads
        (obs/incident.py), so detection never touches the private
        metric child."""
        return int(self._m_shed.value)

    def stale_rejections(self) -> int:
        """Total stale-pull count across reasons for THIS fleet's model
        label (the /healthz ``kv_share.stale_rejections`` figure): pulls
        whose PLAN was not fully honored — at most one count per pull. A
        ``mid_pull_preempt`` entry still installed its partial prefix;
        the per-reason breakdown separates those from true no-page
        rejections."""
        return int(sum(child.value for child in self._m_stale.values()))

    def _full_pages(self, prompt_ids: list[int]) -> int:
        """Full prefix pages a prompt can publish ((len-1)//page_size —
        the engine always prefills at least the last token itself)."""
        return max(0, (len(prompt_ids) - 1) // self._page_size)

    async def _disagg_warm(self, prompt_ids: list[int], hash_seed: int,
                           adapter: Optional[str],
                           trace_id: Optional[str]) -> Optional[int]:
        """Prefill ``prompt_ids`` on the prefill tier: a greedy 1-token
        warm request on the least-loaded prefill replica computes and
        publishes the prompt's full pages, which then hand off to the
        decode replica at first-token time (the pull in generate /
        generate_stream). Returns the warm replica, or None when the
        prompt is too short to be worth the round-trip."""
        if not self._prefill_tier \
                or self._full_pages(prompt_ids) \
                < max(1, self.cfg.disagg_min_prompt_pages):
            return None
        pick = min(self._prefill_tier,
                   key=lambda i: self._live_load(self.cores[i]))
        warm = SamplingParams(temperature=0.0, max_new_tokens=1,
                              stop_token_ids=())
        try:
            out = await self.replicas[pick].generate(
                prompt_ids, warm, adapter=adapter,
                request_id=(f"{trace_id}-warm" if trace_id else None))
        except Exception:  # noqa: BLE001 — a sick prefill tier must not
            return None    # fail the request; decode tier recomputes
        if out.finish_reason is FinishReason.ABORTED:
            return None  # prefill pool pressure — recompute on decode tier
        # runbook: noqa[RBK010] — model/replica labels: configured
        # group name + pinned replica ids, fixed at fleet build.
        self._m_warm.labels(model=self.model,
                            replica=str(self.replica_ids[pick])).inc()
        return pick

    # ----------------------------------------------------- AsyncEngine API

    async def start(self) -> None:
        for replica in self.replicas:
            await replica.start()

    async def stop(self) -> None:
        await asyncio.gather(*(r.stop() for r in self.replicas))

    async def refresh_lora(self) -> None:
        await asyncio.gather(*(r.refresh_lora() for r in self.replicas))

    def _shed_output(self, request_id: Optional[str]) -> EngineOutput:
        return EngineOutput(
            request_id=request_id or "shed", token_ids=[], text="",
            finish_reason=FinishReason.ABORTED, ttft_ms=None,
            decode_tokens=0, elapsed_s=0.0)

    async def generate(
        self,
        prompt_ids: list[int],
        sampling: Optional[SamplingParams] = None,
        timeout_s: Optional[float] = None,
        priority: int = 0,
        adapter: Optional[str] = None,
        request_id: Optional[str] = None,
    ) -> EngineOutput:
        """Route, then delegate to the chosen replica's ``generate``.

        A replica aborting the request (admission fail-fast / pool
        pressure) triggers a retry on its siblings — one replica's full
        pool must not 503 a pod with idle capacity elsewhere. Timeouts
        propagate without retry: the caller's budget is already spent.
        """
        retries = (self.cfg.max_retries if self.cfg.max_retries is not None
                   else self.dp - 1)
        # The TTFT clock starts HERE: warm prefills and page pulls below
        # are part of the first token's latency, so they ride inside the
        # arrival time the replica's EngineRequest is backdated to.
        t_arrival = _time.perf_counter()
        hash_seed = self._hash_seed(adapter)
        if self._prefill_tier and not self.is_saturated():
            # Disaggregation: the heavy prefill runs on the prefill tier
            # first; its pages hand off through the pull below, so the
            # decode replica prefills only the sub-page tail. A saturated
            # fleet skips the warm — the most expensive work in the
            # system must not run for a request about to be shed.
            await self._disagg_warm(prompt_ids, hash_seed, adapter,
                                    request_id)
        tried: set[int] = set()  # decode-tier picks that aborted
        out: Optional[EngineOutput] = None
        for attempt in range(retries + 1):
            if attempt:
                # Bounded exponential backoff with seeded jitter BEFORE
                # re-placing: the sibling that absorbs a failed-over
                # request gets a beat to drain, and concurrent retries
                # de-synchronize instead of stampeding one replica.
                # Sleeping cannot change tokens — retry byte-identity is
                # regression-pinned in tests/test_fleet.py.
                await self._retry_backoff(attempt)
            placement = self._route(prompt_ids, hash_seed,
                                    exclude=frozenset(tried),
                                    trace_id=request_id)
            idx = placement.idx
            if idx is None:
                break
            if attempt:
                self._m_retries.inc()
            if placement.pull_src is not None:
                await self._execute_pull(placement, prompt_ids, hash_seed,
                                         trace_id=request_id)
            out = await self.replicas[idx].generate(
                prompt_ids, sampling, timeout_s=timeout_s,
                priority=priority, adapter=adapter, request_id=request_id,
                arrival_time=t_arrival)
            if out.finish_reason is not FinishReason.ABORTED:
                return out
            tried.add(idx)
        return out if out is not None else self._shed_output(request_id)

    async def generate_stream(
        self,
        prompt_ids: list[int],
        sampling: Optional[SamplingParams] = None,
        priority: int = 0,
        adapter: Optional[str] = None,
        request_sink: Optional[list] = None,
        request_id: Optional[str] = None,
    ):
        """Route, then yield the chosen replica's token stream.

        Failover happens only BEFORE the first token: a replica that
        aborts the request without yielding anything (pool pressure, a
        crash's failover sweep) is retried on its siblings with the same
        backoff as :meth:`generate` — the caller's stream just starts a
        beat later, byte-identical. Once a token has been yielded it
        cannot be unsaid, so a mid-stream abort ends the stream with the
        request's ABORTED state (the HTTP layer turns that into a clean
        SSE error event) instead of hanging or silently truncating.
        Shedding raises :class:`FleetSaturated`."""
        t_arrival = _time.perf_counter()  # TTFT includes warm + pull
        hash_seed = self._hash_seed(adapter)
        if self._prefill_tier and not self.is_saturated():
            await self._disagg_warm(prompt_ids, hash_seed, adapter,
                                    request_id)
        retries = (self.cfg.max_retries if self.cfg.max_retries is not None
                   else self.dp - 1)
        tried: set[int] = set()
        for attempt in range(retries + 1):
            if attempt:
                await self._retry_backoff(attempt)
            placement = self._route(prompt_ids, hash_seed,
                                    exclude=frozenset(tried),
                                    trace_id=request_id)
            idx = placement.idx
            if idx is None:
                raise FleetSaturated(
                    f"all {self.dp} replicas over shed_queue_depth="
                    f"{self.cfg.shed_queue_depth} or quarantined")
            if attempt:
                self._m_retries.inc()
            if placement.pull_src is not None:
                await self._execute_pull(placement, prompt_ids, hash_seed,
                                         trace_id=request_id)
            # The replica appends its EngineRequest to the sink when the
            # stream starts; a private sink keeps failed-over attempts'
            # entries out of the caller's view until they actually serve.
            sink: list = []

            def mirror() -> None:
                if request_sink is not None and sink \
                        and (not request_sink
                             or request_sink[-1] is not sink[0]):
                    request_sink.append(sink[0])

            agen = self.replicas[idx].generate_stream(
                prompt_ids, sampling, priority=priority, adapter=adapter,
                request_sink=sink, request_id=request_id,
                arrival_time=t_arrival)
            yielded = False
            try:
                async for tok in agen:
                    mirror()
                    yielded = True
                    yield tok
            finally:
                # `async for` abandons (never closes) its iterator on
                # early exit; close explicitly so the replica's
                # early-exit abort (slot + KV pages freed) runs NOW,
                # not at GC time.
                await agen.aclose()
            mirror()
            req = sink[0] if sink else None
            if (not yielded and req is not None
                    and req.finish_reason is FinishReason.ABORTED
                    and attempt < retries):
                # Nothing reached the caller: fail over to a sibling —
                # the stream the caller finally sees is byte-identical
                # to an untroubled placement. The serving attempt's
                # request (not this aborted one) is what lands in the
                # caller's request_sink.
                if request_sink is not None and request_sink \
                        and request_sink[-1] is req:
                    request_sink.pop()
                tried.add(idx)
                continue
            return

    # ------------------------------------------------- retry backoff

    async def _retry_backoff(self, attempt: int) -> None:
        """Sleep the bounded-exponential, seeded-jitter backoff for retry
        ``attempt`` (1-based) and observe it into
        ``runbook_router_retry_backoff_seconds``. 0-base disables."""
        base = self.cfg.retry_backoff_base
        if base <= 0:
            return
        raw = min(self.cfg.retry_backoff_max,
                  base * (2 ** (attempt - 1)))
        with self._lock:
            jitter = self._retry_rng.random()
        delay = raw * (0.5 + 0.5 * jitter)
        self._m_backoff.observe(delay)
        await asyncio.sleep(delay)

    # ------------------------------------------- supervision / rebuild

    def quarantine(self, idx: int) -> None:
        """Remove LOCAL replica position ``idx`` from routing (placement
        and pull sources). Idempotent; the supervisor calls this the
        moment a replica is declared failed."""
        with self._lock:
            self._quarantined = self._quarantined | {idx}

    def unquarantine(self, idx: int) -> None:
        with self._lock:
            self._quarantined = self._quarantined - {idx}

    def quarantined_replicas(self) -> list[int]:
        """GLOBAL replica ids currently out of routing."""
        return sorted(self.replica_ids[i] for i in self._quarantined)

    def available_replicas(self) -> int:
        """Decode-tier replicas currently accepting placements."""
        quarantined = self._quarantined
        return sum(1 for i in self._decode_tier if i not in quarantined)

    def failing_over(self) -> bool:
        """True while NO decode-tier replica accepts placements (every
        one quarantined mid-failover): the HTTP layer answers 503 with
        Retry-After instead of burning a shed on a request that cannot
        be placed."""
        return self.available_replicas() == 0

    def _default_replica_factory(self, old: EngineCore) -> EngineCore:
        """Rebuild an EngineCore from the dead core's own construction
        inputs: same model/engine config, the SAME param tree (already
        resident on the replica's device slice — nothing re-uploads),
        same mesh, guided hooks, LoRA registry, tracer, seed and replica
        index. The draft worker is NOT rebuilt (its slot state died with
        the core; speculation resumes only through an explicit
        ``replica_factory``)."""
        params = old.params
        if old.lora is not None:
            # EngineCore re-stacks the registry's adapters itself; the
            # dead core's params carry its stale stacked copy.
            params = {k: v for k, v in params.items() if k != "lora"}
        return EngineCore(
            old.cfg, params, old.tokenizer, old.ecfg,
            mask_fn=old.mask_fn, advance_fn=old.advance_fn,
            seed=old.seed, tracer=old.tracer, mesh=old.mesh,
            lora_registry=old.lora, replica_idx=old.replica_idx)

    def rebuild_replica(self, idx: int) -> EngineCore:
        """Online replica rebuild: tear down LOCAL position ``idx``'s
        engine and construct a fresh one on the same device slice, as a
        first-class runtime operation. The caller (the supervisor) has
        already quarantined the replica and failed over its in-flight
        requests; this swaps the core + AsyncEngine pair under the
        router lock, re-binds the per-replica metric callbacks to the
        new core, and notifies any wrapping fleet (multi-model rollups)
        so no scrape keeps reading the dead engine. The replica remains
        quarantined — rejoining is the supervisor's hysteresis call."""
        old_replica = self.replicas[idx]
        old_core = self.cores[idx]
        # The old loop must exit when (if) it ever wakes: a wedged step
        # thread finishing hours later must find a stopped engine, not
        # re-enter scheduling on an abandoned core.
        old_replica._stopped = True
        factory = self.replica_factory or (
            lambda _gid: self._default_replica_factory(old_core))
        new_core = factory(self.replica_ids[idx])
        with self._lock:
            self.cores[idx] = new_core
            self.replicas[idx] = AsyncEngine(new_core)
        # Re-point every per-replica labeled callback and the unlabeled
        # aggregates at the live core list (the previous bindings hold
        # the dead core). clear=False: sibling labelsets stay bound.
        self._install_metrics(clear=False)
        if self._rebuild_listener is not None:
            self._rebuild_listener()
        return new_core

    # -------------------------------------------------- eval attribution

    def begin_case(self, case_id: str):
        """Attribute subsequent routing in this asyncio task (and its
        awaited children) to ``case_id``; returns the reset token."""
        return CURRENT_CASE.set(case_id)

    def end_case(self, token) -> None:
        CURRENT_CASE.reset(token)

    def case_routes(self, case_id: str) -> dict[int, int]:
        """Pop {replica: request_count} attributed to a finished case."""
        with self._lock:
            return self._case_routes.pop(case_id, {})

    # --------------------------------------------------------- observability

    def routed_counts(self) -> list[int]:
        with self._lock:
            return list(self._routed)

    def _imbalance(self) -> float:
        with self._lock:
            routed = list(self._routed)
        total = sum(routed)
        if total == 0:
            return 0.0
        return max(routed) / (total / len(routed))

    def affinity_hit_ratio(self) -> float:
        with self._lock:
            hits, total = self._affinity_hits, sum(self._routed)
        return hits / total if total else 0.0

    def _install_metrics(self, clear: bool = True) -> None:
        """Router metrics + per-replica labeled gauges — every series
        carries the fleet's ``model`` label so a multi-model deployment
        separates its groups with plain PromQL — and the unlabeled
        engine names re-bound to cross-replica aggregates so an existing
        dashboard keeps reading fleet-wide truth. With ``clear``, labeled
        callbacks are dropped first: a larger previous fleet's stale
        replica labelsets must not keep scraping dead engines."""
        reg = metrics_mod.get_registry()
        model = self.model
        self._m_requests = reg.counter(
            "runbook_router_requests_total",
            "Requests placed by the fleet router",
            labels=("model", "replica"))
        # runbook: noqa[RBK010] — model label: configured group
        # name, fixed at fleet build.
        self._m_affinity = reg.counter(
            "runbook_router_affinity_hits_total",
            "Placements onto a replica already holding the request's "
            "prefix pages (>= one full page matched)",
            labels=("model",)).labels(model=model)
        # runbook: noqa[RBK010] — model label: configured group
        # name, fixed at fleet build.
        self._m_retries = reg.counter(
            "runbook_router_retries_total",
            "Cross-replica retries after a replica aborted on pool "
            "pressure", labels=("model",)).labels(model=model)
        # runbook: noqa[RBK010] — model label: configured group
        # name, fixed at fleet build.
        self._m_backoff = reg.histogram(
            "runbook_router_retry_backoff_seconds",
            "Seeded-jitter exponential backoff slept before each "
            "cross-replica retry re-place",
            buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                     2.0, 5.0),
            labels=("model",)).labels(model=model)
        # runbook: noqa[RBK010] — model label: configured group
        # name, fixed at fleet build.
        self._m_shed = reg.counter(
            "runbook_router_shed_total",
            "Requests shed with every replica over shed_queue_depth",
            labels=("model",)).labels(model=model)
        # Fleet-wide KV page sharing (docs/observability.md): pulls that
        # landed pages, pages moved, wall spent moving them, and pulls
        # whose planned pages were gone by export time.
        # runbook: noqa[RBK010] — model label: configured group
        # name, fixed at fleet build.
        self._m_xreplica_hits = reg.counter(
            "runbook_router_xreplica_hits_total",
            "Placements whose prefix pages were pulled from a sibling "
            "replica instead of re-prefilled",
            labels=("model",)).labels(model=model)
        # runbook: noqa[RBK010] — model label: configured group
        # name, fixed at fleet build.
        self._m_xreplica_pages = reg.counter(
            "runbook_router_xreplica_pages_pulled_total",
            "KV pages pulled across replicas (cross-replica prefix hits "
            "+ prefill-tier handoffs)",
            labels=("model",)).labels(model=model)
        # runbook: noqa[RBK010] — model label: configured group
        # name, fixed at fleet build.
        self._m_xreplica_seconds = reg.counter(
            "runbook_router_xreplica_pull_seconds_total",
            "Wall seconds spent exporting+importing pulled KV pages",
            labels=("model",)).labels(model=model)
        # Stale pulls with a BOUNDED failure-mode label: epoch_moved
        # (chain gone at export), mid_pull_preempt (chain truncated
        # mid-pull — partial prefix still lands), digest_mismatch
        # (corrupted payload rejected at import).
        m_stale = reg.counter(
            "runbook_router_xreplica_stale_total",
            "Planned pulls that fell short of their plan, by reason: the "
            "under-lock export re-walk found nothing (epoch_moved), the "
            "chain truncated mid-pull (mid_pull_preempt), or the import "
            "rejected a corrupted block (digest_mismatch)",
            labels=("model", "reason"))
        self._m_stale = {
            # runbook: noqa[RBK010] — model label: configured group
            # name, fixed at fleet build (reason is the literal tuple).
            reason: m_stale.labels(model=model, reason=reason)
            for reason in ("epoch_moved", "mid_pull_preempt",
                           "digest_mismatch")}
        self._m_warm = reg.counter(
            "runbook_router_prefill_tier_warms_total",
            "Disaggregated prefill-tier warm prefills",
            labels=("model", "replica"))
        # Stored-value gauge (not a callback): the waiting+prefilling
        # depth each candidate replica showed at the LAST routing
        # decision — joins placements against the backlog they saw.
        self._m_depth = reg.gauge(
            "runbook_router_observed_queue_depth",
            "Waiting+prefilling depth per replica as observed by the "
            "router at its most recent placement",
            labels=("model", "replica"))
        g_imbalance = reg.gauge(
            "runbook_router_imbalance_ratio",
            "Max over mean of per-replica routed request counts "
            "(1.0 = perfectly balanced, dp = everything on one replica)",
            labels=("model",))
        per_replica = (
            (reg.gauge("runbook_replica_running_requests",
                       "Requests holding a decode slot, per fleet replica",
                       labels=("model", "replica")),
             lambda c: float(len(c.decoding))),
            (reg.gauge("runbook_replica_waiting_requests",
                       "Requests queued or prefilling, per fleet replica",
                       labels=("model", "replica")),
             lambda c: float(len(c.waiting) + len(c.prefilling))),
            (reg.gauge("runbook_replica_kv_pool_utilization",
                       "Fraction of allocatable KV pages held by live "
                       "sequences, per fleet replica",
                       labels=("model", "replica")),
             lambda c: c.kv.utilization()),
            (reg.counter("runbook_replica_decode_tokens_total",
                         "Tokens sampled by decode dispatches, per fleet "
                         "replica", labels=("model", "replica")),
             lambda c: float(c.metrics.get("decode_tokens", 0))),
        )
        if clear:
            g_imbalance.clear_functions()
            for metric, _fn in per_replica:
                metric.clear_functions()
            # A previous MULTI-MODEL fleet's per-group rollups must not
            # keep scraping (and pinning) its dead cores either — a
            # multi-model build re-binds them right after its groups'
            # fleets construct (fleet/multimodel._install_metrics).
            for name in ("runbook_model_running_requests",
                         "runbook_model_waiting_requests",
                         "runbook_model_kv_pool_utilization",
                         "runbook_model_decode_tokens_total"):
                stale = reg.get(name)
                if stale is not None:
                    stale.clear_functions()
        # runbook: noqa[RBK010] — model label: configured group
        # name, fixed at fleet build.
        g_imbalance.labels(model=model).set_function(self._imbalance)
        for metric, fn in per_replica:
            for gid, core in zip(self.replica_ids, self.cores):
                # runbook: noqa[RBK010] — model/replica labels: configured
                # group name + pinned replica ids, fixed at fleet build.
                metric.labels(model=model, replica=str(gid)).set_function(
                    lambda c=core, f=fn: f(c))
        # Unlabeled engine names → fleet aggregates (each core's
        # _install_metrics bound them to itself during construction; the
        # last rebind wins, and the fleet is constructed last — a
        # multi-model fleet rebinds them once more over ALL groups).
        install_fleet_aggregates(self.cores)

    def _agg_utilization(self) -> float:
        return _agg_utilization(self.cores)

    def _agg_prefix_hit_ratio(self) -> float:
        return _agg_prefix_hit_ratio(self.cores)

    def _agg_overlap_ratio(self) -> float:
        return _agg_overlap_ratio(self.cores)

    def is_saturated(self) -> bool:
        """True when a placement would shed right now (every replica's
        waiting queue at/over ``shed_queue_depth``). The HTTP layer
        pre-checks this before committing SSE headers so a saturated
        stream gets a real 503; the inevitable check-then-route race
        falls back to the in-stream error event."""
        depth = self.cfg.shed_queue_depth
        if depth is None:
            return False
        quarantined = self._quarantined
        live = [i for i in self._decode_tier if i not in quarantined]
        # No live replica at all is failover, not saturation — the HTTP
        # layer checks failing_over() first and answers a distinct 503.
        return bool(live) and all(
            len(self.cores[i].waiting) >= depth for i in live)

    def debug_steps(self, last_n: Optional[int] = None,
                    lock_timeout: float = 0.5) -> dict:
        """Fleet-wide ``GET /debug/steps``: each replica's flight records
        (already stamped with their ``replica`` index by the recorder)
        merged into one timeline ordered by wall-clock ``ts``. ONE shared
        lock budget across the loop, like :meth:`health_snapshot` — a
        debug probe over a dp=8 fleet must stay as bounded as the single
        engine's."""
        import time

        merged: list[dict] = []
        capacity = 0
        steps_total = 0
        deadline = time.monotonic() + lock_timeout
        for engine in self.replicas:
            budget = max(0.0, deadline - time.monotonic())
            snap = engine.debug_steps(last_n, lock_timeout=budget)
            capacity += snap["capacity"]
            steps_total += snap["steps_total"]
            merged.extend(snap["steps"])
        merged.sort(key=lambda r: r.get("ts", 0.0))
        if last_n is not None:
            n = max(0, int(last_n))
            merged = merged[-n:] if n else []
        return {"capacity": capacity, "steps_total": steps_total,
                "dp_replicas": self.dp, "steps": merged}

    def health_snapshot(self, lock_timeout: float = 0.5) -> dict:
        """Aggregated ``/healthz`` body: summed legacy metrics dict (the
        contract keys keep their meaning — fleet-wide totals), pooled KV
        stats, per-replica breakdown, and router state. Each replica's
        metrics snapshot under its own step lock, with ``lock_timeout``
        as ONE shared budget across the whole loop — a probe over a dp=8
        fleet must stay as bounded as the single engine's (a liveness
        probe that blocks seconds gets the pod killed mid-compile); a
        torn-but-live snapshot beats a dead prober."""
        import time

        agg: dict = {}
        replicas = []
        unresponsive: list[int] = []
        quarantined = self._quarantined
        kv_total = kv_used = kv_cached = 0
        deadline = time.monotonic() + lock_timeout
        for i, (engine, core) in enumerate(zip(self.replicas, self.cores)):
            budget = max(0.0, deadline - time.monotonic())
            # Floor of 20 ms even after the shared budget is spent: one
            # genuinely wedged replica must not make every LATER replica
            # (probed with what would be a blocking=False attempt that
            # any normal in-flight dispatch fails) read as a phantom
            # fleet-wide outage. Worst case stays bounded:
            # lock_timeout + dp × 20 ms.
            locked = engine._lock.acquire(timeout=max(budget, 0.02))
            try:
                m = dict(core.metrics)
            finally:
                if locked:
                    engine._lock.release()
            # A replica that exhausts its lock budget is NOT silently
            # reported thin: its step thread is holding the lock past a
            # liveness probe's patience — the cheapest wedge signal the
            # supervisor has. (Its metrics row is the torn lock-free
            # read, explicitly labeled.)
            status = "ok"
            if not locked:
                status = "unresponsive"
                unresponsive.append(self.replica_ids[i])
            elif i in quarantined:
                status = "quarantined"
            for k, v in m.items():
                agg[k] = agg.get(k, 0) + v
            kv = core.kv
            kv_total += kv.allocator.num_pages
            kv_used += kv.pages_in_use
            kv_cached += kv.allocator.cached_pages
            replicas.append({
                "replica": self.replica_ids[i],
                "tier": ("prefill" if i in self._prefill_tier
                         else "decode" if self._prefill_tier else "mixed"),
                "status": status,
                "running": len(core.decoding),
                "waiting": len(core.waiting) + len(core.prefilling),
                "kv": {"pages_total": kv.allocator.num_pages,
                       "pages_in_use": kv.pages_in_use,
                       "pages_cached": kv.allocator.cached_pages,
                       "utilization": round(kv.utilization(), 4)},
                "decode_tokens": m.get("decode_tokens", 0),
                "kv_pages_imported": m.get("kv_pages_imported", 0),
                "kv_pages_exported": m.get("kv_pages_exported", 0),
            })
        usable = sum(c.kv.allocator.num_pages - 1 for c in self.cores)
        body = {
            "dp_replicas": self.dp,
            "kv": {"pages_total": kv_total, "pages_in_use": kv_used,
                   "pages_cached": kv_cached,
                   "utilization": round(kv_used / usable, 4)
                   if usable else 0.0},
            "metrics": agg,
            "replicas": replicas,
            "router": {
                "routed": self.routed_counts(),
                "affinity_hit_ratio": round(self.affinity_hit_ratio(), 4),
                "imbalance_ratio": round(self._imbalance(), 4),
            },
        }
        if unresponsive:
            body["unresponsive_replicas"] = unresponsive
        if quarantined:
            body["router"]["quarantined"] = self.quarantined_replicas()
        if self.supervisor is not None:
            # Replica supervision (chaos/supervisor.py): per-replica
            # state machine, rebuild/failover counters, recent
            # transitions — the `runbook chaos status` body.
            body["supervisor"] = self.supervisor.snapshot()
        if self.chaos is not None:
            # Live fault injection (chaos/inject.py): the seeded
            # schedule and every applied fault window with provenance.
            body["chaos"] = self.chaos.snapshot()
        if self._kv_share:
            body["router"]["kv_share"] = {
                "xreplica_hits": int(self._m_xreplica_hits.value),
                "pages_pulled": int(self._m_xreplica_pages.value),
                "pull_seconds": round(self._m_xreplica_seconds.value, 4),
                "stale_rejections": self.stale_rejections(),
                "stale_by_reason": {
                    reason: int(child.value)
                    for reason, child in self._m_stale.items()},
            }
        if self._prefill_tier:
            # The /healthz tier breakdown: which GLOBAL replica ids serve
            # each tier (matches the replicas[].tier rows above).
            body["router"]["disagg"] = {
                "prefill_replicas": [self.replica_ids[i]
                                     for i in self._prefill_tier],
                "decode_replicas": [self.replica_ids[i]
                                    for i in self._decode_tier],
                "warm_prefills": int(self._m_warm.total()),
            }
        return body
