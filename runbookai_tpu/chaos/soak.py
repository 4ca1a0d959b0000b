"""The production-invariant soak gate (docs/robustness.md).

The one place where the fleet, the replica supervisor, the chaos
injector and the incident monitor run composed. A seeded scenario mix
(simulate/traffic.py: short chat, agentic chains, batch floods,
shared-prefix sessions, spiky tenants) runs TWICE through identically
built fleets (fleet/build.py, the construction `runbook serve` uses): a
chaos-free baseline pass, then a chaos pass with the seeded fault
schedule (chaos/inject.py) and a supervisor on every group
(chaos/supervisor.py). :func:`soak_gate` returns one verdict per
invariant, each with the figures it was decided from:

- zero lost requests outside (recovery-extended) fault windows;
- interactive p95 TTFT within :data:`TTFT_P95_BOUND_MS`;
- per-tenant completion fairness (:data:`TENANT_FAIRNESS_FLOOR`);
- bounded RSS growth (:data:`RSS_GROWTH_BOUND_MB`) and fd delta across
  the chaos pass;
- per-chain digest determinism: every chain completed in both passes
  outside fault windows is byte-identical to the baseline, and at least
  one was compared;
- no turn or recovery probe of either pass waited past
  :data:`TURN_TIMEOUT_S`;
- supervisor recovery: an injected crash is detected, failed over,
  rebuilt and rejoined (the transition record proves it);
- detection coverage: required fault windows overlap a detected
  incident, every bundle verifies, the baseline opened none;
- the same conditions re-derived through each pass's embedded
  time-series store (obs/tsdb.py, obs/query.py), which held series and
  samples and dropped none.

A gate has verdicts, no speed: nothing here is a measurement of the
system (`python3 -m benchmark.run` is).

    python -m runbookai_tpu.chaos.soak [SECONDS] [--models A,B[:dp]]
                                       [--seed N] [--no-chaos]

prints the verdicts as one JSON document and exits non-zero when one
fails.
"""

from __future__ import annotations

import hashlib
import json
import math
import os

# The gate's thresholds: one value each in every run on record, so
# constants beside the invariants they bound.
# Generous on purpose: the bound catches a request parked behind a dead
# or wedged replica (tens of seconds, until a caller's timeout), not a
# slow one; a tiny CPU run and a chip run both sit far under it.
TTFT_P95_BOUND_MS = 30_000.0
# A tenant may lose chains inside fault windows, never most of them:
# under half completed means admission starved that tenant.
TENANT_FAIRNESS_FLOOR = 0.5
# Growth of the process's peak RSS across the chaos pass. A rebuild
# holds a second engine's buffers while the first drains; a leak of one
# engine a fault does not fit under this in a 600 s run.
RSS_GROWTH_BOUND_MB = 8192.0
# Open descriptors across the chaos pass (bundles, the supervisor's and
# monitor's threads): a descriptor leaked per request or fault passes it.
FD_DELTA_BOUND = 64
# A turn or recovery probe that has not come back in two minutes never
# will (a tiny CPU run answers in milliseconds, a chip run in seconds).
# The gate exists to catch a caller parked for ever, so it ends with the
# verdict `turns_timed_out` (which no fault window excuses) instead of
# waiting with that caller; the first one dumps every thread's and every
# task's stack to stderr.
TURN_TIMEOUT_S = 120.0

# Every replica of the gate's fleets: small enough that the tier-1 smoke
# warms in seconds, a pool and a sequence budget the mix never fills.
_PAGE_SIZE = 16
_NUM_PAGES = 512
_SLOTS = 4
_PREFILL_CHUNK = 128
_MAX_SEQ_LEN = 2048
_DEFAULT_MODEL = "llama3-test"
# Two replicas in the default group: with one, every chain that meets the
# crash is lost, and fail-over has nowhere to go.
_DEFAULT_DP = 2
# Arrivals of the scenario mix (simulate/traffic.py), chains a minute.
_CHAINS_PER_MINUTE = 120.0


def token_streams_digest(token_lists) -> str:
    """Digest of a list of output token streams, in submission order —
    equal digests across the two passes prove they served byte-identical
    streams for that chain."""
    return hashlib.md5(json.dumps(
        [list(map(int, ids)) for ids in token_lists]).encode()).hexdigest()


def parse_models_spec(spec: str) -> list[tuple[str, int]]:
    """``A,B:2`` -> [("A", 1), ("B", 2)] — validated against the model
    catalog; group names are distinct."""
    from runbookai_tpu.models.llama import CONFIGS

    groups: list[tuple[str, int]] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, dp_s = part.partition(":")
        if name not in CONFIGS:
            raise ValueError(f"--models: unknown model config {name!r} "
                             f"(see models/llama.CONFIGS)")
        groups.append((name, max(1, int(dp_s or 1))))
    names = [n for n, _ in groups]
    if not groups or len(set(names)) != len(names):
        raise ValueError("--models needs distinct model configs")
    return groups


def _dump_stacks(what: str) -> None:
    """Where everything stands when the first turn times out, before it is
    cancelled: every thread's stack (an engine loop stuck under its lock)
    and every task's chain of awaits (what a turn is parked on)."""
    import asyncio
    import faulthandler
    import sys

    err = sys.__stderr__
    print(f"soak gate: {what} has not come back in {TURN_TIMEOUT_S} s; "
          f"every thread's and task's stack follows", file=err, flush=True)
    faulthandler.dump_traceback(file=err, all_threads=True)
    for task in asyncio.all_tasks():
        print(f"Task {task.get_name()}:", file=err)
        awaited = task.get_coro()
        while awaited is not None:  # Task.print_stack stops at the first
            frame = getattr(awaited, "cr_frame", None) \
                or getattr(awaited, "ag_frame", None)
            if frame is None:
                print(f"  {awaited!r}", file=err)
                break
            print(f'  File "{frame.f_code.co_filename}", line '
                  f"{frame.f_lineno}, in {frame.f_code.co_name}", file=err)
            awaited = getattr(awaited, "cr_await", None) \
                or getattr(awaited, "ag_await", None)
    err.flush()


def _soak_scenarios_pass(fleet, mix, *, chaos_schedule=None,
                         supervisor_kw=None, duration_s=0.0,
                         incident_dir=None):
    """Drive one scenario-mix pass through a live MultiModelFleet.

    Open-loop arrivals: each chain sleeps to its scheduled offset, then
    runs its turns causally (an agentic chain's turn carries the
    previous turns' context). With ``chaos_schedule`` set, a
    FleetSupervisor attaches to every group fleet and a ChaosInjector
    walks the schedule against the FIRST group (the dp the schedule was
    generated for); the pass returns per-chain records plus the
    supervisor/chaos snapshots the invariant gate is computed from.

    EVERY pass (chaos or baseline) runs an IncidentMonitor over the
    group fleets — the detection-coverage invariant needs both sides:
    injected fault windows must overlap detected incidents of matching
    signal classes, and the chaos-free baseline must open ZERO (the
    false-positive gate). Hysteresis scales with the run so a 2 s CPU
    smoke and the 1800 s protocol exercise the same lifecycle.

    Each pass also carries its own :class:`MetricsTSDB` (obs/tsdb.py),
    monitor-driven so a registry sweep lands at every detector poll.
    The per-pass store is what isolates the gate's query-expressed
    invariants: registry counters are process-global and cumulative
    across both passes, but ``increase()`` over one pass's window diffs
    only what that pass contributed. The store is returned so the gate
    can evaluate invariants through obs/query.py."""
    import asyncio
    import random as _random
    import time as _time

    from runbookai_tpu.chaos import ChaosInjector, FleetSupervisor
    from runbookai_tpu.engine.request import (
        FinishReason,
        FleetSaturated,
        SamplingParams,
    )
    from runbookai_tpu.obs import (
        IncidentDetector,
        IncidentMonitor,
        MetricsTSDB,
        default_policies,
    )
    from runbookai_tpu.sched import PRIORITY_BATCH, PRIORITY_INTERACTIVE

    model_groups = list(fleet.groups.values())
    supervisors = []
    injector = None
    records: dict[str, dict] = {}
    timed_out: list[str] = []

    def note_timeout(what: str) -> None:
        if not timed_out:
            _dump_stacks(what)
        timed_out.append(what)

    # Retention must hold the WHOLE pass (plus the recovery tail) or the
    # gate's closing queries would prune away the early fault windows.
    tsdb = MetricsTSDB(
        interval_s=max(0.02, duration_s / 100.0),
        retention_s=max(120.0, duration_s * 4.0 + 60.0),
        max_series=4096)
    incident_monitor = IncidentMonitor(
        [g.fleet for g in model_groups],
        detector=IncidentDetector(default_policies(
            open_after_s=min(5.0, max(0.2, duration_s * 0.1)),
            resolve_after_s=min(10.0, max(0.4, duration_s * 0.2)))),
        bundle_dir=incident_dir, max_bundles=64,
        poll_interval_s=0.02, tsdb=tsdb,
        history_lookback_s=max(2.0, min(60.0, duration_s)))

    async def run_turn(chain, turn, prompt, rec):
        sampling = SamplingParams(
            temperature=chain.temperature,
            max_new_tokens=turn.max_new_tokens, stop_token_ids=(),
            seed=(chain.seed if chain.temperature > 0 else None))
        priority = (PRIORITY_BATCH if chain.priority == "batch"
                    else PRIORITY_INTERACTIVE)
        t0 = _time.monotonic() - rec["_t_origin"]
        toks: list[int] = []
        ttft_ms = None
        aborted = False
        if turn.stream:
            sink: list = []
            try:
                t_start = _time.perf_counter()
                agen = fleet.generate_stream(
                    prompt, sampling, priority=priority,
                    model=chain.model, request_sink=sink,
                    request_id=chain.chain_id)
                async for tok in agen:
                    if ttft_ms is None:
                        ttft_ms = (_time.perf_counter() - t_start) * 1e3
                    toks.append(tok)
            except FleetSaturated:
                aborted = True
            req = sink[-1] if sink else None
            if req is not None and req.finish_reason is FinishReason.ABORTED:
                aborted = True
        else:
            out = await fleet.generate(
                prompt, sampling, priority=priority, model=chain.model,
                request_id=chain.chain_id)
            toks = list(out.token_ids)
            ttft_ms = out.ttft_ms
            aborted = out.finish_reason is FinishReason.ABORTED
        rec["turns"].append({
            "t_start_s": round(t0, 4),
            "t_end_s": round(_time.monotonic() - rec["_t_origin"], 4),
            "ttft_ms": (round(ttft_ms, 3) if ttft_ms is not None
                        else None),
            "tokens": len(toks),
            "aborted": aborted,
        })
        return toks, aborted

    async def run_chain(chain, t_origin):
        rec = {"cls": chain.cls, "tenant": chain.tenant,
               "model": chain.model, "interactive":
               chain.priority == "interactive",
               "turns": [], "aborted": False, "_t_origin": t_origin,
               "streams": []}
        records[chain.chain_id] = rec
        await asyncio.sleep(max(0.0, chain.at_s
                                - (_time.monotonic() - t_origin)))
        context: list[int] = []
        for turn in chain.turns:
            if turn.gap_s:
                await asyncio.sleep(turn.gap_s)
            prompt = (context + list(turn.prompt_ids)
                      if chain.carry_context else list(turn.prompt_ids))
            # Keep causal chains inside the engine's sequence budget.
            max_prompt = _MAX_SEQ_LEN - turn.max_new_tokens - 16
            prompt = prompt[-max_prompt:]
            turn_task = asyncio.ensure_future(
                run_turn(chain, turn, prompt, rec))
            if (await asyncio.wait({turn_task}, timeout=TURN_TIMEOUT_S))[0]:
                toks, aborted = turn_task.result()
            else:
                note_timeout(chain.chain_id)  # while it is still parked
                turn_task.cancel()
                toks, aborted = [], True
                now = round(_time.monotonic() - t_origin, 4)
                rec["turns"].append({
                    "t_start_s": round(now - TURN_TIMEOUT_S, 4),
                    "t_end_s": now, "ttft_ms": None, "tokens": 0,
                    "aborted": True, "timed_out": True})
            rec["streams"].append(toks)
            if aborted:
                rec["aborted"] = True
                break  # a dead turn kills the causal chain
            context = prompt + toks
        rec["t_start_s"] = rec["turns"][0]["t_start_s"] if rec["turns"] \
            else chain.at_s
        rec["t_end_s"] = rec["turns"][-1]["t_end_s"] if rec["turns"] \
            else chain.at_s
        rec["digest"] = token_streams_digest(rec.pop("streams"))
        rec.pop("_t_origin")

    async def _run():
        nonlocal injector
        loop = asyncio.get_running_loop()
        t_origin = _time.monotonic()
        wall_origin = _time.time()
        incident_monitor.start()
        if chaos_schedule is not None:
            for g in model_groups:
                sup = FleetSupervisor(g.fleet, **(supervisor_kw or {}))
                sup.start()
                supervisors.append(sup)

            def flood_fn(event):
                # Synthetic tenant-flood burst: fire-and-forget batch
                # requests through the event loop — chaos traffic, not
                # gated traffic.
                rng = _random.Random(event.at_s)
                sp = SamplingParams(temperature=0.0, max_new_tokens=4,
                                    stop_token_ids=())

                async def _flood():
                    await asyncio.gather(*[
                        fleet.generate(
                            [rng.randrange(0, 256) for _ in range(24)],
                            sp, priority=PRIORITY_BATCH,
                            model=model_groups[0].name)
                        for _ in range(event.params.get("requests", 4))],
                        return_exceptions=True)

                asyncio.run_coroutine_threadsafe(_flood(), loop)

            injector = ChaosInjector(model_groups[0].fleet,
                                     chaos_schedule, flood_fn=flood_fn)
            injector.start()
        await asyncio.gather(*[run_chain(c, t_origin)
                               for c in mix.chains])
        if injector is not None:
            # Recovery phase: keep light probe traffic flowing until an
            # applied crash has been detected AND every replica is back
            # to healthy (or the budget runs out) — a crash whose hook
            # fires on the run's last step still gets its full
            # detect→rebuild→rejoin arc before the supervisors stop.
            # Probes are chaos plumbing, never gated traffic.
            deadline = _time.monotonic() + min(
                15.0, max(3.0, duration_s))
            probe_sp = SamplingParams(temperature=0.0, max_new_tokens=2,
                                      stop_token_ids=())

            def needs_recovery() -> bool:
                crash_applied = any(
                    w["kind"] == "replica_crash"
                    and w["status"] == "applied"
                    for w in injector.snapshot()["windows"])
                trans = [t for s in supervisors for t in s.transitions]
                if crash_applied and not any(t["to"] == "failed"
                                             for t in trans):
                    return True  # hook or detection still pending
                return any(s.state_of(i) != "healthy"
                           for s in supervisors
                           for i in range(s.fleet.dp))

            while needs_recovery() and _time.monotonic() < deadline:
                probes = await asyncio.gather(*[
                    asyncio.wait_for(
                        fleet.generate(list(range(65, 81)), probe_sp,
                                       model=g.name), TURN_TIMEOUT_S)
                    for g in model_groups], return_exceptions=True)
                for g, out in zip(model_groups, probes):
                    if isinstance(out, asyncio.TimeoutError):
                        note_timeout(f"probe:{g.name}")
                await asyncio.sleep(0.05)
            injector.stop()
        for sup in supervisors:
            sup.stop()
        incident_monitor.stop()
        await fleet.stop()
        return t_origin, wall_origin

    t0 = _time.perf_counter()
    _t_origin, wall_origin = asyncio.run(_run())
    wall = _time.perf_counter() - t0
    return {
        "records": records,
        "timed_out": timed_out,
        "wall_s": round(wall, 3),
        "wall_origin": wall_origin,
        "chaos": injector.snapshot() if injector is not None else None,
        "supervisors": [s.snapshot() for s in supervisors],
        "incidents": incident_monitor.incidents(),
        "tsdb": tsdb,
    }


def _soak_query(store, expr: str) -> dict:
    """Evaluate one gate condition through the embedded history
    (obs/tsdb.py + obs/query.py) instead of the pass's in-process
    measurements. The verdict coming out the query path proves the
    store actually carried the signal end to end — sampling, retention,
    and evaluator semantics (counter resets, absence-not-zero) all sit
    between the fleet and the number the gate reads."""
    from runbookai_tpu.obs import evaluate

    newest = store.snapshot()["newest_ts"]
    if newest is None:
        return {"expr": expr, "values": []}
    doc = evaluate(store, expr, now=newest)
    return {"expr": expr,
            "values": [r["value"] for r in doc["result"]]}


def _transitions(passed: dict) -> list[dict]:
    return [t for s in passed["supervisors"] for t in s["transitions"]]


def _rejoin_after(passed: dict, replica: int, start: float) -> float:
    """Run offset of ``replica``'s first rejoin-to-healthy transition at
    or after ``start`` (infinity when it never rejoined): where a crash or
    wedge window really ends."""
    origin = passed["wall_origin"]
    rejoins = [t["ts"] - origin for t in _transitions(passed)
               if t["replica"] == replica and t["to"] == "healthy"
               and t["ts"] - origin >= start]
    return min(rejoins, default=float("inf"))


def _soak_effective_windows(passed: dict) -> list[tuple[float, float]]:
    """Fault windows in run-offset seconds, extended to RECOVERY: a
    crash/wedge window stays open until the target replica's next
    rejoin-to-healthy transition (a chain failing between the crash and
    the rebuild is inside the fault, not a lost request). Every
    supervisor failure→rejoin arc counts as a window too — a failover
    the supervisor initiated IS fault handling, injected or not (excess
    arcs stay visible as ``supervisor_recovered``'s rebuilds_total
    churn)."""
    chaos = passed.get("chaos")
    if not chaos:
        return []
    windows = []
    for w in chaos["windows"]:
        start, end = w["applied_at_s"], w["ends_at_s"]
        if w["kind"] in ("replica_crash", "replica_wedge"):
            end = _rejoin_after(passed, w["replica"], start)
        windows.append((start - 0.1, end + 0.1))
    for t in _transitions(passed):
        if t["to"] == "failed":
            start = t["ts"] - passed["wall_origin"]
            windows.append((start - 0.1,
                            _rejoin_after(passed, t["replica"], start)
                            + 0.1))
    return windows


def _incident_coverage(chaotic: dict) -> tuple[list[dict], bool]:
    """Detection-coverage table: one row per APPLIED fault window —
    which signal class detected it and how long detection took (MTTD).
    Crash/wedge windows extend to the target replica's rejoin (same
    recovery extension as the lost-request gate). Returns ``(rows,
    required_ok)``: kinds in ``COVERAGE_REQUIRED_KINDS`` (their
    detection path — supervisor transitions — is deterministic) MUST
    overlap a detected incident; other kinds are reported but a miss
    does not fail the gate (a 10 ms kv_pull_delay legitimately detects
    as nothing)."""
    from runbookai_tpu.obs import (
        COVERAGE_REQUIRED_KINDS,
        FAULT_SIGNAL_CLASSES,
    )

    chaos = chaotic.get("chaos")
    if not chaos:
        return [], True
    wall_origin = chaotic["wall_origin"]

    spans = [(inc, inc["opened_ts"] - wall_origin,
              (inc["resolved_ts"] - wall_origin)
              if inc.get("resolved_ts") is not None else float("inf"))
             for inc in chaotic.get("incidents", ())]
    rows: list[dict] = []
    required_ok = True
    for w in chaos["windows"]:
        if w["status"] != "applied":
            continue
        start, end = w["applied_at_s"], w["ends_at_s"]
        if w["kind"] in ("replica_crash", "replica_wedge"):
            end = _rejoin_after(chaotic, w["replica"], start)
        expected = FAULT_SIGNAL_CLASSES.get(w["kind"], ())
        hits = [(inc, opened) for inc, opened, resolved in spans
                if inc["signal"] in expected
                and opened <= end + 0.25 and resolved >= start - 0.25]
        hit = min(hits, key=lambda p: p[1]) if hits else None
        required = w["kind"] in COVERAGE_REQUIRED_KINDS
        if required and hit is None:
            required_ok = False
        rows.append({
            "kind": w["kind"],
            "replica": w["replica"],
            "window_s": [round(start, 3),
                         round(end, 3) if end != float("inf") else None],
            "expected_signals": list(expected),
            "detected_signal": hit[0]["signal"] if hit else None,
            "incident": hit[0]["id"] if hit else None,
            "mttd_s": (round(max(0.0, hit[1] - start), 3)
                       if hit else None),
            "required": required,
        })
    return rows, required_ok


def _overlaps(rec: dict, windows) -> bool:
    s, e = rec.get("t_start_s", 0.0), rec.get("t_end_s", 0.0)
    return any(s < we and e > ws for ws, we in windows)



def _build_fleet(groups, token_scale: float):
    """One of the two identically-built fleets: the construction
    ``runbook serve`` uses for ``llm.models`` (fleet/build.py: global
    replica indices contiguous across groups, disjoint device slices
    where the host has enough), then every replica warmed outside the
    pass at the mix's own lengths — a first dispatch that compiles inside
    it reads as a wedge — and its counters forgotten."""
    import jax
    import numpy as np

    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.fleet.build import build_multi_model_fleet
    from runbookai_tpu.utils.config import LLMConfig, ModelGroupConfig

    fleet = build_multi_model_fleet(LLMConfig(
        provider="jax-tpu", model=groups[0][0],
        dtype=("bfloat16" if jax.default_backend() == "tpu"
               else "float32"),
        page_size=_PAGE_SIZE, num_pages=_NUM_PAGES,
        max_batch_slots=_SLOTS, prefill_chunk=_PREFILL_CHUNK,
        max_seq_len=_MAX_SEQ_LEN, decode_steps=8,
        models=[ModelGroupConfig(name=name, dp_replicas=dp)
                for name, dp in groups]))
    # Its own stream: the mix's prompts stay untouched.
    warm_rng = np.random.default_rng(20_011)
    for core in fleet.cores:
        core.submit(EngineRequest(
            prompt_ids=warm_rng.integers(
                0, 256, size=max(16, int(128 * token_scale))).tolist(),
            sampling=SamplingParams(
                temperature=0.0, stop_token_ids=(),
                max_new_tokens=max(2, int(64 * token_scale)))))
        core.run_until_idle()
        core.reset_metrics()
    return fleet


def soak_gate(duration_s: float, *, models: str | None = None,
              seed: int = 14, chaos: bool = True, token_scale: float = 1.0,
              incident_dir: str | None = None) -> dict:
    """Run the gate for ``duration_s`` seconds of traffic a pass and
    return ``{invariant: {"passed": bool, ...its figures}}``.

    ``models`` is ``A,B[:dp]`` (served model groups; the default is one
    ``llama3-test`` group of two replicas), ``seed`` seeds the mix and
    the fault schedule, ``chaos=False`` runs both passes fault-free.
    ``token_scale`` shrinks the mix's prompt and answer lengths (the
    tier-1 smoke runs a quarter: a mix of a few seconds has tenants of a
    single chain, and a long request in flight when the crash lands is
    that tenant's whole share); ``incident_dir`` keeps the chaos pass's
    bundles for ``runbook incident show --bundle`` (a temporary
    directory otherwise)."""
    import resource
    import shutil
    import tempfile
    from pathlib import Path

    from runbookai_tpu.chaos import FaultSchedule
    from runbookai_tpu.obs import BUNDLE_SCHEMA_VERSION
    from runbookai_tpu.obs.incident import bundle_hash, load_bundle
    from runbookai_tpu.simulate.traffic import generate_traffic

    groups = (parse_models_spec(models) if models
              else [(_DEFAULT_MODEL, _DEFAULT_DP)])
    names = [name for name, _ in groups]
    mix = generate_traffic(
        seed, duration_s, chains_per_minute=_CHAINS_PER_MINUTE,
        prompt_scale=token_scale, max_new_scale=token_scale,
        models=(names if len(names) > 1 else None))
    schedule = (FaultSchedule.generate(
        seed, duration_s, groups[0][1], ensure_crash=True)
        if chaos else None)
    supervisor_kw = {
        "poll_interval_s": 0.02,
        # The floor must exceed a rebuilt core's first-dispatch compile
        # (the docs/robustness.md wedge_timeout_s contract) — an
        # aggressive value fails over replicas that are merely
        # compiling, and a dp=1 group then flaps rebuild→compile→
        # false-wedge forever.
        "wedge_timeout_s": max(3.0, min(8.0, duration_s * 0.1)),
        "rejoin_hysteresis_s": min(0.5, max(0.05, duration_s * 0.02)),
    }

    # Baseline pass: same mix, no chaos — the digest reference AND the
    # detection false-positive gate (its incident monitor must open
    # zero incidents against fault-free traffic).
    baseline = _soak_scenarios_pass(_build_fleet(groups, token_scale), mix,
                                    duration_s=duration_s)

    fd_dir = "/proc/self/fd"
    fds_before = (len(os.listdir(fd_dir)) if os.path.isdir(fd_dir)
                  else None)
    rss_before_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    # Black-box capture target for the chaos pass: keep the bundles when
    # the operator names a directory, else a temp dir verified + pruned
    # after the gate reads it.
    keep_bundles = bool(incident_dir)
    if not incident_dir:
        incident_dir = tempfile.mkdtemp(prefix="soak-incidents-")

    chaotic = _soak_scenarios_pass(
        _build_fleet(groups, token_scale), mix, chaos_schedule=schedule,
        supervisor_kw=supervisor_kw, duration_s=duration_s,
        incident_dir=incident_dir)

    rss_after_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    fds_after = (len(os.listdir(fd_dir)) if os.path.isdir(fd_dir)
                 else None)

    windows = _soak_effective_windows(chaotic)
    recs = chaotic["records"]
    base_recs = baseline["records"]
    lost = [cid for cid, r in recs.items() if r["aborted"]]
    lost_outside = [cid for cid in lost
                    if not _overlaps(recs[cid], windows)]
    ttfts = sorted(
        t["ttft_ms"] for r in recs.values() if r["interactive"]
        for t in r["turns"] if t["ttft_ms"] is not None)
    p95_ttft = (ttfts[min(len(ttfts) - 1,
                          int(0.95 * len(ttfts)))] if ttfts else None)
    per_tenant: dict[str, dict] = {}
    for r in recs.values():
        t = per_tenant.setdefault(r["tenant"],
                                  {"chains": 0, "completed": 0})
        t["chains"] += 1
        t["completed"] += 0 if r["aborted"] else 1
    fairness_min = min((t["completed"] / t["chains"]
                        for t in per_tenant.values()), default=1.0)
    comparable = [
        cid for cid, r in recs.items()
        if not r["aborted"] and not _overlaps(r, windows)
        and cid in base_recs and not base_recs[cid]["aborted"]]
    mismatched = [cid for cid in comparable
                  if recs[cid]["digest"] != base_recs[cid]["digest"]]
    rss_growth_mb = (rss_after_kb - rss_before_kb) / 1024.0
    fd_delta = (fds_after - fds_before
                if fds_before is not None and fds_after is not None
                else None)
    crash_applied = bool(chaotic["chaos"]) and any(
        w["kind"] == "replica_crash" and w["status"] == "applied"
        for w in chaotic["chaos"]["windows"])
    transitions = _transitions(chaotic)
    recovered = (not crash_applied) or all(
        any(t["replica"] == w["replica"] and t["to"] == state
            for t in transitions)
        for w in chaotic["chaos"]["windows"]
        if w["kind"] == "replica_crash" and w["status"] == "applied"
        for state in ("failed", "rebuilding", "rejoining", "healthy"))
    # Detection coverage (obs/detect.py, obs/incident.py): every
    # REQUIRED injected fault window overlaps a detected incident of a
    # matching signal class; the chaos-free baseline opened zero
    # incidents; every captured bundle is schema-valid and its content
    # hash verifies.
    coverage_rows, coverage_required_ok = _incident_coverage(chaotic)
    baseline_opens = len(baseline.get("incidents", ()))
    # Verify THIS run's bundles only (each incident records the bundle
    # it captured): a shared incident_dir may hold bundles from
    # earlier runs, and neither a stale corrupt file nor a stale valid
    # one may decide this run's verdict. An incident with NO recorded
    # bundle is itself a failure — the black box went dark exactly when
    # it mattered. One load per bundle; the hash check is inline.
    bundle_rows = []
    for inc in chaotic.get("incidents", ()):
        name = inc.get("bundle")
        row = {"incident": inc["id"], "name": name,
               "hash_verified": False, "schema_valid": False,
               "has_history": False}
        if name:
            try:
                doc = load_bundle(Path(incident_dir) / name)
            except (OSError, json.JSONDecodeError):
                doc = None
            if doc is not None:
                row["hash_verified"] = (doc.get("content_hash")
                                        == bundle_hash(doc))
                row["schema_valid"] = (doc.get("schema_version")
                                       == BUNDLE_SCHEMA_VERSION)
                # The pre-open lookback window (obs/tsdb.py) sits
                # INSIDE the hash envelope — hash_verified above
                # already proves it arrived untampered.
                row["has_history"] = doc.get("history") is not None
        bundle_rows.append(row)
    if not keep_bundles:
        shutil.rmtree(incident_dir, ignore_errors=True)
    # has_history gates too: every soak monitor carries a store, so a
    # bundle without its lookback section means the black box dropped
    # the trend exactly when it mattered.
    bundles_ok = all(b["hash_verified"] and b["schema_valid"]
                     and b["has_history"] for b in bundle_rows)
    invariants = {
        "zero_lost_outside_fault_windows": {
            "passed": not lost_outside,
            "chains": len(recs),
            "turns": sum(len(r["turns"]) for r in recs.values()),
            "classes": mix.by_class(),
            "lost_total": len(lost),
            "lost_outside_windows": lost_outside,
            "fault_windows": [
                [round(s, 3), (round(e, 3) if e != float("inf") else None)]
                for s, e in windows]},
        "interactive_ttft_p95": {
            "passed": p95_ttft is None or p95_ttft <= TTFT_P95_BOUND_MS,
            "p95_ms": (round(p95_ttft, 2) if p95_ttft is not None
                       else None),
            "bound_ms": TTFT_P95_BOUND_MS},
        "tenant_fairness": {
            "passed": fairness_min >= TENANT_FAIRNESS_FLOOR,
            "min_completion_ratio": round(fairness_min, 4),
            "floor": TENANT_FAIRNESS_FLOOR,
            "per_tenant": per_tenant},
        "rss_bound": {
            "passed": rss_growth_mb <= RSS_GROWTH_BOUND_MB,
            "growth_mb": round(rss_growth_mb, 1),
            "bound_mb": RSS_GROWTH_BOUND_MB},
        "fd_bound": {
            "passed": fd_delta is None or fd_delta <= FD_DELTA_BOUND,
            "delta": fd_delta, "bound": FD_DELTA_BOUND},
        "digest_determinism": {
            # Nothing compared proves nothing: a run whose fault windows
            # cover every chain says so instead of passing.
            "passed": bool(comparable) and not mismatched,
            "compared": len(comparable),
            "mismatched": mismatched},
        "turns_timed_out": {
            # Its own verdict: a turn that never came back spans
            # TURN_TIMEOUT_S, so it overlaps some fault window of almost
            # any schedule and has no TTFT. Neither excuses it here.
            "passed": not (baseline["timed_out"] or chaotic["timed_out"]),
            "baseline": baseline["timed_out"],
            "chaos": chaotic["timed_out"],
            "bound_s": TURN_TIMEOUT_S},
        "supervisor_recovered": {
            "passed": recovered,
            "crash_applied": crash_applied,
            "rebuilds_total": sum(s["rebuilds_total"]
                                  for s in chaotic["supervisors"]),
            "failovers_total": sum(s["failovers_total"]
                                   for s in chaotic["supervisors"]),
            "transitions": transitions},
        "detection_coverage": {
            "passed": (coverage_required_ok and baseline_opens == 0
                       and bundles_ok),
            "required_covered": coverage_required_ok,
            "baseline_opens": baseline_opens,
            "chaos_incidents": len(chaotic.get("incidents", ())),
            # Fault kind → detected signal + MTTD, one row per applied
            # window (obs/detect.py's FAULT_SIGNAL_CLASSES mapping).
            "coverage": coverage_rows,
            "bundles": bundle_rows},
    }
    # Query-expressed invariants: the same gate conditions re-derived
    # through each pass's embedded time-series store (obs/tsdb.py) and
    # the PromQL-lite evaluator (obs/query.py). Each pass carries its
    # OWN store, so increase()/max_over_time() over its window isolate
    # that pass's contribution even though registry counters are
    # process-global. These are verdicts like every direct measurement
    # above.
    q_win = f"{int(math.ceil(chaotic['tsdb'].retention_s))}s"
    q_base_inc = _soak_query(
        baseline["tsdb"], f"increase(runbook_incident_total[{q_win}])")
    q_base_shed = _soak_query(
        baseline["tsdb"],
        f"increase(runbook_router_shed_total[{q_win}])")
    q_open = _soak_query(
        chaotic["tsdb"], f"max_over_time(runbook_incident_open[{q_win}])")
    q_ttft = _soak_query(
        chaotic["tsdb"],
        f"histogram_quantile(0.95, runbook_ttft_seconds_bucket[{q_win}])")
    q_ttft_worst = max(q_ttft["values"], default=None)
    stores = {name: {k: passed["tsdb"].snapshot()[k]
                     for k in ("series", "samples", "dropped_series")}
              for name, passed in (("baseline", baseline),
                                   ("chaos", chaotic))}
    invariants["query_stores_held_the_pass"] = {
        # An empty store passes every condition below ("never sampled"):
        # each pass's store must have held series and samples, and
        # dropped none for want of room.
        "passed": all(st["series"] > 0 and st["samples"] > 0
                      and st["dropped_series"] == 0
                      for st in stores.values()),
        **stores}
    invariants["query_baseline_zero_incidents"] = {
        # False-positive gate through the store: the chaos-free pass's
        # incident counters must not have moved. An empty result also
        # passes — absence is "never sampled", not a hidden increment.
        "passed": all(v == 0 for v in q_base_inc["values"]), **q_base_inc}
    invariants["query_baseline_zero_lost"] = {
        "passed": all(v == 0 for v in q_base_shed["values"]),
        **q_base_shed}
    invariants["query_detection_coverage"] = {
        # runbook_incident_open is ABSENT while nothing is open, so a
        # sampled value >= 1 proves the store caught the incident's
        # open window in flight.
        "passed": ((not crash_applied)
                   or any(v >= 1 for v in q_open["values"])),
        "crash_applied": crash_applied, **q_open}
    invariants["query_interactive_ttft_p95"] = {
        # Bucket-interpolated p95 of the worst series (per-replica
        # grouping) against the same bound the direct measurement uses.
        "passed": (q_ttft_worst is None
                   or q_ttft_worst * 1e3 <= TTFT_P95_BOUND_MS),
        "p95_ms": (round(q_ttft_worst * 1e3, 2)
                   if q_ttft_worst is not None else None),
        "bound_ms": TTFT_P95_BOUND_MS, **q_ttft}
    return invariants


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m runbookai_tpu.chaos.soak",
        description="The production-invariant soak gate "
                    "(docs/robustness.md): one JSON document of verdicts; "
                    "exit 1 when an invariant fails.")
    ap.add_argument("seconds", nargs="?", type=float, default=30.0,
                    help="seconds of traffic a pass (default 30; the "
                         "protocol runs 600 and 1800)")
    ap.add_argument("--models", metavar="A,B[:dp]",
                    help="served model groups (default: one llama3-test "
                         "group of two replicas)")
    ap.add_argument("--seed", type=int, default=14,
                    help="seeds the traffic mix and the fault schedule")
    ap.add_argument("--no-chaos", action="store_true",
                    help="the control: the same mix twice, no faults")
    args = ap.parse_args(argv)
    invariants = soak_gate(args.seconds, models=args.models,
                           seed=args.seed, chaos=not args.no_chaos)
    failed = [name for name, v in invariants.items() if not v["passed"]]
    print(json.dumps({"passed": not failed, "failed": failed,
                      "invariants": invariants}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
