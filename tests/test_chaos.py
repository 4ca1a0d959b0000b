"""Chaos hardening (runbookai_tpu/chaos + simulate/traffic.py): seeded
fault-schedule determinism, traffic scenario-mix determinism, the fleet
supervisor's state machine (crash detect → quarantine → failover →
online rebuild → hysteresis rejoin; wedge detection; flap damping), the
injector's fault seams (spill pressure, window provenance), and the
/healthz supervisor/chaos blocks."""

import asyncio
import json
import threading
import time

import pytest

from runbookai_tpu.chaos import (
    FAULT_KINDS,
    SUPERVISOR_STATES,
    ChaosInjector,
    ChaosReplicaCrash,
    FaultEvent,
    FaultSchedule,
    FleetSupervisor,
)
from runbookai_tpu.engine.request import FinishReason, SamplingParams
from runbookai_tpu.model.jax_tpu import JaxTpuClient
from runbookai_tpu.simulate.traffic import (
    SCENARIO_CLASSES,
    TrafficMix,
    generate_traffic,
)


def sp(max_new=8, **kw):
    kw.setdefault("temperature", 0.0)
    kw.setdefault("stop_token_ids", ())
    return SamplingParams(max_new_tokens=max_new, **kw)


def ids(text: str) -> list[int]:
    return list(text.encode())


def crash_hook(core) -> None:
    core.chaos_hook = None
    raise ChaosReplicaCrash("test crash")


# ------------------------------------------------- schedule determinism


def test_fault_schedule_same_seed_byte_identical():
    a = FaultSchedule.generate(17, 30.0, 2)
    b = FaultSchedule.generate(17, 30.0, 2)
    assert a.to_json() == b.to_json()
    # JSON round-trips to the exact same document too.
    assert json.loads(a.to_json()) == json.loads(b.to_json())


def test_fault_schedule_different_seed_differs():
    assert FaultSchedule.generate(17, 30.0, 2).to_json() \
        != FaultSchedule.generate(18, 30.0, 2).to_json()


def test_fault_schedule_bounds_and_kinds():
    s = FaultSchedule.generate(5, 60.0, 4, events_per_minute=30)
    assert s.events, "empty schedule"
    last = -1.0
    for e in s.events:
        assert e.kind in FAULT_KINDS
        assert 0.0 <= e.at_s <= 60.0
        assert e.at_s + e.duration_s <= 60.0 + 1e-6
        assert e.at_s >= last  # sorted
        last = e.at_s
        if e.kind in ("replica_crash", "replica_wedge",
                      "spill_pressure"):
            assert e.replica is not None and 0 <= e.replica < 4
        if e.kind == "replica_crash":
            assert e.duration_s == 0.0


def test_fault_schedule_ensure_crash_and_validation():
    s = FaultSchedule.generate(3, 10.0, 2, kinds=("kv_pull_delay",),
                               ensure_crash=True)
    crashes = [e for e in s.events if e.kind == "replica_crash"]
    assert len(crashes) == 1
    # Mid-run, while traffic still flows.
    assert crashes[0].at_s == pytest.approx(3.5)
    with pytest.raises(ValueError, match="unknown fault kinds"):
        FaultSchedule.generate(1, 10.0, 2, kinds=("nope",))
    with pytest.raises(ValueError, match="at least one"):
        FaultSchedule.generate(1, 10.0, 2, kinds=())


# --------------------------------------------- traffic mix determinism


def test_traffic_mix_same_seed_byte_identical():
    a = generate_traffic(9, 20.0)
    b = generate_traffic(9, 20.0)
    assert a.to_json() == b.to_json()
    assert generate_traffic(10, 20.0).to_json() != a.to_json()


def test_traffic_mix_covers_every_class_and_validates():
    mix = generate_traffic(9, 20.0)
    assert set(mix.by_class()) == set(SCENARIO_CLASSES)
    for c in mix.chains:
        assert c.turns, c.chain_id
        assert 0.0 <= c.at_s <= 20.0
        assert c.priority in ("interactive", "batch")
        for t in c.turns:
            assert t.prompt_ids and all(0 <= x < 256
                                        for x in t.prompt_ids)
            assert t.max_new_tokens >= 2
    # Agentic chains carry context; shared-prefix sessions share one
    # page-aligned prefix across their turns.
    agentic = [c for c in mix.chains if c.cls == "agentic_chain"]
    assert all(c.carry_context and len(c.turns) >= 3 for c in agentic)
    sessions = [c for c in mix.chains
                if c.cls == "shared_prefix_session"]
    for c in sessions:
        prefixes = {c2.turns[0].prompt_ids[:16] for c2 in sessions}
        assert len(prefixes) == 1
        assert all(t.prompt_ids[:16] == c.turns[0].prompt_ids[:16]
                   for t in c.turns)
    with pytest.raises(ValueError, match="unknown scenario classes"):
        generate_traffic(1, 10.0, classes=("nope",))


def test_traffic_mix_round_trip_shape():
    mix = generate_traffic(2, 5.0, chains_per_minute=60)
    doc = json.loads(mix.to_json())
    assert doc["seed"] == 2 and doc["duration_s"] == 5.0
    assert len(doc["chains"]) == len(mix.chains)
    assert isinstance(TrafficMix(seed=2, duration_s=5.0), TrafficMix)


# ----------------------------------------- supervisor state machine


async def test_supervisor_crash_detect_rebuild_rejoin_zero_lost():
    """The acceptance arc at unit scale: a mid-traffic crash is
    detected, the replica quarantined, its in-flight requests failed
    over (zero lost), the engine rebuilt online, routing rejoined after
    hysteresis — and a post-recovery request on the rebuilt replica is
    byte-identical to its pre-crash answer."""
    client = JaxTpuClient.for_testing(max_new_tokens=8, dp_replicas=2)
    fleet = client.engine
    sup = FleetSupervisor(fleet, poll_interval_s=0.02,
                          wedge_timeout_s=30.0,
                          rejoin_hysteresis_s=0.05).start()
    try:
        base = await fleet.generate(ids("determinism probe"), sp())
        fleet.cores[0].chaos_hook = crash_hook
        outs = await asyncio.gather(*[
            fleet.generate(ids(f"crash wave {i}"), sp())
            for i in range(6)])
        assert all(o.finish_reason != FinishReason.ABORTED
                   for o in outs), "requests lost across the crash"
        for _ in range(400):
            if sup.state_of(0) == "healthy" and not fleet._quarantined:
                break
            await asyncio.sleep(0.025)
        assert sup.state_of(0) == "healthy"
        seq = [(t["replica"], t["to"]) for t in sup.transitions]
        assert seq == [(0, "failed"), (0, "rebuilding"),
                       (0, "rejoining"), (0, "healthy")]
        snap = sup.snapshot()
        assert snap["rebuilds_total"] == 1
        assert snap["replicas"][0]["rebuilds"] == 1
        # The rebuilt engine serves byte-identically.
        again = await fleet.generate(ids("determinism probe"), sp())
        assert again.token_ids == base.token_ids
        # Both replicas take traffic again.
        outs = await asyncio.gather(*[
            fleet.generate(ids(f"post {i} request"), sp())
            for i in range(6)])
        served = {o.request_id.split("-", 1)[0] for o in outs}
        assert served == {"r0", "r1"}
        await fleet.stop()
    finally:
        sup.stop()


async def test_supervisor_wedge_detection_caller_never_hangs():
    """A wedged step thread (stall under the engine lock with work
    queued) is detected as suspect → failed; the in-flight caller is
    unblocked (aborted, never hung) even though the wedge still holds
    the engine lock, and the replica rebuilds."""
    from runbookai_tpu.engine.fleet import AsyncFleet

    client = JaxTpuClient.for_testing(max_new_tokens=16)
    # dp=1 via explicit AsyncFleet so the router surface is in play and
    # there is no sibling to fail over to — the caller must STILL be
    # unblocked with a clean abort.
    fleet = AsyncFleet([client.core])
    release = threading.Event()

    def wedge_hook(core) -> None:
        release.wait(timeout=30.0)
        core.chaos_hook = None

    sup = FleetSupervisor(fleet, poll_interval_s=0.02,
                          wedge_timeout_s=0.15,
                          rejoin_hysteresis_s=0.05).start()
    try:
        fleet.cores[0].chaos_hook = wedge_hook
        t0 = time.monotonic()
        out = await asyncio.wait_for(
            fleet.generate(ids("wedged request"), sp()), timeout=20.0)
        # The supervisor unblocked us long before the wedge resolved.
        assert out.finish_reason == FinishReason.ABORTED
        assert time.monotonic() - t0 < 15.0
        tos = [t["to"] for t in sup.transitions]
        assert "suspect" in tos and "failed" in tos
        reason = next(t["reason"] for t in sup.transitions
                      if t["to"] == "failed")
        assert "wedged" in reason
        # Detection proven — restore a production-shaped timeout before
        # the rebuilt core's first dispatch: a fresh engine recompiles,
        # and a compile-length stall is exactly what wedge_timeout_s
        # must tolerate (the config docstring's contract).
        sup.wedge_timeout_s = 30.0
        release.set()
        for _ in range(400):
            if sup.state_of(0) == "healthy":
                break
            await asyncio.sleep(0.025)
        assert sup.state_of(0) == "healthy"
        out = await fleet.generate(ids("after rebuild"), sp())
        assert out.finish_reason != FinishReason.ABORTED
        await fleet.stop()
    finally:
        release.set()
        sup.stop()


def test_supervisor_flap_damping_sticky_failed():
    """A replica that dies on every rebuild stays quarantined (sticky
    ``failed``) after ``max_consecutive_rebuilds`` instead of flapping.
    Driven deterministically: fake clock, manual poll_once, no thread."""
    client = JaxTpuClient.for_testing(max_new_tokens=4, dp_replicas=2)
    fleet = client.engine
    now = [0.0]
    sup = FleetSupervisor(fleet, wedge_timeout_s=1.0,
                          rejoin_hysteresis_s=0.5,
                          max_consecutive_rebuilds=2,
                          clock=lambda: now[0])

    async def crash_via_loop():
        # Crash through the real AsyncEngine loop so loop_crashed trips.
        fleet.cores[0].chaos_hook = crash_hook
        out = await fleet.replicas[0].generate(ids("crash"), sp(2))
        assert out.finish_reason == FinishReason.ABORTED

    for round_i in range(3):
        asyncio.run(crash_via_loop())
        # Crash detected on the first poll of this round.
        sup.poll_once()
        if round_i < 2:
            assert sup.state_of(0) == "rejoining"
            # Hysteresis doubles per consecutive failure.
            hyst = [t["reason"] for t in sup.transitions
                    if t["to"] == "rejoining"][-1]
            assert f"{0.5 * 2 ** round_i:.2f}" in hyst
            now[0] += 1000.0
            sup.poll_once()
            assert sup.state_of(0) == "healthy"
            # Immediately relapse within the flap window: consecutive
            # failure count keeps growing (clock does not advance).
        else:
            assert sup.state_of(0) == "failed"
            assert "left quarantined" in sup._states[0].reason
    # Sticky: further polls never rebuild it again.
    rebuilds = int(sup._m_rebuilds.value)
    now[0] += 1000.0
    sup.poll_once()
    assert sup.state_of(0) == "failed"
    assert int(sup._m_rebuilds.value) == rebuilds
    # The sibling keeps serving (routing excludes the quarantined one).
    out = asyncio.run(fleet.generate(ids("sibling serves"), sp(2)))
    assert out.request_id.startswith("r1-")
    asyncio.run(fleet.stop())


# --------------------------------------------------- injector seams


def test_injector_window_provenance_and_metrics():
    client = JaxTpuClient.for_testing(max_new_tokens=4, dp_replicas=2)
    fleet = client.engine
    schedule = FaultSchedule(seed=1, duration_s=1.0, dp=2, events=[
        FaultEvent(kind="replica_crash", at_s=0.0, duration_s=0.0,
                   replica=0),
        FaultEvent(kind="tenant_flood", at_s=0.0, duration_s=0.1,
                   params={"requests": 2}),
    ])
    floods = []
    inj = ChaosInjector(fleet, schedule, flood_fn=floods.append)
    before = inj._m_faults["replica_crash"].value
    inj.start()
    for _ in range(100):
        if len(inj.windows) == 2:
            break
        time.sleep(0.02)
    # The crash hook was armed on the target core while running...
    assert fleet.cores[0].chaos_hook is not None
    inj.stop()
    snap = inj.snapshot()
    kinds = {w["kind"]: w for w in snap["windows"]}
    # ...and disarmed at stop() because the idle replica never stepped:
    # it must not detonate on the first real request after the run, and
    # the provenance says so instead of claiming the fault happened.
    assert fleet.cores[0].chaos_hook is None
    assert kinds["replica_crash"]["status"] == "disarmed (never fired)"
    assert kinds["replica_crash"]["replica"] == 0
    assert kinds["tenant_flood"]["status"] == "applied"
    assert snap["events_applied"] == 1  # the flood; not the disarmed crash
    assert floods and floods[0].params["requests"] == 2
    assert inj._m_faults["replica_crash"].value == before + 1
    assert fleet.chaos is inj


def test_injector_flood_without_handler_records_error():
    client = JaxTpuClient.for_testing(max_new_tokens=4, dp_replicas=2)
    schedule = FaultSchedule(seed=1, duration_s=1.0, dp=2, events=[
        FaultEvent(kind="tenant_flood", at_s=0.0, duration_s=0.1)])
    inj = ChaosInjector(client.engine, schedule)
    before = inj._m_faults["tenant_flood"].value
    inj._t0 = time.monotonic()
    inj._apply(schedule.events[0])
    assert "error" in inj.windows[0]["status"]
    # An errored fault is never counted as applied.
    assert inj._m_faults["tenant_flood"].value == before
    assert inj.snapshot()["events_applied"] == 0


def test_injector_spill_pressure_collapses_then_restores():
    client = JaxTpuClient.for_testing(max_new_tokens=4,
                                      kv_spill_pages=8)
    core = client.core
    spill = core.kv.spill
    assert spill is not None and spill.max_pages == 8
    from runbookai_tpu.engine.fleet import AsyncFleet

    fleet = AsyncFleet([core])
    now = [0.0]
    schedule = FaultSchedule(seed=1, duration_s=10.0, dp=1, events=[
        FaultEvent(kind="spill_pressure", at_s=0.0, duration_s=5.0,
                   replica=0)])
    inj = ChaosInjector(fleet, schedule, clock=lambda: now[0])
    inj._t0 = 0.0
    inj._apply(schedule.events[0])
    assert core.chaos_hook is not None
    core.step()  # hook fires under the (implicit) step path
    assert spill.max_pages == 0
    now[0] = 6.0  # window over
    core.step()
    assert spill.max_pages == 8
    assert core.chaos_hook is None


def test_spill_tier_evict_all_counts():
    from runbookai_tpu.engine.kv_cache import HostSpillTier

    tier = HostSpillTier(4)
    for h in range(3):
        tier.put(h, (h,), [], [], "d")
    assert len(tier) == 3
    dropped = tier.evict_all()
    assert dropped == 3 and len(tier) == 0
    assert tier.evictions == 3


# --------------------------------------------------- surfaces


async def test_healthz_carries_supervisor_and_chaos_blocks():
    client = JaxTpuClient.for_testing(max_new_tokens=4, dp_replicas=2)
    fleet = client.engine
    sup = FleetSupervisor(fleet)
    schedule = FaultSchedule.generate(1, 5.0, 2)
    inj = ChaosInjector(fleet, schedule)
    snap = fleet.health_snapshot()
    assert snap["supervisor"]["replicas"][0]["state"] == "healthy"
    assert snap["chaos"]["seed"] == 1
    assert snap["chaos"]["events_planned"] == len(schedule.events)
    # The CLI's extraction sees the fleet-level blocks.
    from runbookai_tpu.cli.main import _chaos_blocks, _render_chaos

    body = dict(snap)
    blocks = _chaos_blocks(body)
    assert "(fleet)" in blocks
    text = _render_chaos(blocks)
    assert "r0: healthy" in text and "seed=1" in text
    await fleet.stop()
    sup.stop()


def test_supervisor_states_inventory():
    # The state vocabulary is a wire contract (metric labels, /healthz,
    # docs/robustness.md) — additions must update all three.
    assert SUPERVISOR_STATES == ("healthy", "suspect", "failed",
                                 "rebuilding", "rejoining")


# ------------------------------------------------------- the soak gate


# Two replicas in the group the schedule crashes: with one, every chain that
# meets the outage is lost and a mix this short has tenants of a single chain.
# Under chaos 3.5 s a pass: seed 14's crash lands at 2.07 s, after 13 of the 15
# chains have arrived (the batch flood 0.35 s before it) and before the last
# (2.22 s), so chains are compared on a loaded machine too and the crash meets
# traffic. At 2 s a loaded machine compares as few as 3 of 13; at 3 s and a
# quarter of the lengths the last chain (1.72 s) can finish before the crash
# (1.78 s) and the pass ends with it unapplied.
@pytest.mark.parametrize("models", [None, "llama3-test:2,qwen2-test"],
                         ids=["one_group", "two_groups"])
@pytest.mark.parametrize("chaos", [True, False],
                         ids=["chaos", "no_chaos"])
def test_soak_gate(tmp_path, chaos, models):
    """The composed gate (chaos/soak.py) on the tiny models: the seeded
    mix twice through identically built fleets. With chaos every
    invariant holds and the supervisor's transition record shows the
    injected crash detected, failed over, rebuilt and rejoined, with its
    incident captured; without chaos neither pass opens an incident.
    Either way the two passes agree byte for byte on every chain outside
    a fault window."""
    from runbookai_tpu.chaos.soak import soak_gate

    inv = soak_gate(3.5 if chaos else 2.0, models=models, chaos=chaos,
                    token_scale=0.25,
                    incident_dir=str(tmp_path / "bundles"))
    assert [k for k, v in inv.items() if not v["passed"]] == [], inv
    assert set(inv) == {
        "zero_lost_outside_fault_windows", "interactive_ttft_p95",
        "tenant_fairness", "rss_bound", "fd_bound", "digest_determinism",
        "turns_timed_out", "supervisor_recovered", "detection_coverage",
        "query_stores_held_the_pass",
        "query_baseline_zero_incidents", "query_baseline_zero_lost",
        "query_detection_coverage", "query_interactive_ttft_p95"}
    traffic = inv["zero_lost_outside_fault_windows"]
    chains = traffic["chains"]
    assert chains > 0 and traffic["turns"] >= chains
    # Every scenario class was exercised.
    assert set(traffic["classes"]) == {
        "short_chat", "agentic_chain", "batch_flood",
        "shared_prefix_session", "spiky_tenant"}
    det = inv["digest_determinism"]
    assert det["compared"] > 0 and det["mismatched"] == []
    assert inv["turns_timed_out"]["baseline"] == []
    assert inv["turns_timed_out"]["chaos"] == []
    # Each pass's store held the signal the query_* verdicts read.
    for store in ("baseline", "chaos"):
        held = inv["query_stores_held_the_pass"][store]
        assert held["series"] > 0 and held["samples"] > 0
        assert held["dropped_series"] == 0
    cov = inv["detection_coverage"]
    assert cov["baseline_opens"] == 0
    rec = inv["supervisor_recovered"]
    assert rec["crash_applied"] is chaos
    if not chaos:
        # No fault window: every chain of the second pass is compared.
        assert det["compared"] == chains
        assert cov["chaos_incidents"] == 0 and cov["coverage"] == []
        assert rec["transitions"] == []
        assert traffic["fault_windows"] == []
        return
    tos = [t["to"] for t in rec["transitions"]]
    for state in ("failed", "rebuilding", "rejoining", "healthy"):
        assert state in tos, tos
    assert rec["rebuilds_total"] >= 1
    crash_rows = [r for r in cov["coverage"] if r["kind"] == "replica_crash"]
    assert crash_rows, cov["coverage"]
    for row in crash_rows:
        assert row["detected_signal"] == "replica_failure"
        assert row["incident"] and row["mttd_s"] is not None
    assert cov["bundles"] and all(
        b["hash_verified"] and b["schema_valid"] and b["has_history"]
        for b in cov["bundles"])
    # A named incident_dir keeps the bundles for `runbook incident show`.
    assert {b["name"] for b in cov["bundles"]} <= {
        p.name for p in (tmp_path / "bundles").iterdir()}
    # The store caught the incident's open window in flight: the gauge is
    # absent while nothing is open.
    qcov = inv["query_detection_coverage"]
    assert qcov["crash_applied"] and any(v >= 1 for v in qcov["values"])


def _pass_record(*, windows, transitions, incidents=(), origin=1000.0):
    """What ``_soak_scenarios_pass`` returns, as far as the window and
    coverage arithmetic reads it (offsets in seconds from ``origin``)."""
    return {
        "wall_origin": origin,
        "chaos": {"windows": [dict(w, status=w.get("status", "applied"))
                              for w in windows]},
        "supervisors": [{"transitions": [
            {"replica": r, "to": to, "ts": origin + at}
            for r, to, at in transitions]}],
        "incidents": list(incidents),
    }


def test_soak_effective_windows_extend_to_recovery():
    """A crash or wedge window stays open until its replica's next
    rejoin; every supervisor failure arc is a window of its own; other
    kinds keep their scheduled end. A chain counts as inside when it
    overlaps ANY of them, so overlapping windows act as their union."""
    from runbookai_tpu.chaos.soak import _overlaps, _soak_effective_windows

    passed = _pass_record(
        windows=[
            {"kind": "replica_crash", "replica": 0,
             "applied_at_s": 2.0, "ends_at_s": 2.0},
            {"kind": "kv_pull_delay", "replica": 1,
             "applied_at_s": 3.0, "ends_at_s": 4.0},
            {"kind": "replica_wedge", "replica": 1,
             "applied_at_s": 9.0, "ends_at_s": 9.5}],
        transitions=[(0, "failed", 2.3), (0, "rebuilding", 2.4),
                     (0, "rejoining", 5.0), (0, "healthy", 6.0)])
    windows = _soak_effective_windows(passed)
    assert windows == [
        pytest.approx((1.9, 6.1)),      # the crash, to replica 0's rejoin
        pytest.approx((2.9, 4.1)),      # a delay keeps its scheduled end
        (pytest.approx(8.9), float("inf")),  # a wedge that never rejoined
        pytest.approx((2.2, 6.1)),      # the supervisor's own arc
    ]

    def chain(start, end):
        return {"t_start_s": start, "t_end_s": end}

    assert _overlaps(chain(5.0, 5.5), windows)    # after the crash's
    # scheduled end, before the rejoin: inside the fault
    assert _overlaps(chain(4.05, 4.08), windows)  # covered by the union
    assert not _overlaps(chain(6.2, 8.8), windows)
    assert not _overlaps(chain(0.0, 1.9), windows)
    assert _overlaps(chain(50.0, 51.0), windows)  # the open-ended wedge
    assert _soak_effective_windows({"chaos": None}) == []


def test_incident_coverage_flags_an_uncovered_required_window():
    """One row per APPLIED window. A crash window no incident of a
    matching signal class overlaps fails the required check; an
    overlapping ``replica_failure`` incident passes it and banks the
    time to detect; a missed optional kind is reported, not gated."""
    from runbookai_tpu.chaos.soak import _incident_coverage

    windows = [
        {"kind": "replica_crash", "replica": 0,
         "applied_at_s": 2.0, "ends_at_s": 2.0},
        {"kind": "kv_pull_delay", "replica": 1,
         "applied_at_s": 3.0, "ends_at_s": 3.5},
        {"kind": "tenant_flood", "replica": 0, "status": "skipped",
         "applied_at_s": 4.0, "ends_at_s": 4.5}]
    transitions = [(0, "failed", 2.3), (0, "healthy", 6.0)]

    def incident(signal, opened, resolved, origin=1000.0):
        return {"id": f"inc-{signal}", "signal": signal,
                "opened_ts": origin + opened,
                "resolved_ts": (origin + resolved
                                if resolved is not None else None)}

    # The wrong signal class, and the right class outside the window.
    rows, ok = _incident_coverage(_pass_record(
        windows=windows, transitions=transitions,
        incidents=[incident("queue_wait", 2.5, 3.0),
                   incident("replica_failure", 7.0, 8.0)]))
    assert ok is False
    assert [r["kind"] for r in rows] == ["replica_crash", "kv_pull_delay"]
    assert rows[0]["required"] and rows[0]["detected_signal"] is None
    assert rows[0]["window_s"] == [2.0, 6.0]      # extended to the rejoin
    # The delay window matched queue_wait — one of its expected classes.
    assert rows[1]["detected_signal"] == "queue_wait"
    assert not rows[1]["required"]

    rows, ok = _incident_coverage(_pass_record(
        windows=windows, transitions=transitions,
        incidents=[incident("replica_failure", 2.4, None)]))
    assert ok is True
    assert rows[0]["detected_signal"] == "replica_failure"
    assert rows[0]["incident"] == "inc-replica_failure"
    assert rows[0]["mttd_s"] == pytest.approx(0.4)
    assert rows[1]["detected_signal"] is None     # reported, not gated
    assert _incident_coverage({"chaos": None}) == ([], True)


def test_soak_query_reads_what_the_evaluator_returns():
    """A gate condition evaluated through the embedded store gives what
    obs/query.py gives for the same expression at the store's newest
    sample; an empty store is 'never sampled', not zero."""
    from runbookai_tpu.chaos.soak import _soak_query
    from runbookai_tpu.obs import MetricsTSDB, evaluate
    from runbookai_tpu.utils.metrics import MetricsRegistry

    store = MetricsTSDB(interval_s=1.0, retention_s=3600.0, max_series=64,
                        registry=MetricsRegistry(), clock=lambda: 500.0)
    assert _soak_query(store, "increase(runbook_incident_total[60s])") == {
        "expr": "increase(runbook_incident_total[60s])", "values": []}
    for ts, v in ((100, 0), (110, 2), (140, 5)):
        store.ingest(ts, "runbook_incident_total",
                     {"signal": "replica_failure"}, v)
    store.ingest(140, "runbook_incident_total", {"signal": "slo_burn"}, 0)
    store.ingest(120, "runbook_incident_open",
                 {"signal": "replica_failure"}, 1)
    for expr in ("increase(runbook_incident_total[60s])",
                 "max_over_time(runbook_incident_open[60s])",
                 "increase(runbook_router_shed_total[60s])"):
        got = _soak_query(store, expr)
        want = evaluate(store, expr, now=140)
        assert got == {"expr": expr,
                       "values": [r["value"] for r in want["result"]]}
    # One sample in the window is no increase: that series is absent.
    assert _soak_query(
        store, "increase(runbook_incident_total[60s])")["values"] == [5.0]


def test_soak_cli_exits_nonzero_on_a_failed_invariant(monkeypatch, capsys):
    """``python -m runbookai_tpu.chaos.soak``: one JSON document; a
    failed invariant is named in it and the exit code is non-zero. A
    TTFT bound of 0 fails both TTFT verdicts and nothing else."""
    from runbookai_tpu.chaos import soak

    monkeypatch.setattr(soak, "TTFT_P95_BOUND_MS", 0.0)
    rc = soak.main(["2", "--no-chaos", "--seed", "3"])
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    doc = json.loads(out[0])
    assert rc == 1 and doc["passed"] is False
    assert doc["failed"] == ["interactive_ttft_p95",
                             "query_interactive_ttft_p95"]
    assert doc["invariants"]["interactive_ttft_p95"]["bound_ms"] == 0.0
    assert doc["invariants"]["interactive_ttft_p95"]["p95_ms"] > 0
    assert doc["invariants"]["detection_coverage"]["chaos_incidents"] == 0
    with pytest.raises(ValueError, match="unknown model config"):
        soak.main(["2", "--models", "no-such-model"])


def test_soak_gate_counts_a_turn_that_never_returns_as_lost(monkeypatch,
                                                            capfd):
    """The gate ends with a verdict, never by waiting: a turn past
    ``TURN_TIMEOUT_S`` is recorded as lost (here every turn is) and named
    by a verdict of its own, which no fault window excuses; the first of
    a pass dumps the stacks. With nothing compared, determinism fails."""
    from runbookai_tpu.chaos import soak

    monkeypatch.setattr(soak, "TURN_TIMEOUT_S", 1e-4)
    inv = soak.soak_gate(2.0, chaos=False, token_scale=0.25)
    lost = inv["zero_lost_outside_fault_windows"]
    assert lost["passed"] is False
    assert lost["lost_total"] == lost["chains"] > 0
    assert len(lost["lost_outside_windows"]) == lost["chains"]
    assert inv["tenant_fairness"]["passed"] is False
    assert inv["digest_determinism"]["compared"] == 0
    assert inv["digest_determinism"]["passed"] is False
    timed = inv["turns_timed_out"]
    assert timed["passed"] is False and timed["bound_s"] == 1e-4
    assert len(timed["baseline"]) == len(timed["chaos"]) == lost["chains"]
    err = capfd.readouterr().err
    assert err.count("every thread's and task's stack follows") == 2
    assert "run_chain" in err      # a task's stack: where the turn waits
