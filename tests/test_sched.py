"""SLO-aware multi-tenant scheduler + admission control (runbookai_tpu/sched/).

Covers the three control layers end to end: the weighted-deficit (stride)
admission queue in the engine (interleave ratios, FCFS within class,
no-credit-hoarding, byte parity vs FIFO), per-tenant token budgets / rate
limits at the OpenAI server (429 + Retry-After before enqueue, settle
refunds, /tenants surface), the SLO feedback controller (direction and
clamp bounds, byte parity with feedback off), and the router's
queue-depth-aware placement.
"""

import json
import time
import types
import urllib.error
import urllib.request

import pytest

from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.model.jax_tpu import JaxTpuClient
from runbookai_tpu.sched import (
    PRIORITY_BATCH,
    PRIORITY_INTERACTIVE,
    TenantGovernor,
    TenantPolicy,
    WeightedDeficitScheduler,
    class_label,
    class_name,
    class_priority,
)
from runbookai_tpu.sched.tenants import DEFAULT_TENANT
from runbookai_tpu.utils import metrics as metrics_mod


def sp(max_new=8, **kw):
    kw.setdefault("temperature", 0.0)
    kw.setdefault("stop_token_ids", ())
    return SamplingParams(max_new_tokens=max_new, **kw)


def req(priority, arrival, rid=None):
    r = types.SimpleNamespace(priority=priority, arrival_time=arrival)
    r.rid = rid
    return r


# ------------------------------------------------------------ class naming


def test_class_helpers():
    assert class_priority("interactive") == PRIORITY_INTERACTIVE
    assert class_priority("BATCH") == PRIORITY_BATCH
    assert class_priority("3") == 3
    assert class_priority(2) == 2
    assert class_name(PRIORITY_BATCH) == "batch"
    assert class_name(5) == "p5"
    assert class_label(PRIORITY_INTERACTIVE) == "interactive"
    assert class_label(7) == "other"  # bounded metric cardinality
    with pytest.raises(ValueError):
        class_priority("urgentest")
    with pytest.raises(ValueError):
        class_priority(True)


# ----------------------------------------------------------------- WDRR


def test_wdrr_interleaves_by_weight():
    s = WeightedDeficitScheduler()
    waiting = ([req(PRIORITY_BATCH, i) for i in range(18)]
               + [req(PRIORITY_INTERACTIVE, 100 + i) for i in range(8)])
    out = s.order(waiting)
    # 8:1 default weights: the first 9 admits hold all 8 interactive.
    head = [r.priority for r in out[:9]]
    assert head.count(PRIORITY_INTERACTIVE) == 8
    assert head.count(PRIORITY_BATCH) == 1
    # Every request appears exactly once.
    assert sorted(id(r) for r in out) == sorted(id(r) for r in waiting)


def test_wdrr_fcfs_within_class_and_preempted_head():
    s = WeightedDeficitScheduler()
    # A preempted request keeps its ORIGINAL arrival_time, so it stays
    # ahead of same-class newcomers wherever the list order put it.
    old = req(PRIORITY_BATCH, 1.0, "old")
    newer = req(PRIORITY_BATCH, 2.0, "new")
    out = s.order([newer, old, req(PRIORITY_INTERACTIVE, 3.0, "i")])
    batch_order = [r.rid for r in out if r.priority == PRIORITY_BATCH]
    assert batch_order == ["old", "new"]


def test_wdrr_order_is_pure_and_commit_advances():
    s = WeightedDeficitScheduler()
    waiting = ([req(PRIORITY_BATCH, i) for i in range(4)]
               + [req(PRIORITY_INTERACTIVE, 10 + i) for i in range(4)])
    first = [r.arrival_time for r in s.order(waiting)]
    second = [r.arrival_time for r in s.order(waiting)]
    assert first == second  # ordering alone never charges a class
    # One batch admit "pays" a full stride (840); nine interactive
    # admits overtake it (9 * 105) — batch is then next in line.
    s.commit(PRIORITY_BATCH)
    for _ in range(9):
        s.commit(PRIORITY_INTERACTIVE)
    out = s.order(waiting)
    assert out[0].priority == PRIORITY_BATCH


def test_wdrr_no_credit_hoarding_after_idle():
    s = WeightedDeficitScheduler()
    # Interactive served alone for a long stretch...
    for _ in range(1000):
        s.commit(PRIORITY_INTERACTIVE)
    # ...then batch traffic appears. It must NOT get a 1000-admit burst:
    # it re-joins at the active floor, so the interleave is the plain
    # weight ratio again.
    waiting = ([req(PRIORITY_BATCH, i) for i in range(18)]
               + [req(PRIORITY_INTERACTIVE, 100 + i) for i in range(8)])
    head = [r.priority for r in s.order(waiting)[:9]]
    assert head.count(PRIORITY_BATCH) <= 2


def test_wdrr_no_credit_hoarding_for_previously_served_class():
    """The harder hoarding case: a class that WAS served early (so it
    has a persisted pass) then goes idle for a long stretch. Its stale
    pass is the minimum of the known passes, so a min-based clamp would
    be a no-op and the returning flood would bank the whole idle period
    as credit — admits must stay at the weight ratio instead."""
    s = WeightedDeficitScheduler()
    for _ in range(3):
        s.commit(PRIORITY_BATCH)  # batch served at startup...
    for _ in range(1000):
        s.commit(PRIORITY_INTERACTIVE)  # ...then idle for a long time
    waiting = ([req(PRIORITY_BATCH, i) for i in range(120)]
               + [req(PRIORITY_INTERACTIVE, 1000 + i) for i in range(8)])
    head = [r.priority for r in s.order(waiting)[:9]]
    # At most its one-stride in-rotation credit, never a 100+ burst.
    assert head.count(PRIORITY_BATCH) <= 2
    assert head.count(PRIORITY_INTERACTIVE) >= 7


def test_wdrr_unknown_class_weights_monotone():
    s = WeightedDeficitScheduler()
    assert s.weight_of(PRIORITY_BATCH) == 1.0
    assert s.weight_of(PRIORITY_INTERACTIVE) == 8.0
    assert s.weight_of(-3) == 1.0
    assert s.weight_of(5) > s.weight_of(2) > s.weight_of(PRIORITY_BATCH)
    with pytest.raises(ValueError):
        WeightedDeficitScheduler({0: 0.0})


# ------------------------------------------------------ engine integration


@pytest.fixture(scope="module")
def tiny_client():
    return JaxTpuClient.for_testing(max_new_tokens=8)


def make_core(client, **engine_kw):
    import dataclasses

    from runbookai_tpu.engine.engine import EngineCore

    ecfg = dataclasses.replace(client.core.ecfg, **engine_kw)
    return EngineCore(client.core.cfg, client.core.params,
                      client.tokenizer, ecfg,
                      mask_fn=client.core.mask_fn,
                      advance_fn=client.core.advance_fn)


def _mk_req(text, priority, max_new=4):
    return EngineRequest(prompt_ids=list(text.encode()),
                         sampling=sp(max_new), priority=priority)


def test_engine_batch_flood_does_not_starve_interactive(tiny_client):
    """A batch flood in the queue first; interactive arrives behind it.
    The WDRR queue admits interactive ahead of most of the flood — and
    batch still finishes (no starvation either way)."""
    core = make_core(tiny_client, max_batch_slots=1)
    flood = [_mk_req(f"batch flood item {i:02d}", PRIORITY_BATCH)
             for i in range(6)]
    inter = [_mk_req(f"interactive turn {i}", PRIORITY_INTERACTIVE)
             for i in range(2)]
    for r in flood + inter:
        core.submit(r)
    core.run_until_idle()
    order = [core.finished.index(r) for r in inter]
    last_batch = max(core.finished.index(r) for r in flood)
    # Both interactive requests finished before the flood drained.
    assert max(order) < last_batch
    assert all(r.finish_reason is not None for r in flood + inter)


def test_engine_interactive_load_does_not_starve_batch(tiny_client):
    """Strict priority would never admit batch while interactive waits;
    WDRR gives batch its weighted share (1 in 9)."""
    core = make_core(tiny_client, max_batch_slots=1)
    inter = [_mk_req(f"interactive stream {i:02d}", PRIORITY_INTERACTIVE)
             for i in range(12)]
    batch = _mk_req("the one batch item", PRIORITY_BATCH)
    for r in inter[:6] + [batch] + inter[6:]:
        core.submit(r)
    core.run_until_idle()
    # The batch request is NOT last: it rode its 1-in-9 share.
    assert core.finished.index(batch) < len(core.finished) - 1


def test_engine_priority_policy_keeps_strict_order(tiny_client):
    core = make_core(tiny_client, max_batch_slots=1,
                     sched_policy="priority")
    assert core._sched is None
    lo = _mk_req("low priority arrives first!", 0)
    hi = _mk_req("high priority arrives late", 5)
    core.submit(lo)
    core.submit(hi)
    core.run_until_idle()
    assert core.finished.index(hi) < core.finished.index(lo)


def test_engine_bad_policy_rejected(tiny_client):
    with pytest.raises(ValueError):
        make_core(tiny_client, sched_policy="lottery")


def test_weighted_vs_fifo_byte_parity(tiny_client):
    """Weighted scheduling reorders ADMITS, never a stream's TOKENS: the
    same request set through a WDRR core with mixed classes and through
    a single-class FIFO core yields identical per-request streams."""
    prompts = [f"parity prompt number {i:02d} with some tail" for i in
               range(6)]
    streams = {}
    for arm, classes in (("wdrr", [PRIORITY_INTERACTIVE, PRIORITY_BATCH]),
                         ("fifo", [PRIORITY_BATCH, PRIORITY_BATCH])):
        core = make_core(tiny_client, max_batch_slots=2)
        reqs = [_mk_req(p, classes[i % 2], max_new=6)
                for i, p in enumerate(prompts)]
        for r in reqs:
            core.submit(r)
        core.run_until_idle()
        streams[arm] = [r.all_out_ids for r in reqs]
    assert streams["wdrr"] == streams["fifo"]


def test_flight_recorder_carries_class_occupancy(tiny_client):
    core = make_core(tiny_client, max_batch_slots=2)
    core.submit(_mk_req("interactive in the batch!", PRIORITY_INTERACTIVE,
                        max_new=6))
    core.submit(_mk_req("batch rides along here", PRIORITY_BATCH,
                        max_new=6))
    core.run_until_idle()
    from runbookai_tpu.engine.flight_recorder import STEP_RECORD_FIELDS

    assert "classes" in STEP_RECORD_FIELDS
    recs = core.flight.snapshot()
    busy = [r for r in recs if r["classes"]]
    assert busy, recs
    assert any(set(r["classes"]) == {"interactive", "batch"}
               for r in busy)
    # Slot-steps per priority class over the window: who held the decode
    # batch (the scheduler's fairness evidence), off the records.
    slot_steps: dict[str, int] = {}
    for r in recs:
        for cls, n in r["classes"].items():
            slot_steps[cls] = slot_steps.get(cls, 0) + n
    assert slot_steps.get("interactive", 0) > 0
    assert slot_steps.get("batch", 0) > 0


def test_sched_metrics_and_admit_event_class(tiny_client, tmp_path):
    from runbookai_tpu.utils.trace import Tracer

    trace = tmp_path / "trace.jsonl"
    tracer = Tracer(str(trace))
    core = make_core(tiny_client, max_batch_slots=2)
    core.tracer = tracer
    reg = metrics_mod.get_registry()
    admits = reg.counter("runbook_sched_admits_total",
                         "Requests admitted to prefill, per priority "
                         "class", labels=("cls",))
    before = {label: 0.0 for label in ("interactive", "batch")}
    for (_suffix, labels, value) in admits.samples():
        before[dict(labels).get("cls", "?")] = value
    core.submit(_mk_req("classy interactive request", PRIORITY_INTERACTIVE))
    core.submit(_mk_req("classy batch request here!", PRIORITY_BATCH))
    core.run_until_idle()
    tracer.close()
    after = dict(before)
    for (_suffix, labels, value) in admits.samples():
        after[dict(labels).get("cls", "?")] = value
    assert after["interactive"] >= before.get("interactive", 0) + 1
    assert after["batch"] >= before.get("batch", 0) + 1
    # Queue-wait histogram exists per class, and the scrape has the
    # per-class waiting gauge series.
    text = reg.render()
    assert "runbook_sched_queue_wait_seconds_bucket" in text
    assert 'runbook_sched_waiting_requests{cls="interactive"}' in text
    # The admit trace event carries the class (the per-class queue-wait
    # breakdown of `runbook metrics --trace` reads it).
    events = [json.loads(line) for line in
              trace.read_text().splitlines()]
    admits_ev = [e for e in events if e.get("name") == "engine.admit"]
    assert {e["meta"]["cls"] for e in admits_ev} == {"interactive",
                                                    "batch"}
    from runbookai_tpu.utils.timeline import lifecycle_summary

    lifecycle = lifecycle_summary(events)
    by_class = lifecycle["queue_wait_ms_by_class"]
    assert set(by_class) == {"interactive", "batch"}
    assert by_class["interactive"]["count"] == 1


# ---------------------------------------------------------------- tenants


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_rate_limit_bucket_and_retry_after():
    clock = FakeClock()
    g = TenantGovernor({"t-r1": TenantPolicy(rate_limit_rpm=2)},
                       clock=clock)
    assert g.admit("t-r1", 10, 10).allowed
    assert g.admit("t-r1", 10, 10).allowed
    third = g.admit("t-r1", 10, 10)
    assert not third.allowed and third.reason == "rate_limit"
    assert third.retry_after_s == pytest.approx(30.0)  # refill 2/min
    clock.t += 31.0  # one slot refilled
    assert g.admit("t-r1", 10, 10).allowed


def test_token_budget_reserve_and_settle_refund():
    clock = FakeClock()
    g = TenantGovernor(
        {"t-b1": TenantPolicy(token_budget_per_min=100)}, clock=clock)
    a1 = g.admit("t-b1", 50, 40)  # reserves 90
    assert a1.allowed and a1.reserved_tokens == 90
    denied = g.admit("t-b1", 30, 30)  # 60 > 10 left
    assert not denied.allowed and denied.reason == "token_budget"
    assert denied.retry_after_s > 0
    # The completion used only 10 of its 40 reserved new tokens: the
    # refund makes room the un-settled reservation would have blocked.
    g.settle(a1, 60)
    ok = g.admit("t-b1", 30, 10)  # 40 <= 10 + 30 refunded
    assert ok.allowed
    # Settle is idempotent: a second settle must not double-refund.
    g.settle(a1, 0)
    snap = g.snapshot()["tenants"]["t-b1"]
    assert snap["tokens_charged"] == 60
    assert snap["throttled_tokens"] == 1


def test_rate_bucket_refunded_when_token_budget_throttles():
    clock = FakeClock()
    g = TenantGovernor({"t-rb": TenantPolicy(rate_limit_rpm=2,
                                             token_budget_per_min=10)},
                       clock=clock)
    assert not g.admit("t-rb", 100, 100).allowed  # token throttle
    # The rate slot was credited back: two REAL requests still fit.
    assert g.admit("t-rb", 2, 2).allowed
    assert g.admit("t-rb", 2, 2).allowed


def test_unknown_keys_pool_to_bounded_default():
    clock = FakeClock()
    g = TenantGovernor({}, default=TenantPolicy(rate_limit_rpm=1),
                       clock=clock)
    assert g.admit("rando-1", 1, 1).allowed
    denied = g.admit("rando-2", 1, 1)  # SAME bucket as rando-1
    assert not denied.allowed and denied.tenant == DEFAULT_TENANT
    # No per-key state was allocated for the arbitrary strings.
    assert set(g.snapshot()["tenants"]) == {DEFAULT_TENANT}


def test_priority_class_from_policy():
    g = TenantGovernor(
        {"evals": TenantPolicy(priority="batch")}, clock=FakeClock())
    assert g.admit("evals", 1, 1).priority == PRIORITY_BATCH
    assert g.admit("someone", 1, 1).priority == PRIORITY_INTERACTIVE


def test_api_key_separates_secret_from_public_name():
    """Tenant NAMES are exported verbatim (metric labels, /tenants, the
    CLI), so the bearer secret must be separable: with api_key set, the
    secret resolves the tenant, the PUBLIC name does not act as a
    credential, and no surface ever echoes the secret."""
    g = TenantGovernor(
        {"acme-prod": TenantPolicy(rate_limit_rpm=10,
                                   api_key="sk-secret-123")},
        clock=FakeClock())
    assert g.resolve("sk-secret-123") == "acme-prod"
    assert g.resolve("acme-prod") == DEFAULT_TENANT  # name ≠ credential
    snap = json.dumps(g.snapshot())
    assert "sk-secret-123" not in snap
    assert "acme-prod" in snap
    text = metrics_mod.get_registry().render()
    assert "sk-secret-123" not in text


def test_governor_from_config():
    from runbookai_tpu.utils.config import Config

    cfg = Config.model_validate({"llm": {"tenants": {
        "enabled": True,
        "default": {"rate_limit_rpm": 10},
        "keys": {"acme": {"token_budget_per_min": 500,
                          "priority": "batch"}},
    }}})
    g = TenantGovernor.from_config(cfg.llm.tenants)
    assert g is not None
    snap = g.snapshot()["tenants"]
    assert snap["acme"]["priority"] == "batch"
    assert snap[DEFAULT_TENANT]["rate_limit_rpm"] == 10
    assert TenantGovernor.from_config(Config().llm.tenants) is None
    with pytest.raises(Exception):
        Config.model_validate({"llm": {"tenants": {"enabld": True}}})


def test_tenant_metrics_scrape():
    clock = FakeClock()
    reg = metrics_mod.get_registry()
    g = TenantGovernor({"t-m1": TenantPolicy(rate_limit_rpm=1,
                                             token_budget_per_min=50)},
                       clock=clock)
    a = g.admit("t-m1", 5, 5)
    g.settle(a, 8)
    assert not g.admit("t-m1", 1, 1).allowed
    text = reg.render()
    assert ('runbook_tenant_requests_total{tenant="t-m1",'
            'outcome="admitted"}') in text
    assert ('runbook_tenant_requests_total{tenant="t-m1",'
            'outcome="throttled_rate"}') in text
    assert 'runbook_tenant_tokens_total{tenant="t-m1"} 8' in text
    assert 'runbook_tenant_budget_remaining_tokens{tenant="t-m1"}' in text
    assert "runbook_admission_throttled_total" in text


# ------------------------------------------------------------ server e2e


@pytest.fixture(scope="module")
def tenant_server():
    from runbookai_tpu.server.openai_api import OpenAIServer

    client = JaxTpuClient.for_testing(max_new_tokens=8)
    client.tenants = TenantGovernor({
        # Big enough that suite-order noise never throttles by accident;
        # per-test keys isolate the buckets.
        "t-rate": TenantPolicy(rate_limit_rpm=2),
        "t-tok": TenantPolicy(token_budget_per_min=4096),
        "t-batch": TenantPolicy(priority="batch"),
    })
    srv = OpenAIServer(client, model_name="llama3-test", port=0)
    srv.start_background()
    yield srv
    srv.shutdown()


def _post(srv, payload, headers=None, path="/v1/chat/completions"):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    request = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}",
        data=json.dumps(payload).encode(), headers=hdrs, method="POST")
    return urllib.request.urlopen(request, timeout=120)


def _chat_body(text="hello", max_tokens=4):
    return {"messages": [{"role": "user", "content": text}],
            "max_tokens": max_tokens}


def test_server_rate_limit_429_with_retry_after(tenant_server):
    auth = {"Authorization": "Bearer t-rate"}
    engine_before = len(tenant_server.client.core.finished)
    for _ in range(2):
        with _post(tenant_server, _chat_body(), auth) as r:
            assert r.status == 200
    engine_mid = len(tenant_server.client.core.finished)
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(tenant_server, _chat_body(), auth)
    assert e.value.code == 429
    retry = int(e.value.headers["Retry-After"])
    assert retry >= 1
    body = json.loads(e.value.read())
    assert body["error"]["type"] == "rate_limit_error"
    # The throttled request NEVER consumed an engine slot: nothing new
    # entered (or finished in) the engine.
    assert len(tenant_server.client.core.finished) == engine_mid
    assert engine_mid == engine_before + 2


def test_server_token_budget_429(tenant_server):
    auth = {"Authorization": "Bearer t-tok"}
    # 4096-token/min budget; a huge max_tokens reservation never fits.
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(tenant_server, _chat_body(max_tokens=65536), auth)
    assert e.value.code == 429
    assert "token budget" in json.loads(e.value.read())["error"]["message"]
    # A modest request from the same tenant still fits (the failed one
    # charged nothing).
    with _post(tenant_server, _chat_body(max_tokens=4), auth) as r:
        assert r.status == 200
    snap = tenant_server.client.tenants.snapshot()["tenants"]["t-tok"]
    assert snap["throttled_tokens"] == 1
    assert snap["tokens_charged"] > 0  # settled at the true size


def test_server_settle_refunds_unused_reservation(tenant_server):
    gov = tenant_server.client.tenants
    level_before = gov.snapshot()["tenants"]["t-tok"][
        "budget_remaining_tokens"]
    with _post(tenant_server, _chat_body(max_tokens=16),
               {"Authorization": "Bearer t-tok"}) as r:
        out = json.loads(r.read())
    used = (out["usage"]["prompt_tokens"]
            + out["usage"]["completion_tokens"])
    level_after = gov.snapshot()["tenants"]["t-tok"][
        "budget_remaining_tokens"]
    # Charged roughly the true usage (refill adds a little back), never
    # the full reservation.
    assert level_before - level_after <= used + 1


def test_server_x_priority_header_validation(tenant_server):
    core = tenant_server.client.core
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(tenant_server, _chat_body(),
              {"x-priority": "urgentest"})
    assert e.value.code == 400
    # Network clients may only name the CANONICAL classes: an arbitrary
    # int would mint a priority class with an arbitrarily large stride
    # weight (the starve-everyone-else escalation vector).
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(tenant_server, _chat_body(), {"x-priority": "5"})
    assert e.value.code == 400
    with _post(tenant_server, _chat_body(),
               {"x-priority": "batch"}) as r:
        assert r.status == 200
    assert core.finished[-1].priority == PRIORITY_BATCH
    # Untenanted default is interactive...
    with _post(tenant_server, _chat_body()) as r:
        assert r.status == 200
    assert core.finished[-1].priority == PRIORITY_INTERACTIVE
    # ...a batch-class tenant rides batch...
    with _post(tenant_server, _chat_body(),
               {"Authorization": "Bearer t-batch"}) as r:
        assert r.status == 200
    assert core.finished[-1].priority == PRIORITY_BATCH
    # ...and the header can never PROMOTE past the tenant's class.
    with _post(tenant_server, _chat_body(),
               {"Authorization": "Bearer t-batch",
                "x-priority": "interactive"}) as r:
        assert r.status == 200
    assert core.finished[-1].priority == PRIORITY_BATCH


def test_server_tenants_route(tenant_server):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{tenant_server.port}/tenants",
            timeout=30) as r:
        snap = json.loads(r.read())
    assert snap["enabled"] is True
    assert "t-rate" in snap["tenants"]
    assert snap["tenants"]["t-rate"]["admitted"] >= 2


def test_server_shed_503_carries_retry_after(tenant_server):
    engine = tenant_server.client.engine
    engine.is_saturated = lambda: True
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(tenant_server, dict(_chat_body(), stream=True))
        assert e.value.code == 503
        assert int(e.value.headers["Retry-After"]) >= 1
    finally:
        del engine.is_saturated


def test_tenants_cli_renders_live_snapshot(tenant_server, capsys):
    from runbookai_tpu.cli.main import build_parser

    args = build_parser().parse_args(
        ["tenants", "--url", f"http://127.0.0.1:{tenant_server.port}"])
    assert args.fn(args) == 0
    out = capsys.readouterr().out
    assert "t-rate" in out and "tenant" in out
    args = build_parser().parse_args(
        ["tenants", "--json", "--url",
         f"http://127.0.0.1:{tenant_server.port}"])
    assert args.fn(args) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["enabled"] is True


def test_server_without_governor_unchanged():
    """No llm.tenants = zero tenant surface: /tenants reports disabled
    and requests flow exactly as before (no 429 path)."""
    from runbookai_tpu.server.openai_api import OpenAIServer

    client = JaxTpuClient.for_testing(max_new_tokens=8)
    assert client.tenants is None
    srv = OpenAIServer(client, model_name="llama3-test", port=0)
    srv.start_background()
    try:
        with _post(srv, _chat_body()) as r:
            assert r.status == 200
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/tenants", timeout=30) as r:
            assert json.loads(r.read()) == {"enabled": False,
                                            "tenants": {}}
    finally:
        srv.shutdown()


# ------------------------------------------------------------- feedback


def _tpot_monitor(target_ms=10.0):
    from runbookai_tpu.utils.slo import SLOMonitor

    return SLOMonitor({"tpot_p95_ms": target_ms})


def _tpot_hist():
    reg = metrics_mod.get_registry()
    return reg.histogram("runbook_tpot_seconds",
                         "Per-token decode latency (e2e minus TTFT over "
                         "generated-1)", buckets=metrics_mod.TPOT_BUCKETS)


def test_feedback_shrinks_grows_and_clamps():
    from runbookai_tpu.sched import MixedBudgetController

    hist = _tpot_hist()
    hist.reset()
    ctl = MixedBudgetController(_tpot_monitor(target_ms=10.0),
                                interval_steps=1)
    core = types.SimpleNamespace(_mix_pf_tokens=64)
    # Empty histogram: no signal, no movement.
    ctl.on_step(core)
    assert core._mix_pf_tokens == 64
    # Over-SLO fixture: every decision window sees fresh observations at
    # 10x the target (the burn is WINDOWED — stale history never votes).
    for _ in range(5):
        for _ in range(16):
            hist.observe(0.1)
        ctl.on_step(core)
    # Ladder: 64 -> 48 -> 32 -> 16, hard-clamped at min_fraction=0.25.
    assert core._mix_pf_tokens == 16
    assert ctl.state()["levels"] == [64, 48, 32, 16]
    # A window with no new observations makes no decision.
    level = ctl.state()["level"]
    ctl.on_step(core)
    assert ctl.state()["level"] == level
    # Recovery: fast windows grow the share back, clamped at the base —
    # WITHOUT resetting the histogram (the lifetime p95 is still 10x
    # over target; only the windowed view can see the recovery).
    for _ in range(6):
        for _ in range(16):
            hist.observe(0.001)
        ctl.on_step(core)
    assert core._mix_pf_tokens == 64
    reg = metrics_mod.get_registry()
    text = reg.render()
    assert ('runbook_sched_feedback_adjustments_total'
            '{direction="shrink"}') in text
    assert 'runbook_sched_mixed_prefill_tokens{replica="0"} 64' in text
    # A histogram reset under the controller (reset_metrics) resyncs the
    # window mark instead of serving a garbage negative window.
    hist.reset()
    assert ctl.burn() is None
    hist.reset()


def test_feedback_hysteresis_band_holds():
    from runbookai_tpu.sched import MixedBudgetController

    hist = _tpot_hist()
    hist.reset()
    ctl = MixedBudgetController(_tpot_monitor(target_ms=10.0),
                                interval_steps=1, shrink_at=1.0,
                                grow_at=0.5)
    core = types.SimpleNamespace(_mix_pf_tokens=64)
    ctl.on_step(core)
    # Burn ~0.75 every window: inside the band — no movement either way.
    level0 = ctl.state()["level"]
    for _ in range(5):
        for _ in range(16):
            hist.observe(0.0075)
        ctl.on_step(core)
    assert ctl.state()["level"] == level0
    hist.reset()


def test_feedback_requires_tpot_objective():
    from runbookai_tpu.sched import MixedBudgetController
    from runbookai_tpu.utils.slo import SLOMonitor

    with pytest.raises(ValueError):
        MixedBudgetController(SLOMonitor({"ttft_p95_ms": 100.0}))
    sched_cfg = types.SimpleNamespace(feedback=True)
    with pytest.raises(ValueError):
        MixedBudgetController.for_core(sched_cfg, None)
    off = types.SimpleNamespace(feedback=False)
    assert MixedBudgetController.for_core(off, None) is None
    from runbookai_tpu.utils.config import Config

    cfg = Config.model_validate({"llm": {"sched": {"feedback": True}}})
    from runbookai_tpu.utils.config import validate_config

    assert any("tpot_p95_ms" in p for p in validate_config(cfg))
    # An inverted hysteresis band fails pre-flight validation, not at
    # engine build (the sibling check the controller enforces too).
    bad = Config.model_validate({"llm": {"sched": {
        "feedback": True, "feedback_grow_at": 1.2,
        "feedback_shrink_at": 1.0}, "slo": {"tpot_p95_ms": 40.0}}})
    assert any("hysteresis" in p for p in validate_config(bad))


def test_feedback_moves_budget_but_streams_stay_byte_identical(tiny_client):
    """The controller's actuator changes mixed-step CHUNKING, never
    tokens: an over-SLO run with feedback on yields the same streams as
    feedback off."""
    from runbookai_tpu.sched import MixedBudgetController

    hist = _tpot_hist()
    prompts = [f"feedback parity prompt {i:02d} tail tail tail" * 2
               for i in range(4)]

    def run(with_feedback):
        core = make_core(tiny_client, max_batch_slots=2,
                         mixed_dispatch=True, prefill_chunk=16)
        if with_feedback:
            hist.reset()
            for _ in range(32):
                hist.observe(0.1)  # burn >> 1 from step one
            core.feedback = MixedBudgetController(
                _tpot_monitor(target_ms=1.0), interval_steps=2)
        reqs = [_mk_req(p, PRIORITY_INTERACTIVE, max_new=8)
                for p in prompts]
        for r in reqs:
            core.submit(r)
        core.run_until_idle()
        return core, [r.all_out_ids for r in reqs]

    core_on, streams_on = run(True)
    moved = core_on.feedback.state()["level"]
    core_off, streams_off = run(False)
    assert streams_on == streams_off
    # And the fixture really drove the actuator (direction: shrink).
    assert moved > 0
    assert core_on._mix_pf_tokens < core_off._mix_pf_tokens
    hist.reset()


def test_from_config_wires_sched_tenants_feedback(monkeypatch):
    """from_config: llm.sched lands on EngineConfig, llm.tenants builds
    the governor, llm.sched.feedback attaches a controller per core."""
    from runbookai_tpu.utils.config import Config

    cfg = Config.model_validate({"llm": {
        "provider": "jax-tpu", "model": "llama3-test",
        "max_seq_len": 256, "max_new_tokens": 16,
        "page_size": 4, "num_pages": 128, "max_batch_slots": 2,
        "prefill_chunk": 16,
        "sched": {"policy": "wdrr", "interactive_weight": 4.0,
                  "feedback": True},
        "slo": {"tpot_p95_ms": 40.0},
        "tenants": {"enabled": True,
                    "keys": {"acme": {"rate_limit_rpm": 5}}},
    }})
    client = JaxTpuClient.from_config(cfg.llm)
    assert client.core.ecfg.sched_policy == "wdrr"
    assert client.core.ecfg.sched_weights[PRIORITY_INTERACTIVE] == 4.0
    assert client.core._sched is not None
    assert client.core.feedback is not None
    assert client.tenants is not None
    assert client.tenants.resolve("acme") == "acme"
    # Policy "priority" + feedback off + tenants off = classic engine.
    cfg2 = Config.model_validate({"llm": {
        "provider": "jax-tpu", "model": "llama3-test",
        "max_seq_len": 256, "page_size": 4, "num_pages": 128,
        "max_batch_slots": 2, "prefill_chunk": 16,
        "sched": {"policy": "priority"},
    }})
    client2 = JaxTpuClient.from_config(cfg2.llm)
    assert client2.core._sched is None
    assert client2.core.feedback is None
    assert client2.tenants is None


# ------------------------------------------------- router queue depth


def test_router_breaks_load_ties_on_queue_depth():
    from runbookai_tpu.engine.fleet import AsyncFleet, FleetConfig

    client = JaxTpuClient.for_testing(max_new_tokens=8, dp_replicas=2)
    fleet = AsyncFleet(client.cores, FleetConfig(affinity=False))
    # Same live load (2 each), different shape: replica 0 carries queued
    # requests, replica 1 carries decoders. The router must prefer the
    # decode-heavy replica (its backlog starts this request sooner).
    core0, core1 = client.cores
    core0.waiting.extend(_mk_req(f"queued {i}", 0) for i in range(2))
    core1.decoding.extend(_mk_req(f"decoding {i}", 0) for i in range(2))
    try:
        for _ in range(3):  # round-robin must not override the depth pick
            placement = fleet._route(list(b"totally novel prompt bytes"))
            assert placement.idx == 1
    finally:
        core0.waiting.clear()
        core1.decoding.clear()
    # The depth each candidate showed is exported as a labeled gauge.
    text = metrics_mod.get_registry().render()
    assert ('runbook_router_observed_queue_depth'
            '{model="llama3-test",replica="0"} 2') in text
    assert ('runbook_router_observed_queue_depth'
            '{model="llama3-test",replica="1"} 0') in text
