"""PR 7 deep-introspection layer: engine flight recorder ring semantics,
SLO burn math over synthetic histogram fills, request-timeline stitching
(including a live dp=2 fleet trace), the /debug/steps scrape shape and
trace JSONL rotation."""

import json
import threading
import urllib.error
import urllib.request

import pytest

from runbookai_tpu.engine.flight_recorder import (
    STEP_RECORD_FIELDS,
    FlightRecorder,
)
from runbookai_tpu.utils import metrics as metrics_mod
from runbookai_tpu.utils.slo import OBJECTIVE_HISTOGRAMS, SLOMonitor, parse_objective
from runbookai_tpu.utils.timeline import (
    build_timeline,
    lifecycle_summary,
    render_timeline,
)

# --------------------------------------------------------------------------- #
# flight recorder: ring bounds + append semantics                             #
# --------------------------------------------------------------------------- #


def rec(i, kind="decode", **kw):
    base = {"ts": float(i), "kind": kind, "classes": {}, "tokens": 2,
            "batch": 1, "occupancy": 0.25, "queue_depth": 0,
            "kv_free_pages": 10, "kv_utilization": 0.1,
            "dispatch_s": 0.001, "host_s": 0.0005, "overlap_s": 0.0,
            "wall_s": 0.002, "preemptions": 0, "kv_imported": 0,
            "kv_exported": 0, "t_start": float(i), "t_end": i + 0.002,
            "phases": {"admit": 0.0, "build": 0.0005, "issue": 0.001,
                       "fetch": 0.0, "emit": 0.0, "draft": 0.0,
                       "other": 0.0005},
            "program": ["_decode_multi"], "k": 8, "rows": 1,
            "kv_pages_live": 3,
            "prefill_tokens": 0, "decode_tokens": 2, "compile_s": 0.0,
            "admitted": [], "finished": [], "dispatches": [],
            "sampler": {"calls": 8, "sorted": 0}}
    base.update(kw)
    return base


def test_ring_bounds_overwrite_oldest():
    fr = FlightRecorder(4)
    for i in range(11):
        fr.append(rec(i))
    assert len(fr) == 4 and fr.capacity == 4
    assert fr.total_steps == 11
    snap = fr.snapshot()
    # Oldest→newest, only the last `capacity` survive, step stamped by
    # the recorder itself (monotonic across overwrites).
    assert [r["step"] for r in snap] == [7, 8, 9, 10]
    assert [r["ts"] for r in snap] == [7.0, 8.0, 9.0, 10.0]


def test_ring_snapshot_last_n_and_copies():
    fr = FlightRecorder(8)
    for i in range(5):
        fr.append(rec(i))
    snap = fr.snapshot(2)
    assert [r["step"] for r in snap] == [3, 4]
    # Snapshot returns copies: mutating them must not corrupt the ring.
    snap[0]["kind"] = "mutated"
    assert fr.snapshot(2)[0]["kind"] == "decode"
    assert fr.snapshot(0) == []


def test_ring_zero_capacity_disables():
    fr = FlightRecorder(0)
    assert not fr.enabled
    fr.append(rec(0))  # no-op, no raise
    assert len(fr) == 0 and fr.snapshot() == [] and fr.total_steps == 0
    assert fr.dispatches is None  # no dispatch is numbered or stamped


def test_ring_reset_restarts_cursor():
    fr = FlightRecorder(4)
    for i in range(6):
        fr.append(rec(i))
    fr.reset()
    assert len(fr) == 0 and fr.total_steps == 0
    fr.append(rec(99))
    assert fr.snapshot()[0]["step"] == 0  # measured window restarts at 0


def test_ring_concurrent_append_and_snapshot():
    """The writer never locks; a concurrent reader may tear by a record
    but must never crash or see a partially-written dict."""
    fr = FlightRecorder(16)
    stop = threading.Event()
    errors: list[BaseException] = []

    def reader():
        try:
            while not stop.is_set():
                for r in fr.snapshot():
                    assert r["kind"] in ("decode", "prefill")
                    assert "occupancy" in r
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    t = threading.Thread(target=reader)
    t.start()
    for i in range(3000):
        fr.append(rec(i, kind="prefill" if i % 3 else "decode"))
    stop.set()
    t.join(timeout=30)
    assert not errors, errors
    assert len(fr) == 16 and fr.total_steps == 3000


def test_the_ring_keeps_what_a_window_is_rolled_up_from():
    """Kinds, tokens, occupancy and the pressure peaks of a window are
    read off the records themselves (``snapshot()``): the ring keeps each
    as it was appended, in order."""
    fr = FlightRecorder(64)
    for i in range(10):
        fr.append(rec(i, kind="mixed" if i < 3 else "decode",
                      occupancy=(i + 1) / 10.0, kv_utilization=0.05 * i,
                      queue_depth=i, tokens=3))
    snap = fr.snapshot()
    assert [r["kind"] for r in snap].count("mixed") == 3
    assert [r["kind"] for r in snap].count("decode") == 7
    assert sum(r["tokens"] for r in snap) == 30
    assert [r["occupancy"] for r in snap] == [(i + 1) / 10.0 for i in range(10)]
    assert max(r["kv_utilization"] for r in snap) == pytest.approx(0.45)
    assert max(r["queue_depth"] for r in snap) == 9
    assert len(snap) == 10 and fr.capacity == 64 and fr.total_steps == 10


def test_dump_jsonl_round_trips(tmp_path):
    fr = FlightRecorder(8)
    for i in range(3):
        fr.append(rec(i))
    out = tmp_path / "flight" / "steps.jsonl"
    assert fr.dump_jsonl(out) == 3
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert [r["step"] for r in lines] == [0, 1, 2]
    assert set(STEP_RECORD_FIELDS) - {"replica"} <= set(lines[0])


# --------------------------------------------------------------------------- #
# SLO monitor: burn math over synthetic fills                                 #
# --------------------------------------------------------------------------- #


def test_parse_objective_spellings():
    assert parse_objective("ttft_p95_ms") == ("runbook_ttft_seconds", 95.0)
    assert parse_objective("tpot_p99_ms") == ("runbook_tpot_seconds", 99.0)
    assert parse_objective("e2e_p95_ms") == ("runbook_e2e_seconds", 95.0)
    for bad in ("ttft_p9_ms", "ttft_p95", "p95_ms", "latency_p95_ms", ""):
        with pytest.raises(ValueError):
            parse_objective(bad)


def _reg_with_hist(name, buckets, values=()):
    reg = metrics_mod.MetricsRegistry()
    h = reg.histogram(name, "synthetic", buckets=buckets)
    for v in values:
        h.observe(v)
    return reg, h


def test_burn_math_against_synthetic_fill():
    # 100 observations at 0.4s against a 50ms target: p95 interpolates
    # inside the (0.1, 0.5] bucket and the burn ratio is current/target.
    reg, h = _reg_with_hist("runbook_ttft_seconds", (0.01, 0.1, 0.5, 1.0),
                            values=[0.4] * 100)
    mon = SLOMonitor({"ttft_p95_ms": 50.0}, registry=reg)
    out = mon.evaluate()["ttft_p95_ms"]
    assert out["target_ms"] == 50.0
    assert out["current_ms"] == pytest.approx(480.0)  # 0.1 + 0.95*0.4 s
    assert out["burn_ratio"] == pytest.approx(9.6)
    assert out["breached"] is True
    # The violation counter books one increment per breached evaluation.
    text = reg.render()
    assert 'runbook_slo_burn_ratio{objective="ttft_p95_ms"}' in text
    assert ('runbook_slo_violations_total{objective="ttft_p95_ms"} 2'
            in text)  # evaluate() above + the render's own burn callback


def test_burn_under_target_is_not_breached():
    reg, h = _reg_with_hist("runbook_tpot_seconds", (0.01, 0.02, 0.05),
                            values=[0.015] * 50)
    mon = SLOMonitor({"tpot_p95_ms": 100.0}, registry=reg)
    out = mon.evaluate()["tpot_p95_ms"]
    assert out["breached"] is False and out["burn_ratio"] < 1.0
    assert "runbook_slo_violations_total" in reg.render()
    assert ('runbook_slo_violations_total{objective="tpot_p95_ms"} 0'
            in reg.render())


def test_empty_histogram_scrapes_as_absence_not_zero():
    reg, h = _reg_with_hist("runbook_e2e_seconds", (0.1, 1.0))
    mon = SLOMonitor({"e2e_p99_ms": 1000.0}, registry=reg)
    out = mon.evaluate()["e2e_p99_ms"]
    assert out["current_ms"] is None and out["burn_ratio"] is None
    assert out["breached"] is False
    text = reg.render()
    # Target is always present; current/burn must be ABSENT (a burn of 0
    # would read as a comfortably-met SLO).
    assert 'runbook_slo_target_ms{objective="e2e_p99_ms"} 1000' in text
    assert 'runbook_slo_current_ms{objective="e2e_p99_ms"}' not in text
    assert 'runbook_slo_burn_ratio{objective="e2e_p99_ms"}' not in text
    h.observe(2.0)
    assert 'runbook_slo_burn_ratio{objective="e2e_p99_ms"}' in reg.render()


def test_unconfigured_monitor_exports_no_series():
    reg = metrics_mod.MetricsRegistry()
    reg.histogram("runbook_ttft_seconds", "x", buckets=(0.1, 1.0))
    SLOMonitor({}, registry=reg)
    SLOMonitor({"ttft_p95_ms": None}, registry=reg)
    assert "runbook_slo" not in reg.render()
    assert SLOMonitor.from_config(None) is None


def test_slo_config_block_targets():
    from runbookai_tpu.utils.config import LLMConfig, SLOConfig

    cfg = SLOConfig(ttft_p95_ms=500, tpot_p99_ms=40)
    assert cfg.targets() == {"ttft_p95_ms": 500.0, "tpot_p99_ms": 40.0}
    assert SLOConfig().targets() == {}
    # The default llm block carries an empty SLO config (no series).
    assert LLMConfig().slo.targets() == {}
    reg = metrics_mod.MetricsRegistry()
    assert SLOMonitor.from_config(SLOConfig(), registry=reg) is None
    mon = SLOMonitor.from_config(SLOConfig(ttft_p95_ms=250), registry=reg)
    assert set(mon.objectives) == {"ttft_p95_ms"}
    with pytest.raises(ValueError):
        SLOMonitor({"ttft_p95_ms": -5.0})
    with pytest.raises(ValueError):
        SLOMonitor({"nope_p95_ms": 5.0})


def test_objective_histograms_match_engine_names():
    # The monitor watches the PR 1 histograms the engine actually
    # observes — a rename on either side must fail loudly here.
    import runbookai_tpu.engine.engine as engine_mod
    import inspect

    src = inspect.getsource(engine_mod)
    for hist_name in OBJECTIVE_HISTOGRAMS.values():
        assert f'"{hist_name}"' in src, hist_name


# --------------------------------------------------------------------------- #
# timeline stitching: synthetic dp=2 fixture with a cross-replica retry       #
# --------------------------------------------------------------------------- #


def _dp2_fixture_spans():
    """A fleeted request 'req-x': placed on replica 0, aborted under pool
    pressure, retried onto replica 1 where it finishes — plus an
    unrelated request that must never leak into the timeline."""
    return [
        {"ts": 10.0, "name": "router.place", "ms": 0.0,
         "meta": {"replica": 0, "affinity": False, "trace_id": "req-x"}},
        {"ts": 10.001, "name": "engine.enqueue", "ms": 0.0,
         "meta": {"request": "r0-aaa", "prompt_tokens": 12, "replica": 0,
                  "trace_id": "req-x"}},
        {"ts": 10.002, "name": "engine.admit", "ms": 0.0,
         "meta": {"request": "r0-aaa", "cached_tokens": 0, "queue_ms": 1.0,
                  "replica": 0, "trace_id": "req-x"}},
        {"ts": 10.102, "name": "engine.prefill", "ms": 100.0,
         "meta": {"batch": 1, "tokens": 12, "requests": ["r0-aaa"]}},
        {"ts": 10.2, "name": "engine.request", "ms": 0.0,
         "meta": {"request": "r0-aaa", "reason": "aborted", "generated": 0,
                  "replica": 0, "trace_id": "req-x"}},
        # retry lands on replica 1
        {"ts": 10.21, "name": "router.place", "ms": 0.0,
         "meta": {"replica": 1, "affinity": True, "trace_id": "req-x"}},
        {"ts": 10.211, "name": "engine.enqueue", "ms": 0.0,
         "meta": {"request": "r1-bbb", "prompt_tokens": 12, "replica": 1,
                  "trace_id": "req-x"}},
        {"ts": 10.212, "name": "engine.admit", "ms": 0.0,
         "meta": {"request": "r1-bbb", "cached_tokens": 8, "queue_ms": 0.5,
                  "replica": 1, "trace_id": "req-x"}},
        {"ts": 10.312, "name": "engine.prefill", "ms": 100.0,
         "meta": {"batch": 1, "tokens": 4, "requests": ["r1-bbb"]}},
        {"ts": 10.512, "name": "engine.decode", "ms": 200.0,
         "meta": {"k": 8, "batch": 2, "requests": ["r1-bbb", "r1-other"]}},
        {"ts": 10.6, "name": "engine.request", "ms": 0.0,
         "meta": {"request": "r1-bbb", "reason": "max_tokens",
                  "generated": 8, "ttft_ms": 150.0, "replica": 1,
                  "trace_id": "req-x"}},
        # noise: a different request on replica 1
        {"ts": 10.4, "name": "engine.enqueue", "ms": 0.0,
         "meta": {"request": "r1-other", "prompt_tokens": 3, "replica": 1,
                  "trace_id": "req-y"}},
        {"ts": 10.7, "name": "engine.request", "ms": 0.0,
         "meta": {"request": "r1-other", "reason": "stop_token",
                  "generated": 2, "replica": 1, "trace_id": "req-y"}},
    ]


def test_dp2_stitch_follows_retry_across_replicas():
    tl = build_timeline(_dp2_fixture_spans(), "req-x")
    assert tl is not None
    assert tl["engine_requests"] == ["r0-aaa", "r1-bbb"]
    assert tl["replicas"] == [0, 1]
    names = [e["name"] for e in tl["events"]]
    # Ordered by START time (span ts is written at close).
    assert names == [
        "router.place", "engine.enqueue", "engine.admit", "engine.prefill",
        "engine.request", "router.place", "engine.enqueue", "engine.admit",
        "engine.prefill", "engine.decode", "engine.request"]
    # The shared decode window is attributed via meta.requests; r1-other's
    # own lifecycle events stay out.
    assert not any(e.get("request") == "r1-other" for e in tl["events"])
    assert tl["finish"] == {"reason": "max_tokens", "generated": 8,
                            "ttft_ms": 150.0}
    assert tl["events"][0]["rel_ms"] == 0.0
    # total spans first start (router.place @10.0) to the last event (the
    # finish engine.request @10.6).
    assert tl["total_ms"] == pytest.approx(600.0, abs=1.0)


def test_stitch_by_engine_internal_id_and_missing_id():
    spans = _dp2_fixture_spans()
    tl = build_timeline(spans, "r1-bbb")  # engine id works directly
    assert tl is not None
    assert any(e["name"] == "engine.decode" for e in tl["events"])
    assert build_timeline(spans, "req-does-not-exist") is None
    assert build_timeline([], "req-x") is None


def test_render_tree_and_eliding():
    tl = build_timeline(_dp2_fixture_spans(), "req-x")
    text = render_timeline(tl)
    assert "request req-x" in text
    assert "router.place → replica 0" in text
    assert "(affinity hit)" in text  # the retry placement
    assert "finish: max_tokens" in text
    assert "queue_ms=1.0" in text
    # Long runs collapse their middle dispatch windows.
    many = dict(tl)
    mid = {"name": "engine.decode", "rel_ms": 1.0, "ms": 2.0,
           "label": "decode window"}
    many["events"] = tl["events"][:2] + [dict(mid) for _ in range(100)] \
        + tl["events"][-2:]
    collapsed = render_timeline(many, max_events=10)
    assert "more dispatch windows" in collapsed
    assert len(collapsed.splitlines()) < 20


def test_lifecycle_summary_queue_and_router():
    out = lifecycle_summary(_dp2_fixture_spans())
    assert out["admissions"] == 2
    q = out["queue_wait_ms"]
    assert q["count"] == 2 and q["max"] == 1.0
    assert q["p50"] == pytest.approx(0.75)
    r = out["router"]
    assert r["placements"] == {"0": 1, "1": 1}
    assert r["affinity_hits"] == 1
    assert r["affinity_hit_ratio"] == pytest.approx(0.5)
    assert r["sheds"] == 0
    # No router events at all (single engine): the block is absent.
    single = [s for s in _dp2_fixture_spans()
              if not s["name"].startswith("router.")]
    assert "router" not in lifecycle_summary(single)


# --------------------------------------------------------------------------- #
# trace JSONL rotation                                                        #
# --------------------------------------------------------------------------- #


def test_trace_rotates_at_byte_cap(tmp_path):
    from runbookai_tpu.utils.trace import Tracer

    path = tmp_path / "trace.jsonl"
    t = Tracer(path, max_bytes=400)
    before = metrics_mod.get_registry().counter(
        "runbook_trace_rotations_total",
        "Trace JSONL rotations at the byte cap").value
    for i in range(40):
        t.event("soak", n=i, pad="x" * 30)
    t.close()
    rotated = tmp_path / "trace.jsonl.1"
    assert rotated.exists(), "no rotation at the byte cap"
    # Bounded on disk: live + one rotated generation, each under the cap.
    assert path.stat().st_size <= 400
    assert rotated.stat().st_size <= 400
    assert t._rotations > 0
    after = metrics_mod.get_registry().counter(
        "runbook_trace_rotations_total",
        "Trace JSONL rotations at the byte cap").value
    assert after - before == t._rotations
    # Every surviving line is whole JSON (the swap never tears a record).
    for f in (path, rotated):
        for line in f.read_text().splitlines():
            json.loads(line)


def test_trace_unbounded_when_cap_disabled(tmp_path):
    from runbookai_tpu.utils.trace import Tracer

    path = tmp_path / "t.jsonl"
    t = Tracer(path, max_bytes=None)
    for i in range(50):
        t.event("e", pad="y" * 100)
    t.close()
    assert not (tmp_path / "t.jsonl.1").exists()
    assert len(path.read_text().splitlines()) == 50


# --------------------------------------------------------------------------- #
# live engine: per-step records                                               #
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def live_core():
    import jax
    import jax.numpy as jnp

    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["llama3-test"]
    tok = ByteTokenizer()
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return EngineCore(cfg, params, tok, EngineConfig(
        page_size=4, num_pages=64, max_batch_slots=4, prefill_chunk=8,
        max_seq_len=128, block_pages=4, kv_dtype=jnp.float32,
        flight_recorder_steps=32))


def test_live_engine_appends_one_record_per_step(live_core):
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams

    live_core.flight.reset()
    for text in (b"hello flight", b"recorder test"):
        live_core.submit(EngineRequest(
            prompt_ids=list(text),
            sampling=SamplingParams(temperature=0.0, max_new_tokens=6,
                                    stop_token_ids=())))
    steps = 0
    while live_core.has_work:
        live_core.step()
        steps += 1
    assert live_core.flight.total_steps == steps
    snap = live_core.flight.snapshot()
    assert [r["step"] for r in snap] == list(range(steps))
    kinds = {r["kind"] for r in snap}
    assert kinds <= {"prefill", "decode", "prefill+decode", "mixed", "idle"}
    assert kinds & {"prefill", "prefill+decode", "mixed"}  # prompts ran
    for r in snap:
        assert set(STEP_RECORD_FIELDS) - {"replica"} <= set(r)
        assert 0.0 <= r["occupancy"] <= 1.0
        assert r["kv_free_pages"] >= 0 and 0.0 <= r["kv_utilization"] <= 1.0
        assert r["wall_s"] >= 0.0
    # Tokens booked across the run cover every generated token (decode
    # tokens book at window drain — totals match once idle).
    assert sum(r["tokens"] for r in snap) >= 12
    assert len(snap) == steps == live_core.flight.total_steps


def test_flight_recorder_can_be_disabled(live_core):
    import dataclasses

    from runbookai_tpu.engine.engine import EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams

    core = EngineCore(live_core.cfg, live_core.params, live_core.tokenizer,
                      dataclasses.replace(live_core.ecfg,
                                          flight_recorder_steps=0))
    core.submit(EngineRequest(
        prompt_ids=list(b"off"),
        sampling=SamplingParams(temperature=0.0, max_new_tokens=3,
                                stop_token_ids=())))
    core.run_until_idle()
    assert not core.flight.enabled
    assert core.flight.snapshot() == [] and core.flight.total_steps == 0


# --------------------------------------------------------------------------- #
# /debug/steps scrape shape (live server)                                     #
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def server():
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.server.openai_api import OpenAIServer

    client = JaxTpuClient.for_testing(max_new_tokens=6)
    srv = OpenAIServer(client, model_name="llama3-test", port=0)
    srv.start_background()
    yield srv
    srv.shutdown()


def _get_json(srv, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=60) as r:
        return json.loads(r.read())


def test_debug_steps_scrape_shape(server):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/v1/chat/completions",
        data=json.dumps({"messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    urllib.request.urlopen(req, timeout=120).read()

    body = _get_json(server, "/debug/steps")
    assert set(body) == {"capacity", "steps_total", "steps"}
    assert body["capacity"] > 0 and body["steps_total"] > 0
    assert body["steps"], "no step records after a served request"
    for r in body["steps"]:
        assert r["kind"] in ("prefill", "decode", "prefill+decode",
                             "mixed", "idle")
        assert "occupancy" in r and "kv_utilization" in r
        assert "kv_free_pages" in r and "queue_depth" in r
    # ?n=N bounds the scrape.
    total = len(body["steps"])
    bounded = _get_json(server, "/debug/steps?n=2")
    assert len(bounded["steps"]) == min(2, total)
    assert bounded["steps"][-1]["step"] == body["steps"][-1]["step"]
    # Malformed n is a 400, not a crash.
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get_json(server, "/debug/steps?n=bogus")
    assert exc.value.code == 400
    # /metrics still scrapes the route label.
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=60) as r:
        text = r.read().decode()
    assert 'route="/debug/steps"' in text


def test_healthz_slo_block_when_configured(server):
    from runbookai_tpu.utils.config import SLOConfig

    target = SLOMonitor.from_config(SLOConfig(ttft_p95_ms=0.001))
    srv_client = server.client  # the client behind the handler closure
    try:
        srv_client.slo_monitor = target
        health = _get_json(server, "/healthz")
        assert "slo" in health
        blk = health["slo"]["ttft_p95_ms"]
        assert blk["target_ms"] == 0.001
        # The module's earlier chat request filled the global TTFT
        # histogram, so a 1µs target is breached with burn >> 1.
        assert blk["burn_ratio"] is None or blk["burn_ratio"] > 1.0
    finally:
        srv_client.slo_monitor = None
    health = _get_json(server, "/healthz")
    assert "slo" not in health  # unconfigured: no SLO surface


# --------------------------------------------------------------------------- #
# dp=2 fleet: live trace -> timeline CLI + /debug/steps aggregation           #
# --------------------------------------------------------------------------- #


async def test_dp2_fleet_trace_timeline_and_debug_steps(tmp_path, capsys):
    from runbookai_tpu.cli.main import main
    from runbookai_tpu.engine.request import SamplingParams
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.utils import trace as trace_mod
    from runbookai_tpu.utils.trace import read_spans

    trace_path = tmp_path / "fleet-trace.jsonl"
    old = trace_mod.get_tracer()
    tracer = trace_mod.Tracer(trace_path)
    trace_mod.set_tracer(tracer)
    try:
        client = JaxTpuClient.for_testing(max_new_tokens=8, dp_replicas=2)
        fleet = client.engine
        sp = SamplingParams(temperature=0.0, max_new_tokens=8,
                            stop_token_ids=())
        out_a = await fleet.generate(list(b"the quick brown fox jumps"),
                                     sp, request_id="req-tl-a")
        out_b = await fleet.generate(list(b"zebra stripes pattern xyz"),
                                     sp, request_id="req-tl-b")
        assert out_a.token_ids and out_b.token_ids
        # Fleet-wide /debug/steps: replica-stamped records, one ts-ordered
        # merge, shared shape with the single-engine scrape + dp count.
        agg = fleet.debug_steps()
        assert agg["dp_replicas"] == 2
        assert agg["steps_total"] > 0 and agg["steps"]
        assert {r["replica"] for r in agg["steps"]} \
            <= {0, 1}
        ts = [r["ts"] for r in agg["steps"]]
        assert ts == sorted(ts)
        bounded = fleet.debug_steps(last_n=3)
        assert len(bounded["steps"]) <= 3
        await fleet.stop()
    finally:
        tracer.close()
        trace_mod.set_tracer(old)

    spans = read_spans(trace_path)
    for rid in ("req-tl-a", "req-tl-b"):
        tl = build_timeline(spans, rid)
        assert tl is not None, rid
        assert tl["engine_requests"], rid  # the engine id was stitched in
        assert tl["finish"] is not None and tl["finish"]["generated"] == 8
        names = [e["name"] for e in tl["events"]]
        assert names[0] == "router.place"
        assert "engine.enqueue" in names and "engine.admit" in names
        assert any(n in ("engine.prefill", "engine.mixed") for n in names)
        assert names[-1] == "engine.request"
    # Both requests were placed (router events carry the trace ids).
    placed = [s for s in spans if s["name"] == "router.place"]
    assert {s["meta"]["trace_id"] for s in placed} \
        == {"req-tl-a", "req-tl-b"}

    # CLI: ASCII tree and --json both render from the same file.
    assert main(["timeline", "req-tl-a", "--trace", str(trace_path)]) == 0
    tree = capsys.readouterr().out
    assert "request req-tl-a" in tree and "router.place" in tree
    assert "finish:" in tree
    assert main(["timeline", "req-tl-a", "--trace", str(trace_path),
                 "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["request_id"] == "req-tl-a"
    # Unknown id: exit 1 with a message, not a traceback.
    assert main(["timeline", "req-nope", "--trace", str(trace_path)]) == 1

    # `runbook metrics --trace` reports the queue-wait/router block
    # alongside the dispatch counters (previously dropped: events are
    # ms=0 so the duration table never showed them).
    assert main(["metrics", "--trace", str(trace_path)]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert "dispatch_counters" in summary
    life = summary["request_lifecycle"]
    assert life["admissions"] >= 2
    assert life["queue_wait_ms"]["count"] >= 2
    assert set(life["router"]["placements"]) <= {"0", "1"}
    assert sum(life["router"]["placements"].values()) == 2
