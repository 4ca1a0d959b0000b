"""The six per-layer metrics that read the program's own spans: the five
over the step records (``/debug/steps``) on a hand-made run with each
value worked out by hand, on a run of a program that has no such fields
(None, not an error), with a record lost between two polls; the join of
the profiler's ``engine.step`` spans to the records, on a CPU trace made
here and on the trace recorded on the chip; the front door taken apart
(``server.parse`` / ``server.write`` joined to lifecycle records) by hand
and on a live tiny server; one CPU rehearsal end to end.

No TPU topology is described here, at import or later.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TESTDATA = ROOT / "benchmark" / "testdata"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
STEP_RECORD_METRICS = ("engine_queue_wait_p90_ms", "engine_ttft_p90_ms",
                       "front_door_ttft_p50_ms", "step_stall_max_ms",
                       "step_host_ms_p50")

sys.path.insert(0, str(ROOT))
from benchmark.layer_metrics import _program_spans, _steps  # noqa: E402
from benchmark.layer_metrics._common import load_metric_file  # noqa: E402


def reader(name: str):
    return load_metric_file(ROOT / "benchmark" / "layer_metrics" / f"{name}.py")


def step(number, t_start, t_end, program, rows, fetch, admitted=(),
         finished=()):
    wall = round(t_end - t_start, 6)
    return {"step": number, "kind": "decode" if program else "idle",
            "batch": rows, "t_start": t_start, "t_end": t_end, "wall_s": wall,
            "phases": {"admit": 0.0, "build": 0.0, "issue": 0.0,
                       "fetch": fetch, "emit": 0.0, "draft": 0.0,
                       "other": round(wall - fetch, 6)},
            "program": list(program), "k": 8 if rows else 0, "rows": rows,
            "admitted": [list(a) for a in admitted],
            "finished": list(finished)}


def life(rid, received, enqueued, first_token, first_write):
    return {"id": rid, "trace_id": rid, "t_received": received,
            "t_enqueued": enqueued, "t_admitted": enqueued + 0.01,
            "t_first_token": first_token, "t_first_write": first_write,
            "t_finished": 111.0, "prompt_tokens": 5, "cached_tokens": 0,
            "generated": 4, "preemptions": 0, "reason": "max_tokens",
            "max_emit_gap_s": 0.4}


DM, MX = ["_decode_multi"], ["_mixed_step"]


def hand_made_run() -> dict:
    """A window of 10 s from t0 = 100. Step 0 is the warm-up's tail, step
    3 a stall of 2.1 s, step 4 idle, step 5 lost between two polls, step
    8 ends in the drain."""
    steps = [
        step(0, 99.0, 99.5, DM, 2, 0.4, admitted=[("w", 0.9, 5, 0)],
             finished=[life("w", 98.0, 98.1, 98.5, 98.6)]),
        step(1, 100.0, 100.4, MX, 2, 0.3,
             admitted=[("a", 0.010, 5, 0), ("b", 0.020, 5, 0)]),
        step(2, 100.4, 100.9, DM, 3, 0.45, admitted=[("c", 0.030, 5, 0)]),
        step(3, 100.9, 103.0, DM, 3, 0.1,
             finished=[life("a", 100.0, 100.002, 100.3, 100.301),
                       life("b", 100.1, 100.11, 100.9, 100.95)]),
        step(4, 103.0, 103.1, [], 0, 0.0),
        step(6, 104.0, 104.4, DM, 1, 0.38, admitted=[("d", 0.5, 5, 0)],
             finished=[life("c", 100.2, 100.22, 103.0, None)]),
        step(7, 104.4, 104.8, DM, 1, 0.39),
        step(8, 109.9, 110.5, DM, 1, 0.5, admitted=[("e", 0.040, 5, 0)],
             finished=[life("d", 103.5, 103.6, 104.4, 104.41),
                       life("e", 109.95, 109.96, 110.45, 110.47)]),
    ]
    return {"t0": 100.0, "seconds": 10.0, "steps": steps, "trace": None}


@pytest.mark.parametrize("name,by_hand", [
    # waits from the window's start on: .01 .02 .03 .04 .5; rank 3.6
    ("engine_queue_wait_p90_ms", 1e3 * (0.04 + 0.6 * (0.5 - 0.04))),
    # first token less received, a..e: .3 .8 2.8 .9 .5; rank 3.6
    ("engine_ttft_p90_ms", 1e3 * (0.9 + 0.6 * (2.8 - 0.9))),
    # in plus out, streamed only (c was not): a .003, b .06, d .11, e .03
    ("front_door_ttft_p50_ms", 1e3 * (0.03 + 0.06) / 2),
    # neighbours with rows: 1-2 .5, 2-3 2.1, 6-7 .4 (3-4: no rows; 4-6: 5 lost)
    ("step_stall_max_ms", 2100.0),
    # wall less fetch of dispatching window steps: .1 .05 2.0 .02 .01
    ("step_host_ms_p50", 50.0),
])
def test_a_step_record_reader_by_hand(name, by_hand):
    assert reader(name).read(hand_made_run()) == pytest.approx(by_hand, rel=1e-9)


@pytest.mark.parametrize("name", STEP_RECORD_METRICS)
def test_a_step_record_reader_without_its_input(name):
    """The parent's records have none of the fields; a run may have
    polled nothing; the window may have dispatched nothing."""
    old = {"step": 3, "ts": 1.0, "kind": "decode", "tokens": 8, "batch": 2,
           "wall_s": 0.4}
    for steps in ([], [old, {**old, "step": 4}]):
        run = {**hand_made_run(), "steps": steps}
        assert reader(name).read(run) is None
    outside = {**hand_made_run(), "t0": 500.0}
    assert reader(name).read(outside) is None


def test_a_request_that_never_had_a_token_is_infinitely_slow():
    run = hand_made_run()
    run["steps"][-1]["finished"].append(life("f", 105.0, 105.1, None, None))
    run["steps"][-1]["finished"].append(life("g", 105.0, 105.1, None, None))
    assert reader("engine_ttft_p90_ms").read(run) == 1e12
    assert reader("front_door_ttft_p50_ms").read(run) == pytest.approx(45.0)


def test_the_new_metrics_are_declared_as_the_files_say():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in STEP_RECORD_METRICS + ("idle_under_step_share",):
        mod, entry = reader(name), declared[name]
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            name, entry["unit"], entry["layer"], entry["moves"], entry["source"])
        assert entry["better"] == "lower" and "workloads" not in entry
    assert declared["front_door_ttft_p50_ms"]["layer"] == "front door"


# ---- the spans on the profiler's clock ---------------------------------------


def test_the_engines_spans_join_its_records_on_a_cpu_trace(tmp_path):
    """``annotate("engine.step", step=n)`` reads back as name
    ``engine.step``, stat ``step``; the phases lie inside it. A CPU trace
    has no device plane: no programs, no idle."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce
    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["llama3-test"]
    core = EngineCore(
        cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32),
        ByteTokenizer(), EngineConfig(
            page_size=4, num_pages=64, max_batch_slots=4, prefill_chunk=8,
            max_seq_len=128, block_pages=4, kv_dtype=jnp.float32))

    def serve() -> None:
        core.submit(EngineRequest(
            prompt_ids=list(b"two chunks of prompt"), sampling=SamplingParams(
                temperature=0.0, max_new_tokens=10, stop_token_ids=())))
        core.run_until_idle()

    serve()
    first = core.flight.total_steps
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    serve()
    jax.profiler.stop_trace()
    records = [s for s in core.flight.snapshot() if s["step"] >= first]
    loaded = _program_spans.load(trace_reduce.newest_xplane(tmp_path))
    steps = [sp for sp in loaded["spans"] if sp[0] == _program_spans.STEP]
    assert [sp[3] for sp in steps] == [s["step"] for s in records]
    names = {sp[0] for sp in loaded["spans"]}
    assert {"engine.admit", "engine.build", "engine.fetch_tokens",
            "engine.emit", "prefill", "decode"} <= names
    for name, t0, t1, _ in loaded["spans"]:
        assert name == _program_spans.STEP or any(
            s0 <= t0 and t1 <= s1 for _, s0, s1, _ in steps), name
    for sp, rec in zip(steps, records):  # the two clocks time one step
        assert sp[2] - sp[1] == pytest.approx(rec["wall_s"], abs=2e-3)
    rows = _program_spans.join_steps(loaded, records)
    assert [r["record_program"] for r in rows] == [s["program"] for s in records]
    assert loaded["modules"] == []


@pytest.fixture()
def chip_trace(tmp_path, monkeypatch):
    """The trace recorded on the chip (``tools/record_step_spans.py``),
    laid out as a run leaves it, and the flight records of its steps."""
    from benchmark import serving

    run_dir = tmp_path / "run"
    (run_dir / "trace").mkdir(parents=True)
    xplane = run_dir / "trace" / "step_spans.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (TESTDATA / "step_spans.xplane.pb.gz").read_bytes()))
    monkeypatch.setattr(serving, "RUN_DIR", run_dir)
    return xplane, json.loads((TESTDATA / "step_spans.steps.json").read_text())


def test_the_recorded_chip_trace_joins_spans_records_and_programs(chip_trace):
    from benchmark.tools import join_steps

    xplane, records = chip_trace
    loaded = _program_spans.load(xplane)
    rows = _program_spans.join_steps(loaded, records)
    assert [r["step"] for r in rows] == [s["step"] for s in records]
    known = ["_prefill_step", "_mixed_step", "_decode_step", "_decode_multi",
             "_decode_spec"]
    assert all(join_steps.matches(r, known) for r in rows), rows
    assert any(r["record_program"] for r in rows)
    assert {m for m, _, _ in loaded["modules"]} >= {"jit__prefill_step"}
    assert len((TESTDATA / "step_spans.xplane.pb.gz").read_bytes()) < 400_000


def test_idle_under_step_share_on_the_recorded_chip_trace(chip_trace):
    """The recording steps through ``AsyncEngine``: the way from a step to
    the next is ``engine.loop``, a named row, and what no span covers is
    the slice's two ends only."""
    from benchmark.tools import join_steps

    xplane, records = chip_trace
    mod = reader("idle_under_step_share")
    idle = _program_spans.idle_by_span(xplane)
    assert set(idle) <= set(_program_spans.ENGINE_SPANS) | {"between steps"}
    assert {"engine.build", "engine.loop"} <= set(idle)
    by_hand = (100.0 * (idle.get("engine.step", 0.0)
                        + idle.get("between steps", 0.0))
               / sum(idle.values()))
    assert 0.0 <= by_hand < 100.0
    assert mod.read({"trace": {"window_s": 1.0}}) == pytest.approx(by_hand)
    assert mod.read({"trace": None}) is None  # the CPU rehearsal
    steps = sum(sp[0] == "engine.step"
                for sp in _program_spans.load(xplane)["spans"])
    per_step = join_steps.idle_per_step(idle, steps)
    assert sum(per_step.values()) == pytest.approx(
        1e3 * sum(idle.values()) / steps)
    assert per_step["loop_ms"] > 0.0
    # The records' clock has the same interval, on every pair of steps.
    between = join_steps.between_steps_ms(records)
    assert between["pairs"] >= 1 and 0.0 < between["p50"] <= between["max"]


def test_idle_under_step_share_where_there_is_nothing_to_share(
        tmp_path, monkeypatch):
    """A traced run whose trace file is gone is a fault, not a missing
    input; a slice with no idle time has 0% of it unexplained."""
    from benchmark import serving

    monkeypatch.setattr(serving, "RUN_DIR", tmp_path)
    mod = reader("idle_under_step_share")
    with pytest.raises(FileNotFoundError):
        mod.read({"trace": {"window_s": 1.0}})
    (tmp_path / "trace").mkdir()
    (tmp_path / "trace" / "t.xplane.pb").write_bytes(b"")
    for idle in ({}, {"engine.build": 0.0}):
        monkeypatch.setattr(_program_spans, "idle_by_span", lambda _: idle)
        assert mod.read({"trace": {"window_s": 1.0}}) == 0.0


def test_the_idle_seconds_a_step_and_the_records_own_gaps_by_hand():
    from benchmark.tools import join_steps

    idle = {"engine.build": 0.030, "mixed": 0.020, "engine.step": 0.002,
            "engine.loop": 0.040, "between steps": 0.008}
    assert join_steps.idle_per_step(idle, 10) == pytest.approx(
        {"in_phases_ms": 5.0, "in_step_unnamed_ms": 0.2, "loop_ms": 4.0,
         "no_span_ms": 0.8})
    # The hand-made run's neighbours that both dispatched: 0-1 500 ms,
    # 1-2 0, 2-3 0, 6-7 0, 7-8 5100; widen two of them. 3-4: step 4
    # dispatched nothing; 4-6: step 5 was lost.
    run = hand_made_run()
    run["steps"][2]["t_start"] += 0.003   # step 2 starts 3 ms after 1 ends
    run["steps"][6]["t_start"] += 0.001   # step 7 starts 1 ms after 6 ends
    got = join_steps.between_steps_ms(run["steps"])
    assert got["pairs"] == 5 and got["max"] == pytest.approx(5100.0)
    assert got["sum"] == pytest.approx(5604.0) and got["p50"] == pytest.approx(3.0)
    assert join_steps.between_steps_ms([{"step": 1, "wall_s": 0.1}]) is None


# ---- the front door taken apart ----------------------------------------------


def test_the_front_door_split_by_hand():
    """Two requests in the trace and the records, one streamed; a third
    whose record the polls never saw; a write of a request with no parse
    span in the trace (it began before the slice)."""
    loaded = {"server": [
        ("server.parse", 1.000, 1.004, "a"), ("server.write", 1.004, 1.0045, "a"),
        ("server.parse", 1.100, 1.102, "b"),
        ("server.write", 1.300, 1.301, "a"), ("server.write", 1.400, 1.4004, "a"),
        ("server.parse", 1.500, 1.503, "lost"),
        ("server.write", 1.600, 1.602, "early")]}
    steps = [{"step": 1, "finished": [
        life("a", 100.0, 100.050, 100.3, 100.302),
        life("b", 100.1, 100.1025, 100.9, None)]}]
    got = _program_spans.front_door(loaded, steps)
    assert got["requests"] == 2 and got["writes"] == 4
    a, b = got["rows"]
    assert a == pytest.approx({"request": "a", "parse_ms": 4.0,
                               "handoff_ms": 46.0, "first_write_ms": 2.0,
                               "writes": 3, "write_ms_sum": 1.9,
                               "write_ms_max": 1.0})
    assert (b["parse_ms"], b["handoff_ms"]) == pytest.approx((2.0, 0.5))
    assert b["first_write_ms"] is None and b["writes"] == 0
    assert got["parse_ms_p50"] == pytest.approx(3.0)
    assert got["handoff_ms_p50"] == pytest.approx(23.25)
    assert got["first_write_ms_p50"] == pytest.approx(2.0)
    assert got["write_ms_max"] == pytest.approx(2.0)
    empty = _program_spans.front_door({"server": []}, steps)
    assert empty["requests"] == 0 and empty["parse_ms_p50"] is None


def test_the_front_door_split_on_a_live_tiny_server(tmp_path):
    """``server.parse`` and ``server.write`` carry the request's id, and
    the tool joins them to ``/debug/steps`` as an operator would: a CPU
    trace of the tiny server, two streamed requests and one whole."""
    import urllib.request

    import jax

    from benchmark import trace_reduce
    from benchmark.tools import front_door
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.server.openai_api import OpenAIServer

    client = JaxTpuClient.for_testing(max_new_tokens=6)
    srv = OpenAIServer(client, model_name="llama3-test", port=0)
    srv.start_background()
    base = f"http://127.0.0.1:{srv.port}"

    def chat(content: str, stream: bool, rid: str) -> bytes:
        req = urllib.request.Request(
            base + "/v1/chat/completions",
            data=json.dumps({"messages": [{"role": "user", "content": content}],
                             "max_tokens": 5, "stream": stream}).encode(),
            headers={"Content-Type": "application/json", "x-request-id": rid})
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.read()

    try:
        chat("warm every shape", True, "warm")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "trace"),
                                 profiler_options=options)
        chat("stream me", True, "s1")
        chat("and me", True, "s2")
        chat("all at once", False, "w1")
        jax.profiler.stop_trace()
        with urllib.request.urlopen(base + "/debug/steps?n=512", timeout=60) as r:
            (tmp_path / "steps.json").write_bytes(r.read())
    finally:
        srv.shutdown()
    out = tmp_path / "out" / "front_door.json"
    assert front_door.main(["", str(tmp_path / "trace"),
                            str(tmp_path / "steps.json"), str(out)]) == 0
    got = json.loads(out.read_text())
    rows = {r["request"]: r for r in got["rows"]}
    assert set(rows) == {"s1", "s2", "w1"}
    steps = _steps.load_steps(tmp_path / "steps.json")
    life_of = {f["trace_id"]: f for s in steps for f in s["finished"]}
    for rid, r in rows.items():
        f = life_of[rid]
        assert r["parse_ms"] > 0.0
        assert r["parse_ms"] + r["handoff_ms"] == pytest.approx(
            1e3 * (f["t_enqueued"] - f["t_received"]))
        # The two clocks agree on where the parse ends: the hand-off is
        # what is left of the way in, and it is not negative.
        assert r["handoff_ms"] > -1.0
    for rid in ("s1", "s2"):  # role chunk, content chunks, finish, [DONE]
        assert rows[rid]["writes"] >= 4 and rows[rid]["first_write_ms"] >= 0.0
        assert rows[rid]["write_ms_max"] <= rows[rid]["write_ms_sum"]
    assert rows["w1"]["writes"] == 0 and rows["w1"]["first_write_ms"] is None
    assert got["writes"] == rows["s1"]["writes"] + rows["s2"]["writes"]
    # The engine's loop under the same profiler: the way from a step to
    # the next is a span of its own, outside every step.
    loaded = _program_spans.load(
        trace_reduce.newest_xplane(tmp_path / "trace"))
    steps_sp = [sp for sp in loaded["spans"] if sp[0] == "engine.step"]
    loops = [sp for sp in loaded["spans"] if sp[0] == "engine.loop"]
    assert loops and len(loops) < len(steps_sp)
    for _, t0, t1, _ in loops:
        assert not any(s0 < (t0 + t1) / 2 < s1 for _, s0, s1, _ in steps_sp)


# ---- end to end on the tiny preset -------------------------------------------


def test_a_rehearsal_prints_the_five_step_record_metrics(tmp_path):
    """In a copy of the benchmark's files: a run keeps its plans, records
    and trace under its own root (``serving.RUN_DIR``), and the rehearsals
    of ``test_benchmark.py`` run beside this one in another worker."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "2147496777", "--seconds",
         "5", "--trace", "1", "--rehearse-cpu"],
        cwd=tmp_path, env=ENV, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["failed"] == 0
    values = {k: v["value"] for k, v in last["metrics"].items()}
    assert set(STEP_RECORD_METRICS) <= set(values)
    assert "idle_under_step_share" not in values  # no device plane on a CPU
    assert all(values[k] > 0 for k in STEP_RECORD_METRICS)
    assert values["engine_ttft_p90_ms"] <= values["client_ttft_p90_ms"]
    assert values["front_door_ttft_p50_ms"] < values["engine_ttft_p90_ms"]
    assert values["step_host_ms_p50"] <= values["step_stall_max_ms"]
