"""The five per-layer metrics that read the dispatch as a span
(``layer_metrics/_dispatches.py``): on a hand-made run with each value
worked out by hand (a late issue is ``between``, a record lost between two
polls takes its successor's interval away), on the parent's records (None,
not an error); the benchmark's copy of the interval's definition against
the engine's own sums, on a live CPU engine and on the records recorded on
the chip; the join of a dispatch to the device's event of it by the
``dispatch`` stat, on a CPU trace made here and on the chip's
(``tools/record_dispatch_spans.py``); the table tool; one CPU rehearsal
through ``tools/keep_steps.py``, in a copy of the benchmark's files.

No TPU topology is described here, at import or later.
"""

from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TESTDATA = ROOT / "benchmark" / "testdata"
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
NEW = ("decode_pass_ms_p50", "mixed_dispatch_ms_p50",
       "tpot_decode_ms_per_token_p50", "tpot_mixed_share",
       "window_dispatch_busy_share")
STEP_PROGRAMS = ["_prefill_step", "_mixed_step", "_decode_step",
                 "_decode_multi", "_decode_spec"]

sys.path.insert(0, str(ROOT))
from benchmark.layer_metrics import _dispatches  # noqa: E402
from benchmark.layer_metrics._common import load_metric_file  # noqa: E402


def reader(name: str):
    return load_metric_file(ROOT / "benchmark" / "layer_metrics" / f"{name}.py")


def dispatch(n, program, k, t_issued, t_ready, rows=4, pages=40):
    return {"n": n, "program": program, "k": k, "rows": rows if k else 0,
            "kv_pages_live": pages if k else 0,
            "prefill_tokens": 0 if program.startswith("_decode") else 64,
            "t_issued": t_issued, "t_ready": t_ready, "tokens": k * rows}


def life(rid, received, first_token, finished, generated, rode, preemptions=0):
    return {"id": rid, "trace_id": rid, "t_received": received,
            "t_enqueued": received, "t_admitted": received,
            "t_first_token": first_token, "t_first_write": None,
            "t_finished": finished, "prompt_tokens": 5, "cached_tokens": 0,
            "generated": generated, "preemptions": preemptions,
            "reason": "max_tokens", "max_emit_gap_s": 0.1, "rode": rode}


def step(number, t_start, t_end, dispatches=(), finished=()):
    wall = t_end - t_start
    return {"step": number, "kind": "decode", "t_start": t_start,
            "t_end": t_end, "wall_s": wall, "program": ["_decode_multi"],
            "phases": {"fetch": wall / 2, "other": wall / 2}, "rows": 4,
            "admitted": [], "finished": list(finished),
            "dispatches": list(dispatches)}


DM, MX, SP = "_decode_multi", "_mixed_step", "_decode_spec"


def hand_made_run() -> dict:
    """A window of 10 s from t0 = 100. Dispatch 10 is the warm-up's, 11
    straddles the window's start, 13 was issued 0.4 s late, 14's record was
    lost between two polls, 18 comes back in the drain."""
    rode_a = {DM: [2, 15, 1.8], SP: [1, 4, 0.6], MX: [2, 0, 0.5],
              "_prefill_step": [1, 0, 0.24], "between": 0.56}
    rode_b = {DM: [1, 8, 0.8], MX: [1, 0, 0.1], "between": 0.1}
    rode_c = {DM: [4, 29, 3.0], MX: [3, 0, 0.9], "between": 0.6}
    steps = [
        step(0, 99.0, 99.95, [dispatch(10, DM, 8, 99.0, 99.9)],
             finished=[life("w", 98.0, 98.5, 99.9, 40, rode_c)]),
        step(1, 100.0, 100.75, [dispatch(11, DM, 8, 99.5, 100.7)]),
        step(2, 100.8, 100.95, [dispatch(12, MX, 1, 100.6, 100.9)]),
        step(3, 101.0, 102.3, [dispatch(13, DM, 8, 101.3, 102.26)],
             finished=[life("b", 100.1, 101.2, 102.2, 9, rode_b)]),
        # step 4, with dispatch 14, was lost between two polls
        step(5, 103.0, 104.7, [dispatch(15, DM, 8, 103.0, 104.0),
                               dispatch(16, SP, 4, 103.9, 104.6)],
             finished=[life("a", 100.0, 100.9, 104.6, 20, rode_a),
                       life("p", 100.2, 101.0, 104.6, 30, rode_c, preemptions=1),
                       life("s", 100.3, 101.0, 104.6, 5, rode_b),
                       life("n", 100.4, None, 104.6, 0, None)]),
        step(6, 104.7, 105.1, [dispatch(17, MX, 1, 104.5, 105.0)],
             finished=[life("c", 100.5, 100.6, 105.1, 30, rode_c)]),
        step(7, 109.4, 110.5, [dispatch(18, DM, 8, 109.5, 110.4)]),
    ]
    return {"t0": 100.0, "seconds": 10.0, "steps": steps, "trace": None}


@pytest.mark.parametrize("name,by_hand", [
    # 11: (100.7 - 99.9) / 8 = 100 ms; 13: (102.26 - 101.3) / 8 = 120; 15 has
    # no predecessor; 16: (104.6 - 104.0) / 4 = 150; 18 is ready in the drain
    ("decode_pass_ms_p50", 120.0),
    # 12: 100.9 - max(100.6, 100.7) = 0.2; 17: 105.0 - max(104.5, 104.6) = 0.4
    ("mixed_dispatch_ms_p50", 300.0),
    # a: (1.8 + 0.6) / 19; b: 0.8 / 8; c: 3.0 / 29; p preempted, s short,
    # n never had a token, w was received before the window
    ("tpot_decode_ms_per_token_p50", 1e3 * 3.0 / 29),
    # a: (0.5 + 0.24) / 3.7 = 20%; b: 0.1 / 1.0 = 10%; c: 0.9 / 4.5 = 20%
    ("tpot_mixed_share", 20.0),
    # 11 cut to the window 0.7, 12 0.2, 13 0.96, 16 0.6, 17 0.4, and 18,
    # in flight at the window's end, from 109.5 to 110: 0.5, of 10 s
    ("window_dispatch_busy_share", 33.6),
])
def test_a_dispatch_reader_by_hand(name, by_hand):
    assert reader(name).read(hand_made_run()) == pytest.approx(by_hand, rel=1e-9)


def flat(partition: dict | None) -> dict | None:
    """{"between": s, program: [n, s]} as one level of numbers, for approx."""
    if partition is None:
        return None
    return {"between": partition["between"],
            **{f"{p}.{i}": x for p, v in partition.items() if p != "between"
               for i, x in enumerate(v)}}


def test_a_late_issue_is_between_and_a_lost_record_takes_an_interval_away():
    entries = _dispatches.entries(hand_made_run()["steps"])
    assert [d["n"] for d in entries] == [10, 11, 12, 13, 15, 16, 17, 18]
    rows = {d["n"]: (seconds, between)
            for d, seconds, between in _dispatches.intervals(entries)}
    assert sorted(rows) == [11, 12, 13, 16, 17, 18]  # 10 is first, 15 after the lost one
    assert rows[13] == pytest.approx((0.96, 0.4))   # issued 0.4 s after 12 was ready
    assert rows[12] == pytest.approx((0.2, 0.0))    # queued behind 11
    assert rows[18] == pytest.approx((0.9, 4.5))
    # Anew over (100.9, 102.26]: 13 and the wait for it; past a lost record, None.
    assert flat(_dispatches.partition(entries, 100.9, 102.26)) == pytest.approx(
        flat({"between": 0.4, DM: [1, 0.96]}))
    assert flat(_dispatches.partition(entries, 100.8, 101.5)) == pytest.approx(
        flat({"between": 0.4, MX: [1, 0.1], DM: [0, 0.2]}))
    assert _dispatches.partition(entries, 100.9, 104.3) is None
    assert _dispatches.partition(entries, 98.0, 100.0) is None  # none ready by then
    # After the last dispatch there is, the device has nothing: between.
    assert flat(_dispatches.partition(entries, 110.0, 111.0)) == pytest.approx(
        flat({"between": 0.6, DM: [1, 0.4]}))


def test_an_unwaited_fetch_names_the_dispatches_the_interval_may_overstate():
    """Under the overlapped pipeline ``between`` is 0 by construction, so
    device idle time behind a late issue lies inside the dispatch before
    it. The records cannot say how much; they can say WHERE it can be: in
    a dispatch whose fetch did not wait."""
    steps = hand_made_run()["steps"]
    assert _dispatches.unwaited(steps) == []  # (every fetch phase is half a step)
    steps[2]["phases"]["fetch"] = 2e-4   # 12: the host came after the device was done
    steps[5]["phases"]["fetch"] = 0.0    # 17 ...
    steps[5]["dispatches"][0]["t_ready"] = 104.65  # ... but ready in the step before
    assert [d["n"] for d in _dispatches.unwaited(steps)] == [12]
    for s in steps:
        del s["dispatches"]
    assert _dispatches.unwaited(steps) is None


def loaded_slice(issued, fetched, modules):
    """``_dispatches.load``'s shape from (n, annotation, call, issued),
    (n, fetch end) and (program, start, end)."""
    return {"issued": list(issued), "modules": list(modules),
            "fetched": [(n, _dispatches.FETCH, end - 0.01, end) for n, end in fetched]}


SLICE = dict(
    # 4 was called before the slice began (its event is there, its
    # annotation not); 5 and 7 ride the pipeline, 6 is a mixed step.
    issued=[(5, "decode", 1.00, 1.01), (6, "mixed", 1.16, 1.17),
            (7, "decode", 1.32, 1.33), (8, "decode", 1.40, 1.41)],
    fetched=[(5, 1.31), (6, 1.39), (7, 1.55)],
    modules=[(DM, 0.99, 1.15), (DM, 1.15, 1.30), (MX, 1.30, 1.38),
             (DM, 1.38, 1.54)])


@pytest.mark.parametrize("case,change,numbers,agree", [
    # by number and order: the slice opens on 4's event, 8's lies past its end
    ("whole", {}, [5, 6, 7], [True] * 3),
    # a host span was dropped (or a step program carries no stat): the pairs
    # after it would shift by one with every NAME still agreeing ...
    ("a_dropped_span_among_decodes", dict(
        issued=[(5, "decode", 1.00, 1.01), (7, "decode", 1.32, 1.33),
                (8, "decode", 1.48, 1.49)],
        fetched=[(5, 1.31), (7, 1.63), (8, 1.79)],
        modules=[(DM, 0.99, 1.15), (DM, 1.15, 1.30), (DM, 1.30, 1.46),
                 (DM, 1.46, 1.62), (DM, 1.62, 1.78)]),
     # ... and 7 is seen to sit on an event that began before its call did
     [5, 7], [True, False]),
    # the event at a dispatch's place ended after its result reached the host
    ("ended_after_its_fetch", {"fetched": [(5, 1.31), (6, 1.36), (7, 1.55)]},
     [5, 6], [True, False]),
    # the event at its place is another program's
    ("another_program", {"modules": SLICE["modules"][:2] + [
        (DM, 1.30, 1.38), SLICE["modules"][3]]}, [5, 6], [True, False]),
    # a chunk short of its prompt's end has no fetch of its own: by order
    ("no_fetch_of_its_own", {"fetched": [(5, 1.31), (7, 1.55)]},
     [5, 6, 7], [True] * 3),
    # the device plane's clock reads 0.6 ms early: a program on an idle
    # device "starts" before the annotation around its call does
    ("the_device_clock_reads_early", dict(
        issued=[(5, "decode", 1.00, 1.004), (6, "decode", 1.20, 1.204)],
        fetched=[(5, 1.163), (6, 1.363)],
        modules=[(DM, 0.9994, 1.1594), (DM, 1.1994, 1.3594)]),
     [5, 6], [True, True]),
])
def test_the_join_is_by_number_order_and_the_hosts_two_ends(
        case, change, numbers, agree):
    rows = _dispatches.join(loaded_slice(**{**SLICE, **change}))
    assert [r["n"] for r in rows] == numbers
    assert [r["agrees"] for r in rows] == agree
    assert rows[0]["device_s"] == pytest.approx(0.16 if "clock" in case else 0.15)


@pytest.mark.parametrize("name", NEW)
def test_a_dispatch_reader_on_the_parents_records(name):
    """The parent's records are spans without ``dispatches`` and lifecycle
    records without ``rode``; a run may have polled nothing; a window may
    hold no dispatch of the reader's kind."""
    run = hand_made_run()
    for s in run["steps"]:
        del s["dispatches"]
        for f in s["finished"]:
            del f["rode"]
    assert reader(name).read(run) is None
    assert reader(name).read({**run, "steps": []}) is None
    older = {"step": 3, "ts": 1.0, "kind": "decode", "tokens": 8, "wall_s": 0.4}
    assert reader(name).read({**run, "steps": [older]}) is None
    outside = {**hand_made_run(), "t0": 500.0}
    assert reader(name).read(outside) in (None, 0.0)  # no share of nothing is 0


def test_the_five_are_declared_last_and_as_the_files_say():
    """Last when they came (PR 37); later PRs appended theirs behind them.
    What holds: the five stand together, in their order, and every cell
    reports them (no ``workloads`` key)."""
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(NEW[0])
    declared = BENCH["per_layer"][first:first + 5]
    assert tuple(m["name"] for m in declared) == NEW
    for entry in declared:
        mod = reader(entry["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            entry["name"], entry["unit"], entry["layer"], "tpot_p50_ms",
            "program_span")
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves"}
    assert [m["better"] for m in declared] == ["lower"] * 4 + ["higher"]
    layers = {m["layer"] for m in BENCH["per_layer"][:first]}
    assert {m["layer"] for m in declared} <= layers  # no layer of their own


# ---- the two copies of the definition ----------------------------------------


def check_rode_against_the_dispatches(steps: list[dict]) -> int:
    """Every unpreempted request's ``rode`` (the ENGINE's sums, by
    ``flight_recorder.DispatchLedger``) against ``_dispatches.partition``
    of the same interval (the BENCHMARK's copy of the rule), program by
    program. Returns how many requests were held to it."""
    entries = _dispatches.entries(steps)
    held = 0
    for f in (f for s in steps for f in s["finished"]):
        if not f["rode"] or f["preemptions"]:
            continue
        anew = _dispatches.partition(entries, f["t_first_token"], f["t_finished"])
        if anew is None:
            continue  # its first token came before the first polled record
        rode = f["rode"]
        assert sum(v[2] for k, v in rode.items() if k != "between") + rode["between"] \
            == pytest.approx(f["t_finished"] - f["t_first_token"], abs=1e-6)
        assert sum(v[1] for k, v in rode.items() if k != "between") == f["generated"] - 1
        assert rode["between"] == pytest.approx(anew.pop("between"), abs=1e-6)
        assert {p for p, v in rode.items() if p != "between" and (v[0] or v[2] > 1e-9)} \
            == {p for p, v in anew.items() if v[0] or v[1] > 1e-9}
        for program, (count, seconds) in anew.items():
            got = rode.get(program, [0, 0, 0.0])
            assert (got[0], got[2]) == (count, pytest.approx(seconds, abs=1e-6))
        held += 1
    return held


def test_the_engines_sums_and_the_benchmarks_copy_agree_on_a_live_engine():
    import jax
    import jax.numpy as jnp

    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["llama3-test"]
    core = EngineCore(
        cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32),
        ByteTokenizer(), EngineConfig(
            page_size=4, num_pages=64, max_batch_slots=4, prefill_chunk=8,
            max_seq_len=128, block_pages=4, kv_dtype=jnp.float32,
            decode_steps_per_dispatch=2, mixed_dispatch=True))
    texts = [b"the batch that runs", b"a prompt of three chunks rides along",
             b"short", b"one more, two chunks"]
    for i, text in enumerate(texts):
        core.submit(EngineRequest(prompt_ids=list(text), sampling=SamplingParams(
            temperature=0.0, max_new_tokens=9 + 4 * i, stop_token_ids=())))
        for _ in range(3):  # the next prompt meets a running batch
            core.step()
    core.run_until_idle()
    steps = json.loads(json.dumps(core.flight.snapshot()))  # as served
    assert {d["program"] for d in _dispatches.entries(steps)} >= {
        "_prefill_step", MX, DM}
    assert check_rode_against_the_dispatches(steps) == len(texts)


# ---- on the profiler's clock -------------------------------------------------


def test_the_dispatch_stat_joins_annotations_and_fetches_on_a_cpu_trace(tmp_path):
    """``annotate("decode", dispatch=n)`` and the fetch span that consumed
    ``n`` read back by that number. A CPU trace has no device plane: no
    row to join, and the tool says so without failing."""
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
            max_seq_len=128, block_pages=4, kv_dtype=jnp.float32,
            decode_steps_per_dispatch=2))

    def serve(text: bytes) -> None:
        core.submit(EngineRequest(
            prompt_ids=list(text), sampling=SamplingParams(
                temperature=0.0, max_new_tokens=10, stop_token_ids=())))
        core.run_until_idle()

    serve(b"two chunks of prompt")
    first = core.flight.dispatches.n
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    serve(b"another prompt, two chunks")  # (no page of the first's to hit)
    jax.profiler.stop_trace()
    entries = [d for d in _dispatches.entries(core.flight.snapshot())
               if d["n"] >= first]
    loaded = _dispatches.load(trace_reduce.newest_xplane(tmp_path), STEP_PROGRAMS)
    assert [(n, name) for n, name, _, _ in loaded["issued"]] == [
        (d["n"], _dispatches.ANNOTATION[d["program"]]) for d in entries]
    # Every dispatch that gave a token was fetched under its number; the
    # prompt's chunks short of its end gave none: waited for, not fetched.
    assert {n for n, *_ in loaded["fetched"]} == {
        d["n"] for d in entries if d["tokens"]}
    assert sum(not d["tokens"] for d in entries) >= 1
    for (n, _, t_call, t_issued), d in zip(loaded["issued"], entries):
        fetch_end = [e for m, _, _, e in loaded["fetched"] if m == n]
        if fetch_end:  # the two clocks time one dispatch
            assert fetch_end[0] - t_issued == pytest.approx(
                d["t_ready"] - d["t_issued"], abs=2e-3)
    assert loaded["modules"] == [] and _dispatches.join(loaded) == []


@pytest.fixture(scope="module")
def chip_recording(tmp_path_factory):
    """What ``tools/record_dispatch_spans.py`` recorded on the chip."""
    xplane = tmp_path_factory.mktemp("dispatch_spans") / "dispatch_spans.xplane.pb"
    xplane.write_bytes(gzip.decompress(
        (TESTDATA / "dispatch_spans.xplane.pb.gz").read_bytes()))
    return xplane, json.loads((TESTDATA / "dispatch_spans.steps.json").read_text())


def test_the_recorded_dispatches_join_the_devices_events(chip_recording):
    xplane, steps = chip_recording
    entries = _dispatches.entries(steps)
    assert [d["n"] for d in entries] == list(range(entries[0]["n"],
                                                   entries[-1]["n"] + 1))
    loaded = _dispatches.load(xplane, STEP_PROGRAMS)
    rows = _dispatches.join(loaded)
    assert len(rows) >= 15 and all(r["agrees"] for r in rows)
    by_n = {d["n"]: d for d in entries}
    for r in rows:
        assert by_n[r["n"]]["program"] == r["program"]
        # The device took it up after its call began and was done before
        # the fetch that consumed it returned (the two planes' clocks agree
        # to some tens of microseconds).
        slack = _dispatches.CLOCK_SLACK_S
        assert r["call_s"] <= r["start_s"] + slack
        assert r["fetch_end_s"] is None or r["end_s"] <= r["fetch_end_s"] + slack
    compared = _dispatches.against_records(rows, entries)
    assert {DM, MX} <= set(compared["by_program"])
    assert [p["n"] for p in compared["pairs"]] == [r["n"] for r in rows[1:-1]]
    # A tiny model's dispatch is tens of microseconds on the device and the
    # host's stamps bracket it from outside: never shorter than the event.
    assert all(p["record_ms"] >= p["device_ms"] for p in compared["pairs"])
    assert len((TESTDATA / "dispatch_spans.xplane.pb.gz").read_bytes()) < 1_200_000


def test_the_recorded_rode_is_the_recorded_dispatches(chip_recording):
    _, steps = chip_recording
    assert check_rode_against_the_dispatches(steps) >= 6
    run = {"t0": min(s["t_start"] for s in steps), "steps": steps, "trace": None,
           "seconds": max(s["t_end"] for s in steps) - min(s["t_start"] for s in steps)}
    values = {name: reader(name).read(run) for name in NEW}
    assert all(v is not None and v > 0.0 for v in values.values()), values
    assert values["window_dispatch_busy_share"] < 100.0
    assert values["tpot_mixed_share"] < 100.0


def test_the_table_tool_on_the_recording(tmp_path):
    out = tmp_path / "table.json"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.tools.dispatches",
         str(TESTDATA / "dispatch_spans.xplane.pb.gz"),
         str(TESTDATA / "dispatch_spans.steps.json"), str(out)],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    lines = proc.stdout.strip().splitlines()
    assert lines[0].split() == ["n", "program", "k", "rows", "pages", "record",
                                "ms", "device", "ms", "between", "ms"]
    last = json.loads(lines[-1])
    assert last["joined"] >= 15 and not last["disagree"]
    assert set(last["unwaited"]) == {"dispatches", "seconds"}
    assert len(lines) - 2 == last["dispatches"] and len(last["thirds"]) == 3
    assert json.loads(out.read_text())["pairs"]
    # A tiny engine's stamps are microseconds from a 60 us event: the 3%
    # is a real cell's; here the tool must only say what it found.
    assert proc.returncode in (0, 1), proc.stderr[-2000:]


def test_a_cpu_rehearsal_keeps_its_steps_and_prints_the_new_metrics(tmp_path):
    """``tools/keep_steps.py`` around one rehearsal of the dense cell: the
    run's own line with the new metrics (no mixed step on the CPU: that one
    has nothing to read), and the polled records on disk, whose every
    request adds up."""
    import shutil

    # In a copy of the benchmark's files: a run keeps its serve config,
    # plans and records under its own root (``serving.RUN_DIR``), and the
    # rehearsals of ``test_benchmark.py`` run beside this one in another
    # worker.
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    kept = tmp_path / "steps.json"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.tools.keep_steps", str(kept),
         "--workload", "qwen7b.chat-open", "--seed", "3000000007",
         "--seconds", "4", "--trace", "1", "--rehearse-cpu"],
        cwd=tmp_path, env=ENV, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0
    got = line["metrics"]
    for name in set(NEW) - {"mixed_dispatch_ms_p50"}:
        assert got[name]["value"] >= 0.0 and got[name]["unit"] == reader(name).UNIT
    assert 0.0 < got["window_dispatch_busy_share"]["value"] < 100.0
    saved = json.loads(kept.read_text())
    assert saved["seconds"] == 4.0 and saved["t0"] > 0.0
    assert check_rode_against_the_dispatches(saved["steps"]) >= 5
