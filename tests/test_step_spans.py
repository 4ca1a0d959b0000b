"""The step record as a span (docs/observability.md): every step's two
ends and phases, the lifecycle record of every retired request, what the
front door stamps, and that none of it is built with the recorder off.
CPU, tiny engine and server."""

from __future__ import annotations

import dataclasses
import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from runbookai_tpu.engine.engine import EngineConfig, EngineCore
from runbookai_tpu.engine.flight_recorder import (
    DISPATCH_FIELDS,
    LIFECYCLE_FIELDS,
    PHASE_SPANS,
    STEP_PHASES,
    STEP_RECORD_FIELDS,
    OpenStep,
)
from runbookai_tpu.engine.request import (
    EngineRequest,
    RequestOrigin,
    SamplingParams,
    request_origin,
)
from runbookai_tpu.models.llama import CONFIGS, init_params
from runbookai_tpu.utils.tokens import ByteTokenizer

NEW_FIELDS = ("t_start", "t_end", "phases", "program", "k", "rows",
              "kv_pages_live", "prefill_tokens", "decode_tokens", "compile_s",
              "admitted", "finished", "dispatches", "sampler")
ORDER = ("t_received", "t_enqueued", "t_admitted", "t_first_token",
         "t_finished")


@pytest.fixture(scope="module")
def parts():
    cfg = CONFIGS["llama3-test"]
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)


def make_core(parts, **kw) -> EngineCore:
    cfg, params = parts
    settings = dict(page_size=4, num_pages=64, max_batch_slots=4,
                    prefill_chunk=8, max_seq_len=128, block_pages=4,
                    kv_dtype=jnp.float32, flight_recorder_steps=256)
    settings.update(kw)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(**settings))


def request(text: bytes, n: int = 6) -> EngineRequest:
    return EngineRequest(prompt_ids=list(text), sampling=SamplingParams(
        temperature=0.0, max_new_tokens=n, stop_token_ids=()))


def test_the_two_clocks_are_one():
    """The lifecycle record mixes perf_counter() stamps the engine always
    took (arrival, first token, finish) with monotonic() ones: on Linux
    both read CLOCK_MONOTONIC. Said here, not assumed."""
    for _ in range(5):
        a, b, c = time.monotonic(), time.perf_counter(), time.monotonic()
        assert a - 1e-3 <= b <= c + 1e-3


def test_the_field_lists_hold_the_new_fields():
    assert set(NEW_FIELDS) <= set(STEP_RECORD_FIELDS)
    assert set(PHASE_SPANS) < set(STEP_PHASES) and "issue" in STEP_PHASES
    assert set(ORDER) < set(LIFECYCLE_FIELDS)


def test_a_phase_inside_another_pauses_it():
    step = OpenStep()
    step.enter("build")
    time.sleep(0.01)
    step.enter("fetch")
    time.sleep(0.02)
    step.exit()
    step.exit()
    enclosed = time.monotonic() - step.t_start
    step.dispatched("_prefill_step")
    step.dispatched("_decode_multi", 8, 3, kv_pages_live=17)
    build, fetch = step.phases["build"], step.phases["fetch"]
    assert build >= 0.01 and fetch >= 0.02
    assert build + fetch <= enclosed  # build is NOT the whole it enclosed
    assert sum(step.phases.values()) == build + fetch
    assert (step.programs, step.k, step.rows, step.kv_pages_live) == (
        ["_prefill_step", "_decode_multi"], 8, 3, 17)


def check_steps(steps: list[dict]) -> None:
    for s in steps:
        assert set(NEW_FIELDS) <= set(s), s
        assert s["t_start"] <= s["t_end"]
        assert set(s["phases"]) == set(STEP_PHASES) | {"other"}
        assert all(v >= 0.0 for v in s["phases"].values()), s["phases"]
        assert abs(sum(s["phases"].values()) - s["wall_s"]) < 1e-3
        assert abs((s["t_end"] - s["t_start"]) - s["wall_s"]) < 1e-3
        assert s["tokens"] == s["prefill_tokens"] + s["decode_tokens"]
        assert (s["rows"] > 0) == (s["k"] > 0) == (s["kv_pages_live"] > 0)
        assert all(p.startswith("_") for p in s["program"])
        assert bool(s["program"]) == (s["kind"] != "idle")
    ends = [(s["t_start"], s["t_end"]) for s in steps]
    assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))  # no overlap


def check_lifecycle(steps: list[dict], ids: list[str]) -> dict[str, dict]:
    """Every request of ``ids`` in exactly ONE record's ``finished``."""
    seen = [f for s in steps for f in s["finished"]]
    assert sorted(f["id"] for f in seen) == sorted(ids)
    for f in seen:
        assert set(f) == set(LIFECYCLE_FIELDS)
        # An abort can retire a request before it was admitted or had a
        # token: those stamps stay None.
        stamps = [f[k] for k in ORDER if f[k] is not None]
        assert stamps == sorted(stamps), f
        owner = [s for s in steps if f in s["finished"]][0]
        assert f["t_finished"] <= owner["t_end"]
    return {f["id"]: f for f in seen}


def test_every_step_is_a_span_and_every_request_retires_once(parts):
    core = make_core(parts)
    reqs = [request(t) for t in (b"hello flight", b"recorder test", b"x")]
    for r in reqs:
        core.submit(r)
    core.run_until_idle()
    steps = core.flight.snapshot()
    check_steps(steps)
    life = check_lifecycle(steps, [r.request_id for r in reqs])
    admitted = [a for s in steps for a in s["admitted"]]
    assert sorted(a[0] for a in admitted) == sorted(life)
    for rid, wait_s, prompt_tokens, cached in admitted:
        f = life[rid]
        assert wait_s == pytest.approx(f["t_admitted"] - f["t_received"], abs=1e-3)
        assert (prompt_tokens, cached) == (f["prompt_tokens"], f["cached_tokens"])
    for r in reqs:
        f = life[r.request_id]
        assert f is r.lifecycle and f["reason"] == "max_tokens"
        assert f["generated"] == 6 and f["preemptions"] == 0
        assert f["t_received"] == r.arrival_time  # no front door
        assert f["t_first_write"] is None  # never streamed
        assert 0.0 < f["max_emit_gap_s"] <= f["t_finished"] - f["t_first_token"]
    assert core.metrics["compile_time_s"] == pytest.approx(
        sum(s["compile_s"] for s in steps), abs=1e-4)


@pytest.mark.parametrize("steps_per_dispatch", [1, 4])
def test_the_record_counts_the_pages_its_rows_hold(parts, monkeypatch,
                                                   steps_per_dispatch):
    """``kv_pages_live`` is Σ cdiv(ctx, page_size) over the context lengths
    the decode program was GIVEN (what the decode kernel's page walk
    reads), an empty slot counting nothing: checked against the arrays the
    dispatches received, one a record, in order."""
    import numpy as np

    from runbookai_tpu.engine import engine

    given: list[tuple[str, int]] = []
    for name in ("_decode_step", "_decode_multi"):
        def spy(*args, _real=getattr(engine, name), _name=name, **kw):
            ctx_lens = np.asarray(args[7])  # params, cfg, tokens, positions,
            given.append((_name, sum(       # kv_k, kv_v, tables, ctx_lens
                -(-int(c) // 4) for c in ctx_lens if c > 0)))
            return _real(*args, **kw)
        monkeypatch.setattr(engine, name, spy)

    core = make_core(parts, decode_steps_per_dispatch=steps_per_dispatch,
                     mixed_dispatch=False)
    assert core.ecfg.page_size == 4
    for text, n in ((b"a prompt of twenty-two", 9), (b"short", 5), (b"x", 12)):
        core.submit(request(text, n=n))
    core.run_until_idle()
    steps = core.flight.snapshot()
    check_steps(steps)
    recorded = [(p, s["kv_pages_live"]) for s in steps
                for p in s["program"] if p in ("_decode_step", "_decode_multi")]
    assert recorded == given and len(given) >= 3
    assert max(pages for _, pages in given) >= 6 + 2 + 1  # all three rows
    assert all(s["kv_pages_live"] == 0 for s in steps if not s["k"])


def test_the_records_hold_the_dispatch_fields(parts):
    """``k``, ``rows``, ``prefill_tokens`` and ``decode_tokens``, read off
    the records: every prompt token once, and more decode row-steps
    dispatched than tokens they gave."""
    core = make_core(parts, decode_steps_per_dispatch=4)
    for text in (b"one prompt here", b"and another"):
        core.submit(request(text, n=6))
    core.run_until_idle()
    steps = core.flight.snapshot()
    assert sum(x["prefill_tokens"] for x in steps) \
        == len(b"one prompt here") + len(b"and another")
    assert all(x["prefill_tokens"] + x["decode_tokens"] == x["tokens"]
               for x in steps)
    # Every decode token came out of a dispatched row-step; the windows
    # ran on past max_new_tokens, so some row-steps gave none.
    decode_tokens = sum(x["decode_tokens"] for x in steps)
    assert 0 < decode_tokens < sum(x["rows"] * x["k"] for x in steps)
    assert sum(d["prefill_tokens"] for x in steps for d in x["dispatches"]) \
        == sum(x["prefill_tokens"] for x in steps)


def test_a_compile_is_booked_to_the_step_it_stalled(parts):
    """Whether a program compiles here hangs on what the process compiled
    before, so the listener is fed by hand, from inside a step (the
    feedback hook runs there)."""
    from runbookai_tpu.engine import engine as engine_mod

    class CompilesOnce:
        fired = False

        def on_step(self, core):
            if not self.fired:
                self.fired = True
                engine_mod._on_compile(engine_mod._COMPILE_EVENT, 0.25)
                engine_mod._on_compile("/jax/another/event", 9.0)

    core = make_core(parts)
    core.step()  # an idle step first: the booking is per step
    before = dict(core.metrics)
    core.feedback = CompilesOnce()
    core.submit(request(b"compile"))
    core.run_until_idle()
    idle, stalled, *rest = core.flight.snapshot()
    assert idle["compile_s"] == 0.0
    assert 0.25 <= stalled["compile_s"] < 9.0
    assert core.metrics["compiles"] - before["compiles"] >= 1
    assert core.metrics["compile_time_s"] == pytest.approx(
        sum(s["compile_s"] for s in core.flight.snapshot()), abs=1e-4)


def test_a_preempted_request_keeps_its_first_admission(parts):
    core = make_core(parts, num_pages=20, max_batch_slots=2,
                     decode_steps_per_dispatch=1, admit_headroom_tokens=8)
    reqs = [request(bytes([ch]) * 21, n=40) for ch in b"ab"]
    for r in reqs:
        core.submit(r)
    core.run_until_idle()
    assert core.metrics["preemptions"] >= 1, "scenario must actually preempt"
    steps = core.flight.snapshot()
    check_steps(steps)
    life = check_lifecycle(steps, [r.request_id for r in reqs])
    admitted = [a for s in steps for a in s["admitted"]]
    assert sorted(a[0] for a in admitted) == sorted(life)  # once each
    assert sum(f["preemptions"] for f in life.values()) == \
        core.metrics["preemptions"]
    for a in admitted:
        f = life[a[0]]
        assert a[2] == f["prompt_tokens"] == 21  # not the folded prompt
        assert f["t_admitted"] - f["t_received"] == pytest.approx(a[1], abs=1e-3)


def test_an_abort_between_steps_lands_in_the_next_record(parts):
    core = make_core(parts)
    keep, gone = request(b"stays", n=4), request(b"goes away", n=40)
    core.submit(keep)
    core.submit(gone)
    core.step()
    assert core.abort(gone.request_id)
    core.run_until_idle()
    life = check_lifecycle(core.flight.snapshot(),
                           [keep.request_id, gone.request_id])
    assert life[gone.request_id]["reason"] == "aborted"


def test_with_the_recorder_off_nothing_is_built(parts):
    core = make_core(parts, flight_recorder_steps=0)
    req = request(b"off")
    core.submit(req)
    core.step()
    assert core._open is None
    core.run_until_idle()
    assert req.finish_reason is not None and req.lifecycle is None
    assert req.max_emit_gap_s == 0.0 and req.last_emit_time is None
    assert core._admitted_log == [] and core._finished_log == []
    assert len(core.flight) == 0
    # No dispatch was numbered, stamped or waited on, no request marked.
    assert core.flight.dispatches is None and core._emitting is None
    assert req.rode_mark is None and req.rode_tokens is None


def test_the_first_write_reaches_the_record_whoever_comes_first(parts):
    """The handler thread stamps the first write while the engine thread
    retires the request: whichever order the two take, the record in the
    ring ends up with the stamp (time-bounded; more threads than cores
    would add nothing: the race has two sides)."""
    import sys
    import threading

    core = make_core(parts)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    deadline = time.monotonic() + 2.0
    rounds = 0
    try:
        while time.monotonic() < deadline and rounds < 3000:
            req = request(b"x")
            writer = threading.Thread(target=req.mark_first_write, args=(7.0,))
            writer.start()
            core._retire(req, time.monotonic())
            writer.join(timeout=5)
            assert not writer.is_alive()
            assert req.lifecycle["t_first_write"] == 7.0
            rounds += 1
    finally:
        sys.setswitchinterval(old)
    assert rounds > 100 and len(core._finished_log) == rounds


# ---- the dispatch as a span ---------------------------------------------------

DECODE_PROGRAMS = ("_decode_step", "_decode_multi", "_decode_spec")


def serve(core: EngineCore, arrivals, **sampling) -> list:
    """``arrivals`` = (prompt bytes, max_new_tokens, joins late) through
    the core's ``AsyncEngine``: a late one is submitted once a request is
    decoding, so that its prompt meets a running batch. Returns the
    outputs, in order."""
    import asyncio

    from runbookai_tpu.engine.async_engine import AsyncEngine

    sampling.setdefault("stop_token_ids", ())
    sampling.setdefault("temperature", 0.0)

    async def every() -> list:
        engine = AsyncEngine(core)

        async def one(text: bytes, n: int, late: bool):
            while late and not core.decoding:
                await asyncio.sleep(0.001)
            return await engine.generate(list(text), SamplingParams(
                max_new_tokens=n, **sampling))

        outs = await asyncio.gather(*(one(*a) for a in arrivals))
        await engine.stop()
        return outs

    return asyncio.run(every())


def check_dispatches(core: EngineCore, steps: list[dict]) -> list[dict]:
    """Every dispatch the engine issued is in exactly one record, by
    number and ready in that order; the ledger's sums are the sums of the
    intervals the definition gives; every unpreempted request's ``rode``
    adds up to its decoding time and its tokens."""
    ledger = core.flight.dispatches
    listed = [d for s in steps for d in s["dispatches"]]
    assert [d["n"] for d in listed] == list(range(ledger.n))
    assert not ledger.in_flight and not ledger.log
    # What a record says it ISSUED (``program``), over all records, is
    # what the records list as having come BACK, in the same order.
    assert [p for s in steps for p in s["program"]] == [d["program"] for d in listed]
    for d in listed:
        assert tuple(d) == DISPATCH_FIELDS
        assert d["t_issued"] <= d["t_ready"]
        owner = [s for s in steps if d in s["dispatches"]]
        assert len(owner) == 1 and d["t_ready"] <= owner[0]["t_end"]
        assert (d["k"] > 0) == (d["rows"] > 0) == (d["kv_pages_live"] > 0)
        assert (d["program"] in DECODE_PROGRAMS) == (d["prefill_tokens"] == 0)
    ready = [d["t_ready"] for d in listed]
    assert ready == sorted(ready)
    life = [f for s in steps for f in s["finished"]]
    assert sum(d["tokens"] for d in listed) == sum(f["generated"] for f in life)
    # The definition, written out: each dispatch's interval and the time
    # before it, from the stamps alone.
    seconds: dict[str, float] = {}
    between, t_prev = 0.0, None
    for d in listed:
        if t_prev is not None:
            start = max(d["t_issued"], t_prev)
            between += start - t_prev
            seconds[d["program"]] = seconds.get(d["program"], 0.0) + d["t_ready"] - start
        t_prev = d["t_ready"]
    # The ledger's sums are those, and the first dispatch's own interval:
    # issued after the ledger's start, so all of issue-to-ready.
    first = listed[0]
    seconds[first["program"]] = (seconds.get(first["program"], 0.0)
                                 + first["t_ready"] - first["t_issued"])
    assert ledger.seconds == pytest.approx(seconds, abs=1e-9)
    assert between <= ledger.between  # (and the time before the first)
    assert ledger.count == {p: sum(d["program"] == p for d in listed)
                            for p in seconds}
    for f in life:
        if f["t_first_token"] is None:
            assert f["rode"] is None
            continue
        rode = dict(f["rode"])
        idle = rode.pop("between")
        assert idle >= 0.0 and all(
            n >= 0 and tokens >= 0 and s >= 0.0 for n, tokens, s in rode.values())
        if f["preemptions"]:
            continue  # marked: its interval holds time it did not ride
        assert sum(s for _, _, s in rode.values()) + idle == pytest.approx(
            f["t_finished"] - f["t_first_token"], abs=1e-6)
        assert sum(tokens for _, tokens, _ in rode.values()) == f["generated"] - 1
        # A program it has tokens of, it waited behind.
        assert all(s > 0.0 for _, tokens, s in rode.values() if tokens)
    return listed


KINDS = {
    # kind: (engine settings, arrivals, sampling, a program it must run)
    "decode_multi": (dict(decode_steps_per_dispatch=4, mixed_dispatch=False),
                     [(b"a first prompt", 14, False), (b"second", 9, False),
                      (b"a late one, past a chunk of eight", 6, True)],
                     {}, "_decode_multi"),
    "decode_step": (dict(decode_steps_per_dispatch=1, mixed_dispatch=False),
                    [(b"one token a dispatch", 7, False), (b"x", 5, True)],
                    {}, "_decode_step"),
    "logprobs": (dict(decode_steps_per_dispatch=4, mixed_dispatch=False),
                 [(b"scored tokens", 6, False), (b"and more", 5, False)],
                 {"logprobs": 2}, "_decode_step"),
    "mixed": (dict(decode_steps_per_dispatch=2, mixed_dispatch=True),
              [(b"the batch that runs", 24, False),
               (b"a prompt of three chunks rides along", 6, True),
               (b"short", 5, True)],
              {}, "_mixed_step"),
    "spec": (dict(decode_steps_per_dispatch=4, mixed_dispatch=False,
                  speculative=True, spec_ngram=1),
             [(b"restart the api service; restart the api service; restart",
               24, False)],
             {}, "_decode_spec"),
}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_dispatch_is_a_span_and_every_request_says_what_it_rode(parts, kind):
    settings, arrivals, sampling, program = KINDS[kind]
    core = make_core(parts, **settings)
    outs = serve(core, arrivals, **sampling)
    assert [len(o.token_ids) for o in outs] == [n for _, n, _ in arrivals]
    steps = core.flight.snapshot()
    check_steps(steps)
    listed = check_dispatches(core, steps)
    programs = {d["program"] for d in listed}
    assert program in programs and "_prefill_step" in programs
    life = {f["generated"]: f for s in steps for f in s["finished"]}
    for _, n, _ in arrivals:
        rode = life[n]["rode"]
        # Its tokens after the first came from the decode side (a mixed
        # step's decode rows included), and it waited behind the program
        # of this case unless it was gone before one ran.
        assert sum(rode.get(p, (0, 0, 0.0))[1]
                   for p in DECODE_PROGRAMS + ("_mixed_step",)) == n - 1
    assert any(program in f["rode"] for f in life.values())
    if kind == "mixed":
        # A prompt that ended in a mixed step had its first token fetched
        # in the step that issued it, ahead of the window in flight: that
        # window was waited for first and has a stamp of its own.
        mixed = [d for d in listed if d["program"] == "_mixed_step"]
        assert sum(d["prefill_tokens"] for d in mixed) > 0
        assert sum(d["tokens"] > d["rows"] for d in mixed) >= 1
        late = life[6]["rode"]
        assert "_mixed_step" in late or "_decode_multi" in late
    if kind == "logprobs":
        assert all(len(o.logprobs) == len(o.token_ids) for o in outs)


def test_under_the_pipeline_a_record_lists_the_dispatch_its_fetch_waited_for(parts):
    """A step issues dispatch n + 1 and fetches dispatch n: its record
    names the first under ``program`` and lists the second, whose ready
    stamp lies inside the step, in its fetch."""
    core = make_core(parts, decode_steps_per_dispatch=2, mixed_dispatch=False)
    core.submit(request(b"pipelined", n=17))
    core.run_until_idle()
    steps = core.flight.snapshot()
    check_dispatches(core, steps)
    issued_at, n = {}, 0
    for s in steps:
        for _ in s["program"]:
            issued_at[n] = s["step"]
            n += 1
    lagged = 0
    for s in steps:
        for d in s["dispatches"]:
            assert s["t_start"] <= d["t_ready"] <= s["t_end"]
            if d["program"] == "_decode_multi" and s["program"]:
                assert issued_at[d["n"]] == s["step"] - 1
                assert issued_at[d["n"] + 1] == s["step"]
                assert s["phases"]["fetch"] > 0.0
                lagged += 1
    assert lagged >= 5


def test_a_dispatch_of_rounds_books_what_was_drafted_and_accepted():
    from runbookai_tpu.models import hf_loader

    params = hf_loader.load_or_init("joyai-test", None, seed=11,
                                    dtype=jnp.float32)[1]
    core = EngineCore(CONFIGS["joyai-test"], params, ByteTokenizer(), EngineConfig(
        page_size=16, num_pages=128, max_batch_slots=4, prefill_chunk=32,
        max_seq_len=512, block_pages=2, speculative=True, kv_dtype=jnp.float32,
        decode_steps_per_dispatch=4, mixed_dispatch=False,
        flight_recorder_steps=256), seed=0)
    serve(core, [(bytes(range(40, 90)), 21, False), (bytes(range(60, 101)), 13, False)])
    steps = core.flight.snapshot()
    listed = check_dispatches(core, steps)
    rounds = [(s, d) for s in steps for d in s["dispatches"]
              if d["program"] == "_decode_spec"]
    assert len(rounds) >= 2
    for s, d in rounds:
        # No overlap under rounds: issued and fetched in the same step.
        assert s["spec"]["drafted"] + s["spec"]["accepted"] == d["tokens"]
        assert d["k"] == s["spec"]["rounds"] and d["rows"] == s["spec"]["rows"]
    for f in (f for s in steps for f in s["finished"]):
        assert f["rode"]["_decode_spec"][1] == f["generated"] - 1


# ---- the sampler's calls -------------------------------------------------------


def sampler_calls_of(listed: list[dict]) -> int:
    """What the dispatches in the records must have counted: ``k`` calls of
    the sampler for a ``_decode_multi`` of ``k`` passes, one for a
    ``_decode_step``, two for a mixed step (the decode rows', the
    prompts'), one for a prefill that gave first tokens, none for a
    ``_decode_spec`` (its rounds take the argmax)."""
    per = {"_decode_multi": lambda d: d["k"], "_decode_step": lambda d: 1,
           "_mixed_step": lambda d: 2, "_decode_spec": lambda d: 0,
           "_prefill_step": lambda d: 1 if d["tokens"] else 0}
    return sum(per[d["program"]](d) for d in listed)


@pytest.mark.parametrize("kind", ["decode_multi", "decode_step", "mixed", "spec"])
def test_the_sampler_counts_a_call_a_pass_and_sorts_for_no_greedy_row(parts, kind):
    settings, arrivals, sampling, program = KINDS[kind]
    core = make_core(parts, **settings)
    serve(core, arrivals, **sampling)
    steps = core.flight.snapshot()
    listed = check_dispatches(core, steps)
    assert program in {d["program"] for d in listed}
    m = core.metrics
    assert m["sampler_calls"] == sampler_calls_of(listed) > 0
    assert m["sampler_sorted_calls"] == 0
    assert sum(s["sampler"]["calls"] for s in steps) == m["sampler_calls"]
    assert all(s["sampler"]["sorted"] == 0 for s in steps)
    if kind == "decode_multi":
        k = settings["decode_steps_per_dispatch"]
        assert any(s["sampler"]["calls"] == k for s in steps
                   if s["program"] == ["_decode_multi"])
    if kind == "mixed":
        assert all(s["sampler"]["calls"] == 2 for s in steps
                   if s["program"] == ["_mixed_step"])


@pytest.mark.parametrize("mixed", [False, True], ids=["split", "mixed"])
def test_one_sampling_row_sends_its_calls_down_the_sorted_path(parts, mixed):
    """Greedy rows decode, a sampling request joins them and leaves: the
    calls with its row in them are counted sorted, those before and after
    are not, and the greedy rows' tokens are what they are without it."""
    def run(temperature):
        core = make_core(parts, decode_steps_per_dispatch=2,
                         mixed_dispatch=mixed)
        greedy = [request(b"a greedy row that stays", 30),
                  request(b"and another one", 30)]
        for r in greedy:
            core.submit(r)
        while not core.decoding:
            core.step()
        before = dict(core.metrics)
        joined = EngineRequest(prompt_ids=list(b"the row that samples"),
                               sampling=SamplingParams(
                                   temperature=temperature, top_p=0.9, seed=5,
                                   max_new_tokens=6, stop_token_ids=()))
        core.submit(joined)
        core.run_until_idle()
        return core, before, [r.all_out_ids for r in greedy]

    core, before, tokens = run(0.8)
    m, steps = core.metrics, core.flight.snapshot()
    assert before["sampler_calls"] > 0 and before["sampler_sorted_calls"] == 0
    assert 0 < m["sampler_sorted_calls"] < m["sampler_calls"] - before["sampler_calls"]
    for key, field in (("sampler_calls", "calls"), ("sampler_sorted_calls", "sorted")):
        assert sum(s["sampler"][field] for s in steps) == m[key]
    assert all(s["sampler"]["sorted"] in (0, s["sampler"]["calls"])
               or "_mixed_step" in s["program"] for s in steps)
    # The last dispatches ran without the sampling row: greedy again.
    assert steps[-1]["sampler"]["sorted"] == 0
    plain, _, plain_tokens = run(0.0)
    assert plain.metrics["sampler_sorted_calls"] == 0
    assert tokens == plain_tokens


def test_a_preempted_request_is_marked_and_the_rest_still_add_up(parts):
    core = make_core(parts, num_pages=20, max_batch_slots=2,
                     decode_steps_per_dispatch=1, admit_headroom_tokens=8)
    for ch in b"ab":
        core.submit(request(bytes([ch]) * 21, n=40))
    core.run_until_idle()
    steps = core.flight.snapshot()
    check_dispatches(core, steps)
    life = [f for s in steps for f in s["finished"]]
    assert any(f["preemptions"] for f in life)
    for f in life:
        # Preempted or not, the seconds tile the interval; what a
        # preempted one was given on the way back (its prompt and tokens
        # computed again, a prefill's token) is counted to it too.
        rode = dict(f["rode"])
        idle = rode.pop("between")
        assert sum(s for _, _, s in rode.values()) + idle == pytest.approx(
            f["t_finished"] - f["t_first_token"], abs=1e-6)
        assert sum(t for _, t, _ in rode.values()) == f["generated"] - 1
        if f["preemptions"]:
            assert rode.get("_prefill_step", (0, 0, 0.0))[1] >= 1


def test_a_ring_reset_restarts_the_ledger(parts):
    core = make_core(parts)
    core.submit(request(b"before"))
    core.run_until_idle()
    assert core.flight.dispatches.n > 0
    core.reset_metrics()
    assert core.flight.dispatches.n == 0 and not core.flight.dispatches.count
    core.submit(request(b"after the reset"))
    core.run_until_idle()
    assert check_dispatches(core, core.flight.snapshot())[0]["n"] == 0


def test_a_dispatch_in_flight_across_a_reset_is_of_no_ledger(parts):
    """``reset()`` swaps the ledger while a window may be in flight: its
    drain then stamps, books and logs nothing in the NEW ledger, whose
    numbers start at 0 and whose sums hold its own dispatches only."""
    core = make_core(parts, decode_steps_per_dispatch=2, mixed_dispatch=False)
    core.submit(request(b"over a reset", n=15))
    while core._pending is None:
        core.step()
    old, stale = core.flight.dispatches, core._pending.dispatch
    assert stale["t_issued"] is not None and stale["t_ready"] is None
    core.flight.reset()
    ledger = core.flight.dispatches
    assert ledger is not old and not ledger.mine(stale)
    core.run_until_idle()
    steps = core.flight.snapshot()
    listed = [d for s in steps for d in s["dispatches"]]
    assert listed and [d["n"] for d in listed] == list(range(ledger.n))
    assert stale["t_ready"] is None and all(d is not stale for d in listed)
    assert sum(ledger.count.values()) == len(listed)
    assert sum(ledger.seconds.values()) + ledger.between == pytest.approx(
        ledger.t_ready - ledger.t0, abs=1e-9)
    assert not ledger.in_flight and not ledger.log
    # The request's mark was of the ledger that went: no ``rode``.
    (life,) = [f for s in steps for f in s["finished"]]
    assert life["generated"] == 15 and life["rode"] is None


def test_a_chunk_that_emits_nothing_holds_one_element_of_its_result(parts):
    """A prefill chunk short of its prompt's end is waited on by a later
    fetch; until then the ledger keeps one element of its result alive,
    not ``[rows, vocab]`` logits a chunk of a long prompt."""
    core = make_core(parts, mixed_dispatch=False)
    core.submit(request(b"a prompt of more than three chunks of eight", n=3))
    core.step()
    core.step()
    waiting = core.flight.dispatches.in_flight
    assert [(e["program"], emits) for e, _, emits in waiting] == [
        ("_prefill_step", False)] * 2
    assert all(result.shape == (1, 1) for _, result, _ in waiting)
    core.run_until_idle()
    listed = check_dispatches(core, core.flight.snapshot())
    assert sum(d["program"] == "_prefill_step" and not d["tokens"]
               for d in listed) >= 3


# ---- through the front door --------------------------------------------------


@pytest.fixture(scope="module")
def server():
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.server.openai_api import OpenAIServer

    client = JaxTpuClient.for_testing(max_new_tokens=6)
    srv = OpenAIServer(client, model_name="llama3-test", port=0)
    srv.start_background()
    yield srv
    srv.shutdown()


def chat(srv, content: str, stream: bool, rid: str) -> bytes:
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/chat/completions",
        data=json.dumps({"messages": [{"role": "user", "content": content}],
                         "max_tokens": 5, "stream": stream}).encode(),
        headers={"Content-Type": "application/json", "x-request-id": rid},
        method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.read()


def test_the_front_door_stamps_both_ends(server):
    t_before = time.monotonic()
    assert b"[DONE]" in chat(server, "stream me", True, "rid-streamed")
    chat(server, "all at once", False, "rid-whole")
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/debug/steps?n=512", timeout=60) as r:
        steps = json.loads(r.read())["steps"]
    check_steps(steps)
    by_trace = {f["trace_id"]: f for s in steps for f in s["finished"]}
    streamed, whole = by_trace["rid-streamed"], by_trace["rid-whole"]
    waits = {a[0]: a[1] for s in steps for a in s["admitted"]}
    for f in (streamed, whole):
        stamps = [f[k] for k in ORDER]
        assert stamps == sorted(stamps) and stamps[0] >= t_before
        # The handler's start, carried across the thread hop by context:
        # before the EngineRequest was made (its arrival_time, from which
        # the queue wait counts), with parse, template and tokenise between.
        arrival = f["t_admitted"] - waits[f["id"]]
        assert f["t_received"] < arrival <= f["t_enqueued"] + 1e-3
    assert streamed["t_first_write"] >= streamed["t_first_token"]
    assert whole["t_first_write"] is None
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/healthz", timeout=60) as r:
        health = json.loads(r.read())["metrics"]
    assert health["compiles"] >= 0 and health["compile_time_s"] >= 0.0


async def test_the_fleets_debug_steps_keep_the_new_fields():
    from runbookai_tpu.model.jax_tpu import JaxTpuClient

    client = JaxTpuClient.for_testing(max_new_tokens=6, dp_replicas=2)
    fleet = client.engine
    sp = SamplingParams(temperature=0.0, max_new_tokens=6, stop_token_ids=())
    # As the HTTP handler does it: the stamp travels by context, through
    # the fleet's routing and the replica's AsyncEngine, by no keyword.
    t_received = time.monotonic()
    origin = request_origin.set(RequestOrigin(t_received=t_received))
    outs = [await fleet.generate(list(text), sp, request_id=rid)
            for text, rid in ((b"the quick brown fox", "rid-a"),
                              (b"zebra stripes xyz", "rid-b"))]
    request_origin.reset(origin)
    assert request(b"after the handler").t_received is None
    steps = fleet.debug_steps()["steps"]
    await fleet.stop()
    assert all(o.token_ids for o in outs)
    assert {s["replica"] for s in steps} <= {0, 1}
    for s in steps:
        assert set(NEW_FIELDS) <= set(s)
    finished = {f["trace_id"]: f for s in steps for f in s["finished"]}
    assert set(finished) == {"rid-a", "rid-b"}
    assert all(f["t_received"] == t_received for f in finished.values())


def test_the_front_door_exports_the_samplers_counters(server):
    chat(server, "count my passes", False, "rid-sampler")
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/healthz", timeout=30) as r:
        health = json.loads(r.read())["metrics"]
    assert health["sampler_calls"] > 0 and health["sampler_sorted_calls"] == 0
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=30) as r:
        text = r.read().decode()
    # (The registry is the process's: its callbacks read the engine built
    # last, which other tests of this module may have made.)
    for name in ("runbook_sampler_calls_total", "runbook_sampler_sorted_calls_total"):
        assert f"# TYPE {name} counter" in text
        assert any(ln.split()[0] == name for ln in text.splitlines() if ln)


def test_the_tracer_is_on_the_shared_clock(tmp_path):
    """``t0`` = time.monotonic() at the START of a span or event, beside
    the wall-clock ``ts`` taken at its close; the timeline orders by it,
    and a file from before ``t0`` still reads."""
    from runbookai_tpu.utils.timeline import build_timeline
    from runbookai_tpu.utils.trace import Tracer, read_spans, summarize_spans

    tracer = Tracer(tmp_path / "t.jsonl")
    before = time.monotonic()
    with tracer.span("engine.decode", requests=["r1"]):
        tracer.event("engine.enqueue", request="r1", prompt_tokens=3)
        time.sleep(0.01)
    after = time.monotonic()
    tracer.event("engine.request", request="r1", reason="stop_token",
                 generated=2)
    tracer.close()
    spans = read_spans(tmp_path / "t.jsonl")
    by_name = {r["name"]: r for r in spans}
    outer, inner = by_name["engine.decode"], by_name["engine.enqueue"]
    assert before <= outer["t0"] <= inner["t0"] <= after
    assert outer["ms"] >= 10.0 and outer["t0"] + outer["ms"] / 1e3 <= after + 1e-3
    assert all({"ts", "t0", "name", "depth", "ms"} <= set(r) for r in spans)
    # Written at close, the span comes AFTER the event inside it in the
    # file; by t0 it comes first.
    assert [r["name"] for r in spans][:2] == ["engine.enqueue", "engine.decode"]
    names = [e["name"] for e in build_timeline(spans, "r1")["events"]]
    assert names == ["engine.decode", "engine.enqueue", "engine.request"]
    old = [{k: v for k, v in r.items() if k != "t0"} for r in spans]
    assert build_timeline(old, "r1")["finish"]["generated"] == 2
    assert summarize_spans(old) == summarize_spans(spans)
