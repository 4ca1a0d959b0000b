"""The benchmark's own tests (CPU, tier-1): the contract of
``BENCHMARK.json``, the data files each cell names, the generators, the
metric arithmetic, the trace reduction on a recorded trace, the kernels'
byte counts, the plain reference against the program's training forward,
the control of ``correct``, and the harness end to end on the tiny presets.

No TPU topology is described here, at import or later.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import blocks

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
LAYER_METRICS = [m["name"] for m in BENCH["per_layer"]]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}


def _load(path: Path) -> dict:
    return json.loads(path.read_text())


# ---- BENCHMARK.json against the contract ----------------------------------

def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16 and len(BENCH["command"]) <= 32
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 4)
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]


@pytest.mark.parametrize("section,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_just_the_contract_keys(section, keys):
    names = [e["name"] for e in BENCH[section]]
    assert len(set(names)) == len(names)
    for e in BENCH[section]:
        optional = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert keys <= set(e) <= keys | optional, e["name"]
        assert NAME.match(e["name"]), e["name"]
        for text in (e.get("why"), e.get("layer"), e.get("source")):
            assert text is None or (1 <= len(text) <= 200 and "\n" not in text
                                    and "\t" not in text)
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        if "bound" in e:
            assert 0.01 <= e["bound"] <= 0.1
        for w in e.get("workloads", []):
            assert w in CELLS


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_its_files(cell):
    w = {w["name"]: w for w in BENCH["workloads"]}[cell]
    assert w["chips"] in (1, 4) and NAME.match(w["config"]) and NAME.match(w["traffic"])
    config = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    assert (ROOT / config["file"]).is_file()
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    traffic = _load(ROOT / "benchmark" / "traffic" / f"{w['traffic']}.json")
    importlib.import_module(f"benchmark.generators.{traffic['generator']}")
    if traffic["generator"] == "open_loop":
        extra = _load(ROOT / "benchmark" / "cells" / f"{cell}.json")
        assert isinstance(extra["rate_rps"], (int, float)) and extra["rate_rps"] > 0
    e2e = [m for m in BENCH["end_to_end"] if cell in m.get("workloads", [cell])]
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert any(cell in m.get("workloads", [cell]) for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_layer_metric_is_a_file_that_agrees_with_its_entry(metric):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[metric]
    from benchmark.layer_metrics._common import load_metric_file

    mod = load_metric_file(ROOT / "benchmark" / "layer_metrics" / f"{metric}.py")
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"], entry["source"])
    moved = {m["name"]: m for m in BENCH["end_to_end"]}[entry["moves"]]
    for cell in entry.get("workloads", CELLS):  # each reports what it moves
        assert cell in moved.get("workloads", CELLS)
    assert entry["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
    if metric.endswith("_roofline"):
        assert entry["unit"] == "%"
    assert callable(mod.read)


def test_every_metric_file_has_an_entry():
    files = {p.stem for p in (ROOT / "benchmark" / "layer_metrics").glob("*.py")
             if not p.stem.startswith("_")}
    assert files == set(LAYER_METRICS)


@pytest.mark.parametrize("config", CONFIGS)
def test_configuration_equals_the_programs_outside_reduced(config):
    from benchmark import serving
    from runbookai_tpu.models.llama import CONFIGS as PROGRAM

    entry = {c["name"]: c for c in BENCH["configs"]}[config]
    data = _load(ROOT / entry["file"])
    assert data["reduced"] == entry["reduced"] and data["source"] == entry["source"]
    built = serving.model_config(data)
    base = PROGRAM[data["base"]]
    for key in serving.reference_cfg(base):
        if key not in data["reduced"] + ["name"]:
            assert getattr(built, key) == getattr(base, key), key
    assert built.name == config and data["block"] in blocks.names()


def _cut(**changes):
    """The first configuration's file with ``changes`` made and listed
    under ``reduced`` (what a cut to one chip's share writes)."""
    data = _load(ROOT / BENCH["configs"][0]["file"])
    return dict(data, reduced=list(changes), **changes)


@pytest.mark.parametrize("changes", [
    {"n_layers": 4}, {"n_experts": 8}, {"vocab_size": 152064 // 8},
    {"n_layers": 5, "n_experts": 16, "vocab_size": 19008}])
def test_reduced_takes_depth_experts_held_and_a_vocabulary_slice(changes):
    from benchmark import serving

    built = serving.model_config(_cut(**changes))
    assert {k: getattr(built, k) for k in changes} == changes
    assert built.dim == 3584 and built.ffn_dim == 18944  # every width as published


@pytest.mark.parametrize("changes,why", [
    ({"ffn_dim": 9472}, "never a width"), ({"dim": 1792}, "never a width"),
    ({"n_kv_heads": 2}, "never a width"), ({"top_k_experts": 1}, "never a width"),
    ({"n_experts": 7}, "fewer than 8 experts"), ({"n_experts": 0}, "fewer than 8 experts"),
    ({"vocab_size": 152064 // 8 - 1}, "under an eighth")])
def test_reduced_refuses_a_width_and_a_cut_under_the_floors(changes, why):
    from benchmark import serving

    with pytest.raises(ValueError, match=why):
        serving.model_config(_cut(**changes))
    with pytest.raises(ValueError, match=why):  # as the file is read: a rehearsal too
        serving.model_config(_cut(**changes), rehearsal=True)


@pytest.mark.parametrize("key", ["ffn_dim", "dim", "n_kv_heads", "vocab_size"])
def test_a_changed_width_is_refused(key):
    from benchmark import serving

    data = _load(ROOT / BENCH["configs"][0]["file"])
    with pytest.raises(ValueError):
        serving.model_config(dict(data, **{key: data[key] // 2}))


def test_the_models_sizes_are_the_fields_of_the_programs_dataclass(monkeypatch):
    """A field no earlier configuration had reaches the program's
    configuration and the reference's, with no table of keys in between."""
    import dataclasses

    from benchmark import serving
    from runbookai_tpu.models.llama import CONFIGS as PROGRAM

    @dataclasses.dataclass(frozen=True)
    class Latent:
        name: str
        vocab_size: int
        n_layers: int
        kv_lora_rank: int
        family: str = "latent"

    monkeypatch.setitem(PROGRAM, "latent-published", Latent("latent-published", 131072, 28, 512))
    data = {"name": "latent-L4", "base": "latent-published", "block": "dense",
            "vocab_size": 131072, "n_layers": 4, "kv_lora_rank": 512,
            "reduced": ["n_layers"], "llm": {"num_pages": 8}, "source": "a paper"}
    built = serving.model_config(data)
    assert isinstance(built, Latent) and built.kv_lora_rank == 512 and built.n_layers == 4
    assert serving.reference_cfg(built) == {
        "name": "latent-L4", "vocab_size": 131072, "n_layers": 4, "kv_lora_rank": 512,
        "family": "latent"}
    with pytest.raises(ValueError, match="kv_lora_rank=256 differs"):
        serving.model_config(dict(data, kv_lora_rank=256))
    with pytest.raises(ValueError, match="never a width"):
        serving.model_config(dict(data, kv_lora_rank=256, reduced=["kv_lora_rank"]))


# ---- generators -----------------------------------------------------------

@pytest.mark.parametrize("seeds", [(2**31 + 7, 2**31 + 8), (1, 2), (0, 2**31 + 1000)])
def test_generator_is_a_pure_function_of_the_seed(seeds):
    from benchmark import generators

    params = _load(ROOT / "benchmark" / "traffic" / "chat-open.json")
    gen = generators.load(params["generator"])
    cell = {"rate_rps": 2.0}
    a, b = gen.plan(params, cell, seeds[0], 20.0), gen.plan(params, cell, seeds[0], 20.0)
    c = gen.plan(params, cell, seeds[1], 20.0)
    assert json.dumps(a) == json.dumps(b) and json.dumps(a) != json.dumps(c)
    # Every seed: the same sizes at the same instants in the same order,
    # with other text.
    shape = lambda plan: [(r["due_s"], r["max_tokens"], len(r["messages"][-1]["content"]))  # noqa: E731
                          for r in plan["requests"]]
    assert shape(a) == shape(c)
    for x, y in zip(a["requests"], c["requests"]):
        text = x["messages"][-1]["content"]
        assert text != y["messages"][-1]["content"]
        assert text.isascii() and text.isprintable()


def test_open_loop_fills_the_window_at_its_rate():
    from benchmark.generators import open_loop

    params = _load(ROOT / "benchmark" / "traffic" / "chat-open.json")
    plan = open_loop.plan(params, {"rate_rps": 2.0}, 5, 30.0)
    dues = [r["due_s"] for r in plan["requests"]]
    assert len(dues) == 60 and dues == sorted(dues) and 0 < dues[0] and dues[-1] < 30.0
    lens = [len(r["messages"][-1]["content"]) for r in plan["requests"]]
    assert min(lens) >= 64 and max(lens) <= 2048 and 300 < sorted(lens)[30] < 480
    outs = sorted(r["max_tokens"] for r in plan["requests"])
    assert outs[0] >= 16 and outs[-1] <= 384 and 70 <= outs[30] <= 90


def test_warmup_bursts_are_due_together():
    import random

    from benchmark import generators

    params = _load(ROOT / "benchmark" / "traffic" / "chat-open.json")
    reqs = generators.burst_requests(params["warmup"]["bursts"], params["system"],
                                     random.Random(3))
    by_due = {}
    for r in reqs:
        by_due.setdefault(r["due_s"], []).append(r)
    assert sorted(len(v) for v in by_due.values())[-1] == 8
    assert {r["n_choices"] for r in reqs} == {1, 2, 4}
    assert len({r["messages"][-1]["content"][:16] for r in reqs}) == len(reqs)


# ---- metric arithmetic ------------------------------------------------------

def _rec(due, sent, times, **kw):
    text = "x" * len(times)
    return {"kind": "req", "id": "r0", "due": due, "sent": sent,
            "end": (times[-1] if times else sent) + 0.01, "times": times,
            "text": text, "status": 200, "finish": "length", "prompt_tokens": 10,
            "completion_tokens": len(times), "done_marker": True,
            "terminated": True, "error": None, "max_tokens": 64, **kw}


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4, 5], 90, 4.6), ([7], 90, 7.0),
    ([0, 10], 90, 9.0), ([1, float("inf")], 50, float("inf")),
])
def test_percentile(values, q, want):
    from benchmark import metrics

    assert metrics.percentile(values, q) == pytest.approx(want)


def test_ttft_and_tpot_on_hand_made_records():
    from benchmark import metrics

    nine = [1.5 + 0.1 * i for i in range(9)]  # first token 1.5, last 2.3
    r = _rec(1.0, 1.2, nine)
    assert metrics.ttft_ms(r) == pytest.approx(500.0)  # from DUE, not from send
    assert metrics.tpot_ms(r) == pytest.approx(100.0)
    assert metrics.tpot_ms(_rec(0, 0, [1, 2, 3])) is None  # under 8 tokens
    failed = _rec(1.0, 1.2, nine, status=503, error="shed")
    assert metrics.ttft_ms(failed) == float("inf")
    late = _rec(0.0, 0.0, [9.9 + i for i in range(9)])  # ends after the window
    e2e = metrics.end_to_end([r, failed, late], t0=0.0, seconds=10.0)
    assert e2e["ttft_p90_ms"] == metrics.INF_MS  # one of three failed
    assert e2e["tpot_p50_ms"] == pytest.approx(1000.0)
    assert e2e["samples"] == {"ttft": 3, "tpot": 3, "completed_in_window": 1}


@pytest.mark.parametrize("change,why", [
    ({"status": 500, "error": "boom"}, "HTTP 500"),
    ({"done_marker": False}, "stream cut"),
    ({"completion_tokens": 5}, "tokens counted"),
    ({"max_tokens": 2}, "over max_tokens"),
    ({"text": "xy\U000352ff"}, None),  # one character a token, whatever its bytes
    ({"finish": "stop", "completion_tokens": 4}, None),  # the stop token carries no text
    ({}, None),
])
def test_what_counts_as_failed(change, why):
    from benchmark import metrics

    got = metrics.failure(_rec(0, 0, [1.0, 2.0, 3.0], **change))
    assert (got is None) if why is None else (why in got)


def test_histogram_quantile_from_bucket_deltas():
    from benchmark import metrics

    text = lambda a, b, c: (  # noqa: E731
        f'h_bucket{{le="0.1"}} {a}\nh_bucket{{le="1"}} {b}\nh_bucket{{le="+Inf"}} {c}\n')
    before = metrics.parse_histogram(text(5, 5, 5), "h")
    after = metrics.parse_histogram(text(5, 15, 15), "h")
    assert metrics.histogram_quantile(before, after, 0.9) == pytest.approx(0.1 + 0.9 * 0.9)
    assert metrics.histogram_quantile(before, before, 0.9) is None


# ---- trace reduction on a recorded trace -------------------------------------

def test_trace_reduce_on_the_recorded_trace():
    from benchmark import trace_reduce

    red = trace_reduce.reduce_trace(
        ROOT / "benchmark" / "testdata" / "small.xplane.pb", ["decode", "prefill"])
    assert red["devices"] == 1 and 0 < red["busy_s"] < red["window_s"]
    assert red["modules"]["jit__decode_multi"]["count"] == 3
    assert red["modules"]["jit__prefill_step"]["count"] == 3
    assert red["host_spans"]["decode"]["count"] == 3
    assert sum(t["seconds"] for t in red["ops"].values()) == pytest.approx(
        red["busy_s"], rel=0.05)
    gaps = sum(red["idle_gaps"].values())
    assert gaps == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    bd = trace_reduce.breakdown(red)
    assert 1 <= len(bd["device_ops"]) <= 10 and 1 <= len(bd["idle_gaps"]) <= 10


def test_union_and_base_name():
    from benchmark import trace_reduce

    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.base_name("jit__decode_multi(123)") == "jit__decode_multi"
    op = "%fusion.2 = bf16[16,8]{1,0:T(8,128)(2,1)} fusion(bf16[8]{0} %x), kind=kLoop"
    assert trace_reduce.base_name(op) == "%fusion.2 = bf16[16,8] fusion(bf16[8] %x), kind=kLoop"


# ---- kernels' bytes against hand arithmetic ----------------------------------

@pytest.mark.parametrize("k,n", [(3584, 3584), (3584, 512), (3584, 18944), (18944, 3584)])
def test_the_four_qwen_matrices_and_their_feed_copies(k, n):
    """The kernel's call is read by its shapes; the copy that fed it until
    PR 30 (its reader went with PR 44) is never taken for a call."""
    from benchmark.kernels import qmm_pallas

    qwen = _load(ROOT / "benchmark" / "configs" / "qwen2.5-7b-int8.json")
    d, f, kv = qwen["dim"], qwen["ffn_dim"], qwen["n_kv_heads"] * qwen["dim"] // qwen["n_heads"]
    assert (k, n) in [(d, d), (d, kv), (d, f), (f, d)]
    feed = f"%dynamic-slice_bitcast_fusion.3 = s8[{k},{n}] fusion(s8[28,{k},{n}] %gte.1, s32[] %gte.2)"
    assert qmm_pallas.shape_of(feed) is None
    for rows in (16, 128):
        op = f"%qmm_pallas.7 = bf16[{rows},{n}] custom-call(bf16[{rows},{k}] %x, s8[{k},{n}] %y)"
        assert qmm_pallas.shape_of(op) == (rows, k, n)


def test_decode_step_attention_and_resident_bytes():
    from benchmark.kernels import paged_attention_decode

    decode_step = blocks.load("dense").bytes

    qwen = _load(ROOT / "benchmark" / "configs" / "qwen2.5-7b-int8.json")
    layer = 3584 * 3584 * 2 + 2 * 3584 * 512 + 3 * 3584 * 18944
    head = 3584 * 152064 * 2
    assert decode_step.weight_bytes(qwen) == 28 * layer + head
    assert decode_step.kv_bytes(qwen, 1000) == 1000 * 57344  # 57,344 B a token
    assert decode_step.step_bytes(qwen, 1000) == 28 * layer + head + 1000 * 57344
    assert paged_attention_decode.bytes_per_call(1000, 4, 128) == 1000 * 2048
    assert decode_step.attention_bytes_per_call(qwen, 1000) == 1000 * 2048
    # Resident: matrices, embedding, head and the whole pool of 3072 pages.
    stated = decode_step.resident_bytes(qwen, qwen["llm"], qwen["precision"])
    assert stated == 28 * layer + 2 * head + 3072 * 16 * 57344
    assert 11.4e9 < stated < 11.6e9
    fp8 = dict(qwen["precision"], kv_bytes=1)
    assert stated - decode_step.resident_bytes(qwen, qwen["llm"], fp8) == 3072 * 16 * 57344 // 2


QMM_OP = ("%qmm_pallas.82 = bf16[16,18944] custom-call(bf16[16,3584] %fusion.151, "
          "s8[3584,18944] %dynamic-slice_bitcast_fusion.38, f32[1,18944] %d), "
          'custom_call_target="tpu_custom_call"')
FEED_OP = ("%dynamic-slice_bitcast_fusion.38 = s8[3584,18944] fusion(s8[28,3584,18944] "
           "%get-tuple-element.2149, s32[] %get-tuple-element.2105), kind=kLoop")
ATTN_OP = ("%closed_call.16 = bf16[16,28,128] custom-call(s32[16,513] %copy-done, s32[16] "
           "%copy-done.8, bf16[16,28,128] %pad, bf16[3072,16,4,128] %bitcast.288)")
CHUNK_OP = ("%closed_call.12 = bf16[80,8,28,128] custom-call(s32[80,513] %gte.893, "
            "s32[80] %gte.894, s32[80] %s, bf16[80,8,28,128] %pad)")
VERIFY_OP = ("%closed_call.13 = bf16[16,8,28,128] custom-call(s32[16,513] %copy-done, s32[16] "
             "%copy-done.4, s32[16] %slice_bitcast_fusion.2, bf16[16,8,28,128] %pad)")


def test_kernel_patterns_on_the_traces_own_instruction_texts():
    """Texts as a v5e trace gave them (my chip run, PR 23), layouts removed."""
    from benchmark import trace_reduce
    from benchmark.kernels import paged_attention_decode, qmm_pallas
    from benchmark.layer_metrics import qmm_kernel_ms

    assert qmm_pallas.shape_of(QMM_OP) == (16, 3584, 18944)
    assert not qmm_pallas.PATTERN.search(FEED_OP)
    attn = paged_attention_decode.pattern(16)
    assert attn.search(ATTN_OP) and attn.search(VERIFY_OP) and not attn.search(CHUNK_OP)
    assert trace_reduce.own_name(QMM_OP) == "qmm_pallas"
    # 17 passes of 28 layers (2 runs of 8 steps and a single step): 476
    # calls of the kernel at 75 us; the feed's 91 us (a trace from before
    # PR 30) are no part of the kernel's time.
    qwen = _load(ROOT / "benchmark" / "configs" / "qwen2.5-7b-int8.json")
    run = {"peaks": {"hbm_bytes_per_s": 819e9}, "model": qwen, "llm": qwen["llm"],
           "block": blocks.load("dense"),
           "trace": {"modules": {"jit__decode_multi": {"count": 2, "seconds": 0.8},
                                 "jit__decode_step": {"count": 1, "seconds": 0.05}},
                     "ops": {QMM_OP: {"count": 476, "seconds": 476 * 75e-6},
                             FEED_OP: {"count": 476, "seconds": 476 * 91e-6},
                             ATTN_OP: {"count": 476, "seconds": 0.25}}}}
    assert qmm_kernel_ms.read(run) == pytest.approx(28 * 75e-3)
    assert qmm_kernel_ms.read({**run, "trace": None}) is None
    red = {"ops": {"%while.6 = (s32[]) while(...)": {"count": 1, "seconds": 9.0},
                   **run["trace"]["ops"]}, "idle_gaps": {"decode": 0.1}}
    assert [n for n, _ in trace_reduce.breakdown(red)["device_ops"]][0] == ATTN_OP


def _read_on_slice(metric, slice_name, block, rows=8, prompt_tokens=600):
    """``metric``'s reading of a recorded slice of the cell, with ``rows``
    requests of ``prompt_tokens`` decoding through the middle of it."""
    from benchmark.layer_metrics._common import load_metric_file

    qwen = _load(ROOT / "benchmark" / "configs" / "qwen2.5-7b-int8.json")
    trace = _load(ROOT / "benchmark" / "testdata" / "chat_open_slices.json")[slice_name]
    live = dict(_rec(0.0, 0.0, [1.0 + 0.1 * i for i in range(40)]),
                prompt_tokens=prompt_tokens)
    run = {"peaks": {"hbm_bytes_per_s": 819e9}, "model": qwen, "llm": qwen["llm"],
           "block": block, "trace": trace, "reqs": [live] * rows,
           "traced": {"t_start": 1.5, "t_stop": 4.5,
                      "health_start": {"metrics": {"prefill_tokens": 0}},
                      "health_stop": {"metrics": {"prefill_tokens": 4000}}}}
    return load_metric_file(ROOT / "benchmark" / "layer_metrics" / f"{metric}.py").read(run)


DEVICE_READERS = ["attn_decode_roofline", "decode_hbm_mfu", "prefill_dev_ms_per_ktok",
                  "qmm_kernel_ms"]


@pytest.mark.parametrize("slice_name", ["decode_spec_slice", "decode_multi_slice"])
@pytest.mark.parametrize("metric", DEVICE_READERS)
def test_device_readers_find_their_events_in_both_kinds_of_slice(metric, slice_name):
    """Whichever decode program a slice happened to hold — speculative
    verifies only, or multi-step decode only — every reader of the device
    trace has something to read (a declared metric missing from a traced
    run's line is refused), and no share passes 100%."""
    value = _read_on_slice(metric, slice_name, blocks.load("dense"))
    assert value is not None and value > 0
    if metric.endswith("_roofline") or "mfu" in metric.split("_"):
        assert value < 100


@pytest.mark.parametrize("slice_name,metric,parents", [
    ("decode_multi_slice", "decode_hbm_mfu", 25.7764493041407),
    ("decode_multi_slice", "attn_decode_roofline", 2.059266225381598),
    ("decode_spec_slice", "decode_hbm_mfu", 12.909504132861011),
    ("decode_spec_slice", "attn_decode_roofline", 1.4452421554771608),
])
def test_the_dense_blocks_shares_are_the_closed_formulas_to_the_last_digit(
        slice_name, metric, parents):
    """What the tree before the blocks read (``kernels/decode_step.py``,
    commit 2dee165) from the same slices, 7 live rows of 617 prompt tokens."""
    assert _read_on_slice(metric, slice_name, blocks.load("dense"), 7, 617) == parents


def test_peaks_table_has_the_chip_and_its_source():
    peaks = _load(ROOT / "benchmark" / "peaks.json")
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["bf16_flops"] == 197e12 and "Google Cloud" in peaks["source"]


# ---- the reference ------------------------------------------------------------

def _tiny(name):
    import dataclasses

    from benchmark import serving
    from runbookai_tpu.models.llama import CONFIGS as PROGRAM

    return PROGRAM[name], serving.reference_cfg(PROGRAM[name]), dataclasses


@pytest.mark.parametrize("preset,quantized", [("qwen2-test", True), ("qwen2-test", False)])
def test_reference_weights_are_the_programs_bit_for_bit(preset, quantized):
    import jax

    from runbookai_tpu.models.llama import init_params, init_params_quantized

    weights = blocks.load("dense").weights

    cfg, ref_cfg, _ = _tiny(preset)
    init = init_params_quantized if quantized else init_params
    theirs = init(jax.random.PRNGKey(2**31 - 5), cfg)
    ours = weights.make_params(ref_cfg, 2**31 - 5, quantized)
    assert jax.tree.structure(theirs) == jax.tree.structure(ours)
    for a, b in zip(jax.tree.leaves(theirs), jax.tree.leaves(ours)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a, np.float32),
                                                     np.asarray(b, np.float32))


@pytest.mark.parametrize("preset", ["qwen2-test"])
def test_reference_agrees_with_the_programs_training_forward(preset):
    import jax
    import jax.numpy as jnp

    from runbookai_tpu.models.llama import forward_train

    dense = blocks.load("dense")
    forward, weights = dense.forward, dense.weights
    cfg, ref_cfg, _ = _tiny(preset)
    params = weights.make_params(ref_cfg, 11, quantized=False, dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(32, 127, size=600).tolist()
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(forward_train(params, cfg, jnp.asarray([ids], jnp.int32))[0])
    ours = np.asarray(forward.logits(params, ref_cfg, ids, 600))
    # float32 both sides: what is left is the order of summation.
    assert np.abs(ours - theirs).max() < 2e-4


def _sound_and_control_gaps(preset, seed):
    """Widest logit gap of the program's own greedy picks (bf16, int8
    weights) and of the control's (the reference in fp8 where the
    configuration states bfloat16), at every position of one sequence,
    against the float32 reference."""
    import jax.numpy as jnp

    from runbookai_tpu.models.llama import forward_train

    dense = blocks.load("dense")
    forward, weights = dense.forward, dense.weights
    cfg, ref_cfg, _ = _tiny(preset)
    params = weights.make_params(ref_cfg, seed, quantized=True)
    ids = np.random.default_rng(seed).integers(0, cfg.vocab_size, size=512).tolist()
    ref = np.asarray(forward.logits(params, ref_cfg, ids, 512))
    served = np.asarray(forward_train(params, cfg, jnp.asarray([ids], jnp.int32))[0])
    low = np.asarray(forward.logits(params, ref_cfg, ids, 512, "fp8"))
    rows = np.arange(512)
    gap = lambda lg: float((ref.max(axis=1) - ref[rows, lg.argmax(axis=1)]).max())  # noqa: E731
    return gap(served), gap(low)


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 77])
def test_control_fails_the_limit_and_the_program_passes(seed):
    from benchmark.reference import check

    sound, control = _sound_and_control_gaps("qwen2-test", seed)
    limit = check.limits_for("qwen2-test")["logit_gap"]
    assert sound <= limit < control, (sound, limit, control)


@pytest.mark.parametrize("short,gap,ok", [(0, 0.05, True), (1, 0.05, False), (0, 0.2, False)])
def test_each_number_has_its_own_limit(short, gap, ok, monkeypatch):
    from benchmark.reference import check

    monkeypatch.setattr(check, "gaps_of", lambda *a: {
        "prompt_matches": True, "prompt_tokens": 3, "served_tokens": 2,
        "gaps": np.array([0.0, gap]), "not_comparable": 0, "control_gaps": {}})
    out = check.compare(None, {}, {}, [{"id": "r0"}], check.limits_for("qwen2.5-7b-int8"),
                        {"live_bytes": 100 - short, "stated_bytes": 100})
    assert out["ok"] is ok and out["resident_bytes_short"] == short
    assert out["logit_gap"] == pytest.approx(gap) and out["served_tokens"] == 2


def _compare_gaps(monkeypatch, limits, widest, mean, control_mean=None, n=1000):
    """``check.compare`` over one request of ``n`` served positions whose
    gaps have this widest and this mean (one position at the widest, the
    rest level), and a control whose gaps are the same shape at its mean."""
    from benchmark.reference import check

    def gaps(widest, mean):
        g = np.full(n, (mean * n - widest) / (n - 1))
        g[n // 2] = widest
        return g

    control = {} if control_mean is None else {"fp8": gaps(widest, control_mean)}
    monkeypatch.setattr(check, "gaps_of", lambda *a: {
        "prompt_matches": True, "prompt_tokens": 3, "served_tokens": n,
        "gaps": gaps(widest, mean), "not_comparable": 0, "control_gaps": control})
    return check.compare(None, {}, {}, [{"id": "r0"}],
                         {**check.limits_for("none-of-its-own"), **limits},
                         {"live_bytes": 1, "stated_bytes": 1}, "fp8" if control else None)


@pytest.mark.parametrize("limits,widest,mean,ok,decided_by", [
    # a limits file with the mean: PR 43's refused run passes, a control's mean fails
    ({"logit_gap_mean": 0.28}, 3.3, 0.09, True, ["logit_gap_mean"]),
    ({"logit_gap_mean": 0.28}, 3.3, 0.95, False, ["logit_gap_mean"]),
    ({"logit_gap_mean": 0.28}, 0.29, 0.285, False, ["logit_gap_mean"]),  # whatever the widest
    # ... and with the 99th percentile beside it: both are held
    ({"logit_gap_mean": 0.28, "logit_gap_p99": 1.0}, 3.3, 0.09, True,
     ["logit_gap_mean", "logit_gap_p99"]),
    ({"logit_gap_mean": 2.0, "logit_gap_p99": 1.0}, 3.3, 1.2, False,
     ["logit_gap_mean", "logit_gap_p99"]),
    # a limits file without it: the widest gap decides, as before PR 44
    ({"logit_gap": 2.5}, 3.3, 0.09, False, ["logit_gap"]),
    ({"logit_gap": 2.5}, 2.4, 0.09, True, ["logit_gap"]),
    ({}, 0.09, 0.01, True, ["logit_gap"]),  # the default's 0.1
    ({}, 0.11, 0.01, False, ["logit_gap"]),
])
def test_the_limits_choose_the_statistic_of_the_gaps_that_decides(
        limits, widest, mean, ok, decided_by, monkeypatch):
    out = _compare_gaps(monkeypatch, limits, widest, mean)
    assert out["ok"] is ok and out["decided_by"] == decided_by
    assert out["logit_gap"] == pytest.approx(widest) and out["logit_gap_mean"] == pytest.approx(mean)
    assert 0 < out["logit_gap_p99"] < widest  # one position in a thousand holds the widest
    # a limit beside each number that decides, and beside no other statistic
    assert {k for k in out if k.startswith("limit_logit_gap")} == {f"limit_{k}" for k in decided_by}


@pytest.mark.parametrize("limits,control_mean,control_ok", [
    ({"logit_gap_mean": 0.28}, 0.95, False),  # the fp8 reference's mean, ten times the sound one
    ({"logit_gap_mean": 0.28}, 0.2, True),    # a control that passes is seen to pass: by the mean,
    ({"logit_gap": 2.5}, 0.95, False),        # and by the widest gap where that decides (3.3)
    ({"logit_gap": 3.5}, 0.95, True),
])
def test_a_control_is_held_to_the_number_that_decides(limits, control_mean, control_ok,
                                                      monkeypatch):
    out = _compare_gaps(monkeypatch, limits, 3.3, 0.09, control_mean)
    control = out["control"]["fp8"]
    assert control["ok"] is control_ok
    assert control["logit_gap_mean"] == pytest.approx(control_mean)
    assert control["logit_gap"] == pytest.approx(3.3) and "logit_gap_p99" in control


@pytest.mark.parametrize("config", CONFIGS)
def test_which_statistic_decides_is_the_configurations_own_file(config):
    """A configuration whose own file holds the mean is decided by it, and
    its widest gap has no limit of that file's; the others' files hold the
    widest gap's limit, or none (the default's). No configuration's name is
    in ``check.py``."""
    from benchmark.reference import check

    own = ROOT / "benchmark" / "configs" / f"{config}.limits.json"
    held = _load(own) if own.is_file() else {}
    decides = check.deciding(check.limits_for(config))
    if "logit_gap_mean" in held:
        assert decides[0] == "logit_gap_mean" and "logit_gap" not in held
        assert "logit_gap_p99" in held["comment"]  # read, and given a limit or said why not
        assert 0.1 <= held["logit_gap_mean"] <= 0.6
    else:
        assert decides == ["logit_gap"] and "logit_gap_p99" not in held
    # the configuration PR 43 was refused in is decided by the mean
    assert ("logit_gap_mean" in held) or config != "qwen3-next-80b-ep4-bf16"
    source = (ROOT / "benchmark" / "reference" / "check.py").read_text()
    assert config not in source and config.split("-")[0] not in source


def test_choose_sample_holds_the_longest_and_is_seeded():
    from benchmark.reference import check

    reqs = [dict(_rec(0, 0, [1.0] * 8, id=f"s{i}", prompt_tokens=10 * i + 10),
                 messages=[{"role": "user", "content": "x"}]) for i in range(9)]
    reqs[4]["status"] = 503
    a, b = check.choose_sample(reqs, 4, 5), check.choose_sample(reqs, 4, 5)
    assert [r["id"] for r in a] == [r["id"] for r in b] and len(a) == 4
    assert a[0]["id"] == "s8" and "s4" not in [r["id"] for r in a]
    assert [r["id"] for r in check.choose_sample(reqs, 4, 6)] != [r["id"] for r in a]


def test_reference_templates_are_the_programs():
    from benchmark.reference import tokens
    from runbookai_tpu.model.chat_template import build_chat_prompt
    from runbookai_tpu.utils.tokens import ByteTokenizer

    msgs = [{"role": "system", "content": "sys"}, {"role": "user", "content": "u1"},
            {"role": "assistant", "content": "a1"}, {"role": "user", "content": "u2"}]
    want = ByteTokenizer().encode(build_chat_prompt(
        "sys", "u2", history=[("user", "u1"), ("assistant", "a1")], fmt="chatml"))
    assert tokens.prompt_ids(msgs, "qwen2") == want


@pytest.mark.parametrize("ids", [[72, 105], [0, 127, 128, 255, 256, 261, 262], [152063, 65, 0xD800, 0xDFFF],
                                 list(range(35000, 35400))])
def test_every_id_streams_as_one_character_and_comes_back(ids):
    """As ``stream_text`` decodes: incremental UTF-8 over each token's bytes."""
    import codecs

    from benchmark.reference import tokens

    decoder = codecs.getincrementaldecoder("utf-8")("replace")
    pieces = [decoder.decode(tokens.vocabulary_bytes(t)) for t in ids]
    assert all(len(p) == 1 for p in pieces) and decoder.decode(b"", final=True) == ""
    text = "".join(pieces)
    assert text == tokens.vocabulary_text(ids) and tokens.ids_of_text(text) == ids
    assert json.loads(json.dumps({"c": text}))["c"] == text  # survives the SSE's JSON


def test_an_unknown_block_or_family_names_what_the_directory_holds():
    from benchmark.reference import tokens

    held = sorted(p.name for p in (ROOT / "benchmark" / "blocks").iterdir()
                  if (p / "forward.py").is_file())
    assert blocks.names() == held and "dense" in held
    assert {_load(ROOT / c["file"])["block"] for c in BENCH["configs"]} <= set(held)
    with pytest.raises(SystemExit, match=rf"unknown block 'latent'.*{re.escape(str(held))}"):
        blocks.load("latent")
    templates = sorted(p.stem for p in (ROOT / "benchmark/reference/templates").glob("*.py"))
    assert "qwen2" in templates and "mistral" not in templates
    with pytest.raises(SystemExit, match=rf"no chat template for family 'mistral'.*"
                                         rf"{re.escape(str(templates))}"):
        tokens.prompt_ids([{"role": "user", "content": "x"}], "mistral")


@pytest.mark.parametrize("metric", ["decode_hbm_mfu", "attn_decode_roofline",
                                    "qmm_kernel_ms"])
def test_a_block_without_the_count_is_not_measured_against_anothers(metric):
    """On a slice every dense reader reads, a block that brings only its
    resident bytes gets no share of a roofline and no time per pass."""
    from types import SimpleNamespace

    bare = SimpleNamespace(bytes=SimpleNamespace(resident_bytes=lambda *a: 1))
    assert _read_on_slice(metric, "decode_multi_slice", bare) is None
    assert _read_on_slice(metric, "decode_multi_slice", blocks.load("dense")) > 0


@pytest.mark.parametrize("declared,share_limit,ok,gap", [
    (False, 0, False, 0.5),   # the wide gap is compared, and fails its limit
    (True, 0, False, 0.0),    # declared not comparable: over the default share of 0
    (True, 0.5, True, 0.0),   # inside a share the configuration's limits allow
])
def test_positions_a_block_declares_not_comparable(declared, share_limit, ok, gap):
    from types import SimpleNamespace

    from benchmark.reference import check, tokens

    served = [40, 41, 42]
    msgs = [{"role": "user", "content": "hi"}]

    def logits(params, cfg, ids, n_last, lowp=None):
        lg = np.zeros((n_last, 64), np.float32)
        lg[np.arange(n_last), served] = 1.0
        lg[1, 7] = 1.5  # position 1: another token half a logit above the served one
        skip = np.array([False, True, False])
        return (lg, skip) if declared else lg

    req = {"id": "r0", "messages": msgs, "text": tokens.vocabulary_text(served),
           "prompt_tokens": len(tokens.prompt_ids(msgs, "qwen2"))}
    out = check.compare(SimpleNamespace(forward=SimpleNamespace(logits=logits)), {},
                        {"family": "qwen2"}, [req],
                        dict(check.limits_for("none-of-its-own"), not_comparable_share=share_limit),
                        {"live_bytes": 1, "stated_bytes": 1}, "fp8")
    assert out["ok"] is ok and out["logit_gap"] == pytest.approx(gap)
    assert out["not_comparable"] == int(declared) and out["served_tokens"] == 3
    assert out["not_comparable_share"] == pytest.approx(declared / 3)
    assert out["limit_not_comparable_share"] == share_limit
    assert out["control"]["fp8"]["logit_gap"] == 0.0  # a control's tuple is taken apart too


# ---- the harness -----------------------------------------------------------------

def _run(args, cwd=ROOT, env=ENV, timeout=600):
    return subprocess.run([sys.executable, "-m", "benchmark.run", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=timeout)


def _json_lines(text):
    out = []
    for line in text.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and "nothing was run" in p.stderr
    assert not [j for j in _json_lines(p.stdout) if "correct" in j]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_end_to_end(cell):
    """The whole of a run on the tiny preset: build_server, the SSE format,
    the /healthz keys and the reference check, before they break a chip run."""
    p = _run(["--workload", cell, "--seed", "2147496001", "--seconds", "5",
              "--trace", "1", "--rehearse-cpu"])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = _json_lines(p.stdout)
    last = lines[-1]
    assert last["rehearsal"] and last["rehearsal_correct"] and "correct" not in last
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"decode_rows_mean", "loadgen_late_p90_ms", "prefix_hit_share",
            "queue_wait_p90_ms", "kv_preemptions", "client_ttft_p90_ms",
            "client_tpot_p90_ms"} <= set(last["metrics"])
    assert last["values"]["setup_s"] > 0 and last["values"]["tpot_p50_ms"] > 0
    assert last["metrics"]["client_ttft_p90_ms"]["value"] > 0
    assert (last["metrics"]["client_tpot_p90_ms"]["value"]
            >= last["values"]["tpot_p50_ms"])
    freed = [j for j in lines if j.get("note") == "freed"][0]
    assert freed["live_array_bytes_after_shutdown"] == 0
    ref = [j for j in lines if j.get("note") == "reference"][0]
    assert ref["served_tokens"] >= 8 and ref["decided_by"]
    for name in ref["decided_by"]:  # the statistic of the gaps its limits name
        assert ref[name] <= ref[f"limit_{name}"] and name in last["compared"]
    assert set(last["recorded"]) | set(ref["decided_by"]) == {
        "logit_gap", "logit_gap_mean", "logit_gap_p99"}
    assert ref["resident_bytes_short"] == 0 and ref["live_bytes"] >= ref["stated_bytes"] > 0
    # the configuration's engine_plan reached the engine through llm.plan:
    # drafts are made in the one cell whose plan turns speculation on
    config = {w["name"]: w["config"] for w in BENCH["workloads"]}[cell]
    plan = _load(ROOT / {c["name"]: c for c in BENCH["configs"]}[config]["file"])["engine_plan"]
    window = [j for j in lines if j.get("note") == "window"][0]
    assert window["engine_plan"] == plan
    assert (window["counters"]["spec_drafted"] > 0) is plan["speculative"]


def test_the_programs_own_fp8_cache_comes_out_not_correct():
    """The served control: the program with its fp8 KV cache switched on
    keeps fewer bytes than the configuration states."""
    p = _run(["--workload", CELLS[0], "--seed", "2147496002", "--seconds", "4",
              "--trace", "0", "--rehearse-cpu", "--llm", '{"kv_cache_dtype": "fp8"}'])
    lines = _json_lines(p.stdout)
    assert lines[-1]["rehearsal"] and lines[-1]["rehearsal_correct"] is False, p.stdout[-2000:]
    assert p.returncode != 0 and lines[-1]["failed"] == 0
    ref = [j for j in lines if j.get("note") == "reference"][0]
    assert ref["resident_bytes_short"] > 0.4 * ref["stated_bytes"]
    window = [j for j in lines if j.get("note") == "window"][0]
    assert window["resolved"]["kv_dtype"].startswith("float8")


def test_a_broken_timed_path_comes_out_not_correct():
    """The rest of a run with the token egress altered underneath."""
    p = subprocess.run([sys.executable, str(Path(__file__).with_name("broken_path_driver.py")),
                        "--workload", "qwen7b.chat-open", "--seed", "99", "--seconds", "4",
                        "--trace", "0", "--rehearse-cpu"], cwd=ROOT, env=ENV,
                       capture_output=True, text=True, timeout=600)
    last = _json_lines(p.stdout)[-1]
    assert last["rehearsal"] and last["rehearsal_correct"] is False, p.stdout[-2000:]
    assert p.returncode != 0


def test_a_new_layer_metric_needs_a_file_and_an_entry_only(tmp_path):
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    (tmp_path / "benchmark" / "layer_metrics" / "throwaway_count.py").write_text(
        'NAME, UNIT, LAYER = "throwaway_count", "count", "load generator"\n'
        'MOVES, SOURCE = "tpot_p50_ms", "host_clock"\n\n\n'
        'def read(run):\n    return len(run["reqs"])\n')
    code = ("import benchmark.run as r; "
            "print([m.NAME for m in r.layer_metric_modules()])")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, capture_output=True,
                       text=True, env={**ENV, "PYTHONPATH": f"{tmp_path}:{ROOT}"})
    assert "throwaway_count" in p.stdout and "device_idle_share" in p.stdout, p.stderr


THROWAWAY_BLOCK = {
    "weights.py": "from benchmark import blocks\n\n"
                  "make_params = blocks.load('dense').weights.make_params\n",
    "forward.py": "import numpy as np\n\nfrom benchmark import blocks\n\n"
                  "dense = blocks.load('dense').forward\n\n\n"
                  "def logits(params, cfg, ids, n_last, lowp=None):\n"
                  "    skip = np.zeros(n_last, bool)\n"
                  "    skip[0] = True  # 'the router's margin': a request's first served token\n"
                  "    return dense.logits(params, cfg, ids, n_last, lowp), skip\n",
    "bytes.py": "from benchmark import blocks\n\n"
                "dense = blocks.load('dense').bytes\n\n\n"
                "def resident_bytes(model, llm, precision):\n"
                "    return dense.resident_bytes(model, llm, precision) + 1_000_000\n",
}
MISTRAL_TEMPLATE = (  # a copy of model/chat_template.py _render_mistral
    "def render(system, history, user):\n"
    "    out, first = ['<s>'], True\n"
    "    for role, content in list(history) + [('user', user)]:\n"
    "        if role == 'user':\n"
    "            if first and system:\n"
    "                content, first = f'{system}\\n\\n{content}', False\n"
    "            out.append(f'[INST] {content} [/INST]')\n"
    "        else:\n"
    "            out.append(f' {content}</s>')\n"
    "    return ''.join(out)\n")
# The program's side of a new architecture (a model_config PR's own code): a
# tiny preset of another family, registered before the harness starts.
THROWAWAY_PROGRAM = (
    "import dataclasses, sys\n"
    "from runbookai_tpu.models.llama import CONFIGS\n"
    "CONFIGS['throwaway-test'] = dataclasses.replace(\n"
    "    CONFIGS['qwen2-test'], name='throwaway-test', family='mistral')\n"
    "from benchmark import run\n"
    "sys.exit(run.main())\n")


def _tree_with_a_throwaway_architecture(tmp_path, block="throwaway"):
    """A copy of the benchmark with an architecture ADDED: a block, a chat
    template, a limits file, a configuration, a cell file, and the two
    entries of ``BENCHMARK.json``. Returns the bytes of every file the
    copy had before, to show that none was edited."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    had = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
    bench = tmp_path / "benchmark"
    (bench / "blocks" / "throwaway").mkdir()
    for name, text in THROWAWAY_BLOCK.items():
        (bench / "blocks" / "throwaway" / name).write_text(text)
    (bench / "reference" / "templates" / "mistral.py").write_text(MISTRAL_TEMPLATE)
    config = _load(ROOT / BENCH["configs"][0]["file"])
    config.update(name="throwaway", block=block,
                  rehearsal=dict(config["rehearsal"], base="throwaway-test"))
    (bench / "configs" / "throwaway.json").write_text(json.dumps(config))
    (bench / "configs" / "throwaway.limits.json").write_text(json.dumps(
        {"logit_gap": 0.25, "resident_bytes_short": 2_000_000, "not_comparable_share": 0.5}))
    (bench / "cells" / "throwaway.chat-open.json").write_text(json.dumps({"rate_rps": 1.0}))
    entries = dict(BENCH)
    entries["configs"] = BENCH["configs"] + [dict(
        BENCH["configs"][0], name="throwaway", file="benchmark/configs/throwaway.json")]
    entries["workloads"] = BENCH["workloads"] + [dict(
        BENCH["workloads"][0], name="throwaway.chat-open", config="throwaway")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(entries))
    return had


def _run_throwaway(tmp_path, seconds="4"):
    return subprocess.run(
        [sys.executable, "-c", THROWAWAY_PROGRAM, "--workload", "throwaway.chat-open",
         "--seed", "2147496003", "--seconds", seconds, "--trace", "1", "--rehearse-cpu"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**ENV, "PYTHONPATH": f"{tmp_path}:{ROOT}"})


def test_a_new_architecture_needs_files_and_entries_only(tmp_path):
    """Every part of the seam is seen to be taken from the files ADDED: the
    block's bytes (stated 1,000,000 over the dense block's), its
    not-comparable positions (one a sampled request), the configuration's
    own three limits, and the family's template (the prompts' token counts
    equal the server's, which renders [INST], not ChatML)."""
    from benchmark import serving
    from runbookai_tpu.models.llama import CONFIGS as PROGRAM

    had = _tree_with_a_throwaway_architecture(tmp_path)
    p = _run_throwaway(tmp_path)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert {q: q.read_bytes() for q in had} == had  # no file of the copy edited
    lines = _json_lines(p.stdout)
    last, ref = lines[-1], [j for j in lines if j.get("note") == "reference"][0]
    assert last["rehearsal"] and last["rehearsal_correct"] and last["failed"] == 0
    qwen = _load(ROOT / BENCH["configs"][0]["file"])
    dense = blocks.load("dense").bytes.resident_bytes(
        serving.reference_cfg(PROGRAM["qwen2-test"]), qwen["rehearsal"]["llm"], qwen["precision"])
    assert ref["stated_bytes"] == dense + 1_000_000
    assert ref["resident_bytes_short"] == ref["stated_bytes"] - ref["live_bytes"] > 0
    assert ref["requests"] == ref["not_comparable"] == 4 and ref["served_tokens"] >= 8
    assert ref["not_comparable_share"] == pytest.approx(4 / ref["served_tokens"])
    assert ref["decided_by"] == ["logit_gap"]
    assert (ref["limit_logit_gap"], ref["limit_resident_bytes_short"],
            ref["limit_not_comparable_share"]) == (0.25, 2_000_000, 0.5)
    assert ref["prompt_token_mismatches"] == [] and ref["ok"] is True
    # the last key of the last line, and the last lines of standard error
    assert list(last)[-1] == "compared" and last["compared"]["resident_bytes_short"] == {
        "value": ref["resident_bytes_short"], "limit": 2_000_000}
    assert p.stderr.splitlines()[-len(last["compared"]):] == [
        f"compared {k} {c['value']} limit {c['limit']}" for k, c in last["compared"].items()]


def test_an_unknown_block_ends_the_run_before_anything_is_built(tmp_path):
    _tree_with_a_throwaway_architecture(tmp_path, block="latent")
    p = _run_throwaway(tmp_path)
    assert p.returncode != 0 and not p.stdout.strip()
    held = sorted(blocks.names() + ["throwaway"])  # the copy's directory, the added one in it
    assert f"unknown block 'latent'; benchmark/blocks/ holds: {held}" in p.stderr
