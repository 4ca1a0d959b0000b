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
    for key in serving.MODEL_KEYS:
        if key not in data["reduced"]:
            assert getattr(built, key) == getattr(base, key), key
    for key in data["reduced"]:  # no width is ever reduced
        assert key == "n_layers"


@pytest.mark.parametrize("key", ["ffn_dim", "dim", "n_kv_heads", "vocab_size"])
def test_a_changed_width_is_refused(key):
    from benchmark import serving

    data = _load(ROOT / BENCH["configs"][0]["file"])
    with pytest.raises(ValueError):
        serving.model_config(dict(data, **{key: data[key] // 2}))


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
    from benchmark.kernels import qmm_pallas

    qwen = _load(ROOT / "benchmark" / "configs" / "qwen2.5-7b-int8.json")
    d, f, kv = qwen["dim"], qwen["ffn_dim"], qwen["n_kv_heads"] * qwen["dim"] // qwen["n_heads"]
    assert (k, n) in [(d, d), (d, kv), (d, f), (f, d)]
    feed = f"%dynamic-slice_bitcast_fusion.3 = s8[{k},{n}] fusion(s8[28,{k},{n}] %gte.1, s32[] %gte.2)"
    m = qmm_pallas.FEED.search(feed)
    assert (int(m.group(1)), int(m.group(2))) == (k, n)
    for rows in (16, 128):
        op = f"%qmm_pallas.7 = bf16[{rows},{n}] custom-call(bf16[{rows},{k}] %x, s8[{k},{n}] %y)"
        assert qmm_pallas.shape_of(op) == (rows, k, n)


def test_decode_step_attention_and_resident_bytes():
    from benchmark.kernels import decode_step, paged_attention_decode

    qwen = _load(ROOT / "benchmark" / "configs" / "qwen2.5-7b-int8.json")
    layer = 3584 * 3584 * 2 + 2 * 3584 * 512 + 3 * 3584 * 18944
    head = 3584 * 152064 * 2
    assert decode_step.weight_bytes(qwen) == 28 * layer + head
    assert decode_step.kv_bytes(qwen, 1000) == 1000 * 57344  # 57,344 B a token
    assert decode_step.step_bytes(qwen, 1000) == 28 * layer + head + 1000 * 57344
    assert paged_attention_decode.bytes_per_call(1000, 4, 128) == 1000 * 2048
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
    from benchmark.layer_metrics import qmm_feed_copy_ms, qmm_kernel_ms

    assert qmm_pallas.shape_of(QMM_OP) == (16, 3584, 18944)
    assert qmm_pallas.FEED.search(FEED_OP) and not qmm_pallas.PATTERN.search(FEED_OP)
    assert not qmm_pallas.FEED.search(QMM_OP)
    attn = paged_attention_decode.pattern(16)
    assert attn.search(ATTN_OP) and attn.search(VERIFY_OP) and not attn.search(CHUNK_OP)
    assert trace_reduce.own_name(QMM_OP) == "qmm_pallas"
    # 17 passes of 28 layers (2 runs of 8 steps and a single step): 476
    # calls of the kernel at 75 us and of its feed at 91 us each.
    qwen = _load(ROOT / "benchmark" / "configs" / "qwen2.5-7b-int8.json")
    run = {"peaks": {"hbm_bytes_per_s": 819e9}, "model": qwen, "llm": qwen["llm"],
           "trace": {"modules": {"jit__decode_multi": {"count": 2, "seconds": 0.8},
                                 "jit__decode_step": {"count": 1, "seconds": 0.05}},
                     "ops": {QMM_OP: {"count": 476, "seconds": 476 * 75e-6},
                             FEED_OP: {"count": 476, "seconds": 476 * 91e-6},
                             ATTN_OP: {"count": 476, "seconds": 0.25}}}}
    assert qmm_kernel_ms.read(run) == pytest.approx(28 * 75e-3)
    assert qmm_feed_copy_ms.read(run) == pytest.approx(28 * 91e-3)
    assert qmm_kernel_ms.read({**run, "trace": None}) is None
    red = {"ops": {"%while.6 = (s32[]) while(...)": {"count": 1, "seconds": 9.0},
                   **run["trace"]["ops"]}, "idle_gaps": {"decode": 0.1}}
    assert [n for n, _ in trace_reduce.breakdown(red)["device_ops"]][0] == ATTN_OP


DEVICE_READERS = ["attn_decode_roofline", "decode_hbm_roofline", "prefill_dev_ms_per_ktok",
                  "qmm_feed_copy_ms", "qmm_kernel_ms"]


@pytest.mark.parametrize("slice_name", ["decode_spec_slice", "decode_multi_slice"])
@pytest.mark.parametrize("metric", DEVICE_READERS)
def test_device_readers_find_their_events_in_both_kinds_of_slice(metric, slice_name):
    """Whichever decode program a slice happened to hold — speculative
    verifies only, or multi-step decode only — every reader of the device
    trace has something to read (a declared metric missing from a traced
    run's line is refused), and no share passes 100%."""
    from benchmark.layer_metrics._common import load_metric_file

    qwen = _load(ROOT / "benchmark" / "configs" / "qwen2.5-7b-int8.json")
    trace = _load(ROOT / "benchmark" / "testdata" / "chat_open_slices.json")[slice_name]
    live = dict(_rec(0.0, 0.0, [1.0 + 0.1 * i for i in range(40)]), prompt_tokens=600)
    run = {"peaks": {"hbm_bytes_per_s": 819e9}, "model": qwen, "llm": qwen["llm"],
           "trace": trace, "reqs": [live] * 8,
           "traced": {"t_start": 1.5, "t_stop": 4.5,
                      "health_start": {"metrics": {"prefill_tokens": 0}},
                      "health_stop": {"metrics": {"prefill_tokens": 4000}}}}
    value = load_metric_file(ROOT / "benchmark" / "layer_metrics" / f"{metric}.py").read(run)
    assert value is not None and value > 0
    if metric.endswith("_roofline"):
        assert value < 100


def test_peaks_table_has_the_chip_and_its_source():
    peaks = _load(ROOT / "benchmark" / "peaks.json")
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["bf16_flops"] == 197e12 and "Google Cloud" in peaks["source"]


# ---- the reference ------------------------------------------------------------

def _tiny(name):
    import dataclasses

    from benchmark import serving
    from runbookai_tpu.models.llama import CONFIGS as PROGRAM

    return PROGRAM[name], {k: getattr(PROGRAM[name], k) for k in serving.MODEL_KEYS}, dataclasses


@pytest.mark.parametrize("preset,quantized", [("qwen2-test", True), ("qwen2-test", False)])
def test_reference_weights_are_the_programs_bit_for_bit(preset, quantized):
    import jax

    from benchmark.reference import weights
    from runbookai_tpu.models.llama import init_params, init_params_quantized

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

    from benchmark.reference import forward, weights
    from runbookai_tpu.models.llama import forward_train

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

    from benchmark.reference import forward, weights
    from runbookai_tpu.models.llama import forward_train

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
        "gaps": np.array([0.0, gap]), "control_gaps": {}})
    out = check.compare({}, {}, [{"id": "r0"}], check.limits_for("qwen2.5-7b-int8"),
                        {"live_bytes": 100 - short, "stated_bytes": 100})
    assert out["ok"] is ok and out["resident_bytes_short"] == short
    assert out["logit_gap"] == pytest.approx(gap) and out["served_tokens"] == 2


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
    assert ref["served_tokens"] >= 8 and ref["logit_gap"] <= ref["limit"]
    assert ref["resident_bytes_short"] == 0 and ref["live_bytes"] >= ref["stated_bytes"] > 0
    # the configuration's engine_plan reached the engine through llm.plan
    window = [j for j in lines if j.get("note") == "window"][0]
    assert window["engine_plan"] == {"speculative": False}
    assert window["counters"]["spec_drafted"] == 0


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
