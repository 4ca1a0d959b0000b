"""The files the ``longcat`` block and its cell bring (CPU, tier-1): the
configuration against the published config, the block's bytes against the
issue's arithmetic, the new readers on hand-made runs — what they read, and
that they read nothing (and do not raise) from a program or a block without
it, as the parent of the PR that added them."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from benchmark import blocks, serving
from benchmark.kernels import mla_decode, mla_spec
from benchmark.layer_metrics._common import load_metric_file

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((ROOT / "benchmark/configs/longcat-flash-ep32-bf16.json").read_text())
LIMITS = json.loads((ROOT / "benchmark/configs/longcat-flash-ep32-bf16.limits.json").read_text())
METRICS = ROOT / "benchmark" / "layer_metrics"
NEW = ["mla_decode_roofline", "expert_ffn_ms", "expert_pairs_per_held_expert",
       "zero_expert_pick_share", "expert_overflow_share"]


def _model():
    return serving.reference_cfg(serving.model_config(CONFIG))


def test_every_published_number_is_in_the_file_and_only_the_cut_differs():
    pub = CONFIG["published"]
    differs = {k for k, v in pub.items() if CONFIG[k] != v}
    assert differs == {"num_layers", "vocab_size"} <= set(CONFIG["reduced"])
    assert CONFIG["reduced"] == ["num_layers", "n_experts_held", "vocab_size"]
    assert (CONFIG["num_layers"], CONFIG["n_experts_held"], CONFIG["vocab_size"]) == (4, 16, 16384)
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG["name"]][0]
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    model = _model()  # checked against CONFIGS["longcat-flash-chat"] key by key
    assert model["n_routed_experts"] + model["zero_expert_num"] == 768
    assert model["moe_topk"] == 12 and model["family"] == "longcat"


def test_the_cut_is_the_issues_arithmetic():
    b, model = blocks.load("longcat").bytes, _model()
    assert 2 * b.sublayer_params(model) + b.router_params(model) == 638_844_928
    assert b.expert_params(model) == 37_748_736
    resident = b.resident_bytes(model, CONFIG["llm"], CONFIG["precision"])
    pool = b.latent_bytes(model, 12288 * 16)
    assert pool == 196_608 * 9_216 and 12.15e9 < resident < 12.20e9
    assert resident >= 0.25 * 17_179_869_184  # the floor of cell_too_small, thrice over
    # a pass: everything outside the experts once, the head, the live latents
    assert b.step_bytes(model, 0) == pytest.approx(5.33e9, rel=0.01)
    assert b.step_bytes(model, 50_000) - b.step_bytes(model, 0) == 50_000 * 9_216
    assert not hasattr(b, "attention_bytes_per_call")  # the dense kernel's


def test_mla_decode_counts_and_finds_its_loop():
    assert mla_decode.bytes_per_call(1000, 512, 64) == 1000 * 576 * 2
    assert mla_decode.ops_per_call(1000, 64, 512, 64) == 2 * 64 * (576 + 512) * 1000
    pat = mla_decode.pattern(64, 64, 512)
    walk = ("%while.12 = (s32[], s32[], f32[64,1,64], f32[64,1,64], f32[64,1,64,512], "
            "bf16[64,1,64,512], bf16[1572864,1,512]) while(%tuple.3)")
    layers = "%while.5 = (s32[], bf16[64,1,6144], bf16[8,196608,1,512], s32[4]) while(%t)"
    assert pat.search(walk) and not pat.search(layers)
    assert not mla_decode.pattern(16, 64, 512).search(walk)


def _run(steps=(), ops=None, model=None, block="longcat"):
    reqs = [{"status": 200, "error": None, "text": "x", "times": [1.0, 9.0],
             "prompt_tokens": 1000, "done_marker": True, "terminated": True,
             "completion_tokens": 1, "finish": "length", "max_tokens": 1}] * 50
    return {"steps": list(steps), "model": model or _model(), "block": blocks.load(block),
            "llm": CONFIG["llm"], "reqs": reqs,
            "traced": {"t_start": 4.0, "t_stop": 6.0},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "trace": None if ops is None else {
                "ops": ops, "modules": {"jit__decode_multi": {"count": 5, "seconds": 1.0}}}}


def _read(name, run):
    return load_metric_file(METRICS / f"{name}.py").read(run)


def test_expert_readers_on_hand_made_step_records():
    decode = {"held": 32, "zero": 1024, "absent": 2016, "touched": 20, "overflow": 0,
              "passes": 8, "programs": ["_decode_multi"]}
    mixed = {"held": 100, "zero": 400, "absent": 700, "touched": 40, "overflow": 3,
             "passes": 9, "programs": ["_mixed_step", "_decode_multi"]}
    run = _run([{"step": 1, "experts": decode}, {"step": 2}, {"step": 3, "experts": mixed}])
    assert _read("expert_pairs_per_held_expert", run) == 32 / (8 * 4 * 16)  # decode records only
    assert _read("zero_expert_pick_share", run) == pytest.approx(100 * 1424 / 4272)
    assert _read("expert_overflow_share", run) == pytest.approx(100 * 3 / (17 * 4))
    no_count = {k: v for k, v in decode.items() if k != "overflow"}  # an earlier program's record
    assert _read("expert_overflow_share", _run([{"step": 1, "experts": no_count}])) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_or_block_without_it_is_read_as_nothing(name):
    """The parent has no ``experts`` field, no latent loop and no expert
    conditional; the dense block's model has none of the keys."""
    assert _read(name, _run([{"step": 1}, {"step": 2}], ops={})) is None
    qwen = json.loads((ROOT / BENCH["configs"][0]["file"]).read_text())
    dense = serving.reference_cfg(serving.model_config(qwen))
    assert _read(name, _run([{"step": 1}], ops={}, model=dense, block="dense")) is None


def test_device_readers_on_hand_made_operations():
    ops = {"%while.9 = (s32[], s32[], f32[64,1,64], f32[64,1,64], f32[64,1,64,512], bf16[6": {
               "count": 320, "seconds": 0.32},
           "%conditional.3 = (f32[64,6144]) conditional(s32[] %c, (s32[64,12], f32[64,12]": {
               "count": 160, "seconds": 0.24},
           "%conditional.4 = (f32[1024,6144]) conditional(s32[] %c, (s32[1024,12]": {
               "count": 8, "seconds": 0.5}}
    run = _run(ops=ops)
    assert _read("expert_ffn_ms", run) == pytest.approx(240.0 / 40)  # 5 dispatches x 8 passes
    # 50 live rows of 1,000 prompt tokens: 57.6 MB a call is 70.3 us; a call took 1 ms
    assert _read("mla_decode_roofline", run) == pytest.approx(100 * (50_000 * 1152 / 819e9) / 1e-3)


WHILE_WALK = "%while.9 = (s32[], s32[], f32[64,1,64], f32[64,1,64], f32[64,1,64,512], bf16[6"
# What is NOT a decode pass's call, whichever way it is implemented: a
# speculative round's walk, a mixed step's or chunk's, another kernel whose
# name only starts alike, and the other loops of the program.
NOT_THE_CALL = {
    "%mla_spec_walk.4 = bf16[64,2,64,512] custom-call(s32[64,1025] %t, bf16[64,2,64,576] %q)": {
        "count": 80, "seconds": 9.0},
    "%mla_chunk_walk.2 = bf16[128,8,64,512] custom-call(s32[128,1025] %t)": {
        "count": 8, "seconds": 9.0},
    "%mla_decode_walk_grad.1 = bf16[64,64,512] custom-call(s32[64,1025] %t)": {
        "count": 8, "seconds": 9.0},
    "%while.7 = (s32[], f32[128,8,64], f32[128,8,64], f32[128,8,64,512]) while(": {
        "count": 8, "seconds": 9.0},
    "%while.5 = (s32[], bf16[64,1,6144], bf16[8,196608,1,512], s32[4]) while(%t)": {
        "count": 5, "seconds": 9.0},
}


@pytest.mark.parametrize("call", [
    WHILE_WALK,  # XLA's page-walk loop, by its carry: what the cell runs today
    "%mla_decode_walk.3 = bf16[64,64,512] custom-call(s32[64,1025] %t, bf16[64,64,576] %q, "
    "bf16[196608,1,512] %latents)",  # a Pallas walk, by the name it is to take
    "%mla_decode_walk = bf16[64,64,512] custom-call(s32[64,1025] %t)",  # the first of its name
])
def test_the_latent_roofline_counts_the_work_whatever_implements_it(call):
    """The same seconds give the same share, from a reduced trace that holds
    only the ``while`` and from one that holds only the named call."""
    assert mla_decode.pattern(64, 64, 512).search(call)
    assert not mla_spec.pattern(64, 64, 512).search(call)
    run = _run(ops={call: {"count": 320, "seconds": 0.32}, **NOT_THE_CALL})
    # 50 live rows of 1,000 prompt tokens: 57.6 MB a call is 70.3 us; a call took 1 ms
    assert _read("mla_decode_roofline", run) == pytest.approx(
        100 * (50_000 * 1152 / 819e9) / 1e-3)
    assert _read("mla_decode_roofline", _run(ops=NOT_THE_CALL)) is None


def test_entries_of_the_new_cell():
    cell = [w for w in BENCH["workloads"] if w["name"] == "longcat.reason-open"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG["name"], "reason-open", 1)
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == ["longcat.reason-open"]
        assert by_name[name]["moves"] == "tpot_p50_ms"
    for name in ("qmm_kernel_ms", "attn_decode_roofline"):
        assert by_name[name]["workloads"] == ["qwen7b.chat-open"]
    traffic = json.loads((ROOT / "benchmark/traffic/reason-open.json").read_text())
    assert traffic["generator"] == "open_loop" and traffic["check_sample"] == 4
    assert (traffic["prompt_tokens"]["max"] + traffic["max_tokens"]["max"]
            < CONFIG["llm"]["max_seq_len"])
    assert not re.search(r"<\|", traffic["system"])
    rate = json.loads((ROOT / "benchmark/cells/longcat.reason-open.json").read_text())["rate_rps"]
    assert rate > 0


def test_the_limits_file_holds_sound_and_control_readings():
    """The mean and the 99th percentile decide (PR 44); each limit stands
    1.25 times clear of the readings its comment lists, both ways."""
    from benchmark.reference import check

    assert check.deciding(LIMITS) == ["logit_gap_mean", "logit_gap_p99"] and "logit_gap" not in LIMITS
    for name, sound_max, control_min in (("logit_gap_mean", 0.149, 1.381),
                                         ("logit_gap_p99", 0.956, 3.495)):
        assert 1.25 * sound_max <= LIMITS[name] <= control_min / 1.25, name
        assert str(sound_max) in LIMITS["comment"] and str(control_min) in LIMITS["comment"]
    for word in ("sound", "fp8", "1.25", "1.909", "resident_bytes_short"):
        assert word in LIMITS["comment"], word
