"""The files the ``joyai`` block and its cell bring (CPU, tier-1): the
configuration against the published config and the floors, the block's
bytes against the issue's arithmetic, the new readers on hand-made records
and events and on operations reduced from the builder's own trace of the
cell — what they read, and that they read nothing (and do not raise) from a
program or a block without it, as the parent of the PR that added them —
the cell rehearsed on the CPU, ``tools/mtp_check.py``, and both controls of
``correct`` at test size."""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import blocks, serving
from benchmark.kernels import mla_decode, mla_spec
from benchmark.layer_metrics._common import load_metric_file

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((ROOT / "benchmark/configs/joyai-llm-flash-ep4-bf16.json").read_text())
LIMITS = json.loads((ROOT / "benchmark/configs/joyai-llm-flash-ep4-bf16.limits.json").read_text())
SLICE = json.loads((ROOT / "benchmark/testdata/joyai_spec_slice.json").read_text())
METRICS = ROOT / "benchmark" / "layer_metrics"
CELL = "joyai.sysprompt-open"
NEW = ["mtp_accept_share", "spec_rounds_per_dispatch", "mtp_draft_ms", "mla_spec_roofline",
       "spec_expert_ffn_ms", "spec_expert_touched_share"]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}


def _model():
    return serving.reference_cfg(serving.model_config(CONFIG))


def test_every_published_number_is_in_the_file_and_only_the_cut_differs():
    pub = CONFIG["published"]
    assert {k for k, v in pub.items() if CONFIG[k] != v} == {"num_hidden_layers"}
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_experts_held"]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_experts_held"]) == (13, 64)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"], pub["vocab_size"]) == (40, 256, 129280)
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG["name"]][0]
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    model = _model()  # checked against CONFIGS["joyai-llm-flash"] key by key
    assert (model["n_routed_experts"], model["num_experts_per_tok"]) == (256, 8)
    assert (model["scoring_func"], model["topk_method"], model["norm_topk_prob"]) == (
        "sigmoid", "noaux_tc", True)
    assert model["num_nextn_predict_layers"] == 1 and model["family"] == "qwen2"
    # the floors: the leading dense layer and 12 >= 4 expert layers, 64 >= 8 experts, the whole vocabulary
    assert model["num_hidden_layers"] - model["first_k_dense_replace"] == 12
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size", "q_lora_rank",
                  "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                  "num_experts_per_tok", "vocab_size", "num_attention_heads"):
        assert CONFIG[width] == pub[width] and width not in CONFIG["reduced"]
    for said in ("published", "reduced_why", "assumed", "deployment", "precision"):
        assert CONFIG[said]
    assert CONFIG["engine_plan"] == {"speculative": True} and CONFIG["rehearsal"]["base"] == "joyai-test"


def test_the_cut_is_the_issues_arithmetic():
    b, model, precision = blocks.load("joyai").bytes, _model(), CONFIG["precision"]
    assert b.attention_params(model) == 26_345_472
    assert b.expert_params(model) == 4_718_592 and 64 * b.expert_params(model) == 301_989_888
    assert b.stacks(model) == (14, 13, 1, 1)  # attention blocks, expert layers, dense FFNs, modules
    # an expert layer outside its experts: attention, router, shared expert (the issue's 31,594,752 has its norms too)
    outside = b.attention_params(model) + b.router_params(model) + b.expert_params(model)
    assert outside == 31_588_352 and outside + 301_989_888 == 333_578_240
    matrices = (b.matrix_params_outside_experts(model) + 13 * b.router_params(model)
                + 13 * 64 * b.expert_params(model) + 2 * 129_280 * 2048)
    assert matrices == 4_944_822_272  # the issue's 4,944.9M counts the norms: 4,944,919,808
    assert serving.model_config(CONFIG).total_params == 4_944_919_808
    assert b.latent_token_bytes(model) == 14 * 576 * 2 == 16_128
    pool = 12_288 * 16 * 16_128
    assert pool == 3_170_893_824  # 196,608 tokens: 3.17 GB
    resident = b.resident_bytes(model, CONFIG["llm"], precision)
    assert resident == 13_074_169_856  # the routers at float32's four bytes
    assert 0.76 < resident / 17_179_869_184 < 0.77 and resident >= 0.25 * 17_179_869_184
    # the module's matrices and its cache layer are IN the number
    bare = dataclasses.replace(serving.model_config(CONFIG), num_nextn_predict_layers=0)
    less = b.resident_bytes(serving.reference_cfg(bare), CONFIG["llm"], precision)
    module = outside + 301_989_888 + 2 * 2048 * 2048
    # (its rotated keys take the half row that the 13th block left unused: 512 values a token more, not 576)
    assert resident - less == (module + b.router_params(model)) * 2 + 12_288 * 16 * 512 * 2
    # a round: everything outside the held experts once, the head twice, the live latents
    assert b.step_bytes(model, 0) == pytest.approx(2.05e9, rel=0.01)
    assert b.step_bytes(model, 50_000) - b.step_bytes(model, 0) == 50_000 * 16_128
    assert b.PROGRAMS == {"jit__decode_spec": None} and not hasattr(b, "attention_bytes_per_call")


def test_the_kernel_file_counts_and_finds_its_events():
    assert mla_spec.bytes_per_call(10_000, 512, 64) == 10_000 * 576 * 2
    assert mla_spec.ops_per_call(10_000, 32, 512, 64) == 2 * mla_decode.ops_per_call(10_000, 32, 512, 64)
    spec, decode = mla_spec.pattern(64, 32, 512), mla_decode.pattern(64, 32, 512)
    for name in SLICE["walk_events"]:
        assert spec.search(name) and not decode.search(name), name
    for name in SLICE["other_events"]:
        assert not spec.search(name), name
    one = "%while.9 = (s32[], f32[64,1,32], f32[64,1,32], f32[64,1,32,512], s32[]) while("
    ragged = "%while.7 = (s32[], f32[128,8,32], f32[128,8,32], f32[128,8,32,512]) while("
    assert decode.search(one) and not spec.search(one) and not spec.search(ragged)
    assert not mla_spec.pattern(16, 32, 512).search(SLICE["walk_events"][0])


def _run(steps=(), trace=None, model=None, block="joyai", health=None):
    reqs = [{"status": 200, "error": None, "text": "x", "times": [1.0, 9.0],
             "prompt_tokens": 2800, "done_marker": True, "terminated": True,
             "completion_tokens": 1, "finish": "length", "max_tokens": 1}] * 20
    before, after = health or ({}, {})
    return {"steps": list(steps), "model": model or _model(), "block": blocks.load(block),
            "llm": CONFIG["llm"], "reqs": reqs, "runtime": {},
            "health_before": {"metrics": before}, "health_after": {"metrics": after},
            "traced": {"t_start": 4.0, "t_stop": 9.0, "health_start": {"metrics": {}},
                       "health_stop": {"metrics": {}}},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}, "trace": trace}


def _read(name, run):
    return load_metric_file(METRICS / f"{name}.py").read(run)


def test_the_counter_readers_on_hand_made_records():
    spec = {"rounds": 8, "drafted": 120, "accepted": 0, "rows": 15}
    last = {"rounds": 2, "drafted": 2, "accepted": 1, "rows": 1}
    run = _run([{"step": 1, "spec": spec}, {"step": 2}, {"step": 3, "spec": last},
                {"step": 4, "spec": spec}],
               health=({"spec_drafted": 100, "spec_accepted": 0},
                       {"spec_drafted": 12_100, "spec_accepted": 3}))
    assert _read("spec_rounds_per_dispatch", run) == pytest.approx(18 / 3)
    assert _read("mtp_accept_share", run) == pytest.approx(100 * 3 / 12_000)
    none_taken = _run(health=({"spec_drafted": 5}, {"spec_drafted": 905, "spec_accepted": 0}))
    assert _read("mtp_accept_share", none_taken) == 0.0  # drafted and never right reads 0, not nothing


def test_the_expert_readers_of_a_round():
    """The held experts' conditional at a round's ``2 x slots`` rows (names
    as the builder's trace of the cell has them: 13 expert layers x 80
    rounds), not the mixed step's; the touched share of the dispatches of
    rounds alone, the module's expert layer counted."""
    args = "conditional(s32[] %convert_element_type.1180, (s32[128,8], f32[128,8], s32[], bf16[13,64,2048,768]"
    ops = {f"%cond.2.clone.12 = (f32[128,2048]) {args}": {"count": 960, "seconds": 0.9036},
           f"%cond.8.clone.9 = (f32[128,2048]) {args}": {"count": 80, "seconds": 0.0754},
           f"%conditional.12 = (f32[1024,2048]) {args}": {"count": 48, "seconds": 0.16},
           f"%conditional.3 = (f32[64,2048]) {args}": {"count": 8, "seconds": 0.01}}
    run = _run(trace={"ops": ops, "modules": {"jit__decode_spec": {"count": 10, "seconds": 3.98},
                                              "jit__mixed_step": {"count": 4, "seconds": 0.75}}})
    assert _read("spec_expert_ffn_ms", run) == pytest.approx((0.9036 + 0.0754) * 1e3 / 80)
    counts = {"held": 400, "zero": 0, "absent": 1200, "overflow": 0}
    steps = [{"step": 1, "experts": {**counts, "touched": 2496, "passes": 8, "programs": ["_decode_spec"]}},
             {"step": 2, "experts": {**counts, "touched": 700, "passes": 1, "programs": ["_mixed_step"]}},
             {"step": 3, "experts": {**counts, "touched": 900, "passes": 9,
                                     "programs": ["_module_step", "_decode_spec"]}},
             {"step": 4, "experts": {**counts, "touched": 832, "passes": 8, "programs": ["_decode_spec"]}}]
    assert _read("spec_expert_touched_share", _run(steps)) == pytest.approx(
        100 * (2496 + 832) / (16 * 13 * 64))


def test_the_modules_interval_on_hand_made_events():
    """From the start of a round's product with ``Wp`` to the end of the
    next fusion that reads the head: the module's share of a round."""
    mod = load_metric_file(METRICS / "mtp_draft_ms.py")
    head = "%iota_reduce_fusion.4 = (bf16[64,2], s32[64,2]) fusion(bf16[2048,129280] %p, f32[64,2] %n)"
    wp = ("%fusion.820 = (f32[64,2], bf16[64,2,2048]) fusion(bf16[1,4096,2048] %copy-done.12, "
          "bf16[64,2,2048] %fusion.817, bf16[64,2,2048] %fusion.819), kind=kOutput")
    draft_head = ("%iota_reduce_fusion.5 = (bf16[64], s32[64]) fusion(bf16[2048,129280] %p, "
                  "f32[2048] %b, f32[64] %n, bf16[64,2048] %y)")
    events = [
        # the weight's prefetch, early in the round: the same shape, no fusion
        ("%copy-start.12 = (bf16[1,4096,2048], bf16[1,4096,2048], u32[]) copy-start("
         "bf16[1,4096,2048] %get-tuple-element.5249)", 0.0, 0.0),
        ("%fusion.1 = bf16[64,2,2048] fusion(bf16[1,2048,7168] %w)", 0.0, 1.0),
        (head, 1.0, 1.5),  # the trunk's head: before Wp, not the module's
        ("%copy-done.12 = bf16[1,4096,2048] copy-done((bf16[1,4096,2048], bf16[1,4096,2048], "
         "u32[]) %copy-start.12)", 1.9, 1.9),
        (wp, 2.0, 2.1),
        ("%while.235 = (s32[], f32[64,2,32,512]) while((s32[]) %t)", 2.1, 4.0),
        ("%fusion.9 = bf16[2048,16,512] fusion(bf16[172032,16,512] %pool, s32[2048] %i)", 2.2, 3.9),
        (draft_head, 4.0, 4.5),
        # a mixed step's module pass between two dispatches: other rows, not a round
        ("%fusion.368 = (f32[128,8], bf16[128,8,2048]) fusion(bf16[1,4096,2048] %copy-done.91, "
         "bf16[128,8,2048] %fusion.367)", 5.0, 5.1),
        ("%iota_reduce_fusion.2 = (bf16[68], s32[68]) fusion(bf16[2048,129280] %lm_head, "
         "bf16[68,2048] %fusion.28)", 6.0, 6.5),
        (head, 9.0, 9.5),
        (wp, 10.0, 10.2),
        (draft_head, 11.0, 11.5),
        (wp, 20.0, 20.2),
    ]  # the slice ends inside a third round: not counted
    assert mod.intervals(events, 64, 2048, 129280) == pytest.approx([2.5, 1.5])
    assert mod.intervals(events, 16, 2048, 129280) == []
    assert mod.intervals(events, 64, 4096, 129280) == []


def _slice_run(ops):
    """``testdata/joyai_spec_slice.json``'s slice, with ``ops`` on its
    "XLA Ops" line."""
    run = _run(trace={"ops": ops, "modules": SLICE["modules"]})
    run["reqs"] = [dict(run["reqs"][0], prompt_tokens=SLICE["live_tokens"] / SLICE["live_rows"],
                        times=[0.0, SLICE["slice_seconds"]])] * SLICE["live_rows"]
    run["traced"].update(t_start=0.0, t_stop=SLICE["slice_seconds"])
    return run


def test_the_device_readers_on_the_builders_own_slice():
    """``testdata/joyai_spec_slice.json``: the walks, programs and module
    intervals of one traced slice of the cell on the chip, reduced, with
    what the run's readers printed."""
    run = _slice_run(SLICE["ops"])
    got = _read("mla_spec_roofline", run)
    assert 0 < got < 100 and got == pytest.approx(SLICE["printed"]["mla_spec_roofline"], rel=1e-6)
    calls = sum(t["count"] for t in SLICE["ops"].values())
    rounds = SLICE["modules"]["jit__decode_spec"]["count"] * 8
    assert calls == pytest.approx(14 * rounds, rel=0.15)  # a walk an attention block and round
    hbm = _read("decode_hbm_mfu", run)
    assert 0 < hbm < 100 and hbm == pytest.approx(SLICE["printed"]["decode_hbm_mfu"], rel=1e-6)
    assert 0 < SLICE["printed"]["mtp_draft_ms"] < 1e3 * (
        SLICE["modules"]["jit__decode_spec"]["seconds"] / rounds)


@pytest.mark.parametrize("spelling", ["while", "named"])
def test_the_rounds_roofline_counts_the_work_whatever_implements_it(spelling):
    """The builder's slice as it was traced (XLA's ``while``, by its carry)
    and with every walk re-spelt as the Pallas call it is to become
    (``%mla_spec_walk``), count for count and second for second: the same
    share. A decode pass's walk and a chunk's, by either spelling, count for
    nothing."""
    ops = dict(SLICE["ops"])
    if spelling == "named":
        ops = {f"%mla_spec_walk.{i} = bf16[64,2,32,512] custom-call(s32[64,1025] %t, "
               f"bf16[64,2,32,576] %q, bf16[172032,16,512] %latents)": t
               for i, t in enumerate(ops.values())}
        assert all(mla_spec.pattern(64, 32, 512).search(name) for name in ops)
    others = {name: {"count": 10, "seconds": 5.0} for name in SLICE["other_events"]}
    others.update({
        "%mla_decode_walk.1 = bf16[64,32,512] custom-call(s32[64,1025] %t)": {"count": 9, "seconds": 5.0},
        "%mla_chunk_walk.1 = bf16[128,8,32,512] custom-call(s32[128,1025] %t)": {"count": 9, "seconds": 5.0},
        "%while.9 = (s32[], f32[64,1,32], f32[64,1,32], f32[64,1,32,512], s32[]) while(": {
            "count": 9, "seconds": 5.0}})
    got = _read("mla_spec_roofline", _slice_run({**ops, **others}))
    assert got == pytest.approx(SLICE["printed"]["mla_spec_roofline"], rel=1e-6)
    assert _read("mla_spec_roofline", _slice_run(others)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_or_block_without_it_is_read_as_nothing(name):
    """The parent has no ``spec`` field, drafts nothing in these cells and
    runs no round; the other blocks' models have no module and no such walk."""
    parent = _run([{"step": 1}, {"step": 2}], trace={"ops": {}, "modules": {}},
                  health=({"spec_drafted": 0, "spec_accepted": 0},
                          {"spec_drafted": 0, "spec_accepted": 0}))
    assert _read(name, parent) is None
    assert _read(name, dict(parent, trace=None)) is None
    for entry, block in ((BENCH["configs"][0], "dense"), (BENCH["configs"][1], "longcat")):
        cfg = json.loads((ROOT / entry["file"]).read_text())
        other = _run([{"step": 1}], trace={"ops": {}, "modules": {}}, block=block,
                     model=serving.reference_cfg(serving.model_config(cfg)))
        assert _read(name, other) is None


def test_entries_of_the_new_cell():
    cell = [w for w in BENCH["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG["name"], "sysprompt-open", 1)
    assert len(cell["why"]) <= 200 and "knee" in cell["why"]
    # (no position in a list is pinned: the next PR appends after these)
    assert [w["name"] for w in BENCH["workloads"]].count(CELL) == 1
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(NEW) <= set(by_name)
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
        mod = load_metric_file(METRICS / f"{name}.py")
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            name, m["unit"], m["layer"], m["moves"], m["source"])
    for name, m in by_name.items():  # nothing that listed its cells was given this one
        if name not in NEW and "workloads" in m:
            assert CELL not in m["workloads"]
    traffic = json.loads((ROOT / "benchmark/traffic/sysprompt-open.json").read_text())
    # the longest prompt the traffic file can send, its warm-up's, fits
    assert 2048 + 64 + 8000 + 24 < CONFIG["llm"]["max_seq_len"]
    assert (2048 + 64 + traffic["prompt_tokens"]["max"] + traffic["max_tokens"]["max"]
            < CONFIG["llm"]["max_seq_len"])
    rate = json.loads((ROOT / f"benchmark/cells/{CELL}.json").read_text())
    assert rate["rate_rps"] > 0 and "knee" in rate["note"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A copy of the benchmark's files to rehearse in: ``serving.RUN_DIR``
    (plans, records, the serve config) lies beside the files a run was
    started from, and the other test files' rehearsals, on other workers,
    write theirs under the repository's."""
    root = tmp_path_factory.mktemp("joyai_cell")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def _rehearse(tree, *extra, module="benchmark.run", seconds=("--seconds", "3", "--trace", "0")):
    p = subprocess.run(
        [sys.executable, "-m", module, "--workload", CELL, "--seed", "2147496002",
         *seconds, "--rehearse-cpu", *extra],
        cwd=tree, env={**ENV, "PYTHONPATH": f"{tree}:{ROOT}"}, capture_output=True, text=True,
        timeout=900)
    lines = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
    return p, lines


def test_the_cell_rehearses_on_the_cpu_with_drafting_on(tree):
    """The same code on ``joyai-test``: every request finished, rounds on
    the device, the module's counters fed, the bytes as stated, and the
    reference in fp8 not correct."""
    p, lines = _rehearse(tree, "--trace", "1", "--control", "fp8", seconds=("--seconds", "4"))
    result = lines[-1]
    assert result["rehearsal"] and result["failed"] == 0 and result["attempted"] >= 6, p.stdout[-2000:]
    assert result["compared"]["resident_bytes_short"]["value"] == 0
    assert result["compared"]["prompt_token_mismatches"]["value"] == 0
    assert result["metrics"]["spec_rounds_per_dispatch"]["value"] == 8.0
    assert 0 <= result["metrics"]["mtp_accept_share"]["value"] < 5  # chance is 1 in 262 here
    assert result["metrics"]["prefix_hit_share"]["value"] > 50  # the module's layer rides the hit
    window = [j for j in lines if j.get("note") == "window"][0]
    assert window["counters"]["spec_drafted"] > 0 and window["engine_plan"] == {"speculative": True}
    assert window["counters"]["decode_tokens"] == (
        window["counters"]["spec_drafted"] + window["counters"]["spec_accepted"])
    # the fp8 reference control comes out NOT correct, through the harness's own comparison
    ref = [j for j in lines if j.get("note") == "reference"][0]
    assert ref["decided_by"] == ["logit_gap"]  # this configuration's limits hold no mean
    assert (ref["control"]["fp8"]["ok"] is False
            and ref["control"]["fp8"]["logit_gap"] > ref["limit_logit_gap"])
    assert 0 < ref["not_comparable_share"] <= ref["limit_not_comparable_share"]


def test_mtp_check_holds_the_served_drafts_to_the_blocks(tree):
    p, lines = _rehearse(tree, module="benchmark.tools.mtp_check", seconds=())
    verdict = lines[-1]
    assert p.returncode == 0 and verdict["ok"], p.stdout[-2000:] + p.stderr[-2000:]
    assert verdict["drafts_compared"] >= 9 and verdict["agreement"] >= 0.9
    assert verdict["draft_gap_max"] <= verdict["limit_draft_gap"]


def _gaps(seed, lowp):
    """((widest, mean) gap of the program's greedy tokens (bf16 weights, the
    served forward), the same of each control, the share of positions the
    block declares not comparable) over one sequence of 384 tokens at the
    test preset, read as ``reference/check.py`` reads them: over the
    positions that compare."""
    import jax.numpy as jnp

    from runbookai_tpu.models import joyai
    from runbookai_tpu.models.llama import CONFIGS

    cfg = CONFIGS["joyai-test"]
    block, ref_cfg, t = blocks.load("joyai"), dataclasses.asdict(cfg), 384
    params = block.weights.make_params(ref_cfg, seed % 2 ** 31, False)
    ids = np.random.default_rng(seed).integers(0, 256, size=t).tolist()
    ref, skip = (np.asarray(x) for x in block.forward.logits(params, ref_cfg, ids, t))
    (lk, hk, dk), (lv, hv, dv) = cfg.kv_pool_spec
    served, *_ = joyai.forward_impl(
        params, cfg, jnp.asarray([ids], jnp.int32), jnp.arange(t, dtype=jnp.int32)[None],
        jnp.zeros((lk, 32 * 16, hk, dk), jnp.bfloat16), jnp.zeros((lv, 32 * 16, hv, dv), jnp.bfloat16),
        jnp.arange(1, 26, dtype=jnp.int32)[None], jnp.asarray([t]), page_size=16)
    rows = np.arange(t)

    def gap(lg):
        g = (ref.max(axis=1) - ref[rows, np.asarray(lg).argmax(axis=1)])[~skip]
        return float(g.max()), float(g.mean())

    return (gap(served[0]), {k: gap(block.forward.logits(params, ref_cfg, ids, t, k)[0]) for k in lowp},
            float(skip.mean()))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_the_fp8_control_reads_over_the_limit_and_the_served_path(seed):
    """The reference in fp8 where the configuration states bfloat16 comes
    out NOT correct by the cell's own ``logit_gap`` limit, and reads 1.5
    times over the served bf16 path, over the positions the block lets be
    compared (a held expert no nearer the router's cut than the block's
    ``TOLERANCE``). The test preset has 16 experts and two expert layers, so
    few positions are near a cut (a tenth; most of them at the published
    widths, where the limit was read: the limits file). Rounding the cache
    alone moves the mean least (why ``correct`` also compares the bytes)."""
    (sound_max, sound_mean), control, share = _gaps(seed, ["fp8", "kv_fp8"])
    assert control["fp8"][0] > LIMITS["logit_gap"]
    assert control["fp8"][0] > 1.5 * sound_max and control["fp8"][1] > 4 * sound_mean, (sound_max, control)
    assert control["kv_fp8"][1] < control["fp8"][1]
    assert 0 < share <= LIMITS["not_comparable_share"]


def test_positions_near_the_routers_cut_are_declared_not_comparable():
    """``cut_margin`` on a hand-made router: experts 2-3 of 6 held, the
    two largest of ``s + b`` chosen."""
    import jax.numpy as jnp

    forward = blocks.load("joyai").forward

    def logit(p):
        return float(np.log(p / (1 - p)))

    scores = [[0.9, 0.8, 0.5, 0.1, 0.1, 0.1],     # both chosen absent; the held 0.5 is 0.3 under the cut
              [0.9, 0.5, 0.501, 0.1, 0.1, 0.1],   # a held expert chosen by 0.001 over an absent one
              [0.2, 0.1, 0.9, 0.8, 0.7, 0.7],     # both held and chosen; an absent one 0.1 under
              [0.9, 0.8, 0.1, 0.2, 0.7999, 0.1]]  # an absent near-tie: no held expert near the cut
    u = jnp.eye(4, dtype=jnp.float32)
    router = jnp.asarray([[logit(p) for p in row] for row in scores], jnp.float32)
    got = np.asarray(forward.cut_margin(u, router, jnp.zeros(6), top_k=2, first=2, held=2))
    assert got == pytest.approx([0.3, 0.001, 0.1, 0.6], abs=1e-5)
    bias = jnp.asarray([0, 0, 0.35, 0, 0, 0], jnp.float32)  # the bias moves the choice: 0.85 is in
    assert np.asarray(forward.cut_margin(u, router, bias, top_k=2, first=2, held=2))[0] == pytest.approx(0.05, abs=1e-5)
    assert forward.TOLERANCE == 0.005


def test_a_position_is_not_comparable_by_the_narrowest_of_its_layers():
    """``logits`` at the test preset (two expert layers): not comparable
    where EITHER expert layer's margin is under ``TOLERANCE``, and some
    position is left out by the second layer alone."""
    import jax
    import jax.numpy as jnp

    from runbookai_tpu.models.llama import CONFIGS

    block = blocks.load("joyai")
    forward, ref_cfg = block.forward, dataclasses.asdict(CONFIGS["joyai-test"])
    params = block.weights.make_params(ref_cfg, 5, False)
    ids = np.random.default_rng(5).integers(0, 256, size=256).tolist()
    _, skip = forward.logits(params, ref_cfg, ids, 256)
    tokens, positions = forward._padded(ids)
    lp, k, per_layer = params["layers"], ref_cfg["first_k_dense_replace"], []
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens].astype(jnp.float32)
        for li in range(ref_cfg["num_hidden_layers"]):
            h, m = forward.layer(h, lp, li, li if li < k else None, None if li < k else li - k,
                                 ref_cfg, positions, None)
            if m is not None:
                per_layer.append(np.asarray(m)[:256])
    assert len(per_layer) == 2
    near = [m < forward.TOLERANCE for m in per_layer]
    assert (np.asarray(skip) == (near[0] | near[1])).all() and 0 < np.asarray(skip).sum() < 256
    assert (near[1] & ~near[0]).any()


def test_the_programs_own_fp8_cache_comes_out_not_correct(tree):
    """The served control on this cell: the program with its fp8 latent
    cache keeps fewer bytes than the configuration states — short by the
    pool's half, the module's cache layer included."""
    p, lines = _rehearse(tree, "--llm", '{"kv_cache_dtype": "fp8"}')
    assert lines[-1]["rehearsal"] and lines[-1]["rehearsal_correct"] is False, p.stdout[-2000:]
    assert p.returncode != 0 and lines[-1]["failed"] == 0
    ref = [j for j in lines if j.get("note") == "reference"][0]
    pool = 1024 * 16 * (4 * 16 + 2 * 2 * 8)  # pages x tokens x (4 latents + 2 rows of rotated keys): a byte each saved
    assert 0.9 * pool <= ref["resident_bytes_short"] <= pool
    window = [j for j in lines if j.get("note") == "window"][0]
    assert window["resolved"]["kv_dtype"].startswith("float8")


def test_the_limits_file_holds_sound_and_control_readings():
    assert set(LIMITS) >= {"comment", "logit_gap"}
    for word in ("sound", "fp8", "kv_cache_dtype", "1.5"):
        assert word in LIMITS["comment"], word
    assert "resident_bytes_short" not in LIMITS  # the default's: 0, exact
    assert 0.5 < LIMITS["not_comparable_share"] < 1 and "TOLERANCE" in LIMITS["comment"]
    assert LIMITS["logit_gap"] == 0.3  # as first set: the rule of comparability was tightened, not the limit widened
