"""The files the ``afmoe`` block and its cell bring (CPU, tier-1): the
configuration against the catalog's row and the floors, the block's bytes
against the issue's arithmetic to the digit, the two kernel count files, the
five new readers on a slice recorded from the builder's own traced run of the
cell and on hand-made records — what they read, and that they read nothing
(and do not raise) from a program or a block without it, as the parent of the
PR that added them — and the two controls of ``correct`` at test size.
Every entry is found by NAME."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import blocks, serving
from benchmark.kernels import swa_chunk, swa_decode
from benchmark.layer_metrics._common import load_metric_file

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((ROOT / "benchmark/configs/trinity-mini-ep8-bf16.json").read_text())
LIMITS = json.loads((ROOT / "benchmark/configs/trinity-mini-ep8-bf16.limits.json").read_text())
SLICE = json.loads((ROOT / "benchmark/testdata/trinity_longmix_open_slice.json").read_text())
METRICS = ROOT / "benchmark" / "layer_metrics"
CELL = "trinitymini.longmix-open"
NEW = ["swa_decode_roofline", "swa_chunk_roofline", "kv_window_kept_share",
       "afmoe_expert_ffn_ms", "afmoe_expert_touched_share"]
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def _model():
    return serving.reference_cfg(serving.model_config(CONFIG))


def test_every_published_key_is_in_the_file_and_only_the_cut_differs():
    pub = CONFIG["published"]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():  # the row the driver drew, where the guide is at hand
        row = [json.loads(line) for line in catalog.read_text().splitlines()
               if CONFIG["source"] in line][0]
        assert pub == row["config"] and row["name"] == "Trinity-Mini"
    assert all(k in CONFIG for k in pub)
    differs = {k for k, v in pub.items() if CONFIG[k] != v}
    assert differs == {"vocab_size"} and CONFIG["reduced"] == ["n_experts_held", "vocab_size"]
    assert (CONFIG["n_experts_held"], CONFIG["first_expert"], CONFIG["vocab_size"]) == (16, 0, 25_024)
    assert CONFIG["published_counts"] == {"num_experts": 128, "vocab_size": 200_192}
    assert CONFIG["num_hidden_layers"] == 32 == len(CONFIG["layer_types"])  # the WHOLE depth
    assert CONFIG["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG["name"]][0]
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/trinity-mini-ep8-bf16.json"
    model = _model()  # checked against CONFIGS["trinity-mini"] key by key
    assert (model["num_experts"], model["num_experts_per_tok"], model["route_scale"]) == (128, 8, 2.826)
    assert model["family"] == "qwen2" and CONFIG["block"] == "afmoe" and CONFIG["base"] == "trinity-mini"
    # the floors: the whole depth, 16 >= 8 experts, an eighth of the rows
    assert CONFIG["vocab_size"] * 8 == pub["vocab_size"]
    for width in ("hidden_size", "head_dim", "intermediate_size", "moe_intermediate_size",
                  "num_experts_per_tok", "num_attention_heads", "num_key_value_heads",
                  "num_experts", "sliding_window", "num_dense_layers", "num_shared_experts",
                  "global_attn_every_n_layers"):
        assert CONFIG[width] == pub[width] and width not in CONFIG["reduced"]
    for said in ("published", "reduced_why", "assumed", "deployment", "precision"):
        assert CONFIG[said]
    assumed = CONFIG["assumed"]
    for point in ("output_gate", "qk_norm", "no_rotation_on_global_layers", "four_norms",
                  "bias_on_choice_only", "shared_expert_unscaled", "embedding_scale",
                  "router_dtype"):
        assert "modeling_afmoe.py as the writer of issue 41 knows it" in assumed[point]
    assert "chat_template" in assumed and "eight" in CONFIG["deployment"]
    assert "NOT reduced" in CONFIG["reduced_why"]["num_hidden_layers"]
    assert CONFIG["engine_plan"] == {"speculative": False}
    assert CONFIG["rehearsal"]["base"] == "afmoe-test"
    assert CONFIG["llm"] == {"dtype": "bfloat16", "max_seq_len": 17_408, "page_size": 16,
                             "num_pages": 12_288, "max_batch_slots": 16, "prefill_chunk": 512,
                             "decode_steps": 8}


def test_the_cut_is_the_issues_arithmetic():
    b, model, precision = blocks.load("afmoe").bytes, _model(), CONFIG["precision"]
    assert (b.layers_of(model, b.SLIDING), b.layers_of(model, b.FULL)) == (24, 8)
    # a layer: q, the gate and o 2048 x 4096 each, k and v 2048 x 512: 27.26M
    assert b.attention_params(model) == 3 * 2048 * 4096 + 2 * 2048 * 512 == 27_262_976
    assert b.expert_params(model) == 3 * 2048 * 1024 == 6_291_456  # routed and shared alike
    assert b.dense_ffn_params(model) == 3 * 2048 * 6144 == 37_748_736
    expert_layer = 27_262_976 + 6_291_456 + 16 * 6_291_456  # 134.2M = 268.4 MB
    assert expert_layer == 134_217_728 and 2 * expert_layer == 268_435_456
    dense_layer = 27_262_976 + 37_748_736  # 65.0M = 130.0 MB
    matrices = b.matrix_params_outside_experts(model) + 30 * 16 * b.expert_params(model)
    assert matrices == 30 * expert_layer + 2 * dense_layer
    f32 = b.f32_params(model)
    assert f32 == 32 * (4 * 2048 + 2 * 128) + 30 * (2048 + 1) * 128 + 2048
    # what the pieces count is what the program's own count says it holds
    assert matrices + f32 + 2 * 25_024 * 2048 == serving.model_config(CONFIG).total_params
    weights = matrices * 2 + f32 * 4 + 2 * 25_024 * 2048 * 2
    assert weights == pytest.approx(8.55e9, rel=2e-3)  # 8.05 + 0.26 + 0.21 and the routers
    # the whole model: 26B parameters, 52 GB in bf16
    from runbookai_tpu.models.llama import CONFIGS
    assert CONFIGS["trinity-mini"].total_params * 2 == pytest.approx(52e9, rel=0.01)
    # 2 x 4 x 128 x 2 B = 2,048 B a token and layer: 8 full layers, 24 sliding
    assert b.kv_layer_token_bytes(model) == 2_048
    assert (8 * 2_048, 24 * 2_048) == (16_384, 49_152)
    assert b.window_rows_bound(model, CONFIG["llm"]) == 2_048 + 512 + 2 * 16 == 2_592
    full = 12_288 * 16 * 16_384
    window = 16 * 2_592 * 49_152
    assert full == 3_221_225_472 and window == 2_038_431_744  # 3.22 GB and 2.04 GB
    resident = b.resident_bytes(model, CONFIG["llm"], precision)
    assert resident == int(weights) + full + window == 13_810_326_528  # the issue's 13.8 GB
    assert 0.80 < resident / 17_179_869_184 < 0.81 and resident >= 0.25 * 17_179_869_184
    # every layer paging every position would hold 80,000 tokens in the same bytes
    assert (full + window) // (32 * 2_048) == pytest.approx(80_000, rel=0.005)
    # the program's own pool is what the block states (the null pages aside)
    from runbookai_tpu.engine.kv_cache import WindowSpec
    spec = WindowSpec(24, 2_048, 512, 16)
    assert spec.rows_bound(16) == 2_592 and spec.pages(16) == 16 * 162 + 1
    # a pass: everything outside the held experts once, the head, the keys and
    # values its queries SEE: ONE total, so the sliding layers at no more than
    # a window of it, which no batch can undercut
    assert b.step_bytes(model, 0) == pytest.approx(2.41e9, rel=0.005)
    assert b.step_bytes(model, 1_000) - b.step_bytes(model, 0) == 1_000 * 32 * 2_048
    assert b.step_bytes(model, 50_000) - b.step_bytes(model, 0) == (50_000 * 8 + 2_048 * 24) * 2_048
    assert b.PROGRAMS == {"jit__decode_multi": None, "jit__decode_step": 1}
    assert not hasattr(b, "attention_bytes_per_call")  # the dense kernel's


def test_the_kernel_files_count_and_find_their_events():
    assert swa_decode.bytes_per_call(10_000, 4, 128) == 10_000 * 2_048
    assert swa_chunk.bytes_per_call(2_559, 4, 128) == 2_559 * 2_048
    assert swa_chunk.ops_per_call(1_000_000, 32, 128) == 4 * 1_000_000 * 32 * 128
    for name in SLICE["decode_events"]:
        assert swa_decode.EVENT.search(name) and not swa_chunk.EVENT.search(name), name
    for name in SLICE["chunk_events"]:
        assert swa_chunk.EVENT.search(name) and not swa_decode.EVENT.search(name), name
    for name in SLICE["other_events"]:  # the full layers' calls, the experts' conditional
        assert not swa_decode.EVENT.search(name) and not swa_chunk.EVENT.search(name), name
    assert any(name.startswith("%attn.global") for name in SLICE["other_events"])
    # the dense family's calls have no name of their own: never these
    dense = "%closed_call.12 = bf16[16,28,128] custom-call(s32[16,513] %t, s32[16] %c)"
    assert not swa_decode.EVENT.search(dense) and not swa_chunk.EVENT.search(dense)


def _run(steps=(), trace=None, model=None, block="afmoe", t0=0.0, seconds=51.0,
         traced=(4.0, 9.0)):
    return {"steps": list(steps), "model": model or _model(), "block": blocks.load(block),
            "llm": CONFIG["llm"], "reqs": [], "runtime": {}, "t0": t0, "seconds": seconds,
            "health_before": {"metrics": {}}, "health_after": {"metrics": {}},
            "traced": {"t_start": traced[0], "t_stop": traced[1],
                       "health_start": {"metrics": {}}, "health_stop": {"metrics": {}}},
            "peaks": PEAKS, "trace": trace}


def _read(name, run):
    return load_metric_file(METRICS / f"{name}.py").read(run)


def _step(t, programs, k, window=None, experts=None):
    s = {"step": int(t * 100), "t_start": t, "t_end": t + 0.01, "phases": {}, "program": programs,
         "k": k}
    if window is not None:
        s["window"] = dict({"rows_kept": 0, "rows_context": 0, "rows_kept_max": 0,
                            "rows_released": 0, "rows_seen": 0, "chunk_pairs": 0,
                            "chunk_rows_seen": 0}, **window)
    if experts is not None:
        s["experts"] = experts
    return s


def test_the_readers_on_the_builders_own_slice():
    """``testdata/trinity_longmix_open_slice.json``: the operations and
    programs of one traced slice of the cell on the chip, reduced
    (``trace_reduce``), the step records of that slice, and what the run's
    readers printed."""
    run = _run(SLICE["steps"], trace={"ops": SLICE["ops"], "modules": SLICE["modules"]},
               t0=SLICE["t0"], seconds=SLICE["seconds"], traced=SLICE["traced"])
    for name in ("swa_decode_roofline", "swa_chunk_roofline"):
        value = _read(name, run)
        assert 0 < value <= 105 and value == pytest.approx(SLICE["printed"][name], rel=1e-6)
    assert _read("afmoe_expert_ffn_ms", run) == pytest.approx(
        SLICE["printed"]["afmoe_expert_ffn_ms"], rel=1e-6)
    # the kept share over the slice's records (the run printed the whole window's)
    kept = _read("kv_window_kept_share", run)
    assert 0 < kept < 100 and kept == pytest.approx(SLICE["printed"]["kv_window_kept_share"], rel=0.05)
    # no live sequence's window rows over the bound, at any record
    assert max(s["window"]["rows_kept_max"] for s in SLICE["steps"] if s.get("window")) <= 2_592
    # the decode walk's share, by hand from the same records and events
    recs = [s for s in SLICE["steps"] if s.get("window") and set(s["program"]) <= {"_decode_multi", "_decode_step"}
            and s["program"] and SLICE["traced"][0] <= s["t_start"] <= SLICE["traced"][1]]
    rows = sum(s["k"] * s["window"]["rows_seen"] for s in recs) / sum(s["k"] for s in recs)
    events = [t for n, t in SLICE["ops"].items() if n.startswith("%swa_decode_walk")]
    per_call = sum(t["seconds"] for t in events) / sum(t["count"] for t in events)
    assert _read("swa_decode_roofline", run) == pytest.approx(100 * rows * 2_048 / 819e9 / per_call)
    passes = SLICE["modules"]["jit__decode_multi"]["count"] * 8
    cond_s = sum(t["seconds"] for n, t in SLICE["ops"].items()
                 if n.startswith("%cond") and "f32[16,2048]" in n.split(" conditional(")[0])
    assert _read("afmoe_expert_ffn_ms", run) == pytest.approx(1e3 * cond_s / passes)


def test_the_readers_on_hand_made_records():
    decode = _step(5.0, ["_decode_multi"], 8, {"rows_kept": 12_000, "rows_context": 36_000,
                                               "rows_seen": 10_000})
    late = _step(20.0, ["_decode_multi"], 8, {"rows_kept": 2_000, "rows_context": 2_000,
                                              "rows_seen": 2_000})
    mixed = _step(6.0, ["_mixed_step"], 1, {"rows_kept": 15_000, "rows_context": 40_000,
                                            "rows_seen": 9_000, "chunk_pairs": 1_000_000,
                                            "chunk_rows_seen": 2_559})
    split = _step(7.0, ["_prefill_step", "_decode_multi"], 8,
                  {"rows_kept": 3_000, "rows_context": 4_000, "rows_seen": 3_000,
                   "chunk_pairs": 50_000, "chunk_rows_seen": 400})
    trace = {"ops": {"%swa_decode_walk.47 = bf16[16,32,128] custom-call(s32[16,1089] %t)":
                     {"count": 192, "seconds": 192 * 50e-6},
                     "%swa_chunk_walk.3 = bf16[80,8,32,128] custom-call(s32[80,1089] %t)":
                     {"count": 24, "seconds": 24 * 400e-6},
                     "%attn.global.25 = bf16[16,32,128] custom-call(s32[16,1089] %t)":
                     {"count": 64, "seconds": 1.0},
                     "%conditional.56 = (f32[16,2048]) conditional(s32[] %p, () %t)":
                     {"count": 240, "seconds": 0.072},
                     "%conditional.2 = (f32[640,2048]) conditional(s32[] %p, () %t)":
                     {"count": 30, "seconds": 0.5}},
             "modules": {"jit__decode_multi": {"count": 1, "seconds": 0.12}}}
    run = _run([decode, late, mixed, split], trace=trace)
    # the decode walk: the traced slice's pure decode record alone (10,000 rows a call)
    assert _read("swa_decode_roofline", run) == pytest.approx(100 * (10_000 * 2_048 / 819e9) / 50e-6)
    # the chunk walk: the mixed record; operations 4 x 32 x 128 a pair, the decode rows' too
    ops = 4 * 32 * 128 * (1_000_000 + 9_000) / 197e12
    byts = (2_559 + 9_000) * 2_048 / 819e9
    assert _read("swa_chunk_roofline", run) == pytest.approx(100 * max(ops, byts) / 400e-6)
    # kept over context, the window's pure decode records (not the traced slice's alone)
    assert _read("kv_window_kept_share", run) == pytest.approx(
        100 * (12_000 + 2_000) / (36_000 + 2_000))
    assert _read("afmoe_expert_ffn_ms", run) == pytest.approx(1e3 * 0.072 / 8)
    held = {"held": 600, "zero": 0, "absent": 4200, "touched": 1200, "overflow": 0,
            "passes": 8, "programs": ["_decode_multi"]}
    other = dict(held, programs=["_mixed_step"], passes=1)
    run = _run([_step(5.0, ["_decode_multi"], 8, experts=held), _step(6.0, ["_mixed_step"], 1, experts=other)])
    # decode records only: 1,200 of 8 passes x 30 EXPERT layers (not 32) x 16 held
    assert _read("afmoe_expert_touched_share", run) == pytest.approx(100 * 1200 / (8 * 30 * 16))


@pytest.mark.parametrize("name", NEW)
def test_a_program_or_block_without_it_is_read_as_nothing(name):
    """The parent has no window record and no call of these names; the dense
    and the nemotron_h blocks' models have none of the keys."""
    parent = _run([{"step": 1}, {"step": 2, "t_start": 5.0, "t_end": 5.1, "phases": {},
                                 "program": ["_decode_multi"], "k": 8}],
                  trace={"ops": {}, "modules": {}})
    assert _read(name, parent) is None
    for entry, block in ((BENCH["configs"][0], "dense"),
                         ([c for c in BENCH["configs"] if c["name"].startswith("nemotron")][0],
                          "nemotron_h")):
        other_cfg = json.loads((ROOT / entry["file"]).read_text())
        other = _run([_step(5.0, ["_decode_multi"], 8, experts={
            "held": 1, "zero": 0, "absent": 1, "touched": 1, "overflow": 0, "passes": 8,
            "programs": ["_decode_multi"]})],
                     trace={"ops": SLICE["ops"], "modules": SLICE["modules"]},
                     model=serving.reference_cfg(serving.model_config(other_cfg)), block=block)
        assert _read(name, other) is None


def test_entries_of_the_new_cell_by_name():
    cell = [w for w in BENCH["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG["name"], "longmix-open", 1)
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    want = {"swa_decode_roofline": ("%", "device_trace", "kernels", "higher"),
            "swa_chunk_roofline": ("%", "device_trace", "kernels", "higher"),
            "kv_window_kept_share": ("%", "program_counter", "KV manager", "lower"),
            "afmoe_expert_ffn_ms": ("ms", "device_trace", "kernels", "lower"),
            "afmoe_expert_touched_share": ("%", "program_counter", "model step", "higher")}
    assert sorted(want) == sorted(NEW)
    for name, (unit, source, layer, better) in want.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["better"]) == (unit, source, layer, better)
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
        mod = load_metric_file(METRICS / f"{name}.py")
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            name, unit, layer, "tpot_p50_ms", source)
    for name, m in by_name.items():  # nothing that listed its cells was given this one
        if name not in want and "workloads" in m:
            assert CELL not in m["workloads"]
    traffic = json.loads((ROOT / "benchmark/traffic/longmix-open.json").read_text())
    assert traffic["generator"] == "open_loop" and traffic["check_sample"] == 4
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 4096, "sigma": 0.9,
                                        "min": 512, "max": 16384}
    assert traffic["max_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                                     "min": 64, "max": 512}
    # the longest prompt is eight windows, and fits the context with its answer
    assert traffic["prompt_tokens"]["max"] == 8 * CONFIG["sliding_window"]
    assert (256 + traffic["prompt_tokens"]["max"] + traffic["max_tokens"]["max"]
            < CONFIG["llm"]["max_seq_len"])
    assert traffic["warmup"]["bursts"][-1]["prompt_tokens"] == 16_000
    rate = json.loads((ROOT / f"benchmark/cells/{CELL}.json").read_text())
    assert rate["rate_rps"] > 0 and "knee" in rate["note"] and "sweep" in rate["note"]


def _gaps(seed, lowp):
    """(widest, mean) gap of the program's greedy tokens (bf16 weights and
    pools, the served forward) and of each control's over one sequence of 384
    tokens (twelve windows) at the test preset, over the positions the block
    lets be compared; and the share it does not."""
    import jax.numpy as jnp

    from runbookai_tpu.models import afmoe
    from runbookai_tpu.models.llama import CONFIGS

    cfg = CONFIGS["afmoe-test"]
    block, ref_cfg, t = blocks.load("afmoe"), dataclasses.asdict(cfg), 384
    params = block.weights.make_params(ref_cfg, seed % 2 ** 31, False)
    ids = np.random.default_rng(seed).integers(0, 256, size=t).tolist()
    ref, skip = (np.asarray(a) for a in block.forward.logits(params, ref_cfg, ids, t))
    pages = t // 16

    def side():
        return {"full": jnp.zeros((cfg.n_kind(afmoe.FULL), (pages + 1) * 16, 2, 32), jnp.bfloat16),
                "window": jnp.zeros((cfg.n_kind(afmoe.SLIDING), (pages + 1) * 16, 2, 32),
                                    jnp.bfloat16)}

    tables = np.zeros((1, 2 * (pages + 1)), np.int32)
    tables[0, :pages] = tables[0, pages + 1:2 * pages + 1] = np.arange(1, pages + 1)
    served, *_ = afmoe.forward_impl(
        params, cfg, jnp.asarray([ids], jnp.int32), jnp.arange(t, dtype=jnp.int32)[None],
        side(), side(), jnp.asarray(tables), jnp.asarray([t]), page_size=16)
    rows = np.arange(t)

    def gap(lg):
        g = (ref.max(axis=1) - ref[rows, np.asarray(lg).argmax(axis=1)])[~skip]
        return float(g.max()), float(g.mean())

    return (gap(served[0]), {k: gap(block.forward.logits(params, ref_cfg, ids, t, k)[0])
                             for k in lowp}, float(skip.mean()))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_both_controls_read_over_the_limit_and_the_served_path_under_it(seed):
    """The reference in fp8 where the configuration states bfloat16, and the
    reference with every layer attending to every position (``no_window``:
    the window ignored, or released rows still read), each come out NOT
    correct by the cell's own ``logit_gap`` limit, and the served bf16 path
    correct, over the positions the block lets be compared; rounding the
    paged cache alone to fp8 moves least (why ``correct`` also compares the
    bytes)."""
    (sound_max, sound_mean), control, share = _gaps(seed, ["fp8", "kv_fp8", "no_window"])
    assert sound_max <= LIMITS["logit_gap"] < min(control["fp8"][0], control["no_window"][0]), (
        sound_max, control)
    assert control["fp8"][1] > 5 * sound_mean and control["no_window"][1] > 50 * sound_mean
    assert control["kv_fp8"][1] < control["fp8"][1]
    assert 0 < share <= LIMITS["not_comparable_share"]


def test_no_window_is_the_same_pass_without_the_edge_and_moves_no_margin():
    """``no_window`` at contexts UNDER the window is the sound pass bit for
    bit; past it every position differs; the mask of positions not compared
    is the reference's own sound pass's (no control moves it)."""
    from runbookai_tpu.models.llama import CONFIGS

    cfg = CONFIGS["afmoe-test"]
    block, ref_cfg = blocks.load("afmoe"), dataclasses.asdict(cfg)
    params = block.weights.make_params(ref_cfg, 5, False)
    ids = np.random.default_rng(5).integers(0, 256, size=96).tolist()
    sound, margin = block.forward.logits_and_margins(params, ref_cfg, ids, 96)
    wide, _ = block.forward.logits_and_margins(params, ref_cfg, ids, 96, "no_window")
    off = np.abs(np.asarray(sound) - np.asarray(wide)).max(axis=1)
    assert off[:cfg.sliding_window].max() == 0 and off[cfg.sliding_window + 4:].min() > 1e-3
    _, skip = block.forward.logits(params, ref_cfg, ids, 96)
    assert np.array_equal(np.asarray(skip), np.asarray(margin) < block.forward.TOLERANCE)
    assert block.forward.TOLERANCE == 0.003 and 0 < np.asarray(skip).mean() < 1
