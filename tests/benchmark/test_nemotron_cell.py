"""The files the ``nemotron_h`` block and its cell bring (CPU, tier-1): the
configuration against the published config and the floors, the block's
bytes against the issue's arithmetic, the new readers on operations reduced
from the builder's own trace of the cell and on hand-made records — what
they read, and that they read nothing (and do not raise) from a program or
a block without it, as the parent of the PR that added them — and the fp8
control of ``correct`` at test size. Every entry is found by NAME."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import blocks, serving
from benchmark.kernels import ssm_chunk, ssm_step
from benchmark.layer_metrics._common import load_metric_file

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((ROOT / "benchmark/configs/nemotron-3-nano-30b-ep8-bf16.json").read_text())
LIMITS = json.loads((ROOT / "benchmark/configs/nemotron-3-nano-30b-ep8-bf16.limits.json").read_text())
SLICE = json.loads((ROOT / "benchmark/testdata/nemotron_reason_open_slice.json").read_text())
METRICS = ROOT / "benchmark" / "layer_metrics"
CELL = "nemotron3nano.reason-open"
NEW = ["ssm_decode_roofline", "ssm_chunk_roofline", "relu2_expert_ffn_ms",
       "relu2_expert_touched_share"]
STEP_SIZES = (23, 48, 64, 64, 128, 6144, 4)       # Mamba layers, slots, heads, P, N, conv channels, width
CHUNK_SIZES = (48, 64, 64, 128, 8, 128, 6144, 4)  # slots, heads, P, N, groups, chunk, conv channels, width


def _model():
    return serving.reference_cfg(serving.model_config(CONFIG))


def test_every_published_key_is_in_the_file_and_only_the_cut_differs():
    pub = CONFIG["published"]
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():  # the row the driver drew, where the guide is at hand
        row = [json.loads(line) for line in catalog.read_text().splitlines()
               if CONFIG["source"] in line][0]
        assert pub == row["config"]
    assert all(k in CONFIG for k in pub)
    differs = {k for k, v in pub.items() if CONFIG[k] != v}
    assert differs == {"vocab_size"} and CONFIG["reduced"] == ["n_experts_held", "vocab_size"]
    assert (CONFIG["n_experts_held"], CONFIG["vocab_size"]) == (16, 16_384)
    assert CONFIG["num_hidden_layers"] == 52 == len(CONFIG["hybrid_override_pattern"])
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG["name"]][0]
    assert entry["reduced"] == CONFIG["reduced"] and entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/nemotron-3-nano-30b-ep8-bf16.json"
    model = _model()  # checked against CONFIGS["nemotron-3-nano-30b-a3b"] key by key
    assert (model["n_routed_experts"], model["num_experts_per_tok"]) == (128, 6)
    assert model["family"] == "qwen2" and model["state_snapshots"] == 8
    # the floors: the whole pattern, 16 >= 8 experts, an eighth of the rows
    assert CONFIG["vocab_size"] * 8 == pub["vocab_size"]
    for width in ("hidden_size", "head_dim", "mamba_head_dim", "mamba_num_heads", "n_groups",
                  "ssm_state_size", "conv_kernel", "chunk_size", "moe_intermediate_size",
                  "moe_shared_expert_intermediate_size", "num_experts_per_tok",
                  "num_attention_heads", "num_key_value_heads", "n_routed_experts"):
        assert CONFIG[width] == pub[width] and width not in CONFIG["reduced"]
    for said in ("published", "reduced_why", "assumed", "deployment", "precision"):
        assert CONFIG[said]
    assert "no_rotary_embedding" in CONFIG["assumed"] and "eight" in CONFIG["deployment"]
    assert CONFIG["engine_plan"] == {"speculative": False}
    assert CONFIG["rehearsal"]["base"] == "nemotron-h-test"
    assert CONFIG["llm"] == {"dtype": "bfloat16", "max_seq_len": 8192, "page_size": 16,
                             "num_pages": 8192, "max_batch_slots": 48, "prefill_chunk": 512,
                             "decode_steps": 8}


def test_the_cut_is_the_issues_arithmetic():
    b, model, precision = blocks.load("nemotron_h").bytes, _model(), CONFIG["precision"]
    assert b.counts(model) == {"M": 23, "E": 23, "*": 6, "d_inner": 4096, "conv": 6144}
    # a layer: M 38.74M (27.70M W_in, 11.01M W_out), * 23.40M, E 9.98M an expert, 19.96M shared
    assert 2688 * (4096 + 6144 + 64) == 27_697_152 and 4096 * 2688 == 11_010_048
    assert b.mamba_matrix_params(model) == 27_697_152 + 11_010_048 + 5 * 6144
    assert b.attention_matrix_params(model) == 23_396_352
    assert b.expert_params(model) == 9_977_856 and b.shared_expert_params(model) == 19_955_712
    matrices = b.matrix_params_outside_experts(model) + 23 * 16 * b.expert_params(model)
    # what the pieces count is what the program's own count says it holds
    assert (matrices + b.f32_params(model) + 2 * 16_384 * 2688
            == serving.model_config(CONFIG).total_params == 5_258_420_544)
    weights = matrices * 2 + b.f32_params(model) * 4 + 2 * 16_384 * 2688 * 2
    assert weights == pytest.approx(10.53e9, rel=2e-3)  # the issue's 10.5 GB, the router at float32
    # the whole model: 31.6B parameters, 63 GB in bf16
    from runbookai_tpu.models.llama import CONFIGS
    assert CONFIGS["nemotron-3-nano-30b-a3b"].total_params == pytest.approx(31.58e9, rel=1e-3)
    slot = b.state_slot_bytes(model, precision)
    assert slot == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 4) == 49_930_240  # 49.9 MB
    assert 48 * slot == pytest.approx(2.40e9, rel=2e-3) and 8 * slot == pytest.approx(0.40e9, rel=2e-3)
    assert b.kv_token_bytes(model) == 6_144
    pool = 8192 * 16 * 6_144
    assert pool == pytest.approx(0.81e9, rel=0.01)
    resident = b.resident_bytes(model, CONFIG["llm"], precision)
    assert resident == int(weights) + 56 * slot + pool == 14_134_555_904  # the issue's 14.1 GB
    assert 0.82 < resident / 17_179_869_184 < 0.83 and resident >= 0.25 * 17_179_869_184
    # the pools are IN the number
    bare = dataclasses.replace(serving.model_config(CONFIG), state_snapshots=0)
    less = b.resident_bytes(serving.reference_cfg(bare), dict(CONFIG["llm"], max_batch_slots=0),
                            precision)
    assert resident - less == 56 * slot
    # a pass: everything outside the held experts once, the head, the live keys and values
    assert b.step_bytes(model, 0) == pytest.approx(3.10e9, rel=0.005)
    assert b.step_bytes(model, 50_000) - b.step_bytes(model, 0) == 50_000 * 6_144
    assert b.PROGRAMS == {"jit__decode_multi": None, "jit__decode_step": 1}
    assert not hasattr(b, "attention_bytes_per_call")  # the dense kernel's


def test_the_kernel_files_count_and_find_their_events():
    # the step: a live row's state read and written, its tail, its inputs
    row = 2 * 64 * 64 * 128 * 4 + 2 * 3 * 6144 * 4 + (6144 + 4096 + 64) * 4
    assert ssm_step.bytes_per_call(10, *STEP_SIZES[2:]) == 10 * row
    for name in SLICE["step_events"]:
        assert ssm_step.is_event(name, *STEP_SIZES) and not ssm_chunk.is_event(name, *CHUNK_SIZES), name
    for name in SLICE["chunk_events"]:
        assert ssm_chunk.is_event(name, *CHUNK_SIZES) and not ssm_step.is_event(name, *STEP_SIZES), name
    for name in SLICE["other_events"]:
        assert not ssm_step.is_event(name, *STEP_SIZES), name
        assert not ssm_chunk.is_event(name, *CHUNK_SIZES), name
    loop = ("%while.7 = (s32[], bf16[48,1,2688], f32[23,48,64,64,128], f32[23,48,3,6144]) "
            "while((s32[], bf16[48,1,2688]) %t)")
    assert ssm_step.pools(*STEP_SIZES).search(loop) and not ssm_step.is_event(loop, *STEP_SIZES)
    write_back = ("%fusion.1116 = f32[23,48,64,64,128] fusion(f32[23,48,64,64,128] %a, "
                  "f32[1,64,64,128] %bitcast.1449, pred[] %b)")  # the chunked rule's row into the pool
    assert ssm_chunk.is_event(write_back, *CHUNK_SIZES) and not ssm_step.is_event(write_back, *STEP_SIZES)
    snapshot = ("%dynamic-update-slice.3 = f32[23,8,64,64,128] dynamic-update-slice("
                "f32[23,8,64,64,128] %p, f32[23,1,64,64,128] %row, s32[] %c)")
    assert not ssm_step.is_event(snapshot, *STEP_SIZES)
    assert not ssm_step.is_event(SLICE["step_events"][0], 23, 16, 64, 64, 128, 6144, 4)
    per_token = 2 * (64 * 128 * 8 + 64 * 64 * 64 + 2 * 64 * 128 * 64)
    assert ssm_chunk.ops_per_call(100, 64, 64, 128, 8, 128) == 100 * per_token
    assert ssm_chunk.bytes_per_call(100, 1, 64, 64, 128, 6144) == (
        100 * ((6144 + 64) * 4 + 4096 * 4) + 2 * 64 * 64 * 128 * 4)


def _run(steps=(), trace=None, model=None, block="nemotron_h", health=None):
    reqs = [{"status": 200, "error": None, "text": "x", "times": [1.0, 9.0],
             "prompt_tokens": 700, "done_marker": True, "terminated": True,
             "completion_tokens": 1, "finish": "length", "max_tokens": 1}] * 20
    state = {"prefill_tokens": 0}
    before, start, stop, after = (dict(state, **h) for h in (health or [{}] * 4))
    return {"steps": list(steps), "model": model or _model(), "block": blocks.load(block),
            "llm": CONFIG["llm"], "reqs": reqs, "runtime": {},
            "health_before": {"metrics": before}, "health_after": {"metrics": after},
            "traced": {"t_start": 4.0, "t_stop": 9.0, "health_start": {"metrics": start},
                       "health_stop": {"metrics": stop}},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}, "trace": trace}


def _read(name, run):
    return load_metric_file(METRICS / f"{name}.py").read(run)


def test_the_device_readers_on_the_builders_own_slice():
    """``testdata/nemotron_reason_open_slice.json``: the operations and
    programs of one traced slice of the cell on the chip, reduced
    (``trace_reduce.totals``), with what the run's readers printed."""
    run = _run(trace={"ops": SLICE["ops"], "modules": SLICE["modules"]},
               health=[{}, {"prefill_tokens": 0},
                       {"prefill_tokens": SLICE["traced_prefill_tokens"]}, {}])
    run["reqs"] = run["reqs"][:1] * SLICE["live_rows"]
    run["traced"].update(t_start=0.0, t_stop=SLICE["slice_seconds"])
    for name in ("ssm_decode_roofline", "ssm_chunk_roofline"):
        assert 0 < _read(name, run) < 100
        # what the run itself printed, from its own count of live rows and tokens
        assert _read(name, run) == pytest.approx(SLICE["printed"][name], rel=0.25)
    assert _read("relu2_expert_ffn_ms", run) == pytest.approx(
        SLICE["printed"]["relu2_expert_ffn_ms"], rel=1e-6)
    step_s = sum(t["seconds"] for n, t in SLICE["ops"].items() if ssm_step.is_event(n, *STEP_SIZES))
    passes = SLICE["modules"]["jit__decode_multi"]["count"] * 8
    calls = (passes + SLICE["modules"].get("jit__mixed_step", {"count": 0})["count"]) * 23
    need = ssm_step.bytes_per_call(SLICE["live_rows"], *STEP_SIZES[2:]) / 819e9
    assert _read("ssm_decode_roofline", run) == pytest.approx(100 * need / (step_s / calls))
    cond_s = sum(t["seconds"] for n, t in SLICE["ops"].items()
                 if n.startswith("%cond") and " conditional(" in n and "f32[48,2688]" in n.split(" conditional(")[0])
    assert _read("relu2_expert_ffn_ms", run) == pytest.approx(1e3 * cond_s / passes)


def test_the_counter_readers_on_hand_made_records():
    decode = {"held": 600, "zero": 0, "absent": 4200, "touched": 1500, "overflow": 0,
              "passes": 8, "programs": ["_decode_multi"]}
    mixed = {"held": 400, "zero": 0, "absent": 3000, "touched": 300, "overflow": 0,
             "passes": 1, "programs": ["_mixed_step"]}
    run = _run([{"step": 1, "experts": decode}, {"step": 2}, {"step": 3, "experts": mixed}])
    # decode records only: 1,500 of 8 passes x 23 EXPERT layers (not 52) x 16 held
    assert _read("relu2_expert_touched_share", run) == pytest.approx(100 * 1500 / (8 * 23 * 16))
    assert _read("relu2_expert_touched_share", _run([{"step": 3, "experts": mixed}])) is None
    hand = {"ops": {"%conditional.5 = f32[48,2688] conditional(s32[] %p, () %t)":
                    {"count": 184, "seconds": 0.0552},
                    "%cond.9 = (f32[48,2688]) conditional(pred[] %p, () %t)": {"count": 184, "seconds": 0.0368},
                    "%conditional.6 = f32[896,2688] conditional(s32[] %p, () %t)":  # a mixed step's
                    {"count": 23, "seconds": 0.05}},
            "modules": {"jit__decode_multi": {"count": 2, "seconds": 0.3}}}
    assert _read("relu2_expert_ffn_ms", _run(trace=hand)) == pytest.approx(1e3 * 0.092 / 16)


@pytest.mark.parametrize("name", NEW)
def test_a_program_or_block_without_it_is_read_as_nothing(name):
    """The parent has no state-shaped operation of this model and no record
    of it; the dense and the qwen3next blocks' models have none of the keys."""
    parent = _run([{"step": 1}, {"step": 2}], trace={"ops": {}, "modules": {}})
    for h in ("health_before", "health_after"):
        parent[h] = {"metrics": {"prefill_tokens": 5}}
    assert _read(name, parent) is None
    for entry, block in ((BENCH["configs"][0], "dense"),
                         ([c for c in BENCH["configs"] if c["name"].startswith("qwen3-next")][0],
                          "qwen3next")):
        other_cfg = json.loads((ROOT / entry["file"]).read_text())
        other = _run([{"step": 1, "experts": {"held": 1, "zero": 0, "absent": 1, "touched": 1,
                                              "overflow": 0, "passes": 8,
                                              "programs": ["_decode_multi"]}}],
                     trace={"ops": SLICE["ops"], "modules": SLICE["modules"]},
                     model=serving.reference_cfg(serving.model_config(other_cfg)), block=block)
        assert _read(name, other) is None


def test_entries_of_the_new_cell_by_name():
    cell = [w for w in BENCH["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG["name"], "reason-open", 1)
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    want = {"ssm_decode_roofline": ("%", "device_trace", "kernels"),
            "ssm_chunk_roofline": ("%", "device_trace", "kernels"),
            "relu2_expert_ffn_ms": ("ms", "device_trace", "kernels"),
            "relu2_expert_touched_share": ("%", "program_counter", "model step")}
    for name, (unit, source, layer) in want.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
        assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_ms"
        mod = load_metric_file(METRICS / f"{name}.py")
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            name, unit, layer, "tpot_p50_ms", source)
    for name, m in by_name.items():  # nothing that listed its cells was given this one
        if name not in want and "workloads" in m:
            assert CELL not in m["workloads"]
    # the traffic file is the tree's, as it stands; the prompts fit the context
    traffic = json.loads((ROOT / "benchmark/traffic/reason-open.json").read_text())
    assert traffic["generator"] == "open_loop" and traffic["check_sample"] == 4
    assert (64 + traffic["prompt_tokens"]["max"] + traffic["max_tokens"]["max"] + 256
            < CONFIG["llm"]["max_seq_len"])
    rate = json.loads((ROOT / f"benchmark/cells/{CELL}.json").read_text())
    assert rate["rate_rps"] > 0 and "knee" in rate["note"] and "sweep" in rate["note"]


def _gaps(seed, lowp):
    """(widest, mean) gap of the program's greedy tokens (bf16 weights, the
    served forward) and of each control's over one sequence of 384 tokens at
    the test preset, over the positions the block lets be compared; and the
    share it does not."""
    import jax.numpy as jnp

    from runbookai_tpu.models import nemotron_h
    from runbookai_tpu.models.llama import CONFIGS

    cfg = CONFIGS["nemotron-h-test"]
    block, ref_cfg, t = blocks.load("nemotron_h"), dataclasses.asdict(cfg), 384
    params = block.weights.make_params(ref_cfg, seed % 2 ** 31, False)
    ids = np.random.default_rng(seed).integers(0, 256, size=t).tolist()
    ref, skip = (np.asarray(a) for a in block.forward.logits(params, ref_cfg, ids, t))
    kv = [jnp.zeros((cfg.n_kind("*"), 32 * 16, cfg.num_key_value_heads, cfg.head_dim),
                    jnp.bfloat16) for _ in range(2)]
    served, *_ = nemotron_h.forward_impl(
        params, cfg, jnp.asarray([ids], jnp.int32), jnp.arange(t, dtype=jnp.int32)[None],
        *kv, jnp.arange(1, 26, dtype=jnp.int32)[None], jnp.asarray([t]), page_size=16,
        state=nemotron_h.empty_state(cfg, 1), state_rows=jnp.asarray([0]))
    rows = np.arange(t)

    def gap(lg):
        g = (ref.max(axis=1) - ref[rows, np.asarray(lg).argmax(axis=1)])[~skip]
        return float(g.max()), float(g.mean())

    return (gap(served[0]), {k: gap(block.forward.logits(params, ref_cfg, ids, t, k)[0])
                             for k in lowp}, float(skip.mean()))


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_the_fp8_control_reads_over_the_limit_and_the_served_path(seed):
    """The reference in fp8 where the configuration states bfloat16 comes
    out NOT correct by the cell's own ``logit_gap_mean`` limit and the
    served bf16 path correct, each with 1.25x of room, over the positions
    the block lets be compared (a held expert no nearer a router's cut than
    ``TOLERANCE``); the WIDEST gap of the served path is a draw from a swap's
    tail (0.17 on one seed, 0.95 on the other) and decides nothing; rounding
    the paged cache alone to fp8 moves least (why ``correct`` also compares
    the bytes), and the state rounded to bfloat16 after every token moves
    nothing a token hangs on."""
    from benchmark.reference import check

    (sound_max, sound_mean), control, share = _gaps(seed, ["fp8", "kv_fp8", "state_bf16"])
    assert check.deciding(LIMITS) == ["logit_gap_mean"]
    assert 1.25 * sound_mean <= LIMITS["logit_gap_mean"] <= control["fp8"][1] / 1.25, (
        sound_mean, control)
    assert control["fp8"][1] > 5 * sound_mean and sound_max < control["fp8"][0]
    assert control["kv_fp8"][1] < sound_mean and control["state_bf16"][1] < sound_mean
    assert 0 < share <= LIMITS["not_comparable_share"]


def test_a_position_is_not_comparable_by_the_narrowest_of_its_layers():
    """``logits`` at the test preset (six expert layers): not comparable
    where the narrowest ``cut_margin`` of the expert layers is under
    ``TOLERANCE``; the mask is the reference's own (no control moves it)."""
    from runbookai_tpu.models.llama import CONFIGS

    block = blocks.load("nemotron_h")
    forward, ref_cfg = block.forward, dataclasses.asdict(CONFIGS["nemotron-h-test"])
    params = block.weights.make_params(ref_cfg, 5, False)
    ids = np.random.default_rng(5).integers(0, 256, size=256).tolist()
    out, skip = forward.logits(params, ref_cfg, ids, 256)
    same, margin = forward.logits_and_margins(params, ref_cfg, ids, 256)
    assert forward.TOLERANCE == 0.005 and np.array_equal(np.asarray(out), np.asarray(same))
    assert (np.asarray(skip) == (np.asarray(margin) < forward.TOLERANCE)).all()
    assert 0 < np.asarray(skip).sum() < 256 and float(np.asarray(margin).min()) >= 0


def test_the_cut_margin_on_a_hand_made_router():
    """``cut_margin``: experts 2-3 of 6 held, the two largest of ``s + b``
    chosen (the rule ``blocks/joyai`` has; this block's own copy)."""
    import jax.numpy as jnp

    forward = blocks.load("nemotron_h").forward

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


def test_the_limits_file_holds_sound_and_control_readings():
    """Decided by the MEAN gap over the positions that compare since the
    check refused a sound run's widest gap (1.819 on 1.5, seed 21176564):
    the limit stands 1.25x or more over the largest sound mean and under the
    smallest control's that the comment lists."""
    assert set(LIMITS) >= {"comment", "logit_gap_mean", "not_comparable_share"}
    assert "logit_gap" not in LIMITS
    for word in ("sound", "fp8", "kv_cache_dtype", "1.25", "TOLERANCE", "21176564",
                 "1.819", "logit_gap_p99"):
        assert word in LIMITS["comment"], word
    assert "resident_bytes_short" not in LIMITS  # the default's: 0, exact
    assert 0.5 < LIMITS["not_comparable_share"] < 1
    sound_max, control_min = 0.044, 0.429  # the comment's readings
    assert 1.25 * sound_max <= LIMITS["logit_gap_mean"] <= control_min / 1.25
