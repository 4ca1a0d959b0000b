"""Serving-plan autotuner: cost-model parity, search, plan artifacts.

The contracts this file pins:

- the cost model's residency predictions are EQUAL to
  ``memory_plan.plan_serving`` (delegation, not re-derivation), and its
  per-dispatch byte estimates match a live engine's actual allocations
  within the memory-plan tolerance (the ``hlo_bytes`` measured figures,
  for the shapes both cover);
- the search prunes infeasible and dominated points and the measured
  winner can never regress the hand-picked baseline (it competes);
- plan artifacts round-trip (tune → validate → from_config), explicit
  YAML keys override plan values, model mismatches are refused, and
  every checked-in ``plans/*.json`` validates — with unknown schema
  versions rejected, never half-read;
- a plan resolves to the same EngineConfig as the equivalent explicit
  fields, through ``EngineConfig.from_plan`` and through ``llm.plan``
  (byte-identical streams); its budget is per replica;
- ``runbook metrics --trace`` recovers the PR-4 dispatch-kind counters
  from a span JSONL alone.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from runbookai_tpu.autotune.cost_model import (
    HARDWARE,
    Candidate,
    CostModel,
    Workload,
    smoke_space,
)
from runbookai_tpu.autotune.plan import (
    PLAN_SCHEMA_VERSION,
    PlanArtifact,
    apply_plan_to_llm,
    engine_config_dict,
    engine_only_overrides,
    load_plan,
    save_plan,
    validate_plan,
)
from runbookai_tpu.autotune.search import analytic_prune, pareto_front, tune
from runbookai_tpu.engine.engine import EngineConfig, EngineCore
from runbookai_tpu.engine.hlo_bytes import kv_pool_nbytes, param_nbytes
from runbookai_tpu.engine.memory_plan import plan_serving
from runbookai_tpu.models.llama import CONFIGS, init_params
from runbookai_tpu.models.quant import quantize_params
from runbookai_tpu.utils.tokens import ByteTokenizer

REPO = Path(__file__).resolve().parents[1]
CFG = CONFIGS["llama3-test"]


def make_core(kv_dtype=jnp.bfloat16, **kw):
    params = quantize_params(init_params(jax.random.PRNGKey(0), CFG,
                                         dtype=jnp.bfloat16))
    d = dict(page_size=4, num_pages=48, max_batch_slots=4, prefill_chunk=8,
             max_seq_len=128, block_pages=4, kv_dtype=kv_dtype)
    d.update(kw)
    return EngineCore(CFG, params, ByteTokenizer(), EngineConfig(**d))


# ------------------------------------------------------ cost-model parity


def test_residency_is_memory_plan_exactly():
    """The autotuner must DELEGATE residency to plan_serving — equal
    ServingPlan objects for every kv dtype, never a re-derivation that
    can drift from the arithmetic the engine and docs quote."""
    cm = CostModel(CONFIGS["llama3-8b-instruct"], HARDWARE["v5e"],
                   weights="int8")
    for kv_name, (kv_b, sc_b) in (("bf16", (2, 0)), ("fp8", (1, 0)),
                                  ("int8", (1, 4)), ("auto", (2, 0))):
        cand = Candidate(kv_dtype=kv_name, max_batch_slots=8,
                         max_seq_len=32768, tp=1)
        expect = plan_serving(
            CONFIGS["llama3-8b-instruct"], max_seq_len=32768, batch=8,
            tp=1, weights="int8", kv_dtype_bytes=kv_b, kv_scale_bytes=sc_b,
            hbm_bytes=HARDWARE["v5e"].hbm_bytes)
        assert cm.residency(cand) == expect


def test_dispatch_bytes_match_live_allocations():
    """Per-dispatch byte estimate vs the ACTUAL allocated weights tree +
    KV pool of a live engine (the hlo_bytes measured-figure contract):
    KV pool bytes exact, total within the 15% memory-plan weight
    tolerance."""
    cm = CostModel(CFG, HARDWARE["v5e"], weights="int8")
    for kv_name, kv_dtype in (("bf16", jnp.bfloat16),
                              ("fp8", jnp.float8_e4m3fn),
                              ("int8", jnp.int8)):
        core = make_core(kv_dtype=kv_dtype)
        cand = Candidate(page_size=4, num_pages=48, max_batch_slots=4,
                         kv_dtype=kv_name, max_seq_len=128)
        actual_pool = kv_pool_nbytes(core)
        assert cm.kv_pool_bytes(cand) == pytest.approx(actual_pool), kv_name
        actual = param_nbytes(core.params) + actual_pool
        est = cm.decode_dispatch_bytes(cand)
        assert abs(est - actual) / actual <= 0.15, (kv_name, est, actual)


def test_fp8_kv_halves_pool_estimate_exactly():
    cm = CostModel(CFG, HARDWARE["v5e"], weights="int8")
    c16 = Candidate(page_size=4, num_pages=48, kv_dtype="bf16")
    c8 = Candidate(page_size=4, num_pages=48, kv_dtype="fp8")
    assert cm.kv_pool_bytes(c8) * 2 == cm.kv_pool_bytes(c16)


# ---------------------------------------------------------------- search


def test_analytic_prune_feasibility_and_domination():
    cfg8 = CONFIGS["llama3-8b-instruct"]
    cm = CostModel(cfg8, HARDWARE["v5e"], weights="int8")
    w = Workload(prompt_len=512, output_len=128, concurrency=16)
    # A pool bigger than the 16GB chip can hold must be pruned as
    # infeasible with the memory-plan explanation in the reason.
    whale = cm.score(Candidate(num_pages=65536, kv_dtype="bf16"), w)
    assert not whale.feasible
    assert "budget" in whale.reason
    sane = cm.score(Candidate(num_pages=1024, kv_dtype="fp8"), w)
    assert sane.feasible and sane.decode_tok_s > 0

    kept = analytic_prune([whale, sane], top_k=4)
    assert whale not in kept and sane in kept

    # Dominated-point elimination: worse on both axes loses.
    slower = cm.score(Candidate(num_pages=1024, kv_dtype="fp8",
                                decode_steps_per_dispatch=1,
                                max_batch_slots=4), w)
    assert slower.feasible
    front = pareto_front([sane, slower])
    if (sane.decode_tok_s > slower.decode_tok_s
            and sane.ttft_ms <= slower.ttft_ms):
        assert slower not in front
    assert sane in front

    from runbookai_tpu.autotune.cost_model import SearchSpace

    ests = cm.score_many(SearchSpace().candidates(), w)
    kept = analytic_prune(ests, top_k=3)
    assert 1 <= len(kept) <= 3 and all(e.feasible for e in kept)
    # Ranked by predicted throughput, best first.
    assert kept == sorted(kept, key=lambda e: e.decode_tok_s,
                          reverse=True)


def test_tp_factorization_feasibility():
    """The 70B tp16 = kv8×pg2 plan must be feasible; an unalignable tp
    must be pruned with the kv_split explanation."""
    cfg70 = CONFIGS["llama3-70b-instruct"]
    cm = CostModel(cfg70, HARDWARE["v5e"], weights="int8")
    w = Workload(prompt_len=512, output_len=128, concurrency=8)
    ok = cm.score(Candidate(tp=16, num_pages=2048, kv_dtype="fp8",
                            max_seq_len=8192), w)
    assert ok.feasible, ok.reason
    assert ok.residency.kv_shards == 8 and ok.residency.pg_shards == 2
    bad = cm.score(Candidate(tp=256), w)
    assert not bad.feasible and "tp factorization" in bad.reason


# ----------------------------------------------- tune: measured round-trip


@pytest.fixture(scope="module")
def tuned(tmp_path_factory):
    """ONE bounded smoke sweep shared by the round-trip tests (the
    acceptance path: `runbook tune` → plan → validate → from_config)."""
    out = tmp_path_factory.mktemp("plans") / "smoke.json"
    workload = Workload(prompt_len=48, output_len=12, concurrency=4)
    baseline = Candidate(page_size=4, num_pages=256, max_batch_slots=4,
                         prefill_chunk=32, kv_dtype="auto",
                         max_seq_len=256)
    return tune("llama3-test", workload, HARDWARE["cpu"],
                smoke_space(), weights="bf16", top_k=1,
                baseline=baseline, n_requests=2, new_tokens=8,
                budget_s=240.0, out=out), out


def test_tune_emits_valid_plan_in_bounded_time(tuned):
    result, out = tuned
    data = json.loads(out.read_text())
    assert validate_plan(data) == []
    plan = load_plan(out)
    assert plan.model == "llama3-test"
    assert plan.schema_version == PLAN_SCHEMA_VERSION
    # Provenance carries the full loop: cost scores AND measured figures.
    assert plan.provenance["cost_model"]["candidates_scored"] > 0
    assert plan.provenance["measured"]["decode_tok_s"] > 0
    assert plan.provenance["git_sha"]


def test_tune_winner_never_regresses_baseline(tuned):
    """The hand-picked default competes in the measured phase, so the
    emitted plan's figure is >= the baseline's by construction — the
    no-regression acceptance criterion, pinned."""
    result, _ = tuned
    measured = result.plan.provenance["measured"]
    assert measured["decode_tok_s"] >= measured["baseline_decode_tok_s"]
    assert result.baseline_measured["is_baseline"] is True
    # Every arm recorded dispatch attribution for trace cross-checks.
    for arm in result.measured:
        assert set(arm["dispatches"]) == {"prefill_steps",
                                          "decode_dispatches",
                                          "mixed_steps"}


def _measure(cand, **kw):
    from runbookai_tpu.autotune.search import measure_candidate

    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    return measure_candidate(
        CFG, params, ByteTokenizer(), cand,
        Workload(prompt_len=24, output_len=8, concurrency=2),
        n_requests=2, new_tokens=8, **kw)


@pytest.mark.parametrize("dp", [1, 2])
def test_measure_candidate_window_excludes_the_warmup(monkeypatch, dp):
    """``measure_candidate`` forgets the warm-up through the engine's own
    ``reset_metrics()``: once a core (every replica of a dp candidate),
    leaving every counter, both histograms and the flight ring at zero,
    so the figures describe the measured window alone."""
    cand = Candidate(page_size=4, num_pages=64, max_batch_slots=2,
                     prefill_chunk=32, kv_dtype="auto", max_seq_len=128,
                     dp_replicas=dp)
    after_reset = []
    real = EngineCore.reset_metrics

    def spy(core):
        warm = dict(core.metrics)
        assert warm["decode_tokens"] > 0 and len(core.flight) > 0
        real(core)
        after_reset.append((dict(core.metrics), len(core.flight),
                            core.hist_ttft.count, core.hist_tpot.count,
                            core._flight_kv_mark, warm))

    monkeypatch.setattr(EngineCore, "reset_metrics", spy)
    figures = _measure(cand)
    assert len(after_reset) == dp
    for metrics, flight_len, ttft_n, tpot_n, mark, _ in after_reset:
        assert not any(metrics.values()), metrics
        assert (flight_len, ttft_n, tpot_n, mark) == (0, 0, 0, (0, 0))
    # The window's dispatches, not the warm-up's on top: with the reset
    # taken out the same run counts strictly more.
    monkeypatch.setattr(EngineCore, "reset_metrics", lambda core: None)
    unforgotten = _measure(cand)
    assert sum(figures["dispatches"].values()) < \
        sum(unforgotten["dispatches"].values())


def test_measure_candidate_needs_nothing_from_the_repository_root(
        monkeypatch, tmp_path):
    """`runbook tune`'s measured refinement runs from an installed
    package: with the repository's root off ``sys.path`` (and not the
    working directory) it loads no module by path and imports no
    ``bench``."""
    import importlib.util
    import sys

    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(sys, "path", [
        p for p in sys.path
        if p and Path(p).resolve() != REPO])
    monkeypatch.delitem(sys.modules, "bench", raising=False)

    def no_path_loads(name, location=None, *a, **kw):
        raise AssertionError(f"loaded {name!r} by path from {location}")

    monkeypatch.setattr(importlib.util, "spec_from_file_location",
                        no_path_loads)
    with pytest.raises(ImportError):
        importlib.import_module("bench")
    figures = _measure(Candidate(
        page_size=4, num_pages=64, max_batch_slots=2, prefill_chunk=32,
        kv_dtype="auto", max_seq_len=128))
    assert figures["requests"] == 2 and figures["decode_tok_s"] > 0
    assert "bench" not in sys.modules


def test_tune_skips_unmeasurable_arms(monkeypatch, tmp_path):
    """The in-process harness gates: an infeasible baseline and tp>1
    survivors keep their analytic scores instead of crashing (or
    mis-measuring) the sweep, and a skipped baseline leaves
    ``baseline_measured`` None with provenance intact."""
    import runbookai_tpu.autotune.search as search_mod
    from runbookai_tpu.autotune.cost_model import SearchSpace

    calls = []

    def fake_measure(model_cfg, params, tokenizer, cand, workload, **kw):
        calls.append(cand)
        return {"decode_tok_s": 100.0, "total_tok_s": 100.0,
                "p50_ttft_ms": 1.0, "wall_s": 0.1, "requests": 2,
                "dispatches": {"prefill_steps": 1, "decode_dispatches": 1,
                               "mixed_steps": 0},
                "preemptions": 0, "engine_config": {}}

    monkeypatch.setattr(search_mod, "measure_candidate", fake_measure)
    space = SearchSpace(
        page_size=(4,), num_pages=(64,), max_batch_slots=(2,),
        prefill_chunk=(16,), mixed_token_budget=(None,),
        decode_steps_per_dispatch=(4,), kv_dtype=("auto",),
        speculative=(False,), dp_replicas=(1,), tp=(1, 2),
        max_seq_len=(256,))
    whale = Candidate(num_pages=10**7, kv_dtype="bf16", max_seq_len=256)
    result = search_mod.tune(
        "llama3-test",
        Workload(prompt_len=48, output_len=12, concurrency=4),
        HARDWARE["cpu"], space, weights="bf16", top_k=4, baseline=whale,
        n_requests=2, new_tokens=8, out=tmp_path / "skip.json")
    assert calls, "expected at least one measurable tp=1 survivor"
    assert all(c.tp <= 1 for c in calls)    # tp>1 arms never measured
    assert whale not in calls               # infeasible baseline skipped
    assert result.baseline_measured is None
    assert all(not f["is_baseline"] for f in result.measured)
    assert "baseline_decode_tok_s" not in \
        result.plan.provenance["measured"]


def test_tune_refuses_all_infeasible_sweep(tmp_path):
    """A sweep where EVERY point (baseline included) fails the memory
    plan must refuse to emit an artifact — a written plan validates and
    deploys, then OOMs at engine construction."""
    from runbookai_tpu.autotune.cost_model import Hardware
    from runbookai_tpu.autotune.search import tune as tune_fn

    tiny = Hardware("tiny", hbm_bytes=1 << 20, hbm_bw=1e9,
                    peak_flops=1e9, dispatch_overhead_s=1e-3)
    out = tmp_path / "infeasible.json"
    with pytest.raises(ValueError, match="no feasible candidate"):
        tune_fn("llama3-test",
                Workload(prompt_len=48, output_len=12, concurrency=4),
                tiny, smoke_space(), weights="bf16", measure=False,
                out=out)
    assert not out.exists()


def test_from_config_consumes_plan_and_yaml_overrides(tuned):
    """llm.plan round-trip: the built engine's resolved EngineConfig
    matches the plan; an explicit YAML key overrides the plan value."""
    import asyncio

    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.utils.config import LLMConfig

    result, out = tuned
    plan = result.plan
    client = JaxTpuClient.from_config(LLMConfig(
        provider="jax-tpu", model="llama3-test", plan=str(out)))
    try:
        ecfg = client.core.ecfg
        for key in ("page_size", "num_pages", "max_batch_slots",
                    "prefill_chunk", "decode_steps_per_dispatch",
                    "speculative", "max_seq_len"):
            assert getattr(ecfg, key) == plan.engine[key], key
    finally:
        asyncio.run(client.shutdown())

    explicit = JaxTpuClient.from_config(LLMConfig(
        provider="jax-tpu", model="llama3-test", plan=str(out),
        max_batch_slots=3))
    try:
        assert explicit.core.ecfg.max_batch_slots == 3  # YAML wins
        assert explicit.core.ecfg.num_pages == plan.engine["num_pages"]
    finally:
        asyncio.run(explicit.shutdown())


def test_from_config_plan_composes_with_tp_mesh(tuned):
    """Regression: the TP branch of from_config rebinds ``plan`` to a
    KVSplitPlan — the serving plan must survive it (engine-only keys
    still applied, no AttributeError) when llm.plan rides next to
    llm.mesh.model > 1."""
    import asyncio

    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.utils.config import LLMConfig, MeshConfig

    result, out = tuned
    client = JaxTpuClient.from_config(LLMConfig(
        provider="jax-tpu", model="llama3-test", plan=str(out),
        mesh=MeshConfig(data=1, model=2)))
    try:
        assert client.core.ecfg.speculative == \
            result.plan.engine["speculative"]
        assert client.core.ecfg.num_pages == \
            result.plan.engine["num_pages"]
    finally:
        asyncio.run(client.shutdown())


def test_from_config_refuses_model_mismatch(tuned):
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.utils.config import LLMConfig

    _, out = tuned
    with pytest.raises(ValueError, match="tuned for model"):
        JaxTpuClient.from_config(LLMConfig(
            provider="jax-tpu", model="llama3-8b-instruct",
            plan=str(out)))


def test_apply_plan_precedence_unit(tuned):
    """model_fields_set decides: only explicitly-written YAML keys beat
    the plan; everything else takes the plan's values."""
    from runbookai_tpu.utils.config import LLMConfig

    result, _ = tuned
    plan = result.plan
    merged = apply_plan_to_llm(LLMConfig(page_size=9), plan)
    assert merged.page_size == 9                       # explicit wins
    assert merged.num_pages == plan.engine["num_pages"]  # plan fills rest
    assert merged.decode_steps == \
        plan.engine["decode_steps_per_dispatch"]
    extra = engine_only_overrides(plan)
    assert "speculative" in extra and "num_pages" not in extra


# ------------------------------------------------------- plan artifacts


def test_checked_in_plans_validate():
    """Tier-1 gate: every plans/*.json in the tree validates against the
    current schema — a drifted fixture fails CI, not a hardware window."""
    paths = sorted((REPO / "plans").glob("*.json"))
    assert paths, "no checked-in plan fixtures found under plans/"
    for path in paths:
        data = json.loads(path.read_text())
        assert validate_plan(data) == [], path.name
        assert load_plan(path).model in CONFIGS


def test_unknown_schema_version_rejected():
    data = json.loads(
        (REPO / "plans" / "llama3-test.cpu.json").read_text())
    data["schema_version"] = PLAN_SCHEMA_VERSION + 1
    problems = validate_plan(data)
    assert problems and "unknown schema_version" in problems[0]
    with pytest.raises(ValueError, match="unknown schema_version"):
        PlanArtifact.from_dict(data)


def test_tampered_plan_fails_content_hash(tmp_path):
    data = json.loads(
        (REPO / "plans" / "llama3-test.cpu.json").read_text())
    data["engine"]["num_pages"] = 99999
    assert any("content hash" in p for p in validate_plan(data))
    # Unknown engine keys (a newer plan) are named, not half-applied.
    data2 = json.loads(
        (REPO / "plans" / "llama3-test.cpu.json").read_text())
    data2["engine"]["warp_drive"] = 11
    assert any("unknown engine keys" in p for p in validate_plan(data2))


def test_validate_plan_rejects_bad_impl_values():
    """attn_impl/qmm_impl must be the LLMConfig Literal set — the schema
    is the gate, because apply_plan_to_llm's model_copy bypasses pydantic
    validation and a bad value would silently serve the XLA path."""
    base = json.loads(
        (REPO / "plans" / "llama3-test.cpu.json").read_text())
    for key, bad in (("attn_impl", "Pallas"), ("attn_impl", 123),
                     ("qmm_impl", "fast"), ("qmm_impl", None)):
        data = json.loads(json.dumps(base))
        data["engine"][key] = bad
        assert any(f"engine.{key}" in p for p in validate_plan(data)), \
            (key, bad)


def test_engine_config_from_plan_unit():
    ecfg = EngineConfig.from_plan(
        {"page_size": 8, "num_pages": 128, "kv_dtype": "fp8",
         "speculative": False},
        attn_impl="xla")
    assert (ecfg.page_size, ecfg.num_pages) == (8, 128)
    assert jnp.dtype(ecfg.kv_dtype) == jnp.float8_e4m3fn
    assert ecfg.speculative is False
    auto = EngineConfig.from_plan({"kv_dtype": "auto"},
                                  default_kv_dtype=jnp.float32)
    assert jnp.dtype(auto.kv_dtype) == jnp.float32
    with pytest.raises(ValueError, match="unknown keys"):
        EngineConfig.from_plan({"page_sizes": 8})
    with pytest.raises(ValueError, match="kv_dtype"):
        EngineConfig.from_plan({"kv_dtype": "fp4"})
    # "auto" impls are a deployment-time decision: served literally they
    # would compare false against "pallas" and silently take the XLA
    # path — from_plan demands the caller resolve them.
    with pytest.raises(ValueError, match="attn_impl 'auto'"):
        EngineConfig.from_plan({"attn_impl": "auto"})
    resolved = EngineConfig.from_plan({"attn_impl": "auto"},
                                      attn_impl="xla")
    assert resolved.attn_impl == "xla"


def test_plan_kv_dtype_resolves_identically_across_consumers():
    """One resolver, one meaning: plan "bf16" is a bfloat16 pool for
    every consumer (llm.plan, from_plan) even on float32
    activations, and "auto" follows them — the budget the sweep scored
    is the budget every consumer allocates."""
    from runbookai_tpu.engine.engine import resolve_kv_dtype
    from runbookai_tpu.utils.config import LLMConfig

    assert resolve_kv_dtype("bf16", jnp.float32) == jnp.bfloat16
    assert resolve_kv_dtype("auto", jnp.float32) == jnp.float32
    assert resolve_kv_dtype("", jnp.float32) == jnp.float32
    assert resolve_kv_dtype(None, jnp.bfloat16) == jnp.bfloat16
    assert resolve_kv_dtype("fp8", jnp.float32) == jnp.float8_e4m3fn
    with pytest.raises(ValueError, match="kv_dtype"):
        resolve_kv_dtype("fp4", jnp.float32)
    # apply_plan_to_llm forwards the plan spelling 1:1 (llm.kv_cache_dtype
    # accepts the full set), so from_config resolves through the same
    # function as from_plan.
    plan = PlanArtifact(model="llama3-test", topology={"tp": 1},
                        engine={"kv_dtype": "bf16"})
    assert apply_plan_to_llm(LLMConfig(), plan).kv_cache_dtype == "bf16"
    assert jnp.dtype(EngineConfig.from_plan(
        {"kv_dtype": "bf16"},
        default_kv_dtype=jnp.float32).kv_dtype) == jnp.bfloat16


def test_engine_config_dict_is_json_safe():
    d = engine_config_dict(EngineConfig(kv_dtype=jnp.float8_e4m3fn))
    json.dumps(d)
    assert d["kv_dtype"] == "float8_e4m3fn"
    assert d["num_pages"] == 2048


def test_validate_config_flags_plan_problems(tmp_path):
    from runbookai_tpu.utils.config import Config, validate_config

    cfg = Config.model_validate(
        {"llm": {"plan": str(tmp_path / "missing.json")}})
    assert any("llm.plan does not exist" in p for p in validate_config(cfg))
    plan = PlanArtifact(model="llama3-test", topology={"tp": 1},
                        engine={"num_pages": 64})
    save_plan(plan, tmp_path / "p.json")
    cfg = Config.model_validate({"llm": {"model": "other-model",
                                         "plan": str(tmp_path / "p.json")}})
    assert any("tuned for model" in p for p in validate_config(cfg))


# ------------------------------------------------------ fleet budget split


def test_split_engine_budget_never_rounds_up():
    from runbookai_tpu.engine.fleet import split_engine_budget

    total = EngineConfig(max_batch_slots=8, num_pages=1024, prefill_batch=8,
                         kv_spill_pages=512)
    per = split_engine_budget(total, 3)
    assert per.dp_replicas == 3
    assert per.max_batch_slots * 3 <= total.max_batch_slots
    assert per.num_pages * 3 <= total.num_pages
    # The host spill tier is part of the fixed-total budget too.
    assert per.kv_spill_pages * 3 <= total.kv_spill_pages
    assert per.prefill_batch <= per.max_batch_slots
    # Allocator minimums hold even under absurd splits.
    tiny = split_engine_budget(EngineConfig(max_batch_slots=1,
                                            num_pages=4), 8)
    assert tiny.max_batch_slots == 1 and tiny.num_pages == 2


# ------------------------------------------------ plan parity, end to end


def _served(llm_kw, prompts):
    """Build a client from ``LLMConfig(**llm_kw)``, serve ``prompts``
    greedily, shut it down; returns (resolved EngineConfig as a dict,
    per-replica configs, token streams)."""
    import asyncio

    from runbookai_tpu.engine.request import SamplingParams
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.utils.config import LLMConfig

    client = JaxTpuClient.from_config(LLMConfig(
        provider="jax-tpu", model="llama3-test", dtype="float32", **llm_kw))

    async def run():
        try:
            return [(await client.engine.generate(
                list(p), SamplingParams(temperature=0.0, max_new_tokens=12,
                                        stop_token_ids=()))).token_ids
                    for p in prompts]
        finally:
            await client.shutdown()

    cores = getattr(client.engine, "cores", None) or [client.core]
    return (engine_config_dict(client.core.ecfg),
            [c.ecfg for c in cores], asyncio.run(run()))


def test_plan_matches_explicit_fields(tmp_path):
    """A plan gives the same EngineConfig as the equivalent explicit
    fields, through both consumers: ``EngineConfig.from_plan`` equals the
    hand-built config, ``llm.plan`` serves exactly that config, and the
    same keys written out in the ``llm`` block resolve alike and serve
    byte-identical streams; an explicit key still beats the plan's."""
    engine = {"page_size": 16, "num_pages": 64, "max_batch_slots": 2,
              "prefill_chunk": 128, "max_seq_len": 2048,
              "block_pages": 16, "decode_steps_per_dispatch": 8,
              "prefill_batch": 1, "kv_dtype": "auto",
              "speculative": True, "dp_replicas": 1}
    plan = PlanArtifact(
        model="llama3-test",
        topology={"platform": "cpu", "device_kind": "cpu", "chips": 1,
                  "tp": 1, "dp_replicas": 1},
        engine=engine)
    path = tmp_path / "plan.json"
    save_plan(plan, path)

    by_hand = EngineConfig(
        page_size=16, num_pages=64, max_batch_slots=2, prefill_chunk=128,
        max_seq_len=2048, block_pages=16, decode_steps_per_dispatch=8,
        prefill_batch=1, kv_dtype=jnp.float32, speculative=True,
        dp_replicas=1)
    assert EngineConfig.from_plan(
        load_plan(path).engine, default_kv_dtype=jnp.float32) == by_hand

    prompts = [list(range(65, 129)), list(range(40, 104))]
    via_plan, _, plan_streams = _served({"plan": str(path)}, prompts)
    # llm.plan applies the whole block, the keys with no llm.* spelling
    # (prefill_batch, block_pages, speculative) included.
    # (from_config also spells out the backend's impls and llm.sched's
    # default class weights, which None stands for.)
    assert via_plan == engine_config_dict(by_hand) | {
        "attn_impl": "xla", "qmm_impl": "xla",
        "sched_weights": {0: 1.0, 1: 8.0}}
    spelled = {"page_size": 16, "num_pages": 64, "max_batch_slots": 2,
               "prefill_chunk": 128, "max_seq_len": 2048, "decode_steps": 8}
    explicit, _, explicit_streams = _served(spelled, prompts)
    for key, value in spelled.items():
        key = {"decode_steps": "decode_steps_per_dispatch"}.get(key, key)
        assert explicit[key] == via_plan[key] == value, key
    assert plan_streams == explicit_streams
    override, _, _ = _served({"plan": str(path), "max_batch_slots": 1}, [])
    assert override["max_batch_slots"] == 1
    assert override["num_pages"] == 64


def test_plan_dp_budget_is_per_replica(tmp_path):
    """A plan's slots/pages are PER REPLICA (the llm.*/EngineConfig
    contract): a plan-sized fleet serves each replica the plan's own
    budget; nothing splits it across the replicas."""
    plan = PlanArtifact(
        model="llama3-test",
        topology={"platform": "cpu", "device_kind": "cpu", "chips": 2,
                  "tp": 1, "dp_replicas": 2},
        engine={"page_size": 4, "num_pages": 64, "max_batch_slots": 2,
                "prefill_chunk": 32, "max_seq_len": 256,
                "decode_steps_per_dispatch": 8, "prefill_batch": 1,
                "kv_dtype": "auto", "speculative": False,
                "dp_replicas": 2})
    path = tmp_path / "dp-plan.json"
    save_plan(plan, path)
    _, per_replica, streams = _served(
        {"plan": str(path)}, [list(range(65, 113)), list(range(30, 78))])
    assert len(per_replica) == 2
    for ecfg in per_replica:
        assert ecfg.dp_replicas == 2
        assert ecfg.max_batch_slots == 2 and ecfg.num_pages == 64
    assert all(len(ids) == 12 for ids in streams)


def test_plan_for_another_model_is_refused_by_every_consumer(tmp_path):
    """A plan is per model x topology: the single-model client refuses
    one tuned for another model, and so does a group of ``llm.models``
    (its own derived config goes through the same ``apply_group_plan``)."""
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.utils.config import LLMConfig, ModelGroupConfig

    out = tmp_path / "other.json"
    save_plan(PlanArtifact(model="llama3-8b-instruct", topology={"tp": 1},
                           engine={"num_pages": 64}), out)
    with pytest.raises(ValueError, match="tuned for model"):
        JaxTpuClient.from_config(LLMConfig(
            provider="jax-tpu", model="llama3-test", plan=str(out)))
    with pytest.raises(ValueError, match="tuned for model"):
        JaxTpuClient.from_config(LLMConfig(
            provider="jax-tpu", model="llama3-test", dtype="float32",
            models=[ModelGroupConfig(name="llama3-test", plan=str(out))]))


# --------------------------------------------- trace dispatch counters


def test_trace_summary_reports_dispatch_counters(tmp_path, capsys):
    from runbookai_tpu.cli.main import main
    from runbookai_tpu.utils.trace import dispatch_counters

    spans = ([{"name": "engine.prefill", "ms": 1.0}] * 3
             + [{"name": "engine.decode", "ms": 2.0}] * 5
             + [{"name": "engine.decode_spec", "ms": 2.0}] * 2
             + [{"name": "engine.mixed", "ms": 3.0}] * 4
             + [{"name": "server.request", "ms": 9.0}])
    assert dispatch_counters(spans) == {
        "prefill_steps": 3, "decode_dispatches": 7, "mixed_steps": 4}
    path = tmp_path / "trace.jsonl"
    path.write_text("\n".join(json.dumps(s) for s in spans))
    assert main(["metrics", "--trace", str(path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["dispatch_counters"] == {
        "prefill_steps": 3, "decode_dispatches": 7, "mixed_steps": 4}
    # --span filtering keeps its exact historical output (no counters).
    assert main(["metrics", "--trace", str(path), "--span", "mixed"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert list(out) == ["engine.mixed"]


# ------------------------------------- fleet-shape knobs (kv spill, disagg)


def test_plan_v1_without_fleet_keys_still_validates():
    """Migration contract: pre-PR-8 plans carry neither
    engine.kv_spill_pages nor topology.disagg_prefill_replicas. The
    schema stays v1-compatible — they validate unchanged and resolve to
    a disabled spill tier / symmetric fleet."""
    data = json.loads((REPO / "plans" / "llama3-test.cpu.json").read_text())
    assert "kv_spill_pages" not in data["engine"]
    assert "disagg_prefill_replicas" not in data.get("topology", {})
    assert validate_plan(data) == []
    ecfg = EngineConfig.from_plan(data["engine"])
    assert ecfg.kv_spill_pages == 0


def test_plan_fleet_keys_validated():
    base = json.loads((REPO / "plans" / "llama3-test.cpu.json").read_text())
    # Well-formed new keys: no schema complaint beyond the content hash
    # (the fixture's hash no longer matches once keys are added).
    data = json.loads(json.dumps(base))
    data["engine"]["kv_spill_pages"] = 64
    data.setdefault("topology", {})["disagg_prefill_replicas"] = 1
    data["topology"]["dp_replicas"] = 2
    data["engine"]["dp_replicas"] = 2
    probs = validate_plan(data)
    assert all("kv_spill_pages" not in p for p in probs), probs
    assert all("disagg" not in p for p in probs), probs
    # Malformed values are named precisely.
    bad = json.loads(json.dumps(base))
    bad["engine"]["kv_spill_pages"] = -1
    assert any("kv_spill_pages" in p for p in validate_plan(bad))
    bad2 = json.loads(json.dumps(base))
    bad2["engine"]["dp_replicas"] = 2
    bad2.setdefault("topology", {})["disagg_prefill_replicas"] = 2
    assert any("no decode tier" in p for p in validate_plan(bad2))


def test_candidate_fleet_knobs_feasibility_and_block():
    """kv_spill_pages budgets against HOST RAM (never the HBM pool) and
    disagg splits must leave a decode tier; both knobs ride in the plan
    blocks so Candidate/plan schema stay in sync."""
    model = CostModel(CFG, HARDWARE["cpu"])
    wl = Workload(prompt_len=32, output_len=16, concurrency=4)
    base = Candidate(page_size=4, num_pages=64, max_batch_slots=2,
                     prefill_chunk=16, max_seq_len=256)
    ok, why = model.check_feasible(base, wl)
    assert ok, why
    # A sane spill tier stays feasible; the block carries the knob.
    spill = Candidate(**{**base.__dict__, "kv_spill_pages": 128})
    ok, why = model.check_feasible(spill, wl)
    assert ok, why
    assert spill.engine_plan_block()["kv_spill_pages"] == 128
    assert base.topology_extras() == {}
    # An absurd tier (beyond half the host-RAM envelope) is refused.
    huge = Candidate(**{**base.__dict__, "kv_spill_pages": 10**9})
    ok, why = model.check_feasible(huge, wl)
    assert not ok and "host RAM" in why
    # Disagg must leave a decode tier.
    bad = Candidate(**{**base.__dict__, "dp_replicas": 2,
                       "disagg_prefill_replicas": 2})
    ok, why = model.check_feasible(bad, wl)
    assert not ok and "decode tier" in why
    good = Candidate(**{**base.__dict__, "dp_replicas": 2,
                        "disagg_prefill_replicas": 1})
    ok, why = model.check_feasible(good, wl)
    assert ok, why
    assert good.topology_extras() == {"disagg_prefill_replicas": 1}
    # Residency reports the spill tier in HOST bytes, leaving the HBM
    # pool budget untouched.
    plan_off = model.residency(base)
    plan_on = model.residency(spill)
    assert plan_off.host_spill_bytes == 0 and plan_on.host_spill_bytes > 0
    assert plan_on.pool_budget_bytes == plan_off.pool_budget_bytes


def test_search_space_fleet_axes_default_off():
    """Existing sweeps (and their plan hashes) are unchanged until a
    space opts into the new axes."""
    for cand in smoke_space().candidates():
        assert cand.kv_spill_pages == 0
        assert cand.disagg_prefill_replicas == 0
