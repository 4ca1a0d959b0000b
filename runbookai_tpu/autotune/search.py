"""Autotune search driver: analytic prune → measured refinement → plan.

AIConfigurator's two-stage loop (PAPERS.md) over this engine's knobs:

1. **Analytic prune** — score every candidate in the
   :class:`~runbookai_tpu.autotune.cost_model.SearchSpace` with the cost
   model, drop infeasible points (residency via memory_plan) and
   dominated points (worse on BOTH predicted throughput and TTFT), keep
   the top-K survivors. Pure arithmetic: thousands of points per second.

2. **Measured refinement** — run each survivor (plus the hand-picked
   baseline, so a shipped plan can never regress it) through a short
   in-process serving run: a deterministic prompt stream, a warm-up that
   compiles every program shape, ``EngineCore.reset_metrics()``, then
   the window the figures are read from. The best *measured* candidate
   becomes the plan.

The output is a :class:`~runbookai_tpu.autotune.plan.PlanArtifact` with
full provenance: cost-model scores, per-candidate measured figures, the
baseline figure it had to beat, and the git sha of the tree that ran the
sweep.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Optional

from runbookai_tpu.autotune.cost_model import (
    Candidate,
    CostEstimate,
    CostModel,
    Hardware,
    SearchSpace,
    Workload,
    smoke_space,
)
from runbookai_tpu.autotune.plan import (
    PlanArtifact,
    engine_config_dict,
    git_sha,
    save_plan,
)


# ------------------------------------------------------------- analytic


def pareto_front(estimates: list[CostEstimate]) -> list[CostEstimate]:
    """Dominated-point elimination on (predicted throughput, TTFT): a
    point loses only when another is at least as good on both axes and
    strictly better on one — the two axes a serving operator actually
    trades."""
    front: list[CostEstimate] = []
    for e in estimates:
        dominated = any(
            o.decode_tok_s >= e.decode_tok_s and o.ttft_ms <= e.ttft_ms
            and (o.decode_tok_s > e.decode_tok_s or o.ttft_ms < e.ttft_ms)
            for o in estimates)
        if not dominated:
            front.append(e)
    return front


def analytic_prune(estimates: list[CostEstimate],
                   top_k: int = 4) -> list[CostEstimate]:
    """Feasibility filter, Pareto elimination, then throughput rank. When
    the front is thinner than ``top_k`` the next-best dominated points
    fill the budget — measurement, not the model, gets the last word."""
    feasible = [e for e in estimates if e.feasible]
    front = pareto_front(feasible)
    ranked = sorted(front, key=lambda e: e.decode_tok_s, reverse=True)
    if len(ranked) < top_k:
        rest = sorted((e for e in feasible if e not in front),
                      key=lambda e: e.decode_tok_s, reverse=True)
        ranked += rest[:top_k - len(ranked)]
    return ranked[:top_k]


# ------------------------------------------------------------- measured


def measure_candidate(model_cfg, params, tokenizer, cand: Candidate,
                      workload: Workload, *, n_requests: int = 4,
                      new_tokens: int = 16, seed: int = 0,
                      attn_impl: str = "xla",
                      qmm_impl: str = "xla") -> dict[str, Any]:
    """One short measured serving run of ``cand``, in-process:
    deterministic prompts, warmup to compile every program shape,
    counter reset (``EngineCore.reset_metrics``), then the measured
    window. Returns the figures a plan's provenance records."""
    import numpy as np

    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams

    ecfg = EngineConfig.from_plan(
        cand.engine_plan_block(),
        default_kv_dtype=params["embed"].dtype,
        attn_impl=attn_impl, qmm_impl=qmm_impl)
    prompt_len = min(workload.prompt_len, max(8, cand.max_seq_len
                                              - new_tokens - 1))
    rng = np.random.default_rng(seed)

    def make_req():
        return EngineRequest(
            prompt_ids=rng.integers(0, 256, size=prompt_len).tolist(),
            sampling=SamplingParams(temperature=0.0,
                                    max_new_tokens=new_tokens,
                                    stop_token_ids=()))

    if cand.dp_replicas > 1:
        return _measure_fleet(model_cfg, params, tokenizer, ecfg,
                              make_req, n_requests=n_requests)

    core = EngineCore(model_cfg, params, tokenizer, ecfg)
    for _ in range(min(ecfg.max_batch_slots, n_requests)):
        core.submit(make_req())
    core.run_until_idle()
    core.reset_metrics()

    reqs = [make_req() for _ in range(n_requests)]
    t0 = time.perf_counter()
    for r in reqs:
        core.submit(r)
    core.run_until_idle()
    wall = time.perf_counter() - t0
    m = core.metrics
    ttfts = sorted(r.ttft_ms for r in reqs if r.ttft_ms is not None)
    total = m["decode_tokens"] + m["prefill_tokens"]
    return {
        "decode_tok_s": round(
            m["decode_tokens"] / max(m["decode_time_s"]
                                     + m.get("mixed_time_s", 0.0), 1e-9),
            2),
        "total_tok_s": round(total / max(wall, 1e-9), 2),
        "p50_ttft_ms": (round(ttfts[len(ttfts) // 2], 1)
                        if ttfts else None),
        "wall_s": round(wall, 3),
        "requests": n_requests,
        "dispatches": {
            "prefill_steps": m.get("prefill_steps", 0),
            "decode_dispatches": m.get("decode_dispatches", 0),
            "mixed_steps": m.get("mixed_steps", 0),
        },
        "preemptions": m.get("preemptions", 0),
        "engine_config": engine_config_dict(core.ecfg),
    }


def _measure_fleet(model_cfg, params, tokenizer, ecfg, make_req, *,
                   n_requests: int) -> dict[str, Any]:
    """The dp>1 measured arm: a candidate's slots/pages are PER REPLICA
    (the same contract as ``llm.*`` config and ``EngineConfig`` — so a
    plan applied via ``llm.plan`` serves exactly the budget the sweep
    measured), and the request set serves through an AsyncFleet."""
    import asyncio

    from runbookai_tpu.engine.fleet import AsyncFleet, build_engine_fleet

    per_replica = ecfg
    cores = build_engine_fleet(model_cfg, params, tokenizer, per_replica)
    # EVERY replica warms (compiles its programs) regardless of
    # n_requests — an unwarmed replica would pay multi-second compiles
    # inside the measured window and systematically understate high-dp
    # candidates.
    warm_per_core = max(1, min(per_replica.max_batch_slots, n_requests))
    for core in cores:
        for _ in range(warm_per_core):
            core.submit(make_req())
    for core in cores:
        core.run_until_idle()
        core.reset_metrics()

    fleet = AsyncFleet(cores)
    reqs = [make_req() for _ in range(n_requests)]

    async def _run():
        outs = await asyncio.gather(*[
            fleet.generate(r.prompt_ids, r.sampling) for r in reqs])
        await fleet.stop()
        return outs

    t0 = time.perf_counter()
    outs = asyncio.run(_run())
    wall = time.perf_counter() - t0
    decode = sum(c.metrics["decode_tokens"] for c in cores)
    decode_t = max(c.metrics["decode_time_s"]
                   + c.metrics.get("mixed_time_s", 0.0) for c in cores)
    ttfts = sorted(o.ttft_ms for o in outs if o.ttft_ms is not None)
    total = decode + sum(c.metrics["prefill_tokens"] for c in cores)
    return {
        "decode_tok_s": round(decode / max(decode_t, 1e-9), 2),
        "total_tok_s": round(total / max(wall, 1e-9), 2),
        "p50_ttft_ms": (round(ttfts[len(ttfts) // 2], 1)
                        if ttfts else None),
        "wall_s": round(wall, 3),
        "requests": n_requests,
        "dispatches": {
            "prefill_steps": sum(c.metrics.get("prefill_steps", 0)
                                 for c in cores),
            "decode_dispatches": sum(c.metrics.get("decode_dispatches", 0)
                                     for c in cores),
            "mixed_steps": sum(c.metrics.get("mixed_steps", 0)
                               for c in cores),
        },
        "preemptions": sum(c.metrics.get("preemptions", 0)
                           for c in cores),
        "engine_config": engine_config_dict(per_replica),
    }


# ------------------------------------------------------------------ tune


@dataclass
class TuneResult:
    """Everything a sweep produced (the plan is the shippable part)."""

    plan: PlanArtifact
    estimates: list[CostEstimate] = field(default_factory=list)
    survivors: list[CostEstimate] = field(default_factory=list)
    measured: list[dict[str, Any]] = field(default_factory=list)
    baseline_measured: Optional[dict[str, Any]] = None


def tune(model_name: str, workload: Workload, hardware: Hardware,
         space: Optional[SearchSpace] = None, *,
         weights: str = "bf16", top_k: int = 3, measure: bool = True,
         baseline: Optional[Candidate] = None, n_requests: int = 4,
         new_tokens: int = 16, budget_s: float = 300.0,
         out: Optional[str | Path] = None,
         params=None, tokenizer=None,
         log: Callable[[str], None] = lambda s: None) -> TuneResult:
    """Run the full sweep and return the plan (optionally saved to
    ``out``).

    The hand-picked default (``baseline``, EngineConfig defaults when
    omitted) is ALWAYS measured alongside the survivors and competes for
    the plan — a tune run therefore cannot ship a regression over the
    config it replaces. ``budget_s`` bounds the measured phase: once
    exceeded, remaining survivors keep their analytic score only.
    """
    from runbookai_tpu.models.llama import CONFIGS

    model_cfg = CONFIGS[model_name]
    space = space or smoke_space()
    cm = CostModel(model_cfg, hardware, weights=weights)
    t0 = time.monotonic()

    candidates = space.candidates()
    estimates = cm.score_many(candidates, workload)
    survivors = analytic_prune(estimates, top_k=top_k)
    n_feasible = sum(e.feasible for e in estimates)
    log(f"scored {len(estimates)} candidates: {n_feasible} feasible, "
        f"{len(survivors)} kept for refinement")

    baseline = baseline or Candidate()
    base_est = cm.score(baseline, workload)
    arms: list[CostEstimate] = [base_est] + [
        e for e in survivors if e.candidate != baseline]

    def measurable(est: CostEstimate) -> bool:
        # The in-process harness serves a single unsharded engine (or a
        # CPU fleet): an infeasible baseline must not crash the sweep on
        # allocation, and tp>1 arms would measure a deployment the plan
        # does not describe — both keep their analytic scores only (the
        # measured tp sweep needs the sharded harness; hardware-window
        # work, see docs/autotune.md).
        if not est.feasible:
            return False
        return est.candidate.tp <= 1

    measured: list[dict[str, Any]] = []
    if measure:
        import jax

        # The measured arms must serve the WIDTH and kernel paths the
        # plan will actually deploy: int8 sweeps measure quantized trees
        # (a random float32 8B would be 4x the bytes the cost model
        # ranked — and would not even fit the chip), and on-accelerator
        # runs use the Pallas paths exactly like from_config resolves.
        on_accel = jax.default_backend() == "tpu"
        attn_impl = "pallas" if on_accel else "xla"
        qmm_impl = "pallas" if (on_accel and weights == "int8") else "xla"
        if params is None or tokenizer is None:
            import jax.numpy as jnp

            from runbookai_tpu.models.llama import (
                init_params,
                init_params_quantized,
            )
            from runbookai_tpu.utils.tokens import ByteTokenizer

            dtype = jnp.bfloat16 if on_accel else jnp.float32
            if weights == "int8":
                params = init_params_quantized(
                    jax.random.PRNGKey(0), model_cfg, dtype=dtype)
            else:
                params = init_params(jax.random.PRNGKey(0), model_cfg,
                                     dtype=dtype)
            tokenizer = ByteTokenizer()
        for i, est in enumerate(arms):
            if not measurable(est):
                log(f"arm {i} ({'baseline' if i == 0 else 'survivor'}) "
                    f"not measurable in-process "
                    f"({'infeasible: ' + est.reason if not est.feasible else f'tp={est.candidate.tp}'})"
                    f" — keeps its analytic score")
                continue
            if i > 0 and time.monotonic() - t0 > budget_s:
                log(f"measurement budget ({budget_s:.0f}s) exhausted — "
                    f"{len(arms) - i} survivor(s) keep analytic scores "
                    f"only")
                break
            figs = measure_candidate(model_cfg, params, tokenizer,
                                     est.candidate, workload,
                                     n_requests=n_requests,
                                     new_tokens=new_tokens,
                                     attn_impl=attn_impl,
                                     qmm_impl=qmm_impl)
            figs["candidate"] = est.candidate.engine_plan_block()
            figs["predicted"] = est.to_dict()
            figs["is_baseline"] = i == 0
            figs["arm_index"] = i
            measured.append(figs)
            log(f"measured {'baseline ' if i == 0 else ''}candidate "
                f"{i}/{len(arms) - 1}: "
                f"{figs['decode_tok_s']} decode tok/s")

    if measured:
        best = max(measured, key=lambda f: f["decode_tok_s"])
        winner_est = arms[best["arm_index"]]
        winner = winner_est.candidate
        # The baseline may itself have been skipped as unmeasurable
        # (infeasible on this hardware) — measured[0] is then a survivor.
        baseline_measured = next(
            (f for f in measured if f["is_baseline"]), None)
    else:
        # Analytic-only: the baseline still competes on predicted score —
        # the no-regression contract holds with or without measurement.
        best, baseline_measured = None, None
        winner_est = max(arms, key=lambda e: e.decode_tok_s)
        winner = winner_est.candidate
    if not winner_est.feasible:
        # Every point (including the baseline) failed the memory plan —
        # emitting this artifact would ship a config that OOMs at engine
        # construction. Refuse instead of writing a plan that validates.
        raise ValueError(
            f"no feasible candidate in the sweep ({len(estimates)} "
            f"scored): the best point is infeasible — "
            f"{winner_est.reason or 'see cost-model feasibility checks'}")

    import jax

    topology = {
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "chips": len(jax.devices()),
        "tp": winner.tp,
        "dp_replicas": winner.dp_replicas,
        "hbm_bytes_per_chip": hardware.hbm_bytes,
        # Fleet-shape extras (disagg tier split) — empty for symmetric
        # fleets so pre-PR-8 plan hashes are reproducible.
        **winner.topology_extras(),
    }
    provenance: dict[str, Any] = {
        "tool": "runbook tune",
        "created_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": git_sha(),
        "hardware_model": hardware.to_dict(),
        "weights": weights,
        "cost_model": {
            "winner": winner_est.to_dict(),
            "candidates_scored": len(estimates),
            "candidates_feasible": n_feasible,
            "survivors_refined": len(measured),
        },
    }
    if best is not None:
        provenance["measured"] = {
            k: best[k] for k in ("decode_tok_s", "total_tok_s",
                                 "p50_ttft_ms", "dispatches", "wall_s")}
        if baseline_measured is not None:
            provenance["measured"]["baseline_decode_tok_s"] = \
                baseline_measured["decode_tok_s"]
        provenance["measured"]["all_arms"] = [
            {"candidate": f["candidate"],
             "decode_tok_s": f["decode_tok_s"],
             "is_baseline": f["is_baseline"]} for f in measured]
    plan = PlanArtifact(model=model_name, topology=topology,
                        engine=winner.engine_plan_block(),
                        workload=workload.to_dict(),
                        provenance=provenance)
    if out is not None:
        save_plan(plan, out)
        log(f"wrote plan {plan.plan_id} -> {out}")
    return TuneResult(plan=plan, estimates=estimates,
                      survivors=survivors, measured=measured,
                      baseline_measured=baseline_measured)
