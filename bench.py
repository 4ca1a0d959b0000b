"""Serving benchmark — prints ONE JSON line.

Runs ``run_bench`` once, in this process, on the device JAX reports, and
exits non-zero when anything fails. A TPU is required: without one the
command refuses, unless the CPU is asked for by name (``--cpu``), which
runs the same arms on the tiny model as a functional check — a CPU figure
is not a measurement of this system. The default model on the chip is the
Llama-3-8B shape with int8 weights, random-init (no-egress environment).
ROADMAP A1 replaces this file with a cell matrix; until then it keeps the
arms the tier-1 suite exercises.

Env knobs: BENCH_MODEL, BENCH_REQUESTS, BENCH_PROMPT, BENCH_NEW,
BENCH_SLOTS, BENCH_PAGES, BENCH_ATTN, BENCH_PREFILL_BATCH,
BENCH_OVERLAP (=0 forces synchronous decode; `--no-overlap` sets it, so
the overlapped-pipeline A/B is one flag on hardware), BENCH_MIXED (=0 /
`--no-mixed` forces the split prefill/decode dispatches, =1 forces the
unified mixed dispatch; unset leaves the engine's auto policy),
BENCH_DP (`--dp N`: serve the SAME request set through a data-parallel
engine fleet — N replicas splitting the slot/page budget, fronted by the
prefix-affinity router; details carry per-replica throughput, affinity
hit ratio and imbalance, and `outputs_digest` proves per-request streams
byte-identical across the dp=1/dp=N arms), BENCH_SHARED_PREFIX (first S
prompt tokens shared across requests, exercising the router's
prefix-affinity path; default 0 keeps the historical prompt series),
BENCH_SESSIONS (K distinct shared prefixes — K live "conversations"
cycling across requests, the asymmetric-residency workload the kv-share
pull seam targets; default 1 = the historical single prefix),
BENCH_KV_SHARE (`--kv-share`: fleet-wide KV page sharing — an affinity
miss pulls the prompt's prefix pages from the sibling that holds them
instead of re-prefilling; details carry the cross-replica hit ratio,
pages pulled and pull wall, and `outputs_digest` proves the pulled
pages byte-identical to recompute), BENCH_DISAGG (`--disagg [N]`:
prefill/decode disaggregation — the first N replicas form a prefill
tier whose pages hand off to the decode tier at first-token time;
details carry the tier split and per-tier traffic), BENCH_STAGGER_MS
(inter-arrival spacing of the measured fleet window — the kv-share A/B
runs a staggered prompt burst so siblings have pages to pull; 0 keeps
the historical all-at-once gather),
BENCH_CLASSES (`--classes`: the two-class flood arm — a batch flood plus
interactive requests through one engine, per-class TTFT/TPOT against a
flood-free interactive baseline; BENCH_SCHED=0 collapses the classes
into the FIFO arm, BENCH_BATCH_REQS / BENCH_INT_REQS size the flood and
the interactive set; digests are per class and byte-identical across
arms — BENCHLOG r9),
BENCH_PLAN (`--plan PATH`: pin the engine config to a serving-plan
artifact from `runbook tune` — plan values become the defaults, explicit
BENCH_* env still wins, and the plan id/hash lands in `details` so every
banked figure is auditable against the exact plan that produced it),
BENCH_PROFILE (`--profile [DIR]`: wrap the measured window in an XProf
capture — details.profile records the TensorBoard-readable trace dir, or
a clean skip when jax.profiler capture is unavailable), BENCH_SLO (JSON
dict of llm.slo-style targets, e.g. '{"tpot_p95_ms": 40}' — evaluated
against the measured window's histograms into details.slo with the
per-objective burn ratio).
BENCH_OBS (=0 disables the workload-fingerprint taps — the byte-identity
baseline; default on: every measured window banks
`details.workload_fingerprint`, the live traffic in the autotuner's
Workload schema, so BENCHLOG arms double as fingerprint fixtures),
BENCH_SHIFT (`--shift`: the ROADMAP item 3 scenario — a short-chat phase
then a long-context/guided phase through one engine; details.workload
carries the per-phase drift scores and whether the stale threshold was
crossed, with digests byte-identical to a BENCH_OBS=0 run),
BENCH_SOAK (`--soak [SECONDS]`: time-bounded closed-loop mixed traffic;
compose with `--models A,B` to soak a two-group multi-model fleet —
gates on zero lost requests and banks per-group fingerprints),
BENCH_SOAK_SCENARIOS (`--soak-scenarios [SECONDS]`: the chaos soak gate
— the seeded scenario mix (simulate/traffic.py) through a dp>=2 fleet
with fault injection + replica supervision, run twice (chaos-free
baseline, then chaos) and gated on production invariants: zero lost
requests outside fault windows, interactive p95 TTFT bound, tenant
fairness, RSS/fd bounds, per-chain digest determinism, supervisor
recovery — docs/robustness.md; knobs: BENCH_CHAOS=0 disables faults,
BENCH_CHAOS_SEED, BENCH_SOAK_DP, BENCH_SOAK_RATE,
BENCH_SOAK_TTFT_P95_MS, BENCH_WEDGE_TIMEOUT_S).
Every artifact's `details.engine_config` records the core's fully
resolved EngineConfig, flags or no flags; every
measured window also carries `details.flight_summary` (step-level
dispatch-kind counts, occupancy p50/p95, KV-pressure peak from the
engine flight recorder).
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

# Peak dense bf16 FLOP/s per chip, keyed by substrings of device_kind
# (first match wins; public spec-sheet numbers).
_PEAK_FLOPS = (
    ("v6e", 918e12), ("v6 lite", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12), ("v5lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_chip(device_kind: str) -> float | None:
    kind = device_kind.lower()
    for key, flops in _PEAK_FLOPS:
        if key in kind:
            return flops
    return None


def token_streams_digest(token_lists) -> str:
    """Digest of a list of output token streams, in submission order —
    equal digests across two arms prove they served byte-identical
    per-request streams (the --dp and --models contracts)."""
    import hashlib

    return hashlib.md5(json.dumps(
        [list(map(int, ids)) for ids in token_lists]).encode()).hexdigest()


def make_result(value: float, unit: str, details: dict) -> dict:
    # A CPU run (--cpu) checks that the arms work; its rate is not the
    # chip metric and is not printed under the chip metric's name.
    on_cpu = details.get("platform") == "cpu"
    return {
        "metric": ("cpu_functional_tokens_per_sec" if on_cpu
                   else "decode_tokens_per_sec_per_chip"),
        "value": value,
        "unit": unit,
        "vs_baseline": None if on_cpu else 1.0,
        "details": details,
    }


def emit(value: float, unit: str, details: dict) -> None:
    print(json.dumps(make_result(value, unit, details)), flush=True)


def reset_warmup_metrics(core) -> None:
    """Zero the step counters + latency histograms after warmup, so every
    arm's measured window excludes compile-time traffic. ONE helper for
    the dp=1 and fleet arms — two hand-maintained key lists would drift
    the A/B the first time a new counter lands (cached_prefix_tokens and
    preemptions reset too: both are reported per measured window)."""
    core.metrics.update(
        decode_tokens=0, decode_steps=0, prefill_tokens=0,
        cached_prefix_tokens=0, preemptions=0,
        decode_time_s=0.0, prefill_time_s=0.0,
        decode_dispatch_time_s=0.0, decode_host_time_s=0.0,
        decode_host_overlap_s=0.0, prefill_steps=0,
        decode_dispatches=0, mixed_steps=0, mixed_tokens=0,
        mixed_time_s=0.0, kv_pages_imported=0, kv_pages_exported=0,
        kv_spill_readmits=0)
    # The flight recorder reports page-transfer DELTAS against this mark;
    # zeroing the counters without it would make the first measured step
    # report a negative import delta.
    core._flight_kv_mark = (0, 0)
    core.hist_ttft.reset()
    core.hist_tpot.reset()
    # The flight_summary block must describe the MEASURED window, not the
    # warmup compiles.
    core.flight.reset()


def make_bench_fingerprinter(cores, model_name: str):
    """Workload fingerprinter over a bench arm's cores (None when
    BENCH_OBS=0 — the taps are never installed, so the disabled run is
    the byte-identity baseline for the read-only-layer claim). The
    window is wide enough that one measured window never ages out."""
    if os.environ.get("BENCH_OBS", "1") == "0":
        return None
    from runbookai_tpu.obs import WorkloadFingerprinter

    fp = WorkloadFingerprinter(cores, model=model_name, window_s=3600.0)
    fp.install_taps()
    return fp


def profile_context():
    """BENCH_PROFILE support (`--profile [DIR]`): an XProf capture around
    the measured window, recorded in ``details["profile"]`` as captured
    (with the trace dir) or cleanly skipped — the CPU tier-1 smoke
    asserts exactly that produced-or-skipped contract."""
    import contextlib

    target = os.environ.get("BENCH_PROFILE")
    if not target:
        return contextlib.nullcontext(None), None
    from runbookai_tpu.utils.trace import try_device_trace

    profile_dir = (target if target != "1"
                   else os.path.join(".runbook", "profile", "bench"))
    return try_device_trace(profile_dir), profile_dir


def profile_detail(profile_dir: str | None, captured) -> dict | None:
    if profile_dir is None:
        return None
    return {"dir": profile_dir, "captured": bool(captured),
            **({} if captured else
               {"skipped": "jax.profiler capture unavailable"})}


def slo_detail(registry_targets_env: str | None) -> dict | None:
    """BENCH_SLO='{"tpot_p95_ms": 40}' evaluates the configured targets
    against the measured window's histograms (utils/slo.py) and reports
    the burn — the one-flag proof that a breached objective scrapes
    ``runbook_slo_burn_ratio > 1`` while an unconfigured run carries no
    SLO block at all."""
    if not registry_targets_env:
        return None
    from runbookai_tpu.utils.slo import SLOMonitor

    try:
        targets = json.loads(registry_targets_env)
        if not isinstance(targets, dict):
            raise TypeError(f"expected a JSON object, got {type(targets).__name__}")
        monitor = SLOMonitor(targets)
    except (ValueError, TypeError) as e:
        return {"error": f"bad BENCH_SLO: {e}"}
    return monitor.evaluate()


def _parses(text: str) -> bool:
    try:
        json.loads(text)
        return True
    except ValueError:
        return False


def run_bench(model_name: str, on_accel: bool, probe: dict) -> None:
    import jax
    import jax.numpy as jnp

    from runbookai_tpu.engine.engine import (
        EngineConfig,
        EngineCore,
        resolve_kv_dtype,
    )
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params, init_params_quantized
    from runbookai_tpu.utils.tokens import ByteTokenizer

    n_requests = int(os.environ.get("BENCH_REQUESTS", 8))
    prompt_len = int(os.environ.get("BENCH_PROMPT", 128))
    new_tokens = int(os.environ.get("BENCH_NEW", 64))

    # Serving-plan pinning (--plan PATH / BENCH_PLAN): the artifact's
    # engine block supplies the defaults below; explicit BENCH_* env
    # still wins — the same explicit-beats-plan precedence as `llm.plan`
    # in config files (runbookai_tpu/autotune/plan.py). A plan tuned for
    # a different model is refused like from_config refuses it: a banked
    # figure must never cite an artifact that didn't pin it.
    plan = None
    plan_path = os.environ.get("BENCH_PLAN")
    if plan_path:
        from runbookai_tpu.autotune.plan import load_plan

        plan = load_plan(plan_path)
        if plan.model != model_name:
            raise ValueError(
                f"plan {plan.plan_id} was tuned for model "
                f"{plan.model!r}, not {model_name!r} (set BENCH_MODEL or "
                f"re-run `runbook tune`)")

    def pick(key: str, default, env_var: str | None = None):
        """The one spelling of bench's precedence: explicit BENCH_* env
        beats the plan's engine block beats the hand-picked default.
        Integer knobs coerce (env strings, plan JSON numbers); other
        types pass through raw."""
        coerce = isinstance(default, int) and not isinstance(default, bool)
        if env_var is not None and env_var in os.environ:
            value = os.environ[env_var]
            return int(value) if coerce else value
        if plan is not None and plan.engine.get(key) is not None:
            value = plan.engine[key]
            return int(value) if coerce else value
        return default

    def resolve_impl(value: str, default: str) -> str:
        return default if value == "auto" else value

    models_env = os.environ.get("BENCH_MODELS")
    soak_env = os.environ.get("BENCH_SOAK")
    scenarios_env = os.environ.get("BENCH_SOAK_SCENARIOS")
    if os.environ.get("BENCH_SHIFT") and (
            soak_env or scenarios_env or models_env
            or os.environ.get("BENCH_CLASSES")):
        # The soak/models/classes branches run first and would otherwise
        # silently win — the operator must never believe they measured
        # the traffic-shift scenario when a different arm was banked.
        raise ValueError(
            "BENCH_SHIFT measures the single-engine traffic-shift arm "
            "and does not compose with --soak/--soak-scenarios/--models/"
            "--classes (run them as separate arms)")
    if scenarios_env:
        # Chaos soak gate (`--soak-scenarios [S]`): the seeded scenario
        # mix through the full composed stack, chaos on, gated on
        # production invariants (docs/robustness.md). Composes with
        # --models like --soak; refuses the same arms --soak refuses,
        # plus --soak itself (one soak spelling per run).
        if plan is not None or os.environ.get("BENCH_DP") \
                or os.environ.get("BENCH_CLASSES") or soak_env:
            raise ValueError(
                "BENCH_SOAK_SCENARIOS measures the chaos soak gate and "
                "does not compose with --plan/--dp/--classes/--soak "
                "(run them as separate arms)")
        run_soak_scenarios_bench(
            float(scenarios_env), models_env, model_name, probe,
            prompt_len=prompt_len, new_tokens=new_tokens,
            on_accel=on_accel)
        return
    if soak_env:
        # Soak arm (`--soak [S]`): time-bounded mixed traffic through a
        # live fleet — optionally a TWO-GROUP fleet via `--models A,B`
        # (ROADMAP carry-over: soak runs must exercise multi-model
        # serving, not just one engine). Refuses exactly the
        # combinations --models refuses.
        if plan is not None or os.environ.get("BENCH_DP") \
                or os.environ.get("BENCH_CLASSES"):
            raise ValueError(
                "BENCH_SOAK measures the soak arm and does not compose "
                "with --plan/--dp/--classes (run them as separate arms)")
        run_soak_bench(float(soak_env), models_env, model_name, probe,
                       prompt_len=prompt_len, new_tokens=new_tokens,
                       on_accel=on_accel)
        return
    if models_env:
        # Multi-model fleet arm (`--models A,B[:dp]`): interleaved
        # traffic across named model groups through ONE fleet, with
        # per-model digests proven byte-identical to dedicated
        # single-model engines. A plan is per model×topology and the
        # dp/classes arms are single-model — refusing beats silently
        # measuring something else.
        if plan is not None or os.environ.get("BENCH_DP") \
                or os.environ.get("BENCH_CLASSES"):
            raise ValueError(
                "BENCH_MODELS measures the multi-model fleet arm and "
                "does not compose with --plan/--dp/--classes (run them "
                "as separate arms; per-group plans belong in llm.models)")
        run_multimodel_bench(models_env, probe, n_requests=n_requests,
                             prompt_len=prompt_len, new_tokens=new_tokens,
                             on_accel=on_accel)
        return

    overlap = (os.environ["BENCH_OVERLAP"] != "0"
               if "BENCH_OVERLAP" in os.environ
               else bool(pick("overlap_decode", True)))
    # Mixed-dispatch A/B: unset = the engine's auto policy (on for
    # tpu, off on CPU); BENCH_MIXED=0 / --no-mixed forces the split
    # path, BENCH_MIXED=1 forces mixed (CPU smoke of the ragged program).
    mixed_env = os.environ.get("BENCH_MIXED")
    mixed = (pick("mixed_dispatch", None) if mixed_env is None
             else mixed_env != "0")
    slots = pick("max_batch_slots", 8, env_var="BENCH_SLOTS")
    num_pages = pick("num_pages", 1024, env_var="BENCH_PAGES")

    cfg = CONFIGS[model_name]
    dtype = jnp.bfloat16 if on_accel else jnp.float32
    quantized = on_accel and model_name == "llama3-8b-instruct"
    # Real-weights on-ramp (VERDICT r4 #3): $RUNBOOK_WEIGHTS is picked up
    # automatically, switching the quality axis from "unmeasured" to
    # measurable; otherwise random-init (identical compute, no-egress env).
    from runbookai_tpu.utils.weights import discover_weights, quality_marker

    weights_path = discover_weights(model_name)
    if weights_path:
        from runbookai_tpu.models.hf_loader import load_or_init
        from runbookai_tpu.utils.tokens import load_tokenizer

        cfg, params = load_or_init(model_name, weights_path, dtype=dtype,
                                   quantize_int8=quantized)
        tok = load_tokenizer(weights_path)
    elif quantized:
        params = init_params_quantized(jax.random.PRNGKey(0), cfg, dtype=dtype)
        tok = ByteTokenizer()
    else:
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
        tok = ByteTokenizer()
    # HBM-aware page budget: cap the KV pool so weights + pool + working set
    # fit the chip (the slots=16 experiment OOM'd by preallocating an 8GB
    # pool next to 8.5GB of weights), from the device's reported bytes_limit.
    page_size = pick("page_size", 16)
    # BENCH_KV=fp8 halves page bytes (doubles pooled tokens) and keeps
    # the Pallas attention path.
    # BENCH_KV=int8 also halves values but adds per-token scales and
    # serves via the XLA gather path (better accuracy, no fp8 compute).
    kv_name = os.environ.get("BENCH_KV", "")
    if not kv_name and plan is not None:
        kv_name = plan.engine.get("kv_dtype") or ""
    kv_dtype = resolve_kv_dtype(kv_name, dtype)
    # Draft-model weights load BEFORE the page fit so the HBM budget
    # subtracts them (and the fixed draft pool) — BENCH_DRAFT on a full
    # chip must shrink the target pool, not OOM.
    draft_name = os.environ.get("BENCH_DRAFT")
    dcfg = dparams = None
    DRAFT_POOL_PAGES = 256
    if draft_name == "self":
        # Self-draft: the target drafts for itself. Acceptance is then
        # meaningful EVEN with random weights (greedy draft == greedy
        # target wherever numerics agree), so the artifact carries a
        # real acceptance/amortization figure instead of noise — the
        # measurable-now proof of the speculation pipeline (the real
        # speedup needs a smaller draft + real weights).
        dcfg, dparams = cfg, params
    elif draft_name:
        dcfg = CONFIGS[draft_name]
        if on_accel:
            dparams = init_params_quantized(jax.random.PRNGKey(1), dcfg,
                                            dtype=dtype)
        else:
            dparams = init_params(jax.random.PRNGKey(1), dcfg, dtype=dtype)
    if on_accel:
        from runbookai_tpu.models.quant import weight_bytes

        scale_bytes = 4 if jnp.dtype(kv_dtype) == jnp.int8 else 0
        page_bytes = (page_size * cfg.n_layers * 2 * cfg.n_kv_heads
                      * (cfg.head_dim * jnp.dtype(kv_dtype).itemsize
                         + scale_bytes))
        hbm = jax.devices()[0].memory_stats()["bytes_limit"]
        budget = hbm - weight_bytes(params) - int(2.0 * 1024**3)
        if dparams is not None:
            draft_page_bytes = (page_size * dcfg.n_layers * 2
                                * dcfg.n_kv_heads * dcfg.head_dim
                                * jnp.dtype(dtype).itemsize)
            if draft_name != "self":  # self-draft shares the target tree
                budget -= weight_bytes(dparams)
            budget -= DRAFT_POOL_PAGES * draft_page_bytes
        fit = max(256, int(budget // page_bytes))
        if fit < num_pages:
            num_pages = fit
    ecfg = EngineConfig(
        page_size=page_size, num_pages=num_pages, max_batch_slots=slots,
        prefill_chunk=pick("prefill_chunk", 128),
        max_seq_len=pick("max_seq_len", 2048), kv_dtype=kv_dtype,
        block_pages=pick("block_pages", 16),
        decode_steps_per_dispatch=pick("decode_steps_per_dispatch", 8),
        speculative=bool(pick("speculative", True)),
        mixed_token_budget=pick("mixed_token_budget", None),
        # "auto" (from a plan or env) resolves HERE to the backend
        # default — EngineConfig compares impls literally, so an
        # unresolved "auto" would silently serve the XLA path on TPU.
        attn_impl=resolve_impl(
            os.environ.get("BENCH_ATTN", pick("attn_impl", "auto")),
            "pallas" if on_accel else "xla"),
        # Streamed-int8 matmul kernel (ops/qmm_pallas.py): the decode
        # bound is weight bytes/step; this makes the halved byte count
        # structural instead of an XLA fusion gamble.
        qmm_impl=resolve_impl(
            os.environ.get("BENCH_QMM", pick("qmm_impl", "auto")),
            "pallas" if (on_accel and quantized) else "xla"),
        # Batch all concurrent prompts' prefill chunks into one dispatch so
        # TTFT stays ~flat under load (p50_ttft_ms in details tracks this).
        prefill_batch=pick("prefill_batch", slots,
                           env_var="BENCH_PREFILL_BATCH"),
        # Overlapped decode pipeline (device-resident feedback + async
        # egress); BENCH_OVERLAP=0 / --no-overlap is the sync A/B arm.
        overlap_decode=overlap,
        # Unified mixed prefill+decode dispatch (one ragged forward per
        # step with prompts in flight); --no-mixed is the split A/B arm.
        mixed_dispatch=mixed,
    )
    from runbookai_tpu.model.guided import JsonMaskProvider

    # Opt-in draft-model speculation (BENCH_DRAFT=<config name>): only
    # meaningful with REAL weights (random draft ≠ random target gives
    # ~0 acceptance); reports acceptance via spec_drafted/spec_accepted.
    # Weights were loaded above so the page fit accounts for them.
    draft_worker = None
    if dparams is not None:
        from runbookai_tpu.engine.draft import DraftWorker

        draft_worker = DraftWorker(
            dcfg, dparams, max_batch_slots=slots,
            max_seq_len=ecfg.max_seq_len, page_size=page_size,
            num_pages=DRAFT_POOL_PAGES, attn_impl=ecfg.attn_impl)

    masker = JsonMaskProvider(tok)

    rng = np.random.default_rng(0)
    # Optional shared prompt head (BENCH_SHARED_PREFIX tokens): the same
    # leading pages across requests, so the fleet router's prefix-affinity
    # path is exercised. Drawn FIRST so the per-request tails line up
    # between the dp=1 and dp=N arms regardless of the setting.
    shared_len = min(int(os.environ.get("BENCH_SHARED_PREFIX", 0)),
                     max(prompt_len - 1, 0))
    shared_prefix = (rng.integers(0, 256, size=shared_len).tolist()
                     if shared_len else [])
    # BENCH_SESSIONS=K (default 1): requests cycle through K distinct
    # shared prefixes — K live "conversations". One session degenerates
    # to the historical single-prefix series (same rng draws); several
    # make prefix residency ASYMMETRIC across a fleet, which is the
    # workload the kv-share pull seam exists for: a session's follow-up
    # arriving while its owner replica is busy gets placed elsewhere and
    # pulls the prefix instead of re-prefilling it.
    n_sessions = max(1, int(os.environ.get("BENCH_SESSIONS", 1) or 1))
    session_prefixes = [shared_prefix] + [
        rng.integers(0, 256, size=shared_len).tolist()
        for _ in range(n_sessions - 1)]
    prompt_counter = iter(range(10**9))

    def make_prompt() -> list:
        head = session_prefixes[next(prompt_counter) % n_sessions]
        tail = rng.integers(0, 256, size=prompt_len - shared_len).tolist()
        return head + tail

    # Digest of every request's output token stream, in submission order —
    # equal digests across arms prove byte-identical per-request streams.
    outputs_digest = token_streams_digest

    if os.environ.get("BENCH_CLASSES"):
        if os.environ.get("BENCH_DP") or plan is not None:
            # Refusing beats silently measuring something else: a
            # `--classes --dp 4` run would otherwise bank a single-core
            # figure labeled as if it covered the requested fleet.
            raise ValueError(
                "BENCH_CLASSES measures the single-engine scheduler arm "
                "and does not compose with --dp/--plan (run them as "
                "separate arms)")
        # Two-class flood arm (`--classes` / BENCH_CLASSES=1): a batch
        # flood plus staggered interactive requests through ONE engine,
        # measuring per-class TTFT/TPOT against a flood-free interactive
        # baseline. BENCH_SCHED=0 is the FIFO arm (every request in one
        # class); the default arm runs the weighted-deficit scheduler
        # with real priority classes. Digests are per class and must be
        # byte-identical across the two arms (scheduling reorders admits,
        # never alters a stream).
        run_classes_bench(cfg, params, tok, ecfg, masker, probe,
                          n_requests=n_requests, prompt_len=prompt_len,
                          new_tokens=new_tokens, make_prompt=make_prompt,
                          outputs_digest=outputs_digest,
                          on_accel=on_accel, quantized=quantized,
                          weights_path=weights_path)
        return

    if os.environ.get("BENCH_SHIFT"):
        # Traffic-shift arm (`--shift`): short-chat phase then a
        # long-context/guided phase through ONE engine — the ROADMAP
        # item 3 scenario. Proves runbook_workload_drift_score crosses
        # the stale threshold on the shift while the digest stays
        # byte-identical to a fingerprinting-disabled run (BENCH_OBS=0).
        if os.environ.get("BENCH_DP") or plan is not None:
            raise ValueError(
                "BENCH_SHIFT measures the single-engine traffic-shift "
                "arm and does not compose with --dp/--plan (run them as "
                "separate arms)")
        run_shift_bench(cfg, params, tok, ecfg, masker, probe,
                        model_name=model_name, n_requests=n_requests,
                        prompt_len=prompt_len, new_tokens=new_tokens,
                        make_prompt=make_prompt,
                        outputs_digest=outputs_digest,
                        on_accel=on_accel, quantized=quantized,
                        weights_path=weights_path)
        return

    dp_env = os.environ.get("BENCH_DP")
    dp = int(dp_env) if dp_env else pick("dp_replicas", 1)
    dp = max(1, dp)
    # A plan's slots/pages are PER REPLICA (the llm.*/EngineConfig
    # contract) — a plan-sized fleet must not re-split them. The --dp
    # flag keeps its historical fixed-total-budget A/B semantics.
    per_replica = dp > 1 and not dp_env and plan is not None
    plan_detail = ({"id": plan.plan_id, "hash": plan.content_hash,
                    "path": plan_path} if plan is not None else None)
    if dp > 1:
        run_fleet_bench(cfg, params, tok, ecfg, masker, dp, probe,
                        n_requests=n_requests, prompt_len=prompt_len,
                        new_tokens=new_tokens, make_prompt=make_prompt,
                        outputs_digest=outputs_digest, on_accel=on_accel,
                        quantized=quantized, weights_path=weights_path,
                        draft_cfg=dcfg, draft_params=dparams,
                        draft_name=draft_name,
                        draft_pool_pages=DRAFT_POOL_PAGES,
                        plan_detail=plan_detail,
                        per_replica=per_replica)
        return

    core = EngineCore(cfg, params, tok, ecfg,
                      mask_fn=masker.mask, advance_fn=masker.advance,
                      draft_worker=draft_worker)
    # Workload fingerprinting (runbookai_tpu/obs): BENCHLOG arms double
    # as fingerprint fixtures — the end-of-run fingerprint rides in
    # details. BENCH_OBS=0 removes the taps entirely (the byte-identity
    # A/B for the read-only-layer claim).
    fingerprinter = make_bench_fingerprinter([core], model_name)

    def make_req(max_new=new_tokens, guided=None):
        return EngineRequest(
            prompt_ids=make_prompt(),
            sampling=SamplingParams(temperature=0.0, max_new_tokens=max_new,
                                    stop_token_ids=(), guided=guided),
        )

    # Warmup: compile every program shape the measured run will hit — the
    # batched prefill at full occupancy and the multi-step decode — so the
    # measured TTFT is queue+prefill time, not Mosaic/XLA compile time
    # (first on-chip run showed 15.6s p50 TTFT, all of it the 8-row prefill
    # compile landing inside the measured window).
    for _ in range(min(slots, n_requests)):
        core.submit(make_req(max_new=new_tokens if slots > 1 else 4))
    core.run_until_idle()
    # Counters + latency histograms restart with the measured run so the
    # p95s below exclude warmup-compile TTFTs.
    reset_warmup_metrics(core)
    if fingerprinter is not None:
        fingerprinter.reset()  # the fingerprint describes the measured window

    reqs = [make_req() for _ in range(n_requests)]
    prof_ctx, prof_dir = profile_context()
    t0 = time.perf_counter()
    with prof_ctx as prof_captured:
        for r in reqs:
            core.submit(r)
        core.run_until_idle()
    wall = time.perf_counter() - t0

    m = core.metrics
    decode_tps = m["decode_tokens"] / max(m["decode_time_s"], 1e-9)
    total_tokens = m["decode_tokens"] + m["prefill_tokens"]
    ttfts = sorted(r.ttft_ms for r in reqs if r.ttft_ms is not None)
    p50_ttft = ttfts[len(ttfts) // 2] if ttfts else None
    # Tail latency through the engine's serving histograms (the same
    # runbook_ttft_seconds / runbook_tpot_seconds a production scrape sees):
    # bucket-interpolated, so these track the tail trend rather than exact
    # order statistics — BENCH_r*.json now regresses on p95, not just median.
    p95_ttft = core.hist_ttft.percentile(95)
    p95_tpot = core.hist_tpot.percentile(95)

    # MFU: decode FLOPs/token ≈ 2·N over the matmul params (attention reads
    # against short contexts here add <2% — noted as approximate).
    peak = peak_flops_per_chip(probe.get("kind", "")) if on_accel else None
    mfu = (2.0 * cfg.matmul_params * decode_tps / peak) if peak else None

    # Reproducibility contract: the CORE's fully resolved EngineConfig
    # rides in every artifact, so a banked figure can
    # be replayed — and audited against its plan when one pinned the run.
    from runbookai_tpu.autotune.plan import engine_config_dict

    details = {
        "engine_config": engine_config_dict(core.ecfg),
        "plan": plan_detail,
        "model": model_name,
        "weights": "int8" if quantized else str(dtype.__name__ if hasattr(dtype, "__name__") else dtype),
        # Quality axis honesty: random-init weights give real THROUGHPUT
        # numbers but meaningless quality/acceptance — say so in the
        # artifact until a real checkpoint is discovered.
        "quality": quality_marker(weights_path),
        "weights_path": weights_path,
        "platform": probe.get("platform"),
        "device_kind": probe.get("kind"),
        "devices": probe.get("n"),
        # Report the CORE's resolved config, not the caller's: the engine's
        # static mesh/dtype rules may have rewritten either impl.
        "attn_impl": core.ecfg.attn_impl,
        "qmm_impl": core.ecfg.qmm_impl,
        "kv_dtype": str(jnp.dtype(kv_dtype).name),
        "requests": n_requests,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "batch_slots": slots,
        "num_pages": num_pages,
        "prefill_batch": ecfg.prefill_batch,
        "p50_ttft_ms": round(p50_ttft, 1) if p50_ttft is not None else None,
        "p95_ttft_ms": (round(p95_ttft * 1e3, 1)
                        if p95_ttft is not None else None),
        "p95_tpot_ms": (round(p95_tpot * 1e3, 2)
                        if p95_tpot is not None else None),
        "wall_s": round(wall, 2),
        "total_tokens": total_tokens,
        "total_throughput_tok_s": round(total_tokens / wall, 2),
        "decode_steps": m["decode_steps"],
        # Overlapped-pipeline attribution: host work per decode dispatch
        # and the fraction of it hidden behind device execution.
        "overlap": overlap,
        # Mixed-dispatch attribution: the engine's RESOLVED mode (auto may
        # differ from the request), dispatches that served both phases in
        # one forward, and the real tokens each carried.
        "mixed": core._mixed,
        "mixed_dispatches": m.get("mixed_steps", 0),
        "mixed_tokens_per_dispatch": round(
            m.get("mixed_tokens", 0) / max(m.get("mixed_steps", 0), 1), 1),
        "prefill_dispatches": m.get("prefill_steps", 0),
        "decode_dispatches": m.get("decode_dispatches", 0),
        "host_ms_per_step": round(
            m.get("decode_host_time_s", 0.0)
            / max(m["decode_steps"], 1) * 1e3, 3),
        "overlap_ratio": round(
            m.get("decode_host_overlap_s", 0.0)
            / max(m.get("decode_host_time_s", 0.0), 1e-9), 3),
        "preemptions": m["preemptions"],
        # Step-level provenance of the measured window (engine flight
        # recorder): what kinds of dispatches ran, how full the batch
        # sat, and the KV-pressure peak the run actually hit.
        "flight_summary": core.flight.summary(),
        # End-of-run workload fingerprint (obs/): the measured window's
        # traffic in the autotuner's Workload schema — None with
        # BENCH_OBS=0 (taps never installed).
        "workload_fingerprint": (fingerprinter.fingerprint()
                                 if fingerprinter is not None else None),
        "outputs_digest": outputs_digest([r.all_out_ids for r in reqs]),
        "spec_drafted": m.get("spec_drafted", 0),
        "spec_accepted": m.get("spec_accepted", 0),
        "draft_model": draft_name,
        "draft_tokens": m.get("draft_tokens", 0),
        "matmul_params": cfg.matmul_params,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "peak_flops_per_chip": peak,
    }
    prof = profile_detail(prof_dir, prof_captured)
    if prof is not None:
        details["profile"] = prof
    slo = slo_detail(os.environ.get("BENCH_SLO"))
    if slo is not None:
        details["slo"] = slo
    if on_accel and os.environ.get("BENCH_GUIDED", "1") != "0":
        # Secondary metric: guided JSON decoding through the SAME engine —
        # proves the grammar masks + fast-forward on hardware and gives a
        # guided-tok/s figure next to the free-decode headline.
        # Warmup: the masked-sampling program and the fast-forward fold
        # are NEW jit signatures — compile them outside the timed
        # window (the same compile-in-window trap the headline warmup
        # fixes for prefill/decode).
        core.submit(make_req(max_new=8, guided="json"))
        core.run_until_idle()
        t0 = time.perf_counter()
        greqs = [make_req(max_new=96, guided="json") for _ in range(2)]
        for r in greqs:
            core.submit(r)
        core.run_until_idle()
        g_wall = time.perf_counter() - t0
        g_tokens = sum(r.num_generated for r in greqs)
        details["guided_json"] = {
            "tokens": g_tokens,
            "tok_s": round(g_tokens / max(g_wall, 1e-9), 2),
            "grammar_forced_tokens":
                core.metrics.get("grammar_forced_tokens", 0),
            "parseable": all(_parses(core.output_for(r).text)
                             for r in greqs),
        }
    if on_accel and os.environ.get("BENCH_BGE", "1") != "0":
        details["bge_encode"] = bench_bge_encode()
    emit(round(decode_tps, 2), "tok/s", details)


def parse_models_spec(spec: str) -> list[tuple[str, int]]:
    """``A,B:2`` -> [("A", 1), ("B", 2)] — validated against the model
    catalog; at least two distinct groups (one group is just --dp)."""
    from runbookai_tpu.models.llama import CONFIGS

    groups: list[tuple[str, int]] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, dp_s = part.partition(":")
        if name not in CONFIGS:
            raise ValueError(f"--models: unknown model config {name!r} "
                             f"(see models/llama.CONFIGS)")
        groups.append((name, max(1, int(dp_s or 1))))
    names = [n for n, _ in groups]
    if len(groups) < 2 or len(set(names)) != len(names):
        raise ValueError("--models needs >= 2 distinct model configs "
                         "(a one-group fleet is just --dp)")
    return groups


def bench_group_engine_config(on_accel: bool):
    """The per-replica EngineConfig every model-group arm (--models,
    --soak) builds from the BENCH_* env — ONE spelling so the arms
    cannot measure differently-configured fleets."""
    import jax.numpy as jnp

    from runbookai_tpu.engine.engine import EngineConfig

    dtype = jnp.bfloat16 if on_accel else jnp.float32
    return EngineConfig(
        page_size=16, num_pages=int(os.environ.get("BENCH_PAGES", 512)),
        max_batch_slots=int(os.environ.get("BENCH_SLOTS", 4)),
        prefill_chunk=128, max_seq_len=2048, kv_dtype=dtype,
        decode_steps_per_dispatch=8,
        attn_impl="pallas" if on_accel else "xla")


def build_bench_model_groups(groups, params_by_name, tok, ecfg, *,
                             warm_prompt_len, warm_new_tokens,
                             warm_seed=10_007):
    """Shared --models/--soak fleet construction: global replica indices
    assigned contiguously across groups AND disjoint carved device
    slices, exactly like fleet/build.py (without the carve, a dp>1 group
    would slice jax.devices() from 0 while a dp=1 sibling timeshares
    device 0 — per-group tok_s measured under hidden contention). Warmup
    compiles each group's program shapes outside the measured window
    (its own rng stream — measured prompts stay untouched) and resets
    the warmup counters. Returns the MultiModelFleet."""
    import jax

    from runbookai_tpu.engine.fleet import AsyncFleet, build_engine_fleet
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.fleet.multimodel import ModelGroup, MultiModelFleet
    from runbookai_tpu.models.llama import CONFIGS

    all_devices = list(jax.devices())
    total_dp = sum(dp for _, dp in groups)
    carve = len(all_devices) >= total_dp
    start = 0
    model_groups = []
    for gi, (name, dp) in enumerate(groups):
        import dataclasses as _dc

        cores = build_engine_fleet(
            CONFIGS[name], params_by_name[name], tok,
            _dc.replace(ecfg, dp_replicas=dp),
            replica_indices=list(range(start, start + dp)),
            devices=(all_devices[start:start + dp] if carve else []),
            pin_devices=carve)
        start += dp
        model_groups.append(ModelGroup(
            name=name, tokenizer=tok,
            fleet=AsyncFleet(cores, model_label=name,
                             clear_labeled=(gi == 0))))
    fleet = MultiModelFleet(model_groups)
    warm_rng = np.random.default_rng(warm_seed)
    for g in model_groups:
        for core in g.cores:
            core.submit(EngineRequest(
                prompt_ids=warm_rng.integers(
                    0, 256, size=warm_prompt_len).tolist(),
                sampling=SamplingParams(temperature=0.0,
                                        max_new_tokens=warm_new_tokens,
                                        stop_token_ids=())))
            core.run_until_idle()
            reset_warmup_metrics(core)
    return fleet


def run_multimodel_bench(models_spec: str, probe: dict, *, n_requests,
                         prompt_len, new_tokens, on_accel) -> None:
    """The ``--models`` arm: the same interleaved request set served two
    ways — (a) dedicated single-model engines, one per group, each
    serving its own per-model subset; (b) ONE multi-model fleet
    (runbookai_tpu/fleet) routing every request by its model name. Same
    per-group EngineConfig, same seeded params per group, greedy
    sampling — so the per-model output digests must be EQUAL across the
    arms: model-aware routing chooses a group's replica, it never
    changes what that replica samples. The headline is the fleet arm's
    aggregate decode rate; per-group throughput rides in details."""
    import asyncio
    import time as _time

    import jax
    import jax.numpy as jnp

    from runbookai_tpu.engine.engine import EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer
    from runbookai_tpu.utils.weights import quality_marker

    groups = parse_models_spec(models_spec)
    ecfg = bench_group_engine_config(on_accel)
    dtype = ecfg.kv_dtype
    slots, num_pages = ecfg.max_batch_slots, ecfg.num_pages
    tok = ByteTokenizer()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=prompt_len).tolist()
               for _ in range(n_requests)]
    # Submission-order interleave: request i belongs to group i % G, so
    # both arms serve identical per-model subsets in identical order.
    assign = [i % len(groups) for i in range(n_requests)]
    sampling = SamplingParams(temperature=0.0, max_new_tokens=new_tokens,
                              stop_token_ids=())
    params = {name: init_params(jax.random.PRNGKey(1000 + gi),
                                CONFIGS[name], dtype=dtype)
              for gi, (name, _) in enumerate(groups)}

    # Arm (a): dedicated single-model engines — the byte-identity
    # baseline. Unmeasured (digests only); each engine is released
    # before the fleet arm builds.
    dedicated_digests = {}
    for gi, (name, _dp) in enumerate(groups):
        core = EngineCore(CONFIGS[name], params[name], tok, ecfg)
        reqs = [EngineRequest(prompt_ids=list(p), sampling=sampling)
                for p, a in zip(prompts, assign) if a == gi]
        for r in reqs:
            core.submit(r)
        core.run_until_idle()
        dedicated_digests[name] = token_streams_digest(
            [r.all_out_ids for r in reqs])
        del core

    # Arm (b): one multi-model fleet (shared construction + warmup —
    # build_bench_model_groups).
    fleet = build_bench_model_groups(
        groups, params, tok, ecfg, warm_prompt_len=prompt_len,
        warm_new_tokens=new_tokens)
    all_cores = fleet.cores

    async def _run():
        outs = await asyncio.gather(*[
            fleet.generate(list(p), sampling, model=groups[a][0])
            for p, a in zip(prompts, assign)])
        await fleet.stop()
        return outs

    t0 = _time.perf_counter()
    outs = asyncio.run(_run())
    wall = _time.perf_counter() - t0

    per_model = {}
    identical = True
    for gi, (name, dp) in enumerate(groups):
        g_outs = [o for o, a in zip(outs, assign) if a == gi]
        digest = token_streams_digest([o.token_ids for o in g_outs])
        match = digest == dedicated_digests[name]
        identical = identical and match
        g_cores = fleet.groups[name].cores
        decode = sum(c.metrics["decode_tokens"] for c in g_cores)
        decode_t = max(c.metrics["decode_time_s"] for c in g_cores)
        per_model[name] = {
            "dp": dp,
            "requests": len(g_outs),
            "decode_tokens": decode,
            "tok_s": round(decode / max(decode_t, 1e-9), 2),
            "lost_requests": sum(1 for o in g_outs
                                 if o.finish_reason.value == "aborted"),
            "outputs_digest": digest,
            "dedicated_digest": dedicated_digests[name],
            "byte_identical": match,
        }
    total_decode = sum(c.metrics["decode_tokens"] for c in all_cores)
    max_decode_t = max(c.metrics["decode_time_s"] for c in all_cores)
    from runbookai_tpu.autotune.plan import engine_config_dict

    details = {
        "engine_config": engine_config_dict(all_cores[0].ecfg),
        "models": [name for name, _ in groups],
        "multi_model": True,
        "weights": str(jnp.dtype(dtype).name),
        "quality": quality_marker(None),
        "platform": probe.get("platform"),
        "device_kind": probe.get("kind"),
        "requests": n_requests,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "batch_slots_per_replica": slots,
        "num_pages_per_replica": num_pages,
        "wall_s": round(wall, 2),
        "total_throughput_tok_s": round(
            (total_decode + sum(c.metrics["prefill_tokens"]
                                for c in all_cores)) / wall, 2),
        "per_model": per_model,
        "byte_identical": identical,
    }
    emit(round(total_decode / max(max_decode_t, 1e-9), 2), "tok/s",
         details)


def run_classes_bench(cfg, params, tok, ecfg, masker, probe, *,
                      n_requests, prompt_len, new_tokens, make_prompt,
                      outputs_digest, on_accel, quantized,
                      weights_path) -> None:
    """The two-class flood arm (BENCHLOG r9 protocol): prove interactive
    tail latency holds under a concurrent batch flood.

    Three measured windows on one engine:

    1. **flood-free**: the interactive set alone (its unloaded p95 TTFT
       is the yardstick);
    2. **flood**: BENCH_BATCH_REQS batch requests all in the waiting
       queue, THEN the interactive set arrives behind them. Under the
       FIFO arm (BENCH_SCHED=0: one class) interactive queues behind the
       whole flood; under the scheduler arm the weighted-deficit queue
       interleaves admits 8:1, so interactive p95 TTFT should stay within
       ~1.5x its flood-free value while FIFO degrades with flood size.

    Per-class TTFT/TPOT, admit/throttle/shed counters, per-class output
    digests (byte-identical across arms — scheduling must reorder admits,
    never change tokens) and the flight recorder's per-class slot
    occupancy land in ``details``.
    """
    import jax.numpy as jnp

    from runbookai_tpu.engine.engine import EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.sched import PRIORITY_BATCH, PRIORITY_INTERACTIVE
    from runbookai_tpu.utils.metrics import get_registry
    from runbookai_tpu.utils.weights import quality_marker

    sched_on = os.environ.get("BENCH_SCHED", "1") != "0"
    n_batch = int(os.environ.get("BENCH_BATCH_REQS", n_requests))
    n_int = int(os.environ.get("BENCH_INT_REQS", 4))

    core = EngineCore(cfg, params, tok, ecfg,
                      mask_fn=masker.mask, advance_fn=masker.advance)

    def make_req(priority: int, max_new=new_tokens):
        return EngineRequest(
            prompt_ids=make_prompt(),
            sampling=SamplingParams(temperature=0.0, max_new_tokens=max_new,
                                    stop_token_ids=()),
            priority=priority)

    def class_stats(reqs):
        ttfts = sorted(r.ttft_ms for r in reqs if r.ttft_ms is not None)
        tpots = sorted(
            ((r.finish_time - r.first_token_time) * 1e3
             / (r.num_generated - 1))
            for r in reqs
            if r.finish_time and r.first_token_time
            and r.num_generated > 1)

        def pct(values, q):
            if not values:
                return None
            idx = min(len(values) - 1, int(round(q / 100 * (len(values) - 1))))
            return round(values[idx], 2)

        return {
            "requests": len(reqs),
            "p50_ttft_ms": pct(ttfts, 50),
            "p95_ttft_ms": pct(ttfts, 95),
            "p50_tpot_ms": pct(tpots, 50),
            "p95_tpot_ms": pct(tpots, 95),
            "outputs_digest": outputs_digest(
                [r.all_out_ids for r in reqs]),
        }

    # Warmup compiles the program shapes; excluded from every window.
    for _ in range(min(ecfg.max_batch_slots, n_int + n_batch)):
        core.submit(make_req(PRIORITY_INTERACTIVE))
    core.run_until_idle()
    reset_warmup_metrics(core)

    # Window 1: flood-free interactive baseline. The prompt stream is
    # drawn fresh per window (make_prompt advances one rng), so byte
    # parity across arms compares the SAME window index in each arm.
    base_reqs = [make_req(PRIORITY_INTERACTIVE) for _ in range(n_int)]
    for r in base_reqs:
        core.submit(r)
    core.run_until_idle()
    base = class_stats(base_reqs)
    reset_warmup_metrics(core)

    # Window 2: batch flood first, interactive arrives behind it. The
    # FIFO arm collapses the classes (everything batch-priority — one
    # class is FIFO-by-arrival under either policy).
    int_priority = PRIORITY_INTERACTIVE if sched_on else PRIORITY_BATCH
    batch_reqs = [make_req(PRIORITY_BATCH) for _ in range(n_batch)]
    int_reqs = [make_req(int_priority) for _ in range(n_int)]
    t0 = time.perf_counter()
    for r in batch_reqs + int_reqs:
        core.submit(r)
    core.run_until_idle()
    wall = time.perf_counter() - t0

    m = core.metrics
    reg = get_registry()
    interactive = class_stats(int_reqs)
    batch = class_stats(batch_reqs)
    base_p95 = base.get("p95_ttft_ms")
    flood_p95 = interactive.get("p95_ttft_ms")
    details = {
        "arm": "sched" if sched_on else "fifo",
        "sched_policy": ecfg.sched_policy if sched_on else "fifo",
        "model": cfg.name,
        "weights": "int8" if quantized else "float32",
        "quality": quality_marker(weights_path),
        "platform": probe.get("platform"),
        "device_kind": probe.get("kind"),
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "batch_slots": ecfg.max_batch_slots,
        "wall_s": round(wall, 2),
        "classes": {"interactive": interactive, "batch": batch},
        "flood_free_interactive": base,
        # THE acceptance ratio: interactive p95 TTFT under flood over its
        # flood-free value (scheduler arm target: <= 1.5; the FIFO arm
        # grows with flood size).
        "interactive_ttft_ratio": (
            round(flood_p95 / base_p95, 3)
            if base_p95 and flood_p95 else None),
        "throttled_total": (reg.get("runbook_admission_throttled_total")
                            .value
                            if reg.get("runbook_admission_throttled_total")
                            else 0.0),
        "shed_total": (reg.get("runbook_router_shed_total").total()
                       if reg.get("runbook_router_shed_total") else 0.0),
        "preemptions": m["preemptions"],
        "flight_summary": core.flight.summary(),
        "kv_dtype": str(jnp.dtype(ecfg.kv_dtype).name),
    }
    decode_tps = m["decode_tokens"] / max(m["decode_time_s"], 1e-9)
    emit(round(decode_tps, 2), "tok/s", details)


def run_shift_bench(cfg, params, tok, ecfg, masker, probe, *,
                    model_name, n_requests, prompt_len, new_tokens,
                    make_prompt, outputs_digest, on_accel, quantized,
                    weights_path) -> None:
    """The ``--shift`` arm (ROADMAP item 3's scenario): traffic shifts
    mid-run from short-chat to a long-context/guided mix through ONE
    engine, and the workload monitor must SEE it.

    The reference descriptor is the arm's NOMINAL short-chat workload
    (prompt_len/new_tokens/request count — what a plan tuned for this
    traffic would carry as provenance). Phase 1 serves exactly that
    traffic and its measured fingerprint is scored against the nominal
    reference — a real measurement, not a tautology. Phase 2 serves
    4x-length grammar-guided requests scored against the same reference.
    The acceptance contract: ``drift_phase2`` crosses the stale
    threshold while ``drift_phase1`` stays under it, and
    ``outputs_digest`` is byte-identical to a BENCH_OBS=0 run — the
    fingerprint layer observes, it never touches a stream."""
    import jax.numpy as jnp

    from runbookai_tpu.engine.engine import EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.obs import DEFAULT_DRIFT_THRESHOLD, drift_score
    from runbookai_tpu.utils.weights import quality_marker

    core = EngineCore(cfg, params, tok, ecfg,
                      mask_fn=masker.mask, advance_fn=masker.advance)
    fingerprinter = make_bench_fingerprinter([core], model_name)
    long_len = min(prompt_len * 4,
                   max(prompt_len, ecfg.max_seq_len - new_tokens - 8))
    rng = np.random.default_rng(4242)

    def submit(length: int, guided):
        req = EngineRequest(
            prompt_ids=rng.integers(0, 256, size=length).tolist(),
            sampling=SamplingParams(temperature=0.0,
                                    max_new_tokens=new_tokens,
                                    stop_token_ids=(), guided=guided))
        core.submit(req)
        return req

    # Warmup compiles both phases' shapes (incl. the masked-sampling
    # program) outside every measured window.
    warm = [submit(prompt_len, None), submit(long_len, "json")]
    core.run_until_idle()
    del warm
    reset_warmup_metrics(core)
    if fingerprinter is not None:
        fingerprinter.reset()

    # The drift yardstick: the nominal short-chat workload this arm was
    # "tuned" for — independent of anything measured, so drift_phase1 is
    # a real comparison (measured vs nominal), never score(x, x).
    reference = {"prompt_len": prompt_len, "output_len": new_tokens,
                 "concurrency": max(1, n_requests),
                 "guided_share": 0.0, "spec_hit_rate": 0.0}

    t0 = time.perf_counter()
    phase1 = [submit(prompt_len, None) for _ in range(n_requests)]
    core.run_until_idle()
    drift1 = None
    if fingerprinter is not None:
        fp1 = fingerprinter.fingerprint()
        drift1 = (drift_score(fp1["workload"], reference)
                  if fp1 is not None else None)
        # Phase 2 is its own window: clear the request samples AND the
        # flight ring, or phase-1 step records would contaminate the
        # phase-2 concurrency fold.
        fingerprinter.reset()
        core.flight.reset()

    phase2 = [submit(long_len, "json") for _ in range(n_requests)]
    core.run_until_idle()
    wall = time.perf_counter() - t0
    fingerprint = drift2 = None
    if fingerprinter is not None:
        fingerprint = fingerprinter.fingerprint()
        if fingerprint is not None:
            drift2 = drift_score(fingerprint["workload"], reference)

    from runbookai_tpu.autotune.plan import engine_config_dict

    m = core.metrics
    threshold = DEFAULT_DRIFT_THRESHOLD
    details = {
        "arm": "shift",
        "engine_config": engine_config_dict(core.ecfg),
        "model": model_name,
        "weights": "int8" if quantized else "float32",
        "quality": quality_marker(weights_path),
        "platform": probe.get("platform"),
        "device_kind": probe.get("kind"),
        "requests": 2 * n_requests,
        "prompt_len": prompt_len,
        "long_prompt_len": long_len,
        "new_tokens": new_tokens,
        "wall_s": round(wall, 2),
        "obs_enabled": fingerprinter is not None,
        "workload": {
            "reference": reference,
            "drift_phase1": drift1,
            "drift_phase2": drift2,
            "stale_threshold": threshold,
            "crossed": (drift2 is not None and drift2 > threshold),
        },
        "workload_fingerprint": fingerprint,
        "flight_summary": core.flight.summary(),
        # ONE digest over both phases in submission order: equal between
        # BENCH_OBS=1 and BENCH_OBS=0 runs, or the layer is not read-only.
        "outputs_digest": outputs_digest(
            [r.all_out_ids for r in phase1 + phase2]),
        "kv_dtype": str(jnp.dtype(ecfg.kv_dtype).name),
        "preemptions": m["preemptions"],
    }
    decode_tps = m["decode_tokens"] / max(m["decode_time_s"], 1e-9)
    emit(round(decode_tps, 2), "tok/s", details)


def run_soak_bench(duration_s: float, models_spec: str | None,
                   model_name: str, probe: dict, *, prompt_len,
                   new_tokens, on_accel) -> None:
    """The ``--soak [S]`` arm: time-bounded closed-loop mixed traffic
    through a live fleet. With ``--models A,B`` the soak drives a
    TWO-GROUP multi-model fleet (ROADMAP carry-over — soak coverage must
    include model routing), otherwise the single configured model. The
    gate is production shape, not throughput: zero lost requests, every
    group served, and the end-of-run fingerprint banked per group."""
    import asyncio
    import time as _time

    import jax

    from runbookai_tpu.engine.flight_recorder import FlightRecorder
    from runbookai_tpu.engine.request import SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    groups = (parse_models_spec(models_spec) if models_spec
              else [(model_name, 1)])
    ecfg = bench_group_engine_config(on_accel)
    slots = ecfg.max_batch_slots
    tok = ByteTokenizer()
    params = {name: init_params(jax.random.PRNGKey(1000 + gi),
                                CONFIGS[name], dtype=ecfg.kv_dtype)
              for gi, (name, _) in enumerate(groups)}
    # Shared construction + warmup with the --models arm
    # (build_bench_model_groups); fingerprinters install AFTER warmup so
    # the measured loop alone feeds the banked fingerprints.
    fleet = build_bench_model_groups(
        groups, params, tok, ecfg, warm_prompt_len=prompt_len,
        warm_new_tokens=new_tokens, warm_seed=20_011)
    model_groups = list(fleet.groups.values())
    total_dp = fleet.dp
    fingerprinters = {
        g.name: make_bench_fingerprinter(g.cores, g.name)
        for g in model_groups}

    names = [name for name, _ in groups]
    counts = {name: {"requests": 0, "lost": 0} for name in names}
    rng = np.random.default_rng(77)
    prompt_lens = [max(16, prompt_len // 2), prompt_len]

    async def worker(wid: int, deadline: float) -> None:
        i = wid
        while _time.monotonic() < deadline:
            name = names[i % len(names)]
            i += 1
            prompt = rng.integers(
                0, 256, size=prompt_lens[i % len(prompt_lens)]).tolist()
            out = await fleet.generate(
                prompt,
                SamplingParams(temperature=0.0, max_new_tokens=new_tokens,
                               stop_token_ids=()),
                model=name)
            counts[name]["requests"] += 1
            if out.finish_reason.value == "aborted":
                counts[name]["lost"] += 1

    async def _run() -> None:
        deadline = _time.monotonic() + duration_s
        await asyncio.gather(*[worker(w, deadline)
                               for w in range(2 * max(1, total_dp))])
        await fleet.stop()

    t0 = _time.perf_counter()
    asyncio.run(_run())
    wall = _time.perf_counter() - t0

    per_model = {}
    for g in model_groups:
        decode = sum(c.metrics["decode_tokens"] for c in g.cores)
        decode_t = max(c.metrics["decode_time_s"] for c in g.cores)
        fp = fingerprinters[g.name]
        per_model[g.name] = {
            "dp": g.fleet.dp,
            **counts[g.name],
            "decode_tokens": decode,
            "tok_s": round(decode / max(decode_t, 1e-9), 2),
            "workload_fingerprint": (fp.fingerprint()
                                     if fp is not None else None),
        }
    all_cores = fleet.cores
    total_decode = sum(c.metrics["decode_tokens"] for c in all_cores)
    max_decode_t = max(c.metrics["decode_time_s"] for c in all_cores)
    from runbookai_tpu.autotune.plan import engine_config_dict

    details = {
        "arm": "soak",
        "engine_config": engine_config_dict(all_cores[0].ecfg),
        "models": names,
        "multi_model": len(names) > 1,
        "duration_s": duration_s,
        "wall_s": round(wall, 2),
        "platform": probe.get("platform"),
        "device_kind": probe.get("kind"),
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "batch_slots_per_replica": slots,
        "requests": sum(c["requests"] for c in counts.values()),
        "lost_requests": sum(c["lost"] for c in counts.values()),
        "per_model": per_model,
        "flight_summary": FlightRecorder.merge_summaries(
            [c.flight.summary() for c in all_cores]),
    }
    emit(round(total_decode / max(max_decode_t, 1e-9), 2), "tok/s",
         details)


def _soak_scenarios_pass(fleet, mix, *, chaos_schedule=None,
                         supervisor_kw=None, duration_s=0.0,
                         incident_dir=None):
    """Drive one scenario-mix pass through a live MultiModelFleet.

    Open-loop arrivals: each chain sleeps to its scheduled offset, then
    runs its turns causally (an agentic chain's turn carries the
    previous turns' context). With ``chaos_schedule`` set, a
    FleetSupervisor attaches to every group fleet and a ChaosInjector
    walks the schedule against the FIRST group (the dp the schedule was
    generated for); the pass returns per-chain records plus the
    supervisor/chaos snapshots the invariant gate is computed from.

    EVERY pass (chaos or baseline) runs an IncidentMonitor over the
    group fleets — the detection-coverage invariant needs both sides:
    injected fault windows must overlap detected incidents of matching
    signal classes, and the chaos-free baseline must open ZERO (the
    false-positive gate). Hysteresis scales with the run so a 2 s CPU
    smoke and the 1800 s protocol exercise the same lifecycle.

    Each pass also carries its own :class:`MetricsTSDB` (obs/tsdb.py),
    monitor-driven so a registry sweep lands at every detector poll.
    The per-pass store is what isolates the gate's query-expressed
    invariants: registry counters are process-global and cumulative
    across both passes, but ``increase()`` over one pass's window diffs
    only what that pass contributed. The store is returned so the gate
    can evaluate invariants through obs/query.py."""
    import asyncio
    import random as _random
    import time as _time

    from runbookai_tpu.chaos import ChaosInjector, FleetSupervisor
    from runbookai_tpu.engine.request import (
        FinishReason,
        FleetSaturated,
        SamplingParams,
    )
    from runbookai_tpu.obs import (
        IncidentDetector,
        IncidentMonitor,
        MetricsTSDB,
        default_policies,
    )
    from runbookai_tpu.sched import PRIORITY_BATCH, PRIORITY_INTERACTIVE

    model_groups = list(fleet.groups.values())
    supervisors = []
    injector = None
    records: dict[str, dict] = {}
    # Retention must hold the WHOLE pass (plus the recovery tail) or the
    # gate's closing queries would prune away the early fault windows.
    tsdb = MetricsTSDB(
        interval_s=max(0.02, duration_s / 100.0),
        retention_s=max(120.0, duration_s * 4.0 + 60.0),
        max_series=4096)
    incident_monitor = IncidentMonitor(
        [g.fleet for g in model_groups],
        detector=IncidentDetector(default_policies(
            open_after_s=min(5.0, max(0.2, duration_s * 0.1)),
            resolve_after_s=min(10.0, max(0.4, duration_s * 0.2)))),
        bundle_dir=incident_dir, max_bundles=64,
        poll_interval_s=0.02, tsdb=tsdb,
        history_lookback_s=max(2.0, min(60.0, duration_s)))

    async def run_turn(chain, turn, prompt, rec):
        sampling = SamplingParams(
            temperature=chain.temperature,
            max_new_tokens=turn.max_new_tokens, stop_token_ids=(),
            seed=(chain.seed if chain.temperature > 0 else None))
        priority = (PRIORITY_BATCH if chain.priority == "batch"
                    else PRIORITY_INTERACTIVE)
        t0 = _time.monotonic() - rec["_t_origin"]
        toks: list[int] = []
        ttft_ms = None
        aborted = False
        if turn.stream:
            sink: list = []
            try:
                t_start = _time.perf_counter()
                agen = fleet.generate_stream(
                    prompt, sampling, priority=priority,
                    model=chain.model, request_sink=sink,
                    request_id=chain.chain_id)
                async for tok in agen:
                    if ttft_ms is None:
                        ttft_ms = (_time.perf_counter() - t_start) * 1e3
                    toks.append(tok)
            except FleetSaturated:
                aborted = True
            req = sink[-1] if sink else None
            if req is not None and req.finish_reason is FinishReason.ABORTED:
                aborted = True
        else:
            out = await fleet.generate(
                prompt, sampling, priority=priority, model=chain.model,
                request_id=chain.chain_id)
            toks = list(out.token_ids)
            ttft_ms = out.ttft_ms
            aborted = out.finish_reason is FinishReason.ABORTED
        rec["turns"].append({
            "t_start_s": round(t0, 4),
            "t_end_s": round(_time.monotonic() - rec["_t_origin"], 4),
            "ttft_ms": (round(ttft_ms, 3) if ttft_ms is not None
                        else None),
            "tokens": len(toks),
            "aborted": aborted,
        })
        return toks, aborted

    async def run_chain(chain, t_origin):
        rec = {"cls": chain.cls, "tenant": chain.tenant,
               "model": chain.model, "interactive":
               chain.priority == "interactive",
               "turns": [], "aborted": False, "_t_origin": t_origin,
               "streams": []}
        records[chain.chain_id] = rec
        await asyncio.sleep(max(0.0, chain.at_s
                                - (_time.monotonic() - t_origin)))
        context: list[int] = []
        for turn in chain.turns:
            if turn.gap_s:
                await asyncio.sleep(turn.gap_s)
            prompt = (context + list(turn.prompt_ids)
                      if chain.carry_context else list(turn.prompt_ids))
            # Keep causal chains inside the engine's sequence budget.
            max_prompt = 2048 - turn.max_new_tokens - 16
            prompt = prompt[-max_prompt:]
            toks, aborted = await run_turn(chain, turn, prompt, rec)
            rec["streams"].append(toks)
            if aborted:
                rec["aborted"] = True
                break  # a dead turn kills the causal chain
            context = prompt + toks
        rec["t_start_s"] = rec["turns"][0]["t_start_s"] if rec["turns"] \
            else chain.at_s
        rec["t_end_s"] = rec["turns"][-1]["t_end_s"] if rec["turns"] \
            else chain.at_s
        rec["digest"] = token_streams_digest(rec.pop("streams"))
        rec.pop("_t_origin")

    async def _run():
        nonlocal injector
        loop = asyncio.get_running_loop()
        t_origin = _time.monotonic()
        wall_origin = _time.time()
        incident_monitor.start()
        if chaos_schedule is not None:
            for g in model_groups:
                sup = FleetSupervisor(g.fleet, **(supervisor_kw or {}))
                sup.start()
                supervisors.append(sup)

            def flood_fn(event):
                # Synthetic tenant-flood burst: fire-and-forget batch
                # requests through the event loop — chaos traffic, not
                # gated traffic.
                rng = _random.Random(event.at_s)
                sp = SamplingParams(temperature=0.0, max_new_tokens=4,
                                    stop_token_ids=())

                async def _flood():
                    await asyncio.gather(*[
                        fleet.generate(
                            [rng.randrange(0, 256) for _ in range(24)],
                            sp, priority=PRIORITY_BATCH,
                            model=model_groups[0].name)
                        for _ in range(event.params.get("requests", 4))],
                        return_exceptions=True)

                asyncio.run_coroutine_threadsafe(_flood(), loop)

            injector = ChaosInjector(model_groups[0].fleet,
                                     chaos_schedule, flood_fn=flood_fn)
            injector.start()
        await asyncio.gather(*[run_chain(c, t_origin)
                               for c in mix.chains])
        if injector is not None:
            # Recovery phase: keep light probe traffic flowing until an
            # applied crash has been detected AND every replica is back
            # to healthy (or the budget runs out) — a crash whose hook
            # fires on the run's last step still gets its full
            # detect→rebuild→rejoin arc before the supervisors stop.
            # Probes are chaos plumbing, never gated traffic.
            deadline = _time.monotonic() + min(
                15.0, max(3.0, duration_s))
            probe_sp = SamplingParams(temperature=0.0, max_new_tokens=2,
                                      stop_token_ids=())

            def needs_recovery() -> bool:
                crash_applied = any(
                    w["kind"] == "replica_crash"
                    and w["status"] == "applied"
                    for w in injector.snapshot()["windows"])
                trans = [t for s in supervisors for t in s.transitions]
                if crash_applied and not any(t["to"] == "failed"
                                             for t in trans):
                    return True  # hook or detection still pending
                return any(s.state_of(i) != "healthy"
                           for s in supervisors
                           for i in range(s.fleet.dp))

            while needs_recovery() and _time.monotonic() < deadline:
                await asyncio.gather(*[
                    fleet.generate(list(range(65, 81)), probe_sp,
                                   model=g.name)
                    for g in model_groups], return_exceptions=True)
                await asyncio.sleep(0.05)
            injector.stop()
        for sup in supervisors:
            sup.stop()
        incident_monitor.stop()
        await fleet.stop()
        return t_origin, wall_origin

    t0 = _time.perf_counter()
    _t_origin, wall_origin = asyncio.run(_run())
    wall = _time.perf_counter() - t0
    return {
        "records": records,
        "wall_s": round(wall, 3),
        "wall_origin": wall_origin,
        "chaos": injector.snapshot() if injector is not None else None,
        "supervisors": [s.snapshot() for s in supervisors],
        "incidents": incident_monitor.incidents(),
        "tsdb": tsdb,
    }


def _soak_query(store, expr: str) -> dict:
    """Evaluate one gate condition through the embedded history
    (obs/tsdb.py + obs/query.py) instead of the pass's in-process
    measurements. The verdict coming out the query path proves the
    store actually carried the signal end to end — sampling, retention,
    and evaluator semantics (counter resets, absence-not-zero) all sit
    between the fleet and the number the gate reads."""
    from runbookai_tpu.obs import evaluate

    newest = store.snapshot()["newest_ts"]
    if newest is None:
        return {"expr": expr, "values": []}
    doc = evaluate(store, expr, now=newest)
    return {"expr": expr,
            "values": [r["value"] for r in doc["result"]]}


def _soak_effective_windows(passed: dict) -> list[tuple[float, float]]:
    """Fault windows in run-offset seconds, extended to RECOVERY: a
    crash/wedge window stays open until the target replica's next
    rejoin-to-healthy transition (a chain failing between the crash and
    the rebuild is inside the fault, not a lost request). Every
    supervisor failure→rejoin arc counts as a window too — a failover
    the supervisor initiated IS fault handling, injected or not (excess
    arcs stay visible as details.supervisor.rebuilds_total churn)."""
    chaos = passed.get("chaos")
    if not chaos:
        return []
    wall_origin = passed["wall_origin"]
    transitions = [t for s in passed["supervisors"]
                   for t in s["transitions"]]

    def rejoin_after(replica, start):
        rejoins = [t["ts"] - wall_origin for t in transitions
                   if t["replica"] == replica and t["to"] == "healthy"
                   and t["ts"] - wall_origin >= start]
        return min(rejoins) if rejoins else float("inf")

    windows = []
    for w in chaos["windows"]:
        start, end = w["applied_at_s"], w["ends_at_s"]
        if w["kind"] in ("replica_crash", "replica_wedge"):
            end = rejoin_after(w["replica"], start)
        windows.append((start - 0.1, end + 0.1))
    for t in transitions:
        if t["to"] == "failed":
            start = t["ts"] - wall_origin
            windows.append((start - 0.1,
                            rejoin_after(t["replica"], start) + 0.1))
    return windows


def _incident_coverage(chaotic: dict) -> tuple[list[dict], bool]:
    """Detection-coverage table: one row per APPLIED fault window —
    which signal class detected it and how long detection took (MTTD).
    Crash/wedge windows extend to the target replica's rejoin (same
    recovery extension as the lost-request gate). Returns ``(rows,
    required_ok)``: kinds in ``COVERAGE_REQUIRED_KINDS`` (their
    detection path — supervisor transitions — is deterministic) MUST
    overlap a detected incident; other kinds are reported but a miss
    does not fail the gate (a 10 ms kv_pull_delay legitimately detects
    as nothing)."""
    from runbookai_tpu.obs import (
        COVERAGE_REQUIRED_KINDS,
        FAULT_SIGNAL_CLASSES,
    )

    chaos = chaotic.get("chaos")
    if not chaos:
        return [], True
    wall_origin = chaotic["wall_origin"]
    transitions = [t for s in chaotic["supervisors"]
                   for t in s["transitions"]]

    def rejoin_after(replica, start):
        rejoins = [t["ts"] - wall_origin for t in transitions
                   if t["replica"] == replica and t["to"] == "healthy"
                   and t["ts"] - wall_origin >= start]
        return min(rejoins) if rejoins else float("inf")

    spans = [(inc, inc["opened_ts"] - wall_origin,
              (inc["resolved_ts"] - wall_origin)
              if inc.get("resolved_ts") is not None else float("inf"))
             for inc in chaotic.get("incidents", ())]
    rows: list[dict] = []
    required_ok = True
    for w in chaos["windows"]:
        if w["status"] != "applied":
            continue
        start, end = w["applied_at_s"], w["ends_at_s"]
        if w["kind"] in ("replica_crash", "replica_wedge"):
            end = rejoin_after(w["replica"], start)
        expected = FAULT_SIGNAL_CLASSES.get(w["kind"], ())
        hits = [(inc, opened) for inc, opened, resolved in spans
                if inc["signal"] in expected
                and opened <= end + 0.25 and resolved >= start - 0.25]
        hit = min(hits, key=lambda p: p[1]) if hits else None
        required = w["kind"] in COVERAGE_REQUIRED_KINDS
        if required and hit is None:
            required_ok = False
        rows.append({
            "kind": w["kind"],
            "replica": w["replica"],
            "window_s": [round(start, 3),
                         round(end, 3) if end != float("inf") else None],
            "expected_signals": list(expected),
            "detected_signal": hit[0]["signal"] if hit else None,
            "incident": hit[0]["id"] if hit else None,
            "mttd_s": (round(max(0.0, hit[1] - start), 3)
                       if hit else None),
            "required": required,
        })
    return rows, required_ok


def _overlaps(rec: dict, windows) -> bool:
    s, e = rec.get("t_start_s", 0.0), rec.get("t_end_s", 0.0)
    return any(s < we and e > ws for ws, we in windows)


def run_soak_scenarios_bench(duration_s: float, models_spec: str | None,
                             model_name: str, probe: dict, *,
                             prompt_len, new_tokens, on_accel) -> None:
    """The ``--soak-scenarios [S]`` arm: the production-invariant soak
    gate (ROADMAP item 5; docs/robustness.md).

    A seeded scenario mix (simulate/traffic.py: short chat, agentic
    chains, batch floods, shared-prefix sessions, spiky tenants) runs
    TWICE through identically-built fleets: a chaos-free baseline pass,
    then a chaos pass with the seeded fault schedule (chaos/inject.py)
    and a fleet supervisor on every group (chaos/supervisor.py). The
    gate is production shape, not throughput:

    - zero lost requests outside (recovery-extended) fault windows;
    - interactive p95 TTFT within ``BENCH_SOAK_TTFT_P95_MS``;
    - per-tenant completion fairness;
    - bounded RSS growth and fd delta across the chaos pass;
    - per-chain digest determinism: every chain completed in both
      passes outside fault windows is byte-identical to the baseline;
    - supervisor recovery: an injected crash is detected, failed over,
      rebuilt and rejoined (the transition record proves it).

    Every verdict lands in ``details["invariants"]`` with its measured
    figures; the headline stays the chaos pass's decode rate."""
    import jax

    from runbookai_tpu.chaos import FaultSchedule
    from runbookai_tpu.engine.flight_recorder import FlightRecorder
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.simulate.traffic import generate_traffic
    from runbookai_tpu.utils.tokens import ByteTokenizer

    dp_default = int(os.environ.get("BENCH_SOAK_DP", 2))
    groups = (parse_models_spec(models_spec) if models_spec
              else [(model_name, max(2, dp_default))])
    ecfg = bench_group_engine_config(on_accel)
    tok = ByteTokenizer()
    params = {name: init_params(jax.random.PRNGKey(1000 + gi),
                                CONFIGS[name], dtype=ecfg.kv_dtype)
              for gi, (name, _) in enumerate(groups)}
    names = [name for name, _ in groups]
    seed = int(os.environ.get("BENCH_CHAOS_SEED", 14))
    chaos_on = os.environ.get("BENCH_CHAOS", "1") != "0"
    mix = generate_traffic(
        seed, duration_s,
        chains_per_minute=float(os.environ.get("BENCH_SOAK_RATE", 120)),
        prompt_scale=prompt_len / 128.0,
        max_new_scale=new_tokens / 64.0,
        models=(names if len(names) > 1 else None))
    schedule = (FaultSchedule.generate(
        seed, duration_s, groups[0][1], ensure_crash=True)
        if chaos_on else None)
    supervisor_kw = {
        "poll_interval_s": 0.02,
        # The floor must exceed a rebuilt core's first-dispatch compile
        # (the docs/robustness.md wedge_timeout_s contract) — an
        # aggressive value fails over replicas that are merely
        # compiling, and a dp=1 group then flaps rebuild→compile→
        # false-wedge forever.
        "wedge_timeout_s": float(os.environ.get(
            "BENCH_WEDGE_TIMEOUT_S",
            max(3.0, min(8.0, duration_s * 0.1)))),
        "rejoin_hysteresis_s": min(0.5, max(0.05, duration_s * 0.02)),
    }

    def build():
        return build_bench_model_groups(
            groups, params, tok, ecfg, warm_prompt_len=prompt_len,
            warm_new_tokens=new_tokens, warm_seed=20_011)

    import resource
    import shutil
    import tempfile

    # Baseline pass: same mix, no chaos — the digest reference AND the
    # detection false-positive gate (its incident monitor must open
    # zero incidents against fault-free traffic).
    baseline = _soak_scenarios_pass(build(), mix, duration_s=duration_s)

    fd_dir = "/proc/self/fd"
    fds_before = (len(os.listdir(fd_dir)) if os.path.isdir(fd_dir)
                  else None)
    rss_before_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    # Black-box capture target for the chaos pass: keep the bundles when
    # the operator names a directory, else a temp dir verified + pruned
    # after the gate reads it.
    incident_dir = os.environ.get("BENCH_INCIDENT_DIR")
    keep_bundles = bool(incident_dir)
    if not incident_dir:
        incident_dir = tempfile.mkdtemp(prefix="bench-incidents-")

    fleet = build()
    chaotic = _soak_scenarios_pass(
        fleet, mix, chaos_schedule=schedule,
        supervisor_kw=supervisor_kw, duration_s=duration_s,
        incident_dir=incident_dir)
    # Read AFTER the pass: a rebuild swapped the crashed replica's core,
    # and the throughput/flight summaries must cover the live fleet.
    all_cores = fleet.cores

    rss_after_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    fds_after = (len(os.listdir(fd_dir)) if os.path.isdir(fd_dir)
                 else None)

    windows = _soak_effective_windows(chaotic)
    recs = chaotic["records"]
    base_recs = baseline["records"]
    lost = [cid for cid, r in recs.items() if r["aborted"]]
    lost_outside = [cid for cid in lost
                    if not _overlaps(recs[cid], windows)]
    ttfts = sorted(
        t["ttft_ms"] for r in recs.values() if r["interactive"]
        for t in r["turns"] if t["ttft_ms"] is not None)
    p95_ttft = (ttfts[min(len(ttfts) - 1,
                          int(0.95 * len(ttfts)))] if ttfts else None)
    ttft_bound = float(os.environ.get("BENCH_SOAK_TTFT_P95_MS", 30_000))
    per_tenant: dict[str, dict] = {}
    for r in recs.values():
        t = per_tenant.setdefault(r["tenant"],
                                  {"chains": 0, "completed": 0})
        t["chains"] += 1
        t["completed"] += 0 if r["aborted"] else 1
    fairness_floor = float(os.environ.get("BENCH_SOAK_FAIRNESS", 0.5))
    fairness_min = min((t["completed"] / t["chains"]
                        for t in per_tenant.values()), default=1.0)
    mismatched = [
        cid for cid, r in recs.items()
        if not r["aborted"] and not _overlaps(r, windows)
        and cid in base_recs and not base_recs[cid]["aborted"]
        and r["digest"] != base_recs[cid]["digest"]]
    rss_growth_mb = (rss_after_kb - rss_before_kb) / 1024.0
    rss_bound_mb = float(os.environ.get("BENCH_SOAK_RSS_MB", 8192))
    fd_delta = (fds_after - fds_before
                if fds_before is not None and fds_after is not None
                else None)
    crash_applied = bool(chaotic["chaos"]) and any(
        w["kind"] == "replica_crash" and w["status"] == "applied"
        for w in chaotic["chaos"]["windows"])
    transitions = [t for s in chaotic["supervisors"]
                   for t in s["transitions"]]
    recovered = (not crash_applied) or all(
        any(t["replica"] == w["replica"] and t["to"] == state
            for t in transitions)
        for w in chaotic["chaos"]["windows"]
        if w["kind"] == "replica_crash" and w["status"] == "applied"
        for state in ("failed", "rebuilding", "rejoining", "healthy"))
    # Detection coverage (obs/detect.py, obs/incident.py): every
    # REQUIRED injected fault window overlaps a detected incident of a
    # matching signal class; the chaos-free baseline opened zero
    # incidents; every captured bundle is schema-valid and its content
    # hash verifies.
    coverage_rows, coverage_required_ok = _incident_coverage(chaotic)
    baseline_opens = len(baseline.get("incidents", ()))
    from pathlib import Path as _Path

    from runbookai_tpu.obs import BUNDLE_SCHEMA_VERSION
    from runbookai_tpu.obs.incident import bundle_hash, load_bundle

    # Verify THIS run's bundles only (each incident records the bundle
    # it captured): a shared BENCH_INCIDENT_DIR may hold bundles from
    # earlier runs, and neither a stale corrupt file nor a stale valid
    # one may decide this run's verdict. An incident with NO recorded
    # bundle is itself a failure — the black box went dark exactly when
    # it mattered. One load per bundle; the hash check is inline.
    bundle_rows = []
    for inc in chaotic.get("incidents", ()):
        name = inc.get("bundle")
        row = {"incident": inc["id"], "name": name,
               "hash_verified": False, "schema_valid": False,
               "has_history": False}
        if name:
            try:
                doc = load_bundle(_Path(incident_dir) / name)
            except (OSError, json.JSONDecodeError):
                doc = None
            if doc is not None:
                row["hash_verified"] = (doc.get("content_hash")
                                        == bundle_hash(doc))
                row["schema_valid"] = (doc.get("schema_version")
                                       == BUNDLE_SCHEMA_VERSION)
                # The pre-open lookback window (obs/tsdb.py) sits
                # INSIDE the hash envelope — hash_verified above
                # already proves it arrived untampered.
                row["has_history"] = doc.get("history") is not None
        bundle_rows.append(row)
    if not keep_bundles:
        shutil.rmtree(incident_dir, ignore_errors=True)
    # has_history gates too: every soak monitor carries a store, so a
    # bundle without its lookback section means the black box dropped
    # the trend exactly when it mattered.
    bundles_ok = all(b["hash_verified"] and b["schema_valid"]
                     and b["has_history"] for b in bundle_rows)
    invariants = {
        "zero_lost_outside_fault_windows": {
            "passed": not lost_outside,
            "lost_total": len(lost),
            "lost_outside_windows": lost_outside},
        "interactive_ttft_p95": {
            "passed": p95_ttft is None or p95_ttft <= ttft_bound,
            "p95_ms": (round(p95_ttft, 2) if p95_ttft is not None
                       else None),
            "bound_ms": ttft_bound},
        "tenant_fairness": {
            "passed": fairness_min >= fairness_floor,
            "min_completion_ratio": round(fairness_min, 4),
            "floor": fairness_floor,
            "per_tenant": per_tenant},
        "rss_bound": {
            "passed": rss_growth_mb <= rss_bound_mb,
            "growth_mb": round(rss_growth_mb, 1),
            "bound_mb": rss_bound_mb},
        "fd_bound": {
            "passed": fd_delta is None or fd_delta <= 64,
            "delta": fd_delta},
        "digest_determinism": {
            "passed": not mismatched,
            "compared": sum(
                1 for cid, r in recs.items()
                if not r["aborted"] and not _overlaps(r, windows)
                and cid in base_recs and not base_recs[cid]["aborted"]),
            "mismatched": mismatched},
        "supervisor_recovered": {
            "passed": recovered,
            "crash_applied": crash_applied},
        "detection_coverage": {
            "passed": (coverage_required_ok and baseline_opens == 0
                       and bundles_ok),
            "required_covered": coverage_required_ok,
            "baseline_opens": baseline_opens,
            "chaos_incidents": len(chaotic.get("incidents", ())),
            "bundles": bundle_rows},
    }
    # Query-expressed invariants: the same gate conditions re-derived
    # through each pass's embedded time-series store (obs/tsdb.py) and
    # the PromQL-lite evaluator (obs/query.py). Each pass carries its
    # OWN store, so increase()/max_over_time() over its window isolate
    # that pass's contribution even though registry counters are
    # process-global. These merge into ``invariants`` and therefore
    # gate ``invariants_passed`` like every direct measurement above.
    q_win = f"{int(math.ceil(chaotic['tsdb'].retention_s))}s"
    q_base_inc = _soak_query(
        baseline["tsdb"], f"increase(runbook_incident_total[{q_win}])")
    q_base_shed = _soak_query(
        baseline["tsdb"],
        f"increase(runbook_router_shed_total[{q_win}])")
    q_open = _soak_query(
        chaotic["tsdb"], f"max_over_time(runbook_incident_open[{q_win}])")
    q_ttft = _soak_query(
        chaotic["tsdb"],
        f"histogram_quantile(0.95, runbook_ttft_seconds_bucket[{q_win}])")
    q_ttft_worst = max(q_ttft["values"], default=None)
    invariants["query_baseline_zero_incidents"] = {
        # False-positive gate through the store: the chaos-free pass's
        # incident counters must not have moved. An empty result also
        # passes — absence is "never sampled", not a hidden increment.
        "passed": all(v == 0 for v in q_base_inc["values"]), **q_base_inc}
    invariants["query_baseline_zero_lost"] = {
        "passed": all(v == 0 for v in q_base_shed["values"]),
        **q_base_shed}
    invariants["query_detection_coverage"] = {
        # runbook_incident_open is ABSENT while nothing is open, so a
        # sampled value >= 1 proves the store caught the incident's
        # open window in flight.
        "passed": ((not crash_applied)
                   or any(v >= 1 for v in q_open["values"])),
        "crash_applied": crash_applied, **q_open}
    invariants["query_interactive_ttft_p95"] = {
        # Bucket-interpolated p95 of the worst series (per-replica
        # grouping) against the same bound the direct measurement uses.
        "passed": (q_ttft_worst is None
                   or q_ttft_worst * 1e3 <= ttft_bound),
        "p95_ms": (round(q_ttft_worst * 1e3, 2)
                   if q_ttft_worst is not None else None),
        "bound_ms": ttft_bound, **q_ttft}
    total_decode = sum(c.metrics["decode_tokens"] for c in all_cores)
    max_decode_t = max(c.metrics["decode_time_s"] for c in all_cores)
    from runbookai_tpu.autotune.plan import engine_config_dict

    details = {
        "arm": "soak_scenarios",
        "engine_config": engine_config_dict(all_cores[0].ecfg),
        "models": names,
        "multi_model": len(names) > 1,
        "dp": fleet.dp,
        "duration_s": duration_s,
        "wall_s": chaotic["wall_s"],
        "baseline_wall_s": baseline["wall_s"],
        "platform": probe.get("platform"),
        "device_kind": probe.get("kind"),
        "chaos_enabled": chaos_on,
        "chaos_seed": seed,
        "chains": len(recs),
        "turns": sum(len(r["turns"]) for r in recs.values()),
        "classes": mix.by_class(),
        "fault_windows": [[round(s, 3),
                           (round(e, 3) if e != float("inf") else None)]
                          for s, e in windows],
        # Fault kind → detected signal + MTTD, one row per applied
        # window — the banked detection-coverage table (obs/detect.py's
        # FAULT_SIGNAL_CLASSES mapping).
        "incident_coverage": coverage_rows,
        "incidents": chaotic.get("incidents", []),
        # Chaos pass store accounting (series/sample/memory bounds) —
        # the query invariants above were evaluated against this store.
        "tsdb": chaotic["tsdb"].snapshot(),
        "invariants": invariants,
        "invariants_passed": all(v["passed"]
                                 for v in invariants.values()),
        "chaos": chaotic["chaos"],
        "supervisor": ({"rebuilds_total": sum(
            s["rebuilds_total"] for s in chaotic["supervisors"]),
            "failovers_total": sum(
                s["failovers_total"] for s in chaotic["supervisors"]),
            "transitions": transitions}
            if chaotic["supervisors"] else None),
        "flight_summary": FlightRecorder.merge_summaries(
            [c.flight.summary() for c in all_cores]),
    }
    emit(round(total_decode / max(max_decode_t, 1e-9), 2), "tok/s",
         details)


def run_fleet_bench(cfg, params, tok, ecfg, masker, dp, probe, *,
                    n_requests, prompt_len, new_tokens, make_prompt,
                    outputs_digest, on_accel, quantized, weights_path,
                    draft_cfg=None, draft_params=None, draft_name=None,
                    draft_pool_pages=256, plan_detail=None,
                    per_replica=False) -> None:
    """The ``--dp N`` arm: the SAME request set through a data-parallel
    engine fleet. The slot/page budget splits across replicas (fixed total
    resources, like a pod slicing its chips along the dp axis — the split
    is exact, never rounded UP past the dp=1 arm's budget), each replica's
    AsyncEngine loop steps on its own worker thread, and the
    prefix-affinity router places every request. BENCH_DRAFT builds one
    draft worker per replica so a speculative A/B stays symmetric. The
    headline is the aggregate decode rate over the concurrent window
    (total decode tokens / the busiest replica's decode wall);
    ``outputs_digest`` must equal the dp=1 arm's — routing chooses a
    replica, never changes a stream."""
    import asyncio
    import time as _time

    import jax.numpy as jnp

    from runbookai_tpu.engine.fleet import (
        AsyncFleet,
        FleetConfig,
        build_engine_fleet,
        split_engine_budget,
    )
    from runbookai_tpu.engine.flight_recorder import FlightRecorder
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.utils.weights import quality_marker

    if per_replica:
        # Plan-sized fleet: slots/pages already PER REPLICA (the
        # llm.*/EngineConfig contract) — just stamp the replica count.
        import dataclasses as _dc

        ecfg = _dc.replace(ecfg, dp_replicas=dp)
        slots_total = ecfg.max_batch_slots * dp
    else:
        # --dp A/B: exact per-replica split of the fleet-TOTAL budget
        # (never rounded UP past the dp=1 arm's resources) —
        # fleet.split_engine_budget.
        slots_total = ecfg.max_batch_slots
        ecfg = split_engine_budget(ecfg, dp)
    slots_per = ecfg.max_batch_slots
    draft_factory = None
    if draft_params is not None:
        from runbookai_tpu.engine.draft import DraftWorker

        def draft_factory(_idx: int) -> "DraftWorker":
            return DraftWorker(
                draft_cfg, draft_params, max_batch_slots=slots_per,
                max_seq_len=ecfg.max_seq_len, page_size=ecfg.page_size,
                num_pages=max(2, draft_pool_pages // dp),
                attn_impl=ecfg.attn_impl)
    cores = build_engine_fleet(cfg, params, tok, ecfg,
                               mask_fn=masker.mask,
                               advance_fn=masker.advance,
                               draft_worker_factory=draft_factory)
    fingerprinter = make_bench_fingerprinter(cores, cfg.name)

    # KV-share / disagg A/B arms (BENCH_KV_SHARE / BENCH_DISAGG): same
    # request set, same per-replica budgets — the only change is the
    # router's page policy, so any TTFT/TPOT delta is attributable to it.
    kv_share = os.environ.get("BENCH_KV_SHARE", "0") == "1"
    disagg_n = int(os.environ.get("BENCH_DISAGG", 0) or 0)
    # Either arm of the kv-share A/B (BENCH_KV_SHARE set to 0 OR 1, or a
    # disagg run): warmup prompts must not carry the measured shared
    # prefix, or warmup pre-publishes it on EVERY replica and both arms
    # measure a pool where there is nothing left to pull. Off by default:
    # the historical --dp affinity arm deliberately warms the prefix.
    deshared_warmup = "BENCH_KV_SHARE" in os.environ or disagg_n > 0

    # Warmup compiles every program shape per replica (each replica's
    # device slice is its own executable), consuming exactly the same rng
    # draws as the dp=1 arm so the measured prompts line up across arms
    # (a de-shared warmup draws its replacement tokens from a SEPARATE
    # rng, leaving the measured stream untouched).
    warm_rng = np.random.default_rng(10_007)
    warm = min(slots_total, n_requests)
    for w in range(warm):
        p = make_prompt()
        if deshared_warmup:
            p = warm_rng.integers(0, 256, size=len(p)).tolist()
        cores[w % dp].submit(EngineRequest(
            prompt_ids=p,
            sampling=SamplingParams(temperature=0.0,
                                    max_new_tokens=new_tokens,
                                    stop_token_ids=())))
    for core in cores:
        core.run_until_idle()
        reset_warmup_metrics(core)
    if fingerprinter is not None:
        fingerprinter.reset()

    fleet = AsyncFleet(cores, FleetConfig(
        kv_share=kv_share, disagg_prefill_replicas=disagg_n))
    prompts = [make_prompt() for _ in range(n_requests)]
    sampling = SamplingParams(temperature=0.0, max_new_tokens=new_tokens,
                              stop_token_ids=())

    # BENCH_STAGGER_MS: inter-arrival spacing for the measured window.
    # 0 (default) keeps the historical all-at-once gather; the kv-share
    # A/B needs a stagger, because a request can only pull pages a
    # sibling has already prefilled — an instantaneous burst routes every
    # request before any prefix page exists anywhere.
    stagger_s = float(os.environ.get("BENCH_STAGGER_MS", 0) or 0) / 1e3

    async def _one(i: int, p: list) -> "EngineOutput":
        if stagger_s:
            await asyncio.sleep(i * stagger_s)
        return await fleet.generate(p, sampling)

    async def _run():
        outs = await asyncio.gather(*[
            _one(i, p) for i, p in enumerate(prompts)])
        await fleet.stop()
        return outs

    prof_ctx, prof_dir = profile_context()
    t0 = _time.perf_counter()
    with prof_ctx as prof_captured:
        outs = asyncio.run(_run())
    wall = _time.perf_counter() - t0

    # Lost = aborted/shed (a stop-token finish is a legitimate completion;
    # byte-identity across arms is what outputs_digest pins).
    lost = sum(1 for o in outs if o.finish_reason.value == "aborted")
    total_decode = sum(c.metrics["decode_tokens"] for c in cores)
    max_decode_t = max(c.metrics["decode_time_s"] for c in cores)
    routed = fleet.routed_counts()
    replica_stats = [{
        "replica": i,
        "tier": ("prefill" if i < disagg_n
                 else "decode" if disagg_n else "mixed"),
        "requests_routed": routed[i],
        "decode_tokens": c.metrics["decode_tokens"],
        "decode_time_s": round(c.metrics["decode_time_s"], 3),
        "tok_s": round(c.metrics["decode_tokens"]
                       / max(c.metrics["decode_time_s"], 1e-9), 2),
        "prefill_tokens": c.metrics["prefill_tokens"],
        "cached_prefix_tokens": c.metrics["cached_prefix_tokens"],
        "kv_pages_imported": c.metrics.get("kv_pages_imported", 0),
        "kv_pages_exported": c.metrics.get("kv_pages_exported", 0),
        "spec_drafted": c.metrics.get("spec_drafted", 0),
        "spec_accepted": c.metrics.get("spec_accepted", 0),
    } for i, c in enumerate(cores)]
    ttfts = sorted(o.ttft_ms for o in outs if o.ttft_ms is not None)
    # Tail latency per arm through the shared serving histograms (every
    # replica observes into the same registry series, so these are
    # fleet-wide percentiles of the measured window) — the numbers the
    # kv-share / disagg A/B is judged on.
    p95_ttft = cores[0].hist_ttft.percentile(95)
    p95_tpot = cores[0].hist_tpot.percentile(95)
    from runbookai_tpu.autotune.plan import engine_config_dict

    details = {
        # Per-REPLICA resolved config (the fleet split applied), plus the
        # plan that pinned this arm when --plan was used.
        "engine_config": engine_config_dict(cores[0].ecfg),
        "plan": plan_detail,
        "model": cfg.name,
        "weights": "int8" if quantized else "float32",
        "quality": quality_marker(weights_path),
        "platform": probe.get("platform"),
        "device_kind": probe.get("kind"),
        "dp": dp,
        "attn_impl": cores[0].ecfg.attn_impl,
        "kv_dtype": str(jnp.dtype(ecfg.kv_dtype).name),
        "requests": n_requests,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "batch_slots_per_replica": ecfg.max_batch_slots,
        "num_pages_per_replica": ecfg.num_pages,
        "num_pages_total": ecfg.num_pages * dp,
        "draft_model": draft_name,
        "shared_prefix": int(os.environ.get("BENCH_SHARED_PREFIX", 0)),
        "sessions": max(1, int(os.environ.get("BENCH_SESSIONS", 1) or 1)),
        "stagger_ms": float(os.environ.get("BENCH_STAGGER_MS", 0) or 0),
        "kv_share_enabled": bool(kv_share or disagg_n),
        "wall_s": round(wall, 2),
        "total_tokens": total_decode + sum(c.metrics["prefill_tokens"]
                                           for c in cores),
        "total_throughput_tok_s": round(
            (total_decode + sum(c.metrics["prefill_tokens"]
                                for c in cores)) / wall, 2),
        "decode_tps_sum_per_replica": round(
            sum(r["tok_s"] for r in replica_stats), 2),
        "p50_ttft_ms": (round(ttfts[len(ttfts) // 2], 1) if ttfts else None),
        "p95_ttft_ms": (round(p95_ttft * 1e3, 1)
                        if p95_ttft is not None else None),
        "p95_tpot_ms": (round(p95_tpot * 1e3, 2)
                        if p95_tpot is not None else None),
        "lost_requests": lost,
        "outputs_digest": outputs_digest([o.token_ids for o in outs]),
        "per_replica": replica_stats,
        "affinity_hit_ratio": round(fleet.affinity_hit_ratio(), 4),
        "imbalance_ratio": round(fleet._imbalance(), 4),
        "router_retries": int(fleet._m_retries.value),
        # Fleet-wide flight provenance: kinds/tokens summed, pressure
        # peaks = the worst replica (engine/flight_recorder.py).
        "flight_summary": FlightRecorder.merge_summaries(
            [c.flight.summary() for c in cores]),
        # End-of-run workload fingerprint across every replica (obs/).
        "workload_fingerprint": (fingerprinter.fingerprint()
                                 if fingerprinter is not None else None),
    }
    if kv_share or disagg_n:
        # The A/B evidence for the kv-share arm: how many placements rode
        # pulled pages, how many pages moved, what the moves cost, and how
        # many planned pulls the staleness epoch rejected — read from the
        # same public health snapshot the /healthz endpoint serves.
        router_hz = fleet.health_snapshot()["router"]
        ks = dict(router_hz["kv_share"])
        ks["xreplica_hit_ratio"] = round(
            ks["xreplica_hits"] / max(n_requests, 1), 4)
        details["kv_share"] = ks
        if disagg_n:
            details["disagg"] = dict(router_hz["disagg"])
    prof = profile_detail(prof_dir, prof_captured)
    if prof is not None:
        details["profile"] = prof
    slo = slo_detail(os.environ.get("BENCH_SLO"))
    if slo is not None:
        details["slo"] = slo
    emit(round(total_decode / max(max_decode_t, 1e-9), 2), "tok/s", details)


def bench_bge_encode() -> dict:
    """Secondary metric: bge-base embedding throughput (BASELINE.md config 3
    — knowledge-index encode). Random-init weights, identical compute."""
    import jax
    import jax.numpy as jnp

    from runbookai_tpu.models.bge import CONFIGS as BGE_CONFIGS
    from runbookai_tpu.models.bge import encode, init_params

    cfg = BGE_CONFIGS["bge-base-en-v1.5"]
    b, t = (int(os.environ.get("BENCH_BGE_BATCH", 128)),
            int(os.environ.get("BENCH_BGE_SEQ", 512)))
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(b, t)), jnp.int32)
    attn_mask = jnp.ones((b, t), jnp.int32)
    fn = jax.jit(lambda p, i, m: encode(p, cfg, i, m))
    jax.block_until_ready(fn(params, ids, attn_mask))  # compile
    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        out = fn(params, ids, attn_mask)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    return {"texts_per_s": round(b / dt, 1), "batch": b, "seq_len": t,
            "model": cfg.name, "weights": "bfloat16"}


def run_inner(model_name: str, on_accel: bool, probe: dict) -> None:
    """Size the CPU's virtual devices when the CPU was asked for, then do
    the one run. A failure raises: no result line is printed for a run
    that did not happen."""
    if not on_accel:
        from runbookai_tpu.utils.cpu_mesh import force_cpu_platform

        # A CPU fleet needs one virtual device per replica so each
        # replica's compiled steps run on its own device slice. A plan
        # may size the fleet when BENCH_DP doesn't (autotune.plan is
        # stdlib-only, so loading it here cannot initialize jax before
        # force_cpu_platform runs).
        dp_env = os.environ.get("BENCH_DP")
        dp = int(dp_env) if dp_env else 1
        plan_path = os.environ.get("BENCH_PLAN")
        # Only an UNSET BENCH_DP defers to the plan — an explicit
        # BENCH_DP=1 pins a single-device run (env beats plan).
        if not dp_env and plan_path:
            from runbookai_tpu.autotune.plan import load_plan

            try:
                dp = int(load_plan(plan_path).engine.get("dp_replicas")
                         or 1)
            except ValueError:
                dp = 1  # invalid plans fail in run_bench with
                # load_plan's real error, not here
        models_env = os.environ.get("BENCH_MODELS")
        if models_env:
            # A multi-model CPU fleet needs one virtual device per
            # TOTAL replica across groups (spec parse errors fall
            # through to run_bench, which raises the real message).
            total = 0
            for part in models_env.split(","):
                part = part.strip()
                if part:
                    _, _, dp_s = part.partition(":")
                    try:
                        total += max(1, int(dp_s or 1))
                    except ValueError:
                        total += 1
            dp = max(dp, total)
        force_cpu_platform(max(1, dp))
    run_bench(model_name, on_accel, probe)


def main() -> None:
    # One-flag A/Bs for the overlapped decode pipeline and the unified
    # mixed dispatch: each flag is spelled as the env knob run_bench reads.
    if "--no-overlap" in sys.argv:
        sys.argv.remove("--no-overlap")
        os.environ["BENCH_OVERLAP"] = "0"
    if "--no-mixed" in sys.argv:
        sys.argv.remove("--no-mixed")
        os.environ["BENCH_MIXED"] = "0"
    if "--classes" in sys.argv:
        # Two-class flood A/B (BENCHLOG r9): batch flood + staggered
        # interactive through one engine; BENCH_SCHED=0 is the FIFO arm.
        sys.argv.remove("--classes")
        os.environ["BENCH_CLASSES"] = "1"
    if "--profile" in sys.argv:
        # On-demand XProf capture around the measured window
        # (BENCH_PROFILE=DIR|1): TensorBoard-readable trace dir, or a
        # clean skip recorded in details.profile when capture is
        # unavailable. An optional following arg names the directory.
        i = sys.argv.index("--profile")
        sys.argv.pop(i)
        if i < len(sys.argv) and not sys.argv[i].startswith("-"):
            os.environ["BENCH_PROFILE"] = sys.argv.pop(i)
        else:
            os.environ["BENCH_PROFILE"] = "1"
    if "--dp" in sys.argv:
        # Data-parallel fleet A/B: `--dp N` serves the same request set
        # through N engine replicas behind the prefix-affinity router.
        i = sys.argv.index("--dp")
        sys.argv.pop(i)
        if i >= len(sys.argv) or not sys.argv[i].isdigit():
            print("usage: bench.py --dp N (replica count)", file=sys.stderr)
            sys.exit(2)
        os.environ["BENCH_DP"] = sys.argv.pop(i)
    if "--kv-share" in sys.argv:
        # Fleet-wide KV page sharing A/B: the router pulls a prompt's
        # prefix pages from the sibling replica that holds them
        # (digest-checked host-staged copy) instead of re-prefilling.
        # Pair with --dp N and BENCH_SHARED_PREFIX for the
        # prompt-burst-over-decode workload.
        sys.argv.remove("--kv-share")
        os.environ["BENCH_KV_SHARE"] = "1"
    if "--disagg" in sys.argv:
        # Disaggregated tiers A/B: `--disagg [N]` dedicates the first N
        # replicas (default 1) to a prefill tier; prompts prefill there
        # and their pages hand off to the decode tier at first-token
        # time. Implies --kv-share (the handoff IS a pull).
        i = sys.argv.index("--disagg")
        sys.argv.pop(i)
        if i < len(sys.argv) and sys.argv[i].isdigit():
            os.environ["BENCH_DISAGG"] = sys.argv.pop(i)
        else:
            os.environ["BENCH_DISAGG"] = "1"
    if "--shift" in sys.argv:
        # Traffic-shift arm: short-chat then long-context/guided through
        # one engine; the workload fingerprint's drift must cross the
        # stale threshold while digests stay byte-identical to a
        # BENCH_OBS=0 run (runbookai_tpu/obs).
        sys.argv.remove("--shift")
        os.environ["BENCH_SHIFT"] = "1"
    if "--soak-scenarios" in sys.argv:
        # Chaos soak gate: `--soak-scenarios [SECONDS]` (default 30) of
        # the seeded scenario mix with fault injection + supervision,
        # gated on production invariants (docs/robustness.md). Compose
        # with `--models A,B`; BENCH_CHAOS=0 runs the mix chaos-free.
        i = sys.argv.index("--soak-scenarios")
        sys.argv.pop(i)
        if i < len(sys.argv) and not sys.argv[i].startswith("-") \
                and sys.argv[i].replace(".", "", 1).isdigit():
            os.environ["BENCH_SOAK_SCENARIOS"] = sys.argv.pop(i)
        else:
            os.environ["BENCH_SOAK_SCENARIOS"] = "30"
    if "--soak" in sys.argv:
        # Soak arm: `--soak [SECONDS]` (default 30) of closed-loop mixed
        # traffic; compose with `--models A,B` for a two-group fleet.
        i = sys.argv.index("--soak")
        sys.argv.pop(i)
        if i < len(sys.argv) and not sys.argv[i].startswith("-") \
                and sys.argv[i].replace(".", "", 1).isdigit():
            os.environ["BENCH_SOAK"] = sys.argv.pop(i)
        else:
            os.environ["BENCH_SOAK"] = "30"
    if "--models" in sys.argv:
        # Multi-model fleet A/B: `--models A,B[:dp]` serves interleaved
        # per-model traffic through one fleet; per-model digests must
        # equal dedicated single-model engines'. Does not compose with
        # --plan/--dp/--classes (refused in run_bench).
        i = sys.argv.index("--models")
        sys.argv.pop(i)
        if i >= len(sys.argv) or sys.argv[i].startswith("-"):
            print("usage: bench.py --models A,B[:dp] (model config names)",
                  file=sys.stderr)
            sys.exit(2)
        os.environ["BENCH_MODELS"] = sys.argv.pop(i)
    if "--plan" in sys.argv:
        # Pin the engine config to a `runbook tune` serving-plan artifact
        # (explicit BENCH_* env still overrides individual plan keys).
        i = sys.argv.index("--plan")
        sys.argv.pop(i)
        if i >= len(sys.argv):
            print("usage: bench.py --plan PATH (serving-plan artifact)",
                  file=sys.stderr)
            sys.exit(2)
        os.environ["BENCH_PLAN"] = sys.argv.pop(i)
    from runbookai_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    if "--cpu" in sys.argv:
        # The CPU, asked for by name: the same arms on the tiny model.
        sys.argv.remove("--cpu")
        run_inner(os.environ.get("BENCH_MODEL", "llama3-test"), False,
                  {"platform": "cpu", "kind": "cpu", "n": 1})
        return
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench.py measures on a TPU and JAX reports platform "
              f"{devices[0].platform!r}; pass --cpu to ask for the CPU by "
              f"name (a functional run, not a measurement)",
              file=sys.stderr)
        sys.exit(1)
    run_inner(os.environ.get("BENCH_MODEL", "llama3-8b-instruct"), True,
              {"platform": devices[0].platform,
               "kind": devices[0].device_kind, "n": len(devices)})


if __name__ == "__main__":
    main()
