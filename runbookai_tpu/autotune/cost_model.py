"""Analytical serving cost model: residency × HLO-bytes roofline.

Scores a candidate knob tuple against a workload descriptor WITHOUT
touching a device, in the AIConfigurator style (PAPERS.md): first predict
whether the config *fits* (delegating every residency number to
:func:`runbookai_tpu.engine.memory_plan.plan_serving` — the arithmetic
already cross-checked against live allocations to 0.35% by
``tests/test_hlo_bytes.py``), then predict how fast it *runs* from the
byte/flop movement of each dispatch kind:

- **decode**: HBM-bandwidth-bound — per step the program reads every
  weight matrix once at stored width plus the live KV pages (the
  ``hlo_bytes.decode_accounting`` contract), so batching is ~free until
  KV reads or compute catch up;
- **prefill**: MXU-bound — ``2 · matmul_params`` FLOPs per prompt token,
  dispatched per ``prefill_chunk`` with one host sync each;
- **mixed**: the PR-4 unified dispatch folds a prefill chunk into the
  decode step — one host sync where the split path pays two.

The model's absolute numbers are calibration-grade, not gospel — that is
why :mod:`~runbookai_tpu.autotune.search` refines the analytic top-K with
short measured runs. Its *relative* ordering is what prunes the space.

Parity contracts (pinned in tests/test_autotune.py): ``residency()``
returns exactly ``plan_serving``'s ServingPlan, and
``decode_dispatch_bytes()`` matches the compiled decode program's
resident argument bytes within the memory-plan tolerance.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Iterable, Optional

from runbookai_tpu.engine.memory_plan import GiB, ServingPlan, plan_serving

# kv_dtype name -> (bytes per value, extra scale bytes per (token, kv head))
# — the byte widths engine.resolve_kv_dtype's dtypes allocate ("bf16" pins
# a 2-byte bfloat16 pool; "auto" follows the activation dtype, which the
# model assumes is bf16 — the hardware deployments it targets; int8 adds
# f32 absmax rows).
KV_DTYPE_BYTES: dict[str, tuple[int, int]] = {
    "auto": (2, 0), "bf16": (2, 0), "fp8": (1, 0), "int8": (1, 4),
}


@dataclass(frozen=True)
class Workload:
    """What the traffic looks like — the tune target, not a knob."""

    prompt_len: int = 512
    output_len: int = 128
    concurrency: int = 8
    # Fraction of requests that are grammar-guided (forced-sync: no
    # overlap, single-token dispatches — agent tool-call traffic).
    guided_share: float = 0.0
    # Expected extra accepted tokens per decode dispatch from speculation
    # (0 = repetition-free traffic; agent workloads bank 0.3-0.8).
    spec_hit_rate: float = 0.0

    @property
    def context_len(self) -> int:
        return self.prompt_len + self.output_len

    def to_dict(self) -> dict[str, Any]:
        return {"prompt_len": self.prompt_len,
                "output_len": self.output_len,
                "concurrency": self.concurrency,
                "guided_share": self.guided_share,
                "spec_hit_rate": self.spec_hit_rate}

    @classmethod
    def from_dict(cls, data: dict[str, Any]) -> "Workload":
        """The inverse of :meth:`to_dict` — what ``runbook tune
        --workload`` reads, so a live descriptor emitted by ``runbook
        workload --emit-descriptor`` (runbookai_tpu/obs) round-trips into
        a sweep unchanged. Unknown keys are REJECTED: a typo'd or
        stale-schema descriptor must fail loudly, not tune against a
        half-read workload."""
        if not isinstance(data, dict):
            raise ValueError(
                f"workload descriptor must be a JSON object, got "
                f"{type(data).__name__}")
        known = {"prompt_len", "output_len", "concurrency",
                 "guided_share", "spec_hit_rate"}
        unknown = sorted(str(k) for k in set(data) - known)
        if unknown:
            raise ValueError(
                f"unknown workload descriptor keys: {', '.join(unknown)} "
                f"(expected a subset of {', '.join(sorted(known))})")
        base = cls()
        try:
            return cls(
                prompt_len=int(data.get("prompt_len", base.prompt_len)),
                output_len=int(data.get("output_len", base.output_len)),
                concurrency=int(data.get("concurrency",
                                         base.concurrency)),
                guided_share=float(data.get("guided_share",
                                            base.guided_share)),
                spec_hit_rate=float(data.get("spec_hit_rate",
                                             base.spec_hit_rate)))
        except (TypeError, ValueError) as e:
            # null / list / non-numeric values must surface as the same
            # ValueError contract unknown keys do — the CLI catches it
            # and prints the friendly message instead of a traceback.
            raise ValueError(
                f"bad workload descriptor value: {e}") from e


@dataclass(frozen=True)
class Hardware:
    """Per-chip envelope the roofline divides by. ``dispatch_overhead_s``
    is the host→device round-trip a dispatch pays regardless of payload."""

    name: str
    hbm_bytes: int
    hbm_bw: float        # achievable bytes/s
    peak_flops: float
    dispatch_overhead_s: float
    # Host RAM available to the KV spill tier (kv_spill_pages feasibility
    # envelope — host bytes, never HBM). Spec-sheet default: serving hosts
    # carry at least this much.
    host_ram_bytes: int = 64 * GiB

    def to_dict(self) -> dict[str, Any]:
        return {"name": self.name, "hbm_bytes": self.hbm_bytes,
                "hbm_bw": self.hbm_bw, "peak_flops": self.peak_flops,
                "dispatch_overhead_s": self.dispatch_overhead_s,
                "host_ram_bytes": self.host_ram_bytes}


# Spec-sheet envelopes (benchmark/peaks.json holds the chip's for the
# runner; ROADMAP C8); "cpu"
# is deliberately pessimistic — it exists so the CPU smoke path orders
# candidates sanely, not to predict CPU tok/s.
HARDWARE: dict[str, Hardware] = {
    "v5e": Hardware("v5e", 16 * GiB, 8.1e11, 197e12, 1e-3),
    "v6e": Hardware("v6e", 32 * GiB, 1.6e12, 918e12, 1e-3),
    "cpu": Hardware("cpu", 16 * GiB, 2e10, 2e11, 2e-4),
}


def hardware_for(device) -> Hardware:
    """The envelope of the device JAX reports. A device this table does
    not know is an error: a roofline divided by another chip's peaks
    ranks candidates for a machine nobody is running on."""
    if device.platform == "cpu":
        return HARDWARE["cpu"]
    kind = device.device_kind.lower()
    for name, markers in (("v5e", ("v5e", "v5 lite", "v5lite")),
                          ("v6e", ("v6e", "v6 lite", "v6lite"))):
        if any(m in kind for m in markers):
            return HARDWARE[name]
    raise KeyError(
        f"no hardware envelope for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); known: {sorted(HARDWARE)} — "
        f"pass --hw to name one")


@dataclass(frozen=True)
class Candidate:
    """One point of the coupled knob space the autotuner searches.

    ``num_pages`` / ``max_batch_slots`` are PER REPLICA when
    ``dp_replicas > 1`` — the same contract as ``llm.*`` config and
    ``EngineConfig``, so the budget a plan deploys through ``llm.plan``
    is exactly the budget the sweep scored and measured.
    """

    page_size: int = 16
    num_pages: int = 2048
    max_batch_slots: int = 8
    prefill_chunk: int = 256
    mixed_token_budget: Optional[int] = None
    decode_steps_per_dispatch: int = 8
    kv_dtype: str = "bf16"
    speculative: bool = True
    dp_replicas: int = 1
    tp: int = 1
    max_seq_len: int = 8192
    # Host-RAM spill tier pages (EngineConfig.kv_spill_pages; 0 = off).
    # Budgeted against host RAM via memory_plan.host_spill_bytes, never
    # HBM — feasibility checks the host envelope, not the pool budget.
    kv_spill_pages: int = 0
    # Prefill/decode disaggregation: replicas dedicated to the prefill
    # tier (FleetConfig.disagg_prefill_replicas; 0 = symmetric). Rides in
    # the plan's TOPOLOGY block, not the engine block — it is a fleet
    # deployment shape, not an EngineConfig knob.
    disagg_prefill_replicas: int = 0

    def engine_plan_block(self) -> dict[str, Any]:
        """The candidate as a plan artifact's ``engine`` block (tp and the
        disagg tier split ride in ``topology``)."""
        return {
            "page_size": self.page_size, "num_pages": self.num_pages,
            "max_batch_slots": self.max_batch_slots,
            "prefill_chunk": self.prefill_chunk,
            "mixed_token_budget": self.mixed_token_budget,
            "decode_steps_per_dispatch": self.decode_steps_per_dispatch,
            "kv_dtype": self.kv_dtype, "speculative": self.speculative,
            "dp_replicas": self.dp_replicas,
            "max_seq_len": self.max_seq_len,
            "kv_spill_pages": self.kv_spill_pages,
        }

    def topology_extras(self) -> dict[str, Any]:
        """Topology-block keys this candidate pins beyond tp/dp (empty
        for symmetric fleets, so existing plans hash unchanged)."""
        return ({"disagg_prefill_replicas": self.disagg_prefill_replicas}
                if self.disagg_prefill_replicas else {})

    @property
    def pool_tokens(self) -> int:
        return self.page_size * self.num_pages


@dataclass(frozen=True)
class CostEstimate:
    """The cost model's verdict on one candidate."""

    candidate: Candidate
    feasible: bool
    reason: str                      # why infeasible ("" when feasible)
    residency: Optional[ServingPlan]
    decode_tok_s: float              # predicted aggregate decode rate
    ttft_ms: float                   # predicted prompt-latency floor
    decode_step_bytes: float         # bytes one decode step moves per chip
    effective_batch: float

    def to_dict(self) -> dict[str, Any]:
        return {
            "feasible": self.feasible, "reason": self.reason,
            "decode_tok_s": round(self.decode_tok_s, 2),
            "ttft_ms": round(self.ttft_ms, 2),
            "decode_step_bytes": round(self.decode_step_bytes),
            "effective_batch": round(self.effective_batch, 2),
        }


class CostModel:
    """Analytic scorer for (model, hardware, weights-width) deployments."""

    def __init__(self, model_cfg, hardware: Hardware,
                 weights: str = "int8",
                 headroom_bytes: int = int(1.5 * GiB)):
        if weights not in ("int8", "bf16"):
            raise ValueError(f"weights must be int8|bf16, got {weights!r}")
        self.model_cfg = model_cfg
        self.hw = hardware
        self.weights = weights
        self.headroom_bytes = headroom_bytes

    # ------------------------------------------------------- residency

    def residency(self, cand: Candidate,
                  max_seq_len: Optional[int] = None) -> ServingPlan:
        """The candidate's HBM arithmetic — *delegated* to
        :func:`~runbookai_tpu.engine.memory_plan.plan_serving`, never
        re-derived, so the autotuner can't drift from the planner the
        engine and docs quote (pinned equal by test)."""
        kv_bytes, scale_bytes = KV_DTYPE_BYTES[cand.kv_dtype]
        return plan_serving(
            self.model_cfg,
            max_seq_len=max_seq_len or cand.max_seq_len,
            batch=cand.max_batch_slots, tp=cand.tp, weights=self.weights,
            kv_dtype_bytes=kv_bytes, kv_scale_bytes=scale_bytes,
            hbm_bytes=self.hw.hbm_bytes,
            headroom_bytes=self.headroom_bytes,
            kv_spill_pages=cand.kv_spill_pages,
            page_size=cand.page_size)

    def kv_pool_bytes(self, cand: Candidate,
                      plan: Optional[ServingPlan] = None) -> float:
        """Allocated pool bytes per chip for the candidate's page budget
        (pool token axis shards over pg_shards inside plan_serving's
        per-token figure). ``plan`` reuses an already-computed residency
        (weight/per-token bytes are max_seq_len-independent)."""
        plan = plan if plan is not None else self.residency(cand)
        return cand.pool_tokens * plan.kv_bytes_per_token_per_chip

    def decode_dispatch_bytes(self, cand: Candidate,
                              plan: Optional[ServingPlan] = None) -> float:
        """Resident argument bytes of one compiled decode step: weights at
        stored width + the KV pool + O(batch) small operands — the
        ``hlo_bytes.decode_accounting`` ``arguments_expected`` contract,
        predicted instead of measured."""
        plan = plan if plan is not None else self.residency(cand)
        small = 2048 * cand.max_batch_slots  # tokens/tables/sampling rows
        return (plan.weight_bytes_per_chip
                + self.kv_pool_bytes(cand, plan) + small)

    # ----------------------------------------------------- feasibility

    def check_feasible(self, cand: Candidate, workload: Workload,
                       plan: Optional[ServingPlan] = None) -> tuple[bool, str]:
        if plan is None:
            # A supplied plan proves the factorization already resolved.
            try:
                from runbookai_tpu.parallel.kv_split import plan_kv_split

                plan_kv_split(self.model_cfg, cand.tp)
            except ValueError as e:
                return False, f"tp factorization: {e}"
        if cand.dp_replicas > 1 and cand.tp > 1:
            return False, "dp_replicas > 1 requires tp == 1 (a replica is a single-slice engine)"
        if cand.kv_spill_pages < 0:
            return False, "kv_spill_pages must be >= 0"
        if cand.disagg_prefill_replicas < 0:
            return False, "disagg_prefill_replicas must be >= 0"
        if cand.disagg_prefill_replicas:
            if cand.disagg_prefill_replicas >= max(1, cand.dp_replicas):
                return False, (
                    f"disagg_prefill_replicas="
                    f"{cand.disagg_prefill_replicas} leaves no decode tier "
                    f"in a dp={cand.dp_replicas} fleet")
        ctx = min(workload.context_len, cand.max_seq_len)
        if workload.prompt_len >= cand.max_seq_len:
            return False, (f"prompt_len {workload.prompt_len} >= "
                           f"max_seq_len {cand.max_seq_len}")
        if cand.mixed_token_budget is not None and \
                cand.mixed_token_budget <= cand.max_batch_slots:
            return False, ("mixed_token_budget must exceed max_batch_slots "
                           "(decode slots alone consume the budget)")
        if plan is None:
            plan = self.residency(cand, max_seq_len=ctx)
        # Every co-resident replica pins its OWN tier; budget the worst
        # case of all dp replicas sharing one host (single-host fleets —
        # the CPU shape — and the conservative bound for pods).
        spill_total = plan.host_spill_bytes * max(1, cand.dp_replicas)
        if spill_total > self.hw.host_ram_bytes // 2:
            return False, (
                f"spill tier {spill_total / GiB:.2f} GiB across "
                f"{max(1, cand.dp_replicas)} replica(s) exceeds half the "
                f"host RAM envelope "
                f"({self.hw.host_ram_bytes / GiB:.0f} GiB)")
        pool_bytes = cand.pool_tokens * plan.kv_bytes_per_token_per_chip
        if pool_bytes > plan.pool_budget_bytes:
            return False, (
                f"KV pool {pool_bytes / GiB:.2f} GiB exceeds the "
                f"post-weights budget {plan.pool_budget_bytes / GiB:.2f} "
                f"GiB ({plan.explain()})")
        if cand.pool_tokens < ctx + cand.prefill_chunk:
            return False, (f"pool holds {cand.pool_tokens} tokens < one "
                           f"{ctx}-token context + a prefill chunk")
        if not plan.fits:
            return False, plan.explain()
        return True, ""

    # --------------------------------------------------------- scoring

    def score(self, cand: Candidate, workload: Workload) -> CostEstimate:
        ctx = min(workload.context_len, cand.max_seq_len)
        # ONE plan_serving call per candidate, threaded through every
        # consumer (weight/per-token bytes are max_seq_len-independent).
        # Residency may be undefined (e.g. an unalignable tp
        # factorization) — an infeasible point scores zero, it doesn't
        # raise; check_feasible re-derives the reason from the probe.
        try:
            plan = self.residency(cand, max_seq_len=ctx)
        except ValueError:
            plan = None
        feasible, reason = self.check_feasible(cand, workload, plan=plan)
        if not feasible:
            return CostEstimate(cand, False, reason, None, 0.0,
                                float("inf"), 0.0, 0.0)
        step_bytes = self.decode_dispatch_bytes(cand, plan)
        cfg, hw = self.model_cfg, self.hw

        dp = max(1, cand.dp_replicas)
        # Disaggregation dedicates replicas to prefill: only the decode
        # tier contributes to the aggregate decode rate (its win — prompt
        # bursts off the decode path — shows up as TTFT stability in the
        # MEASURED arms, not in this roofline).
        dp_decode = max(1, dp - cand.disagg_prefill_replicas)
        # Effective decode batch per replica: bounded by slots, by the
        # share of traffic this replica sees, and by how many average
        # contexts the page pool actually holds.
        avg_ctx = workload.prompt_len + workload.output_len / 2
        pool_contexts = cand.pool_tokens / max(avg_ctx, 1)
        batch = min(cand.max_batch_slots, workload.concurrency / dp_decode,
                    pool_contexts)
        batch = max(batch, 1e-6)

        # One decode step over `batch` rows: every weight matrix read once
        # at stored width + the live KV pages + sampled-token output.
        live_kv = batch * avg_ctx * plan.kv_bytes_per_token_per_chip
        bytes_moved = plan.weight_bytes_per_chip + live_kv
        flops = 2.0 * cfg.matmul_params * batch / max(cand.tp, 1)
        device_s = max(bytes_moved / hw.hbm_bw, flops / hw.peak_flops)

        # Host-sync amortization: k tokens per dispatch, speculation
        # stretches the accepted run, guided traffic forces k=1 sync
        # dispatches (the classic path) for its share.
        k = max(1, cand.decode_steps_per_dispatch)
        if cand.speculative:
            k = k * (1.0 + max(0.0, workload.spec_hit_rate))
        sync_s = hw.dispatch_overhead_s
        per_step_overhead = (
            (1.0 - workload.guided_share) * sync_s / k
            + workload.guided_share * sync_s)
        step_s = device_s + per_step_overhead
        decode_tok_s = batch / step_s * dp_decode

        # TTFT floor: chunked prefill, one dispatch per chunk; the mixed
        # dispatch (budget permitting) folds each chunk into a decode step
        # it was going to pay for anyway — one sync instead of two.
        chunk = min(cand.prefill_chunk,
                    (cand.mixed_token_budget - cand.max_batch_slots)
                    if cand.mixed_token_budget else cand.prefill_chunk)
        chunk = max(1, chunk)
        n_chunks = -(-workload.prompt_len // chunk)
        chunk_flops = 2.0 * cfg.matmul_params * chunk / max(cand.tp, 1)
        chunk_bytes = plan.weight_bytes_per_chip
        chunk_s = max(chunk_flops / hw.peak_flops,
                      chunk_bytes / hw.hbm_bw)
        syncs_per_chunk = 1 if cand.mixed_token_budget is None else 0.5
        ttft_s = n_chunks * (chunk_s + syncs_per_chunk * sync_s)

        return CostEstimate(cand, True, "", plan, decode_tok_s,
                            ttft_s * 1e3, step_bytes, batch)

    def score_many(self, cands: Iterable[Candidate],
                   workload: Workload) -> list[CostEstimate]:
        return [self.score(c, workload) for c in cands]


# ------------------------------------------------------------ search space


@dataclass(frozen=True)
class SearchSpace:
    """Axis values the sweep enumerates (cartesian product, then pruned).
    Defaults cover the hand-picked regimes that were A/B'd by hand."""

    page_size: tuple[int, ...] = (16,)
    num_pages: tuple[int, ...] = (1024, 2048, 4096)
    max_batch_slots: tuple[int, ...] = (4, 8, 16, 32)
    prefill_chunk: tuple[int, ...] = (128, 256, 512)
    mixed_token_budget: tuple[Optional[int], ...] = (None,)
    decode_steps_per_dispatch: tuple[int, ...] = (1, 4, 8)
    kv_dtype: tuple[str, ...] = ("bf16", "fp8")
    speculative: tuple[bool, ...] = (True, False)
    dp_replicas: tuple[int, ...] = (1,)
    tp: tuple[int, ...] = (1,)
    max_seq_len: tuple[int, ...] = (8192,)
    # Fleet-shape knobs (PR 8): off by default so existing sweeps and
    # their plan hashes are unchanged until a space opts in.
    kv_spill_pages: tuple[int, ...] = (0,)
    disagg_prefill_replicas: tuple[int, ...] = (0,)

    def candidates(self) -> list[Candidate]:
        axes = (self.page_size, self.num_pages, self.max_batch_slots,
                self.prefill_chunk, self.mixed_token_budget,
                self.decode_steps_per_dispatch, self.kv_dtype,
                self.speculative, self.dp_replicas, self.tp,
                self.max_seq_len, self.kv_spill_pages,
                self.disagg_prefill_replicas)
        return [Candidate(*values) for values in itertools.product(*axes)]


def smoke_space(max_seq_len: int = 256) -> SearchSpace:
    """A CPU-sized space for the tier-1 / `runbook tune --smoke` path:
    small enough that analytic prune + a couple of measured runs finish
    in seconds on the tiny test model."""
    return SearchSpace(
        page_size=(4,), num_pages=(64, 256),
        max_batch_slots=(2, 4), prefill_chunk=(16, 32),
        mixed_token_budget=(None,), decode_steps_per_dispatch=(4, 8),
        kv_dtype=("auto",), speculative=(True, False),
        dp_replicas=(1,), tp=(1,), max_seq_len=(max_seq_len,))
