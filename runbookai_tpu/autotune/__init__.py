"""Serving-plan autotuner (ROADMAP item 3).

Searches the coupled engine-knob space — ``(page_size, num_pages,
max_batch_slots, prefill_chunk, mixed_token_budget,
decode_steps_per_dispatch, kv_dtype, speculative, dp_replicas, tp)`` — in
the AIConfigurator / FlashInfer-Bench style (PAPERS.md): an analytical
cost model prunes the space, short measured runs refine the survivors, and
the result ships as a schema-versioned *plan artifact* that
``JaxTpuClient.from_config`` (``llm.plan``) and
``EngineConfig.from_plan`` consume directly.

- :mod:`~runbookai_tpu.autotune.cost_model` — residency (delegating to
  :mod:`runbookai_tpu.engine.memory_plan`, pinned equal by test) composed
  with an HLO-bytes roofline per dispatch kind.
- :mod:`~runbookai_tpu.autotune.search` — analytic prune (feasibility +
  dominated-point elimination) then measured refinement: short
  serving runs in-process.
- :mod:`~runbookai_tpu.autotune.plan` — the versioned JSON artifact with
  provenance (cost-model scores, measured figures, git sha).

CLI: ``runbook tune`` / ``runbook plan show|validate`` (docs/autotune.md).
"""

from runbookai_tpu.autotune.plan import (  # noqa: F401
    PLAN_SCHEMA_VERSION,
    PlanArtifact,
    load_plan,
    save_plan,
    validate_plan,
)
