"""Versioned serving-plan artifacts: the autotuner's shippable output.

A plan is one JSON document per model×topology that pins every engine knob
the sweep decided, with provenance (cost-model scores, measured figures,
git sha) so a measured figure can always be traced back to the exact
config that produced it — the FlashInfer-Bench artifact-driven loop
(PAPERS.md) applied to this engine's knob space.

Consumers:

- ``JaxTpuClient.from_config`` via the ``llm.plan`` config key — plan
  values become the defaults; keys the operator set explicitly in YAML
  still win (:func:`apply_plan_to_llm` reads pydantic's
  ``model_fields_set`` for exactly that precedence).
- ``EngineConfig.from_plan`` — the engine block as a config, for callers
  that build a core themselves (the tuner's measured arms).
- ``runbook plan show|validate`` — operator inspection; tier-1 validates
  every checked-in ``plans/*.json`` against this schema.

Tamper evidence: ``plan_id`` ends in the content hash of
``(model, topology, engine)`` — editing a knob by hand without re-hashing
fails ``validate_plan``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

PLAN_SCHEMA_VERSION = 1

# Engine-block keys a plan may carry, mapped 1:1 onto EngineConfig fields
# (kv_dtype travels as a string; EngineConfig.from_plan resolves it).
# Slot/page values are PER REPLICA when dp_replicas > 1 — the EngineConfig
# / llm.* contract, honored identically by the tuner's measured arms
# and from_config.
ENGINE_PLAN_KEYS = frozenset({
    "page_size", "num_pages", "max_batch_slots", "prefill_chunk",
    "max_seq_len", "block_pages", "decode_steps_per_dispatch",
    "prefill_batch", "mixed_token_budget", "mixed_dispatch",
    "overlap_decode", "speculative", "kv_dtype", "attn_impl", "qmm_impl",
    "dp_replicas", "kv_spill_pages",
})

# kv_dtype spellings a plan may use ("auto" = follow the activation dtype,
# exactly llm.kv_cache_dtype's contract).
KV_DTYPE_NAMES = ("auto", "bf16", "fp8", "int8")

# attn_impl / qmm_impl spellings — LLMConfig's Literal set. The schema is
# the gate: apply_plan_to_llm injects via pydantic ``model_copy`` which
# skips Literal validation, and a bad value there would silently serve
# the XLA fallback path.
IMPL_NAMES = ("auto", "pallas", "xla")

# plan engine key -> LLMConfig field, for keys YAML can also spell. The
# rest (ENGINE_PLAN_KEYS - this - {"kv_dtype"}) apply straight onto
# EngineConfig (engine_only_overrides).
_PLAN_TO_LLM = {
    "page_size": "page_size",
    "num_pages": "num_pages",
    "max_batch_slots": "max_batch_slots",
    "prefill_chunk": "prefill_chunk",
    "max_seq_len": "max_seq_len",
    "decode_steps_per_dispatch": "decode_steps",
    "attn_impl": "attn_impl",
    "qmm_impl": "qmm_impl",
    "dp_replicas": "dp_replicas",
    "kv_spill_pages": "kv_spill_pages",
}


@dataclass
class PlanArtifact:
    """One serving plan: model × topology × engine knobs + provenance."""

    model: str
    topology: dict[str, Any]
    engine: dict[str, Any]
    workload: dict[str, Any] = field(default_factory=dict)
    provenance: dict[str, Any] = field(default_factory=dict)
    schema_version: int = PLAN_SCHEMA_VERSION
    plan_id: str = ""

    def __post_init__(self) -> None:
        if not self.plan_id:
            self.plan_id = default_plan_id(
                self.model, self.topology, self.engine)

    @property
    def content_hash(self) -> str:
        return plan_hash(self.model, self.topology, self.engine)

    def to_dict(self) -> dict[str, Any]:
        return {
            "schema_version": self.schema_version,
            "plan_id": self.plan_id,
            "model": self.model,
            "topology": self.topology,
            "engine": self.engine,
            "workload": self.workload,
            "provenance": self.provenance,
        }

    @classmethod
    def from_dict(cls, data: dict[str, Any]) -> "PlanArtifact":
        problems = validate_plan(data)
        if problems:
            raise ValueError(
                "invalid plan artifact: " + "; ".join(problems))
        return cls(
            schema_version=data["schema_version"], plan_id=data["plan_id"],
            model=data["model"], topology=dict(data["topology"]),
            engine=dict(data["engine"]),
            workload=dict(data.get("workload") or {}),
            provenance=dict(data.get("provenance") or {}),
        )


def plan_hash(model: str, topology: dict, engine: dict) -> str:
    """Content hash over what the plan *decides* (not its provenance), so
    re-running a sweep that lands on the same config yields the same id."""
    canonical = json.dumps({"model": model, "topology": topology,
                            "engine": engine}, sort_keys=True)
    return hashlib.sha256(canonical.encode()).hexdigest()[:12]


def default_plan_id(model: str, topology: dict, engine: dict) -> str:
    tp = int(topology.get("tp", 1) or 1)
    dp = int(engine.get("dp_replicas", topology.get("dp_replicas", 1)) or 1)
    kind = str(topology.get("device_kind", "unknown")).replace(" ", "-")
    return (f"{model}.{kind}.tp{tp}dp{dp}."
            f"{plan_hash(model, topology, engine)}")


def validate_plan(data: Any) -> list[str]:
    """Human-readable schema problems (empty = valid).

    Unknown schema versions are REJECTED — a v2 plan must never be
    half-read by v1 code and silently serve the keys it understood.
    """
    problems: list[str] = []
    if not isinstance(data, dict):
        return ["plan is not a JSON object"]
    version = data.get("schema_version")
    if version != PLAN_SCHEMA_VERSION:
        return [f"unknown schema_version {version!r} "
                f"(this build reads version {PLAN_SCHEMA_VERSION})"]
    for key in ("plan_id", "model", "topology", "engine"):
        if key not in data:
            problems.append(f"missing required key {key!r}")
    if problems:
        return problems
    if not isinstance(data["model"], str) or not data["model"]:
        problems.append("model must be a non-empty string")
    if not isinstance(data["topology"], dict):
        problems.append("topology must be an object")
    engine = data["engine"]
    if not isinstance(engine, dict):
        problems.append("engine must be an object")
        return problems
    unknown = sorted(set(engine) - ENGINE_PLAN_KEYS)
    if unknown:
        problems.append(f"unknown engine keys: {', '.join(unknown)} "
                        f"(allowed: {', '.join(sorted(ENGINE_PLAN_KEYS))})")
    for key in ("page_size", "num_pages", "max_batch_slots",
                "prefill_chunk", "max_seq_len", "block_pages",
                "decode_steps_per_dispatch", "prefill_batch",
                "dp_replicas"):
        if key in engine and (not isinstance(engine[key], int)
                              or isinstance(engine[key], bool)
                              or engine[key] < 1):
            problems.append(f"engine.{key} must be a positive integer")
    if "mixed_token_budget" in engine and engine["mixed_token_budget"] \
            is not None and (not isinstance(engine["mixed_token_budget"],
                                            int)
                             or engine["mixed_token_budget"] < 1):
        problems.append("engine.mixed_token_budget must be a positive "
                        "integer or null")
    # v1-compatible optional keys (absent in pre-PR-8 plans — they still
    # validate; present means a host spill tier / disagg deployment).
    if "kv_spill_pages" in engine and (
            not isinstance(engine["kv_spill_pages"], int)
            or isinstance(engine["kv_spill_pages"], bool)
            or engine["kv_spill_pages"] < 0):
        problems.append("engine.kv_spill_pages must be a non-negative "
                        "integer (0 = spill tier disabled)")
    topo = data.get("topology")
    if isinstance(topo, dict) and "disagg_prefill_replicas" in topo:
        n_pf = topo["disagg_prefill_replicas"]
        dp = engine.get("dp_replicas", topo.get("dp_replicas", 1)) or 1
        if (not isinstance(n_pf, int) or isinstance(n_pf, bool)
                or n_pf < 0):
            problems.append("topology.disagg_prefill_replicas must be a "
                            "non-negative integer")
        elif n_pf and isinstance(dp, int) and n_pf >= dp:
            problems.append(
                f"topology.disagg_prefill_replicas={n_pf} leaves no "
                f"decode tier (dp_replicas={dp})")
    if "kv_dtype" in engine and engine["kv_dtype"] not in KV_DTYPE_NAMES:
        problems.append(f"engine.kv_dtype must be one of "
                        f"{'/'.join(KV_DTYPE_NAMES)}")
    for key in ("attn_impl", "qmm_impl"):
        if key in engine and engine[key] not in IMPL_NAMES:
            problems.append(f"engine.{key} must be one of "
                            f"{'/'.join(IMPL_NAMES)}")
    for key in ("speculative", "overlap_decode"):
        if key in engine and not isinstance(engine[key], bool):
            problems.append(f"engine.{key} must be a boolean")
    if "mixed_dispatch" in engine and engine["mixed_dispatch"] is not None \
            and not isinstance(engine["mixed_dispatch"], bool):
        problems.append("engine.mixed_dispatch must be a boolean or null")
    if isinstance(data.get("topology"), dict):
        expect = plan_hash(data["model"], data["topology"], engine)
        if not str(data["plan_id"]).endswith(expect):
            problems.append(
                f"plan_id does not end in the content hash {expect} — "
                f"the plan was edited without re-hashing (regenerate via "
                f"`runbook tune` or fix the id)")
    return problems


def load_plan(path: str | Path) -> PlanArtifact:
    """Read + validate a plan file; raises ValueError with the problems."""
    try:
        data = json.loads(Path(path).read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(f"could not read plan {path}: {e}") from e
    return PlanArtifact.from_dict(data)


def save_plan(plan: PlanArtifact, path: str | Path) -> Path:
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(plan.to_dict(), indent=2, sort_keys=False)
                 + "\n")
    return p


# ----------------------------------------------------------- consumption


def apply_plan_to_llm(llm_cfg, plan: PlanArtifact):
    """Plan values become the llm-config defaults; explicitly-set YAML
    keys keep winning (precedence read off pydantic ``model_fields_set``,
    so only the operator's own lines override the sweep's decision).

    Returns a COPY of ``llm_cfg``; the caller's object is never mutated.
    """
    explicit = set(llm_cfg.model_fields_set)
    updates: dict[str, Any] = {}
    for plan_key, llm_key in _PLAN_TO_LLM.items():
        if plan_key in plan.engine and llm_key not in explicit:
            updates[llm_key] = plan.engine[plan_key]
    if "kv_dtype" in plan.engine and "kv_cache_dtype" not in explicit:
        # 1:1 spelling — llm.kv_cache_dtype accepts the full plan set,
        # and engine.resolve_kv_dtype gives every consumer (llm.plan,
        # from_plan) the same pool for the same string
        # ("bf16" pins bfloat16 even on float32 activations; "auto"
        # follows them).
        updates["kv_cache_dtype"] = plan.engine["kv_dtype"]
    tp = int(plan.topology.get("tp", 1) or 1)
    if tp > 1 and "mesh" not in explicit:
        mesh_cls = type(llm_cfg.mesh)
        updates["mesh"] = mesh_cls(data=1, model=tp)
    return llm_cfg.model_copy(update=updates) if updates else \
        llm_cfg.model_copy()


def engine_only_overrides(plan: PlanArtifact) -> dict[str, Any]:
    """Plan engine keys that have NO LLMConfig spelling — they apply
    directly onto the built EngineConfig (from_config threads them through
    ``dataclasses.replace``). kv_dtype is excluded: it routes through
    ``llm.kv_cache_dtype`` so the activation-dtype default keeps working.
    """
    skip = set(_PLAN_TO_LLM) | {"kv_dtype"}
    return {k: v for k, v in plan.engine.items() if k not in skip}


def engine_config_dict(ecfg) -> dict[str, Any]:
    """JSON-safe dump of a resolved EngineConfig (plan provenance): every dataclass field, kv_dtype as its dtype name."""
    import jax.numpy as jnp

    out: dict[str, Any] = {}
    for f in dataclasses.fields(ecfg):
        value = getattr(ecfg, f.name)
        if f.name == "kv_dtype":
            value = str(jnp.dtype(value).name)
        out[f.name] = value
    return out


def git_sha(repo_root: Optional[str | Path] = None) -> Optional[str]:
    """Best-effort provenance sha; None outside a git checkout."""
    import subprocess

    root = Path(repo_root) if repo_root else Path(__file__).parents[2]
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=10)
    except (OSError, subprocess.SubprocessError):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None
