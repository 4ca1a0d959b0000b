"""Seeded serving-traffic scenario mix (ROADMAP item 5's generator half).

``simulate/generator.py`` fabricates *incidents* for the agent to
investigate; this module fabricates the *serving workload* a
million-session deployment actually sees — the mix the chaos soak
(``chaos/soak.py``) drives through the full composed stack:

``short_chat``
    Single-turn interactive requests, short prompts, streamed — the
    TTFT-sensitive class whose p95 the invariant gate holds through
    every fault.
``agentic_chain``
    Multi-turn tool-call-shaped chains: each turn's prompt carries the
    previous turns' outputs (so a chain is a causal sequence, not N
    independent requests) — the workload agents generate.
``batch_flood``
    A burst of batch-priority single-turn requests landing together —
    the scheduler-fairness pressure case (PR 9's flood protocol).
``shared_prefix_session``
    Multi-turn sessions sharing one long page-aligned system prefix —
    the prefix-cache / kv-share / affinity workload.
``spiky_tenant``
    A tight cluster of interactive requests from one tenant — the
    admission-fairness pressure case.

Everything derives from ``random.Random(seed)``: the same
``(seed, duration_s, …)`` produces a byte-identical :meth:`TrafficMix.
to_json` (pinned by ``tests/test_chaos.py``), prompts included — so a
chaos run and its chaos-free baseline serve the exact same token
streams, and per-chain digests are comparable across runs.
"""

from __future__ import annotations

import json
import random
from dataclasses import dataclass, field

SCENARIO_CLASSES = ("short_chat", "agentic_chain", "batch_flood",
                    "shared_prefix_session", "spiky_tenant")

# Tenant names per class (closed set — fairness accounting and metric
# labels in the soak arm stay bounded).
_INTERACTIVE_TENANTS = ("acme", "beta", "gamma")
_BATCH_TENANT = "batchcorp"
_SPIKY_TENANT = "spiky"


@dataclass(frozen=True)
class TrafficTurn:
    """One request within a chain. ``prompt_ids`` is the turn's own
    prompt; an ``agentic_chain`` driver appends the chain's accumulated
    context in front at serve time (``TrafficChain.carry_context``)."""

    prompt_ids: tuple
    max_new_tokens: int
    gap_s: float  # pause before this turn, after the previous finished
    stream: bool

    def to_dict(self) -> dict:
        return {"prompt_ids": list(self.prompt_ids),
                "max_new_tokens": self.max_new_tokens,
                "gap_s": self.gap_s, "stream": self.stream}


@dataclass(frozen=True)
class TrafficChain:
    """One causal request sequence (a chat, a session, an agent run)."""

    chain_id: str
    cls: str
    tenant: str
    at_s: float             # arrival offset from run start
    priority: str           # "interactive" | "batch"
    temperature: float
    seed: int               # sampling seed (deterministic even at T>0)
    carry_context: bool
    turns: tuple = ()
    model: str | None = None

    def to_dict(self) -> dict:
        return {"chain_id": self.chain_id, "cls": self.cls,
                "tenant": self.tenant, "at_s": self.at_s,
                "priority": self.priority,
                "temperature": self.temperature, "seed": self.seed,
                "carry_context": self.carry_context,
                "model": self.model,
                "turns": [t.to_dict() for t in self.turns]}


@dataclass
class TrafficMix:
    seed: int
    duration_s: float
    chains: list = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps(
            {"seed": self.seed, "duration_s": self.duration_s,
             "chains": [c.to_dict() for c in self.chains]},
            indent=2, sort_keys=True)

    def by_class(self) -> dict:
        counts: dict[str, int] = {}
        for c in self.chains:
            counts[c.cls] = counts.get(c.cls, 0) + 1
        return dict(sorted(counts.items()))


def _prompt(rng: random.Random, n: int) -> tuple:
    """Byte-vocabulary prompt ids (the soak gate serves the byte
    tokenizer; real deployments swap prompts, not the mix shape)."""
    return tuple(rng.randrange(0, 256) for _ in range(n))


def generate_traffic(seed: int, duration_s: float, *,
                     classes: tuple = SCENARIO_CLASSES,
                     chains_per_minute: float = 120.0,
                     prompt_scale: float = 1.0,
                     max_new_scale: float = 1.0,
                     models: list | None = None) -> TrafficMix:
    """Deterministic scenario mix for a ``duration_s`` window.

    Arrivals land in the first 80% of the window (tails must finish
    inside the measured run). Every requested class appears at least
    once; beyond that the mix is sampled with interactive-heavy weights.
    ``prompt_scale`` / ``max_new_scale`` shrink the token volumes for
    CPU smokes. ``models`` assigns chains to served model groups
    round-robin (deterministic in chain index), like
    ``generate_scenarios``."""
    unknown = set(classes) - set(SCENARIO_CLASSES)
    if unknown:
        raise ValueError(f"unknown scenario classes {sorted(unknown)}; "
                         f"valid: {SCENARIO_CLASSES}")
    if not classes:
        raise ValueError("at least one scenario class is required")
    rng = random.Random(seed)
    n = max(len(classes),
            int(duration_s * chains_per_minute / 60.0))
    # Interactive-heavy sampling weights; every class floor-guaranteed.
    weights = {"short_chat": 5, "agentic_chain": 2, "batch_flood": 1,
               "shared_prefix_session": 2, "spiky_tenant": 1}
    picks = list(classes)
    pool = [c for c in classes for _ in range(weights[c])]
    while len(picks) < n:
        picks.append(pool[rng.randrange(len(pool))])
    # One shared session prefix per mix (page-aligned at the soak gate's
    # page_size=16): every shared_prefix_session chain reuses it.
    shared_prefix = _prompt(rng, max(16, int(64 * prompt_scale) // 16 * 16))

    def plen(lo: int, hi: int) -> int:
        return max(4, int(rng.randint(lo, hi) * prompt_scale))

    def new_toks(lo: int, hi: int) -> int:
        return max(2, int(rng.randint(lo, hi) * max_new_scale))

    chains: list[TrafficChain] = []
    idx = 0

    def add(cls: str, at_s: float, tenant: str, priority: str,
            turns: list, *, temperature: float = 0.0,
            carry: bool = False) -> None:
        nonlocal idx
        chains.append(TrafficChain(
            chain_id=f"c{idx:04d}-{cls}", cls=cls, tenant=tenant,
            at_s=round(at_s, 3), priority=priority,
            temperature=temperature, seed=seed * 10_000 + idx,
            carry_context=carry, turns=tuple(turns),
            model=(models[idx % len(models)] if models else None)))
        idx += 1

    horizon = duration_s * 0.8
    for cls in picks:
        at = rng.random() * horizon
        if cls == "short_chat":
            tenant = _INTERACTIVE_TENANTS[
                rng.randrange(len(_INTERACTIVE_TENANTS))]
            # A third of chats sample at temperature with a pinned seed:
            # the digest-determinism gate must cover seeded sampling,
            # not just greedy.
            temp = 0.8 if rng.random() < 0.33 else 0.0
            add(cls, at, tenant, "interactive",
                [TrafficTurn(_prompt(rng, plen(16, 48)),
                             new_toks(8, 16), 0.0, stream=True)],
                temperature=temp)
        elif cls == "agentic_chain":
            tenant = _INTERACTIVE_TENANTS[
                rng.randrange(len(_INTERACTIVE_TENANTS))]
            turns = [TrafficTurn(_prompt(rng, plen(24, 64)),
                                 new_toks(8, 24),
                                 0.0 if t == 0
                                 else round(rng.uniform(0.01, 0.05), 3),
                                 stream=False)
                     for t in range(rng.randint(3, 5))]
            add(cls, at, tenant, "interactive", turns, carry=True)
        elif cls == "batch_flood":
            # A burst of independent single-turn batch chains at one
            # arrival instant.
            for _ in range(rng.randint(3, 6)):
                add(cls, at, _BATCH_TENANT, "batch",
                    [TrafficTurn(_prompt(rng, plen(32, 96)),
                                 new_toks(16, 32), 0.0, stream=False)])
        elif cls == "shared_prefix_session":
            tenant = _INTERACTIVE_TENANTS[
                rng.randrange(len(_INTERACTIVE_TENANTS))]
            turns = [TrafficTurn(
                shared_prefix + _prompt(rng, plen(8, 24)),
                new_toks(6, 12),
                0.0 if t == 0 else round(rng.uniform(0.01, 0.04), 3),
                stream=True)
                for t in range(rng.randint(2, 4))]
            add(cls, at, tenant, "interactive", turns)
        else:  # spiky_tenant
            for k in range(rng.randint(3, 6)):
                add(cls, at + k * 0.01, _SPIKY_TENANT, "interactive",
                    [TrafficTurn(_prompt(rng, plen(12, 32)),
                                 new_toks(4, 10), 0.0, stream=False)])
    chains.sort(key=lambda c: (c.at_s, c.chain_id))
    return TrafficMix(seed=seed, duration_s=duration_s, chains=chains)
