"""Traffic generators: one module a KIND of traffic, found by the
``generator`` key of a file under ``benchmark/traffic/``.

A generator is a pure function of (parameters, seed, seconds): it returns
a plan — requests with their due times — that ``loadgen.py`` replays.
Every seed gets the same sizes and gaps (the quantiles of the stated
distributions) in the same fixed order; the seed changes the text (and,
in ``run.py``, the weights), so the work of a window does not depend on
it. Why not another order for each seed: measured, any reordering moves
a near-knee tail by more than any bound would hold (PERF.md).
"""

from __future__ import annotations

import importlib
import math
import random
from statistics import NormalDist

_WORDS = (
    "checkout payments gateway latency saturated canary rollback replica "
    "deploy error budget alert pager timeout retry queue depth memory cpu "
    "throttle eviction pod node region shard cache miss ratio p99 spike "
    "baseline drift incident runbook mitigation owner service dependency "
    "database connection pool lock contention upstream downstream trace "
    "span log metric dashboard threshold burn rate window restart oom "
    "certificate dns route health probe failover leader quorum lag").split()


def load(kind: str):
    """The generator module of a traffic kind (``benchmark/generators/<kind>.py``)."""
    return importlib.import_module(f"benchmark.generators.{kind}")


ORDER_SEED = 20240607  # the order of every mix, the same for every seed


def quantiles(spec: dict, n: int, divisor: float = 1.0) -> list[float]:
    """``n`` values at the quantiles (i + 0.5) / n of ``spec``'s
    distribution, clipped to its range, in a fixed shuffle (``ORDER_SEED``).
    ``divisor`` scales the lengths down for the CPU rehearsal."""
    us = [(i + 0.5) / n for i in range(n)]
    lo, hi = spec["min"] / divisor, spec["max"] / divisor
    if spec["dist"] == "lognormal":
        mu, nd = math.log(spec["median"] / divisor), NormalDist()
        vals = [math.exp(mu + spec["sigma"] * nd.inv_cdf(u)) for u in us]
    elif spec["dist"] == "exponential":
        vals = [-math.log(1.0 - u) * spec["mean"] for u in us]
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    vals = [min(hi, max(lo, v)) for v in vals]
    random.Random(f"{ORDER_SEED}:{spec}:{n}").shuffle(vals)
    return vals


def text_of(rng: random.Random, n_bytes: int, tag: str) -> str:
    """Exactly ``n_bytes`` of printable ASCII (one byte token each under the
    byte tokenizer), distinct from its first page on through ``tag``."""
    head = f"[{tag}] "
    parts, size = [head], len(head)
    while size < n_bytes:
        w = rng.choice(_WORDS) + " "
        parts.append(w)
        size += len(w)
    return "".join(parts)[:max(1, n_bytes)]


def request(rid: str, due_s: float, system: str, user: str, max_tokens: int,
            n_choices: int = 1) -> dict:
    return {"id": rid, "due_s": due_s, "max_tokens": max_tokens, "n_choices": n_choices,
            "messages": [{"role": "system", "content": system},
                         {"role": "user", "content": user}]}


def burst_requests(bursts: list[dict], system: str, rng: random.Random,
                   divisor: int = 1) -> list[dict]:
    """Warm-up bursts, as the traffic file lists them: ``requests`` fresh
    prompts all due at the same instant ``at_s``; with ``n_choices`` each
    is ONE non-streamed request for that many choices, which reach the
    engine together whatever the timing — so each batched prefill width
    has compiled before the window."""
    return [request(f"burst{k}.{j}", b["at_s"], system,
                    text_of(rng, max(32, b["prompt_tokens"] // divisor),
                            f"b{k}.{j}.{rng.random():.6f}"),
                    b["max_tokens"], int(b.get("n_choices", 1)))
            for k, b in enumerate(bursts) for j in range(b["requests"])]
