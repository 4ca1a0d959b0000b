"""Open loop: independent single-turn requests on a fixed schedule.

Arrivals are a Poisson process at ``rate_rps`` (the cell's number): the
gaps are the quantiles of the exponential distribution, scaled so that the
arrivals fill the window exactly. A request is due whether or not earlier
ones have finished.
"""

from __future__ import annotations

import random

from benchmark.generators import quantiles, request, text_of


def plan(params: dict, cell: dict, seed: int, seconds: float,
         rehearsal: bool = False) -> dict:
    rh = params.get("rehearsal", {}) if rehearsal else {}
    div = float(rh.get("length_divisor", 1))
    rate = float(rh.get("rate_rps") or cell["rate_rps"])
    rng = random.Random(seed)
    n = max(1, round(rate * seconds))
    gaps = quantiles({"dist": "exponential", "mean": 1.0 / rate,
                      "min": 0.0, "max": 1e9}, n)
    scale = seconds / sum(gaps)
    prompts = quantiles(params["prompt_tokens"], n, div)
    outs = quantiles(params["max_tokens"], n, div)
    requests, t = [], 0.0
    for i in range(n):
        # The first request is due half a gap in, the last half a gap
        # before the window closes.
        t += gaps[i] * scale * (0.5 if i == 0 else 1.0)
        requests.append(request(f"r{i}", t, params["system"],
                                text_of(rng, int(prompts[i]), f"{seed}.{i}"),
                                max(2, int(outs[i]))))
    return {"seconds": seconds, "requests": requests, "rate_rps": rate}
