"""End-to-end arithmetic on the load generator's records.

Kept here so that no later PR can change how a tail or a rate is taken:
a tail is over ALL requests sent in the window (one that failed counts as
infinitely slow).
"""

from __future__ import annotations

import json
import math

INF_MS = 1e12  # what an infinite latency is printed as (JSON has no inf)


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks — the
    same rule for every metric and every PR. Empty input is an error."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if xs[lo] == xs[hi]:  # also keeps inf - inf out of the interpolation
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def load_records(path: str) -> tuple[dict, list[dict], dict]:
    """(start record, request records, end record) of a loadgen output."""
    start, reqs, end = {}, [], {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if r["kind"] == "start":
                start = r
            elif r["kind"] == "end":
                end = r
            else:
                reqs.append(r)
    return start, reqs, end


def failure(r: dict) -> str | None:
    """Why a request counts as failed, or None."""
    if r["status"] != 200:
        return f"HTTP {r['status']}: {r['error']}"
    if r["error"]:
        return r["error"]
    if not (r["done_marker"] and r["terminated"]):
        return "stream cut before [DONE]"
    ct, n_chars = r["completion_tokens"], len(r["text"])
    if ct is None:
        return "no usage block"
    # One character a token; a stop token is counted and carries none.
    if not (n_chars == ct or (r["finish"] == "stop" and n_chars == ct - 1)):
        return f"{ct} tokens counted, {n_chars} characters streamed"
    if ct > r["max_tokens"]:
        return f"{ct} tokens over max_tokens {r['max_tokens']}"
    return None


def ttft_ms(r: dict) -> float:
    """Client-side time to the first streamed token, from the instant the
    request was DUE (an open loop: a late send is the system's queue)."""
    if failure(r) is not None or not r["times"]:
        return math.inf
    return (r["times"][0] - r["due"]) * 1e3


def tpot_ms(r: dict) -> float | None:
    """(last token − first token) / (tokens − 1); None under 8 tokens."""
    n = len(r["times"])
    if failure(r) is not None:
        return math.inf
    if n < 8:
        return None
    return (r["times"][-1] - r["times"][0]) / (n - 1) * 1e3


def finite(v: float) -> float:
    return INF_MS if math.isinf(v) else v


def end_to_end(reqs: list[dict], t0: float, seconds: float) -> dict:
    """{metric: value} plus the sample counts, over the window's requests."""
    ttfts = [ttft_ms(r) for r in reqs]
    tpots = [v for v in (tpot_ms(r) for r in reqs) if v is not None]
    done_in = [r for r in reqs if failure(r) is None
               and r["end"] is not None and r["end"] <= t0 + seconds]
    out = {"samples": {"ttft": len(ttfts), "tpot": len(tpots),
                       "completed_in_window": len(done_in)}}
    if ttfts:
        out["ttft_p90_ms"] = finite(percentile(ttfts, 90))
        out["ttft_p50_ms"] = finite(percentile(ttfts, 50))
    if tpots:
        out["tpot_p90_ms"] = finite(percentile(tpots, 90))
        out["tpot_p50_ms"] = finite(percentile(tpots, 50))
    return out


def histogram_quantile(before: dict[float, float], after: dict[float, float],
                       q: float) -> float | None:
    """Quantile from the DELTA of a cumulative Prometheus histogram
    ({upper bound: count}), linear inside the bucket it lands in; the
    +Inf bucket answers with the last finite bound. None without samples."""
    bounds = sorted(after)
    delta = [after[b] - before.get(b, 0.0) for b in bounds]
    total = delta[-1] if delta else 0.0
    if total <= 0:
        return None
    rank, prev_b, prev_c = q * total, 0.0, 0.0
    for b, c in zip(bounds, delta):
        if c >= rank:
            if math.isinf(b):
                return prev_b
            span = c - prev_c
            return prev_b + (b - prev_b) * ((rank - prev_c) / span if span else 1.0)
        prev_b, prev_c = (b if not math.isinf(b) else prev_b), c
    return prev_b


def parse_histogram(text: str, name: str) -> dict[float, float]:
    """``name``'s unlabelled buckets from a /metrics exposition."""
    out = {}
    prefix = name + '_bucket{le="'
    for line in text.splitlines():
        if line.startswith(prefix):
            le, count = line[len(prefix):].split('"} ')
            out[math.inf if le == "+Inf" else float(le)] = float(count)
    return out
