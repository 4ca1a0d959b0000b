"""The comparison that decides ``correct`` for a served model.

Once the window has closed: a sample, drawn from the seed, of the greedy
requests it finished — the longest among them — is replayed through the
plain reference, ONE full forward pass over each prompt with its served
tokens. At every served position the reference's logit of the served
token is compared with the reference's best logit. Greedy decoding serves
the argmax of the PROGRAM's logits, so the gap is zero unless the
program's logits differ from the reference's by about the gap: the widest
gap over the sample is the first number compared (``logit_gap``).

WHICH statistic of the gaps decides is the limits' to say (``deciding``):
a configuration whose limits hold ``logit_gap_mean`` (and, if it has one,
``logit_gap_p99``) is decided by the MEAN gap over the sampled positions
(and their 99th percentile), and its widest gap is printed beside them
without a limit; limits that hold neither are decided by the widest gap.
Where rounding is amplified by the model itself (a delta rule's linear
system, attention scores with a deviation near 6) the widest of ~1,000
gaps is a draw from a heavy tail that a sound program takes anew in every
run, while the mean stands ten times under the control's. What a mean
cannot see is a fault confined to a few positions: 1% of them at a gap of
10 moves it by 0.1 (the configurations' limits files say what the 99th
percentile reads, and why it has a limit or none). A control is held to
the same numbers as the program, and has to fail one of them.

The second (``resident_bytes_short``) holds the program to the precisions
the configuration file states for what it KEEPS: the bytes of its live
device arrays when the window has closed may not be under the stated
layer matrices, embedding, head and key-value pool (the block's
``bytes.resident_bytes``). It is exact (limit 0), and it
is there for the lower precision the first number cannot see: with seeded
random weights attention is spread over hundreds of positions, rounding
the cache to fp8 moves the logits by less than bfloat16 activations do
(PERF.md section 2), and no number made from served tokens separates the
two — the bytes the cache holds do.

A block with a discrete choice inside (a router) may declare positions
not comparable (``blocks/__init__.py``): they are left out of the gap, and
their share of the sampled positions is the third number compared
(``not_comparable_share``). The dense block declares none.

The architecture is the run's block (``blocks.load`` of the configuration's
``block`` key), handed in: this file imports no block by name. The limits
are data, set from readings on the chip: ``limits.json``'s ``default``,
and over it a configuration's own ``configs/<name>.limits.json``, if it
has one.
"""

from __future__ import annotations

import json
import random
from pathlib import Path

import numpy as np

from benchmark.reference import tokens

LIMITS = Path(__file__).with_name("limits.json")
CONFIGS = Path(__file__).parents[1] / "configs"


# The statistics of the sampled gaps. The widest decides unless a
# configuration's limits hold one of the others: then those decide.
WIDEST = "logit_gap"
STATS = {WIDEST: np.max, "logit_gap_mean": np.mean,
         "logit_gap_p99": lambda gaps: np.percentile(gaps, 99)}


def limits_for(config_name: str) -> dict:
    """{"logit_gap": limit, "resident_bytes_short": limit,
    "not_comparable_share": limit}, and ``logit_gap_mean`` /
    ``logit_gap_p99`` where the configuration's own file holds them."""
    own = CONFIGS / f"{config_name}.limits.json"
    return {**json.loads(LIMITS.read_text())["default"],
            **(json.loads(own.read_text()) if own.is_file() else {})}


def deciding(limits: dict) -> list[str]:
    """The statistics of the gaps that ``ok`` is held to under ``limits``."""
    return [k for k in STATS if k != WIDEST and k in limits] or [WIDEST]


def gap_stats(gaps: np.ndarray) -> dict:
    return {name: float(of(gaps)) for name, of in STATS.items()}


def choose_sample(reqs: list[dict], n: int, seed: int) -> list[dict]:
    """``n`` finished requests: the longest (prompt plus served tokens) and
    ``n - 1`` more drawn from the seed."""
    ok = [r for r in reqs if r["status"] == 200 and not r["error"] and r["text"]
          and r["prompt_tokens"]]
    if not ok:
        return []
    ok.sort(key=lambda r: r["id"])
    longest = max(ok, key=lambda r: r["prompt_tokens"] + len(r["text"]))
    rest = [r for r in ok if r is not longest]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:max(0, n - 1)]


def _logits(block, *args) -> tuple[np.ndarray, np.ndarray]:
    """The block's logits, and the positions it declares not comparable
    (none, for a block that returns the logits alone)."""
    out = block.forward.logits(*args)
    lg, skip = out if isinstance(out, tuple) else (out, None)
    lg = np.asarray(lg)
    return lg, (np.zeros(len(lg), bool) if skip is None else np.asarray(skip, bool))


def gaps_of(block, params: dict, cfg: dict, req: dict, lowp: str | None = None) -> dict:
    """Per-position gaps of one request against the reference (and of each
    control of ``lowp``, comma-separated: the token IT puts first)."""
    prompt = tokens.prompt_ids(req["messages"], cfg["family"])
    served = tokens.ids_of_text(req["text"])
    out = {"prompt_tokens": len(prompt), "served_tokens": len(served),
           "prompt_matches": len(prompt) == req["prompt_tokens"]}
    if not out["prompt_matches"]:
        return out
    ids = prompt + served
    # Row i predicts ids[i + 1]: the rows that predict the served tokens.
    ref, skip = _logits(block, params, cfg, ids[:-1], len(served))
    rows, best = np.arange(len(served)), ref.max(axis=1)
    out["gaps"] = (best - ref[rows, served])[~skip]
    out["not_comparable"] = int(skip.sum())
    out["control_gaps"] = {}
    for kind in (lowp.split(",") if lowp else []):
        low, _ = _logits(block, params, cfg, ids[:-1], len(served), kind)
        out["control_gaps"][kind] = (best - ref[rows, low.argmax(axis=1)])[~skip]
    return out


def compare(block, params: dict, cfg: dict, sample: list[dict], limits: dict,
            resident: dict, control_kinds: str | None = None) -> dict:
    """{"ok", "decided_by", "logit_gap", ...}: every number beside its limit
    (``limit_<name>``; a statistic of the gaps that does not decide has
    none). ``resident``: {"live_bytes": measured, "stated_bytes": lower
    bound}."""
    decides = deciding(limits)

    def within(stats: dict) -> bool:
        return all(stats[k] <= limits[k] for k in decides)

    short = max(0, resident["stated_bytes"] - resident["live_bytes"])
    out = {"requests": len(sample), "served_tokens": 0, WIDEST: None,
           "decided_by": decides, **{f"limit_{k}": limits[k] for k in decides},
           "resident_bytes_short": short,
           "limit_resident_bytes_short": limits["resident_bytes_short"],
           "not_comparable": 0, "not_comparable_share": None,
           "limit_not_comparable_share": limits["not_comparable_share"],
           **resident, "prompt_token_mismatches": [], "ok": False}
    gaps, control = [], {}
    for r in sample:
        g = gaps_of(block, params, cfg, r, control_kinds)
        if not g["prompt_matches"]:
            out["prompt_token_mismatches"].append(
                (r["id"], g["prompt_tokens"], r["prompt_tokens"]))
            continue
        out["served_tokens"] += g["served_tokens"]
        out["not_comparable"] += g["not_comparable"]
        gaps.append(g["gaps"])
        for kind, cg in g["control_gaps"].items():
            control.setdefault(kind, []).append(cg)
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    if not gaps.size:  # nothing served, or nothing the block lets be compared
        return out
    out.update(gap_stats(gaps), tokens_off_the_reference_best=int((gaps > 0).sum()),
               not_comparable_share=out["not_comparable"] / (out["not_comparable"] + gaps.size))
    out["ok"] = (not out["prompt_token_mismatches"]
                 and within(out)
                 and short <= limits["resident_bytes_short"]
                 and out["not_comparable_share"] <= limits["not_comparable_share"])
    for kind, cg in control.items():  # has to come out NOT ok, by the same numbers
        stats = gap_stats(np.concatenate(cg))
        out.setdefault("control", {})[kind] = {
            **stats, "ok": within(stats)}
    return out
