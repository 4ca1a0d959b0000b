"""The comparison that decides ``correct`` for a served model.

Once the window has closed: a sample, drawn from the seed, of the greedy
requests it finished — the longest among them — is replayed through the
plain reference, ONE full forward pass over each prompt with its served
tokens. At every served position the reference's logit of the served
token is compared with the reference's best logit. Greedy decoding serves
the argmax of the PROGRAM's logits, so the gap is zero unless the
program's logits differ from the reference's by about the gap: the widest
gap over the sample is the first number compared (``logit_gap``).

The second (``resident_bytes_short``) holds the program to the precisions
the configuration file states for what it KEEPS: the bytes of its live
device arrays when the window has closed may not be under the stated
layer matrices, embedding, head and key-value pool
(``kernels/decode_step.resident_bytes``). It is exact (limit 0), and it
is there for the lower precision the first number cannot see: with seeded
random weights attention is spread over hundreds of positions, rounding
the cache to fp8 moves the logits by less than bfloat16 activations do
(PERF.md section 2), and no number made from served tokens separates the
two — the bytes the cache holds do.

The limits are data (``limits.json``), set from readings on the chip.
"""

from __future__ import annotations

import json
import random
from pathlib import Path

import numpy as np

from benchmark.reference import forward, tokens

LIMITS = Path(__file__).with_name("limits.json")


def limits_for(config_name: str) -> dict:
    """{"logit_gap": limit, "resident_bytes_short": limit}."""
    table = json.loads(LIMITS.read_text())
    return {**table["default"], **table.get(config_name, {})}


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


def gaps_of(params: dict, cfg: dict, req: dict, lowp: str | None = None) -> dict:
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
    ref = np.asarray(forward.logits(params, cfg, ids[:-1], len(served)))
    rows, best = np.arange(len(served)), ref.max(axis=1)
    out["gaps"] = best - ref[rows, served]
    out["control_gaps"] = {}
    for kind in (lowp.split(",") if lowp else []):
        low = np.asarray(forward.logits(params, cfg, ids[:-1], len(served), kind))
        out["control_gaps"][kind] = best - ref[rows, low.argmax(axis=1)]
    return out


def compare(params: dict, cfg: dict, sample: list[dict], limits: dict,
            resident: dict, control_kinds: str | None = None) -> dict:
    """{"ok", "logit_gap", "limit", ...}: every number beside its limit.
    ``resident``: {"live_bytes": measured, "stated_bytes": lower bound}."""
    limit = limits["logit_gap"]
    short = max(0, resident["stated_bytes"] - resident["live_bytes"])
    out = {"requests": len(sample), "served_tokens": 0, "logit_gap": None,
           "limit": limit, "resident_bytes_short": short,
           "limit_resident_bytes_short": limits["resident_bytes_short"],
           **resident, "prompt_token_mismatches": [], "ok": False}
    gaps, control = [], {}
    for r in sample:
        g = gaps_of(params, cfg, r, control_kinds)
        if not g["prompt_matches"]:
            out["prompt_token_mismatches"].append(
                (r["id"], g["prompt_tokens"], r["prompt_tokens"]))
            continue
        out["served_tokens"] += g["served_tokens"]
        gaps.append(g["gaps"])
        for kind, cg in g["control_gaps"].items():
            control.setdefault(kind, []).append(cg)
    if not gaps:
        return out
    gaps = np.concatenate(gaps)
    out.update(logit_gap=float(gaps.max()), logit_gap_mean=float(gaps.mean()),
               tokens_off_the_reference_best=int((gaps > 0).sum()))
    out["ok"] = (not out["prompt_token_mismatches"] and out["logit_gap"] <= limit
                 and short <= limits["resident_bytes_short"])
    for kind, cg in control.items():  # has to come out NOT ok
        cg = np.concatenate(cg)
        out.setdefault("control", {})[kind] = {
            "logit_gap": float(cg.max()), "logit_gap_mean": float(cg.mean()),
            "ok": bool(cg.max() <= limit)}
    return out
