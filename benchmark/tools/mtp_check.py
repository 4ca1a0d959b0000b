"""Are the drafts the served path makes the prediction module's?

    python3 -m benchmark.tools.mtp_check --workload joyai.sysprompt-open \
        --seed <n> [--requests 3] [--tokens 40] [--rehearse-cpu]

``correct`` cannot see a draft: drafts never reach the stream, and whatever
they are, a request is served the trunk's own greedy tokens. A module that
read the wrong cache row or the wrong next token would only be accepted
even less often than chance. So this tool serves a few of the cell's own
requests through the engine the benchmark builds (``serving.build``'s
server; no request reaches its front door, the engine is stepped here, in
this process), reads
after every dispatch the draft each row holds — ``EngineCore._draft_toks``,
the device array the next round feeds; this tool is the one place outside
the engine that looks at it — and, once the device is freed, compares each
with the block's ``draft_logits`` over the tokens the row had committed:

- ``agreement``: the share of drafts that are the reference's argmax;
- ``draft_gap_max``: the widest gap by which the reference's logit of a
  served draft lies below the reference's best (the module's ``logit_gap``).

Limits (``LIMITS``; readings in ``PERF.md`` section 6): a draft is made in
bfloat16 from 129,280 near-tied logits of seeded weights, so the argmax
itself moves on rounding, as a served token's does. EVERY draft is compared
(a few dozen: too few to leave out the positions near a router's cut, as
``correct`` does for served tokens), so the widest gap has the tail of a
swapped expert and its limit stands above it; a module that read the wrong
cache row or the wrong next token moves the AGREEMENT to chance, and that
is what the tool holds. Prints one JSON line; exits 1 outside a limit.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import blocks, generators, serving

# The chip's readings (my chip runs, PR 35): agreement 0.909 (40 of 44 drafts) and 0.934 (71 of 76), widest
# gap 0.341 and 0.944 beside served tokens' own 0.48-1.04; in float32 on the CPU every draft agrees.
LIMITS = {"agreement_min": 0.75, "draft_gap_max": 1.6}


def serve_and_record(core, prompts: list[list[int]], tokens: int) -> list[dict]:
    """Serve ``prompts`` greedily, ``tokens`` each; after every step, each
    decoding row's (committed tokens, draft held). Returns a dict a
    request: ``ids`` (prompt + served) and ``drafts`` {committed: draft}."""
    import numpy as np

    from runbookai_tpu.engine.request import EngineRequest, RequestState, SamplingParams

    reqs = [EngineRequest(request_id=f"mtp{i}", prompt_ids=list(p), sampling=SamplingParams(
        temperature=0.0, max_new_tokens=tokens, stop_token_ids=())) for i, p in enumerate(prompts)]
    seen: list[dict[int, int]] = [{} for _ in reqs]
    for r in reqs:
        core.submit(r)
    while core.has_work:
        core.step()
        core._drain_pending()  # the host's view of every row, current
        drafts = np.asarray(core._draft_toks)
        for r, mine in zip(reqs, seen):
            if r.state == RequestState.DECODE and r.slot is not None:
                mine[len(r.prompt_ids) + len(r.out_ids)] = int(drafts[r.slot])
    return [{"ids": list(r.prompt_ids) + list(r.out_ids), "drafts": mine}
            for r, mine in zip(reqs, seen)]


def compare(block, params, cfg: dict, served: list[dict]) -> dict:
    """Each recorded draft against the block's ``draft_logits``: with ``n``
    tokens committed the draft is of token ``n``, made at position ``n - 2``
    (from the trunk's state there and token ``n - 1``)."""
    import numpy as np

    agree, gaps = [], []
    for req in served:
        if not req["drafts"]:
            continue
        first = min(req["drafts"])
        ids = req["ids"]
        # rows for positions first - 2 .. len(ids) - 2: one pass a request
        ref = np.asarray(block.forward.draft_logits(params, cfg, ids, len(ids) - first + 1))
        for n, draft in sorted(req["drafts"].items()):
            row = ref[n - first]
            agree.append(int(np.argmax(row)) == draft)
            gaps.append(float(row.max() - row[draft]))
    return {"drafts_compared": len(gaps),
            "agreement": sum(agree) / len(agree) if agree else None,
            "draft_gap_max": max(gaps) if gaps else None,
            "draft_gap_mean": sum(gaps) / len(gaps) if gaps else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--tokens", type=int, default=40)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.reference import tokens
    from benchmark.run import free_device

    _, config, traffic, cell_extra = serving.find_cell(serving.benchmark_json(), args.workload)
    block = blocks.load(config["block"])
    model_cfg = serving.model_config(config, args.rehearse_cpu)
    if not getattr(model_cfg, "self_draft", False):
        raise SystemExit(f"{config['name']}: the model has no prediction module to check")
    serving.register(model_cfg, args.seed)
    # The server the benchmark builds. No request is ever sent to it, so the
    # engine's own loop never starts and the engine is stepped here.
    server = serving.build(serving.render_serve_config(
        config, model_cfg.name, args.rehearse_cpu))
    core = server.client.core
    # the window's own requests for this seed, the shortest prompts first
    plan = generators.load(traffic["generator"]).plan(
        traffic, cell_extra, args.seed, 30.0, args.rehearse_cpu)
    prompts = sorted((tokens.prompt_ids(r["messages"], model_cfg.family)
                      for r in plan["requests"]), key=len)[:args.requests]
    served = serve_and_record(core, prompts, args.tokens)
    counters = {k: core.metrics[k] for k in ("spec_drafted", "spec_accepted")}
    ref_cfg = serving.reference_cfg(model_cfg)
    free_device(server)
    params = block.weights.make_params(ref_cfg, args.seed % (2 ** 31), quantized=False)
    verdict = compare(block, params, ref_cfg, served)
    gap_limit = LIMITS["draft_gap_max"]
    ok = (verdict["drafts_compared"] > 0
          and verdict["agreement"] >= LIMITS["agreement_min"]
          and verdict["draft_gap_max"] <= gap_limit)
    print(json.dumps({"mtp_check": args.workload, "ok": ok, **verdict, **counters,
                      "limit_agreement_min": LIMITS["agreement_min"],
                      "limit_draft_gap": gap_limit, "requests": len(served),
                      "prompt_tokens": [len(p) for p in prompts]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
