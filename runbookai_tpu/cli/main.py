"""``runbook`` CLI — argparse command surface.

Parity target: reference ``src/cli.tsx`` (commander + Ink): ask :1104, chat
:1119, investigate :1133, status :1193, init :1208, demo :1240, knowledge
:1250-1471, config :1587, webhook :1999, slack-gateway :2057, mcp :2182,
checkpoint :2353, plus the eval runners. Rendering is plain-text streaming of
the shared AgentEvent vocabulary (runbookai_tpu.demo.runner.render_event)
instead of a React terminal UI.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
from pathlib import Path

from runbookai_tpu.utils.config import (
    Config,
    load_config,
    save_config,
    set_config_value,
    validate_config,
)


_token_line_open = False


def _print_event(ev) -> None:
    from runbookai_tpu.demo.runner import render_event

    global _token_line_open
    if ev.kind == "token":
        # Live token deltas paint inline (raw model output — tool-call
        # markup included); the parsed answer still renders afterwards.
        print(ev.data.get("delta", ""), end="", flush=True)
        _token_line_open = True
        return
    if _token_line_open:
        print(flush=True)  # close the streamed line before a normal event
        _token_line_open = False
    print(render_event(ev), flush=True)


def _load(args) -> Config:
    return load_config(path=getattr(args, "config", None))


# --------------------------------------------------------------------------- #
# commands                                                                    #
# --------------------------------------------------------------------------- #


def cmd_ask(args) -> int:
    from runbookai_tpu.cli.runtime import build_agent, build_runtime

    config = _load(args)
    runtime = build_runtime(config, interactive=not args.yes)
    agent = build_agent(runtime)

    async def run() -> None:
        async for ev in agent.run(args.query, session_id=args.session):
            _print_event(ev)

    asyncio.run(run())
    return 0


def cmd_deploy(args) -> int:
    """Deploy via the deploy-service skill through the agent loop (reference
    cli.tsx:1556) — pre-deployment checks first; mutations route through the
    safety/approval gate like any other remediation."""
    from runbookai_tpu.cli.runtime import build_agent, build_runtime

    config = _load(args)
    runtime = build_runtime(config, interactive=not args.yes)
    agent = build_agent(runtime)
    version = f" version {args.version}" if args.version else ""
    if args.dry_run:
        query = (f"Show me what would happen if I deploy {args.service} to "
                 f"{args.environment}{version}. Do not execute, just explain "
                 "the steps.")
    else:
        query = (f"Deploy {args.service} to {args.environment}{version} using "
                 "the deploy-service skill. Perform all pre-deployment checks "
                 "first.")
    print(f"Deploying {args.service} to {args.environment}..."
          + (" (dry run)" if args.dry_run else ""))

    async def run() -> None:
        async for ev in agent.run(query):
            _print_event(ev)

    asyncio.run(run())
    return 0


def cmd_chat(args) -> int:
    from runbookai_tpu.agent.memory import ConversationMemory
    from runbookai_tpu.cli.runtime import build_agent, build_runtime

    config = _load(args)
    runtime = build_runtime(config)
    if getattr(args, "raw", False):
        return _chat_raw(runtime)
    agent = build_agent(runtime)
    memory = ConversationMemory(summarize_after_messages=16)
    print("runbook chat — empty line or 'exit' to quit")

    async def turn(text: str) -> None:
        memory.add("user", text)
        answer = ""
        query = text
        context = memory.context_block()
        if context:
            query = f"{context}\n\n# Current question\n{text}"
        async for ev in agent.run(query):
            if ev.kind == "answer":
                answer = ev.data["text"]
            _print_event(ev)
        memory.add("assistant", answer)
        if memory.needs_summarization:
            await memory.summarize(runtime.llm)

    while True:
        try:
            line = input("\nyou> ").strip()
        except (EOFError, KeyboardInterrupt):
            break
        if not line or line in ("exit", "quit"):
            break
        asyncio.run(turn(line))
    return 0


def _chat_raw(runtime) -> int:
    """Direct model chat (no agent loop): tokens print as they stream —
    the human-facing path for eyeballing model behavior and latency."""
    history: list[tuple[str, str]] = []
    llm = runtime.llm
    print("runbook chat --raw — streaming model chat; empty line to quit")

    async def turn(text: str) -> None:
        pieces = []
        # Prior turns ride in the prompt (the agentless path has no
        # ConversationMemory; without this every turn would be stateless).
        if history:
            transcript = "\n".join(f"{role}: {msg}" for role, msg in history)
            prompt = (f"# Conversation so far\n{transcript}\n\n"
                      f"# Current message\n{text}")
        else:
            prompt = text
        # Event-dict stream protocol (LLMClient.chat_stream): true token
        # streaming on the engine client, chunked fallback on mocks.
        async for ev in llm.chat_stream("You are a concise SRE assistant.",
                                        prompt):
            if ev.get("type") == "text":
                pieces.append(ev["delta"])
                print(ev["delta"], end="", flush=True)
        print()
        history.append(("user", text))
        history.append(("assistant", "".join(pieces)))

    while True:
        try:
            line = input("\nyou> ").strip()
        except (EOFError, KeyboardInterrupt):
            break
        if not line or line in ("exit", "quit"):
            break
        asyncio.run(turn(line))
    if hasattr(llm, "shutdown"):
        asyncio.run(llm.shutdown())
    return 0


def cmd_investigate(args) -> int:
    from runbookai_tpu.cli.runtime import build_orchestrator, build_runtime
    from runbookai_tpu.session.checkpoint import CheckpointStore

    config = _load(args)
    runtime = build_runtime(config, interactive=not args.yes)
    orch = build_orchestrator(runtime, incident_id=args.incident_id,
                              execute_remediation=args.execute)
    # Live hypothesis tree repaints under the event stream on TTYs
    # (reference cli.tsx:116 Ink tree); pipes get plain line events.
    from runbookai_tpu.cli.live_view import LiveTreeSink

    live = LiveTreeSink(orch.machine, fallback=_print_event)
    orch.event_sink = live
    result = asyncio.run(orch.investigate(args.incident_id, args.description or ""))
    live.finish()
    store = CheckpointStore(f"{config.runbook_dir}/checkpoints")
    store.save_machine(orch.machine, label="final")
    hypotheses = list(orch.machine.hypotheses.values())
    if hypotheses:
        import sys

        from runbookai_tpu.cli.hypothesis_view import render_summary, render_tree

        color = sys.stdout.isatty()
        print("\n" + render_tree(hypotheses, color=color))
        print(render_summary(hypotheses, color=color))
    print(f"\nroot cause: {result.root_cause}")
    print(f"confidence: {result.confidence}")
    print(f"services:   {', '.join(result.affected_services)}")
    if args.learn:
        from runbookai_tpu.learning.loop import run_learning_loop

        artifacts = asyncio.run(run_learning_loop(
            runtime.llm, result, out_dir=f"{config.runbook_dir}/learning",
            base_dir=config.runbook_dir,
            apply_updates=getattr(args, "apply_learnings", False)))
        print(f"learning artifacts: {artifacts}")
    return 0


def cmd_demo(args) -> int:
    from runbookai_tpu.demo.runner import run_demo

    run_demo(emit=_print_event, fast=args.fast)
    return 0


def cmd_status(args) -> int:
    config = _load(args)
    problems = validate_config(config)
    print(f"llm provider: {config.llm.provider} ({config.llm.model})")
    enabled = []
    if config.providers.aws.enabled:
        enabled.append("aws" + (" (simulated)" if config.providers.aws.simulated else ""))
    if config.providers.kubernetes.enabled:
        enabled.append("kubernetes" + (" (simulated)" if config.providers.kubernetes.simulated else ""))
    for name, c in (("datadog", config.observability.datadog),
                    ("prometheus", config.observability.prometheus),
                    ("pagerduty", config.incident.pagerduty),
                    ("opsgenie", config.incident.opsgenie),
                    ("slack", config.incident.slack)):
        if c.enabled:
            enabled.append(name)
    print(f"providers: {', '.join(enabled) or '(none enabled)'}")
    db = Path(config.knowledge.db_path)
    if db.is_file():
        from runbookai_tpu.knowledge.store.sqlite_fts import KnowledgeStore

        stats = KnowledgeStore(db).stats()
        print(f"knowledge: {stats['documents']} docs / {stats['chunks']} chunks")
    else:
        print("knowledge: (no database — run `runbook knowledge sync`)")
    if problems:
        print("config problems:")
        for p in problems:
            print(f"  - {p}")
        return 1
    print("config: ok")
    return 0


def cmd_init(args) -> int:
    target = Path(args.dir or ".") / ".runbook" / "config.yaml"
    if target.exists() and not args.force and not args.interactive:
        print(f"{target} already exists (use --force to overwrite)")
        return 1
    if args.interactive:
        from runbookai_tpu.cli.wizard import (
            hydrate_answers,
            run_wizard,
            save_wizard_configs,
        )

        base = hydrate_answers(target.parent) if target.exists() else None
        answers = run_wizard(base=base)
        config_path, services_path = save_wizard_configs(
            answers, config_dir=target.parent)
        print(f"wrote {config_path} and {services_path}")
        return 0
    config = Config()
    if args.template == "simulated":
        config = Config.model_validate({
            "llm": {"provider": "mock"},
            "providers": {"aws": {"enabled": True, "simulated": True},
                          "kubernetes": {"enabled": True, "simulated": True}},
            "observability": {"datadog": {"enabled": True, "simulated": True},
                              "prometheus": {"enabled": True, "simulated": True}},
            "incident": {"pagerduty": {"enabled": True, "simulated": True}},
        })
    elif args.template == "tpu":
        config = Config.model_validate({
            "llm": {"provider": "jax-tpu", "model": "llama3-8b-instruct",
                    "dtype": "bfloat16"},
            "providers": {"aws": {"enabled": True, "simulated": True},
                          "kubernetes": {"enabled": True, "simulated": True}},
            "incident": {"pagerduty": {"enabled": True, "simulated": True}},
        })
    save_config(config, target)
    print(f"wrote {target} (template: {args.template})")
    return 0


def cmd_config(args) -> int:
    config = _load(args)
    if args.set:
        for assignment in args.set:
            if "=" not in assignment:
                print(f"expected key=value, got {assignment!r}")
                return 1
            key, value = assignment.split("=", 1)
            config = set_config_value(config, key.strip(), value.strip())
        path = args.config or Path(".runbook") / "config.yaml"
        save_config(config, path)
        print(f"updated {path}")
    if args.show or not args.set:
        print(json.dumps(config.model_dump(mode="json"), indent=2))
    return 0


def cmd_knowledge(args) -> int:
    config = _load(args)
    if args.knowledge_cmd == "auth":
        # `runbook knowledge auth google` (reference cli.tsx:1450, google-auth.ts)
        import os

        from runbookai_tpu.knowledge.sources.google_auth import (
            TokenStore,
            authorization_url,
            exchange_code,
        )

        client_id = os.environ.get("GOOGLE_CLIENT_ID", "")
        client_secret = os.environ.get("GOOGLE_CLIENT_SECRET", "")
        if not client_id or not client_secret:
            print("set GOOGLE_CLIENT_ID and GOOGLE_CLIENT_SECRET first")
            return 1
        print("Open this URL, authorize, and paste the code:")
        print(f"  {authorization_url(client_id)}")
        code = input("code> ").strip()
        tokens = exchange_code(client_id, client_secret, code)
        TokenStore().save(tokens)
        print("tokens saved to .runbook/google-tokens.json")
        return 0

    from runbookai_tpu.knowledge.retriever import create_retriever

    retriever = create_retriever(config)
    if args.knowledge_cmd == "sync":
        if not config.knowledge.sources:
            # Silent zero-document syncs are a config-location trap
            # (config lives at .runbook/config.yaml, not ./runbook.yaml).
            print("warning: no knowledge sources configured — add "
                  "knowledge.sources entries to .runbook/config.yaml "
                  "(see docs/CONFIG.md)", file=sys.stderr)
        counts = retriever.sync(force=args.force)
        for name, n in counts.items():
            print(f"{name}: {n} documents synced")
        print(json.dumps(retriever.stats(), indent=2, default=str))
        return 0
    if args.knowledge_cmd == "search":
        hits = retriever.hybrid.search(args.query, limit=args.limit,
                                       knowledge_type=args.type,
                                       service=args.service)
        for h in hits:
            print(f"[{h.score:.4f}] ({h.doc.knowledge_type}) {h.doc.title} "
                  f"§{h.chunk.section or '-'}")
            print(f"    {h.chunk.content[:180]}")
        if not hits:
            print("(no results)")
        return 0
    if args.knowledge_cmd == "stats":
        print(json.dumps(retriever.stats(), indent=2, default=str))
        return 0
    if args.knowledge_cmd == "add":
        from runbookai_tpu.knowledge.chunker import document_from_markdown

        path = Path(args.file)
        doc = document_from_markdown(str(path), path.read_text(),
                                     default_title=path.stem)
        retriever.store.upsert_document(doc)
        if retriever.hybrid.embedder and retriever.hybrid.vectors is not None:
            embs = retriever.hybrid.embedder.embed_texts(
                [c.content for c in doc.chunks])
            retriever.hybrid.vectors.store_many([
                (c.chunk_id, doc.doc_id, embs[i]) for i, c in enumerate(doc.chunks)])
        print(f"added {doc.doc_id}: {doc.title} ({len(doc.chunks)} chunks)")
        return 0
    if args.knowledge_cmd == "validate":
        problems = validate_config(config)
        for p in problems:
            print(f"- {p}")
        print("ok" if not problems else f"{len(problems)} problem(s)")
        return 0 if not problems else 1
    print("unknown knowledge command")
    return 1


def cmd_checkpoint(args) -> int:
    from runbookai_tpu.session.checkpoint import CheckpointStore

    config = _load(args)
    store = CheckpointStore(f"{config.runbook_dir}/checkpoints")
    if args.checkpoint_cmd == "list":
        metas = store.list(args.investigation)
        for m in metas:
            print(f"{m.checkpoint_id}  {m.investigation_id:14} {m.phase:12} {m.label}")
        if not metas:
            print("(no checkpoints)")
        return 0
    if args.checkpoint_cmd == "show":
        data = store.show(args.checkpoint_id)
        if data is None:
            print("not found")
            return 1
        print(json.dumps(data, indent=2, default=str))
        return 0
    if args.checkpoint_cmd == "delete":
        ok = store.delete(args.checkpoint_id)
        print("deleted" if ok else "not found")
        return 0 if ok else 1
    return 1


def _live_eval_report(args, cases, name: str,
                      case_labels: Optional[dict] = None) -> int:
    """Shared run-live-and-report tail for eval and simulate eval.

    ``case_labels`` (case_id -> {label: value}) adds grouped pass rates —
    simulate eval reports per-fault-family and per-adversarial-split
    accuracy with it (VERDICT r4 #4)."""
    from runbookai_tpu.cli.runtime import build_runtime
    from runbookai_tpu.evalsuite.runner import run_live, write_reports

    runtime = build_runtime(_load(args), interactive=False)
    report = asyncio.run(run_live(
        cases, lambda: runtime.llm, name=name,
        concurrency=args.concurrency))
    out = report.to_dict()
    if case_labels:
        out["breakdown"] = _pass_rate_breakdown(report.cases, case_labels)
    summary_path = write_reports([report], args.out)
    out_path = Path(args.out) / f"{name}.json"
    if case_labels and out_path.exists():
        # The per-case file write_reports produced, plus the breakdown.
        out_path.write_text(json.dumps(out, indent=2, default=str))
    print(json.dumps(out | {"summary_path": str(summary_path)},
                     indent=2, default=str))
    return 0 if report.pass_rate >= getattr(args, "min_pass_rate", 0.0) else 1


def _pass_rate_breakdown(case_results: list, case_labels: dict) -> dict:
    """{label_kind: {label_value: {passed, total, pass_rate}}}."""
    out: dict = {}
    for c in case_results:
        labels = case_labels.get(c.get("case_id"), {})
        for kind, value in labels.items():
            bucket = out.setdefault(kind, {}).setdefault(
                str(value), {"passed": 0, "total": 0})
            bucket["total"] += 1
            bucket["passed"] += bool(c.get("passed"))
    for kind in out.values():
        for bucket in kind.values():
            bucket["pass_rate"] = round(
                bucket["passed"] / max(1, bucket["total"]), 4)
    return out


def cmd_eval(args) -> int:
    from runbookai_tpu.evalsuite.runner import (
        load_fixtures_file,
        run_live,
        run_offline,
        write_reports,
    )

    if args.run_all:
        from runbookai_tpu.evalsuite.run_all import parse_shard, run_all_benchmarks

        try:
            shard = (parse_shard(args.shard)
                     if getattr(args, "shard", None) else None)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 1
        runner = None
        if not args.offline:
            from runbookai_tpu.cli.runtime import build_runtime

            runtime = build_runtime(_load(args), interactive=False)
            runner = lambda cases: asyncio.run(run_live(  # noqa: E731
                cases, lambda: runtime.llm, concurrency=args.concurrency))
        aggregate = run_all_benchmarks(
            datasets_root=args.datasets_root, out_dir=args.out,
            runner=runner, min_pass_rate=args.min_pass_rate,
            setup=args.setup_datasets, shard=shard)
        print(json.dumps(aggregate, indent=2, default=str))
        return 0 if aggregate["failed"] == 0 else 1

    cases = load_fixtures_file(args.fixtures)
    if args.offline:
        report = run_offline(cases, name=args.name)
        summary_path = write_reports([report], args.out)
        print(json.dumps(report.to_dict()
                         | {"summary_path": str(summary_path)},
                         indent=2, default=str))
        return 0 if report.pass_rate >= args.min_pass_rate else 1
    return _live_eval_report(args, cases, name=args.name)


def cmd_simulate(args) -> int:
    """Incident simulator: generated fault scenarios against the fixture
    providers (reference scripts/simulate/setup-incidents.sh — here
    credential-free: seeded novel topologies + faults with ground truth)."""
    from runbookai_tpu.simulate import (
        FAULT_TYPES,
        Scenario,
        generate_scenarios,
        to_eval_case,
    )
    from runbookai_tpu.simulate.generator import write_scenarios

    if args.sim_cmd == "faults":
        for name in sorted(FAULT_TYPES):
            print(name)
        return 0

    if getattr(args, "fault", None) and args.fault not in FAULT_TYPES:
        print(f"unknown fault type {args.fault!r}; valid: "
              f"{', '.join(sorted(FAULT_TYPES))}", file=sys.stderr)
        return 1

    models = [m for m in (getattr(args, "models", None) or "").split(",")
              if m] or None

    if args.sim_cmd == "generate":
        scenarios = generate_scenarios(
            args.n, seed=args.seed, fault_type=args.fault,
            adversarial=getattr(args, "adversarial", None), models=models)
        paths = write_scenarios(scenarios, args.out)
        for s, p in zip(scenarios, paths):
            line = f"{s.scenario_id}  {s.truth['fault_type']:22s}  {p}"
            if s.model:
                line += f"  model={s.model}"
            if args.reveal:
                line += f"\n    truth: {s.truth['root_cause']}"
            print(line)
        return 0

    if args.sim_cmd == "investigate":
        from runbookai_tpu.cli.runtime import build_agent, build_runtime

        s = Scenario.from_json(Path(args.scenario).read_text())
        config = _load(args)
        # The scenario only exists in its fixtures: force every provider
        # into simulated mode (a real-cloud config here would query live
        # infrastructure while the CLI claims the generated fault is the
        # answer) and route the fixtures through the standard injection
        # seam (providers.aws.fixtures_path -> SimulatedCloud).
        for block in (config.providers.aws, config.providers.kubernetes,
                      config.observability.datadog,
                      config.observability.prometheus,
                      config.incident.pagerduty,
                      config.providers.github):
            block.enabled = True
            block.simulated = True
        # No simulated gitlab twin: a real client here would query live
        # infra for a synthetic incident.
        config.providers.gitlab.enabled = False
        import tempfile

        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as f:
            json.dump(s.fixtures, f)
            config.providers.aws.fixtures_path = f.name
        try:
            # SimulatedCloud reads the file eagerly inside build_runtime.
            runtime = build_runtime(config, interactive=not args.yes)
        finally:
            Path(f.name).unlink(missing_ok=True)
        agent = build_agent(runtime)

        async def run() -> None:
            async for ev in agent.run(s.query, incident_id=s.scenario_id):
                _print_event(ev)

        asyncio.run(run())
        print(f"\n── ground truth ({s.scenario_id}) ──")
        print(f"  fault:      {s.truth['fault_type']}")
        print(f"  root cause: {s.truth['root_cause']}")
        return 0

    if args.sim_cmd == "eval":
        scenarios = generate_scenarios(
            args.n, seed=args.seed, fault_type=args.fault,
            adversarial=getattr(args, "adversarial", None), models=models)
        cases = [to_eval_case(s) for s in scenarios]
        # Per-family + adversarial-split accuracy (VERDICT r4 #4): the
        # breakdown is what separates reasoning from keyword overlap.
        # Multi-model runs add a per-served-model split next to them.
        labels = {s.scenario_id: {
            "fault_family": s.truth["fault_type"],
            "adversarial": s.truth.get("adversarial", "none"),
            **({"model": s.model} if s.model else {}),
        } for s in scenarios}
        # Deterministic triage baseline: what timeline+topology analysis
        # alone scores (agent/signal_triage.py) — the floor any LLM-led
        # investigation should beat on root-cause service identification.
        from runbookai_tpu.agent.signal_triage import triage_signals

        hits = 0
        for s in scenarios:
            fx = s.fixtures
            rep = triage_signals(
                alarms=fx["cloudwatch_alarms"], logs=fx["cloudwatch_logs"],
                dd_events=fx["datadog"]["events"],
                pods=fx["kubernetes"]["pods"],
                prom_alerts=fx["prometheus"]["alerts"],
                incident=fx["pagerduty"][0] if fx["pagerduty"] else {},
                known_services=[e["service"] for e in fx["aws"]["ecs"]])
            top = rep.candidates[0]["service"] if rep.candidates else None
            hits += top == s.truth["root_cause_service"]
        print(json.dumps({
            "triage_baseline_top1_service_accuracy":
                round(hits / max(1, len(scenarios)), 4),
            "cases": len(scenarios)}), file=sys.stderr)
        return _live_eval_report(args, cases, name="simulated-incidents",
                                 case_labels=labels)

    if args.sim_cmd == "provision":
        # Real-infrastructure mode (reference setup-incidents.sh). The
        # plan — teardown first — is printed BEFORE any execution, so an
        # interrupted apply always has its undo recipe on screen; apply
        # refuses without credentials or with unresolved operator inputs.
        from runbookai_tpu.simulate.provision import apply_plan, provision_plan

        s = Scenario.from_json(Path(args.scenario).read_text())
        plan = provision_plan(s)
        print(plan.render())
        if not args.apply:
            print("dry-run (pass --apply with AWS credentials to execute)")
            return 0
        status = apply_plan(plan)
        print(status)
        return 0 if status.startswith("applied") else 1

    print("unknown simulate subcommand", file=sys.stderr)
    return 1


class ServeConfigError(ValueError):
    """The config file cannot be served; ``str()`` is the operator message."""


def build_server(config_path, host: str = "127.0.0.1", port: int = 8000,
                 allow_runtime_adapters: bool = False):
    """Config file -> a constructed (not yet serving) ``OpenAIServer``.

    THE construction path of ``runbook serve``: load + validate the
    config, build the engine client from ``config.llm``, front it with the
    HTTP server. ``chip_smoke.py`` calls this same function, so what the
    smoke proves on the chip is what the CLI starts. ``server.client`` is
    the ``JaxTpuClient``; ``server.shutdown()`` stops everything built
    here."""
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.server.openai_api import OpenAIServer

    config = load_config(path=config_path)
    if config.llm.provider != "jax-tpu":
        raise ServeConfigError(
            "serve requires llm.provider: jax-tpu (a real engine to serve)")
    problems = [p for p in validate_config(config) if "llm." in p]
    if problems:
        raise ServeConfigError(
            "\n".join(f"config error: {p}" for p in problems))
    client = JaxTpuClient.from_config(config.llm)
    # Multi-model fleets serve under the DEFAULT group's name; the
    # request's model field selects any group (GET /v1/models lists all).
    served_name = (config.llm.models[0].name if config.llm.models
                   else config.llm.model)
    if client.multi_model is not None:
        groups = ", ".join(
            f"{g.name} (dp={g.fleet.dp})"
            for g in client.multi_model.groups.values())
        print(f"multi-model fleet: {groups}", file=sys.stderr)
    # Surface the serving memory plan (engine/memory_plan.py) so operators
    # see what their context/batch choice costs before traffic arrives.
    from runbookai_tpu.models.llama import CONFIGS as _MODEL_CONFIGS

    if not config.llm.models and config.llm.model in _MODEL_CONFIGS:
        from runbookai_tpu.engine.memory_plan import plan_serving

        plan = plan_serving(
            _MODEL_CONFIGS[config.llm.model],
            max_seq_len=min(config.llm.max_seq_len,
                            _MODEL_CONFIGS[config.llm.model].max_seq_len),
            batch=config.llm.max_batch_slots,
            tp=max(1, config.llm.mesh.model),
            weights="int8" if config.llm.dtype == "int8" else "bf16",
            # fp8/int8 pools store 1 byte per value; int8 adds one f32
            # absmax scale per (token, kv head) on top.
            kv_dtype_bytes=(1 if config.llm.kv_cache_dtype
                            in ("fp8", "int8") else 2),
            kv_scale_bytes=(4 if config.llm.kv_cache_dtype == "int8"
                            else 0),
        )
        print(f"memory plan: {plan.explain()}", file=sys.stderr)
    embedder = None
    emb_cfg = config.knowledge.embedder
    # Real weights only: with model_path unset, bge random-inits — serving
    # noise labeled as bge embeddings would silently corrupt any vector
    # index built against the endpoint. (Test configs use bge-test.)
    if emb_cfg.enabled and (emb_cfg.model_path
                            or "test" in emb_cfg.model):
        from runbookai_tpu.knowledge.embedder import Embedder

        embedder = Embedder.from_config(emb_cfg)
    elif emb_cfg.enabled:
        print("note: /v1/embeddings disabled — set knowledge.embedder."
              "model_path to serve real bge embeddings", file=sys.stderr)
    return OpenAIServer(client, model_name=served_name,
                        host=host, port=port,
                        allow_runtime_adapters=allow_runtime_adapters,
                        embedder=embedder)


def cmd_serve(args) -> int:
    """OpenAI-compatible HTTP endpoint over the serving engine."""
    try:
        server = build_server(getattr(args, "config", None), args.host,
                              args.port, args.allow_adapter_loading)
    except ServeConfigError as e:
        print(e, file=sys.stderr)
        return 1
    print(f"serving {server.model_name} at "
          f"http://{args.host}:{server.port}/v1 "
          f"(POST /v1/chat/completions"
          + (", /v1/embeddings" if server.embedder else "")
          + ", GET /v1/models, /healthz, /metrics, /debug/steps)")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
    return 0


def cmd_metrics(args) -> int:
    """Observability snapshot: scrape a running server's ``/metrics``
    (Prometheus text), or summarize a tracer JSONL into per-span latency
    percentiles. The correlation workflow (docs/observability.md): take a
    response's ``x-request-id``, grep the trace JSONL for it, then compare
    that request against the population summarized here."""
    if args.trace:
        from runbookai_tpu.utils.trace import (
            dispatch_counters,
            read_spans,
            summarize_spans,
        )

        try:
            spans = read_spans(args.trace)
        except (OSError, json.JSONDecodeError) as e:
            print(f"could not read trace {args.trace}: {e}", file=sys.stderr)
            return 1
        summary = summarize_spans(spans)
        if args.span:
            summary = {k: v for k, v in summary.items() if args.span in k}
        else:
            # Dispatch-kind counters (PR 4 attribution) recovered from the
            # trace alone — a tune run's measured refinement is
            # sanity-checkable without its Prometheus scrape: zero
            # engine.mixed spans under a mixed-dispatch plan is a lie.
            summary["dispatch_counters"] = dispatch_counters(spans)
            # Queue-wait and router-placement live in EVENT meta (ms=0),
            # so the per-span duration table above drops them; surface
            # them as a lifecycle block alongside the dispatch counters.
            from runbookai_tpu.utils.timeline import lifecycle_summary

            summary["request_lifecycle"] = lifecycle_summary(spans)
        print(json.dumps(summary, indent=2))
        return 0

    import urllib.error
    import urllib.request

    url = args.url.rstrip("/")
    if not url.endswith("/metrics"):
        url += "/metrics"
    try:
        with urllib.request.urlopen(url, timeout=args.timeout) as r:
            text = r.read().decode()
    except (urllib.error.URLError, OSError, TimeoutError) as e:
        print(f"could not scrape {url}: {e}", file=sys.stderr)
        return 1
    if args.grep:
        text = "\n".join(line for line in text.splitlines()
                         if args.grep in line)
    print(text)
    return 0


def _fetch_json(url: str, timeout: float) -> dict:
    """GET ``url`` and parse the JSON body — the one scrape used by the
    live-server subcommands (tenants, workload), so their transport and
    error surfaces cannot drift apart. Raises ``OSError``/``ValueError``
    on unreachable/unparseable; callers pick their fallback."""
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as r:
        body = json.loads(r.read())
    if not isinstance(body, dict):
        raise ValueError(f"{url} returned non-object JSON")
    return body


def _render_tenants(snap: dict) -> str:
    """Table view of a /tenants snapshot (or of configured policies)."""
    if not snap.get("enabled"):
        return "tenant admission control is disabled (llm.tenants)"
    cols = ("tenant", "class", "rpm", "tok/min", "admitted", "throttled",
            "budget left")
    rows = []
    for name, row in sorted(snap.get("tenants", {}).items()):
        throttled = (row.get("throttled_rate", 0)
                     + row.get("throttled_tokens", 0))
        rows.append((
            name, str(row.get("priority", "-")),
            str(row.get("rate_limit_rpm") or "-"),
            str(row.get("token_budget_per_min") or "-"),
            str(row.get("admitted", 0)), str(throttled),
            str(row.get("budget_remaining_tokens", "-"))))
    widths = [max(len(c), *(len(r[i]) for r in rows)) if rows else len(c)
              for i, c in enumerate(cols)]
    out = ["  ".join(c.ljust(w) for c, w in zip(cols, widths))]
    for r in rows:
        out.append("  ".join(v.ljust(w) for v, w in zip(r, widths)))
    return "\n".join(out)


def cmd_tenants(args) -> int:
    """``runbook tenants`` — live tenant-accounting state. Prefers a
    running server's ``GET /tenants`` (live bucket levels + counters);
    with no server reachable, falls back to rendering the CONFIGURED
    ``llm.tenants`` policies so the command is useful pre-deploy too."""
    url = args.url.rstrip("/") + "/tenants"
    snap = None
    try:
        snap = _fetch_json(url, args.timeout)
        source = url
    except (OSError, TimeoutError, ValueError):
        config = _load(args)
        tcfg = config.llm.tenants
        source = "config (no server at %s)" % args.url
        snap = {"enabled": tcfg.enabled, "tenants": {}}
        if tcfg.enabled:
            blocks = dict(tcfg.keys)
            blocks["default"] = tcfg.default
            for name, block in blocks.items():
                snap["tenants"][name] = {
                    "priority": block.priority,
                    "rate_limit_rpm": block.rate_limit_rpm,
                    "token_budget_per_min": block.token_budget_per_min,
                    "admitted": 0, "throttled_rate": 0,
                    "throttled_tokens": 0,
                }
    if args.json:
        print(json.dumps(snap, indent=2))
    else:
        print(f"# {source}")
        print(_render_tenants(snap))
    return 0


def _chaos_blocks(health: dict) -> dict:
    """Extract {scope: {supervisor, chaos}} from a /healthz body —
    top-level for a single fleet, per served model group otherwise."""
    blocks: dict[str, dict] = {}
    if "supervisor" in health or "chaos" in health:
        blocks["(fleet)"] = {"supervisor": health.get("supervisor"),
                             "chaos": health.get("chaos")}
    for name, g in (health.get("models") or {}).items():
        if isinstance(g, dict) and ("supervisor" in g or "chaos" in g):
            blocks[name] = {"supervisor": g.get("supervisor"),
                            "chaos": g.get("chaos")}
    return blocks


def _render_chaos(blocks: dict) -> str:
    out: list[str] = []
    for scope, b in blocks.items():
        sup = b.get("supervisor")
        out.append(f"## {scope}")
        if sup:
            out.append(f"supervision: wedge_timeout={sup['wedge_timeout_s']}s "
                       f"rebuilds={sup['rebuilds_total']} "
                       f"failovers={sup['failovers_total']}")
            for r in sup["replicas"]:
                line = (f"  r{r['replica']}: {r['state']}"
                        f" (rebuilds={r['rebuilds']})")
                if r.get("reason"):
                    line += f" — {r['reason']}"
                out.append(line)
            tail = sup["transitions"][-8:]
            if tail:
                out.append("  recent transitions:")
                out.extend(f"    r{t['replica']}: {t['from']} -> "
                           f"{t['to']} ({t['reason']})" for t in tail)
        else:
            out.append("supervision: not attached "
                       "(llm.fleet.supervisor.enabled)")
        chaos = b.get("chaos")
        if chaos:
            out.append(f"chaos: seed={chaos['seed']} applied="
                       f"{chaos['events_applied']}/"
                       f"{chaos['events_planned']} "
                       f"active={chaos['active'] or '-'}")
            for w in chaos["windows"][-8:]:
                tgt = (f" r{w['replica']}"
                       if w.get("replica") is not None else "")
                out.append(f"  {w['kind']}{tgt} at {w['applied_at_s']}s "
                           f"for {w['duration_s']}s [{w['status']}]")
        else:
            out.append("chaos: no injector attached")
    return "\n".join(out)


def cmd_chaos(args) -> int:
    """``runbook chaos status`` — replica supervision + fault-injection
    state from a running server's ``/healthz`` (the ``supervisor`` and
    ``chaos`` blocks each fleet's health snapshot carries when a
    FleetSupervisor / ChaosInjector is attached)."""
    url = args.url.rstrip("/") + "/healthz"
    try:
        health = _fetch_json(url, args.timeout)
    except (OSError, TimeoutError, ValueError) as e:
        print(f"no server reachable at {args.url} ({e})")
        return 1
    blocks = _chaos_blocks(health)
    if args.json:
        print(json.dumps(blocks, indent=2))
        return 0
    print(f"# {url}")
    if not blocks:
        print("no supervisor or chaos injector attached "
              "(single engine, or llm.fleet.supervisor disabled)")
        return 0
    print(_render_chaos(blocks))
    return 0


def _incident_feed(args) -> tuple[list[dict], str | None, str]:
    """Incident docs + bundle-dir for ``runbook incident``: a running
    server's ``GET /debug/incidents`` when reachable, else the incident
    headers read straight off the on-disk bundle directory (``--dir`` /
    ``llm.obs.incident_dir``) — a dead server's black box is exactly
    when this command matters most."""
    from runbookai_tpu.obs.incident import list_bundles, load_bundle

    url = args.url.rstrip("/") + "/debug/incidents"
    try:
        snap = _fetch_json(url, args.timeout)
    except (OSError, TimeoutError, ValueError):
        snap = None
    if snap is not None and snap.get("enabled"):
        incidents = list(snap.get("open", [])) + list(snap.get("recent", []))
        return incidents, snap.get("bundle_dir"), url
    directory = args.dir
    if directory is None:
        config = _load(args)
        directory = config.llm.obs.incident_dir
    if not directory:
        source = ("incident detection is disabled on this server"
                  if snap is not None else f"no server at {args.url}")
        return [], None, source + " and no bundle dir configured (--dir)"
    incidents = []
    for path in list_bundles(directory):
        try:
            incidents.append(load_bundle(path).get("incident") or {})
        except (OSError, json.JSONDecodeError):
            continue
    return incidents, str(directory), f"bundles in {directory}"


def _render_incidents(incidents: list[dict]) -> str:
    if not incidents:
        return "no incidents"
    cols = ("id", "signal", "severity", "status", "opened", "duration",
            "peak", "bundle")
    rows = []
    for inc in sorted(incidents, key=lambda i: i.get("id", "")):
        dur = inc.get("duration_s")
        rows.append((
            str(inc.get("id", "?")), str(inc.get("signal", "?")),
            str(inc.get("severity", "?")), str(inc.get("status", "?")),
            str(inc.get("opened_ts", "?")),
            "-" if dur is None else f"{dur:.1f}s",
            str(inc.get("peak", "-")), inc.get("bundle") or "-"))
    widths = [max(len(c), *(len(r[i]) for r in rows))
              for i, c in enumerate(cols)]
    out = ["  ".join(c.ljust(w) for c, w in zip(cols, widths))]
    out += ["  ".join(v.ljust(w) for v, w in zip(r, widths)) for r in rows]
    return "\n".join(out)


def cmd_incident(args) -> int:
    """``runbook incident list|show [--bundle]`` — the fleet's incident
    feed (obs/incident.py): detected incidents with their lifecycle
    state, and the captured black-box bundles. ``show <id> --bundle``
    loads the incident's bundle, VERIFIES its content hash, and prints
    the evidence inventory — a bundle that fails verification is not
    evidence."""
    incidents, bundle_dir, source = _incident_feed(args)
    if args.incident_cmd == "list":
        if args.json:
            print(json.dumps(incidents, indent=2))
        else:
            print(f"# {source}")
            print(_render_incidents(incidents))
        return 0
    # show <id>
    inc = next((i for i in incidents if i.get("id") == args.id), None)
    if inc is None:
        print(f"no incident {args.id!r} ({source}); known: "
              f"{sorted(i.get('id', '?') for i in incidents)}",
              file=sys.stderr)
        return 1
    if not args.bundle:
        print(json.dumps(inc, indent=2, sort_keys=True))
        return 0
    from runbookai_tpu.obs.incident import (
        bundle_hash,
        list_bundles,
        load_bundle,
    )

    if not bundle_dir:
        print("no bundle directory (server has no llm.obs.incident_dir; "
              "pass --dir)", file=sys.stderr)
        return 1
    # Bundle names are <captured-ms>-<id>-<signal>.json; ids restart
    # per process, so prefer the NEWEST match for this id.
    matches = [p for p in list_bundles(bundle_dir)
               if f"-{args.id}-" in p.name]
    if inc.get("bundle"):
        matches = [p for p in matches if p.name == inc["bundle"]] or matches
    if not matches:
        print(f"no bundle for {args.id!r} in {bundle_dir}",
              file=sys.stderr)
        return 1
    path = matches[-1]
    # One load serves the hash check AND the rendering below.
    doc = load_bundle(path)
    expected = str(doc.get("content_hash", ""))
    actual = bundle_hash(doc)
    ok = expected == actual
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 0 if ok else 1
    evidence = doc.get("evidence", {})
    print(f"# {path}")
    print(f"schema_version: {doc.get('schema_version')}")
    print(f"content_hash: {expected} "
          f"[{'verified' if ok else 'MISMATCH — got ' + actual}]")
    print(f"captured_ts: {doc.get('captured_ts')}")
    print("incident:")
    print(json.dumps(doc.get("incident"), indent=2, sort_keys=True))
    print("evidence:")
    for key in sorted(evidence):
        val = evidence[key]
        size = (len(val) if isinstance(val, (list, str))
                else len(json.dumps(val)))
        unit = ("records" if isinstance(val, list)
                else "bytes" if isinstance(val, str) else "json bytes")
        print(f"  {key}: {size} {unit}")
    history = doc.get("history")
    if history is not None:
        # Pre-open lookback from the embedded tsdb (obs/tsdb.py): what
        # each detector input signal was doing BEFORE this opened.
        print(f"history (lookback {history.get('lookback_s')}s, "
              f"schema v{history.get('schema_version')}):")
        signals = history.get("signals") or {}
        if not signals:
            print("  (no signal samples in the lookback window)")
        for signal in sorted(signals):
            points = signals[signal]
            values = [p[1] for p in points]
            print(f"  {signal:16s} {_spark(values)}  "
                  f"{values[0]:.4g} -> {values[-1]:.4g}  "
                  f"({len(values)} samples)")
    return 0 if ok else 1


_SPARK_BLOCKS = "▁▂▃▄▅▆▇█"


def _spark(values: list, width: int = 40) -> str:
    """Unicode sparkline of a signal's lookback trend, downsampled to
    ``width`` evenly spaced points. Flat series render mid-block."""
    if not values:
        return ""
    if len(values) > width:
        step = len(values) / width
        values = [values[int(i * step)] for i in range(width)]
    lo, hi = min(values), max(values)
    span = hi - lo
    if span <= 0:
        return _SPARK_BLOCKS[3] * len(values)
    return "".join(
        _SPARK_BLOCKS[min(len(_SPARK_BLOCKS) - 1,
                          int((v - lo) / span * (len(_SPARK_BLOCKS) - 1)))]
        for v in values)


def _render_workload(snap: dict) -> str:
    """Table view of a /debug/workload snapshot."""
    if not snap.get("enabled"):
        return "workload fingerprinting is disabled (llm.obs.enabled)"
    cols = ("model", "reqs", "prompt p50", "out p50", "conc", "guided",
            "spec", "prefix$", "drift", "stale", "reference")
    rows = []
    entries = dict(snap.get("models", {}))
    merged = snap.get("merged")
    if merged is not None and len(entries) > 1:
        entries["(fleet)"] = {"fingerprint": merged,
                              "drift_score": snap.get("drift_score"),
                              "plan_stale": snap.get("plan_stale"),
                              "reference_source": "worst group"}
    for name, m in entries.items():
        fp = m.get("fingerprint")
        if fp is None:
            rows.append((name, "0", "-", "-", "-", "-", "-", "-", "-",
                         "-", m.get("reference_source", "-")))
            continue
        wl = fp["workload"]
        drift = m.get("drift_score")
        stale = m.get("plan_stale")
        rows.append((
            name, str(fp["window"]["samples"]),
            str(wl["prompt_len"]), str(wl["output_len"]),
            str(wl["concurrency"]), f"{wl['guided_share']:.2f}",
            f"{wl['spec_hit_rate']:.2f}",
            f"{fp['prefix_cache_share']:.2f}",
            "-" if drift is None else f"{drift:.3f}",
            "-" if stale is None else ("STALE" if stale else "ok"),
            m.get("reference_source", "-")))
    widths = [max(len(c), *(len(r[i]) for r in rows)) if rows else len(c)
              for i, c in enumerate(cols)]
    out = ["  ".join(c.ljust(w) for c, w in zip(cols, widths))]
    for r in rows:
        out.append("  ".join(v.ljust(w) for v, w in zip(r, widths)))
    out.append(f"drift threshold: {snap.get('drift_threshold')}")
    return "\n".join(out)


def cmd_workload(args) -> int:
    """``runbook workload`` — live traffic fingerprints + plan drift
    from a running server's ``GET /debug/workload``
    (``runbookai_tpu/obs``). ``--watch`` re-renders every ``--interval``
    seconds; ``--emit-descriptor out.json`` writes the live tuner
    descriptor — JSON that feeds ``runbook tune --workload out.json``
    unchanged (the ROADMAP item 3 hand-off)."""
    import time as _time

    url = args.url.rstrip("/") + "/debug/workload"

    def scrape() -> dict | None:
        try:
            return _fetch_json(url, args.timeout)
        except (OSError, TimeoutError, ValueError) as e:
            print(f"could not scrape {url}: {e}", file=sys.stderr)
            return None

    snap = scrape()
    if snap is None:
        return 1
    if args.emit_descriptor:
        from runbookai_tpu.autotune.cost_model import Workload
        from runbookai_tpu.obs import descriptor_json

        if not snap.get("enabled"):
            print("workload fingerprinting is disabled on this server "
                  "(llm.obs.enabled) — nothing to emit", file=sys.stderr)
            return 1
        models = snap.get("models", {})
        if args.model:
            entry = models.get(args.model)
            if entry is None:
                print(f"model {args.model!r} not served; served: "
                      f"{sorted(models)}", file=sys.stderr)
                return 1
            fp = entry.get("fingerprint")
        else:
            # One served model -> its fingerprint; several -> the merged
            # fleet-wide one (name a group with --model to split them).
            only = (next(iter(models.values()))["fingerprint"]
                    if len(models) == 1 else None)
            fp = only if only is not None else snap.get("merged")
        if fp is None:
            print("fingerprint window is empty (no completed requests "
                  "yet) — nothing to emit", file=sys.stderr)
            return 1
        payload = descriptor_json(fp)
        # Round-trip gate BEFORE writing: the emitted bytes must parse
        # back into the tuner's own schema, or the hand-off is broken.
        Workload.from_dict(json.loads(payload))
        out = Path(args.emit_descriptor)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(payload)
        print(f"wrote {out} (feed it to `runbook tune --workload {out}`)")
        return 0
    while True:
        if args.json:
            print(json.dumps(snap, indent=2))
        else:
            print(f"# {url}")
            print(_render_workload(snap))
        if not args.watch:
            return 0
        try:
            _time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0
        snap = scrape()
        if snap is None:
            return 1


def _render_query_result(doc: dict) -> str:
    """Table view of a /debug/query result: one row per series,
    canonical selector -> value. An empty result prints as absence —
    the store never materializes zeros for missing series."""
    rows = []
    for entry in doc.get("result", []):
        labels = dict(entry.get("metric", {}))
        name = labels.pop("__name__", "")
        body = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
        sel = f"{name}{{{body}}}" if body else (name or "{}")
        rows.append((sel, entry.get("value")))
    if not rows:
        return "(empty result — absent series stay absent, never zero)"
    width = max(len(sel) for sel, _ in rows)
    return "\n".join(f"{sel.ljust(width)}  {value}" for sel, value in rows)


def cmd_query(args) -> int:
    """``runbook query EXPR [--range 5m] [--watch]`` — PromQL-lite over
    a running server's embedded metric history (``GET /debug/query``;
    obs/tsdb.py + obs/query.py). The grammar and the mapping to real
    Prometheus are in docs/observability.md "Metric history & query"."""
    import time as _time
    import urllib.parse

    qs = urllib.parse.urlencode({"expr": args.expr, "range": args.range})
    url = f"{args.url.rstrip('/')}/debug/query?{qs}"

    def scrape() -> dict | None:
        import urllib.error

        try:
            return _fetch_json(url, args.timeout)
        except urllib.error.HTTPError as e:
            # A 400 carries the evaluator's parse error — surface it
            # instead of a bare HTTP status.
            try:
                detail = json.loads(e.read()).get("error", {}).get(
                    "message", "")
            except (ValueError, OSError):
                detail = ""
            print(f"query rejected ({e.code}): {detail or e.reason}",
                  file=sys.stderr)
            return None
        except (OSError, TimeoutError, ValueError) as e:
            print(f"could not scrape {url}: {e}", file=sys.stderr)
            return None

    while True:
        doc = scrape()
        if doc is None:
            return 1
        if not doc.get("enabled", True):
            print("metric history is disabled (llm.obs.tsdb.enabled)",
                  file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(doc, indent=2, sort_keys=True))
        else:
            print(f"# {args.expr}  (range {args.range}, "
                  f"now {doc.get('now')})")
            print(_render_query_result(doc))
        if not args.watch:
            return 0
        try:
            _time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


def cmd_timeline(args) -> int:
    """``runbook timeline <request-id> --trace <file>`` — stitch one
    request's trace JSONL records (enqueue → router placement → admit →
    prefill chunks → decode windows → finish/abort) into a span tree.
    The id may be the caller's ``x-request-id`` or an engine-internal
    ``r{i}-…`` id; a fleet request shows every replica it touched."""
    from runbookai_tpu.utils.timeline import build_timeline, render_timeline
    from runbookai_tpu.utils.trace import read_spans

    try:
        spans = read_spans(args.trace)
    except (OSError, json.JSONDecodeError) as e:
        print(f"could not read trace {args.trace}: {e}", file=sys.stderr)
        return 1
    tl = build_timeline(spans, args.request_id)
    if tl is None:
        print(f"no records for request {args.request_id!r} in {args.trace}",
              file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(tl, indent=2))
    else:
        print(render_timeline(tl, max_events=args.max_events))
    return 0


def cmd_profile(args) -> int:
    """``runbook profile`` — on-demand XLA/XProf capture around N engine
    steps of synthetic load on the CONFIGURED engine, written as a
    TensorBoard-readable trace directory (``tensorboard --logdir DIR``,
    or upload to xprof). Probe-gated: an environment without a working
    ``jax.profiler`` capture path reports the skip and exits cleanly."""
    import numpy as _np

    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.utils.trace import try_device_trace

    config = _load(args)
    if config.llm.provider != "jax-tpu":
        print("profile requires llm.provider: jax-tpu (a real engine to "
              "profile)", file=sys.stderr)
        return 1
    client = JaxTpuClient.from_config(config.llm)
    core = client.core  # replica 0 when fleeted: one engine's device view
    rng = _np.random.default_rng(0)

    def _submit(n: int, max_new: int) -> None:
        for _ in range(n):
            core.submit(EngineRequest(
                prompt_ids=rng.integers(
                    1, min(256, core.cfg.vocab_size - 1),
                    size=args.prompt_len).tolist(),
                sampling=SamplingParams(temperature=0.0,
                                        max_new_tokens=max_new,
                                        stop_token_ids=())))

    # Warmup outside the capture: compile time would drown the N measured
    # steps and the trace would profile Mosaic/XLA, not serving.
    _submit(min(2, max(1, args.concurrency)), 4)
    core.run_until_idle()

    _submit(args.concurrency, args.new_tokens)
    steps = 0
    with try_device_trace(args.out) as captured:
        while core.has_work and steps < args.steps:
            core.step()
            steps += 1
    while core.has_work:  # settle outside the capture
        core.step()
    if captured:
        print(f"captured {steps} engine steps -> {args.out} "
              f"(view: tensorboard --logdir {args.out})")
        return 0
    print(f"profile skipped: jax.profiler capture unavailable on this "
          f"backend (ran {steps} steps uncaptured)", file=sys.stderr)
    return 0


def cmd_tune(args) -> int:
    """``runbook tune`` — serving-plan autotuner sweep (docs/autotune.md):
    analytic cost-model prune over the engine knob space, measured
    refinement of the survivors (baseline always competes, so the emitted
    plan can never regress the hand-picked defaults), versioned plan
    artifact out."""
    import os

    if args.smoke and not os.environ.get("JAX_PLATFORMS"):
        # --smoke asks for the CPU by name: a bounded sweep of the tiny
        # model, wherever it is run.
        os.environ["JAX_PLATFORMS"] = "cpu"

    from runbookai_tpu.autotune.cost_model import (
        HARDWARE,
        Candidate,
        SearchSpace,
        Workload,
        smoke_space,
    )
    from runbookai_tpu.autotune.search import tune

    # ONE config read serves both defaults (model, out) — or none at all
    # when the flags pin everything.
    config = _load(args) if args.out is None or (
        args.model is None and not args.smoke) else None
    # --workload FILE: a live descriptor emitted by `runbook workload
    # --emit-descriptor` (or any Workload.to_dict JSON) replaces the
    # per-field flags — the obs/ -> autotune hand-off.
    file_workload = None
    if getattr(args, "workload", None):
        try:
            file_workload = Workload.from_dict(
                json.loads(Path(args.workload).read_text()))
        except (OSError, ValueError) as e:
            print(f"could not read workload descriptor "
                  f"{args.workload}: {e}", file=sys.stderr)
            return 1
    if args.smoke:
        model = args.model or "llama3-test"
        space = smoke_space()
        src = file_workload or Workload(
            prompt_len=args.prompt_len, output_len=args.output_len,
            concurrency=args.concurrency,
            guided_share=getattr(args, "guided_share", 0.0),
            spec_hit_rate=getattr(args, "spec_hit_rate", 0.0))
        # The smoke path bounds the sweep to the tiny CPU model's
        # envelope whatever the descriptor says — a live long-context
        # fingerprint must still smoke in seconds.
        workload = Workload(prompt_len=min(src.prompt_len, 48),
                            output_len=min(src.output_len, 16),
                            concurrency=min(src.concurrency, 4),
                            guided_share=src.guided_share,
                            spec_hit_rate=src.spec_hit_rate)
        baseline = Candidate(page_size=4, num_pages=256,
                             max_batch_slots=4, prefill_chunk=32,
                             kv_dtype="auto", max_seq_len=256)
        hw, weights = HARDWARE["cpu"], "bf16"
    else:
        model = args.model or config.llm.model
        workload = file_workload or Workload(
            prompt_len=args.prompt_len, output_len=args.output_len,
            concurrency=args.concurrency, guided_share=args.guided_share,
            spec_hit_rate=args.spec_hit_rate)
        axes = {}
        if args.dp:
            axes["dp_replicas"] = tuple(
                int(v) for v in args.dp.split(","))
        if args.tp:
            axes["tp"] = tuple(int(v) for v in args.tp.split(","))
        space = SearchSpace(**axes)
        baseline = None
        hw_name = args.hw
        if hw_name == "auto":
            import jax

            from runbookai_tpu.autotune.cost_model import hardware_for

            try:
                hw_name = hardware_for(jax.devices()[0]).name
            except KeyError as e:
                print(e.args[0], file=sys.stderr)
                return 1
        hw, weights = HARDWARE[hw_name], args.weights
    out = args.out or str(
        Path(config.runbook_dir) / "plans" / f"{model}.{hw.name}.json")
    try:
        result = tune(
            model, workload, hw, space, weights=weights, top_k=args.top_k,
            measure=not args.no_measure, baseline=baseline,
            n_requests=args.requests, new_tokens=args.new_tokens,
            budget_s=args.budget_s, out=out, log=print)
    except ValueError as e:
        # e.g. an all-infeasible sweep — no plan artifact is written.
        print(str(e), file=sys.stderr)
        return 1
    plan = result.plan
    print(json.dumps({
        "plan_id": plan.plan_id, "out": str(out),
        "engine": plan.engine,
        "cost_model": plan.provenance.get("cost_model"),
        "measured": plan.provenance.get("measured"),
    }, indent=2))
    return 0


def cmd_plan(args) -> int:
    """``runbook plan show|validate`` — inspect / gate plan artifacts."""
    from runbookai_tpu.autotune.plan import load_plan, validate_plan

    if args.plan_cmd == "show":
        try:
            plan = load_plan(args.path)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 1
        print(json.dumps(plan.to_dict(), indent=2))
        return 0
    if args.plan_cmd == "validate":
        failures = 0
        for path in args.paths:
            try:
                data = json.loads(Path(path).read_text())
            except (OSError, json.JSONDecodeError) as e:
                print(f"{path}: unreadable ({e})")
                failures += 1
                continue
            problems = validate_plan(data)
            if problems:
                failures += 1
                print(f"{path}: INVALID")
                for p in problems:
                    print(f"  - {p}")
            else:
                print(f"{path}: ok ({data['plan_id']})")
        return 0 if failures == 0 else 1
    return 1


def cmd_weights(args) -> int:
    from runbookai_tpu.models.checkpoint import (
        checkpoint_config,
        convert_hf_to_checkpoint,
        is_checkpoint,
    )

    if args.weights_cmd == "convert":
        try:
            out = convert_hf_to_checkpoint(
                args.model_path, args.out, model_name=args.name,
                quantize_int8=args.int8, allow_random_init=args.random_init,
            )
        except FileNotFoundError as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 1
        print(f"wrote checkpoint: {out} (int8={args.int8})")
        return 0
    if not is_checkpoint(args.path):
        print(f"not a checkpoint: {args.path}")
        return 1
    cfg = checkpoint_config(args.path)
    print(json.dumps(cfg.__dict__, indent=2))
    return 0


def cmd_lint(args) -> int:
    """``runbook lint`` — the static-analysis gate (docs/lint.md).

    Exit 0 when the tree has no findings beyond the committed baseline,
    non-zero otherwise; ``--update-baseline`` regenerates
    lint-baseline.json. Dependency-free (never imports jax), so it runs
    first and fastest in CI.
    """
    from runbookai_tpu.analysis.cli import run_lint

    return run_lint(args)


def cmd_mcp(args) -> int:
    from runbookai_tpu.server.mcp import MCPServer, run_stdio_server

    config = _load(args)
    server = MCPServer.from_config(config)
    if args.mcp_cmd == "tools":
        for tool in server.list_tools():
            print(f"{tool['name']}: {tool['description']}")
        return 0
    run_stdio_server(server)
    return 0


def cmd_webhook(args) -> int:
    from runbookai_tpu.server.webhook import run_webhook_server

    config = _load(args)
    run_webhook_server(config, port=args.port)
    return 0


def cmd_slack_gateway(args) -> int:
    from runbookai_tpu.server.slack_gateway import run_slack_gateway

    config = _load(args)
    run_slack_gateway(config, mode=args.mode or config.incident.slack.mode,
                      port=args.port)
    return 0


# --------------------------------------------------------------------------- #
# parser                                                                      #
# --------------------------------------------------------------------------- #


def cmd_integrations(args) -> int:
    from runbookai_tpu.integrations.claude_hooks import (
        hooks_status,
        install_hooks,
        uninstall_hooks,
    )

    settings = Path(args.settings).expanduser()
    if args.integrations_cmd == "enable":
        install_hooks(settings)
        print(f"hooks installed into {settings}")
        return 0
    if args.integrations_cmd == "status":
        status = hooks_status(settings)
        for event, on in status.items():
            print(f"{event:18} {'enabled' if on else '-'}")
        return 0
    if args.integrations_cmd == "disable":
        removed = uninstall_hooks(settings)
        print("hooks removed" if removed else "no hooks found")
        return 0
    if args.integrations_cmd == "learn":
        # reference `runbook integrations claude learn` (cli.tsx:1667+)
        from runbookai_tpu.cli.runtime import build_runtime
        from runbookai_tpu.integrations.session_store import create_session_store
        from runbookai_tpu.learning.claude_session import run_learning_from_session

        config = _load(args)
        store = create_session_store(config)
        session_ids = [args.session_id] if args.session_id else store.list_sessions()
        if not session_ids:
            print("no captured sessions")
            return 1
        runtime = build_runtime(config, interactive=False)
        for sid in session_ids:
            out = asyncio.run(run_learning_from_session(
                runtime.llm, sid, store=store,
                out_dir=f"{config.runbook_dir}/learning"))
            print(f"{sid}: artifacts in {out}")
        return 0
    return 1


def cmd_hook(args) -> int:
    """Hidden hook entrypoint (reference cli.tsx:1667-1889 `runbook hook`)."""
    from runbookai_tpu.integrations.claude_hooks import HookHandlers, run_hook_stdin
    from runbookai_tpu.integrations.session_store import create_session_store

    config = _load(args)
    retriever = None
    if Path(config.knowledge.db_path).is_file():
        from runbookai_tpu.knowledge.retriever import create_retriever

        retriever = create_retriever(config)
    handlers = HookHandlers(retriever=retriever,
                            session_store=create_session_store(config))
    return run_hook_stdin(args.event, handlers)


def cmd_operability(args) -> int:
    config = _load(args)
    from runbookai_tpu.integrations.operability_ingestion import IngestionClient
    from runbookai_tpu.integrations.session_store import create_session_store
    from runbookai_tpu.providers.operability import create_adapter

    adapter = create_adapter(config)
    client = IngestionClient(adapter,
                             spool_dir=f"{config.runbook_dir}/operability-spool")
    if args.operability_cmd == "status":
        print(json.dumps(client.status(), indent=2))
        return 0
    if args.operability_cmd == "replay":
        print(json.dumps(asyncio.run(client.replay()), indent=2))
        return 0
    if args.operability_cmd == "ingest":
        store = create_session_store(config)
        events = []
        for session_id in store.list_sessions():
            events.extend(store.read(session_id))
        print(json.dumps(asyncio.run(client.ingest(events)), indent=2))
        return 0
    return 1


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="runbook",
        description="TPU-native AI SRE agent: incident investigation served by "
                    "an in-tree JAX inference engine.",
    )
    p.add_argument("--config", help="explicit config.yaml path")
    sub = p.add_subparsers(dest="cmd", required=True)

    ask = sub.add_parser("ask", help="one-shot question through the agent loop")
    ask.add_argument("query")
    ask.add_argument("--session", default=None)
    ask.add_argument("--yes", action="store_true", help="non-interactive approvals")
    ask.set_defaults(fn=cmd_ask)

    chat = sub.add_parser("chat", help="interactive conversation")
    chat.add_argument("--raw", action="store_true",
                      help="direct streaming model chat (no agent loop)")
    chat.set_defaults(fn=cmd_chat)

    dep = sub.add_parser("deploy", help="deploy a service via the deploy-service skill")
    dep.add_argument("service")
    dep.add_argument("-e", "--environment", default="production")
    dep.add_argument("--version", default=None)
    dep.add_argument("--dry-run", action="store_true")
    dep.add_argument("--yes", action="store_true",
                     help="non-interactive: no CLI prompts; mutations are "
                          "approved via Slack buttons when configured, "
                          "denied otherwise")
    dep.set_defaults(fn=cmd_deploy)

    inv = sub.add_parser("investigate", help="structured incident investigation")
    inv.add_argument("incident_id")
    inv.add_argument("--description", default="")
    inv.add_argument("--execute", action="store_true",
                     help="execute the remediation plan (approval-gated)")
    inv.add_argument("--apply-learnings", action="store_true",
                     help="apply runbook updates to the local library "
                          "instead of writing proposals")
    inv.add_argument("--learn", action="store_true",
                     help="run the learning loop afterwards")
    inv.add_argument("--yes", action="store_true")
    inv.set_defaults(fn=cmd_investigate)

    demo = sub.add_parser("demo", help="scripted demo investigation (no model)")
    demo.add_argument("--fast", action="store_true", help="3x speed")
    demo.set_defaults(fn=cmd_demo)

    status = sub.add_parser("status", help="config + provider status")
    status.set_defaults(fn=cmd_status)

    init = sub.add_parser("init", help="write a starter config")
    init.add_argument("--template", choices=["minimal", "simulated", "tpu"],
                      default="simulated")
    init.add_argument("--dir", default=".")
    init.add_argument("--force", action="store_true")
    init.add_argument("--interactive", "-i", action="store_true",
                      help="guided setup wizard (hydrates an existing config)")
    init.set_defaults(fn=cmd_init)

    cfg = sub.add_parser("config", help="show or set config values")
    cfg.add_argument("--set", action="append", metavar="a.b.c=value")
    cfg.add_argument("--show", action="store_true")
    cfg.set_defaults(fn=cmd_config)

    kn = sub.add_parser("knowledge", help="knowledge base management")
    kn_sub = kn.add_subparsers(dest="knowledge_cmd", required=True)
    kn_sync = kn_sub.add_parser("sync")
    kn_sync.add_argument("--force", action="store_true")
    kn_search = kn_sub.add_parser("search")
    kn_search.add_argument("query")
    kn_search.add_argument("--type", default=None)
    kn_search.add_argument("--service", default=None)
    kn_search.add_argument("--limit", type=int, default=8)
    kn_sub.add_parser("stats")
    kn_add = kn_sub.add_parser("add")
    kn_add.add_argument("file")
    kn_sub.add_parser("validate")
    kn_auth = kn_sub.add_parser("auth")
    kn_auth.add_argument("provider", choices=["google"])
    kn.set_defaults(fn=cmd_knowledge)

    cp = sub.add_parser("checkpoint", help="investigation checkpoints")
    cp_sub = cp.add_subparsers(dest="checkpoint_cmd", required=True)
    cp_list = cp_sub.add_parser("list")
    cp_list.add_argument("--investigation", default=None)
    cp_show = cp_sub.add_parser("show")
    cp_show.add_argument("checkpoint_id")
    cp_del = cp_sub.add_parser("delete")
    cp_del.add_argument("checkpoint_id")
    cp.set_defaults(fn=cmd_checkpoint)

    sim = sub.add_parser("simulate",
                         help="generated fault scenarios (incident simulator)")
    sim_sub = sim.add_subparsers(dest="sim_cmd", required=True)
    sim_gen = sim_sub.add_parser("generate", help="write N scenario files")
    sim_gen.add_argument("--n", type=int, default=5)
    sim_gen.add_argument("--seed", type=int, default=0)
    sim_gen.add_argument("--fault", default=None,
                         help="pin a fault type (see: simulate faults)")
    sim_gen.add_argument("--out", default=".runbook/simulate")
    sim_gen.add_argument("--reveal", action="store_true",
                         help="print ground truth with each scenario")
    sim_gen.add_argument(
        "--adversarial", default=None,
        choices=["misleading_symptom", "two_fault", "signal_dropout", "mix"],
        help="harden scenarios: stale red-herring signals on a non-culprit "
             "service, a concurrent second fault, or a dropped telemetry "
             "modality")
    sim_gen.add_argument(
        "--models", default=None, metavar="A,B",
        help="assign served model groups round-robin (multi-model "
             "fleets, llm.models) so eval load exercises model routing")
    sim_sub.add_parser("faults", help="list fault types")
    sim_inv = sim_sub.add_parser("investigate",
                                 help="run the agent against a scenario")
    sim_inv.add_argument("--scenario", required=True)
    sim_inv.add_argument("--yes", action="store_true")
    sim_eval = sim_sub.add_parser("eval",
                                  help="run + score N generated scenarios")
    sim_eval.add_argument("--n", type=int, default=5)
    sim_eval.add_argument("--seed", type=int, default=0)
    sim_eval.add_argument("--fault", default=None)
    sim_eval.add_argument("--concurrency", type=int, default=4)
    sim_eval.add_argument("--min-pass-rate", type=float, default=0.0)
    sim_eval.add_argument("--out", default=".runbook/eval-reports")
    sim_eval.add_argument(
        "--adversarial", default=None,
        choices=["misleading_symptom", "two_fault", "signal_dropout", "mix"],
        help="run the hardened split (reported separately in breakdown)")
    sim_eval.add_argument(
        "--models", default=None, metavar="A,B",
        help="round-robin cases across served model groups (llm.models); "
             "per-model pass rates land in the breakdown and "
             "summary.json gains model_attribution")
    sim_prov = sim_sub.add_parser(
        "provision",
        help="real-infra mode: map a scenario onto actual AWS breakage "
             "(dry-run plan offline; --apply needs credentials)")
    sim_prov.add_argument("scenario", help="scenario JSON file")
    sim_prov.add_argument("--apply", action="store_true",
                          help="execute the break steps (tagged, reversible)")
    sim.set_defaults(fn=cmd_simulate)

    ev = sub.add_parser("eval", help="run the investigation benchmark")
    ev.add_argument("--fixtures",
                    default="examples/evals/investigation-fixtures.sample.json")
    ev.add_argument("--offline", action="store_true",
                    help="score fixture mock_results without a model")
    ev.add_argument("--name", default="investigation")
    ev.add_argument("--out", default=".runbook/eval-reports")
    ev.add_argument("--concurrency", type=int, default=4)
    ev.add_argument("--min-pass-rate", type=float, default=0.0)
    ev.add_argument("--all", action="store_true", dest="run_all",
                    help="run every public benchmark (rcaeval/rootly/tracerca)")
    ev.add_argument("--datasets-root", default="examples/evals/datasets")
    ev.add_argument("--setup-datasets", action="store_true",
                    help="git-clone missing dataset repos first")
    ev.add_argument("--shard", default=None, metavar="I/N",
                    help="with --all: statically take cases i::n on this "
                         "host ('auto' = this process's multihost rank); "
                         "the engine fleet balances within the shard")
    ev.set_defaults(fn=cmd_eval)

    serve = sub.add_parser(
        "serve", help="OpenAI-compatible HTTP endpoint over the engine")
    serve.add_argument("--host", default="127.0.0.1")
    serve.add_argument("--port", type=int, default=8000)
    serve.add_argument("--allow-adapter-loading", action="store_true",
                       help="enable POST /v1/adapters (operator action)")
    serve.set_defaults(fn=cmd_serve)

    tune = sub.add_parser(
        "tune", help="serving-plan autotuner: cost-model sweep + measured "
                     "refinement -> plan artifact (docs/autotune.md)")
    tune.add_argument("--model", default=None,
                      help="model config name (default: llm.model; "
                           "--smoke: llama3-test)")
    tune.add_argument("--smoke", action="store_true",
                      help="bounded CPU smoke sweep (tiny model + space)")
    tune.add_argument("--hw", default="auto",
                      choices=["auto", "v5e", "v6e", "cpu"],
                      help="hardware envelope for the cost model")
    tune.add_argument("--weights", default="int8", choices=["int8", "bf16"])
    tune.add_argument("--prompt-len", type=int, default=512)
    tune.add_argument("--output-len", type=int, default=128)
    tune.add_argument("--concurrency", type=int, default=16)
    tune.add_argument("--guided-share", type=float, default=0.0)
    tune.add_argument("--spec-hit-rate", type=float, default=0.0)
    tune.add_argument("--workload", default=None, metavar="JSON",
                      help="workload descriptor file (Workload.to_dict "
                           "JSON — e.g. from `runbook workload "
                           "--emit-descriptor`); replaces the per-field "
                           "workload flags")
    tune.add_argument("--dp", default=None, metavar="1,2,4",
                      help="dp_replicas axis values (comma-separated)")
    tune.add_argument("--tp", default=None, metavar="1,8,16",
                      help="tp axis values (comma-separated)")
    tune.add_argument("--top-k", type=int, default=3,
                      help="survivors refined with measured runs")
    tune.add_argument("--no-measure", action="store_true",
                      help="analytic only (no engine runs)")
    tune.add_argument("--requests", type=int, default=4,
                      help="measured-run request count")
    tune.add_argument("--new-tokens", type=int, default=16,
                      help="measured-run decode tokens per request")
    tune.add_argument("--budget-s", type=float, default=300.0,
                      help="measured-phase time budget")
    tune.add_argument("--out", default=None,
                      help="plan path (default: "
                           ".runbook/plans/<model>.<hw>.json)")
    tune.set_defaults(fn=cmd_tune)

    plan = sub.add_parser("plan", help="serving-plan artifacts")
    plan_sub = plan.add_subparsers(dest="plan_cmd", required=True)
    plan_show = plan_sub.add_parser("show", help="print a validated plan")
    plan_show.add_argument("path")
    plan_val = plan_sub.add_parser(
        "validate", help="schema + content-hash check (CI gate)")
    plan_val.add_argument("paths", nargs="+")
    plan.set_defaults(fn=cmd_plan)

    wl = sub.add_parser(
        "workload", help="live workload fingerprints + plan drift from "
                         "a running server (GET /debug/workload)")
    wl.add_argument("--url", default="http://127.0.0.1:8000",
                    help="server base URL")
    wl.add_argument("--json", action="store_true",
                    help="raw JSON instead of the table")
    wl.add_argument("--watch", action="store_true",
                    help="re-render every --interval seconds")
    wl.add_argument("--interval", type=float, default=5.0)
    wl.add_argument("--model", default=None,
                    help="with --emit-descriptor: which served model "
                         "group's fingerprint to emit (default: the one "
                         "group, or the merged fleet view)")
    wl.add_argument("--emit-descriptor", default=None, metavar="OUT",
                    help="write the live tuner descriptor as JSON; feeds "
                         "`runbook tune --workload OUT` unchanged")
    wl.add_argument("--timeout", type=float, default=10.0)
    wl.set_defaults(fn=cmd_workload)

    tl = sub.add_parser(
        "timeline", help="render one request's span tree from a trace "
                         "JSONL (enqueue -> route -> admit -> prefill -> "
                         "decode -> finish)")
    tl.add_argument("request_id",
                    help="x-request-id (or engine-internal r{i}-… id)")
    tl.add_argument("--trace", required=True, metavar="JSONL",
                    help="tracer JSONL file (RUNBOOK_TRACE output)")
    tl.add_argument("--json", action="store_true",
                    help="structured timeline instead of the ASCII tree")
    tl.add_argument("--max-events", type=int, default=60,
                    help="tree rows before the middle dispatch windows "
                         "collapse into one summary line")
    tl.set_defaults(fn=cmd_timeline)

    prof = sub.add_parser(
        "profile", help="on-demand XLA/XProf capture around N engine "
                        "steps -> TensorBoard-readable trace dir")
    prof.add_argument("--steps", type=int, default=32,
                      help="engine steps to capture (after warmup)")
    prof.add_argument("--out", default=".runbook/profile",
                      help="trace output directory")
    prof.add_argument("--concurrency", type=int, default=4,
                      help="synthetic requests in flight during capture")
    prof.add_argument("--prompt-len", type=int, default=128)
    prof.add_argument("--new-tokens", type=int, default=32)
    prof.set_defaults(fn=cmd_profile)

    tn = sub.add_parser(
        "tenants", help="tenant accounting state: live /tenants from a "
                        "running server, else the configured llm.tenants "
                        "policies")
    tn.add_argument("--url", default="http://127.0.0.1:8000",
                    help="server base URL (GET <url>/tenants)")
    tn.add_argument("--json", action="store_true",
                    help="raw JSON instead of the table")
    tn.add_argument("--timeout", type=float, default=10.0)
    tn.set_defaults(fn=cmd_tenants)

    ch = sub.add_parser(
        "chaos", help="chaos-hardening state: replica supervision + "
                      "fault-injection windows from a running server")
    ch_sub = ch.add_subparsers(dest="chaos_cmd", required=True)
    ch_status = ch_sub.add_parser(
        "status", help="supervisor replica states, rebuild/failover "
                       "counters, recent transitions and applied fault "
                       "windows (GET <url>/healthz)")
    ch_status.add_argument("--url", default="http://127.0.0.1:8000",
                           help="server base URL (GET <url>/healthz)")
    ch_status.add_argument("--json", action="store_true",
                           help="raw JSON instead of the table")
    ch_status.add_argument("--timeout", type=float, default=10.0)
    ch.set_defaults(fn=cmd_chaos)

    inc = sub.add_parser(
        "incident", help="fleet incident feed + captured black-box "
                         "bundles (obs/incident.py): live from "
                         "GET /debug/incidents, else from the bundle "
                         "directory")
    inc_sub = inc.add_subparsers(dest="incident_cmd", required=True)

    def _incident_args(p) -> None:
        p.add_argument("--url", default="http://127.0.0.1:8000",
                       help="server base URL (GET <url>/debug/incidents)")
        p.add_argument("--dir", default=None,
                       help="bundle directory fallback (default: "
                            "llm.obs.incident_dir)")
        p.add_argument("--json", action="store_true",
                       help="raw JSON instead of the table")
        p.add_argument("--timeout", type=float, default=10.0)

    inc_list = inc_sub.add_parser(
        "list", help="detected incidents: lifecycle state, severity, "
                     "peak, captured bundle")
    _incident_args(inc_list)
    inc_show = inc_sub.add_parser(
        "show", help="one incident in full; --bundle loads + "
                     "hash-verifies its black-box bundle")
    inc_show.add_argument("id", help="incident id (inc-0001)")
    inc_show.add_argument("--bundle", action="store_true",
                          help="load the incident's bundle, verify its "
                               "content hash, print the evidence "
                               "inventory")
    _incident_args(inc_show)
    inc.set_defaults(fn=cmd_incident)

    qy = sub.add_parser(
        "query", help="PromQL-lite over the server's embedded metric "
                      "history (GET /debug/query; obs/query.py grammar)")
    qy.add_argument("expr",
                    help="query expression, e.g. "
                         "'rate(runbook_requests_total[1m])' or "
                         "'histogram_quantile(0.95, "
                         "runbook_ttft_seconds_bucket[5m])'")
    qy.add_argument("--url", default="http://127.0.0.1:8000",
                    help="server base URL (GET <url>/debug/query)")
    qy.add_argument("--range", default="5m",
                    help="default window for selectors without an "
                         "explicit [range] (duration: 30s, 5m, 1h)")
    qy.add_argument("--watch", action="store_true",
                    help="re-evaluate every --interval seconds")
    qy.add_argument("--interval", type=float, default=2.0)
    qy.add_argument("--json", action="store_true",
                    help="raw result JSON instead of the table")
    qy.add_argument("--timeout", type=float, default=10.0)
    qy.set_defaults(fn=cmd_query)

    met = sub.add_parser(
        "metrics", help="scrape a server's /metrics or summarize a trace")
    met.add_argument("--url", default="http://127.0.0.1:8000",
                     help="server base URL (GET <url>/metrics)")
    met.add_argument("--trace", default=None, metavar="JSONL",
                     help="summarize a tracer JSONL (per-span p50/p95/max) "
                          "instead of scraping")
    met.add_argument("--span", default=None,
                     help="with --trace: only span names containing this")
    met.add_argument("--grep", default=None,
                     help="only /metrics lines containing this substring")
    met.add_argument("--timeout", type=float, default=10.0)
    met.set_defaults(fn=cmd_metrics)

    lint = sub.add_parser(
        "lint", help="whole-program AST static analysis for TPU serving "
                     "hazards (RBK001-RBK010; docs/lint.md)")
    from runbookai_tpu.analysis.cli import add_lint_arguments

    add_lint_arguments(lint)
    lint.set_defaults(fn=cmd_lint)

    mcp = sub.add_parser("mcp", help="MCP server over stdio")
    mcp_sub = mcp.add_subparsers(dest="mcp_cmd", required=True)
    mcp_sub.add_parser("serve")
    mcp_sub.add_parser("tools")
    mcp.set_defaults(fn=cmd_mcp)

    wh = sub.add_parser("webhook", help="Slack approval webhook server")
    wh.add_argument("--port", type=int, default=3939)
    wh.set_defaults(fn=cmd_webhook)

    sg = sub.add_parser("slack-gateway", help="Slack gateway (socket|http)")
    sg.add_argument("--mode", choices=["socket", "http"], default=None,
                    help="default: incident.slack.mode from config")
    sg.add_argument("--port", type=int, default=3940)
    sg.set_defaults(fn=cmd_slack_gateway)

    integ = sub.add_parser("integrations", help="editor/agent integrations")
    integ_sub = integ.add_subparsers(dest="integration", required=True)
    claude = integ_sub.add_parser("claude")
    claude_sub = claude.add_subparsers(dest="integrations_cmd", required=True)
    for name in ("enable", "status", "disable"):
        c = claude_sub.add_parser(name)
        c.add_argument("--settings", default="~/.claude/settings.json")
    learn = claude_sub.add_parser("learn")
    learn.add_argument("--session-id", default=None)
    learn.add_argument("--settings", default="~/.claude/settings.json")
    integ.set_defaults(fn=cmd_integrations)

    hook = sub.add_parser("hook")  # hidden hook entrypoint (stdin protocol)
    hook.add_argument("event")
    hook.set_defaults(fn=cmd_hook)

    op = sub.add_parser("operability", help="operability-context ingestion")
    op_sub = op.add_subparsers(dest="operability_cmd", required=True)
    for name in ("ingest", "replay", "status"):
        op_sub.add_parser(name)
    op.set_defaults(fn=cmd_operability)

    w = sub.add_parser("weights", help="model weight checkpoints")
    w_sub = w.add_subparsers(dest="weights_cmd", required=True)
    conv = w_sub.add_parser(
        "convert", help="HF safetensors -> orbax checkpoint (optionally int8)")
    conv.add_argument("model_path", help="HF model dir (safetensors + config)")
    conv.add_argument("out", help="output checkpoint dir")
    conv.add_argument("--int8", action="store_true",
                      help="quantize layer weights to int8 during conversion")
    conv.add_argument("--name", default="hf-model")
    conv.add_argument("--random-init", action="store_true",
                      help="allow a missing model_path (random weights; CI only)")
    info = w_sub.add_parser("info", help="describe a checkpoint")
    info.add_argument("path")
    w.set_defaults(fn=cmd_weights)

    return p


def main(argv=None) -> int:
    from runbookai_tpu.utils.compile_cache import ensure_compile_cache

    parser = build_parser()
    args = parser.parse_args(argv)
    ensure_compile_cache()
    try:
        return args.fn(args)
    except KeyboardInterrupt:
        print("\ninterrupted")
        return 130


if __name__ == "__main__":
    sys.exit(main())
