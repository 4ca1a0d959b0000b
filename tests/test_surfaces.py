"""Surface layers: demo, CLI commands, checkpoints, MCP server, webhook,
slack gateway, learning loop."""

import io
import json
import urllib.parse

import pytest

from runbookai_tpu.demo.runner import render_event, run_demo
from runbookai_tpu.session.checkpoint import CheckpointStore
from runbookai_tpu.utils.config import Config


def test_demo_script_plays_and_renders():
    events = run_demo(sleep=lambda s: None)
    kinds = [e.kind for e in events]
    assert kinds[0] == "start" and kinds[-1] == "done"
    assert kinds.count("hypothesis_created") == 4
    assert "conclusion" in kinds
    conclusion = next(e for e in events if e.kind == "conclusion")
    assert "pool" in conclusion.data["root_cause"]
    assert "┤" in conclusion.data["chart"]  # chart attached
    rendered = [render_event(e) for e in events]
    assert any("ROOT CAUSE" in r for r in rendered)
    assert any("[CONFIRM" in r.upper() or "confirm" in r for r in rendered)


def test_checkpoint_store_roundtrip(tmp_path):
    from runbookai_tpu.agent.state_machine import InvestigationStateMachine

    store = CheckpointStore(tmp_path)
    m = InvestigationStateMachine(incident_id="PD-9")
    m.add_hypothesis("h1", priority=0.7)
    meta = store.save_machine(m, label="mid")
    metas = store.list("PD-9")
    assert len(metas) == 1 and metas[0].label == "mid"
    shown = store.show(meta.checkpoint_id)
    assert shown["snapshot"]["hypothesis_detail"]["H1"]["statement"] == "h1"
    assert store.latest("PD-9")["meta"]["checkpoint_id"] == meta.checkpoint_id
    assert store.delete(meta.checkpoint_id)
    assert store.list("PD-9") == []


def test_checkpoint_prune_cap(tmp_path):
    import runbookai_tpu.session.checkpoint as cp

    store = CheckpointStore(tmp_path)
    orig = cp.MAX_CHECKPOINTS_PER_INVESTIGATION
    cp.MAX_CHECKPOINTS_PER_INVESTIGATION = 3
    try:
        for i in range(5):
            store.save("inv", {"phase": "x", "i": i})
        assert len(store.list("inv")) == 3
    finally:
        cp.MAX_CHECKPOINTS_PER_INVESTIGATION = orig


def test_cli_init_status_config(tmp_path, monkeypatch, capsys):
    from runbookai_tpu.cli.main import main

    monkeypatch.chdir(tmp_path)
    assert main(["init", "--template", "simulated"]) == 0
    assert (tmp_path / ".runbook" / "config.yaml").exists()
    assert main(["status"]) == 0
    out = capsys.readouterr().out
    assert "aws (simulated)" in out
    assert main(["config", "--set", "agent.max_iterations=4"]) == 0
    assert main(["config", "--show"]) == 0
    out = capsys.readouterr().out
    assert '"max_iterations": 4' in out


def test_cli_demo_and_eval_offline(tmp_path, monkeypatch, capsys, request):
    from runbookai_tpu.cli.main import main

    repo_fixtures = str(
        (request.config.rootpath / "examples/evals/investigation-fixtures.sample.json")
    )
    monkeypatch.setattr("time.sleep", lambda s: None)
    assert main(["demo", "--fast"]) == 0
    out = capsys.readouterr().out
    assert "ROOT CAUSE" in out
    monkeypatch.chdir(tmp_path)
    code = main(["eval", "--offline", "--fixtures", repo_fixtures,
                 "--out", str(tmp_path / "reports")])
    assert code == 0
    report = json.loads((tmp_path / "reports" / "investigation.json").read_text())
    assert report["total"] == 3 and report["passed"] == 2


def test_cli_knowledge_roundtrip(tmp_path, monkeypatch, capsys):
    from runbookai_tpu.cli.main import main

    monkeypatch.chdir(tmp_path)
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "r.md").write_text(
        "---\ntype: runbook\nservices: [svc-a]\n---\n# Pool runbook\n\nCheck the pool.")
    cfg_dir = tmp_path / ".runbook"
    cfg_dir.mkdir()
    (cfg_dir / "config.yaml").write_text(f"""
knowledge:
  db_path: {tmp_path}/kb.db
  embedder: {{enabled: true, model: bge-test, max_length: 64}}
  sources:
    - {{type: filesystem, name: docs, path: {docs}}}
""")
    assert main(["knowledge", "sync"]) == 0
    out = capsys.readouterr().out
    assert "docs: 1 documents synced" in out
    assert main(["knowledge", "search", "pool"]) == 0
    out = capsys.readouterr().out
    assert "Pool runbook" in out
    assert main(["knowledge", "stats"]) == 0


def test_cli_ask_with_mock_runtime(tmp_path, monkeypatch, capsys):
    """`runbook ask` through build_runtime with mock provider + simulated tools."""
    from runbookai_tpu.cli.main import main

    monkeypatch.chdir(tmp_path)
    (tmp_path / ".runbook").mkdir()
    (tmp_path / ".runbook" / "config.yaml").write_text("""
llm: {provider: mock}
providers:
  aws: {enabled: true, simulated: true}
""")
    assert main(["ask", "what is on fire?", "--yes"]) == 0
    out = capsys.readouterr().out
    assert "done" in out


def test_mcp_server_protocol(tmp_path):
    from runbookai_tpu.knowledge.chunker import document_from_markdown
    from runbookai_tpu.knowledge.retriever import HybridRetriever, KnowledgeRetriever
    from runbookai_tpu.knowledge.store.sqlite_fts import KnowledgeStore
    from runbookai_tpu.server.mcp import MCPServer, run_stdio_server

    store = KnowledgeStore(":memory:")
    store.upsert_document(document_from_markdown(
        "r.md", "---\ntype: runbook\n---\n# Pool runbook\n\npool saturation steps"))
    retriever = KnowledgeRetriever(store, HybridRetriever(store))
    server = MCPServer(retriever)

    init = server.handle({"jsonrpc": "2.0", "id": 1, "method": "initialize"})
    assert init["result"]["serverInfo"]["name"] == "runbookai-tpu"
    tools = server.handle({"jsonrpc": "2.0", "id": 2, "method": "tools/list"})
    names = [t["name"] for t in tools["result"]["tools"]]
    assert "search_runbooks" in names and "get_knowledge_stats" in names
    call = server.handle({"jsonrpc": "2.0", "id": 3, "method": "tools/call",
                          "params": {"name": "search_runbooks",
                                     "arguments": {"query": "pool"}}})
    payload = json.loads(call["result"]["content"][0]["text"])
    assert payload["results"] and "Pool runbook" in payload["results"][0]["title"]
    bad = server.handle({"jsonrpc": "2.0", "id": 4, "method": "nope"})
    assert bad["error"]["code"] == -32601

    # stdio loop
    stdin = io.StringIO(json.dumps({"jsonrpc": "2.0", "id": 9,
                                    "method": "tools/list"}) + "\n")
    stdout = io.StringIO()
    run_stdio_server(server, stdin=stdin, stdout=stdout)
    reply = json.loads(stdout.getvalue())
    assert reply["id"] == 9


def test_webhook_signature_and_approval_flow(tmp_path):
    from runbookai_tpu.server.webhook import (
        ApprovalFileStore,
        verify_slack_signature,
    )
    import hashlib
    import hmac
    import time as _time

    secret = "s3cret"
    ts = str(_time.time())
    body = b"payload=%7B%7D"
    sig = "v0=" + hmac.new(secret.encode(), f"v0:{ts}:".encode() + body,
                           hashlib.sha256).hexdigest()
    assert verify_slack_signature(secret, ts, body, sig)
    assert not verify_slack_signature(secret, ts, body, "v0=bad")
    assert not verify_slack_signature(secret, "123", body, sig)  # stale ts

    store = ApprovalFileStore(tmp_path)
    store.create_pending("ap-1", {"operation": "rollback"})
    assert store.list_pending() == ["ap-1"]
    assert store.poll_response("ap-1") is None
    assert store.respond("ap-1", True, user="alice")
    resp = store.poll_response("ap-1")
    assert resp["approved"] is True and resp["user"] == "alice"
    assert store.list_pending() == []
    assert not store.respond("ap-404", True)


async def test_slack_gateway_parse_authz_dedupe():
    from runbookai_tpu.server.slack_gateway import (
        DedupeCache,
        SlackGateway,
        parse_mention_command,
    )

    assert parse_mention_command("<@U1> investigate PD-1 now") == ("investigate", "PD-1 now")
    assert parse_mention_command("<@U1> why is checkout slow") == ("infra", "why is checkout slow")
    assert parse_mention_command("<@U1>") is None

    config = Config.model_validate({
        "incident": {"slack": {"enabled": True, "allowed_channels": ["C1"],
                               "allowed_users": ["U-ok"]}}})
    answered = []

    async def run_request(req):
        answered.append(req)
        return f"answer to {req.text}"

    posts = []
    gw = SlackGateway(config=config, run_request=run_request,
                      post_message=lambda c, t, th: posts.append((c, t, th)))
    # unauthorized channel
    out = await gw.handle_event({"type": "app_mention", "channel": "C2",
                                 "user": "U-ok", "ts": "1", "text": "<@B> hi"})
    assert "Not authorized" in out
    # authorized
    out = await gw.handle_event({"type": "app_mention", "channel": "C1",
                                 "user": "U-ok", "ts": "2",
                                 "text": "<@B> infra what broke"},
                                event_id="ev1")
    assert out == "answer to what broke"
    assert posts[-1][0] == "C1"
    # dedupe: same event id ignored
    out2 = await gw.handle_event({"type": "app_mention", "channel": "C1",
                                  "user": "U-ok", "ts": "2",
                                  "text": "<@B> infra what broke"},
                                 event_id="ev1")
    assert out2 is None and len(answered) == 1
    cache = DedupeCache(ttl_s=0.0)
    assert not cache.seen("x")


async def test_learning_loop_artifacts(tmp_path):
    from runbookai_tpu.agent.orchestrator import OrchestratorResult
    from runbookai_tpu.agent.types import AgentEvent
    from runbookai_tpu.learning.loop import run_learning_loop
    from runbookai_tpu.model.client import MockLLMClient

    llm = MockLLMClient([
        "# Postmortem\n\nPool exhausted.",
        json.dumps({"suggestions": [{"type": "runbook", "title": "Pool saturation",
                                     "reason": "recurring", "services": ["payment-api"],
                                     "outline": "check pool"}]}),
    ])
    result = OrchestratorResult(
        summary={"incident_id": "PD-7"},
        root_cause="pool exhausted", confidence="high",
        affected_services=["payment-api"],
        conclusion_summary="pool too small",
        events=[AgentEvent("conclusion", {"root_cause": "pool"})],
    )
    out = await run_learning_loop(llm, result, out_dir=tmp_path)
    assert (out / "postmortem-draft.md").read_text().startswith("# Postmortem")
    suggestions = json.loads((out / "knowledge-suggestions.json").read_text())
    assert suggestions["suggestions"][0]["title"] == "Pool saturation"
    assert json.loads((out / "record.json").read_text())["root_cause"] == "pool exhausted"


# ---------------------------------------------------------------------------
# terminal UI components (reference src/cli/components/*.tsx) + setup wizard

def test_markdown_renderer_blocks():
    from runbookai_tpu.cli.markdown import parse_blocks, render_markdown

    md = """# Incident report

Root cause was a **bad deploy** touching `payments`.

- first item
- second item

```bash
kubectl rollout undo deploy/payments
```

| svc | status |
|-----|--------|
| payments | degraded |

> quote line
"""
    kinds = [b.kind for b in parse_blocks(md)]
    assert kinds == ["header", "paragraph", "list", "code", "table", "blockquote"]

    plain = render_markdown(md, color=False)
    assert "# Incident report" in plain
    assert "bad deploy" in plain and "**" not in plain
    assert "• first item" in plain
    assert "kubectl rollout undo" in plain
    assert "│ payments" in plain

    ansi = render_markdown(md, color=True)
    assert "\x1b[1m" in ansi  # bold somewhere


def test_markdown_ordered_list_and_links():
    from runbookai_tpu.cli.markdown import render_markdown

    md = "1. step one\n2. step two\n\nsee [runbook](https://kb/x)"
    plain = render_markdown(md, color=False)
    assert "1. step one" in plain and "2. step two" in plain
    assert "runbook <https://kb/x>" in plain


def test_hypothesis_tree_rendering():
    from runbookai_tpu.agent.state_machine import FSMHypothesis
    from runbookai_tpu.cli.hypothesis_view import (
        count_statuses,
        render_summary,
        render_tree,
    )

    nodes = [
        FSMHypothesis(id="h1", statement="bad deploy", status="confirmed",
                      confidence=85.0, children=["h2", "h3"]),
        FSMHypothesis(id="h2", statement="config drift", parent_id="h1",
                      status="pruned", depth=1),
        FSMHypothesis(id="h3", statement="pool exhaustion", parent_id="h1",
                      status="investigating", depth=1,
                      evidence=[{"summary": "x"}]),
    ]
    tree = render_tree(nodes, color=False)
    assert "● bad deploy 85%" in tree
    assert "├─" in tree and "└─" in tree
    assert "config drift" in tree
    hidden = render_tree(nodes, show_pruned=False, color=False)
    assert "config drift" not in hidden
    assert "[1 evidence]" in tree

    counts = count_statuses(nodes)
    assert counts["confirmed"] == 1 and counts["pruned"] == 1
    summary = render_summary(nodes, color=False)
    assert "Root cause: bad deploy (85%)" in summary


def test_wizard_scripted_flow_and_save(tmp_path):
    from runbookai_tpu.cli.wizard import (
        OnboardingAnswers,
        generate_configs,
        hydrate_answers,
        run_wizard,
        save_wizard_configs,
    )

    answers_script = iter([
        "custom",            # template
        "jax-tpu", "llama3-8b-instruct",
        "multi", "prod,staging", "us-east-1,eu-west-1",
        "ecs,eks", "rds",
        "y",                  # kubernetes
        "pagerduty",
        "n",                  # slack
        "./docs/runbooks",
    ])
    answers = run_wizard(ask=lambda q, d: next(answers_script))
    assert answers.account_names == ["prod", "staging"]
    assert answers.compute_services == ["ecs", "eks"]
    assert answers.use_kubernetes

    config_path, services_path = save_wizard_configs(answers, tmp_path)
    assert config_path.exists() and services_path.exists()

    config, services = generate_configs(answers)
    assert config.llm.provider == "jax-tpu"
    assert config.providers.kubernetes.enabled  # eks implies k8s
    assert config.incident.pagerduty.enabled
    assert len(services.accounts) == 2
    assert {s.type for s in services.services} == {"ecs", "eks", "rds"}

    # hydration round-trip picks the saved answers back up
    hydrated = hydrate_answers(tmp_path)
    assert hydrated.account_setup == "multi"
    assert hydrated.compute_services == ["ecs", "eks"]
    assert hydrated.incident_provider == "pagerduty"


def test_wizard_quick_template():
    from runbookai_tpu.cli.wizard import run_wizard

    answers = run_wizard(ask=lambda q, d: "kubernetes")
    assert answers.use_kubernetes and answers.compute_services == ["eks"]


def test_markdown_unterminated_table_does_not_hang():
    from runbookai_tpu.cli.markdown import parse_blocks, render_markdown

    blocks = parse_blocks("| a | b")  # no trailing pipe — must still terminate
    assert [b.kind for b in blocks] == ["table"]
    assert "a" in render_markdown("| a | b\nplain text after", color=False)


def test_hypothesis_confidence_fraction_scaling():
    from runbookai_tpu.agent.state_machine import FSMHypothesis
    from runbookai_tpu.cli.hypothesis_view import render_summary, render_tree

    nodes = [FSMHypothesis(id="h", statement="bad deploy",
                           status="confirmed", confidence=0.85)]
    assert "85%" in render_tree(nodes, color=False)
    assert "(85%)" in render_summary(nodes, color=False)


def test_cli_chat_raw_streams(tmp_path, monkeypatch, capsys):
    """chat --raw streams through the LLMClient event protocol (mock
    fallback here; true token streaming on the jax-tpu provider)."""
    from runbookai_tpu.cli.main import main

    monkeypatch.chdir(tmp_path)
    inputs = iter(["hello there", ""])
    monkeypatch.setattr("builtins.input", lambda *a: next(inputs))
    assert main(["chat", "--raw"]) == 0
    out = capsys.readouterr().out
    assert "streaming model chat" in out


def test_cli_serve_requires_engine_provider(tmp_path, monkeypatch, capsys):
    from runbookai_tpu.cli.main import main

    monkeypatch.chdir(tmp_path)
    # Default config is the mock provider: serve must refuse, not crash.
    assert main(["serve", "--port", "0"]) == 1


def test_llm_config_knobs():
    from runbookai_tpu.models.llama import CONFIGS
    from runbookai_tpu.utils.config import LLMConfig

    assert CONFIGS["qwen2.5-7b-instruct"].family == "qwen2"
    assert LLMConfig().attn_impl == "auto"
    assert LLMConfig(attn_impl="xla").attn_impl == "xla"


def test_live_tree_sink_repaints_during_run():
    """TTY mode: the hypothesis tree erases + repaints under the event
    stream (reference Ink live tree); non-TTY falls back to line events."""
    import io

    from runbookai_tpu.agent.state_machine import InvestigationStateMachine
    from runbookai_tpu.agent.types import AgentEvent
    from runbookai_tpu.cli.live_view import LiveTreeSink

    machine = InvestigationStateMachine(incident_id="INC-9")
    out = io.StringIO()
    lines: list = []
    sink = LiveTreeSink(machine, fallback=lambda ev: lines.append(ev.kind),
                        out=out, enabled=True)

    sink(AgentEvent("phase_change", {"phase": "triage"}))
    assert "\x1b[" not in out.getvalue()  # nothing painted yet (no hyps)

    machine.add_hypothesis("db pool exhausted", priority=8)
    sink(AgentEvent("hypothesis_created", {"id": "H1"}))
    first = out.getvalue()
    assert "db pool exhausted" in first

    machine.add_hypothesis("bad deploy", priority=5)
    sink(AgentEvent("hypothesis_created", {"id": "H2"}))
    second = out.getvalue()[len(first):]
    # The repaint erased the old block (cursor-up F + clear 0J) and the
    # new tree carries BOTH hypotheses.
    assert "\x1b[" in second and "F\x1b[0J" in second
    assert "bad deploy" in second and "db pool exhausted" in second
    assert lines == ["phase_change", "hypothesis_created",
                     "hypothesis_created"]

    # Non-TTY: pure passthrough, zero ANSI.
    out2 = io.StringIO()
    plain: list = []
    sink2 = LiveTreeSink(machine, fallback=lambda ev: plain.append(ev.kind),
                         out=out2, enabled=False)
    sink2(AgentEvent("hypothesis_created", {"id": "H3"}))
    assert out2.getvalue() == "" and plain == ["hypothesis_created"]


def test_package_loads_nothing_from_the_repository_root():
    """An installed package has no repository root beside it: no module
    under ``runbookai_tpu/`` imports ``bench``, names ``bench.py`` or
    builds a path out of ``parents[2]`` to a ``.py`` file, and the
    ``runbook`` parser has no ``bench`` subcommand (it could only run a
    file the package does not ship)."""
    import argparse
    import re
    from pathlib import Path

    import runbookai_tpu
    from runbookai_tpu.cli.main import build_parser

    offending = re.compile(
        r"^\s*(import|from)\s+bench\b|bench\.py|parents\[2\][^\n]*\.py",
        re.MULTILINE)
    package = Path(runbookai_tpu.__file__).parent
    hits = {str(p.relative_to(package)): m.group(0)
            for p in sorted(package.rglob("*.py"))
            if (m := offending.search(p.read_text()))}
    assert hits == {}
    commands = next(a for a in build_parser()._actions
                    if isinstance(a, argparse._SubParsersAction))
    assert "bench" not in commands.choices
    assert {"serve", "tune", "profile"} <= set(commands.choices)
