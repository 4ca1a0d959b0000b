"""The RBK rule set — one class per rule, subscribed to the shared walker.

Every rule documents the runtime failure it prevents, because a lint gate
nobody understands gets noqa'd into irrelevance. docs/lint.md carries the
bad/good examples; keep both in sync when adding a rule.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, Optional

from runbookai_tpu.analysis.core import (
    HOT_PATH_TAGS,
    ModuleContext,
    Rule,
    Scope,
    Severity,
    dotted_name,
    mentions_traced,
)

# The PR-1 observability contract (utils/metrics.py METRIC_NAME_RE) —
# duplicated as a literal on purpose: the analyzer must not import jax-adjacent
# modules, and a drift between the two regexes is itself caught by
# tests/test_lint.py.
METRIC_NAME_RE = re.compile(r"^runbook_[a-z0-9_]+$")


class DataDependentHostOps(Rule):
    """RBK001 — host branching / host conversion on traced values in jit.

    ``if traced:`` forces a concrete bool → one blocking device sync per
    call AND a retrace per novel shape; ``bool()/int()/float()/.item()/
    .tolist()`` on a traced value are the same sync spelled differently.
    Inside the decode loop that's a device stall per occurrence — the
    exact failure class Ragged Paged Attention's shape-discipline work
    exists to prevent.
    """

    rule_id = "RBK001"
    severity = Severity.ERROR
    description = ("data-dependent Python branching or host conversion on a "
                   "traced value inside a @jax.jit-reachable function")

    _CONVERSIONS = frozenset({"bool", "int", "float"})
    _SYNC_METHODS = frozenset({"item", "tolist"})

    def on_branch(self, ctx: ModuleContext, scope: Scope,
                  node: ast.stmt) -> Iterator[tuple[ast.AST, str]]:
        if not scope.in_jit:
            return
        test = node.test  # type: ignore[attr-defined]
        if mentions_traced(test, scope.traced_params):
            kind = "if" if isinstance(node, ast.If) else "while"
            yield (node,
                   f"data-dependent `{kind}` on a traced value inside a "
                   f"jit-reachable function — use jnp.where/lax.cond/"
                   f"lax.while_loop (each concrete branch forces a host "
                   f"sync and a recompile per novel value)")

    def on_call(self, ctx: ModuleContext, scope: Scope,
                node: ast.Call) -> Iterator[tuple[ast.AST, str]]:
        if not scope.in_jit:
            return
        if (isinstance(node.func, ast.Name)
                and node.func.id in self._CONVERSIONS and node.args
                and mentions_traced(node.args[0], scope.traced_params)):
            yield (node,
                   f"`{node.func.id}()` on a traced value inside a "
                   f"jit-reachable function forces a blocking device→host "
                   f"sync at trace time (ConcretizationTypeError on "
                   f"abstract values)")
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in self._SYNC_METHODS
                and mentions_traced(node.func.value, scope.traced_params)):
            yield (node,
                   f"`.{node.func.attr}()` on a traced value inside a "
                   f"jit-reachable function is a device→host transfer; keep "
                   f"values on device or move the conversion to the host "
                   f"caller")


class EngineLoopHostSync(Rule):
    """RBK002 — host syncs in the engine step/decode loop.

    The engine's throughput contract is ONE sanctioned token fetch in the
    decode loop: the async-egress consumption point
    (``EngineCore._fetch_tokens``) of the overlapped pipeline
    (docs/decode_pipeline.md) — every decode path funnels through it.
    Every extra ``block_until_ready`` / ``device_get`` / implicit
    ``np.asarray(jnp...)`` in ``engine/`` modules serializes the pipeline
    behind a device round-trip. Sanctioned
    barriers carry ``# runbook: noqa[RBK002] — <reason>`` so the next
    reader knows why the sync is load-bearing; tests/test_lint.py pins the
    full per-function inventory.
    """

    rule_id = "RBK002"
    severity = Severity.ERROR
    description = ("device→host sync (block_until_ready / device_get / "
                   "np.asarray of a jnp value) in an engine/ module outside "
                   "a sanctioned sync point")

    _SYNC_CALLS = frozenset({"jax.block_until_ready", "jax.device_get"})
    _NP_CTORS = frozenset({"np.asarray", "np.array", "numpy.asarray",
                           "numpy.array", "onp.asarray", "onp.array"})

    @staticmethod
    def _contains_jnp(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            name = None
            if isinstance(sub, (ast.Attribute, ast.Name)):
                name = dotted_name(sub)
            if name and (name.startswith("jnp.") or name.startswith("jax.numpy.")):
                return True
        return False

    def on_call(self, ctx: ModuleContext, scope: Scope,
                node: ast.Call) -> Iterator[tuple[ast.AST, str]]:
        if "engine" not in ctx.tags:
            return
        name = dotted_name(node.func)
        if name in self._SYNC_CALLS:
            yield (node,
                   f"`{name}` in an engine module: a blocking device→host "
                   f"sync outside the sanctioned per-dispatch token fetch — "
                   f"annotate sanctioned barriers with "
                   f"`# runbook: noqa[RBK002] — <reason>`")
            return
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready" and not node.args):
            yield (node,
                   "`.block_until_ready()` in an engine module: blocking "
                   "device sync outside the sanctioned token fetch")
            return
        if name in self._NP_CTORS and node.args \
                and self._contains_jnp(node.args[0]):
            yield (node,
                   f"`{name}` of a jnp expression implicitly copies "
                   f"device→host; fetch once via jax.device_get at the "
                   f"sanctioned sync point instead")


class BlockingCallUnderLock(Rule):
    """RBK003 — blocking I/O while holding a lock.

    The engine step lock serializes submit/step/abort: a ``time.sleep`` or
    file/socket/subprocess call inside ``with self._lock:`` stalls every
    live decode for its duration (and an admission storm turns that into
    head-of-line blocking for the whole server).
    """

    rule_id = "RBK003"
    severity = Severity.ERROR
    description = "blocking I/O (sleep/file/socket/subprocess) under a lock"

    _EXACT = frozenset({"time.sleep", "os.system", "os.popen"})
    _PREFIXES = ("subprocess.", "socket.", "requests.", "urllib.",
                 "http.client.", "shutil.")
    _IO_METHODS = frozenset({"read_text", "write_text", "read_bytes",
                             "write_bytes"})

    def on_call(self, ctx: ModuleContext, scope: Scope,
                node: ast.Call) -> Iterator[tuple[ast.AST, str]]:
        if not scope.in_lock:
            return
        name = dotted_name(node.func)
        blocking: Optional[str] = None
        if name in self._EXACT or (name == "sleep"):
            blocking = name
        elif name and name.startswith(self._PREFIXES):
            blocking = name
        elif isinstance(node.func, ast.Name) and node.func.id == "open":
            blocking = "open"
        elif (isinstance(node.func, ast.Attribute)
              and node.func.attr in self._IO_METHODS):
            blocking = f".{node.func.attr}"
        if blocking:
            yield (node,
                   f"`{blocking}(...)` while holding a lock blocks every "
                   f"thread contending for it (the engine step lock "
                   f"serializes ALL live decodes); move the I/O outside "
                   f"the `with` scope")


class UnlockedSharedMutation(Rule):
    """RBK004 — attributes mutated both inside and outside lock scopes.

    If a class protects ``self.x`` writes with ``with self._lock:``
    somewhere, an unprotected ``self.x = ...`` elsewhere is (at best) a
    benign race waiting for a refactor to make it malignant. ``__init__``
    and friends are exempt — construction happens-before sharing.
    """

    rule_id = "RBK004"
    severity = Severity.WARNING
    description = ("shared attribute mutated both inside and outside a "
                   "lock scope")

    _CTOR_METHODS = frozenset({"__init__", "__new__", "__post_init__",
                               "__init_subclass__"})

    def __init__(self) -> None:
        # (class, attr) → {"locked": [...nodes], "unlocked": [...nodes]}
        self._writes: dict[tuple[str, str], dict[str, list[ast.AST]]] = {}

    def on_attr_write(self, ctx: ModuleContext, scope: Scope,
                      node: ast.AST, attr: str) -> Iterator[tuple[ast.AST, str]]:
        if scope.class_name is None or scope.func_name is None:
            return
        if not scope.in_lock and scope.func_name in self._CTOR_METHODS:
            return
        rec = self._writes.setdefault((scope.class_name, attr),
                                      {"locked": [], "unlocked": []})
        rec["locked" if scope.in_lock else "unlocked"].append(node)
        return
        yield  # pragma: no cover — generator signature

    def finish(self, ctx: ModuleContext) -> Iterator[tuple[ast.AST, str]]:
        writes, self._writes = self._writes, {}
        for (cls, attr), rec in sorted(writes.items()):
            if rec["locked"] and rec["unlocked"]:
                first = min(rec["unlocked"],
                            key=lambda n: getattr(n, "lineno", 0))
                locked_line = min(getattr(n, "lineno", 0)
                                  for n in rec["locked"])
                yield (first,
                       f"`{cls}.{attr}` is written under a lock (line "
                       f"{locked_line}) but also mutated here without it — "
                       f"take the same lock or document the happens-before")


class MetricContract(Rule):
    """RBK005 — metric registrations must honor the PR-1 contract.

    Names match ``^runbook_[a-z0-9_]+$`` and histograms pass explicit
    buckets. The registry enforces this at runtime; this rule moves the
    failure to lint time, before a bad name ships a dashboard that can
    never be renamed compatibly.
    """

    rule_id = "RBK005"
    severity = Severity.ERROR
    description = ("metric registration violating the naming/bucket "
                   "contract (docs/observability.md)")

    _REGISTRY_METHODS = frozenset({"counter", "gauge", "histogram"})

    def on_call(self, ctx: ModuleContext, scope: Scope,
                node: ast.Call) -> Iterator[tuple[ast.AST, str]]:
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr in self._REGISTRY_METHODS):
            return
        first = node.args[0] if node.args else None
        if not (isinstance(first, ast.Constant) and isinstance(first.value, str)):
            return  # dynamic names are the registry's runtime problem
        name = first.value
        if not METRIC_NAME_RE.match(name):
            yield (node,
                   f"metric name {name!r} violates the contract "
                   f"`{METRIC_NAME_RE.pattern}` (docs/observability.md)")
        if node.func.attr == "histogram":
            # The registry takes buckets KEYWORD-ONLY; a third positional
            # arg is a runtime TypeError, not a bucket declaration.
            has_buckets = any(kw.arg == "buckets" for kw in node.keywords)
            if not has_buckets:
                yield (node,
                       f"histogram {name!r} registered without explicit "
                       f"buckets — implied defaults drift silently across "
                       f"library versions")


class HotPathPrint(Rule):
    """RBK006 — ``print`` / ``jax.debug.print`` left in serving hot paths.

    A stray print in the decode loop is an unbounded-stdout tax per token
    (and ``jax.debug.print`` inserts a host callback into the compiled
    program). Anything load-bearing routes through utils/trace.py spans.
    """

    rule_id = "RBK006"
    severity = Severity.WARNING
    description = "print/jax.debug.print in engine/ops/model hot paths"

    def on_call(self, ctx: ModuleContext, scope: Scope,
                node: ast.Call) -> Iterator[tuple[ast.AST, str]]:
        if not (ctx.tags & HOT_PATH_TAGS):
            return
        if isinstance(node.func, ast.Name) and node.func.id == "print":
            yield (node,
                   "stray `print` in a serving hot path — route through "
                   "utils/trace.py (Tracer.event/span) or delete")
        elif dotted_name(node.func) == "jax.debug.print":
            yield (node,
                   "`jax.debug.print` compiles a host callback into the "
                   "program — debugging leftover; remove before serving")


def default_rules() -> list[Rule]:
    """Fresh rule instances (RBK004 aggregates per-walk state)."""
    return [DataDependentHostOps(), EngineLoopHostSync(),
            BlockingCallUnderLock(), UnlockedSharedMutation(),
            MetricContract(), HotPathPrint()]


def rule_by_id(rule_id: str) -> Optional[Rule]:
    for rule in default_rules():
        if rule.rule_id == rule_id.upper():
            return rule
    return None
