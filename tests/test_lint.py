"""``runbook lint`` — the static-analysis gate (runbookai_tpu/analysis/).

Covers every rule (positive + negative), the noqa and baseline semantics,
both CLI surfaces, and the tier-1 integration gate: the whole package must
analyze clean against the committed baseline forever.
"""

import argparse
import io
import json
import textwrap
from pathlib import Path

import pytest

from runbookai_tpu.analysis import (
    analyze_paths,
    analyze_source,
    baseline_counts,
    load_baseline,
    new_findings,
    write_baseline,
)
from runbookai_tpu.analysis.cli import main as lint_main

ROOT = Path(__file__).resolve().parent.parent


def lint(src: str, path: str = "runbookai_tpu/engine/mod.py"):
    return analyze_source(textwrap.dedent(src), path)


def rules_of(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------- RBK001


class TestRBK001:
    def test_data_dependent_if_in_jit(self):
        out = lint("""
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """)
        assert "RBK001" in rules_of(out)

    def test_partial_jit_and_while(self):
        out = lint("""
            from functools import partial
            import jax

            @partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                while x < n:
                    x = x + 1
                return x
        """)
        assert rules_of(out) == ["RBK001"]

    def test_static_argnames_branch_ok(self):
        out = lint("""
            from functools import partial
            import jax

            @partial(jax.jit, static_argnames=("mode",))
            def f(x, mode):
                if mode == "fast":
                    return x * 2
                return x
        """)
        assert out == []

    def test_is_none_and_shape_checks_ok(self):
        out = lint("""
            import jax

            @jax.jit
            def f(x, mask):
                if mask is not None:
                    x = x * mask
                if x.shape[0] > 4:
                    return x
                if len(x) > 2:
                    return x
                return x
        """)
        assert out == []

    def test_host_conversion_calls(self):
        out = lint("""
            import jax

            @jax.jit
            def f(x):
                return float(x) + x.item()
        """)
        assert rules_of(out).count("RBK001") == 2

    def test_item_on_host_value_ok(self):
        # .item() on a non-traced (host numpy) value inside a jit-reachable
        # helper is not a device sync.
        out = lint("""
            import jax
            import numpy as np

            @jax.jit
            def f(x, shape):
                n = np.prod(np.array([2, 3])).item()
                return x * n
        """)
        assert out == []

    def test_closure_propagates_traced_args_only(self):
        out = lint("""
            import jax

            def helper(v):
                if v > 0:
                    return v
                return -v

            def shape_helper(dim):
                if dim % 128 == 0:
                    return dim
                return None

            @jax.jit
            def f(x):
                k = x.shape[0]
                return helper(x) + shape_helper(k)
        """)
        # helper(x) receives the traced param -> flagged; shape_helper
        # receives a static shape int -> clean.
        assert len(out) == 1
        assert out[0].rule == "RBK001" and out[0].line == 5

    def test_nested_fn_inside_jit_is_traced(self):
        out = lint("""
            import jax

            @jax.jit
            def f(x):
                def body(carry):
                    if carry:
                        return carry
                    return x
                return body(x)
        """)
        assert "RBK001" in rules_of(out)

    def test_host_function_not_flagged(self):
        out = lint("""
            def host(x):
                if x > 0:
                    return float(x)
                return x.item()
        """)
        assert out == []


# --------------------------------------------------------------------- RBK002


class TestRBK002:
    SRC = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def step(toks):
            jax.block_until_ready(toks)
            host = jax.device_get(toks)
            arr = np.asarray(jnp.add(toks, 1))
            return host, arr
    """

    def test_sync_calls_in_engine_module(self):
        out = lint(self.SRC, path="runbookai_tpu/engine/mod.py")
        assert rules_of(out) == ["RBK002", "RBK002", "RBK002"]

    def test_method_style_block_until_ready(self):
        out = lint("""
            def step(toks):
                toks.block_until_ready()
        """, path="runbookai_tpu/engine/mod.py")
        assert rules_of(out) == ["RBK002"]

    def test_same_code_outside_engine_ok(self):
        out = lint(self.SRC, path="runbookai_tpu/server/mod.py")
        assert out == []

    def test_np_asarray_of_host_value_ok(self):
        out = lint("""
            import numpy as np

            def step(hist):
                return np.asarray(hist[-2048:], dtype=np.int64)
        """, path="runbookai_tpu/engine/mod.py")
        assert out == []


# --------------------------------------------------------------------- RBK003


class TestRBK003:
    def test_sleep_open_subprocess_under_lock(self):
        out = lint("""
            import subprocess
            import time

            class Engine:
                def step(self):
                    with self._lock:
                        time.sleep(0.1)
                        fh = open("/tmp/x")
                        subprocess.run(["ls"])
        """)
        assert rules_of(out) == ["RBK003", "RBK003", "RBK003"]

    def test_io_outside_lock_ok(self):
        out = lint("""
            import time

            class Engine:
                def step(self):
                    time.sleep(0.1)
                    with self._lock:
                        self.n += 1
        """)
        assert "RBK003" not in rules_of(out)

    def test_async_lock_tracked(self):
        out = lint("""
            import time

            class Engine:
                async def step(self):
                    async with self._lock:
                        time.sleep(0.1)
        """)
        assert rules_of(out) == ["RBK003"]

    def test_def_nested_in_lock_block_not_flagged(self):
        # The nested body runs LATER, when the lock is no longer held.
        out = lint("""
            import time

            class Engine:
                def step(self):
                    with self._lock:
                        def callback():
                            time.sleep(0.1)
                        self.cb = callback
        """)
        assert "RBK003" not in rules_of(out)

    def test_non_lock_context_ok(self):
        out = lint("""
            import time

            class Engine:
                def step(self):
                    with self.tracer.span("s"):
                        time.sleep(0.1)
        """)
        assert out == []

    def test_block_named_context_is_not_a_lock(self):
        # KV "block" state everywhere in this codebase: substring matching
        # on "lock" must not classify block-named managers as locks.
        out = lint("""
            import time

            class Engine:
                def step(self):
                    with self.on_block:
                        time.sleep(0.1)
                    with self.block_pages_guard:
                        time.sleep(0.1)
        """)
        assert out == []

    def test_lock_word_segments_still_match(self):
        out = lint("""
            import time

            class Engine:
                def step(self):
                    with self.step_lock:
                        time.sleep(0.1)
        """)
        assert rules_of(out) == ["RBK003"]


# --------------------------------------------------------------------- RBK004


class TestRBK004:
    def test_mixed_lock_discipline_flagged(self):
        out = lint("""
            class Core:
                def locked(self):
                    with self._lock:
                        self.count = 1

                def unlocked(self):
                    self.count = 2
        """)
        assert rules_of(out) == ["RBK004"]
        assert "Core.count" in out[0].message

    def test_init_writes_exempt(self):
        out = lint("""
            class Core:
                def __init__(self):
                    self.count = 0

                def locked(self):
                    with self._lock:
                        self.count = 1
        """)
        assert out == []

    def test_consistent_discipline_ok(self):
        out = lint("""
            class Core:
                def a(self):
                    with self._lock:
                        self.count = 1

                def b(self):
                    with self._lock:
                        self.count += 2
        """)
        assert out == []


# --------------------------------------------------------------------- RBK005


class TestRBK005:
    def test_bad_name_and_missing_buckets(self):
        out = lint("""
            def install(reg):
                reg.counter("requests_total", "no prefix")
                reg.histogram("runbook_latency_seconds", "no buckets")
        """, path="runbookai_tpu/server/mod.py")
        assert rules_of(out) == ["RBK005", "RBK005"]

    def test_contract_compliant_ok(self):
        out = lint("""
            def install(reg):
                reg.counter("runbook_requests_total", "ok")
                reg.gauge("runbook_kv_pages_in_use", "ok")
                reg.histogram("runbook_ttft_seconds", "ok",
                              buckets=(0.1, 0.5, 1.0))
        """, path="runbookai_tpu/server/mod.py")
        assert out == []

    def test_positional_buckets_not_accepted(self):
        # utils/metrics.py takes buckets KEYWORD-ONLY; a third positional
        # arg is a runtime TypeError, not a bucket declaration.
        out = lint("""
            def install(reg):
                reg.histogram("runbook_x_seconds", "help", [0.1, 1.0])
        """)
        assert rules_of(out) == ["RBK005"]

    def test_dynamic_names_skipped(self):
        out = lint("""
            def install(reg, name):
                reg.counter(name, "runtime-checked")
        """)
        assert out == []

    def test_regex_matches_metrics_module_contract(self):
        from runbookai_tpu.analysis.rules import METRIC_NAME_RE as lint_re
        from runbookai_tpu.utils.metrics import METRIC_NAME_RE as runtime_re

        assert lint_re.pattern == runtime_re.pattern


# --------------------------------------------------------------------- RBK006


class TestRBK006:
    def test_print_in_hot_paths(self):
        for pkg in ("engine", "ops", "model", "models", "parallel"):
            out = lint("""
                def f(x):
                    print("debug", x)
            """, path=f"runbookai_tpu/{pkg}/mod.py")
            assert rules_of(out) == ["RBK006"], pkg

    def test_jax_debug_print(self):
        out = lint("""
            import jax

            def f(x):
                jax.debug.print("x={}", x)
        """, path="runbookai_tpu/ops/mod.py")
        assert rules_of(out) == ["RBK006"]

    def test_print_in_cli_ok(self):
        out = lint("""
            def f(x):
                print("user-facing", x)
        """, path="runbookai_tpu/cli/mod.py")
        assert out == []


# ----------------------------------------------------------------- noqa/parse


class TestSuppression:
    def test_same_line_noqa(self):
        out = lint("""
            def f(x):
                print(x)  # runbook: noqa[RBK006] — demo output
        """, path="runbookai_tpu/engine/mod.py")
        assert out == []

    def test_preceding_comment_block_noqa(self):
        out = lint("""
            import jax

            def step(toks):
                # runbook: noqa[RBK002] — sanctioned sync: the one token
                # fetch this dispatch is allowed.
                return jax.device_get(toks)
        """, path="runbookai_tpu/engine/mod.py")
        assert out == []

    def test_bare_noqa_suppresses_all(self):
        out = lint("""
            import jax

            def step(toks):
                jax.block_until_ready(toks)  # runbook: noqa
        """, path="runbookai_tpu/engine/mod.py")
        assert out == []

    def test_noqa_for_other_rule_does_not_suppress(self):
        out = lint("""
            def f(x):
                print(x)  # runbook: noqa[RBK001]
        """, path="runbookai_tpu/engine/mod.py")
        assert rules_of(out) == ["RBK006"]

    def test_unparseable_module_is_a_finding(self):
        out = lint("def f(:\n")
        assert rules_of(out) == ["RBK000"]

    def test_malformed_noqa_suppresses_nothing(self):
        # An unclosed bracket must NOT degrade to bare suppress-all.
        out = lint("""
            def f(x):
                print(x)  # runbook: noqa[RBK006
        """, path="runbookai_tpu/engine/mod.py")
        assert rules_of(out) == ["RBK006"]

    def test_noqa_ish_word_is_not_a_noqa(self):
        out = lint("""
            def f(x):
                print(x)  # runbook: noqa-ish note, not a suppression
        """, path="runbookai_tpu/engine/mod.py")
        assert rules_of(out) == ["RBK006"]

    def test_noqa_inside_string_literal_does_not_suppress(self):
        # Only real comments count — a string QUOTING the syntax (error
        # messages, fixtures) must not disable the gate for its statement.
        out = lint("""
            import jax

            def step(toks):
                msg = "# runbook: noqa[RBK002]"
                return jax.device_get(toks), msg
        """, path="runbookai_tpu/engine/mod.py")
        assert rules_of(out) == ["RBK002"]


# ------------------------------------------------------------------- baseline


class TestBaseline:
    def _findings(self):
        return lint("""
            def f(x):
                print(x)
                print(x)
        """, path="runbookai_tpu/engine/mod.py")

    def test_counts_and_roundtrip(self, tmp_path):
        found = self._findings()
        counts = baseline_counts(found)
        assert counts == {"runbookai_tpu/engine/mod.py:RBK006": 2}
        path = tmp_path / "baseline.json"
        write_baseline(path, found)
        assert load_baseline(path) == counts

    def test_new_findings_beyond_grandfathered_count(self):
        found = self._findings()
        baseline = {"runbookai_tpu/engine/mod.py:RBK006": 1}
        fresh = new_findings(found, baseline)
        # One finding is grandfathered (the earliest); the excess reports.
        assert len(fresh) == 1 and fresh[0].line == 4

    def test_baseline_fully_covers(self):
        found = self._findings()
        assert new_findings(found, baseline_counts(found)) == []

    def test_missing_baseline_is_empty(self, tmp_path):
        assert load_baseline(tmp_path / "nope.json") == {}

    def test_malformed_baseline_raises(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text('{"k": "not-an-int"}')
        with pytest.raises(ValueError):
            load_baseline(p)

    def test_parse_errors_are_never_baselined(self, tmp_path):
        broken = lint("def f(:\n", path="runbookai_tpu/engine/mod.py")
        path = tmp_path / "baseline.json"
        assert write_baseline(path, broken) == {}  # RBK000 excluded
        # Even a hand-edited baseline cannot grandfather a parse error.
        hand = {"runbookai_tpu/engine/mod.py:RBK000": 5}
        assert len(new_findings(broken, hand)) == 1

    def test_partial_update_preserves_other_files_keys(self, tmp_path):
        # Files a.py and b.py each carry one grandfathered finding; a
        # baseline update scoped to a.py must keep b.py's key.
        pkg = tmp_path / "engine"
        pkg.mkdir()
        for name in ("a.py", "b.py"):
            (pkg / name).write_text("def f(x):\n    print(x)\n")
        base = tmp_path / "baseline.json"
        from runbookai_tpu.analysis.cli import main as cli_main

        import contextlib
        import os

        with contextlib.ExitStack() as stack:
            cwd = os.getcwd()
            stack.callback(os.chdir, cwd)
            os.chdir(tmp_path)
            assert cli_main(["engine", "--update-baseline",
                             "--baseline", str(base)]) == 0
            assert cli_main(["engine", "--baseline", str(base)]) == 0
            # Narrow update over a.py only: b.py's key must survive.
            assert cli_main(["engine/a.py", "--update-baseline",
                             "--baseline", str(base)]) == 0
            assert cli_main(["engine", "--baseline", str(base)]) == 0


# ------------------------------------------------------------------ CLI gates


class TestCLI:
    def _tree(self, tmp_path, violate: bool):
        pkg = tmp_path / "engine"
        pkg.mkdir(parents=True)
        body = "def f(x):\n    print(x)\n" if violate else "def f(x):\n    return x\n"
        (pkg / "mod.py").write_text(body)
        return tmp_path

    def test_exit_codes(self, tmp_path, capsys):
        tree = self._tree(tmp_path, violate=True)
        assert lint_main([str(tree), "--no-baseline"]) == 1
        clean = self._tree(tmp_path / "ok", violate=False)
        assert lint_main([str(clean), "--no-baseline"]) == 0
        capsys.readouterr()

    def test_update_baseline_then_gate_passes(self, tmp_path, capsys, monkeypatch):
        tree = self._tree(tmp_path, violate=True)
        monkeypatch.chdir(tmp_path)
        base = tmp_path / "lint-baseline.json"
        assert lint_main([str(tree), "--update-baseline",
                          "--baseline", str(base)]) == 0
        assert lint_main([str(tree), "--baseline", str(base)]) == 0
        # A NEW violation on top of the baselined one fails the gate.
        (tree / "engine" / "mod.py").write_text(
            "def f(x):\n    print(x)\n    print(x)\n")
        assert lint_main([str(tree), "--baseline", str(base)]) == 1
        capsys.readouterr()

    def test_json_format(self, tmp_path, capsys):
        tree = self._tree(tmp_path, violate=True)
        assert lint_main([str(tree), "--no-baseline", "--format", "json"]) == 1
        data = json.loads(capsys.readouterr().out)
        assert data["new"] == 1
        assert data["findings"][0]["rule"] == "RBK006"

    def test_overlapping_paths_do_not_double_count(self, tmp_path):
        from runbookai_tpu.analysis import iter_python_files

        tree = self._tree(tmp_path, violate=True)
        files = iter_python_files([tree, tree / "engine",
                                   tree / "engine" / "mod.py"])
        assert len(files) == 1

    def test_gate_matches_baseline_from_any_cwd(self, tmp_path, capsys,
                                                monkeypatch):
        # Keys anchor to the baseline file's directory, so invoking from
        # an unrelated cwd with absolute paths still matches (and a
        # partial update from there must not drop existing keys).
        tree = self._tree(tmp_path, violate=True)
        base = tmp_path / "lint-baseline.json"
        monkeypatch.chdir(tmp_path)
        assert lint_main([str(tree / "engine"), "--update-baseline",
                          "--baseline", str(base)]) == 0
        monkeypatch.chdir("/")
        assert lint_main([str(tree / "engine"),
                          "--baseline", str(base)]) == 0
        assert lint_main([str(tree / "engine"), "--update-baseline",
                          "--baseline", str(base)]) == 0
        assert json.loads(base.read_text()) == {"engine/mod.py:RBK006": 1}
        capsys.readouterr()

    def test_missing_path_is_usage_error(self, capsys):
        assert lint_main(["definitely/not/a/path"]) == 2
        capsys.readouterr()

    def test_main_module_importable_without_side_effects(self):
        import importlib

        mod = importlib.import_module("runbookai_tpu.analysis.__main__")
        assert hasattr(mod, "main")  # no lint run / SystemExit on import

    def test_default_rules_are_fresh_per_call(self):
        # RBK004 aggregates per-walk state; repeated analyses must not
        # leak or share it across calls.
        src = """
            class Core:
                def locked(self):
                    with self._lock:
                        self.count = 1

                def unlocked(self):
                    self.count = 2
        """
        assert rules_of(lint(src)) == rules_of(lint(src)) == ["RBK004"]

    def test_runbook_cli_wires_lint(self, capsys):
        from runbookai_tpu.cli.main import build_parser

        args = build_parser().parse_args(
            ["lint", str(ROOT / "runbookai_tpu" / "analysis"),
             "--no-baseline"])
        assert args.fn(args) == 0
        assert "clean" in capsys.readouterr().out


# ------------------------------------------------- whole-program (PR 13)


def write_tree(tmp_path, files):
    """Write a fixture tree, creating ``__init__.py`` package markers in
    every intermediate directory — module names resolve from the on-disk
    package root, exactly like the real tree."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        d = p.parent
        while d != tmp_path:
            init = d / "__init__.py"
            if not init.exists():
                init.write_text("")
            d = d.parent
        p.write_text(textwrap.dedent(src))


def lint_tree(tmp_path, files):
    """Write a fixture tree and run the full two-phase analysis on it."""
    write_tree(tmp_path, files)
    return analyze_paths([tmp_path], root=tmp_path)


class TestCrossModuleRBK001:
    """The documented "same module only" gap is CLOSED: jit-reachability
    and traced-ness ride the project call graph. If these fixtures stop
    flagging, reachability regressed to per-file."""

    A = """
        import jax
        from pkg.b import helper, shape_helper

        @jax.jit
        def f(x):
            k = x.shape[0]
            return helper(x) + shape_helper(k)
    """
    B = """
        def helper(v):
            if v > 0:
                return v
            return -v

        def shape_helper(dim):
            if dim % 128 == 0:
                return dim
            return None
    """

    def test_jit_in_a_flags_branching_helper_in_b(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/a.py": self.A, "pkg/b.py": self.B})
        assert [(f.rule, f.path, f.symbol) for f in out] == \
            [("RBK001", "pkg/b.py", "helper")]

    def test_module_attribute_call_resolves(self, tmp_path):
        out = lint_tree(tmp_path, {
            "pkg/a.py": """
                import jax
                import pkg.b

                @jax.jit
                def f(x):
                    return pkg.b.helper(x)
            """,
            "pkg/b.py": self.B})
        assert [(f.rule, f.symbol) for f in out] == [("RBK001", "helper")]

    def test_static_args_stay_clean_cross_module(self, tmp_path):
        out = lint_tree(tmp_path, {
            "pkg/a.py": """
                import jax
                from pkg.b import shape_helper

                @jax.jit
                def f(x):
                    return x * shape_helper(x.shape[0])
            """,
            "pkg/b.py": self.B})
        assert out == []

    def test_per_file_pass_alone_misses_it(self, tmp_path):
        # Control: project=False reverts to the first-order analyzer —
        # proving the finding above comes from the call graph.
        write_tree(tmp_path, {"pkg/a.py": self.A, "pkg/b.py": self.B})
        assert analyze_paths([tmp_path], root=tmp_path, project=False) == []


class TestRBK007:
    def test_lock_order_cycle_flagged_both_sites(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/engine/locks.py": """
            import threading

            class A:
                def __init__(self, b: "B"):
                    self._lock = threading.Lock()
                    self.b = b

                def outer(self):
                    with self._lock:
                        self.b.poke()

                def inner(self):
                    with self._lock:
                        pass

            class B:
                def __init__(self, a: "A"):
                    self._lock = threading.Lock()
                    self.a = a

                def poke(self):
                    with self._lock:
                        pass

                def reverse(self):
                    with self._lock:
                        self.a.inner()
        """})
        assert [(f.rule, f.symbol) for f in out] == \
            [("RBK007", "A.outer"), ("RBK007", "B.reverse")]
        assert "lock-order cycle" in out[0].message

    def test_consistent_order_clean(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/engine/locks.py": """
            import threading

            class A:
                def __init__(self, b: "B"):
                    self._lock = threading.Lock()
                    self.b = b

                def outer(self):
                    with self._lock:
                        self.b.poke()

                def outer2(self):
                    with self._lock:
                        self.b.poke()

            class B:
                def __init__(self):
                    self._lock = threading.Lock()

                def poke(self):
                    with self._lock:
                        pass
        """})
        assert out == []

    def test_await_under_sync_lock(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/engine/aw.py": """
            import asyncio
            import threading

            class E:
                def __init__(self):
                    self._lock = threading.Lock()

                async def bad(self):
                    with self._lock:
                        await asyncio.sleep(0.1)

                async def good(self):
                    with self._lock:
                        snap = 1
                    await asyncio.sleep(snap)
        """})
        assert [(f.rule, f.symbol) for f in out] == [("RBK007", "E.bad")]
        assert "await" in out[0].message

    def test_async_with_lock_is_not_flagged(self, tmp_path):
        # asyncio.Lock held across await is its normal operation.
        out = lint_tree(tmp_path, {"pkg/engine/aw.py": """
            import asyncio

            class E:
                def __init__(self):
                    self._lock = asyncio.Lock()

                async def ok(self):
                    async with self._lock:
                        await asyncio.sleep(0.1)
        """})
        assert out == []

    def test_handoff_under_lock(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/engine/ho.py": """
            import asyncio
            import threading

            class E:
                def __init__(self):
                    self._lock = threading.Lock()

                async def bad(self, fn):
                    with self._lock:
                        await asyncio.to_thread(fn)

                async def good(self, fn):
                    with self._lock:
                        snap = fn
                    await asyncio.to_thread(snap)
        """})
        rules = [(f.rule, f.symbol) for f in out]
        assert ("RBK007", "E.bad") in rules
        assert all(sym == "E.bad" for _r, sym in rules)
        assert any("to_thread" in f.message for f in out)

    def test_run_locked_under_lock(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/fleet/rl.py": """
            import threading

            class Router:
                def __init__(self, eng):
                    self._lock = threading.Lock()
                    self.eng = eng

                async def bad(self):
                    with self._lock:
                        await self.eng.run_locked(lambda: 1)
        """})
        assert any("run_locked" in f.message and f.rule == "RBK007"
                   for f in out)

    def test_same_instance_reacquisition(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/engine/re.py": """
            import threading

            class E:
                def __init__(self):
                    self._lock = threading.Lock()

                def helper(self):
                    with self._lock:
                        pass

                def reenter(self):
                    with self._lock:
                        self.helper()
        """})
        assert [(f.rule, f.symbol) for f in out] == \
            [("RBK007", "E.reenter")]
        assert "re-enters" in out[0].message

    def test_cross_instance_same_class_clean(self, tmp_path):
        # Two DIFFERENT instances of one class lock sequentially — the
        # (class, attr) ids collide but no same-instance deadlock exists.
        out = lint_tree(tmp_path, {"pkg/engine/xi.py": """
            import threading

            class E:
                def __init__(self, peer: "E"):
                    self._lock = threading.Lock()
                    self.peer = peer

                def helper(self):
                    with self._lock:
                        pass

                def poke_peer(self):
                    with self._lock:
                        self.peer.helper()
        """})
        assert out == []

    def test_noqa_suppresses(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/engine/nq.py": """
            import threading

            class E:
                def __init__(self):
                    self._lock = threading.Lock()

                def helper(self):
                    with self._lock:
                        pass

                def reenter(self):
                    with self._lock:
                        # runbook: noqa[RBK007] — RLock at runtime
                        self.helper()
        """})
        assert out == []


class TestRBK008:
    RACE = """
        import asyncio
        import threading

        class Core:
            def __init__(self):
                self.epoch = 0

            def bump(self):
                self.epoch += 1

        class Front:
            def __init__(self, core: Core):
                self._lock = threading.Lock()
                self.core = core

            async def submit(self):
                {submit_body}

            async def run(self):
                await asyncio.to_thread(self._step)

            def _step(self):
                with self._lock:
                    self.core.bump()
    """

    def test_unlocked_cross_entry_write_flagged(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/engine/sh.py": self.RACE.format(
            submit_body="self.core.bump()")})
        assert [(f.rule, f.symbol) for f in out] == \
            [("RBK008", "Core.bump")]
        assert "Core.epoch" in out[0].message
        assert "event-loop" in out[0].message

    def test_common_lock_clean(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/engine/sh.py": self.RACE.format(
            submit_body="""with self._lock:
                    self.core.bump()""")})
        assert out == []

    def test_single_role_clean(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/engine/sh.py": """
            import asyncio

            class Core:
                def __init__(self):
                    self.epoch = 0

                async def a(self):
                    self.epoch += 1

                async def b(self):
                    self.epoch = 0
        """})
        assert out == []

    def test_ctor_writes_exempt_and_non_audited_pkg_clean(self, tmp_path):
        # Same race shape, but the class lives outside the audited
        # engine/fleet/sched/obs/server packages.
        out = lint_tree(tmp_path, {"pkg/agentx/sh.py": self.RACE.format(
            submit_body="self.core.bump()")})
        assert out == []


class TestRBK009:
    def test_direct_blocking_in_async_body(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/server/s.py": """
            import time

            async def handler():
                time.sleep(0.5)
                fh = open("/tmp/x")
        """})
        assert [f.rule for f in out] == ["RBK009", "RBK009"]

    def test_one_hop_sync_helper_flagged_at_call_site(self, tmp_path):
        out = lint_tree(tmp_path, {
            "pkg/server/s.py": """
                from pkg.server.util import slow_helper

                async def handler():
                    slow_helper()
            """,
            "pkg/server/util.py": """
                import time

                def slow_helper():
                    time.sleep(1.0)
            """})
        flagged = [(f.rule, f.path, f.symbol) for f in out]
        assert ("RBK009", "pkg/server/s.py", "handler") in flagged
        assert any("slow_helper" in f.message for f in out)

    def test_bare_lock_acquire(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/fleet/l.py": """
            class R:
                async def bad(self):
                    self._lock.acquire()

                async def ok(self):
                    self._lock.acquire(timeout=0.5)
        """})
        assert [(f.rule, f.symbol) for f in out] == [("RBK009", "R.bad")]

    def test_sync_def_and_other_packages_clean(self, tmp_path):
        out = lint_tree(tmp_path, {
            "pkg/server/s.py": """
                import time

                def sync_handler():
                    time.sleep(0.5)
            """,
            "pkg/cli/c.py": """
                import time

                async def cli_cmd():
                    time.sleep(0.5)
            """})
        assert out == []


class TestRBK010:
    def test_unbounded_label_flagged(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/obs/m.py": """
            def install(reg, name):
                m = reg.counter("runbook_x_total", "h", labels=("k",))
                m.labels(k=name).inc()
        """})
        assert [f.rule for f in out] == ["RBK010"]
        assert "k" in out[0].message

    def test_bounded_forms_clean(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/obs/m.py": """
            from pkg.obs.names import KINDS

            LOCAL = ("x", "y")
            NAMES = {1: "one", 2: "two"}


            def canonical(n):
                return NAMES.get(n, "other")


            def install(reg, name, n):
                m = reg.counter("runbook_x_total", "h", labels=("k",))
                m.labels(k="const").inc()
                for k in LOCAL:
                    m.labels(k=k).inc()
                for k in KINDS:
                    m.labels(k=k).inc()
                m.labels(k=name if name in KINDS else "other").inc()
                m.labels(k=canonical(n)).inc()
                m.labels(k=str(canonical(n))).inc()
                pick = "a" if n else "b"
                m.labels(k=pick).inc()
        """, "pkg/obs/names.py": """
            KINDS = frozenset({"a", "b", "c"})
        """})
        assert out == []

    def test_literal_param_and_callsite_propagation(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/obs/m.py": """
            from typing import Literal


            def record(reg, kind: Literal["hit", "miss"]):
                reg.counter("runbook_k_total", "h",
                            labels=("kind",)).labels(kind=kind).inc()


            def record2(reg, kind):
                reg.counter("runbook_k2_total", "h",
                            labels=("kind",)).labels(kind=kind).inc()


            def caller(reg):
                record2(reg, "hit")
                record2(reg, "miss")
        """})
        assert out == []

    def test_unbounded_callsite_breaks_propagation(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/obs/m.py": """
            def record2(reg, kind):
                reg.counter("runbook_k2_total", "h",
                            labels=("kind",)).labels(kind=kind).inc()


            def caller(reg, user_value):
                record2(reg, "hit")
                record2(reg, user_value)
        """})
        assert [f.rule for f in out] == ["RBK010"]

    def test_instance_attr_unbounded_needs_noqa(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/fleet/m.py": """
            class F:
                def __init__(self, model):
                    self.model = model

                def install(self, reg):
                    m = reg.counter("runbook_m_total", "h",
                                    labels=("model",))
                    m.labels(model=self.model).inc()

                def install_ok(self, reg):
                    m = reg.counter("runbook_m2_total", "h",
                                    labels=("model",))
                    # runbook: noqa[RBK010] — model fixed at build
                    m.labels(model=self.model).inc()
        """})
        assert [(f.rule, f.symbol) for f in out] == \
            [("RBK010", "F.install")]


class TestDeterminism:
    FILES = {
        "pkg/engine/a.py": """
            import threading

            class A:
                def __init__(self):
                    self._lock = threading.Lock()

                def helper(self):
                    with self._lock:
                        pass

                def reenter(self):
                    with self._lock:
                        self.helper()
        """,
        "pkg/server/s.py": """
            import time

            async def handler():
                time.sleep(0.5)
        """,
        "pkg/obs/m.py": """
            def install(reg, name):
                reg.counter("runbook_x_total", "h",
                            labels=("k",)).labels(k=name).inc()
        """,
        "pkg/b.py": """
            def helper(v):
                if v > 0:
                    return v
                return -v
        """,
        "pkg/a.py": """
            import jax
            from pkg.b import helper

            @jax.jit
            def f(x):
                return helper(x)
        """,
    }

    def _dump(self, findings):
        from runbookai_tpu.analysis import finding_fingerprints

        rows = [f.to_json() for f in findings]
        for row, fp in zip(rows, finding_fingerprints(findings)):
            row["fingerprint"] = fp
        return json.dumps(rows, sort_keys=True)

    def test_shuffled_input_order_is_byte_identical(self, tmp_path):
        write_tree(tmp_path, self.FILES)
        files = [tmp_path / rel for rel in self.FILES]
        runs = []
        for order in (files, list(reversed(files)),
                      files[2:] + files[:2], [tmp_path]):
            runs.append(self._dump(analyze_paths(order, root=tmp_path)))
        assert len(set(runs)) == 1
        assert json.loads(runs[0]), "fixture tree must produce findings"

    def test_repeated_runs_identical(self, tmp_path):
        write_tree(tmp_path, self.FILES)
        a = self._dump(analyze_paths([tmp_path], root=tmp_path))
        b = self._dump(analyze_paths([tmp_path], root=tmp_path))
        assert a == b


class TestFingerprints:
    def test_line_move_tolerant(self):
        from runbookai_tpu.analysis import finding_fingerprints

        src = """
            def f(x):
                print(x)
        """
        moved = "\n\n\n# a comment\n" + textwrap.dedent(src)
        a = lint(src)
        b = analyze_source(moved, "runbookai_tpu/engine/mod.py")
        assert a[0].line != b[0].line
        assert finding_fingerprints(a) == finding_fingerprints(b)

    def test_second_finding_in_symbol_gets_new_fingerprint(self):
        from runbookai_tpu.analysis import finding_fingerprints

        out = lint("""
            def f(x):
                print(x)
                print(x)
        """)
        fps = finding_fingerprints(out)
        assert len(fps) == 2 and fps[0] != fps[1]

    def test_symbol_recorded(self):
        out = lint("""
            class C:
                def f(self, x):
                    print(x)
        """)
        assert out[0].symbol == "C.f"
        assert out[0].to_json()["symbol"] == "C.f"


class TestFormatsAndChanged:
    def _tree(self, tmp_path):
        pkg = tmp_path / "engine"
        pkg.mkdir(parents=True, exist_ok=True)
        (pkg / "mod.py").write_text("def f(x):\n    print(x)\n")
        return tmp_path

    def test_json_rows_carry_severity_symbol_fingerprint(self, tmp_path,
                                                         capsys):
        tree = self._tree(tmp_path)
        assert lint_main([str(tree), "--no-baseline",
                          "--format", "json"]) == 1
        data = json.loads(capsys.readouterr().out)
        row = data["findings"][0]
        assert row["severity"] == "warning"
        assert row["symbol"] == "f"
        assert len(row["fingerprint"]) == 16
        int(row["fingerprint"], 16)  # hex

    def test_sarif_minimal_shape(self, tmp_path, capsys):
        tree = self._tree(tmp_path)
        assert lint_main([str(tree), "--no-baseline",
                          "--format", "sarif"]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
        assert ids == sorted(ids)
        assert {"RBK000", "RBK001", "RBK006", "RBK007", "RBK008",
                "RBK009", "RBK010"} <= set(ids)
        res = run["results"][0]
        assert res["ruleId"] == "RBK006"
        assert res["level"] == "warning"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("engine/mod.py")
        assert loc["region"]["startLine"] >= 1
        assert res["partialFingerprints"]["runbookLint/v1"]

    def test_changed_filters_to_git_modified_files(self, tmp_path,
                                                   capsys, monkeypatch):
        import subprocess

        def git(*args):
            r = subprocess.run(["git", *args], cwd=tmp_path,
                               capture_output=True, text=True)
            assert r.returncode == 0, r.stderr
            return r

        tree = self._tree(tmp_path)
        monkeypatch.chdir(tmp_path)
        git("init", "-q")
        git("-c", "user.email=t@t", "-c", "user.name=t",
            "add", ".")
        git("-c", "user.email=t@t", "-c", "user.name=t",
            "commit", "-qm", "seed")
        # Clean work tree: the committed violation is NOT reported.
        assert lint_main(["engine", "--no-baseline", "--changed"]) == 0
        capsys.readouterr()
        # A new violating file IS reported; the committed one stays out.
        (tmp_path / "engine" / "new.py").write_text(
            "def g(x):\n    print(x)\n")
        assert lint_main(["engine", "--no-baseline", "--changed"]) == 1
        out = capsys.readouterr().out
        assert "new.py" in out and "mod.py" not in out

    def test_changed_outside_git_is_usage_error(self, tmp_path, capsys,
                                                monkeypatch):
        import unittest.mock as mock

        tree = self._tree(tmp_path)
        monkeypatch.chdir(tmp_path)
        with mock.patch("runbookai_tpu.analysis.cli._git_changed_paths",
                        return_value=None):
            assert lint_main(["engine", "--no-baseline", "--changed"]) == 2
        assert "git" in capsys.readouterr().out

    def test_changed_sees_files_in_untracked_directories(self, tmp_path,
                                                         capsys,
                                                         monkeypatch):
        # `git status --porcelain` collapses a new directory to one
        # "?? newpkg/" line; without -uall the files inside would slip
        # past the .py filter — the exact new-package pre-commit case.
        import subprocess

        def git(*args):
            r = subprocess.run(["git", *args], cwd=tmp_path,
                               capture_output=True, text=True)
            assert r.returncode == 0, r.stderr
            return r

        self._tree(tmp_path)
        monkeypatch.chdir(tmp_path)
        git("init", "-q")
        git("-c", "user.email=t@t", "-c", "user.name=t", "add", ".")
        git("-c", "user.email=t@t", "-c", "user.name=t",
            "commit", "-qm", "seed")
        newpkg = tmp_path / "newpkg" / "engine"
        newpkg.mkdir(parents=True)
        (newpkg / "mod.py").write_text("def h(x):\n    print(x)\n")
        assert lint_main(["newpkg", "--no-baseline", "--changed"]) == 1
        assert "newpkg/engine/mod.py" in capsys.readouterr().out


class TestReviewRegressions:
    """Pins for the scanner/driver defects the PR-13 review pass found."""

    def test_lambda_body_is_not_the_enclosing_context(self, tmp_path):
        # `to_thread(lambda: time.sleep(...))` is RBK009's own recommended
        # remediation — the lambda runs on a worker thread, not the loop.
        out = lint_tree(tmp_path, {"pkg/server/s.py": """
            import asyncio
            import time

            async def handler():
                await asyncio.to_thread(lambda: time.sleep(1.0))
        """})
        assert out == []

    def test_relative_import_in_package_init_resolves(self, tmp_path):
        # `from .b import helper` inside pkg/__init__.py anchors at pkg
        # itself (the __init__ IS its package) — a dropped component here
        # silently unlinked every call edge through a package __init__.
        out = lint_tree(tmp_path, {
            "pkg/__init__.py": """
                import jax
                from .b import helper

                @jax.jit
                def f(x):
                    return helper(x)
            """,
            "pkg/b.py": """
                def helper(v):
                    if v > 0:
                        return v
                    return -v
            """})
        assert [(f.rule, f.path) for f in out] == [("RBK001", "pkg/b.py")]

    def test_module_level_label_site_is_checked(self, tmp_path):
        out = lint_tree(tmp_path, {"pkg/obs/m.py": """
            import os

            _M = REG.counter("runbook_x_total", "h", labels=("k",))
            _M.labels(k=os.environ["USER"]).inc()
            _M.labels(k="const").inc()
        """})
        assert [(f.rule, f.symbol) for f in out] == \
            [("RBK010", "<module>")]

    def test_absolute_path_invocation_still_links_cross_module(
            self, tmp_path, capsys, monkeypatch):
        # Module names come from the on-disk package root, not the display
        # path: an absolute-path --no-baseline run from a foreign cwd must
        # resolve the same import graph as an in-repo run — degrading to
        # per-file analysis would print "clean" on code it never linked.
        write_tree(tmp_path, {
            "pkg/a.py": TestCrossModuleRBK001.A,
            "pkg/b.py": TestCrossModuleRBK001.B})
        monkeypatch.chdir("/")
        assert lint_main([str(tmp_path), "--no-baseline"]) == 1
        assert "RBK001" in capsys.readouterr().out


# ---------------------------------------------------------------- integration


class TestTreeIsClean:
    def test_package_has_no_new_findings(self):
        """Tier-1 gate: the whole package analyzes clean against the
        committed baseline. If this fails, either fix the finding, annotate
        the sanctioned exception with `# runbook: noqa[RULE] — reason`, or
        (pre-existing debt only) regenerate via scripts/lint.py
        --update-baseline."""
        findings = analyze_paths([ROOT / "runbookai_tpu"], root=ROOT)
        baseline = load_baseline(ROOT / "lint-baseline.json")
        fresh = new_findings(findings, baseline)
        assert fresh == [], "\n".join(f.format() for f in fresh)

    def test_engine_noqa_annotations_carry_reasons(self):
        """Sanctioned engine syncs must say WHY (a bare noqa rots)."""
        src = (ROOT / "runbookai_tpu" / "engine" / "engine.py").read_text()
        for line in src.splitlines():
            if "noqa[RBK002]" in line:
                comment = line.split("#", 1)[1]
                assert "—" in comment and len(comment.strip()) > 25, line

    @staticmethod
    def _rbk002_sites(path):
        """Map each noqa[RBK002] annotation to its enclosing function."""
        import re

        sites: dict = {}
        fn = None
        for line in path.read_text().splitlines():
            m = re.match(r"\s*def (\w+)", line)
            if m:
                fn = m.group(1)
            if "noqa[RBK002]" in line:
                sites[fn] = sites.get(fn, 0) + 1
        return sites

    def test_rbk002_inventory_pinned(self):
        """The sanctioned-sync inventory is load-bearing: the overlapped
        decode pipeline's contract is that the ASYNC EGRESS CONSUMPTION
        POINT (`_fetch_tokens`) is the single token fetch in the decode
        loop — every decode path (lagged drain, forced-sync, guided k=1,
        speculative verify) consumes tokens through it. A new annotation
        anywhere else in the loop means a second host sync crept back in;
        update docs/lint.md and this pin only with a design reason."""
        engine = self._rbk002_sites(
            ROOT / "runbookai_tpu" / "engine" / "engine.py")
        assert engine == {
            # Once-per-process Mosaic probe barriers:
            "_probe_pallas_attn_cached": 3,
            "_probe_pallas_attn_int8_cached": 1,
            "_probe_qmm_pallas_cached": 1,
            "_probe_pallas_ragged_cached": 1,
            # Per-prefill-dispatch first-token fetch (TTFT emission):
            "_run_prefill": 1,
            # Per-mixed-dispatch first-token fetch: same TTFT emission
            # point as _run_prefill's, for prefill rows that complete
            # inside a unified mixed dispatch (decode rows stay in the
            # async-egress window and never add a sync):
            "_run_mixed": 1,
            # Logprob triple fetch ([B, K+1], logprob requests only):
            "_append_logprob_entries": 1,
            # THE decode-loop token fetch (async egress consumption):
            "_fetch_tokens": 1,
            # Recorder on only, inside the fetch phase of the three fetches
            # above: a dispatch issued BEFORE the one being fetched and not
            # yet known ready (a prefill chunk nothing fetches, the window
            # in flight under a first-token fetch) is waited for first, so
            # that it gets a ready stamp of its own. One device runs the
            # dispatches in order: the fetch waits for it in any case, so
            # no wait is added, only split in two (docs/lint.md).
            "_fetching": 1,
        }, engine
        draft = self._rbk002_sites(
            ROOT / "runbookai_tpu" / "engine" / "draft.py")
        assert draft == {"draft": 1}, draft
        # The page-transfer path (fleet-wide KV sharing / disagg handoff /
        # spill capture) funnels every device→host copy through ONE
        # sanctioned fetch helper: export_pages and spill_evictable both
        # call _fetch_rows, so a second annotation in this module means a
        # transfer path stopped batching its copy.
        kv_cache = self._rbk002_sites(
            ROOT / "runbookai_tpu" / "engine" / "kv_cache.py")
        assert kv_cache == {"_fetch_rows": 1}, kv_cache
        # The fleet router itself stays HOST-ONLY code: routing reads the
        # replicas' prefix-cache indexes and pool counters, never device
        # state, and a planned page pull executes through the engines'
        # export/import APIs (whose sync is the kv_cache._fetch_rows site
        # above, under the source engine's lock in a worker thread) — a
        # noqa[RBK002] appearing in fleet.py would mean the router started
        # syncing the device inline on the placement path. RBK004 lock
        # discipline covers the module through the standard engine/ tag
        # (fleet.py's shared router state mutates only under
        # AsyncFleet._lock).
        fleet = self._rbk002_sites(
            ROOT / "runbookai_tpu" / "engine" / "fleet.py")
        assert fleet == {}, fleet

    @staticmethod
    def _noqa_sites(path, rule):
        """Map each noqa[RULE] annotation to its (nearest) enclosing def."""
        import re

        sites: dict = {}
        fn = None
        for line in path.read_text().splitlines():
            m = re.match(r"\s*(?:async )?def (\w+)", line)
            if m:
                fn = m.group(1)
            if f"noqa[{rule}]" in line:
                sites[fn] = sites.get(fn, 0) + 1
        return sites

    def _package_noqa_is_rbk010_only(self, pkg):
        """Control-path packages sanction NOTHING except the RBK010
        label-identity sites pinned below: a noqa for any other rule
        appearing means control code started doing data-path work
        (device syncs, blocking under locks)."""
        import re

        files = sorted((ROOT / "runbookai_tpu" / pkg).glob("*.py"))
        assert files, f"{pkg} package missing"
        for path in files:
            for m in re.finditer(r"noqa\[([A-Z0-9]+)\]", path.read_text()):
                assert m.group(1) == "RBK010", (
                    f"unexpected noqa[{m.group(1)}] in {path}")
        findings = analyze_paths([ROOT / "runbookai_tpu" / pkg], root=ROOT)
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_fleet_package_noqa_is_rbk010_only(self):
        self._package_noqa_is_rbk010_only("fleet")

    def test_obs_package_noqa_is_rbk010_only(self):
        self._package_noqa_is_rbk010_only("obs")

    def test_sched_package_noqa_is_rbk010_only(self):
        self._package_noqa_is_rbk010_only("sched")

    def test_chaos_package_has_zero_noqa_sites(self):
        """chaos/ sanctions NOTHING — zero runbook-noqa markers of any
        rule: its supervisor/injector threading is exactly what the
        RBK007–010 concurrency rules exist to check, and its metric
        labels are designed statically bounded (state/kind literal
        tuples; per-replica detail lives in the /healthz supervisor
        block, not in label values)."""
        import re

        files = sorted((ROOT / "runbookai_tpu" / "chaos").glob("*.py"))
        assert files, "chaos package missing"
        for path in files:
            assert not re.search(r"noqa\[[A-Z0-9]+\]", path.read_text()), (
                f"unexpected runbook noqa in {path}")
        findings = analyze_paths([ROOT / "runbookai_tpu" / "chaos"],
                                 root=ROOT)
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_rbk010_inventory_pinned(self):
        """Every RBK010 suppression documents a label whose value set is
        bounded at RUNTIME by config or registration (group names, replica
        ids, tenant policies, SLO objectives, registered tools) — the
        static analyzer cannot see that, so the noqa + reason IS the
        pinned allowlist. A new annotation anywhere else means a metric
        label started following request-derived values; fix the label
        (membership-guarded fallback, `class_label` idiom) instead of
        widening this pin."""
        expected = {
            "engine/fleet.py": {"_route": 2, "_disagg_warm": 1,
                                "_install_metrics": 10},
            "fleet/multimodel.py": {"_install_metrics": 1},
            # Attribution is nearest-preceding-def: monitor's sites sit
            # after the nested fp_value/drift_or_raise helpers.
            "obs/monitor.py": {"fp_value": 1, "drift_or_raise": 3},
            # Incident detection sanctions NOTHING: the signal label is
            # the INCIDENT_SIGNALS literal tuple (bounded statically,
            # like the supervisor's state label).
            "obs/detect.py": {},
            "obs/incident.py": {},
            # The history layer sanctions nothing either: tsdb
            # self-accounting metrics are unlabeled, and the query
            # evaluator registers no metrics at all.
            "obs/tsdb.py": {},
            "obs/query.py": {},
            "sched/feedback.py": {"on_step": 1},
            "sched/tenants.py": {"__init__": 2, "admit": 2,
                                 "_throttle_metrics": 1, "settle": 1},
            "utils/slo.py": {"__init__": 4, "_burn_or_raise": 1,
                             "evaluate": 1},
            "agent/agent.py": {"_execute_calls": 1},
            "agent/parallel_executor.py": {"_execute_one": 4},
            # The server's status label is FIXED in code (allowlist +
            # "other" fallback), not suppressed.
            "server/openai_api.py": {},
        }
        for rel, sites in expected.items():
            got = self._noqa_sites(ROOT / "runbookai_tpu" / rel, "RBK010")
            assert got == sites, (rel, got)

    def test_rbk010_annotations_carry_reasons(self):
        """Every RBK010 suppression says WHY the set is bounded."""
        for rel in ("engine/fleet.py", "fleet/multimodel.py",
                    "obs/monitor.py", "sched/feedback.py",
                    "sched/tenants.py", "utils/slo.py", "agent/agent.py",
                    "agent/parallel_executor.py"):
            src = (ROOT / "runbookai_tpu" / rel).read_text()
            for line in src.splitlines():
                if "noqa[RBK010]" in line:
                    comment = line.split("#", 1)[1]
                    assert "—" in comment, (rel, line)
