"""bench.py pieces that must never regress: the MFU peak-FLOPs mapping, the
one-line result format, the refusal to measure without a TPU, and every arm
running end to end on the tiny CPU model."""

import json
import os
import subprocess
import sys

import pytest

from bench import emit, peak_flops_per_chip


def test_peak_flops_mapping():
    assert peak_flops_per_chip("TPU v5e") == 197e12
    assert peak_flops_per_chip("TPU v5 lite") == 197e12
    assert peak_flops_per_chip("TPU v5p") == 459e12
    assert peak_flops_per_chip("TPU v4") == 275e12
    assert peak_flops_per_chip("TPU v6 lite") == 918e12
    assert peak_flops_per_chip("weird accelerator") is None


def test_emit_is_one_json_line(capsys):
    emit(1.5, "tok/s", {"model": "x"})
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    parsed = json.loads(out[0])
    assert parsed["metric"] == "decode_tokens_per_sec_per_chip"
    assert parsed["vs_baseline"] == 1.0


def test_bench_refuses_to_measure_without_a_tpu():
    """`python bench.py` on a machine where JAX finds no TPU exits non-zero
    and prints no result line: a CPU figure under the metric's name is
    what the deleted fallback used to produce. The CPU must be asked for
    by name (--cpu; the arms below run that way in-process)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(root, "bench.py")],
                         capture_output=True, text=True, timeout=120,
                         env=env, cwd=root)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "--cpu" in out.stderr


def test_failed_run_raises_and_prints_no_result(tmp_path, monkeypatch,
                                                capsys):
    """A run that fails raises out of run_inner; at the parent commit it
    printed `value: 0.0` with the error tucked into details and exited 0."""
    import bench as bench_mod

    monkeypatch.setenv("BENCH_PLAN", str(tmp_path / "no-such-plan.json"))
    with pytest.raises((OSError, ValueError)):
        bench_mod.run_inner("llama3-test", False,
                            {"platform": "cpu", "kind": "cpu", "n": 1})
    assert capsys.readouterr().out.strip() == ""


def test_weights_discovery_and_quality_marker(tmp_path, monkeypatch):
    from runbookai_tpu.utils.weights import (
        QUALITY_UNMEASURED,
        discover_weights,
        quality_marker,
    )

    monkeypatch.delenv("RUNBOOK_WEIGHTS", raising=False)
    assert discover_weights("llama3-8b-instruct") is None
    assert quality_marker(None) == QUALITY_UNMEASURED

    # Parent-of-models layout wins over the root itself.
    (tmp_path / "llama3-8b-instruct").mkdir()
    monkeypatch.setenv("RUNBOOK_WEIGHTS", str(tmp_path))
    assert discover_weights("llama3-8b-instruct") == str(
        tmp_path / "llama3-8b-instruct")
    assert discover_weights("other-model") == str(tmp_path)
    # Configured path beats the env var.
    cfgd = tmp_path / "explicit"
    cfgd.mkdir()
    assert discover_weights("llama3-8b-instruct", str(cfgd)) == str(cfgd)
    assert "real weights" in quality_marker(str(cfgd))


def test_bench_smoke_executes_ab_flags(monkeypatch, capsys):
    """The --no-mixed / --no-overlap A/B arms must actually RUN end-to-end
    on the tiny CPU model (not just parse), so the flags can't bit-rot
    before a chip run. Forced-sync + split-dispatch arm first, then
    mixed+overlap forced ON with a prompt long enough to mix — the
    details must carry the resolved modes and the dispatch attribution."""
    import bench as bench_mod

    # BENCH_NEW spans several k=8 decode windows so the second request's
    # prefill chunks land while the first still decodes (the mix window).
    for var, val in (("BENCH_REQUESTS", "2"), ("BENCH_PROMPT", "160"),
                     ("BENCH_NEW", "48"), ("BENCH_SLOTS", "2"),
                     ("BENCH_PAGES", "64"), ("BENCH_PREFILL_BATCH", "1"),
                     ("BENCH_BGE", "0"), ("BENCH_GUIDED", "0")):
        monkeypatch.setenv(var, val)
    probe = {"ok": True, "platform": "cpu", "kind": "cpu", "n": 1}

    monkeypatch.setenv("BENCH_OVERLAP", "0")  # what --no-overlap sets
    monkeypatch.setenv("BENCH_MIXED", "0")    # what --no-mixed sets
    bench_mod.run_inner("llama3-test", False, probe)
    off = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    d = off["details"]
    assert "error" not in d, d
    assert off["value"] > 0
    assert d["overlap"] is False and d["mixed"] is False
    assert d["mixed_dispatches"] == 0
    assert d["prefill_dispatches"] > 0 and d["decode_dispatches"] > 0

    monkeypatch.setenv("BENCH_OVERLAP", "1")
    monkeypatch.setenv("BENCH_MIXED", "1")
    bench_mod.run_inner("llama3-test", False, probe)
    on = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    d = on["details"]
    assert "error" not in d, d
    assert on["value"] > 0
    assert d["overlap"] is True and d["mixed"] is True
    # 160-token prompts over 128-token chunks with prefill_batch=1: the
    # second request's chunks land while the first decodes → mixed steps.
    assert d["mixed_dispatches"] > 0
    assert d["mixed_tokens_per_dispatch"] > 0


def test_bench_two_class_smoke_executes_both_arms(monkeypatch, capsys):
    """The two-class flood arm (BENCH_CLASSES / --classes) must RUN end
    to end on the tiny CPU model in BOTH its scheduler and FIFO arms,
    bank per-class TTFT/TPOT + the acceptance ratio + throttle/shed
    counts, and produce byte-identical per-class digests across arms
    (scheduling reorders admits, never alters a stream)."""
    import bench as bench_mod

    for var, val in (("BENCH_PROMPT", "48"), ("BENCH_NEW", "12"),
                     ("BENCH_SLOTS", "2"), ("BENCH_PAGES", "128"),
                     ("BENCH_CLASSES", "1"), ("BENCH_BATCH_REQS", "6"),
                     ("BENCH_INT_REQS", "2"), ("BENCH_BGE", "0"),
                     ("BENCH_GUIDED", "0")):
        monkeypatch.setenv(var, val)
    probe = {"ok": True, "platform": "cpu", "kind": "cpu", "n": 1}

    arms = {}
    for arm, sched in (("sched", "1"), ("fifo", "0")):
        monkeypatch.setenv("BENCH_SCHED", sched)
        bench_mod.run_inner("llama3-test", False, probe)
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        d = out["details"]
        assert "error" not in d, d
        assert d["arm"] == arm
        for cls in ("interactive", "batch"):
            stats = d["classes"][cls]
            assert stats["requests"] > 0
            assert stats["p95_ttft_ms"] is not None
            assert stats["outputs_digest"]
        assert d["flood_free_interactive"]["p95_ttft_ms"] is not None
        assert d["interactive_ttft_ratio"] is not None
        assert "throttled_total" in d and "shed_total" in d
        # Scheduler fairness evidence rides the flight summary.
        assert "class_slot_steps" in d["flight_summary"]
        arms[arm] = d
    # --classes refuses to compose with --dp (it would silently measure
    # a single core labeled as the requested fleet).
    monkeypatch.setenv("BENCH_DP", "2")
    with pytest.raises(ValueError, match="does not compose"):
        bench_mod.run_bench("llama3-test", False, probe)
    monkeypatch.delenv("BENCH_DP")

    # Byte parity per class across arms: same prompts, same tokens.
    for cls in ("interactive", "batch"):
        assert (arms["sched"]["classes"][cls]["outputs_digest"]
                == arms["fifo"]["classes"][cls]["outputs_digest"])
    # The A/B direction: interactive TTFT under the flood degrades less
    # with the scheduler than under FIFO (<= tolerates timer noise on a
    # loaded CI box; the full protocol ratios live in BENCHLOG r9).
    assert (arms["sched"]["interactive_ttft_ratio"]
            <= arms["fifo"]["interactive_ttft_ratio"])


def test_bench_shift_smoke_drift_crosses_and_digests_match(monkeypatch,
                                                           capsys):
    """The --shift arm (ROADMAP item 3's scenario) must RUN on the tiny
    CPU model: the short-chat → long-context/guided shift pushes
    `drift_phase2` past the stale threshold while `drift_phase1` stays
    under it, and the output digest is byte-identical to a BENCH_OBS=0 run —
    fingerprinting observes, it never touches a stream."""
    import bench as bench_mod

    for var, val in (("BENCH_REQUESTS", "2"), ("BENCH_PROMPT", "48"),
                     ("BENCH_NEW", "12"), ("BENCH_SLOTS", "2"),
                     ("BENCH_PAGES", "128"), ("BENCH_SHIFT", "1"),
                     ("BENCH_BGE", "0"), ("BENCH_GUIDED", "0")):
        monkeypatch.setenv(var, val)
    probe = {"ok": True, "platform": "cpu", "kind": "cpu", "n": 1}

    digests = {}
    for obs in ("1", "0"):
        monkeypatch.setenv("BENCH_OBS", obs)
        bench_mod.run_inner("llama3-test", False, probe)
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        d = out["details"]
        assert "error" not in d, d
        assert d["arm"] == "shift"
        digests[obs] = d["outputs_digest"]
        if obs == "1":
            wl = d["workload"]
            # A real measured-vs-nominal comparison: small, under the
            # threshold — not a score(x, x) tautology.
            assert wl["drift_phase1"] is not None
            assert wl["drift_phase1"] < wl["stale_threshold"]
            assert wl["drift_phase2"] > wl["stale_threshold"]
            assert wl["crossed"] is True
            fp = d["workload_fingerprint"]
            assert fp is not None and fp["guided_share"] == 1.0
        else:
            assert d["obs_enabled"] is False
            assert d["workload"]["drift_phase2"] is None
            assert d["workload_fingerprint"] is None
    # Byte identity across the obs on/off arms: the read-only claim.
    assert digests["1"] == digests["0"]
    # --shift refuses arms that would otherwise silently win (the
    # classes/models/soak branches run first in run_bench).
    monkeypatch.setenv("BENCH_CLASSES", "1")
    with pytest.raises(ValueError, match="does not compose"):
        bench_mod.run_bench("llama3-test", False, probe)
    monkeypatch.delenv("BENCH_CLASSES")


def test_bench_soak_smoke_two_group_fleet(monkeypatch, capsys):
    """The --soak arm composed with --models (ROADMAP carry-over) must
    RUN a short two-group soak on CPU: both groups serve traffic, zero
    lost requests, and per-group fingerprints land in details. The
    refusal set matches --models (no --plan/--dp/--classes)."""
    import bench as bench_mod

    for var, val in (("BENCH_PROMPT", "32"), ("BENCH_NEW", "8"),
                     ("BENCH_SLOTS", "2"), ("BENCH_PAGES", "128"),
                     ("BENCH_SOAK", "2"),
                     ("BENCH_MODELS", "llama3-test,qwen2-test"),
                     ("BENCH_BGE", "0"), ("BENCH_GUIDED", "0")):
        monkeypatch.setenv(var, val)
    probe = {"ok": True, "platform": "cpu", "kind": "cpu", "n": 1}
    bench_mod.run_inner("llama3-test", False, probe)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    d = out["details"]
    assert "error" not in d, d
    assert d["arm"] == "soak" and d["multi_model"] is True
    assert d["models"] == ["llama3-test", "qwen2-test"]
    assert d["lost_requests"] == 0
    for name in d["models"]:
        pm = d["per_model"][name]
        assert pm["requests"] > 0 and pm["lost"] == 0
        assert pm["workload_fingerprint"]["window"]["samples"] > 0
    # Same refusals as --models: a --soak --dp run must not silently
    # measure something else.
    monkeypatch.setenv("BENCH_DP", "2")
    with pytest.raises(ValueError, match="does not compose"):
        bench_mod.run_bench("llama3-test", False, probe)
    monkeypatch.delenv("BENCH_DP")


def test_bench_soak_scenarios_smoke_chaos_gate(monkeypatch, capsys):
    """The --soak-scenarios chaos gate must RUN on CPU in tier-1: a
    dp=2 fleet serves the seeded scenario mix twice (chaos-free
    baseline, then with an injected mid-run replica crash), the
    supervisor detects/rebuilds/rejoins, and EVERY production invariant
    verdict passes — zero lost outside fault windows, TTFT bound,
    fairness, RSS/fd bounds, digest determinism, supervisor recovery."""
    import bench as bench_mod

    for var, val in (("BENCH_PROMPT", "32"), ("BENCH_NEW", "8"),
                     ("BENCH_SLOTS", "2"), ("BENCH_PAGES", "128"),
                     ("BENCH_SOAK_SCENARIOS", "2"),
                     ("BENCH_BGE", "0"), ("BENCH_GUIDED", "0")):
        monkeypatch.setenv(var, val)
    probe = {"ok": True, "platform": "cpu", "kind": "cpu", "n": 1}
    bench_mod.run_inner("llama3-test", False, probe)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    d = out["details"]
    assert "error" not in d, d
    assert d["arm"] == "soak_scenarios" and d["dp"] == 2
    assert d["chaos_enabled"] is True
    assert d["chains"] > 0 and d["turns"] >= d["chains"]
    # Every scenario class was exercised.
    assert set(d["classes"]) == {
        "short_chat", "agentic_chain", "batch_flood",
        "shared_prefix_session", "spiky_tenant"}
    # The injected crash was applied and fully recovered from.
    assert any(w["kind"] == "replica_crash"
               and w["status"] == "applied"
               for w in d["chaos"]["windows"])
    tos = [t["to"] for t in d["supervisor"]["transitions"]]
    for state in ("failed", "rebuilding", "rejoining", "healthy"):
        assert state in tos, tos
    assert d["supervisor"]["rebuilds_total"] >= 1
    # The production-invariant gate: every verdict must hold.
    assert d["invariants_passed"] is True, d["invariants"]
    assert d["invariants"]["digest_determinism"]["compared"] > 0
    # Detection coverage (PR 15): the injected crash window overlaps a
    # detected replica_failure incident with a banked MTTD, a captured
    # bundle verifies (schema + content hash), and the chaos-free
    # baseline pass opened ZERO incidents (false-positive gate).
    cov = d["invariants"]["detection_coverage"]
    assert cov["passed"] is True, cov
    assert cov["baseline_opens"] == 0
    assert cov["bundles"] and all(
        b["hash_verified"] and b["schema_valid"] for b in cov["bundles"])
    # Embedded-history gate (obs/tsdb.py + obs/query.py): every bundle
    # carries its hash-verified pre-open lookback window, the chaos
    # pass's store actually held series, and the query-expressed
    # invariants — the same gate conditions re-derived through the
    # PromQL-lite evaluator — all hold. query_detection_coverage in
    # particular must have SAMPLED runbook_incident_open >= 1: that
    # gauge is absent while nothing is open, so a stored value proves
    # the ring caught the incident in flight.
    assert all(b["has_history"] for b in cov["bundles"]), cov["bundles"]
    assert d["tsdb"]["series"] > 0 and d["tsdb"]["samples"] > 0
    assert d["tsdb"]["dropped_series"] == 0
    for name in ("query_baseline_zero_incidents",
                 "query_baseline_zero_lost",
                 "query_detection_coverage",
                 "query_interactive_ttft_p95"):
        assert d["invariants"][name]["passed"] is True, \
            d["invariants"][name]
    qcov = d["invariants"]["query_detection_coverage"]
    assert qcov["crash_applied"] is True
    assert any(v >= 1 for v in qcov["values"]), qcov
    crash_rows = [r for r in d["incident_coverage"]
                  if r["kind"] == "replica_crash"]
    assert crash_rows, d["incident_coverage"]
    for row in crash_rows:
        assert row["detected_signal"] == "replica_failure"
        assert row["incident"] and row["mttd_s"] is not None
    assert any(i["signal"] == "replica_failure" for i in d["incidents"])
    # Same refusal posture as the other fleet arms.
    monkeypatch.setenv("BENCH_DP", "2")
    with pytest.raises(ValueError, match="does not compose"):
        bench_mod.run_bench("llama3-test", False, probe)
    monkeypatch.delenv("BENCH_DP")
    monkeypatch.setenv("BENCH_SOAK", "2")
    with pytest.raises(ValueError, match="does not compose"):
        bench_mod.run_bench("llama3-test", False, probe)
    monkeypatch.delenv("BENCH_SOAK")


def test_eval_artifacts_carry_quality_marker(tmp_path, monkeypatch):
    # Every eval artifact must state whether quality was measured with
    # real weights (VERDICT r4 #3).
    from runbookai_tpu.evalsuite.run_all import run_all_benchmarks
    from runbookai_tpu.utils.weights import QUALITY_UNMEASURED

    monkeypatch.delenv("RUNBOOK_WEIGHTS", raising=False)
    agg = run_all_benchmarks(datasets_root=tmp_path / "none",
                             out_dir=tmp_path / "out")
    assert agg["quality"] == QUALITY_UNMEASURED
    on_disk = json.loads((tmp_path / "out" / "run-all.json").read_text())
    assert on_disk["quality"] == QUALITY_UNMEASURED
