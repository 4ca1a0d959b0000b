"""One run of one cell of the benchmark.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it registers the cell's configuration, builds
the server through ``cli.main.build_server`` (what ``runbook serve``
calls), serves on a loopback port, and drives it from a child process
that never imports JAX (``loadgen.py``). Phases: build -> warm-up of the
cell's own shapes -> measured window of ``--seconds`` -> drain -> server
shutdown -> the plain reference over a sample of what the window served
-> one JSON line. ``setup_s`` is process start to the start of the window.

Without a TPU it exits non-zero and prints no result. ``--rehearse-cpu``
runs the same code on the tiny presets and prints a rehearsal line that
is not the contract's line.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python allows

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `python3 benchmark/run.py` as well as `-m`
    sys.path.insert(0, str(ROOT))

from benchmark import blocks, generators, metrics, serving, trace_reduce  # noqa: E402

TRACE_AT_S, TRACE_FOR_S = 4.0, 5.0  # the traced slice of the steady window
DRAIN_S = 60.0
WARMUP_DRAIN_S = 1000.0  # a cold first run compiles every program in warm-up


def say(kind: str, **facts) -> None:
    """An earlier line of the run: facts for a reader, never the result."""
    print(json.dumps({"note": kind, **facts}), flush=True)


class CompileCounter:
    """Programs compiled (or loaded from the persistent cache: the event
    wraps both) since ``reset()``, by name — any in the window means it
    met a shape that set-up had not warmed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.names: list[str] = []
        self.seconds = 0.0  # compiling or loading, since the process began
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, fun_name: str = "?", **_) -> None:
        if event == self.EVENT:
            self.names.append(fun_name)
            self.seconds += duration

    @property
    def count(self) -> int:
        return len(self.names)

    def reset(self) -> None:
        self.names = []


def run_loadgen(plan: dict, tag: str, port: int) -> tuple[subprocess.Popen, Path]:
    """Start the load generator on ``plan``; returns (process, records path)."""
    serving.RUN_DIR.mkdir(parents=True, exist_ok=True)
    plan_path = serving.RUN_DIR / f"plan.{tag}.json"
    out_path = serving.RUN_DIR / f"records.{tag}.jsonl"
    plan_path.write_text(json.dumps(plan))
    out_path.unlink(missing_ok=True)
    proc = subprocess.Popen([sys.executable, str(ROOT / "benchmark" / "loadgen.py"),
                             str(plan_path), str(out_path), str(port)])
    return proc, out_path


def wait_started(proc: subprocess.Popen, out_path: Path) -> float:
    """The load generator's t0 (system-wide monotonic clock)."""
    while True:
        if out_path.is_file():
            with open(out_path) as f:
                line = f.readline()
            if line.endswith("\n"):
                return json.loads(line)["t0"]
        if proc.poll() is not None:
            raise RuntimeError(f"loadgen exited {proc.returncode} before starting")
        time.sleep(0.005)


def warmup_plans(gen, traffic: dict, cell_extra: dict, seed: int,
                 rehearsal: bool) -> list[tuple[str, dict]]:
    """Two phases, each run to its end before the next: the traffic file's
    bursts (1, 2, 4… fresh prompts at once), then the cell's own mix for a
    few seconds — every shape the window can meet, before it. Sequential,
    so that a cold first run, whose bursts take minutes to compile, still
    runs the mix before its window."""
    rh = traffic.get("rehearsal", {}) if rehearsal else {}
    wu = traffic["warmup"]
    seconds = float(rh.get("warmup_seconds") or wu["seconds"])
    mix = gen.plan(traffic, cell_extra, seed ^ 0x5EED, seconds, rehearsal)
    kept = wu["bursts"][:int(rh.get("warmup_bursts", len(wu["bursts"])))]
    bursts = {"seconds": max(b["at_s"] for b in kept) + 1.0,
              "requests": generators.burst_requests(
                  kept, traffic["system"], random.Random(seed ^ 0xB0057),
                  int(rh.get("length_divisor", 1)))}
    for plan in (bursts, mix):
        plan["drain_s"] = WARMUP_DRAIN_S
    return [("bursts", bursts), ("mix", mix)]


def free_device(server) -> int:
    """Shut the server down and delete every array it left on the device
    (the engine outlives its server in registries and callbacks, so the
    buffers are deleted, not waited for), so that the reference has the
    chip's memory. Returns the bytes still alive, which is 0."""
    import jax

    server.shutdown()
    del server
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays() if not a.is_deleted())


def sweep(args, gen, traffic, cell_extra, server, http) -> int:
    """One window at each rate of ``--sweep``: the tails, how late the
    generator ran, and the backlog (requests due and not finished) at the
    middle and at the end of the window — a rate is sustained where the
    backlog does not grow."""
    for k, rate in enumerate(float(r) for r in args.sweep.split(",")):
        plan = gen.plan(traffic, {**cell_extra, "rate_rps": rate},
                        args.seed + k, args.seconds)
        plan["drain_s"] = DRAIN_S
        proc, path = run_loadgen(plan, f"sweep{k}", server.port)
        t0 = wait_started(proc, path)
        if proc.wait() != 0:
            raise RuntimeError(f"loadgen exited {proc.returncode}")
        _, reqs, end = metrics.load_records(str(path))
        e2e = metrics.end_to_end(reqs, t0, args.seconds)

        def backlog(t: float) -> int:
            return sum(1 for r in reqs if r["due"] <= t and (r["end"] or 1e18) > t)

        late = [(r["sent"] - r["due"]) * 1e3 for r in reqs if r["sent"]]
        say("sweep", rate_rps=rate, attempted=len(reqs),
            failed=sum(1 for r in reqs if metrics.failure(r)),
            first_failures=[f for f in map(metrics.failure, reqs) if f][:2],
            ttft_p50_ms=e2e.get("ttft_p50_ms"), ttft_p90_ms=e2e.get("ttft_p90_ms"),
            tpot_p50_ms=e2e.get("tpot_p50_ms"), tpot_p90_ms=e2e.get("tpot_p90_ms"),
            loadgen_late_p90_ms=metrics.percentile(late, 90),
            backlog_mid=backlog(t0 + args.seconds / 2),
            backlog_end=backlog(t0 + args.seconds),
            completed_in_window=e2e["samples"]["completed_in_window"],
            drained_s=round(end["t_end"] - t0 - args.seconds, 3),
            decode_rows=http.healthz()["metrics"].get("decode_tokens"))
    server.shutdown()
    return 0


def device_block(devices, peak: int | None) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def layer_metric_modules() -> list:
    """Every metric file of ``benchmark/layer_metrics/`` (no registry)."""
    from benchmark.layer_metrics._common import load_metric_file

    files = sorted(p for p in (ROOT / "benchmark" / "layer_metrics").glob("*.py")
                   if not p.stem.startswith("_"))
    return [load_metric_file(p) for p in files]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the same code on the tiny presets, on the CPU; "
                         "prints a rehearsal line, never the contract's")
    ap.add_argument("--control", default=None,
                    help="also read the control of `correct`: the reference "
                         "in the nearest lower precision — fp8, kv_fp8, "
                         "act_fp8, comma-separated (chip proof only)")
    ap.add_argument("--llm", default=None,
                    help="JSON of llm.* overrides: the program's own lower "
                         "precision as the served control, "
                         '{"kv_cache_dtype": "fp8"} (tests and chip proof only)')
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates: one window of --seconds at "
                         "each, in this one process, a line each and no "
                         "result (how a cell's rate_rps was found)")
    args = ap.parse_args(argv)

    bench = serving.benchmark_json()
    cell, config, traffic, cell_extra = serving.find_cell(bench, args.workload)
    block = blocks.load(config["block"])  # the architecture: its reference, its bytes
    rehearsal = args.rehearse_cpu

    from runbookai_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearsal and platform != "cpu":
        print(f"benchmark: --rehearse-cpu is the CPU's rehearsal and JAX "
              f"reports {platform!r}", file=sys.stderr)
        return 2
    if not rehearsal and (platform != "tpu" or len(devices) < cell["chips"]):
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX reports {len(devices)} device(s) of platform "
              f"{platform!r} — nothing was run", file=sys.stderr)
        return 2
    peaks = serving.load_json(ROOT / "benchmark" / "peaks.json")
    if not rehearsal and devices[0].device_kind not in peaks:
        print(f"benchmark: no peaks for device kind "
              f"{devices[0].device_kind!r} in peaks.json", file=sys.stderr)
        return 2
    compiles = CompileCounter()

    # ---- build: the construction path of `runbook serve` ----------------
    model_cfg = serving.model_config(config, rehearsal)
    serving.register(model_cfg, args.seed)
    overrides = json.loads(args.llm) if args.llm else None
    server = serving.build(serving.render_serve_config(
        config, model_cfg.name, rehearsal, overrides))
    http = serving.Http(server.port)
    runtime = http.healthz()["runtime"]
    t_built = time.monotonic()
    spans = serving.load_json(ROOT / "benchmark" / "spans.json")
    if args.trace:
        missing = serving.wrap_spans(spans)
        if missing:
            say("spans_not_found", paths=missing)

    # ---- warm-up: the cell's own shapes, counted as set-up ---------------
    gen = generators.load(traffic["generator"])
    for tag, wu_plan in warmup_plans(gen, traffic, cell_extra, args.seed, rehearsal):
        proc, wu_path = run_loadgen(wu_plan, f"warmup-{tag}", server.port)
        if proc.wait() != 0:
            raise RuntimeError(f"warm-up loadgen exited {proc.returncode}")
        _, wu_reqs, _ = metrics.load_records(str(wu_path))
        wu_failed = [f for f in map(metrics.failure, wu_reqs) if f]
        say("warmup", phase=tag, requests=len(wu_reqs), failed=len(wu_failed),
            first_failures=wu_failed[:3], programs_compiled_or_loaded=compiles.count,
            seconds_compiling_or_loading=round(compiles.seconds, 3),
            step_programs={n: compiles.names.count(f"jit({n})")
                           for n in spans["step_programs"]},
            seconds_since_build=round(time.monotonic() - t_built, 3))

    if args.sweep:
        return sweep(args, gen, traffic, cell_extra, server, http)

    # ---- the measured window --------------------------------------------
    plan = gen.plan(traffic, cell_extra, args.seed, args.seconds, rehearsal)
    plan["drain_s"] = DRAIN_S
    health_before = http.healthz()
    hist_before = http.metrics_text()
    compiles.reset()
    proc, rec_path = run_loadgen(plan, "window", server.port)
    t0 = wait_started(proc, rec_path)
    setup_s = t0 - T_START
    steps: dict[int, dict] = {}
    traced: dict = {}
    if args.trace:
        trace_dir = serving.RUN_DIR / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)

        def poll_steps() -> None:
            for s in http.steps():
                steps[s["step"]] = s

        def trace_slice() -> None:
            time.sleep(max(0.0, t0 + min(TRACE_AT_S, args.seconds / 4) - time.monotonic()))
            traced["health_start"] = http.healthz()
            traced["t_start"] = time.monotonic()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # spans and device only: no call stacks
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            time.sleep(min(TRACE_FOR_S, args.seconds / 3))
            traced["health_stop"] = http.healthz()  # before the (slow) stop
            traced["t_stop"] = time.monotonic()
            jax.profiler.stop_trace()

        tracer = threading.Thread(target=trace_slice, name="bench-trace")
        tracer.start()
        while proc.poll() is None:
            time.sleep(2.0)
            poll_steps()
        tracer.join()
        poll_steps()
    if proc.wait() != 0:
        raise RuntimeError(f"loadgen exited {proc.returncode}")
    # A step program (jit(_prefill_step), jit(_decode_multi)...) that
    # compiles or loads in the window makes the run not correct; a small
    # eager helper (a sampling variant) is named on an earlier line only.
    compiled_names = sorted(set(compiles.names))
    compiled_in_window = sum(1 for n in compiles.names
                             if n in {f"jit({p})" for p in spans["step_programs"]})
    health_after = http.healthz()
    hist_after = http.metrics_text()
    start, reqs, end = metrics.load_records(str(rec_path))
    by_id = {d["id"]: d for d in health_after["runtime"]["devices"]}
    peak = max((by_id[i]["peak_bytes_in_use"] or 0)
               for replica in health_after["runtime"]["replicas"] for i in replica)
    # What the program keeps on the device once the window has drained,
    # as JAX counts it, beside what the configuration's precisions state.
    llm = config["rehearsal"]["llm"] if rehearsal else config["llm"]
    ref_cfg = serving.reference_cfg(model_cfg)
    resident = {"live_bytes": sum(a.nbytes for a in jax.live_arrays()),
                "stated_bytes": block.bytes.resident_bytes(ref_cfg, llm, config["precision"])}

    # ---- shutdown, then the plain reference over a sample ----------------
    left = free_device(server)
    say("freed", live_array_bytes_after_shutdown=left)
    from benchmark.reference import check

    sample = check.choose_sample(reqs, int(traffic["check_sample"]), args.seed)
    t_ref = time.monotonic()
    params = block.weights.make_params(ref_cfg, args.seed % (2 ** 31),
                                       quantized=runtime["weight_dtype"] == "int8")
    verdict = check.compare(block, params, ref_cfg, sample,
                            check.limits_for(config["name"]), resident, args.control)
    del params
    say("reference", seconds=round(time.monotonic() - t_ref, 3), **verdict)

    # ---- the result ------------------------------------------------------
    failures = [f for f in map(metrics.failure, reqs) if f]
    e2e = metrics.end_to_end(reqs, t0, args.seconds)
    slowest = sorted(((round(v, 2), len(r["times"])) for r in reqs
                      if (v := metrics.tpot_ms(r)) is not None), reverse=True)[:12]
    say("window", attempted=len(reqs), failed=len(failures),
        first_failures=failures[:3], samples=e2e["samples"],
        step_programs_compiled_or_loaded_in_window=compiled_in_window,
        limit_step_programs_in_window=0, programs_in_window=compiled_names[:20],
        cut_by_drain_limit=end.get("cut_by_drain_limit"), setup_s=round(setup_s, 3),
        drained_s=round(end.get("t_end", t0) - t0 - args.seconds, 3),
        ttft_p50_ms=e2e.get("ttft_p50_ms"), tpot_p50_ms=e2e.get("tpot_p50_ms"),
        ttft_p90_ms=e2e.get("ttft_p90_ms"), tpot_p90_ms=e2e.get("tpot_p90_ms"),
        slowest_tpot_ms_and_tokens=slowest, counters={
            k: health_after["metrics"].get(k, 0) - health_before["metrics"].get(k, 0)
            for k in ("decode_tokens", "prefill_tokens", "spec_drafted", "spec_accepted",
                      "mixed_steps", "prefill_steps", "decode_dispatches", "preemptions")},
        rate_rps=plan.get("rate_rps"), compile_cache=cache_dir,
        engine_plan=config.get("engine_plan"),
        resolved={k: runtime[k] for k in ("attn_impl", "qmm_impl", "kv_dtype",
                                           "weight_dtype", "mixed_dispatch")})
    correct = bool(verdict["ok"]) and compiled_in_window == 0
    # Every number `correct` compares, beside its limit: the result line's
    # last key, and the run's last lines on standard error. Which statistic
    # of the gaps that is, the configuration's limits say (`check.deciding`);
    # the others are recorded before it, with no limit.
    recorded = {k: verdict.get(k) for k in check.STATS if k not in verdict["decided_by"]}
    compared = {
        **{k: (verdict.get(k), verdict[f"limit_{k}"]) for k in verdict["decided_by"]},
        "resident_bytes_short": (verdict["resident_bytes_short"],
                                 verdict["limit_resident_bytes_short"]),
        "not_comparable_share": (verdict["not_comparable_share"],
                                 verdict["limit_not_comparable_share"]),
        "prompt_token_mismatches": (len(verdict["prompt_token_mismatches"]), 0),
        "step_programs_in_window": (compiled_in_window, 0)}
    compared = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}

    declared = {m["name"]: m for m in bench["end_to_end"]}
    values = {"setup_s": setup_s, **{k: v for k, v in e2e.items() if k in declared}}
    out_metrics = {}
    if not args.trace:
        for name, m in declared.items():
            if args.workload in m.get("workloads", [args.workload]) and name in values:
                out_metrics[name] = {"value": values[name], "unit": m["unit"]}
    device = device_block(devices, peak)
    result = {"correct": correct, "attempted": len(reqs), "failed": len(failures),
              "metrics": out_metrics, "device": device}
    if args.trace:
        xplane = trace_reduce.newest_xplane(serving.RUN_DIR / "trace")
        reduced = (trace_reduce.reduce_trace(xplane, spans["annotations"]
                                             + list(spans["wrap"].values()))
                   if xplane and not rehearsal else None)
        run = {"reqs": reqs, "t0": t0, "seconds": args.seconds,
               "health_before": health_before,
               "health_after": health_after, "hist_before": hist_before,
               "hist_after": hist_after, "steps": [steps[k] for k in sorted(steps)],
               "traced": traced, "trace": reduced, "model": ref_cfg,
               "block": block, "llm": llm,
               "peaks": peaks.get(devices[0].device_kind), "runtime": runtime}
        declared_layer = {m["name"]: m for m in bench["per_layer"]}
        for mod in layer_metric_modules():
            m = declared_layer.get(mod.NAME)
            if m is None or args.workload not in m.get("workloads", [args.workload]):
                continue
            value = mod.read(run)
            if value is not None:
                out_metrics[mod.NAME] = {"value": value, "unit": mod.UNIT}
        if reduced is not None:
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            result["breakdown"] = trace_reduce.breakdown(reduced)
            say("trace", modules=reduced["modules"], host_spans=reduced["host_spans"],
                file=str(xplane.relative_to(ROOT)))
    for name, value in recorded.items():
        print(f"recorded {name} {value}", file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    if rehearsal:
        print(json.dumps({"rehearsal": True, "rehearsal_correct": bool(verdict["ok"]),
                          "values": values, **{k: result[k] for k in
                                               ("attempted", "failed", "metrics")},
                          "platform": platform, "recorded": recorded,
                          "compared": compared}), flush=True)
        return 0 if verdict["ok"] and not failures else 1
    print(json.dumps({**result, "recorded": recorded, "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
