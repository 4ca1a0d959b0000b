"""The quickest proof that `runbook serve` still starts on the chip.

    python3 chip_smoke.py                      one v5e chip, Qwen2.5-7B int8
    python3 chip_smoke.py --config examples/serve/qwen2.5-7b-int8-dp4.yaml
    python3 chip_smoke.py --config examples/serve/qwen2.5-7b-int8-tp4.yaml
    python3 chip_smoke.py --rehearse-cpu       tiny model, CPU, no pass line

Builds the server through ``runbookai_tpu.cli.main.build_server`` — the
function ``runbook serve`` itself calls — from a checked-in config at the
model's published widths and full depth (random weights from a seed),
serves it in this process (one process holds the chip), and talks to it
only over HTTP: plain, n-choice, concurrent (one streamed), guided-JSON and
repeated requests to ``/v1/chat/completions``, then ``/healthz`` for the counters,
the resolved kernels and per-device memory. It also captures a short
``jax.profiler`` trace of steady decode and checks its device plane.

It refuses to pass anywhere but on a TPU: when ``jax.devices()[0]`` is
not one it exits non-zero before any work and prints no result. On a TPU
it prints two lines of JSON: the report (versions, resolved kernels,
allocator, compile cache, timings, every phase; also written to
``result.json``), then, as the LAST line of standard output, the verdict
— exactly ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}``. The rehearsal prints its report and no verdict. The
exit status is the AND of the phases. A check that misses is recorded
under its phase; an exception is not caught. Timings are smoke timings —
set-up (build plus every first-shape compile) apart from a steady pass —
and none is a benchmark metric.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.metadata
import json
import sys
import threading
import time
from pathlib import Path

from runbookai_tpu.cli.main import build_server
from runbookai_tpu.model.guided import JsonMachine
from runbookai_tpu.utils.compile_cache import (
    cache_entries,
    ensure_compile_cache,
)

ROOT = Path(__file__).resolve().parent
CHIP_CONFIG = ROOT / "examples" / "serve" / "qwen2.5-7b-int8.yaml"
REHEARSAL_CONFIG = ROOT / "examples" / "serve" / "llama3-test-cpu.yaml"

# Threads that must be gone once the server has shut down: everything
# JaxTpuClient.from_config and OpenAIServer start.
SERVER_THREADS = ("incident-monitor", "tsdb-sampler", "fleet-supervisor",
                  "openai-http", "serve-loop")

_PROSE = ("The checkout service began returning 502s at 14:07 UTC after the "
          "canary of build 8841 reached half of the fleet; p99 latency on "
          "the payments dependency rose from 180 ms to 2.4 s, the connection "
          "pool saturated, and retries from the gateway tripled the load. ")


def prompt_of(n_bytes: int, tag: str) -> str:
    """A prompt of about ``n_bytes`` byte tokens, distinct per ``tag`` from
    its first page on (so no two requests share a cached prefix unless
    the smoke repeats one on purpose)."""
    head = f"[{tag}] Summarize the incident and name the next check. "
    body = (_PROSE * (n_bytes // len(_PROSE) + 1))[:max(0, n_bytes - len(head))]
    return head + body


class Client:
    """Plain HTTP to the server under test (stdlib only)."""

    def __init__(self, port: int, timeout_s: float):
        self.port, self.timeout_s = port, timeout_s

    def _conn(self) -> http.client.HTTPConnection:
        return http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=self.timeout_s)

    def healthz(self) -> dict:
        conn = self._conn()
        try:
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"/healthz answered {resp.status}: "
                                   f"{body[:300]!r}")
            return json.loads(body)
        finally:
            conn.close()

    def chat(self, prompt: str, max_tokens: int, **extra) -> dict:
        """One completion -> {status, finish, completion_tokens,
        prompt_tokens, cached_tokens, content, streamed_deltas}."""
        body = {"messages": [{"role": "user", "content": prompt}],
                "max_tokens": max_tokens, **extra}
        conn = self._conn()
        try:
            conn.request("POST", "/v1/chat/completions", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
        finally:
            conn.close()
        out = {"status": resp.status, "finish": None,
               "completion_tokens": None, "prompt_tokens": None,
               "cached_tokens": None, "content": "",
               "streamed_deltas": None, "error": None}
        if resp.status != 200:
            out["error"] = raw[:400].decode("utf-8", "replace")
            return out
        if not extra.get("stream"):
            payload = json.loads(raw)
            choice, usage = payload["choices"][0], payload["usage"]
            out.update(
                finish=choice["finish_reason"],
                content=choice["message"]["content"],
                completion_tokens=usage["completion_tokens"],
                prompt_tokens=usage["prompt_tokens"],
                cached_tokens=usage.get("prompt_tokens_details", {})
                .get("cached_tokens"))
            return out
        # Server-sent events (http.client already undid the chunking).
        events = [line[len(b"data: "):] for line in raw.split(b"\n")
                  if line.startswith(b"data: ")]
        out["done_marker"] = bool(events) and events[-1] == b"[DONE]"
        deltas = 0
        for ev in events:
            if ev == b"[DONE]":
                continue
            chunk = json.loads(ev)
            if chunk.get("error"):
                out["error"] = json.dumps(chunk["error"])
            if chunk.get("usage"):
                out["completion_tokens"] = chunk["usage"]["completion_tokens"]
                out["prompt_tokens"] = chunk["usage"]["prompt_tokens"]
            for choice in chunk.get("choices", []):
                if choice.get("finish_reason"):
                    out["finish"] = choice["finish_reason"]
                piece = choice.get("delta", {}).get("content")
                if piece:
                    deltas += 1
                    out["content"] += piece
        out["streamed_deltas"] = deltas
        return out


class Phase:
    """Facts and missed checks of one phase."""

    def __init__(self, name: str):
        self.name, self.facts, self.misses = name, {}, []
        self.t0 = time.perf_counter()

    def check(self, ok: bool, miss: str) -> None:
        if not ok:
            self.misses.append(miss)

    def report(self) -> dict:
        return {"ok": not self.misses, "seconds":
                round(time.perf_counter() - self.t0, 2),
                **self.facts, **({"misses": self.misses}
                                 if self.misses else {})}


def check_completion(ph: Phase, label: str, r: dict, want_tokens: int) -> None:
    """200, and the token count asked for or finish_reason "stop"."""
    ph.check(r["status"] == 200,
             f"{label}: HTTP {r['status']} {r['error'] or ''}".strip())
    if r["status"] != 200:
        return
    ph.check(r["error"] is None, f"{label}: stream error {r['error']}")
    ph.check(r["finish"] in ("stop", "length"),
             f"{label}: finish_reason {r['finish']!r}")
    ph.check(r["finish"] == "stop" or r["completion_tokens"] == want_tokens,
             f"{label}: {r['completion_tokens']} completion tokens, "
             f"asked {want_tokens}, finish {r['finish']!r}")


def run_burst(client: Client, ph: Phase, sizes: list[int], new_tokens: int,
              tag: str) -> list[dict]:
    """``len(sizes)`` requests at once (barrier-released threads), the
    second one streamed: chunked and batched prefill, multi-token decode
    and — prompts still prefilling while others decode — mixed dispatch."""
    results: list = [None] * len(sizes)
    failures: list[BaseException] = []
    gate = threading.Barrier(len(sizes))

    def one(i: int) -> None:
        extra = ({"stream": True, "stream_options": {"include_usage": True}}
                 if i == 1 else {})
        gate.wait()
        try:
            results[i] = client.chat(prompt_of(sizes[i], f"{tag}-{i}"),
                                     new_tokens, **extra)
        except BaseException as e:  # re-raised on the main thread below
            failures.append(e)

    threads = [threading.Thread(target=one, args=(i,), name=f"smoke-req-{i}")
               for i in range(len(sizes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if failures:  # a transport error is not a missed check: stop here
        raise failures[0]
    for i, r in enumerate(results):
        check_completion(ph, f"{tag}[{i}] ({sizes[i]} B)", r, new_tokens)
    s = results[1]
    if s["status"] == 200:
        # The stream delivers what its usage block counts: a usage block
        # and the [DONE] marker arrived, and no more content deltas than
        # tokens (a token without a byte expansion — most ids of a random
        # full-vocabulary model under the byte tokenizer — is counted and
        # carries no text).
        ph.check(s.get("done_marker", False), f"{tag}[1]: no [DONE] marker")
        ph.check(s["completion_tokens"] is not None,
                 f"{tag}[1]: stream carried no usage block")
        ph.check(s["completion_tokens"] is None
                 or s["streamed_deltas"] <= s["completion_tokens"],
                 f"{tag}[1]: {s['streamed_deltas']} content deltas for "
                 f"{s['completion_tokens']} tokens")
    ph.facts["completion_tokens"] = [r["completion_tokens"] for r in results]
    ph.facts["streamed_deltas"] = s["streamed_deltas"]
    return results


def device_plane_events(trace_dir: Path) -> dict:
    """Planes and event counts of the newest ``.xplane.pb`` under
    ``trace_dir`` (``jax.profiler.ProfileData`` — nothing but JAX)."""
    from jax.profiler import ProfileData

    files = sorted(trace_dir.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return {"file": None, "planes": {}}
    planes = {}
    for plane in ProfileData.from_file(str(files[-1])).planes:
        planes[plane.name] = sum(sum(1 for _ in line.events)
                                 for line in plane.lines)
    return {"file": str(files[-1].relative_to(trace_dir)),
            "bytes": files[-1].stat().st_size, "planes": planes}


def verdict(passed: bool, devices) -> dict:
    """The last line of a TPU run: these keys and no others (the driver
    reads it), the device as JAX reports it."""
    return {"ok": passed,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=None,
                    help=f"serve config (default {CHIP_CONFIG.name})")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the same code at llama3-test size on the CPU; "
                         "prints its platform and never the chip's pass")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="directory for the profiler trace and result.json")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    cache_dir = ensure_compile_cache()
    entries_before = cache_entries(cache_dir)

    import jax
    import jaxlib

    devices = jax.devices()
    platform = devices[0].platform
    rehearsal = args.rehearse_cpu
    if rehearsal and platform != "cpu":
        print(f"chip_smoke: --rehearse-cpu is the CPU's rehearsal and JAX "
              f"reports platform {platform!r}", file=sys.stderr)
        return 2
    if not rehearsal and platform != "tpu":
        print(f"chip_smoke: JAX reports platform {platform!r}, not a TPU — "
              f"nothing was run and nothing passed", file=sys.stderr)
        return 2

    config = Path(args.config) if args.config else (
        REHEARSAL_CONFIG if rehearsal else CHIP_CONFIG)
    if not config.is_file():
        raise FileNotFoundError(config)
    # Traffic: ~100 to ~1,500 byte tokens and 64 new on the chip; a
    # tenth of that for the rehearsal's 1,024-token context.
    sizes = ([100, 1500, 300, 1100, 180, 800, 500, 1300] if not rehearsal
             else [40, 400, 60, 300, 50, 200, 120, 350])
    new_tokens = 64 if not rehearsal else 16
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    phases: dict[str, dict] = {}

    # ---- build: the cmd_serve construction path -------------------------
    ph = Phase("build")
    server = build_server(str(config), host="127.0.0.1", port=0)
    server.start_background()
    serving = True
    try:
        client = Client(server.port, timeout_s=1100.0)
        runtime = client.healthz()["runtime"]
        on_tpu = platform == "tpu"
        n_replicas = len(runtime["replicas"])
        tp = max(len(replica) for replica in runtime["replicas"])
        ph.check(runtime["platform"] == platform,
                 f"/healthz runtime.platform {runtime['platform']!r} != "
                 f"jax {platform!r}")
        # What `auto` must have resolved to on this platform (the Pallas qmm
        # is single-shard code: a TP mesh serves int8 matmuls through XLA).
        want = {"attn_impl": "pallas" if on_tpu else "xla",
                "qmm_impl": "pallas" if on_tpu and tp == 1 else "xla",
                "mixed_dispatch": on_tpu, "overlap_decode": True}
        for key, value in want.items():
            ph.check(runtime[key] == value,
                     f"resolved {key}={runtime[key]!r}, expected {value!r}")
        ph.facts.update({k: runtime[k] for k in want}, config=config.name,
                        replicas=runtime["replicas"])
        phases["build"] = ph.report()
        # One batch's worth of concurrent requests PER REPLICA: every
        # request opens with the same chat-template page, the router counts
        # that page as a prefix match, and a matching replica keeps winning
        # placement until it is a batch (the affinity slack) ahead of the
        # idlest — eight at once all land on replica 0 (observed, four
        # chips, PR 21).
        sizes = sizes * n_replicas

        # ---- plain: one completion (first prefill/decode compiles) ----------
        ph = Phase("plain")
        plain_prompt = prompt_of(220 if not rehearsal else 80, "plain")
        first = client.chat(plain_prompt, new_tokens)
        check_completion(ph, "plain", first, new_tokens)
        phases["plain"] = ph.report()

        # ---- widths: every batched-prefill width, on purpose ---------------
        # Prompts that reach an idle engine in the same step prefill in ONE
        # dispatch, rows padded to 1, 2 or 4, each its own compiled
        # program; which widths a burst meets depends on arrival timing.
        # The n choices of one request are submitted together, so n = 2
        # and n = 4 meet the other two widths whatever the timing — and a
        # second run against the same compile cache finds every program.
        ph = Phase("widths")
        for n in (2, 4):
            r = client.chat(prompt_of(sizes[0], f"width-{n}"), new_tokens,
                            n=n)
            check_completion(ph, f"n={n}", r, n * new_tokens)
        phases["widths"] = ph.report()

        # ---- burst: eight at once, one streamed -----------------------------
        ph = Phase("burst")
        run_burst(client, ph, sizes, new_tokens, "burst")
        phases["burst"] = ph.report()

        # ---- guided: response_format json_object (forced-sync path) ---------
        ph = Phase("guided")
        g = client.chat(prompt_of(160 if not rehearsal else 60, "guided")
                        + " Answer as a JSON object.", 2 * new_tokens,
                        response_format={"type": "json_object"})
        check_completion(ph, "guided", g, 2 * new_tokens)
        if g["status"] == 200:
            machine = JsonMachine()
            admitted = machine.advance_bytes(g["content"].encode("utf-8"))
            ph.check(admitted, f"guided: the JSON grammar rejects "
                               f"{g['content'][:120]!r}")
            if g["finish"] == "stop":
                ph.check(machine.is_complete,
                         "guided: finished without a complete document")
                json.loads(g["content"])
            ph.facts.update(finish=g["finish"], chars=len(g["content"]),
                            completion_tokens=g["completion_tokens"])
        phases["guided"] = ph.report()

        # ---- repeat: an earlier prompt again (prefix cache) -----------------
        ph = Phase("repeat")
        again = client.chat(plain_prompt, new_tokens)
        check_completion(ph, "repeat", again, new_tokens)
        ph.check((again["cached_tokens"] or 0) > 0,
                 f"repeat: cached_tokens={again['cached_tokens']} for a prompt "
                 f"served before")
        ph.facts["cached_tokens"] = again["cached_tokens"]
        phases["repeat"] = ph.report()
        setup_s = time.perf_counter() - t_start

        # ---- steady: the burst again with fresh prompts, shapes now warm ----
        ph = Phase("steady")
        t_steady = time.perf_counter()
        run_burst(client, ph, sizes, new_tokens, "steady")
        steady_s = time.perf_counter() - t_steady
        phases["steady"] = ph.report()

        # ---- trace: ~2.5 s of steady decode under jax.profiler --------------
        ph = Phase("trace")
        trace_dir = out_dir / "trace"
        long_new = 8 * new_tokens
        trace_results: dict[int, dict] = {}
        decoders = [threading.Thread(
            target=lambda i=i: trace_results.__setitem__(
                i, client.chat(prompt_of(sizes[0], f"trace-{i}"), long_new)),
            name=f"smoke-trace-{i}") for i in range(4)]
        for t in decoders:
            t.start()
        time.sleep(1.0 if on_tpu else 0.2)  # past prefill, into decode
        jax.profiler.start_trace(str(trace_dir))
        time.sleep(2.5 if on_tpu else 0.5)
        jax.profiler.stop_trace()
        for t in decoders:
            t.join()
        for i, r in sorted(trace_results.items()):
            check_completion(ph, f"trace[{i}]", r, long_new)
        trace = device_plane_events(trace_dir)
        device_events = sum(n for name, n in trace["planes"].items()
                            if name.startswith("/device:TPU"))
        ph.check(trace["file"] is not None, "trace: no .xplane.pb written")
        if on_tpu:
            ph.check(device_events > 0,
                     f"trace: no events on a /device:TPU plane "
                     f"(planes: {trace['planes']})")
        else:
            ph.check(sum(trace["planes"].values()) > 0,
                     "trace: the .xplane.pb holds no events")
        ph.facts.update(trace, device_events=device_events)
        phases["trace"] = ph.report()

        # ---- healthz: counters, devices, memory -----------------------------
        ph = Phase("healthz")
        health = client.healthz()
        metrics, runtime = health["metrics"], health["runtime"]
        counters = ["decode_tokens", "prefill_tokens", "cached_prefix_tokens"]
        if runtime["mixed_dispatch"]:
            counters.append("mixed_steps")
        for key in counters:
            ph.check(metrics.get(key, 0) > 0, f"healthz metrics.{key} = "
                                              f"{metrics.get(key)}")
        ph.facts["metrics"] = {k: metrics.get(k) for k in (
            *counters, "mixed_steps", "prefill_steps", "decode_dispatches",
            "spec_accepted", "grammar_forced_tokens", "preemptions")}
        if n_replicas > 1:
            routed = health["router"]["routed"]
            ph.check(all(n > 0 for n in routed),
                     f"not every replica served a request: routed={routed}")
            ph.check(all(r["decode_tokens"] > 0 for r in health["replicas"]),
                     "a replica decoded no token")
            ph.facts["routed"] = routed
        # The full-width model is on the device(s): each device an engine
        # uses holds at least its share of that engine's weights and pool.
        need = (runtime["weight_bytes"] + runtime["kv_pool_bytes"]) // tp
        by_id = {d["id"]: d for d in runtime["devices"]}
        used = sorted({i for replica in runtime["replicas"] for i in replica})
        ph.facts.update(
            weights_and_pool_bytes_per_device=need,
            bytes_in_use={i: by_id[i]["bytes_in_use"] for i in used},
            peak_bytes_in_use={i: by_id[i]["peak_bytes_in_use"] for i in used},
            bytes_limit=by_id[used[0]]["bytes_limit"])
        if on_tpu:
            for i in used:
                ph.check((by_id[i]["bytes_in_use"] or 0) >= 0.98 * need,
                         f"device {i}: bytes_in_use {by_id[i]['bytes_in_use']} "
                         f"< weights + pool {need}")
        phases["healthz"] = ph.report()

        # ---- shutdown: stop everything that was started ---------------------
        ph = Phase("shutdown")
        server.shutdown()
        serving = False
        time.sleep(0.2)
        left = sorted(t.name for t in threading.enumerate()
                      if t.is_alive() and t.name.startswith(SERVER_THREADS))
        ph.check(not left, f"threads alive after shutdown: {left}")
        phases["shutdown"] = ph.report()
    finally:
        if serving:  # an exception cut the phases short
            server.shutdown()

    passed = all(p["ok"] for p in phases.values())

    def dist_version(name: str):
        try:
            return importlib.metadata.version(name)
        except importlib.metadata.PackageNotFoundError:
            return None

    report = {
        # The rehearsal carries no "ok": only a TPU run can print the pass.
        **({"rehearsal_passed": passed} if rehearsal else {"ok": passed}),
        "device": verdict(passed, devices)["device"],
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": dist_version("libtpu")},
        "config": config.name, "model": runtime["model"],
        "n_layers": runtime["n_layers"],
        "weight_dtype": runtime["weight_dtype"],
        "kv_dtype": runtime["kv_dtype"],
        "attn_impl": runtime["attn_impl"], "qmm_impl": runtime["qmm_impl"],
        "mixed_dispatch": runtime["mixed_dispatch"],
        "overlap_decode": runtime["overlap_decode"],
        "allocator": runtime["allocator"],
        "compile_cache": {"dir": cache_dir, "entries_before": entries_before,
                          "entries_after": cache_entries(cache_dir)},
        "smoke_setup_seconds": round(setup_s, 1),
        "smoke_steady_seconds": round(steady_s, 1),
        "peak_device_bytes": max(
            (by_id[i]["peak_bytes_in_use"] or 0) for i in used),
        "phases": phases,
    }
    line = json.dumps(report)
    (out_dir / "result.json").write_text(line + "\n")
    print(line, flush=True)
    if not rehearsal:
        print(json.dumps(verdict(passed, devices)), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
