"""Soak: the long-running surfaces under churn (VERDICT r4 next-round #5).

``RUNBOOK_SOAK=1`` drives the OpenAI server with mixed traffic (buffered
chat, completions, SSE streams, deliberate client disconnects) for
``RUNBOOK_SOAK_SECONDS`` (default 120; set higher for a real soak) while
injecting an engine-step crash mid-run, and churns the socket-mode
gateway through dozens of reconnect cycles with redelivered envelopes.
Asserts the days-long-process claims the unit tests only state: zero
lost requests outside the injected-fault window, preemption cycling
under pool pressure, crash recovery (the engine loop restarts and serves
again), bounded ack history, and no fd/RSS growth.

Run:  RUNBOOK_SOAK=1 [RUNBOOK_SOAK_SECONDS=600] pytest tests/test_soak.py
Record the run in CHANGES.md (reliability posture parity with the
reference's gateway, src/slack/gateway.ts:531).
"""

import gc
import json
import os
import random
import socket
import threading
import time
import urllib.request

import pytest

pytestmark = pytest.mark.skipif(
    not os.environ.get("RUNBOOK_SOAK"),
    reason="soak is minutes-long; set RUNBOOK_SOAK=1")

DURATION = float(os.environ.get("RUNBOOK_SOAK_SECONDS", "120"))


def _fd_count() -> int:
    return len(os.listdir("/proc/self/fd"))


def _rss_mb() -> float:
    pages = int(open("/proc/self/statm").read().split()[1])
    return pages * os.sysconf("SC_PAGE_SIZE") / 1e6


def test_soak_openai_server_mixed_traffic_with_injected_faults():
    from runbookai_tpu.model.jax_tpu import JaxTpuClient
    from runbookai_tpu.server.openai_api import OpenAIServer

    # Small pool on purpose: 4 concurrent workers against 160 pooled
    # tokens forces continuous preemption cycling.
    client = JaxTpuClient.for_testing(
        max_new_tokens=12, num_pages=40, max_batch_slots=4, max_seq_len=192)
    srv = OpenAIServer(client, "llama3-test", port=0)
    srv.start_background()
    core = client.engine.core
    base = f"http://127.0.0.1:{srv.port}"

    ok = [0]
    disconnects = [0]
    shed = [0]  # explicit 503 capacity aborts — load shedding, not loss
    crash_window_errors: list[str] = []
    errors: list[str] = []
    lock = threading.Lock()
    stop = threading.Event()
    crash_window = threading.Event()

    def post(path, payload, timeout=180):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        return urllib.request.urlopen(req, timeout=timeout)

    def worker(wid: int) -> None:
        rng = random.Random(wid)
        while not stop.is_set():
            kind = rng.choice(("chat", "completion", "stream", "disconnect"))
            try:
                if kind == "chat":
                    with post("/v1/chat/completions", {
                        "messages": [{"role": "user",
                                      "content": f"soak {rng.random():.6f}"}],
                        "max_tokens": rng.randint(4, 12)}) as r:
                        body = json.loads(r.read())
                    assert body["choices"][0]["message"]["role"] == "assistant"
                elif kind == "completion":
                    # n=2 + logprobs: the multi-choice and logprob paths
                    # under sustained load (the API is chat-shaped).
                    with post("/v1/chat/completions", {
                        "messages": [{"role": "user",
                                      "content": f"soak {rng.random():.6f}"}],
                        "n": 2, "logprobs": True, "top_logprobs": 3,
                        "max_tokens": rng.randint(4, 12)}) as r:
                        body = json.loads(r.read())
                    assert len(body["choices"]) == 2
                elif kind == "stream":
                    with post("/v1/chat/completions", {
                        "messages": [{"role": "user", "content": "s"}],
                        "max_tokens": rng.randint(4, 12),
                        "stream": True}) as r:
                        raw = r.read().decode()
                    assert raw.rstrip().endswith("[DONE]")
                else:
                    # Deliberate mid-stream disconnect: the server's
                    # BrokenPipe path must abort the engine request and
                    # keep serving everyone else.
                    s = socket.create_connection(("127.0.0.1", srv.port),
                                                 timeout=30)
                    payload = json.dumps({
                        "messages": [{"role": "user", "content": "bye"}],
                        "max_tokens": 12, "stream": True}).encode()
                    s.sendall(
                        b"POST /v1/chat/completions HTTP/1.1\r\n"
                        b"Host: x\r\nContent-Type: application/json\r\n"
                        + f"Content-Length: {len(payload)}\r\n\r\n".encode()
                        + payload)
                    s.recv(256)  # first bytes only, then vanish
                    s.close()
                    with lock:
                        disconnects[0] += 1
                    continue
                with lock:
                    ok[0] += 1
            except Exception as e:  # noqa: BLE001 — classified below
                # An explicit 503 under the deliberately undersized pool
                # is the engine SHEDDING load (admission cannot fit even
                # after preempting everything younger) — a definite,
                # correct response. Losing a request means silence or an
                # unclassified error, not this.
                if getattr(e, "code", None) == 503:
                    with lock:
                        shed[0] += 1
                    continue
                msg = f"{kind}: {type(e).__name__}: {e}"
                with lock:
                    (crash_window_errors if crash_window.is_set()
                     else errors).append(msg)

    workers = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(4)]
    t0 = time.time()
    for w in workers:
        w.start()

    # Baseline AFTER warm-up (first compiles, pool allocations).
    time.sleep(DURATION * 0.25)
    gc.collect()
    fd0, rss0 = _fd_count(), _rss_mb()

    # Mid-run crash injection: one engine step raises like a device
    # error; AsyncEngine fails live requests and the next caller's
    # start() restarts the loop (engine/async_engine.py).
    time.sleep(DURATION * 0.25)
    crash_window.set()
    orig_step = core.step

    def boom():
        core.step = orig_step  # one-shot
        raise RuntimeError("injected device error (soak)")

    core.step = boom
    time.sleep(max(5.0, DURATION * 0.05))
    crash_window.clear()

    time.sleep(max(0.0, t0 + DURATION - time.time()))
    stop.set()
    for w in workers:
        w.join(timeout=200)
    assert not any(w.is_alive() for w in workers)

    # Recovery proof: a fresh request AFTER the injected crash succeeds.
    with post("/v1/chat/completions", {
            "messages": [{"role": "user", "content": "post-crash"}],
            "max_tokens": 4}) as r:
        assert json.loads(r.read())["choices"]

    gc.collect()
    fd1, rss1 = _fd_count(), _rss_mb()
    m = dict(core.metrics)
    srv.shutdown()

    # Zero lost requests outside the injected-fault window: every normal
    # request either completed or was explicitly shed with a 503.
    assert not errors, errors[:5]
    assert ok[0] >= DURATION / 2, (ok[0], DURATION)  # sustained progress
    assert shed[0] <= max(4, ok[0] // 20), (shed[0], ok[0])  # shedding rare
    assert disconnects[0] > 0  # the disconnect path actually ran
    assert m["preemptions"] > 0, m  # pool pressure exercised scheduling
    # Crash window was real but bounded (in-flight requests only).
    assert len(crash_window_errors) <= 4 * 8, crash_window_errors[:5]
    # Stability: descriptors flat, resident set bounded.
    assert fd1 - fd0 <= 16, (fd0, fd1)
    assert rss1 - rss0 <= 80.0, (rss0, rss1)


def test_soak_socket_mode_reconnect_churn_bounded_state():
    from test_slack_socket import FakeSlackWS

    from runbookai_tpu.server.slack_gateway import DedupeCache
    from runbookai_tpu.server.slack_socket import SocketModeClient

    n_conns = max(72, int(DURATION // 2))  # 72*8 = 576 > 512
    per_conn = 8
    total = n_conns * per_conn  # > 512: proves the ack deque bound

    def envelope(conn: int, j: int, redelivered: bool = False) -> dict:
        # Every 4th envelope redelivers the previous one (same event_ts)
        # — Slack does this when acks race the connection refresh.
        uid = f"{conn}-{j - 1 if redelivered else j}"
        return {"type": "events_api", "envelope_id": f"env-{conn}-{j}",
                "payload": {"event": {"type": "app_mention",
                                      "event_ts": f"ts-{uid}",
                                      "text": f"<@U0BOT> status {uid}"}}}

    scripts = []
    for c in range(n_conns):
        script = [{"type": "hello"}]
        for j in range(per_conn):
            script.append(envelope(c, j, redelivered=(j % 4 == 3)))
        script.extend(["ping", "close"])
        scripts.append(script)
    fake = FakeSlackWS(scripts)

    dedupe = DedupeCache(ttl_s=3600.0, max_size=4 * total)
    handled: list[str] = []
    handled_lock = threading.Lock()

    def handler(event: dict) -> None:
        if dedupe.seen(event["event_ts"]):
            return
        with handled_lock:
            handled.append(event["event_ts"])

    client = SocketModeClient(
        "xapp-soak", handler,
        connections_open=lambda tok: f"ws://127.0.0.1:{fake.port}/",
        max_reconnects=n_conns + 2)
    baseline_threads = threading.active_count()
    t = threading.Thread(target=client.run, daemon=True)
    t.start()
    fake.thread.join(timeout=300)  # server finishes all scripted conns
    assert not fake.thread.is_alive()
    deadline = time.time() + 60
    while len(fake.received) < total and time.time() < deadline:
        time.sleep(0.05)
    client.stop()
    t.join(timeout=60)

    # Every envelope acked exactly once, in order per connection.
    assert len(fake.received) == total
    # Redeliveries dispatched but deduped: unique event ids only.
    expected_unique = n_conns * len(
        {(j - 1 if j % 4 == 3 else j) for j in range(per_conn)})
    deadline = time.time() + 30
    while len(handled) < expected_unique and time.time() < deadline:
        time.sleep(0.05)  # handler threads drain
    assert len(handled) == expected_unique, (len(handled), expected_unique)
    # Bounded state for days-long runs: ack history capped.
    assert client.acked.maxlen == 512
    assert len(client.acked) == 512 < total
    # Handler threads drained; no thread leak.
    time.sleep(1.0)
    assert threading.active_count() <= baseline_threads + 3
