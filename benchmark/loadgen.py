"""The load generator: a process of its own that never imports JAX.

    python3 benchmark/loadgen.py PLAN.json OUT.jsonl PORT

Replays a plan (``benchmark/generators``) against the server on the
loopback port, from ONE thread (asyncio, stdlib sockets, server-sent
events parsed as they arrive), and writes one record per request: when it
was due, sent, when each streamed token arrived, the counts the server
reported and the text. Times are ``time.monotonic()`` — the system-wide
monotonic clock, so the parent can place them on its own timeline.

Open loop: every request is sent at its due time whether or not earlier
ones finished. What is in flight when the window (``seconds``) closes
drains, at most ``drain_s`` seconds.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

async def one_request(port: int, body: dict, rec: dict) -> None:
    """POST one streamed chat completion; fill ``rec`` in place."""
    payload = json.dumps(body).encode()
    rec["sent"] = time.monotonic()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(
            b"POST /v1/chat/completions HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            b"Content-Type: application/json\r\nConnection: close\r\n"
            + f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)
        await writer.drain()
        status_line = await reader.readline()
        rec["status"] = int(status_line.split()[1])
        chunked = False
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"transfer-encoding") and b"chunked" in line.lower():
                chunked = True
        if rec["status"] == 200 and not body.get("stream"):
            # Warm-up only: the n choices of one request reach the engine
            # together, so n = 2 and n = 4 meet those prefill widths
            # whatever the timing. Nothing of it is timed.
            reply = json.loads(await reader.read())
            rec["prompt_tokens"] = reply["usage"]["prompt_tokens"]
            rec["completion_tokens"] = reply["usage"]["completion_tokens"]
            rec["finish"] = reply["choices"][0]["finish_reason"]
            rec["done_marker"] = rec["terminated"] = True
            rec["max_tokens"] *= len(reply["choices"])
            rec["text"] = "".join(c["message"]["content"] for c in reply["choices"])
            return
        if rec["status"] != 200 or not chunked:
            rec["error"] = (await reader.read(600)).decode("utf-8", "replace")
            return
        buf = b""
        while True:
            size_line = await reader.readline()
            if not size_line:
                break  # stream cut: no terminating chunk
            size = int(size_line.strip() or b"0", 16)
            if size == 0:
                rec["terminated"] = True
                break
            buf += await reader.readexactly(size)
            await reader.readexactly(2)
            now = time.monotonic()
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if not event.startswith(b"data: "):
                    continue
                data = event[6:]
                if data == b"[DONE]":
                    rec["done_marker"] = True
                    continue
                chunk = json.loads(data)
                if chunk.get("error"):
                    rec["error"] = json.dumps(chunk["error"])
                if chunk.get("usage"):
                    rec["prompt_tokens"] = chunk["usage"]["prompt_tokens"]
                    rec["completion_tokens"] = chunk["usage"]["completion_tokens"]
                for choice in chunk.get("choices", []):
                    if choice.get("finish_reason"):
                        rec["finish"] = choice["finish_reason"]
                    piece = choice.get("delta", {}).get("content")
                    if piece:  # one character a token (reference/tokens.py)
                        rec["times"] += [now] * len(piece)
                        rec["text"] += piece
    finally:
        rec["end"] = time.monotonic()
        writer.close()


async def run_request(port: int, req: dict, t0: float, out) -> None:
    due = t0 + req["due_s"]
    await asyncio.sleep(max(0.0, due - time.monotonic()))
    body = {"messages": req["messages"], "max_tokens": req["max_tokens"],
            "temperature": 0}
    if req["n_choices"] > 1:
        body["n"] = req["n_choices"]
    else:
        body.update(stream=True, stream_options={"include_usage": True})
    rec = {"kind": "req", "id": req["id"], "due": due, "sent": None, "end": None,
           "times": [], "text": "", "status": None, "finish": None,
           "prompt_tokens": None, "completion_tokens": None,
           "done_marker": False, "terminated": False, "error": None,
           "max_tokens": req["max_tokens"],
           "messages": req["messages"]}  # what the reference check replays
    try:
        await one_request(port, body, rec)
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    except asyncio.CancelledError:
        rec["error"] = "cut at the drain limit"
        out.write(json.dumps(rec) + "\n")
        raise
    out.write(json.dumps(rec) + "\n")


async def replay(plan: dict, port: int, out) -> None:
    t0 = time.monotonic()
    out.write(json.dumps({"kind": "start", "t0": t0}) + "\n")
    out.flush()
    tasks = [asyncio.create_task(run_request(port, r, t0, out))
             for r in plan["requests"]]
    done, pending = await asyncio.wait(
        tasks, timeout=plan["seconds"] + plan.get("drain_s", 60.0))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    for t in done:
        if t.exception() is not None:
            raise t.exception()
    out.write(json.dumps({"kind": "end", "t_end": time.monotonic(),
                          "cut_by_drain_limit": len(pending)}) + "\n")


def main(argv: list[str]) -> int:
    plan_path, out_path, port = argv[1], argv[2], int(argv[3])
    with open(plan_path) as f:
        plan = json.load(f)
    with open(out_path, "w") as out:
        asyncio.run(replay(plan, port, out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
