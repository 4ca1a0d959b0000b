"""OpenAI-compatible HTTP serving surface over the continuous-batching engine.

``runbook serve`` exposes the in-tree TPU serving engine the way the
ecosystem expects a model server to look (vLLM/TGI-style), so existing
OpenAI-client tooling can point at a TPU slice with no code changes:

- ``POST /v1/chat/completions`` — non-streaming and ``stream: true`` (SSE
  ``data:`` chunks, ``[DONE]`` terminator).
- ``GET /v1/models`` — the served catalog: the single model (plus its
  LoRA adapters), or under ``llm.models`` every model group with its
  replica count and group-local adapters.
- ``GET /healthz`` — liveness + engine metrics snapshot (taken under the
  engine's step lock) + uptime + KV-pool pressure.
- ``GET /metrics`` — Prometheus text exposition of the process registry
  (``runbookai_tpu.utils.metrics``): request/latency per route, engine
  TTFT/TPOT histograms, KV gauges, agent tool counters, and (when
  ``llm.slo`` objectives are configured) the ``runbook_slo_*`` series.
- ``GET /debug/steps?n=N`` — the engine flight recorder's last N per-step
  records (``engine/flight_recorder.py``): dispatch kind, tokens,
  occupancy (total + per priority class), queue depth, KV pressure, wall
  split; fleet deployments merge every replica's ring into one
  ts-ordered timeline.
- ``GET /debug/workload`` — live workload fingerprints + plan-drift
  (``runbookai_tpu/obs``): per served model group, the live traffic
  folded into the autotuner's ``Workload`` schema with its drift score
  against the serving plan's provenance workload, plus a merged
  fleet-wide view.
- ``GET /tenants`` — live tenant-accounting state (``sched/tenants.py``):
  per-tenant policy, bucket levels, admit/throttle counters.

Multi-model routing (``llm.models`` → ``runbookai_tpu/fleet``): the
request's ``model`` field resolves to a served model group (adapter
names resolve within their owning group; unknown names are 404s, never
silent base-model serving), and EVERYTHING downstream — prompt
encoding, sampling limits, admission page estimates, the stream itself
— uses the resolved group's tokenizer/chat-format/engine. A tenant may
be pinned to one group (``llm.tenants.keys.<name>.model``): requests
without a model field route there, explicit different groups are 403s.

Multi-tenant admission (``llm.tenants`` → ``runbookai_tpu/sched``): every
chat/completions request resolves its tenant from ``Authorization:
Bearer`` / ``x-api-key`` and must pass the tenant's rate, token-budget,
and in-flight KV-page buckets BEFORE enqueue — a throttled request is
answered ``429`` naming the failing bucket, with ``Retry-After``, and
never consumes an engine slot. Requests carry a
priority class (the tenant's configured class, or an explicit
``x-priority: interactive|batch`` header) into the engine's
weighted-deficit scheduler; fleet sheds and engine pool-pressure aborts
answer ``503`` with ``Retry-After``.

Every response carries an ``x-request-id`` header (client-supplied value
echoed, else generated); the id is attached to the handler thread's tracer
context and carried through the async engine into its span records, so a
trace JSONL line joins back to the request that produced it.

Architecture: a ``ThreadingHTTPServer`` (stdlib; no web framework in the
image) with a dedicated asyncio loop thread that owns the
:class:`~runbookai_tpu.engine.async_engine.AsyncEngine` — request handlers
bridge with ``run_coroutine_threadsafe``, so concurrent HTTP requests batch
together inside the engine exactly like concurrent agent investigations do.
No reference counterpart (RunbookAI calls hosted APIs; SURVEY.md §2.2).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import threading
import time
import uuid
from concurrent.futures import TimeoutError as _FutTimeout  # builtin alias 3.11+, distinct on 3.10
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

from runbookai_tpu.engine.request import (
    FinishReason,
    FleetSaturated,
    RequestOrigin,
    request_origin,
)
from runbookai_tpu.sched import (
    CLASS_NAMES,
    PRIORITY_INTERACTIVE,
    class_priority,
)
from runbookai_tpu.utils.metrics import REQUEST_LATENCY_BUCKETS, get_registry
from runbookai_tpu.utils.trace import annotate, get_tracer

# Bounded route-label cardinality: anything else is scraped as "other".
_KNOWN_ROUTES = frozenset((
    "/v1/chat/completions", "/v1/completions", "/v1/embeddings",
    "/v1/adapters", "/v1/models", "/healthz", "/metrics", "/debug/steps",
    "/debug/workload", "/debug/incidents", "/debug/query", "/tenants",
))

# Every status this server emits; anything novel scrapes as "other" so the
# status label stays a statically bounded set (RBK010 contract).
_KNOWN_STATUSES = frozenset((
    "200", "400", "403", "404", "429", "500", "503", "504",
))

# Retry-After for fleet sheds / engine pool-pressure 503s: the backlog
# drains in engine-step time, so "about a second" is the honest hint (a
# tenant throttle's Retry-After is computed from its bucket instead).
_SHED_RETRY_AFTER_S = 1


def messages_to_prompt_parts(messages: list[dict[str, Any]]):
    """OpenAI messages -> (system, history, user) for build_chat_prompt."""
    system = ""
    turns: list[tuple[str, str]] = []
    for m in messages:
        role = m.get("role", "user")
        content = m.get("content") or ""
        if isinstance(content, list):  # content-part arrays
            content = "".join(p.get("text", "") for p in content
                              if isinstance(p, dict))
        if role in ("system", "developer"):
            # 'developer' is OpenAI's successor to 'system' — same slot.
            system = content if not system else f"{system}\n{content}"
        elif role in ("user", "assistant"):
            turns.append((role, content))
        elif role == "tool":
            # Tool-result round-trips: fold the result into the transcript
            # as a user-visible observation (our chat template has no
            # separate tool role) instead of silently dropping it.
            tool_id = m.get("tool_call_id") or m.get("name") or "tool"
            turns.append(("user", f"[tool result {tool_id}]\n{content}"))
        else:
            raise ValueError(f"unsupported message role {role!r}")
    if turns and turns[-1][0] == "assistant":
        # Assistant-prefill (trailing assistant message) is not supported
        # by the chat template; rendering an empty user turn would degrade
        # the prompt silently. Refuse loudly (maps to HTTP 400). A
        # system-only request stays valid (empty user turn, as before).
        raise ValueError(
            "the last non-system message must be a user or tool message; "
            "assistant prefill is not supported")
    user = turns.pop()[1] if turns else ""
    return system, turns, user


class _EngineBridge:
    """Owns the asyncio loop thread the AsyncEngine lives on."""

    def __init__(self, client):
        self.client = client  # JaxTpuClient
        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self.loop.run_forever, name="serve-loop", daemon=True)
        self._thread.start()

    def run(self, coro, timeout: Optional[float] = None):
        return asyncio.run_coroutine_threadsafe(
            coro, self.loop).result(timeout)

    def stream(self, agen, timeout: Optional[float] = None):
        """Drain an async generator from a plain thread, yielding items.

        On a per-item timeout the pending ``__anext__`` task is CANCELLED
        on the loop first — that unwinds the generator's suspended await so
        its ``finally`` (the engine-abort path) actually runs — and only
        then is ``aclose`` awaited; closing a still-running generator would
        raise RuntimeError and leak the engine request."""
        sentinel = object()

        async def _next():
            try:
                return await agen.__anext__()
            except StopAsyncIteration:
                return sentinel

        while True:
            fut = asyncio.run_coroutine_threadsafe(_next(), self.loop)
            try:
                item = fut.result(timeout)
            except _FutTimeout:
                fut.cancel()

                async def _close():
                    try:
                        await agen.aclose()
                    except RuntimeError:
                        pass  # cancellation still unwinding

                try:
                    asyncio.run_coroutine_threadsafe(
                        _close(), self.loop).result(10)
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
                raise TimeoutError("stream item timed out")
            if item is sentinel:
                return
            yield item

    def shutdown(self) -> None:
        try:
            self.run(self.client.shutdown(), timeout=10)
        except Exception:  # noqa: BLE001 — best-effort teardown
            pass
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=5)


def _logprob_entry(tokenizer, e: dict, top_n: int) -> dict:
    """Engine logprob record → OpenAI chat-completions schema entry."""

    def token_fields(tid: int) -> dict:
        # id_to_bytes round-trips tokens that are PARTIAL UTF-8 sequences
        # (byte-level BPE splits characters across tokens); decode([tid])
        # would corrupt them to U+FFFD and the bytes field exists so
        # clients can reassemble exactly these splits.
        raw = tokenizer.id_to_bytes(tid)
        return {"token": raw.decode("utf-8", errors="replace"),
                "bytes": list(raw)}

    out = token_fields(e["token_id"]) | {"logprob": e["logprob"]}
    if top_n:
        out["top_logprobs"] = [
            token_fields(t) | {"logprob": lp}
            for t, lp in e["top"][:top_n]]
    else:
        out["top_logprobs"] = []
    return out


def parse_openai_sampling(body: dict, client, tokenizer=None,
                          defaults=None) -> tuple[Any, int, int]:
    """Shared OpenAI sampling-field parsing for the chat and legacy
    completions endpoints: stop, n, logprobs, penalties, seed,
    logit_bias, max_tokens (and its max_completion_tokens alias).
    Returns (sampling, n, top_logprobs); raises ValueError on invalid
    input (the handlers map that to HTTP 400). ``tokenizer`` and
    ``defaults`` are the RESOLVED model group's pieces under multi-model
    serving — stop ids and the logit_bias vocab check are per group, and
    a group's derived config (``llm.models[].overrides``) supplies the
    temperature/top_p/top_k/max_new_tokens fallbacks for fields the
    request leaves unset. Both default to the client's."""
    from runbookai_tpu.engine.request import SamplingParams

    tokenizer = tokenizer if tokenizer is not None else client.tokenizer
    defaults = defaults if defaults is not None else client
    stop = body.get("stop") or []
    if isinstance(stop, str):
        stop = [stop]
    if not all(isinstance(s, str) for s in stop):
        raise ValueError("stop must be a string or list of strings")
    if len(stop) > 4:
        raise ValueError("at most 4 stop sequences")
    n = int(body.get("n", 1))
    if not 1 <= n <= 8:
        raise ValueError("n must be in [1, 8]")
    want_logprobs = bool(body.get("logprobs"))
    top_logprobs = int(body.get("top_logprobs") or 0)
    if top_logprobs and not want_logprobs:
        raise ValueError("top_logprobs requires logprobs: true")
    if not 0 <= top_logprobs <= 20:
        raise ValueError("top_logprobs must be 0..20")
    # `or 0.0`: OpenAI marks these nullable (null == default).
    presence = float(body.get("presence_penalty") or 0.0)
    frequency = float(body.get("frequency_penalty") or 0.0)
    if not -2.0 <= presence <= 2.0:
        raise ValueError("presence_penalty must be in [-2, 2]")
    if not -2.0 <= frequency <= 2.0:
        raise ValueError("frequency_penalty must be in [-2, 2]")
    seed = body.get("seed")
    if seed is not None:
        seed = int(seed)
    lb = body.get("logit_bias") or {}
    if not isinstance(lb, dict):
        raise ValueError("logit_bias must be an object of token_id -> bias")
    logit_bias = []
    for tok_id, b_val in lb.items():
        b_val = float(b_val)
        if not -100.0 <= b_val <= 100.0:
            raise ValueError("logit_bias values must be in [-100, 100]")
        tid = int(tok_id)
        if not 0 <= tid < tokenizer.vocab_size:
            raise ValueError(f"logit_bias token id {tid} out of vocab range")
        logit_bias.append((tid, b_val))
    sampling = SamplingParams(
        temperature=float(body.get("temperature", defaults.temperature)),
        top_p=float(body.get("top_p", defaults.top_p)),
        top_k=int(body.get("top_k", defaults.top_k)),
        max_new_tokens=int(body.get("max_tokens")
                           or body.get("max_completion_tokens")
                           or defaults.max_new_tokens),
        stop_token_ids=(tokenizer.eot_id, tokenizer.eos_id),
        stop_strings=tuple(stop),
        logprobs=((top_logprobs or 1) if want_logprobs else 0),
        presence_penalty=presence,
        frequency_penalty=frequency,
        seed=seed,
        logit_bias=tuple(logit_bias),
    )
    return sampling, n, top_logprobs


def _completion_payload(model: str, content: str, usage: dict,
                        finish: str = "stop") -> dict:
    return {
        "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
        "object": "chat.completion",
        "created": int(time.time()),
        "model": model,
        "choices": [{
            "index": 0,
            "message": {"role": "assistant", "content": content},
            "finish_reason": finish,
        }],
        "usage": {
            "prompt_tokens": usage.get("prompt_tokens", 0),
            "completion_tokens": usage.get("completion_tokens", 0),
            "total_tokens": (usage.get("prompt_tokens", 0)
                             + usage.get("completion_tokens", 0)),
        },
    }


def _chunk_payload(model: str, delta: dict, finish: Optional[str],
                   chunk_id: str) -> dict:
    return {
        "id": chunk_id,
        "object": "chat.completion.chunk",
        "created": int(time.time()),
        "model": model,
        "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
    }


def make_handler(bridge: _EngineBridge, model_name: str,
                 request_timeout: float,
                 allow_runtime_adapters: bool = False,
                 embedder=None):
    client = bridge.client
    _embed_mutex = threading.Lock()
    started_at = time.time()
    registry = get_registry()
    requests_total = registry.counter(
        "runbook_requests_total", "HTTP requests served",
        labels=("route", "method", "status"))
    request_latency = registry.histogram(
        "runbook_request_latency_seconds", "HTTP request handling latency",
        labels=("route", "method"), buckets=REQUEST_LATENCY_BUCKETS)

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *args) -> None:  # quiet; metrics via /metrics
            pass

        def send_response(self, code: int, message=None) -> None:
            # Every response (JSON, SSE, errors) echoes the correlation id;
            # the hook also records the status for the route metrics.
            super().send_response(code, message)
            self._status = code
            rid = getattr(self, "_request_id", None)
            if rid:
                self.send_header("x-request-id", rid)

        def _dispatch(self, method: str, fn) -> None:
            """Route wrapper: request-id propagation, tracer context, and
            per-route request/latency instrumentation."""
            self._request_id = (self.headers.get("x-request-id")
                                or f"req-{uuid.uuid4().hex[:16]}")
            self._status = 0
            # Route label from the bare path (query strings must neither
            # split the label cardinality nor 404 a known route).
            bare = self.path.partition("?")[0]
            route = bare if bare in _KNOWN_ROUTES else "other"
            tracer = get_tracer()
            tracer.set_context(request_id=self._request_id)
            # time.monotonic(): the request's t_received, on the clock of
            # the engine's step and lifecycle records. It reaches the
            # EngineRequest by context (request.RequestOrigin).
            t0 = time.monotonic()
            origin = request_origin.set(RequestOrigin(t_received=t0))
            # On the profiler's clock, ``server.parse``: from here (body
            # read, template, tokenise, admission) to the engine hand-off,
            # where the route closes it, or to the end of a route that
            # has none (closing twice is harmless). Stat ``request`` is
            # the lifecycle record's ``trace_id``: the join that splits a
            # request's way in into parse and hand-off
            # (benchmark/tools/front_door.py).
            self._parse = contextlib.ExitStack()
            self._parse.enter_context(
                annotate("server.parse", request=self._request_id))
            try:
                with tracer.span("server.request", route=route,
                                 method=method):
                    fn()
            finally:
                self._parse.close()
                request_origin.reset(origin)
                tracer.clear_context()
                status = str(self._status or 500)
                requests_total.labels(
                    route=route, method=method,
                    status=status if status in _KNOWN_STATUSES
                    else "other").inc()
                request_latency.labels(route=route, method=method).observe(
                    time.monotonic() - t0)

        def _json(self, code: int, payload: dict,
                  headers: Optional[dict] = None) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for key, value in (headers or {}).items():
                self.send_header(key, str(value))
            self.end_headers()
            self.wfile.write(body)

        def _error(self, code: int, message: str,
                   retry_after: Optional[float] = None,
                   err_type: str = "invalid_request_error") -> None:
            import math

            headers = None
            if retry_after is not None:
                # Both throttles (429) and sheds (503) tell the client
                # WHEN to come back — integer seconds, never 0 (a zero
                # would read as "retry immediately", i.e. a retry storm).
                headers = {"Retry-After": max(1, math.ceil(retry_after))}
            self._json(code, {"error": {"message": message,
                                        "type": err_type}},
                       headers=headers)

        def _api_key(self) -> Optional[str]:
            """Tenant key of this request: ``Authorization: Bearer`` wins,
            ``x-api-key`` is the fallback, absent = anonymous (pools
            under the default tenant)."""
            auth = self.headers.get("Authorization") or ""
            if auth.lower().startswith("bearer "):
                return auth[7:].strip() or None
            return self.headers.get("x-api-key")

        def _priority_override(self) -> Optional[int]:
            """Explicit ``x-priority`` header, or None to follow the
            tenant's configured class. Only the canonical class names
            are accepted from the NETWORK — arbitrary ints would let any
            client mint a priority class with an arbitrarily large
            scheduler weight (internal callers keep free-form ints on
            the engine API). Raises ValueError on junk (→ 400)."""
            hdr = self.headers.get("x-priority")
            if hdr is None:
                return None
            priority = class_priority(hdr)
            if priority not in CLASS_NAMES:
                raise ValueError(
                    f"x-priority must be one of "
                    f"{sorted(CLASS_NAMES.values())}, got {hdr!r}")
            return priority

        def _admit_tenant(self, prompt_tokens: int, max_new_tokens: int,
                          kv_pages: float = 0.0):
            """Tenant admission BEFORE enqueue (sched/tenants.py):
            returns ``(admission, priority)`` — admission is None when no
            governor is configured. A throttled request is answered 429 +
            Retry-After here and ``(None, None)`` is returned; the caller
            must then bail without touching the engine. ``kv_pages`` is
            the request's estimated worst-case KV footprint
            (ceil((prompt + n·max_new)/page_size)) — tenants with a
            kv_page_limit reserve it for the request's lifetime, and the
            429 names WHICH bucket refused."""
            # Header parse FIRST: a junk x-priority must 400 before any
            # bucket is charged (no refund bookkeeping for bad input).
            override = self._priority_override()  # caller catches ValueError
            governor = getattr(client, "tenants", None)
            admission = None
            if governor is not None:
                admission = governor.admit(self._api_key(), prompt_tokens,
                                           max_new_tokens,
                                           kv_pages=kv_pages)
                if not admission.allowed:
                    if admission.reason == "kv_pages_oversized":
                        # The request ALONE exceeds the tenant's page
                        # ledger: no amount of waiting admits it, so a
                        # retryable 429 would loop a compliant client
                        # forever — refuse it outright.
                        self._error(
                            400,
                            f"request exceeds tenant "
                            f"{admission.tenant!r}'s kv_page_limit "
                            f"(estimated pages > limit); shrink the "
                            f"prompt or max_tokens")
                        return None, None
                    limit = {"rate_limit": "rate limit",
                             "token_budget": "token budget",
                             "kv_pages": "kv page budget",
                             }.get(admission.reason, "limit")
                    self._error(
                        429,
                        f"tenant {admission.tenant!r} is over its {limit}; "
                        f"retry after {max(1.0, admission.retry_after_s):.0f}s",
                        retry_after=admission.retry_after_s,
                        err_type="rate_limit_error")
                    return None, None
            # Untenanted server traffic defaults to the interactive
            # class: a human is usually waiting on an HTTP response, and
            # batch tiers must OPT IN (tenant config or header).
            ceiling = (admission.priority if admission is not None
                       else PRIORITY_INTERACTIVE)
            if override is not None:
                # The header can DEMOTE a request below its tenant's
                # class, never promote past it — a tenant configured
                # batch must not self-escalate into the interactive tier
                # by setting a header.
                priority = min(override, ceiling)
            else:
                priority = ceiling
            return admission, priority

        def _settle_tenant(self, admission, actual_tokens: int) -> None:
            governor = getattr(client, "tenants", None)
            if governor is not None and admission is not None:
                governor.settle(admission, actual_tokens)

        def _resolve_model(self, requested):
            """Resolve the request's ``model`` field to the serving
            pieces: ``(model_out, adapter, engine, tokenizer,
            chat_format, page_size, sampling_defaults)`` — or ``None``
            with the error already sent (404 unknown model, 403
            tenant-pin violation).

            Multi-model fleets (``llm.models``) dispatch to the owning
            group: group name -> that group, adapter name -> its group
            with the adapter selected, absent -> the tenant's pinned
            group or the default. The single-model path is exactly the
            historical logic (adapter-as-model within the one engine).
            Everything downstream — prompt encoding, sampling limits,
            admission page estimates, the stream itself — uses the
            RESOLVED group's tokenizer/engine, so a request never mixes
            one model's tokenizer with another's replicas."""
            governor = getattr(client, "tenants", None)
            pinned = (governor.pinned_model(self._api_key())
                      if governor is not None else None)
            mm = getattr(client, "multi_model", None)
            if mm is not None:
                try:
                    group_name, adapter = mm.resolve(requested or pinned)
                except KeyError as e:
                    self._error(404, str(e.args[0]) if e.args else str(e))
                    return None
                if pinned is not None and group_name != pinned:
                    # Tenant-affine placement: the pin is an isolation
                    # boundary, not a default — a pinned tenant naming
                    # another group is refused, never silently re-routed.
                    self._error(
                        403,
                        f"tenant {governor.resolve(self._api_key())!r} "
                        f"is pinned to model {pinned!r}; requested "
                        f"{requested!r}", err_type="permission_error")
                    return None
                group = mm.groups[group_name]
                # The group's derived config supplies sampling
                # fallbacks (llm.models[].overrides — e.g. a per-group
                # max_new_tokens); client-level defaults otherwise.
                return ((requested or group_name), adapter, group.fleet,
                        group.tokenizer, group.chat_format,
                        group.page_size, group.llm_cfg or client)
            adapter = None
            if requested and requested != model_name:
                names = (client.core.lora.names
                         if client.core.lora is not None else [])
                if requested in names:
                    adapter = requested
                else:
                    # vLLM semantics: unknown model names are errors,
                    # not silent base-model serving.
                    self._error(404, f"model {requested!r} not found; "
                                     f"served: {[model_name] + names}")
                    return None
            return (requested or model_name, adapter, client.engine,
                    client.tokenizer, client.chat_format,
                    client.core.ecfg.page_size, client)

        def _read_json(self) -> dict:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(body, dict):
                raise ValueError("request body must be a JSON object")
            return body

        def do_GET(self) -> None:  # noqa: N802 — http.server API
            self._dispatch("GET", self._route_get)

        def do_POST(self) -> None:  # noqa: N802
            self._dispatch("POST", self._route_post)

        def _route_get(self) -> None:
            # Match every route on the bare path: a query string must not
            # 404 a known route the metrics just labeled as served.
            path, _, query = self.path.partition("?")
            if path == "/debug/steps":
                self._debug_steps(query)
                return
            if path == "/debug/workload":
                # Live workload fingerprints + plan-drift (obs/): per
                # served model group with a merged fleet-wide view.
                # Without a monitor the surface reports itself disabled
                # (not 404 — the CLI distinguishes "off" from "no
                # server"), matching /tenants.
                monitor = getattr(client, "workload_monitor", None)
                self._json(200, monitor.snapshot() if monitor is not None
                           else {"enabled": False, "models": {}})
                return
            if path == "/debug/query":
                self._debug_query(query)
                return
            if path == "/debug/incidents":
                # Live incident feed + captured-bundle listing
                # (obs/incident.py). Without a monitor the surface
                # reports itself disabled (not 404 — the CLI
                # distinguishes "off" from "no server"), matching
                # /debug/workload and /tenants.
                monitor = getattr(client, "incident_monitor", None)
                self._json(200, monitor.snapshot(full=True)
                           if monitor is not None
                           else {"enabled": False, "open": []})
                return
            if path == "/v1/models":
                mm = getattr(client, "multi_model", None)
                if mm is not None:
                    # Full served catalog: every model group (with its
                    # replica count) and every group's adapters, each
                    # adapter parented to its group.
                    self._json(200, {"object": "list",
                                     "data": mm.served_models()})
                    return
                models = [{"id": model_name, "object": "model",
                           "owned_by": "runbookai-tpu"}]
                if client.core.lora is not None:
                    # vLLM-style: LoRA adapters are served as model names.
                    models += [{"id": n, "object": "model",
                                "owned_by": "runbookai-tpu",
                                "parent": model_name}
                               for n in client.core.lora.names]
                self._json(200, {"object": "list", "data": models})
            elif path == "/healthz":
                # Snapshot under the engine's step lock: the loop thread
                # mutates several keys per step, so a lock-free shallow
                # copy could pair a new decode_tokens with an old
                # decode_time_s. Bounded wait only — a step that is busy
                # compiling a new batch shape can hold the lock for tens
                # of seconds, and a liveness probe that blocks that long
                # gets the pod killed mid-compile. A torn-but-live
                # snapshot beats a dead prober.
                body = {"status": "ok", "model": model_name,
                        "uptime_s": round(time.time() - started_at, 3)}
                snapshot = getattr(client.engine, "health_snapshot", None)
                if snapshot is not None:
                    # Engine fleet: summed metrics dict (the contract keys
                    # become fleet-wide totals), pooled KV stats, plus the
                    # per-replica breakdown and router state.
                    body.update(snapshot())
                else:
                    lock = getattr(client.engine, "_lock", None)
                    locked = lock is not None and lock.acquire(timeout=0.5)
                    try:
                        m = dict(client.core.metrics)
                    finally:
                        if locked:
                            lock.release()
                    kv = client.core.kv
                    body["kv"] = {
                        "pages_total": kv.allocator.num_pages,
                        "pages_in_use": kv.pages_in_use,
                        "pages_cached": kv.allocator.cached_pages,
                        "utilization": round(kv.utilization(), 4)}
                    body["metrics"] = m
                runtime = getattr(client, "runtime_info", None)
                if runtime is not None:
                    # Device, resolved kernels, allocator, compile cache:
                    # what this process serves ON (chip_smoke.py reads
                    # its facts here).
                    body["runtime"] = runtime()
                slo = getattr(client, "slo_monitor", None)
                if slo is not None and slo.objectives:
                    # Live SLO state (utils/slo.py): targets vs current
                    # percentiles and the burn ratio per objective — the
                    # feedback signal SLO-aware scheduling will consume.
                    body["slo"] = slo.evaluate()
                monitor = getattr(client, "workload_monitor", None)
                if monitor is not None:
                    # Live workload fingerprint + plan-drift (obs/):
                    # per-group for multi-model fleets, merged
                    # fleet-wide like debug_steps.
                    body["workload"] = monitor.snapshot()
                store = getattr(client, "tsdb", None)
                if store is not None:
                    # Metric-history accounting (obs/tsdb.py): series /
                    # sample / memory bounds of the embedded store that
                    # /debug/query evaluates against. Block present
                    # only when a store is attached (llm.obs.tsdb).
                    body["history"] = store.snapshot()
                incidents = getattr(client, "incident_monitor", None)
                if incidents is not None:
                    # Incident feed (obs/incident.py): open incidents +
                    # per-signal totals. Block present only when a
                    # monitor is attached, and totals carry only
                    # signals that HAVE incidents — absence-not-zero,
                    # the runbook_slo_* contract.
                    body["incidents"] = incidents.snapshot()
                self._json(200, body)
            elif path == "/tenants":
                # Tenant accounting state (sched/tenants.py): configured
                # policies, live bucket levels, admit/throttle counters —
                # the `runbook tenants` CLI renders this. Without a
                # governor the surface reports itself disabled (not 404:
                # the CLI distinguishes "off" from "no server").
                governor = getattr(client, "tenants", None)
                self._json(200, governor.snapshot() if governor is not None
                           else {"enabled": False, "tenants": {}})
            elif path == "/metrics":
                body = registry.render().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self._error(404, f"no route {self.path}")

        def _debug_steps(self, query: str) -> None:
            """``GET /debug/steps[?n=N]`` — the engine flight recorder's
            last N per-step records (dispatch kind, tokens, occupancy,
            queue depth, KV pressure, wall split). Single engine and
            fleet both serve it: ``AsyncFleet.debug_steps`` merges the
            replicas' rings into one ts-ordered timeline."""
            n = 128
            for part in query.split("&"):
                if part.startswith("n="):
                    try:
                        n = max(0, int(part[2:]))
                    except ValueError:
                        self._error(400, f"bad n value {part[2:]!r}")
                        return
            snap_fn = getattr(client.engine, "debug_steps", None)
            if snap_fn is None:
                self._error(404, "engine has no flight recorder")
                return
            self._json(200, snap_fn(n))

        def _debug_query(self, query: str) -> None:
            """``GET /debug/query?expr=EXPR[&range=5m]`` — PromQL-lite
            over the embedded time-series store (obs/tsdb.py +
            obs/query.py). The body is the evaluator's CANONICAL bytes
            (sorted keys, compact separators), so the query-determinism
            pin covers the HTTP surface too. Without a store the
            surface reports itself disabled (not 404 — the CLI
            distinguishes "off" from "no server"), matching
            /debug/workload."""
            import urllib.parse

            from runbookai_tpu.obs.query import (
                QueryError,
                evaluate,
                parse_duration,
                result_json,
            )

            store = getattr(client, "tsdb", None)
            params = urllib.parse.parse_qs(query)
            expr = (params.get("expr") or [""])[0]
            if store is None:
                self._json(200, {"enabled": False, "expr": expr,
                                 "result": []})
                return
            if not expr:
                self._error(400, "expr parameter is required")
                return
            range_s = None
            raw_range = (params.get("range") or [None])[0]
            try:
                if raw_range:
                    range_s = parse_duration(raw_range)
                doc = evaluate(store, expr,
                               **({"default_range_s": range_s}
                                  if range_s is not None else {}))
            except QueryError as e:
                self._error(400, str(e))
                return
            body = result_json(doc).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _route_post(self) -> None:
            if self.path == "/v1/adapters":
                self._load_adapter()
                return
            if self.path == "/v1/embeddings":
                self._embeddings()
                return
            if self.path == "/v1/completions":
                self._legacy_completions()
                return
            if self.path != "/v1/chat/completions":
                self._error(404, f"no route {self.path}")
                return
            try:
                body = self._read_json()
                messages = body.get("messages") or []
                if not messages:
                    raise ValueError("messages is required")
                system, history, user = messages_to_prompt_parts(messages)
                # Model-field routing: a multi-model fleet dispatches to
                # the owning group (unknown model -> 404, tenant pin ->
                # 403); single-model keeps vLLM-style adapter-as-model.
                resolved = self._resolve_model(body.get("model"))
                if resolved is None:
                    return  # 404/403 already sent
                (model_out, adapter, eng, tok, chat_fmt, page_size,
                 sp_defaults) = resolved
                # Client-supplied values: coercion failures are 400s too.
                sampling, n, top_logprobs = parse_openai_sampling(
                    body, client, tokenizer=tok, defaults=sp_defaults)
                # response_format json_object -> grammar-constrained
                # decoding (the engine's guided JSON automaton): output is
                # a valid-JSON prefix by construction, and a COMPLETE
                # parseable document whenever finish_reason != "length"
                # (max_tokens can still truncate mid-document).
                rf = body.get("response_format") or {}
                if not isinstance(rf, dict):
                    # {"response_format": "json_object"} is a common client
                    # mistake; coercing to text would silently drop the
                    # JSON guarantee the caller asked for.
                    raise ValueError("response_format must be an object "
                                     "like {\"type\": \"json_object\"}")
                rf_type = rf.get("type", "text")
                if rf_type not in ("text", "json_object"):
                    raise ValueError(
                        "response_format.type must be text or json_object")
                sampling.guided = ("json" if rf_type == "json_object"
                                   else None)
            except (ValueError, TypeError, json.JSONDecodeError) as e:
                self._error(400, str(e))
                return

            import math

            from runbookai_tpu.model.chat_template import build_chat_prompt

            prompt = build_chat_prompt(system, user, history=history,
                                       fmt=chat_fmt)
            ids = tok.encode(prompt)

            # Tenant admission BEFORE the engine sees anything: a tenant
            # over its rate limit, token budget, or in-flight KV-page
            # ledger gets 429 + Retry-After and never consumes a slot, a
            # KV page, or a queue entry. The page estimate is the
            # request's worst case at the RESOLVED group's page size:
            # the n choices run as n CONCURRENT engine requests, each
            # holding its own live copy of the prompt's pages while it
            # decodes (in-flight prefills don't share; only retired
            # prefix pages do) — so the prompt counts n times here even
            # though the token budget counts it once.
            try:
                admission, priority = self._admit_tenant(
                    len(ids), n * sampling.max_new_tokens,
                    kv_pages=math.ceil(
                        n * (len(ids) + sampling.max_new_tokens)
                        / max(1, page_size)))
            except ValueError as e:  # junk x-priority header
                self._error(400, str(e))
                return
            if priority is None:
                return  # throttled; 429 already sent

            # Replica failover: while EVERY replica of the resolved
            # group is quarantined (supervisor mid-rebuild), nothing can
            # be placed — answer a real 503 with Retry-After now instead
            # of burning a shed/abort on a request that cannot be
            # served. Healthy siblings of a multi-model fleet are
            # unaffected (the check is per resolved group).
            failover = getattr(eng, "failing_over", None)
            if failover is not None and failover():
                self._settle_tenant(admission, 0)
                self._error(503, "replica failover in progress (no "
                                 "replica available; retry shortly)",
                            retry_after=_SHED_RETRY_AFTER_S)
                return
            try:
                if body.get("stream"):
                    if n != 1:
                        self._settle_tenant(admission, 0)
                        self._error(400, "stream with n > 1 is unsupported")
                        return
                    # Fleet shedding: refuse BEFORE committing SSE headers
                    # so a saturated pod answers a real 503 (the check-
                    # then-route race falls back to an in-stream error
                    # event inside _stream_response). The RESOLVED
                    # group's saturation is what matters — one model's
                    # flood must not shed a healthy sibling's stream.
                    saturated = getattr(eng, "is_saturated", None)
                    if saturated is not None and saturated():
                        self._settle_tenant(admission, 0)
                        self._error(503, "all fleet replicas are "
                                         "saturated (request shed)",
                                    retry_after=_SHED_RETRY_AFTER_S)
                        return
                    so = body.get("stream_options") or {}
                    self._stream_response(
                        ids, sampling, adapter,
                        top_logprobs=top_logprobs,
                        include_usage=bool(so.get("include_usage")),
                        priority=priority, admission=admission,
                        engine=eng, tokenizer=tok, model=model_out)
                else:
                    # The engine-side timeout ABORTS a stalled request
                    # (frees slot + KV pages) before raising; the bridge
                    # timeout is just a belt over a wedged loop thread.
                    # n > 1 choices submit concurrently: the engine batches
                    # them in one decode dispatch and the shared prompt
                    # prefix rides the page cache.
                    def _choice_sampling(i: int):
                        # A fixed seed must still produce n DISTINCT
                        # choices: choice i samples under seed+i (choice
                        # 0 reproduces the n=1 output for that seed).
                        if sampling.seed is None or i == 0:
                            return sampling
                        import dataclasses as _dc

                        return _dc.replace(sampling,
                                           seed=sampling.seed + i)

                    async def _gen_n():
                        # return_exceptions: every sibling runs to its own
                        # terminal state (each generate aborts itself on
                        # its engine-side timeout) — nothing keeps decoding
                        # unobserved after an error response.
                        return await asyncio.gather(*[
                            eng.generate(
                                ids, _choice_sampling(i),
                                timeout_s=request_timeout,
                                priority=priority, adapter=adapter,
                                request_id=self._request_id)
                            for i in range(n)], return_exceptions=True)

                    self._parse.close()  # the engine's from here
                    outs = bridge.run(_gen_n(), timeout=request_timeout + 60)
                    if any(isinstance(o, BaseException) for o in outs):
                        self._settle_tenant(admission, 0)
                        err = next(o for o in outs
                                   if isinstance(o, BaseException))
                        if isinstance(err, (TimeoutError, _FutTimeout)):
                            self._error(504, "generation timed out")
                        else:
                            raise err
                        return
                    if any(o.finish_reason.value == "aborted" for o in outs):
                        # Admission fail-fast (prompt can never fit), a
                        # fleet shed, or a mid-decode abort: an error, not
                        # a completion — and a failed request is never
                        # billed against the tenant's budget.
                        self._settle_tenant(admission, 0)
                        self._error(503, "request aborted by the engine "
                                         "(insufficient KV capacity)",
                                    retry_after=_SHED_RETRY_AFTER_S)
                        return
                    self._settle_tenant(
                        admission,
                        len(ids) + sum(o.decode_tokens for o in outs))

                    def choice(i, o):
                        c = {"index": i,
                             "message": {"role": "assistant",
                                         "content": o.text},
                             "finish_reason": ("length"
                                               if o.finish_reason.value
                                               == "max_tokens"
                                               else "stop")}
                        if o.logprobs is not None:
                            c["logprobs"] = {"content": [
                                _logprob_entry(tok, e, top_logprobs)
                                for e in o.logprobs]}
                        return c

                    payload = _completion_payload(
                        model_out, "",
                        {"prompt_tokens": len(ids),
                         "completion_tokens": sum(o.decode_tokens
                                                  for o in outs)})
                    # prompt_tokens is counted ONCE for n>1 (the choices
                    # share one prompt), so cached_tokens must stay a
                    # subset of it: max() = how much of that one counted
                    # prompt was cache-served. Later choices hitting the
                    # prefix the first published is internal dedupe, not
                    # request-level caching — summing it would report
                    # cached > prompt_tokens (negative uncached math for
                    # OpenAI-schema clients).
                    payload["usage"]["prompt_tokens_details"] = {
                        "cached_tokens": max(o.cached_tokens for o in outs)}
                    payload["choices"] = [choice(i, o)
                                          for i, o in enumerate(outs)]
                    self._json(200, payload)
            except (TimeoutError, _FutTimeout):
                self._settle_tenant(admission, 0)
                self._error(504, "generation timed out")
            except BrokenPipeError:
                # Client went away; engine abort handled in stream path.
                # The reservation is refunded (failed work isn't billed).
                self._settle_tenant(admission, 0)

        def _legacy_completions(self) -> None:
            """Legacy `/v1/completions`: raw-prompt text completion, no
            chat template. ``prompt`` may be a string or list of strings
            (OpenAI returns len(prompt) * n choices, prompt-major); all
            shared sampling fields apply, ``logprobs`` is the classic
            int (top-N per sampled token), and adapter-as-model routing
            matches the chat endpoint. Streaming is not offered on the
            legacy surface — use `/v1/chat/completions`."""
            admission = None
            try:
                body = self._read_json()
                if body.get("stream"):
                    raise ValueError(
                        "stream is not supported on /v1/completions; "
                        "use /v1/chat/completions")
                prompts = body.get("prompt")
                if isinstance(prompts, str):
                    prompts = [prompts]
                if (not prompts or not isinstance(prompts, list)
                        or not all(isinstance(p, str) for p in prompts)):
                    raise ValueError(
                        "prompt must be a string or list of strings")
                if len(prompts) > 8:
                    raise ValueError("at most 8 prompts per request")
                # Same routing policy as chat: model-field dispatch
                # (multi-model groups / adapter-as-model), unknown names
                # are 404s — never silent base-model serving.
                requested = body.get("model")
                resolved = self._resolve_model(requested)
                if resolved is None:
                    return  # 404/403 already sent
                (model_out, adapter, eng, tok, _fmt, page_size,
                 sp_defaults) = resolved
                sampling, n, _ = parse_openai_sampling(
                    body, client, tokenizer=tok, defaults=sp_defaults)
                # Classic logprobs is an int: top-N alternatives per token.
                lp_n = int(body.get("logprobs") or 0)
                if not 0 <= lp_n <= 5:
                    raise ValueError("logprobs must be 0..5")
                sampling.logprobs = lp_n
                echo = bool(body.get("echo"))
                # Tokenize each prompt ONCE: the same ids feed the engine
                # and the usage count, so they cannot disagree.
                all_ids = [tok.encode(p) for p in prompts]

                # Same tenant gate as the chat endpoint: the reservation
                # covers every prompt and all n completions per prompt
                # (tokens AND estimated KV pages — each of the n×len(
                # prompts) concurrent requests holds its own live prompt
                # copy, so prompts count n times in the page estimate).
                import math

                prompt_total = sum(len(ids) for ids in all_ids)
                reserve_new = n * len(all_ids) * sampling.max_new_tokens
                admission, priority = self._admit_tenant(
                    prompt_total, reserve_new,
                    kv_pages=math.ceil(
                        (n * prompt_total + reserve_new)
                        / max(1, page_size)))
                if priority is None:
                    return  # throttled; 429 + Retry-After already sent

                async def _gen_all():
                    import dataclasses as _dc

                    jobs = []
                    for ids in all_ids:
                        for i in range(n):
                            sp = sampling
                            if sampling.seed is not None and i:
                                sp = _dc.replace(sampling,
                                                 seed=sampling.seed + i)
                            jobs.append(eng.generate(
                                ids, sp, timeout_s=request_timeout,
                                priority=priority, adapter=adapter,
                                request_id=self._request_id))
                    return await asyncio.gather(*jobs,
                                                return_exceptions=True)

                self._parse.close()  # the engine's from here
                outs = bridge.run(_gen_all(), timeout=request_timeout + 60)
                if any(isinstance(o, BaseException) for o in outs):
                    self._settle_tenant(admission, 0)
                    err = next(o for o in outs
                               if isinstance(o, BaseException))
                    if isinstance(err, (TimeoutError, _FutTimeout)):
                        self._error(504, "generation timed out")
                        return
                    raise err
                if any(o.finish_reason.value == "aborted" for o in outs):
                    self._settle_tenant(admission, 0)
                    self._error(503, "request aborted by the engine "
                                     "(insufficient KV capacity)",
                                retry_after=_SHED_RETRY_AFTER_S)
                    return
                self._settle_tenant(
                    admission,
                    prompt_total + sum(o.decode_tokens for o in outs))

                def legacy_lp(o, text_start: int):
                    if not lp_n or not o.logprobs:
                        return None
                    tokens, tlps, tops, offsets = [], [], [], []
                    off = text_start
                    for e in o.logprobs:
                        raw = tok.id_to_bytes(
                            e["token_id"]).decode("utf-8", "replace")
                        tokens.append(raw)
                        tlps.append(e["logprob"])
                        tops.append({
                            tok.id_to_bytes(t).decode(
                                "utf-8", "replace"): lp
                            for t, lp in e["top"][:lp_n]})
                        offsets.append(off)
                        off += len(raw)
                    return {"tokens": tokens, "token_logprobs": tlps,
                            "top_logprobs": tops, "text_offset": offsets}

                choices = []
                for pi, p in enumerate(prompts):
                    for i in range(n):
                        o = outs[pi * n + i]
                        choices.append({
                            "index": pi * n + i,
                            "text": (p + o.text) if echo else o.text,
                            "logprobs": legacy_lp(
                                o, len(p) if echo else 0),
                            "finish_reason": ("length"
                                              if o.finish_reason.value
                                              == "max_tokens" else "stop"),
                        })
                prompt_tokens = sum(len(ids) for ids in all_ids)
                completion_tokens = sum(o.decode_tokens for o in outs)
                self._json(200, {
                    "id": f"cmpl-{uuid.uuid4().hex[:12]}",
                    "object": "text_completion",
                    "created": int(time.time()),
                    "model": model_out,
                    "choices": choices,
                    "usage": {
                        "prompt_tokens": prompt_tokens,
                        "completion_tokens": completion_tokens,
                        "total_tokens": prompt_tokens + completion_tokens,
                    },
                })
            except (ValueError, TypeError, json.JSONDecodeError) as e:
                self._settle_tenant(admission, 0)
                self._error(400, str(e))
            except (TimeoutError, _FutTimeout):
                self._settle_tenant(admission, 0)
                self._error(504, "generation timed out")
            except BrokenPipeError:
                self._settle_tenant(admission, 0)  # client went away

        def _embeddings(self) -> None:
            """OpenAI embeddings API over the on-device bge encoder (the
            same encoder the knowledge index uses)."""
            if embedder is None:
                self._error(400, "no embedder configured "
                                 "(knowledge.embedder.enabled + model_path)")
                return
            emb_model = getattr(embedder.cfg, "name", "bge")
            try:
                body = self._read_json()
                requested = body.get("model")
                if requested and requested != emb_model:
                    # Same policy as chat: no silent model substitution.
                    self._error(404, f"model {requested!r} not found; "
                                     f"embeddings model: {emb_model}")
                    return
                texts = body.get("input")
                if isinstance(texts, str):
                    texts = [texts]
                if (not isinstance(texts, list) or not texts
                        or not all(isinstance(t, str) for t in texts)):
                    raise ValueError(
                        "input must be a string or list of strings")
                if len(texts) > 256:
                    raise ValueError("at most 256 inputs per request")
            except (ValueError, TypeError, json.JSONDecodeError) as e:
                self._error(400, str(e))
                return
            try:
                # One request at a time: encode bursts contend with decode
                # for the device, and the Embedder's cache/stats aren't
                # thread-safe across handler threads.
                with _embed_mutex:
                    vecs = embedder.embed_texts(texts)
                n_tokens = embedder.estimate_tokens(texts)
                self._json(200, {
                    "object": "list",
                    "model": emb_model,
                    "data": [{"object": "embedding", "index": i,
                              "embedding": [float(x) for x in v]}
                             for i, v in enumerate(vecs)],
                    "usage": {"prompt_tokens": n_tokens,
                              "total_tokens": n_tokens},
                })
            except BrokenPipeError:
                pass
            except Exception as e:  # noqa: BLE001 — compute failures -> 500
                self._error(500, f"embedding failed ({type(e).__name__})")

        def _load_adapter(self) -> None:
            """Hot-load a LoRA adapter into the running engine:
            ``POST /v1/adapters {"name": ..., "path": <PEFT dir>}``. The
            registry re-stacks and the engine swaps its params tree under
            the engine lock, so in-flight dispatches finish on the old
            tree and the next dispatch serves the new adapter."""
            if not allow_runtime_adapters:
                # Loading arbitrary server-side paths is an operator
                # action; gate it (vLLM gates its equivalent the same way).
                self._error(403, "runtime adapter loading is disabled; "
                                 "start with --allow-adapter-loading")
                return
            if getattr(client, "multi_model", None) is not None:
                # Runtime loads would need a target-group parameter and
                # per-group refresh; configure multi-model adapters in
                # llm.models[].adapters instead (loaded at startup).
                self._error(400, "runtime adapter loading is not "
                                 "supported with llm.models; configure "
                                 "llm.models[].adapters")
                return
            if client.core.lora is None:
                self._error(400, "engine has no LoRA registry (configure "
                                 "llm.lora_rank/lora_targets)")
                return
            try:
                body = self._read_json()
                name, path = body["name"], body["path"]
                if not isinstance(name, str) or not isinstance(path, str):
                    raise ValueError("name and path must be strings")
            except (ValueError, TypeError, KeyError,
                    json.JSONDecodeError) as e:
                self._error(400, f"expected {{name, path}}: {e}")
                return
            try:
                client.core.lora.load_peft_dir(name, path)
            except (OSError, TypeError, ValueError, KeyError) as e:
                # No raw OS error text: it would leak filesystem detail.
                import logging

                logging.getLogger(__name__).warning(
                    "adapter load %r failed: %s", name, e)
                self._error(400, f"could not load adapter {name!r} "
                                 f"({type(e).__name__})")
                return
            # Pre-stack on THIS thread (registry caches it); the engine
            # refresh then runs in a worker thread (loop stays live) and
            # only swaps the params dict. Even without it, submit()
            # detects a stale row count and refreshes safely.
            client.core.lora.stacked()
            try:
                bridge.run(client.engine.refresh_lora(), timeout=60)
            except (TimeoutError, _FutTimeout):
                self._error(504, f"adapter {name!r} registered but the "
                                 f"engine refresh timed out; it activates "
                                 f"on the next request")
                return
            self._json(200, {"loaded": name,
                             "adapters": client.core.lora.names})

        def _stream_response(self, ids, sampling, adapter=None,
                             top_logprobs: int = 0,
                             include_usage: bool = False,
                             priority: int = PRIORITY_INTERACTIVE,
                             admission=None, engine=None, tokenizer=None,
                             model: Optional[str] = None) -> None:
            from runbookai_tpu.model.jax_tpu import stream_text

            # The resolved model group's pieces (multi-model routing);
            # defaults keep the historical single-engine behavior for
            # direct callers.
            engine = engine if engine is not None else client.engine
            tokenizer = (tokenizer if tokenizer is not None
                         else client.tokenizer)
            model = model or model_name
            self._parse.close()  # the engine's from here
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

            # ``server.write``: each SSE write, with the request it is for.
            write_meta = {"request": getattr(self, "_request_id", "")}

            def send_chunk(payload: dict) -> None:
                with annotate("server.write", **write_meta):
                    data = f"data: {json.dumps(payload)}\n\n".encode()
                    self.wfile.write(f"{len(data):x}\r\n".encode() + data
                                     + b"\r\n")
                    self.wfile.flush()

            def send_terminator(extra: bytes = b"") -> None:
                with annotate("server.write", **write_meta):
                    done = extra + b"data: [DONE]\n\n"
                    self.wfile.write(f"{len(done):x}\r\n".encode() + done
                                     + b"\r\n0\r\n\r\n")
                    self.wfile.flush()

            chunk_id = f"chatcmpl-{uuid.uuid4().hex[:12]}"
            send_chunk(_chunk_payload(model, {"role": "assistant"},
                                      None, chunk_id))
            state: dict = {}
            # Shared with JaxTpuClient.chat_stream: one copy of the
            # incremental-UTF-8 / stop-token handling for all surfaces.
            # With logprobs, the live EngineRequest rides along (entries
            # accumulate on the engine thread; list reads are safe) and
            # each chunk carries the entries for tokens consumed since the
            # last chunk — OpenAI streams logprobs in the deltas.
            req_sink: list = []
            agen = stream_text(engine, tokenizer, ids,
                               sampling, state=state, priority=priority,
                               adapter=adapter, request_sink=req_sink,
                               request_id=getattr(self, "_request_id", None))
            lp_sent = 0

            def chunk_logprobs() -> Optional[dict]:
                nonlocal lp_sent
                if not sampling.logprobs or not req_sink:
                    return None
                entries = req_sink[0].out_logprobs
                upto = min(len(entries),
                           state.get("n_tokens", 0)
                           - (1 if state.get("saw_stop") else 0))
                if upto <= lp_sent:
                    return None
                out = {"content": [
                    _logprob_entry(tokenizer, e, top_logprobs)
                    for e in entries[lp_sent:upto]]}
                lp_sent = upto
                return out

            try:
                try:
                    for piece in bridge.stream(agen,
                                               timeout=request_timeout):
                        payload = _chunk_payload(
                            model, {"content": piece}, None, chunk_id)
                        lp = chunk_logprobs()
                        if lp is not None:
                            payload["choices"][0]["logprobs"] = lp
                        send_chunk(payload)
                        if req_sink:
                            # The first content chunk is on the socket
                            # (later calls change nothing): the request's
                            # t_first_write. The fleet appends the SERVING
                            # attempt's request last.
                            req_sink[-1].mark_first_write(time.monotonic())
                finally:
                    # Settle the tenant reservation at the TRUE size: the
                    # tokens the client actually received are billed even
                    # on disconnect; the unused tail of the reservation is
                    # refunded. Zero generated tokens means the engine
                    # never served this request (shed / abort) — full
                    # refund (sched/tenants.py).
                    n_streamed = state.get("n_tokens", 0)
                    self._settle_tenant(
                        admission,
                        (len(ids) + n_streamed) if n_streamed else 0)
                # Mid-stream abort (a replica died after tokens were
                # already streamed, past the fleet's pre-token failover;
                # or a shed landed mid-flight): end the SSE body with an
                # explicit error event — a clean signal, never a silent
                # "stop" truncation and never a hang. The fleet path
                # appends the SERVING attempt's request last.
                live_req = req_sink[-1] if req_sink else None
                if live_req is not None and live_req.finish_reason \
                        is FinishReason.ABORTED:
                    send_terminator(
                        b'data: {"error": {"message": "stream aborted '
                        b'by the engine (replica failure or shed)"}}'
                        b'\n\n')
                    return
                # max_tokens truncation reports "length", like non-stream.
                finish = ("length"
                          if not state.get("saw_stop")
                          and state.get("n_tokens", 0)
                          >= sampling.max_new_tokens else "stop")
                final = _chunk_payload(model, {}, finish, chunk_id)
                lp_tail = chunk_logprobs()  # entries past the last piece
                if lp_tail is not None:
                    final["choices"][0]["logprobs"] = lp_tail
                send_chunk(final)
                if include_usage:
                    # stream_options.include_usage: one extra chunk after
                    # the finish chunk with empty choices (OpenAI shape).
                    n_out = state.get("n_tokens", 0)
                    send_chunk({
                        "id": chunk_id,
                        "object": "chat.completion.chunk",
                        "created": int(time.time()),
                        "model": model,
                        "choices": [],
                        "usage": {"prompt_tokens": len(ids),
                                  "completion_tokens": n_out,
                                  "total_tokens": len(ids) + n_out},
                    })
                send_terminator()
            except (BrokenPipeError, ConnectionResetError):
                # Client disconnected mid-stream: close the generator so
                # AsyncEngine aborts the request and frees its slot/pages.
                try:
                    bridge.run(agen.aclose(), timeout=10)
                except Exception:  # noqa: BLE001 — socket is gone anyway
                    pass
            except (TimeoutError, _FutTimeout):
                # bridge.stream already cancelled + closed the generator
                # (engine abort ran). Headers are out, so end the chunked
                # SSE body well-formed with an error event, never a 504.
                try:
                    send_terminator(b'data: {"error": {"message": '
                                    b'"generation timed out"}}\n\n')
                except OSError:
                    pass
            except FleetSaturated:
                # Lost the pre-header saturation race: the fleet shed this
                # placement after the 200/SSE headers went out. Same
                # well-formed-body policy as the timeout path.
                try:
                    send_terminator(b'data: {"error": {"message": '
                                    b'"all fleet replicas are saturated '
                                    b'(request shed)"}}\n\n')
                except OSError:
                    pass

    return Handler


class _HTTPServer(ThreadingHTTPServer):
    # listen(2) backlog. At the stdlib's 5, a burst of simultaneous
    # connects wider than that is reset by the kernel before a handler
    # thread ever accepts it (32 at once from chip_smoke.py's four-replica
    # burst: "Connection reset by peer").
    request_queue_size = 128


class OpenAIServer:
    """Lifecycle wrapper: build, serve_forever (or background), shutdown."""

    def __init__(self, client, model_name: str, host: str = "127.0.0.1",
                 port: int = 8000, request_timeout: float = 600.0,
                 allow_runtime_adapters: bool = False, embedder=None):
        self.bridge = _EngineBridge(client)
        self.httpd = _HTTPServer(
            (host, port), make_handler(self.bridge, model_name,
                                       request_timeout,
                                       allow_runtime_adapters, embedder))
        self.model_name = model_name
        self.embedder = embedder

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    @property
    def client(self):
        """The serving client behind the handler closure (tests swap its
        ``slo_monitor`` to drive the /healthz SLO block)."""
        return self.bridge.client

    def serve_forever(self) -> None:
        self.httpd.serve_forever()

    def start_background(self) -> threading.Thread:
        t = threading.Thread(target=self.serve_forever, name="openai-http",
                             daemon=True)
        t.start()
        return t

    def shutdown(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.bridge.shutdown()
