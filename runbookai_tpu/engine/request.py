"""Engine request/response types and sampling parameters."""

from __future__ import annotations

import asyncio
import contextvars
import time
import uuid
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Optional


class FleetSaturated(RuntimeError):
    """Every fleet replica is over the shed queue depth — the request was
    shed without being submitted (engine/fleet.py raises it on streaming
    placements; the HTTP layer maps it to 503 before headers, or to an
    SSE error event once they are out). Lives here, not in fleet.py, so
    the server can catch it without importing the jax-heavy fleet module."""


@dataclass
class RequestOrigin:
    """What a front door stamps on a request before the engine sees it.
    It travels by context, not by keyword: the HTTP handler sets
    :data:`request_origin`, asyncio copies the calling thread's context
    into every coroutine the handler starts on the engine's loop
    (``run_coroutine_threadsafe``), and an :class:`EngineRequest` made
    there reads it. A further stamp is a field here, and no signature
    between the handler and the engine changes."""

    t_received: float  # time.monotonic() at the handler's start


request_origin: contextvars.ContextVar[Optional[RequestOrigin]] = (
    contextvars.ContextVar("runbook_request_origin", default=None))


def _t_received() -> Optional[float]:
    origin = request_origin.get()
    return None if origin is None else origin.t_received


class RequestState(str, Enum):
    WAITING = "waiting"  # queued, no pages yet
    PREFILL = "prefill"  # prompt being processed in chunks
    DECODE = "decode"  # generating, owns a batch slot
    FINISHED = "finished"
    FAILED = "failed"


class FinishReason(str, Enum):
    STOP_TOKEN = "stop_token"
    MAX_TOKENS = "max_tokens"
    STOP_STRING = "stop_string"
    GRAMMAR_END = "grammar_end"
    ABORTED = "aborted"


@dataclass
class SamplingParams:
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled; composes with top_p
    max_new_tokens: int = 512
    stop_token_ids: tuple[int, ...] = ()
    stop_strings: tuple[str, ...] = ()
    # When set, token-level grammar masking constrains output: "json" is the
    # generic well-formed-JSON automaton (runbookai_tpu.model.guided); any
    # name registered with the mask provider selects a compiled schema
    # grammar ("triage", "evaluation", ... — model.schema_guided).
    guided: Optional[str] = None
    # Top-N token logprobs per sampled token (0 = off). Forces single-step
    # decode dispatches (the multi-step scan never surfaces logits) and
    # disables speculation/grammar fast-forward for the request; values
    # come from the RAW model distribution (pre-grammar-mask).
    logprobs: int = 0
    # OpenAI-style repetition penalties over the request's GENERATED
    # tokens (OpenAI's c[j] counts previously sampled tokens — prompt
    # content is never penalized): logits - presence*(count>0) -
    # frequency*count, applied before masking and greedy selection.
    # Token counts live in a device-resident [slots, vocab] array seeded
    # at slot assignment and updated in-dispatch — no per-step host
    # traffic. Penalized requests are excluded from speculation (the
    # verify argmax would need evolving counts per position).
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # Per-request sampling seed (OpenAI `seed`): each sampled position
    # draws from fold_in(PRNGKey(seed), position) — reproducible for a
    # given (seed, position) regardless of batch composition or engine
    # history. None keeps the engine's dispatch key — reproducible only
    # per run shape, since the overlapped decode pipeline's overshoot
    # windows consume extra key splits at stream tails
    # (docs/decode_pipeline.md). Seeded requests are pipeline-independent.
    seed: Optional[int] = None
    # OpenAI logit_bias: ((token_id, bias), ...) added to the logits
    # before penalties/masking/greedy. Densified host-side per dispatch
    # (same shipping pattern as grammar masks); -100/+100 effectively
    # ban/force tokens.
    logit_bias: tuple[tuple[int, float], ...] = ()

    @property
    def penalized(self) -> bool:
        return bool(self.presence_penalty or self.frequency_penalty)

    @property
    def forced_sync(self) -> bool:
        """True when the request pins the engine to synchronous k=1 decode
        dispatches: per-token grammar masks and logprob attachment both
        need the previous token on host before the next dispatch can be
        built. Such requests also keep the classic split prefill/decode
        dispatches — the unified mixed dispatch excludes them so its
        single-forward fast path never has to reconcile mid-step."""
        return bool(self.guided or self.logprobs)


@dataclass
class EngineRequest:
    prompt_ids: list[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    request_id: str = field(default_factory=lambda: f"req-{uuid.uuid4().hex[:10]}")
    # Scheduling class: higher admits first and is preempted last (FCFS
    # within a class). Interactive agent turns outrank background eval
    # batches this way without separate engines.
    priority: int = 0
    # LoRA adapter name (None = base model). Resolved to a stacked-adapter
    # row index at submit; requests with different adapters batch together.
    adapter: Optional[str] = None
    adapter_idx: int = 0  # engine-resolved; 0 is the reserved zero adapter
    # Monotonic clock — compared against perf_counter() timestamps in the engine.
    arrival_time: float = field(default_factory=time.perf_counter)
    # Caller-supplied correlation id (the server's x-request-id): carried
    # into the engine's tracer records so a JSONL trace line joins back to
    # the HTTP request that produced it. None for internal callers.
    trace_id: Optional[str] = None

    # Mutable engine-owned state:
    state: RequestState = RequestState.WAITING
    prefill_pos: int = 0  # tokens of the prompt already processed
    out_ids: list[int] = field(default_factory=list)
    # Generated tokens folded into prompt_ids by preemption-by-recompute.
    # Logical output = folded_out_ids + out_ids; ctx_len must not double-count.
    folded_out_ids: list[int] = field(default_factory=list)
    # Memoized full-page hash chain over prompt_ids (admission hot path).
    block_hashes: Optional[list[int]] = None
    slot: Optional[int] = None  # decode batch slot index
    # The slot of the state pool a sequence with recurrent layers runs in,
    # from admission on (prefill included); it decodes in the same one.
    state_slot: Optional[int] = None
    first_token_time: Optional[float] = None  # TTFT measurement
    finish_time: Optional[float] = None  # set by _finish; e2e/TPOT source
    finish_reason: Optional[FinishReason] = None
    guided_state: Any = None  # grammar automaton state
    # Completion signal for the async API (set by AsyncEngine).
    done_event: Optional[asyncio.Event] = None
    # Streaming hook: called with each sampled token id from the engine's
    # worker thread (bridge to an event loop with call_soon_threadsafe).
    # Preemption-by-recompute does NOT re-call this for folded tokens, so
    # a stream sees every token exactly once.
    on_token: Optional[Any] = None
    # Per emitted token, when sampling.logprobs > 0: dicts of
    # {"token_id", "logprob", "top": [(token_id, logprob), ...]}.
    out_logprobs: list = field(default_factory=list)
    # Prompt tokens served from the prefix cache at admission.
    cached_tokens: int = 0
    # Lifecycle stamps. The engine builds ONE record from them when the
    # request retires — the flight record's ``finished`` entry and the
    # tracer's engine.request event (docs/observability.md). The stamps
    # new with that record read time.monotonic(). ``t_admitted`` reads
    # perf_counter() like ``arrival_time``, ``first_token_time`` and
    # ``finish_time`` above, because queue wait, TTFT and TPOT are
    # differences among those four; on Linux both are CLOCK_MONOTONIC
    # (tests/test_step_spans.py says so), so the record is on one clock.
    # ``t_received`` is the front door's (RequestOrigin); None for a
    # caller that has none (arrival_time stands in).
    t_received: Optional[float] = field(default_factory=_t_received)
    t_enqueued: Optional[float] = None  # EngineCore.submit, monotonic()
    t_admitted: Optional[float] = None  # first admission only
    t_first_write: Optional[float] = None  # first content chunk flushed
    last_emit_time: Optional[float] = None
    max_emit_gap_s: float = 0.0  # longest interval between two emits
    preemptions: int = 0
    lifecycle: Optional[dict] = None  # the record, once retired
    # For the record's ``rode``, with the recorder on: the dispatch
    # ledger and its sums at the first token, and this request's tokens
    # after that one by the program that gave them.
    rode_mark: Optional[tuple] = None
    rode_tokens: Optional[dict] = None

    def mark_first_write(self, t: float) -> None:
        """The server flushed this request's first content chunk at ``t``
        (handler thread). A request can retire before that write; its
        record is then already in the ring, and is completed in place."""
        if self.t_first_write is None:
            self.t_first_write = t
            if self.lifecycle is not None:
                self.lifecycle["t_first_write"] = t

    @property
    def ctx_len(self) -> int:
        return self.prefill_pos + len(self.out_ids)

    @property
    def all_out_ids(self) -> list[int]:
        """Every generated token, including ones folded by preemption."""
        return self.folded_out_ids + self.out_ids

    @property
    def num_generated(self) -> int:
        return len(self.folded_out_ids) + len(self.out_ids)

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return (self.first_token_time - self.arrival_time) * 1000.0


@dataclass
class EngineOutput:
    request_id: str
    token_ids: list[int]
    text: str
    finish_reason: FinishReason
    ttft_ms: Optional[float]
    decode_tokens: int
    elapsed_s: float
    # Present when sampling.logprobs > 0 (same entries as out_logprobs).
    logprobs: Optional[list] = None
    # Prompt tokens served from the prefix cache (usage detail).
    cached_tokens: int = 0
