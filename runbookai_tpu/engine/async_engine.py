"""Async facade over :class:`EngineCore` — the host program's serving loop.

The agent's hot loop alternates LLM decode and tool I/O (SURVEY.md §7 hard
part 3): ``generate`` awaits a completion event while the engine loop task
keeps stepping the device for *other* live sequences, so eval DP batches and
concurrent investigations overlap tool latency with decode throughput.

Device work runs in a worker thread (``asyncio.to_thread``) so the event loop
stays free for tool HTTP/subprocess I/O; a lock serializes core mutation
between ``submit`` and ``step``.
"""

from __future__ import annotations

import asyncio
import threading
from typing import Optional

from runbookai_tpu.engine.engine import EngineCore
from runbookai_tpu.engine.request import (
    EngineOutput,
    EngineRequest,
    FinishReason,
    SamplingParams,
)
from runbookai_tpu.utils.trace import annotate


class AsyncEngine:
    def __init__(self, core: EngineCore):
        self.core = core
        self._lock = threading.Lock()
        self._wake: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._stopped = False
        # ``engine.loop`` on the profiler's clock: from the end of a step
        # that leaves work to the start of the next, which is this
        # class's doing (the hop back to the event loop, whatever the
        # loop runs before it resumes ``_loop``, the hop out to a worker
        # thread, the wait for the lock). On the records' clock the same
        # interval is a record's ``t_start`` less the ``t_end`` before it.
        # Opened on one worker thread and closed on the next: the
        # profiler takes the event whole at its close, on that thread's
        # line. Touched under the lock only.
        self._gap = None
        # Monotonic count of engine-loop crashes (step exceptions). The
        # fleet supervisor reads this as its STICKY crash signal: a
        # caller's start() may restart a crashed loop before the
        # supervisor's next poll, but the count never un-bumps.
        self.crash_count = 0

    async def start(self) -> None:
        # A done task means the loop that owned it was torn down (e.g. a
        # caller drives each turn with its own asyncio.run) — restart on
        # the current loop, along with the loop-bound wake event, or every
        # later request would enqueue forever with nothing stepping.
        if self._task is not None and self._task.done():
            # Retrieve the crashed task's exception so asyncio doesn't log
            # "Task exception was never retrieved" at GC (the crash itself
            # was already reported by _fail_live_requests).
            try:
                self._task.exception()
            except asyncio.CancelledError:
                pass
            self._task = None
        if self._task is None:
            self._wake = asyncio.Event()
            self._stopped = False
            self._task = asyncio.create_task(self._loop(), name="engine-loop")

    async def stop(self) -> None:
        self._stopped = True
        if self._wake:
            self._wake.set()
        task, self._task = self._task, None
        if task is not None:
            try:
                await task
            except asyncio.CancelledError:
                if not task.cancelled():
                    raise  # the cancellation targeted stop() itself, not the loop
            except Exception:  # noqa: BLE001
                pass  # step crash — already reported by _fail_live_requests
        # Drain the overlapped decode pipeline: a window dispatched on the
        # loop's final step would otherwise strand its tokens on device and
        # leave streams/done_events waiting on a drain that never comes.
        def _flush() -> None:
            with self._lock:
                self._end_gap()
                self.core.flush()

        try:
            await asyncio.to_thread(_flush)
        except Exception:  # noqa: BLE001 — a poisoned core must not block stop
            pass

    async def _loop(self) -> None:
        while not self._stopped:
            with self._lock:
                has_work = self.core.has_work
                if not has_work:
                    self._end_gap()  # idle for want of work, not of the loop
            if not has_work:
                self._wake.clear()
                await self._wake.wait()
                continue
            try:
                await asyncio.to_thread(self._locked_step)
            except Exception:  # noqa: BLE001 — step blew up (e.g. device error)
                # Fail every live request NOW: letting the loop task die
                # would leave their done_events unset and every pending
                # generate()/generate_stream() awaiting forever. The next
                # caller's start() clears the done task and restarts.
                self.crash_count += 1
                self._fail_live_requests()
                raise

    def _fail_live_requests(self) -> None:
        import logging

        logging.getLogger(__name__).exception(
            "engine step failed; aborting live requests")
        with self._lock:
            for req in list(self.core.waiting) + list(self.core.prefilling) \
                    + list(self.core.decoding):
                try:
                    self.core.abort(req.request_id)
                except Exception:  # noqa: BLE001 — core state corrupted
                    # abort()'s own cleanup failed: force-finish so a
                    # restarted loop doesn't re-step a zombie and the
                    # awaiter unblocks.
                    self.core.force_finish(req)
            # Drop (don't drain) any in-flight decode window: fetching from
            # a poisoned device would raise again on every restarted loop's
            # first step, wedging has_work true forever.
            self.core.discard_inflight()

    def _end_gap(self) -> None:
        gap, self._gap = self._gap, None
        if gap is not None:
            gap.__exit__(None, None, None)

    def _locked_step(self) -> None:
        with self._lock:
            self._end_gap()
            self.core.step()
            if self.core.has_work:
                self._gap = annotate("engine.loop")
                self._gap.__enter__()

    @property
    def loop_crashed(self) -> bool:
        """True when the engine-loop task died on an exception (a step
        blew up) and no stop() was requested — the fleet supervisor's
        replica-crash signal. Reading ``Task.done()`` from a foreign
        thread is safe (it's a plain state check); the exception itself
        stays unretrieved until start() clears the task."""
        task = self._task
        if self._stopped or task is None or not task.done():
            return False
        if task.cancelled():
            return False
        return task.exception() is not None

    def debug_steps(self, last_n: Optional[int] = None,
                    lock_timeout: float = 0.5) -> dict:
        """Flight-recorder snapshot for ``GET /debug/steps``.

        Taken under the step lock (bounded wait, same contract as the
        ``/healthz`` snapshot: a step busy compiling can hold the lock
        for tens of seconds and a debug probe must not hang that long —
        a torn-by-one-record snapshot beats a wedged prober)."""
        locked = self._lock.acquire(timeout=lock_timeout)
        try:
            flight = self.core.flight
            return {
                "capacity": flight.capacity,
                "steps_total": flight.total_steps,
                "steps": flight.snapshot(last_n),
            }
        finally:
            if locked:
                self._lock.release()

    async def run_locked(self, fn):
        """Run ``fn()`` under the step lock in a worker thread and return
        its result. The seam the fleet's KV page transfers go through:
        export/import must see a quiesced core (no step mid-flight
        mutating the pool arrays), and the lock wait happens off the
        event loop so every in-flight stream keeps draining while a slow
        step finishes."""

        def _locked():
            with self._lock:
                return fn()

        return await asyncio.to_thread(_locked)

    async def refresh_lora(self) -> None:
        """Swap in the registry's latest stacked adapters between steps.
        The lock wait happens in a worker thread so the event loop (and
        every in-flight stream) stays live while a step finishes."""

        def _locked_refresh() -> None:
            with self._lock:
                self.core.refresh_lora()

        await asyncio.to_thread(_locked_refresh)

    async def generate(
        self,
        prompt_ids: list[int],
        sampling: Optional[SamplingParams] = None,
        timeout_s: Optional[float] = None,
        priority: int = 0,
        adapter: Optional[str] = None,
        request_id: Optional[str] = None,
        arrival_time: Optional[float] = None,
    ) -> EngineOutput:
        """Submit one request and await its completion.

        With ``timeout_s``, a stalled generation is ABORTED in the engine
        (slot + KV pages freed) before ``TimeoutError`` propagates — a
        caller-side timeout alone would leave the request decoding to
        max_new_tokens for nobody. ``request_id`` (the server's
        x-request-id) rides into the engine's tracer records for
        trace-to-request correlation. ``arrival_time`` (a perf_counter
        reading) backdates the TTFT clock to when the request entered
        the SYSTEM — the fleet passes its routing-entry time so disagg
        warm prefills and page pulls stay inside the measured TTFT."""
        await self.start()  # idempotent; restarts after a torn-down loop
        req = EngineRequest(prompt_ids=prompt_ids,
                            sampling=sampling or SamplingParams(),
                            priority=priority, adapter=adapter,
                            trace_id=request_id)
        if arrival_time is not None:
            req.arrival_time = arrival_time
        req.done_event = asyncio.Event()
        loop = asyncio.get_running_loop()
        # done_event.set() happens on a worker thread; bridge it safely.
        done = loop.create_future()

        class _Event:
            def set(self_inner) -> None:  # noqa: N805
                loop.call_soon_threadsafe(
                    lambda: done.done() or done.set_result(True)
                )

        req.done_event = _Event()  # type: ignore[assignment]
        with self._lock:
            self.core.submit(req)
        self._wake.set()
        # No liveness re-check needed: there is no await between start()
        # and this point, so a loop crash can only be delivered once we
        # suspend below — and its abort sweep then sees this request in
        # the pools and resolves our future.
        if timeout_s is None:
            await done
        else:
            try:
                await asyncio.wait_for(done, timeout_s)
            except asyncio.TimeoutError:
                with self._lock:
                    aborted = self.core.abort(req.request_id)
                # Race: the request can finish in the window between
                # wait_for timing out and the abort taking the lock. abort
                # returns False for already-finished requests — a completed
                # generation must not be reported as a timeout.
                if not aborted and req.finish_reason not in (None, "aborted"):
                    return self.core.output_for(req)
                raise TimeoutError(
                    f"generation exceeded {timeout_s}s (request aborted)")
        return self.core.output_for(req)

    async def generate_stream(
        self,
        prompt_ids: list[int],
        sampling: Optional[SamplingParams] = None,
        priority: int = 0,
        adapter: Optional[str] = None,
        request_sink: Optional[list] = None,
        request_id: Optional[str] = None,
        arrival_time: Optional[float] = None,
    ):
        """Async iterator of token ids as the engine samples them.

        Token callbacks fire on the engine's worker thread and bridge to
        the caller's loop through an asyncio queue; ``None`` is the
        completion sentinel. Stop tokens ARE yielded (callers that render
        text should skip ids in their stop set, as ``output_for`` does) —
        see ``JaxTpuClient.chat_stream`` for the text-level wrapper.
        """
        await self.start()  # idempotent; restarts after a torn-down loop
        req = EngineRequest(prompt_ids=prompt_ids,
                            sampling=sampling or SamplingParams(),
                            priority=priority, adapter=adapter,
                            trace_id=request_id)
        if arrival_time is not None:
            req.arrival_time = arrival_time
        if request_sink is not None:
            # Streaming consumers that need per-token request state
            # (logprob entries accumulate on the engine worker thread;
            # CPython list appends are atomic, so index reads are safe).
            request_sink.append(req)
        loop = asyncio.get_running_loop()
        queue: asyncio.Queue = asyncio.Queue()

        def on_token(tok: int) -> None:
            loop.call_soon_threadsafe(queue.put_nowait, tok)

        class _Event:
            def set(self_inner) -> None:  # noqa: N805
                loop.call_soon_threadsafe(queue.put_nowait, None)

        req.on_token = on_token
        req.done_event = _Event()  # type: ignore[assignment]
        with self._lock:
            self.core.submit(req)
        self._wake.set()
        try:
            while True:
                tok = await queue.get()
                if tok is None:
                    break
                yield tok
        finally:
            # Early exit (consumer break / exception): free the slot + KV
            # pages instead of decoding to max_new_tokens for nobody.
            if req.finish_reason is None:
                with self._lock:
                    self.core.abort(req.request_id)
