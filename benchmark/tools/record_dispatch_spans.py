"""Record the small trace of the engine's DISPATCHES kept in
``benchmark/testdata/`` (on the chip): a tiny engine (``llama3-test``, XLA
attention, mixed dispatch on, two decode steps a dispatch) behind its
``AsyncEngine`` serving six staggered requests, its ``/debug/steps``
polled from a thread as a traced benchmark run polls it, the profiler on
for the second half of them: the file holds the dispatch annotations and
fetch spans with their ``dispatch`` stat and the step programs on the
device plane, and beside it the polled records with ``dispatches`` and
``rode``. Written gzipped, like ``record_step_spans``.

    python3 -m benchmark.tools.record_dispatch_spans <out dir>
"""

from __future__ import annotations

import asyncio
import gzip
import json
import shutil
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmark import trace_reduce

PROMPTS = (b"the first prompt, two chunks", b"second", b"a third one, longer than a chunk",
           b"fourth", b"the fifth arrives late", b"six")


def main(argv: list[str]) -> int:
    from runbookai_tpu.engine.async_engine import AsyncEngine
    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    out = Path(argv[1])
    out.mkdir(parents=True, exist_ok=True)
    cfg = CONFIGS["llama3-test"]
    core = EngineCore(
        cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32),
        ByteTokenizer(), EngineConfig(
            page_size=4, num_pages=128, max_batch_slots=4, prefill_chunk=16,
            max_seq_len=128, block_pages=4, kv_dtype=jnp.float32,
            decode_steps_per_dispatch=2, mixed_dispatch=True,
            flight_recorder_steps=64))
    engine = AsyncEngine(core)
    polled: dict[int, dict] = {}

    def serve(prompts) -> None:
        async def staggered() -> None:
            sampling = SamplingParams(temperature=0.0, max_new_tokens=14,
                                      stop_token_ids=())

            async def one(i: int, text: bytes):
                await asyncio.sleep(0.004 * i)
                return await engine.generate(list(text), sampling)

            await asyncio.gather(*(one(i, t) for i, t in enumerate(prompts)))
            await engine.stop()

        asyncio.run(staggered())

    serve(PROMPTS)  # every shape compiled before the trace
    serving = threading.Event()

    def poll() -> None:
        while not serving.is_set():
            for s in engine.debug_steps(64)["steps"]:
                polled[s["step"]] = s
            time.sleep(0.01)

    poller = threading.Thread(target=poll, name="poll-steps")
    poller.start()
    serve(PROMPTS[:3])  # polled and not traced: the slice opens on a warm engine
    tmp = out / "_trace"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    serve(PROMPTS)
    jax.profiler.stop_trace()
    serving.set()
    poller.join()
    for s in engine.debug_steps(64)["steps"]:
        polled[s["step"]] = s
    (out / "dispatch_spans.xplane.pb.gz").write_bytes(
        gzip.compress(trace_reduce.newest_xplane(tmp).read_bytes(), 9))
    shutil.rmtree(tmp)
    (out / "dispatch_spans.steps.json").write_text(
        json.dumps([polled[k] for k in sorted(polled)], indent=0))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
