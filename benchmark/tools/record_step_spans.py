"""Record the small trace of the ENGINE's own spans kept in
``benchmark/testdata/`` (on the chip): a tiny engine (``llama3-test``, XLA
attention) behind its ``AsyncEngine`` serving three requests under the
profiler, so that the file holds ``engine.step`` spans with their ``step``
stat, the phases inside them, ``engine.loop`` from each step to the next,
and the step programs on the device plane, and beside it the flight
records of the same steps. Written gzipped: every instruction of a step
program brings its HLO text and source lines, a megabyte as it comes.

    python3 -m benchmark.tools.record_step_spans <out dir>
"""

from __future__ import annotations

import asyncio
import gzip
import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmark import trace_reduce


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
            page_size=4, num_pages=64, max_batch_slots=4, prefill_chunk=8,
            max_seq_len=128, block_pages=4, kv_dtype=jnp.float32,
            decode_steps_per_dispatch=2, flight_recorder_steps=64))

    engine = AsyncEngine(core)

    def serve() -> None:
        async def three() -> None:
            sampling = SamplingParams(temperature=0.0, max_new_tokens=6,
                                      stop_token_ids=())
            await asyncio.gather(*(
                engine.generate(list(text), sampling)
                for text in (b"the first prompt",
                             b"a second, longer prompt here", b"third")))
            await engine.stop()

        asyncio.run(three())

    serve()  # every shape compiled before the trace
    first = core.flight.total_steps
    tmp = out / "_trace"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    serve()
    jax.profiler.stop_trace()
    (out / "step_spans.xplane.pb.gz").write_bytes(
        gzip.compress(trace_reduce.newest_xplane(tmp).read_bytes(), 9))
    shutil.rmtree(tmp)
    steps = [s for s in core.flight.snapshot() if s["step"] >= first]
    (out / "step_spans.steps.json").write_text(json.dumps(steps, indent=0))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
