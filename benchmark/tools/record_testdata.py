"""Record the small trace kept in ``benchmark/testdata/`` (on the chip):
two tiny jitted programs named like the engine's step programs, inside
the host spans the engine emits, for a fraction of a second.

    python3 -m benchmark.tools.record_testdata <out dir>
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmark import trace_reduce


def _decode_multi(x):
    return jnp.tanh(x @ x).sum(axis=0)


def _prefill_step(x):
    return jax.nn.softmax(x @ x.T, axis=-1)


def main(argv: list[str]) -> int:
    out = Path(argv[1])
    out.mkdir(parents=True, exist_ok=True)
    x = jnp.ones((512, 512), jnp.bfloat16)
    dec, pre = jax.jit(_decode_multi), jax.jit(_prefill_step)
    dec(x).block_until_ready(), pre(x).block_until_ready()
    tmp = out / "_trace"
    jax.profiler.start_trace(str(tmp))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("decode"):
            dec(x).block_until_ready()
        time.sleep(0.002)
        with jax.profiler.TraceAnnotation("prefill"):
            pre(x).block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(trace_reduce.newest_xplane(tmp), out / "small.xplane.pb")
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
