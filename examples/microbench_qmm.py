"""Microbench: what one int8 layer matrix costs a decode pass.

Two questions, both answered in effective HBM bandwidth (int8 bytes of the
matrix over the time of a call) against the chip's peak
(``benchmark/peaks.json``):

1. One matrix at decode shapes: is the int8->bf16 convert fused into the
   matmul's operand read? int8 should move about half the bytes of bf16; if
   XLA materializes a bf16 copy of the weight, int8 is *slower* (read int8 +
   write bf16 + read bf16). The Pallas kernel makes the byte count
   structural.
2. The shape a serving model runs: a 28-layer scan over the stacked
   ``[L, K, N]`` array of each of the 7B cell's matrices, at the rows of a
   decode dispatch. ``sliced`` hands the kernel the scan's per-layer slice
   (what every decode program did before PR 30: XLA copies ``s8[K, N]`` out
   of the stack, then the kernel reads the copy); ``in place`` hands it the
   stack and the layer's number. ``--sweep`` repeats the second under
   several block budgets (``ops/qmm_pallas.py`` ``_BLOCK_BYTES``): the
   readings its one stated value rests on.

Run on the chip:  python examples/microbench_qmm.py [--rows 16] [--sweep]
"""

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from runbookai_tpu.models.quant import quantize_tensor
from runbookai_tpu.ops import qmm_pallas
from runbookai_tpu.ops.dense import qmm

LAYERS = 28
# Qwen2.5-7B's layer matrices (K, N): the dense benchmark cell.
CELL_MATRICES = {"w_gate/w_up": (3584, 18944), "w_down": (18944, 3584),
                 "wq/wo": (3584, 3584), "wk/wv": (3584, 512)}


def timeit(fn, *args, iters=50):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def hbm_peak() -> float | None:
    peaks = json.loads((pathlib.Path(__file__).parents[1] / "benchmark"
                        / "peaks.json").read_text())
    kind = peaks.get(jax.devices()[0].device_kind)
    return kind["hbm_bytes_per_s"] if kind else None


def one_matrix() -> None:
    d_in, d_out = 4096, 14336
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (d_in, d_out), jnp.bfloat16)
    wq = quantize_tensor(w)
    bf16_mm = jax.jit(lambda x, w: x @ w)
    q_mm = jax.jit(qmm)
    for b in (8, 16, 32):
        x = jax.random.normal(key, (b, d_in), jnp.bfloat16)
        t_bf = timeit(bf16_mm, x, w)
        t_q = timeit(q_mm, x, wq)
        bytes_bf, bytes_q = d_in * d_out * 2, d_in * d_out
        assert qmm_pallas.qmm_pallas_eligible(b, d_in, d_out)
        t_p = timeit(qmm_pallas.qmm_pallas, x, wq["q"],
                     wq["s"].reshape(1, d_out))
        print(f"b={b:3d}  bf16 {t_bf*1e3:7.3f} ms ({bytes_bf/t_bf/1e9:6.1f} GB/s)"
              f"   int8-xla {t_q*1e3:7.3f} ms ({bytes_q/t_q/1e9:6.1f} GB/s eff)"
              f"   int8-pallas {t_p*1e3:7.3f} ms ({bytes_q/t_p/1e9:6.1f} GB/s eff)"
              f"   pallas-vs-bf16 {t_bf/t_p:4.2f}x")


def _feed_back(h, out):
    """The layer's output back into the carry, whatever N is to K, so the
    matmul stays live (a *0 trick would let XLA drop the compute)."""
    k, n = h.shape[1], out.shape[1]
    return h + 1e-6 * jnp.tile(out, (1, -(-k // n)))[:, :k]


def layer_scans(interp: bool):
    """The two ways a layer scan feeds the kernel, jitted."""
    call = lambda *a: qmm_pallas.qmm_pallas(*a, interpret=interp)  # noqa: E731

    @jax.jit
    def sliced(x, q, s):
        def step(h, w):
            return _feed_back(h, call(h, w[0], w[1])), None
        return jax.lax.scan(step, x, (q, s))[0]

    @jax.jit
    def in_place(x, q, s):
        def step(h, xs):
            return _feed_back(h, call(h, q, xs[1], xs[0])), None
        layer = jnp.arange(q.shape[0], dtype=jnp.int32)
        return jax.lax.scan(step, x, (layer, s))[0]

    return {"sliced": sliced, "in place": in_place}


def layer_scan(rows: int, interp: bool, layers: int, peak: float | None,
               matrices: dict) -> None:
    key = jax.random.PRNGKey(0)
    scans = layer_scans(interp)
    for name, (k, n) in matrices.items():
        q = jax.random.randint(key, (layers, k, n), -127, 128, dtype=jnp.int8)
        s = jnp.full((layers, 1, n), 3 ** 0.5 / (127.0 * k ** 0.5))
        x = jax.random.normal(key, (rows, k), jnp.bfloat16)
        line = (f"{name:12s} [{k:5d},{n:5d}] x{layers} rows={rows:3d} "
                f"blocks={qmm_pallas.blocks(rows, k, n)}")
        for label, fn in scans.items():
            if interp:  # the CPU says nothing of the chip's time
                jax.block_until_ready(fn(x, q, s))
                line += f"   {label}: ran"
                continue
            t = timeit(fn, x, q, s, iters=20) / layers
            line += f"   {label} {t*1e6:7.1f} us ({k*n/t/1e9:6.1f} GB/s"
            line += f", {k*n/t/peak:5.1%} of peak)" if peak else ")"
        print(line, flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=16,
                    help="M of the dispatch: 16 = the cell's _decode_multi, "
                         "128 = _decode_spec")
    ap.add_argument("--sweep", action="store_true",
                    help="repeat the layer scan under several block budgets")
    args = ap.parse_args()
    print("backend:", jax.default_backend(), jax.devices()[0].device_kind)
    interp = jax.default_backend() == "cpu"
    peak = hbm_peak()
    if interp:  # a rehearsal of the control flow, not a measurement
        return layer_scan(args.rows, interp, 2, peak,
                          {"tiny": (256, 512), "tiny down": (512, 256)})
    one_matrix()
    layer_scan(args.rows, interp, LAYERS, peak, CELL_MATRICES)
    if args.sweep:
        for mib in (0.5, 1, 2, 3, 4):
            qmm_pallas._BLOCK_BYTES = int(mib * 2 ** 20)
            jax.clear_caches()  # block sizes are read while tracing
            print(f"--- block budget {mib} MiB")
            layer_scan(args.rows, interp, LAYERS, peak, CELL_MATRICES)


if __name__ == "__main__":
    main()
