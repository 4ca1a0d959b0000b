"""Pallas TPU kernel: int8 weight-only quantized matmul for the decode loop.

Decode is HBM-bandwidth-bound on the *weights*: every generated token reads
every layer matrix once, so the floor on step time is weight-bytes / HBM
bandwidth. The XLA path (``llama.qmm``) expresses the int8 matmul as
``(x @ q.astype(bf16)) * s`` and trusts the compiler to fuse the convert
into the dot's operand read; when it instead materializes a bf16 copy the
step moves 3x the bytes (read int8 + write bf16 + read bf16) — the r3
on-chip number (209.9 tok/s, ~27% of roofline) has exactly that signature.

This kernel makes the byte count structural rather than a fusion gamble,
and reads each matrix ONCE, where it lies. Its int8 operand is the STACKED
array of the layer scan, ``q [L, K, N]``, with the layer's number; it stays
in HBM (``pl.ANY``) and the kernel copies layer ``li``'s blocks HBM→VMEM
itself, two in flight, so no per-layer ``s8[K, N]`` exists in front of the
call (a scan's ``dynamic-slice`` of its ``xs`` does not fuse into a custom
call: XLA copied every matrix out of the stack first, and that copy WAS
the HBM read — PERF.md section 6, PR 30). The int8 block is widened in VMEM
on its way into the MXU, accumulates in f32 scratch, and the
per-output-channel scale is applied once in the epilogue:

    grid = (N/bn, K/bk)           # k innermost: sequential accumulation
    step t waits for block t, has started block t+1 (the other buffer)
    acc[M, bn] += x[M, bk] @ widen(q[li, bk, bn])
    out[M, bn]  = acc * s[1, bn]  # on the last k step

A block follows the matrix's layout: as wide as the accumulator allows
(``bn = N`` at decode's M) by as many K-rows as ``_BLOCK_BYTES`` holds, so
one fetch is one contiguous run of the array's (32, 128) tiles
(:func:`blocks`). A plain ``[K, N]`` matrix is the same kernel at L = 1.
Which leaves a forward hands over stacked is :func:`reads_in_place`.

Math is identical to dequantize-then-matmul because the scale is constant
along the contraction (see models/quant.py). Selected per dispatch by
``EngineConfig.qmm_impl = "pallas"``; ``llama.qmm`` falls back to the XLA
expression for shapes the kernel does not cover (prefill-sized M, ragged
dims, unquantized leaves), so callers can pass every matmul through it.

No reference counterpart: RunbookAI calls hosted LLM APIs (SURVEY.md §2.2);
this is the TPU-native serving stack underneath the same product surface.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Decode/verify dispatches have M = batch_slots * k_steps rows (<= ~256).
# Larger M means chunked prefill, which is MXU-bound, overlaps the dequant
# with compute, and amortizes any materialized copy over hundreds of
# tokens — the XLA path is the right tool there.
MAX_PALLAS_M = 256

# What one call holds in VMEM stays inside Mosaic's default scope (16 MiB
# of a v5e core's 128: a larger scope is taken from what XLA stages there
# between calls, and at 48 MiB one of the two KV layer slices of the
# cell's decode program fell back to HBM). Mosaic's own count for a call
# compiled for a v5e is two int8 blocks, eight bytes an output element of
# the step (the float32 accumulator and the bf16 output block twice), and
# under 1 MiB more (the activations' blocks, the scales; the widened block
# is never whole): 7.6 MB at M = 16 for [128, 18944] blocks, 15.3 MB at
# M = 256 for [512, 4736].
_VMEM_BYTES = 16 * 1024 * 1024
_OUT_BYTES = _VMEM_BYTES // 2  # at most, for the output side
# One int8 block [bk, bn], at most: a call's first fetch overlaps nothing,
# so a block stays a small share of a large matrix. On the chip, a 68 MB
# FFN matrix of the 7B cell in a 28-layer scan at M = 16 (my chip runs, PR
# 30; examples/microbench_qmm.py --sweep): blocks of 0.44 MiB 106-119 us a
# call, 0.6-0.9 MiB 94.5-99, 1.2 to 4.6 MiB 93.8-95.4 (88% of the HBM
# peak); [128, N] whole rows are 2.3 MiB there.
_BLOCK_BYTES = 5 * 512 * 1024

# A stacked operand that FITS on-chip memory is not left where it lies: in
# the layer scan's body XLA prefetches all L layers of it there before
# every call (wk and wv of the 7B cell, 51 MB a stack, and every
# ``f32[L, 1, N]`` scale array: seen in ``_decode_multi`` compiled for a
# described v5e, PR 30; naming HBM as the operand's memory space does not
# stop it). So only a stack that cannot fit there is read in place; the
# others, and all scales, ride the scan's ``xs`` and XLA slices one layer
# out.
_ON_CHIP_BYTES = 128 * 1024 * 1024


def _widest(dim: int, unit: int, limit: int) -> int | None:
    """The largest divisor of ``dim`` that is a multiple of ``unit`` and at
    most ``limit``."""
    return next((dim // d for d in range(1, dim // unit + 1)
                 if dim % d == 0 and (dim // d) % unit == 0
                 and dim // d <= limit), None)


def blocks(m: int, k: int, n: int, itemsize: int = 2
           ) -> tuple[int, int] | None:
    """``(bk, bn)`` of the weight block for ``x[m, k] @ q[k, n]`` with
    activations of ``itemsize`` bytes, or None where the kernel does not
    cover the shape. ``bn``: whole rows if the output side fits its share
    of VMEM (and 128 of them a block), else the widest lane-aligned
    divisor of N that does; ``bk``:
    as many K-rows as the rest holds twice and ``_BLOCK_BYTES`` allows,
    lane-aligned for the activations' block beside it (or all of a K
    narrower than that)."""
    m_pad = -(-m // 16) * 16
    per_column = (4 + 2 * itemsize) * m_pad
    bn = _widest(n, 128, min(_OUT_BYTES // per_column, _BLOCK_BYTES // 128))
    if m > MAX_PALLAS_M or bn is None or k % 32:
        return None
    room = min(_BLOCK_BYTES,
               (_VMEM_BYTES - per_column * bn - 1024 * 1024) // 2)
    bk = _widest(k, 128, room // bn)
    if bk is None and k * bn <= room:
        bk = k
    return None if bk is None else (bk, bn)


def qmm_pallas_eligible(m: int, k: int, n: int) -> bool:
    """Static (trace-time) eligibility for the kernel path."""
    return blocks(m, k, n) is not None


def reads_in_place(m: int, stack: tuple[int, int, int]) -> bool:
    """Whether a forward with ``m`` rows hands the kernel the stacked
    ``[L, K, N]`` array and the layer's number (True) or one layer's
    matrix, sliced by the scan (False): static, by shape alone."""
    n_layers, k, n = stack
    return (qmm_pallas_eligible(m, k, n)
            and n_layers * k * n >= _ON_CHIP_BYTES)


def _qmm_kernel(x_ref, li_ref, q_hbm, s_ref, o_ref, q_buf, sems, acc_ref, *,
                bk: int, bn: int, n_k: int, n_steps: int):
    i, k = pl.program_id(0), pl.program_id(1)
    t = i * n_k + k  # grid steps run in this order, one after another
    li = li_ref[0]
    slot = jax.lax.rem(t, 2)

    def block(step, into):
        """The copy of grid step ``step``'s int8 block out of layer ``li``
        of the stacked array."""
        rows = pl.multiple_of(jax.lax.rem(step, n_k) * bk, bk)
        cols = pl.multiple_of(jax.lax.div(step, n_k) * bn, bn)
        return pltpu.make_async_copy(
            q_hbm.at[li, pl.ds(rows, bk), pl.ds(cols, bn)],
            q_buf.at[into], sems.at[into])

    @pl.when(t == 0)
    def _first():
        block(t, slot).start()

    @pl.when(t + 1 < n_steps)
    def _next():
        block(t + 1, 1 - slot).start()

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    block(t, slot).wait()
    # int8 block widens in VMEM on its way into the MXU; f32 accumulate.
    # The precision is spelled out because the operands are bf16 by
    # construction (bf16 x int8 products are exact in one MXU pass): a
    # process-wide jax_default_matmul_precision of "highest" would
    # otherwise ask Mosaic for an fp32 contraction of bf16 operands, which
    # it refuses ("Bad lhs type", TPU v5 lite, jax 0.9.0).
    acc_ref[:] += jax.lax.dot_general(
        x_ref[:], q_buf[slot].astype(x_ref.dtype),
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[:] = (acc_ref[:] * s_ref[:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qmm_pallas(x2: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
               layer: jnp.ndarray | None = None, *,
               interpret: bool = False) -> jnp.ndarray:
    """``(x2 @ q[layer]) * s``, layer ``layer``'s int8 matrix read block by
    block out of the stacked array in HBM.

    ``x2 [M, K]`` activations, ``q [L, K, N]`` int8 with ``layer`` an int32
    scalar, or one matrix ``q [K, N]`` with none; ``s [1, N]`` f32, that
    matrix's per-output-channel scales. Returns ``[M, N]`` in ``x2.dtype``.
    Callers must have checked :func:`qmm_pallas_eligible`. The activations
    are the call's first operand and its result is ``[M, N]``: the
    benchmark finds the kernel in a trace by those
    (``benchmark/kernels/qmm_pallas.py``).
    """
    if layer is None:
        q, layer = q[None], 0
    m, k_dim = x2.shape
    n = q.shape[2]
    bk, bn = blocks(m, k_dim, n, x2.dtype.itemsize)
    # Sublane-align the row block (bf16 tile: 16); padding rows are zeros
    # and sliced off after the call.
    m_pad = -(-m // 16) * 16
    if m_pad != m:
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
    n_k, n_n = k_dim // bk, n // bn

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, bk=bk, bn=bn, n_k=n_k,
                          n_steps=n_k * n_n),
        grid=(n_n, n_k),
        in_specs=[
            pl.BlockSpec((m_pad, bk), lambda i, j: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((m_pad, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x2.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, bk, bn), jnp.int8),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((m_pad, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # Both sequential: a block's copy is started a grid step ahead.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
    )(x2, jnp.asarray(layer, jnp.int32).reshape(1), q,
      s.astype(jnp.float32))
    return out[:m]
