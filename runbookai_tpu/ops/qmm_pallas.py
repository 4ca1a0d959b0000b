"""Pallas TPU kernel: int8 weight-only quantized matmul for the decode loop.

Decode is HBM-bandwidth-bound on the *weights*: every generated token reads
every layer matrix once, so the floor on step time is weight-bytes / HBM
bandwidth. The XLA path (``llama.qmm``) expresses the int8 matmul as
``(x @ q.astype(bf16)) * s`` and trusts the compiler to fuse the convert
into the dot's operand read; when it instead materializes a bf16 copy the
step moves 3x the bytes (read int8 + write bf16 + read bf16) — the r3
on-chip number (209.9 tok/s, ~27% of roofline) has exactly that signature.

This kernel makes the byte count structural rather than a fusion gamble:
int8 weight tiles stream HBM→VMEM (half the bf16 bytes), are widened
in-register on the way into the MXU, accumulate in f32 scratch, and the
per-output-channel scale is applied once in the epilogue:

    grid = (N/bn, K/bk)           # k innermost: sequential accumulation
    acc[M, bn] += x[M, bk] @ widen(q[bk, bn])
    out[M, bn]  = acc * s[1, bn]  # on the last k step

Math is identical to dequantize-then-matmul because the scale is constant
along the contraction (see models/quant.py). Selected per dispatch by
``EngineConfig.qmm_impl = "pallas"``; the wrapper falls back to the XLA
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

_BK_CANDIDATES = (1024, 512, 256, 128, 64, 32)  # int8 sublane multiple: 32
_BN_CANDIDATES = (512, 256, 128)  # lane multiple: 128


def _pick(cands: tuple[int, ...], dim: int) -> int | None:
    for c in cands:
        if dim % c == 0:
            return c
    return None


def qmm_pallas_eligible(m: int, k: int, n: int) -> bool:
    """Static (trace-time) eligibility for the kernel path."""
    return (m <= MAX_PALLAS_M
            and _pick(_BK_CANDIDATES, k) is not None
            and _pick(_BN_CANDIDATES, n) is not None)


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # int8 tile widens in-register on its way into the MXU; f32 accumulate.
    # The precision is spelled out because the operands are bf16 by
    # construction (bf16 x int8 products are exact in one MXU pass): a
    # process-wide jax_default_matmul_precision of "highest" would
    # otherwise ask Mosaic for an fp32 contraction of bf16 operands, which
    # it refuses ("Bad lhs type", TPU v5 lite, jax 0.9.0).
    acc_ref[:] += jax.lax.dot_general(
        x_ref[:], q_ref[:].astype(x_ref.dtype),
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[:] = (acc_ref[:] * s_ref[:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qmm_pallas(x2: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
               *, interpret: bool = False) -> jnp.ndarray:
    """``(x2 @ q) * s`` with int8 ``q`` streamed tile-by-tile from HBM.

    ``x2 [M, K]`` activations, ``q [K, N]`` int8, ``s [1, N]`` f32 per-output
    -channel scales. Returns ``[M, N]`` in ``x2.dtype``. Callers must have
    checked :func:`qmm_pallas_eligible`.
    """
    m, k_dim = x2.shape
    n = q.shape[1]
    bk = _pick(_BK_CANDIDATES, k_dim)
    bn = _pick(_BN_CANDIDATES, n)
    assert bk is not None and bn is not None, (m, k_dim, n)
    # Sublane-align the row block (bf16 tile: 16); padding rows are zeros
    # and sliced off after the call.
    m_pad = max(16, -(-m // 16) * 16)
    if m_pad != m:
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
    n_k = k_dim // bk

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k),
        grid=(n // bn, n_k),
        in_specs=[
            pl.BlockSpec((m_pad, bk), lambda i, j: (0, j)),
            pl.BlockSpec((bk, bn), lambda i, j: (j, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((m_pad, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x2.dtype),
        scratch_shapes=[pltpu.VMEM((m_pad, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x2, q, s.astype(jnp.float32))
    return out[:m]
