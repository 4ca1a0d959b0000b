"""The Pallas int8 matmul (``ops/qmm_pallas.py``): its events in the device
trace.

A call computes ``(x[M, K] @ q[K, N]) * s``. What the v5e traces showed
until PR 30 (my chip runs, PR 23): the int8 operand was never the stacked
``s8[L,K,N]`` array but ``%dynamic-slice_bitcast_fusion.N``, one layer's
matrix copied out of it first, and WHERE the copy landed differed by
program. In ``_decode_multi`` (M = 16) every copy landed in memory space 1
(``S(1)`` in the operand's layout: on-chip memory, not HBM): the copy was
the HBM read (68 MB in 91 us, 91% of the HBM peak) and the kernel then
read on-chip memory (68 MB in 75 us, which no HBM could deliver). In
``_decode_spec`` (M = 128) the three large matrices were copied HBM to HBM
(208 us) and the kernel read HBM (180 us, 46% of the peak). Since PR 30
the kernel reads each layer's matrix in place in the stacked array and
the copy is gone from every program (its reader read nothing from PR 30
on and went with PR 44); the kernel's own time is what is reported
(``layer_metrics/qmm_kernel_ms.py``), with no share of a roofline yet.
"""

from __future__ import annotations

import re

# The kernel's own events ("XLA Ops" line): the instruction carries its
# shapes, ``%qmm_pallas.82 = bf16[M,N] custom-call(bf16[M,K] .., s8[K,N] ..``.
PATTERN = re.compile(r"^%qmm_pallas[.\d]* = \w+\[(\d+),(\d+)\] custom-call\("
                     r"\w+\[\d+,(\d+)\]")


def shape_of(op: str) -> tuple[int, int, int] | None:
    """(M, K, N) of one of the kernel's events, or None."""
    m = PATTERN.search(op)
    return (int(m.group(1)), int(m.group(3)), int(m.group(2))) if m else None
