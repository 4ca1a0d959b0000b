"""The Pallas int8 matmul (``ops/qmm_pallas.py``): its events in the device
trace, and those of the copy that feeds it.

A call computes ``(x[M, K] @ q[K, N]) * s``. What the v5e traces show (my
chip runs, PR 23): the int8 operand is never the stacked ``s8[L,K,N]``
array but ``%dynamic-slice_bitcast_fusion.N``, one layer's matrix copied
out of it first, and WHERE the copy lands differs by program. In
``_decode_multi`` (M = 16) every copy lands in memory space 1 (``S(1)`` in
the operand's layout: on-chip memory, not HBM): the copy is the HBM read
(68 MB in 91 us, 91% of the HBM peak) and the kernel then reads on-chip
memory (68 MB in 75 us, which no HBM could deliver). In ``_decode_spec``
(M = 128) the three large matrices are copied HBM to HBM (208 us) and the
kernel reads HBM (180 us, 46% of the peak); the small ones stay on chip.
So the kernel alone has no one roofline to report a share of: the two
times are reported side by side (``layer_metrics/qmm_kernel_ms.py``,
``qmm_feed_copy_ms.py``).
"""

from __future__ import annotations

import re

# The kernel's own events ("XLA Ops" line): the instruction carries its
# shapes, ``%qmm_pallas.82 = bf16[M,N] custom-call(bf16[M,K] .., s8[K,N] ..``.
PATTERN = re.compile(r"^%qmm_pallas[.\d]* = \w+\[(\d+),(\d+)\] custom-call\("
                     r"\w+\[\d+,(\d+)\]")
# The copy that feeds it: one layer's int8 matrix sliced out of the stacked array.
FEED = re.compile(r"^%dynamic-slice_bitcast_fusion[.\d]* = s8\[(\d+),(\d+)\] fusion\(s8\[")


def shape_of(op: str) -> tuple[int, int, int] | None:
    """(M, K, N) of one of the kernel's events, or None."""
    m = PATTERN.search(op)
    return (int(m.group(1)), int(m.group(3)), int(m.group(2))) if m else None
