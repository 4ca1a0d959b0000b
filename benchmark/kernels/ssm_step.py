"""The Mamba-2 rule's recurrent step (``ops/ssm.py`` ``ssm_step_live``: one
token a row, the LIVE rows of the state pool in place, a block of slots a
turn of a loop; with it the convolution over every slot's tail): its
events, and the bytes one call needs.

One call serves one Mamba layer of one forward pass: a decode program's, or
the decode rows of a mixed step. For every LIVE row it must read and write
the row's state — ``heads x head size x state size`` float32 — and read the
convolution's tail with the row's ``xBC``, ``z`` and ``dt`` (float32, as the
projection leaves them) and write the tail back; the rows of free slots
need nothing. Operations are left out: two multiply-adds a state value, far
under the bytes at any row count.

The step is XLA's, not a kernel with a name of its own (its
``jax.named_scope`` ``ssm.step`` is in the instruction's metadata, which the
reduced trace cuts off with everything past 200 characters). On the "XLA
Ops" line its events are the operations that name the WHOLE pool of either
kind — ``f32[Mamba layers, slots, heads, head size, state size]`` read or
written in place at a block of slots, or the tails ``f32[Mamba layers,
slots, width - 1, channels]`` — and do work of their own, less those that
also name the state of FEWER rows than the pool has slots: a prefill row's,
which the chunked rule gathers and writes back (``kernels/ssm_chunk.py``),
or a snapshot's. Loops, branches and tuples that merely carry a pool do no
work of their own, and are left out.
"""

from __future__ import annotations

import re

from benchmark.kernels.gdn_step import NO_WORK, op_kind


def pools(layers: int, slots: int, heads: int, p: int, n: int, conv: int,
          width: int) -> re.Pattern:
    """Either pool, whole, or one layer of it over every slot."""
    return re.compile(rf"f32\[({layers},)?{slots},{heads},{p},{n}\]"
                      rf"|f32\[({layers},)?{slots},{width - 1},{conv}\]")


def fewer_rows(slots: int, heads: int, p: int, n: int, conv: int, width: int) -> re.Pattern:
    """The state or tail of fewer rows than the pool has slots, with or
    without the layers leading: a prefill row's, a snapshot's."""
    rows = "|".join(str(r) for r in range(1, slots))
    return re.compile(rf"f32\[(\d+,)?({rows}),{heads},{p},{n}\]"
                      rf"|f32\[(\d+,)?({rows}),{width - 1},{conv}\]")


def is_event(name: str, layers: int, slots: int, heads: int, p: int, n: int,
             conv: int, width: int) -> bool:
    return (op_kind(name) not in NO_WORK
            and bool(pools(layers, slots, heads, p, n, conv, width).search(name))
            and not fewer_rows(slots, heads, p, n, conv, width).search(name))


def bytes_per_call(rows: float, heads: int, p: int, n: int, conv: int, width: int,
                   state_bytes: int = 4, act_bytes: int = 4) -> float:
    state = 2.0 * heads * p * n * state_bytes                  # read, written
    tail = 2.0 * (width - 1) * conv * act_bytes                # read, written
    row = (conv + heads * p + heads) * act_bytes               # xBC, z, dt
    return rows * (state + tail + row)
