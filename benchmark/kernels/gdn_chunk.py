"""The gated delta rule in its chunked form (``ops/gated_delta.py``
``chunk_gated_delta``: a run of tokens a row, blocks of 64): its events, and
the operations and bytes one call needs.

One call serves one linear-attention layer of one prefill or mixed program.
Per block of ``C`` tokens and value head it multiplies ``K K^T``, ``Q K^T``
(``C x C x d_k`` each), solves one unit-triangular ``C x C`` system against
the identity, applies the solved matrix to the writes and to the decayed
keys (``C x C x d_v`` and ``C x C x d_k``), reads the carried state twice and
writes it once (``C x d_k x d_v`` each) and applies the in-block scores to the
new writes (``C x C x d_v``): two operations a multiply-add. The bytes it
must move: the run's ``[q | k | v]`` and ``[b | a]`` in and its output out
(float32 all), and each row's state read and written once a call.

The rule is XLA's. On the "XLA Ops" line its events are the operations
whose result or operand is one of its block-shaped float32 arrays — ``[...,
value heads, C, C]``, ``[..., value heads, C, d_k]`` or ``[..., value
heads, C, d_v]`` — or a prefill row's state ``f32[rows, value heads, d_k,
d_v]`` with fewer rows than the pool has slots (the decode step's has all of
them: ``kernels/gdn_step.py``). Containers are left out.
"""

from __future__ import annotations

import re

from benchmark.kernels.gdn_step import NO_WORK, fewer_rows, op_kind

BLOCK = 64


def pattern(slots: int, heads: int, dk: int, dv: int) -> re.Pattern:
    inner = "|".join(sorted({str(BLOCK), str(dk), str(dv)}))
    return re.compile(rf"f32\[(\d+,){{0,3}}{heads},(1,)?{BLOCK},({inner})\]"
                      rf"|{fewer_rows(slots, heads, dk, dv).pattern}")


def is_event(name: str, slots: int, heads: int, dk: int, dv: int) -> bool:
    return (op_kind(name) not in NO_WORK
            and bool(pattern(slots, heads, dk, dv).search(name)))


def ops_per_call(tokens: float, heads: int, dk: int, dv: int) -> float:
    c = BLOCK
    per_token = 2.0 * (c * (3 * dk + 2 * dv) + c * c / 2 + 3 * dk * dv)
    return tokens * heads * per_token


def bytes_per_call(tokens: float, rows: float, heads: int, dk: int, dv: int,
                   conv_channels: int) -> float:
    per_token = (conv_channels + 2 * heads) * 4 + heads * dv * 4
    return tokens * per_token + rows * 2.0 * heads * dk * dv * 4
