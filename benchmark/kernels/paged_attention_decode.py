"""The Pallas paged attention of the decode programs
(``ops/paged_attention_pallas.py``): its events, and the bytes one call
must move.

One call serves one layer of one pass of a decode program: it must read
the keys and values of every live token of the pass's rows once — 2 ·
tokens · n_kv · head_dim · bytes per value — whatever the grid it is
launched over, and whether the pass asks one position a row
(``paged_decode_attention``) or, in a speculative verify, up to
``decode_steps`` of them (the chunk kernel). Queries and outputs are left
out (a lower bound).
"""

from __future__ import annotations

import re


def pattern(slots: int) -> re.Pattern:
    """The kernels have no name of their own in the trace (``%closed_call.N``,
    until the tracing issue gives them a scope): they are the custom calls
    whose first operand is the page table of the ``slots`` batch rows —
    query [rows, heads, head_dim] or [rows, positions, heads, head_dim].
    (The mixed step's table has a row per 8-token piece of its chunk.)"""
    return re.compile(rf"^%closed_call[.\d]* = \w+\[{slots},(\d+,)?\d+,\d+\] "
                      rf"custom-call\(s32\[{slots},\d+\]")


def bytes_per_call(live_tokens: float, n_kv: int, head_dim: int,
                   kv_bytes: int = 2) -> float:
    return 2.0 * live_tokens * n_kv * head_dim * kv_bytes
