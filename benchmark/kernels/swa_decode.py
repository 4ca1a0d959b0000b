"""The window layers' decode walk (``ops/paged_attention_pallas.py``
``paged_decode_attention`` with a ``window``): its events, and the bytes one
call must move.

One call serves one sliding-window layer of one pass of a decode program:
for each row it must read the keys and values of the positions the row's
query sees — ``min(context, window)`` of them, 2 x KV heads x head size x
bytes a value each — whatever the groups of pages it is launched over (the
walk fetches whole pages from the group that holds the window's edge, up to
a group more than this). Queries and outputs are left out: a lower bound.

The call has a name of its own in the trace (the kernels' ``name``), so its
events are found by it, not by operand shapes: the full layers' call in the
same program is ``%attn.global`` (its ``jax.named_scope``).
"""

from __future__ import annotations

import re

EVENT = re.compile(r"^%swa_decode_walk[.\d]* = ")


def bytes_per_call(rows_seen: float, n_kv: int, head_dim: int, kv_bytes: int = 2) -> float:
    """``rows_seen``: over the call's rows, the positions inside the window."""
    return 2.0 * rows_seen * n_kv * head_dim * kv_bytes
