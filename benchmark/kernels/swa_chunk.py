"""The window layers' chunk walk (``ops/paged_attention_pallas.py``
``paged_chunk_attention`` with a ``window``, in the prefill programs and,
through ``paged_ragged_attention``, the mixed step): its events, and the
operations and bytes one call needs.

One call serves one sliding-window layer of one prefill or mixed program.
Operations: a query at position ``p`` sees ``min(p + 1, window)`` keys, and
each query-key pair costs two multiply-adds of ``head size`` a query head
(the scores, then the weighted values): ``4 x heads x head size`` a pair.
Bytes: every key and value row some query of the call sees, once — the rows
from the first query's edge to the last query of a chunk, ``min(context,
window)`` of a decode row of a mixed step — at 2 x KV heads x head size x
bytes a value. Both are lower bounds: a block of queries fetches whole pages
from the group of its first query's edge, every block of a chunk fetches
its own copy, and masked pairs are computed and thrown away.

The call has a name of its own in the trace (the kernels' ``name``).
"""

from __future__ import annotations

import re

EVENT = re.compile(r"^%swa_chunk_walk[.\d]* = ")


def ops_per_call(pairs: float, n_heads: int, head_dim: int) -> float:
    """``pairs``: query-key pairs inside the window, over the call's queries."""
    return 4.0 * pairs * n_heads * head_dim


def bytes_per_call(rows_seen: float, n_kv: int, head_dim: int, kv_bytes: int = 2) -> float:
    return 2.0 * rows_seen * n_kv * head_dim * kv_bytes
