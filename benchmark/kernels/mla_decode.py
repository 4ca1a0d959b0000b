"""The decode programs' latent (MLA) attention (``ops/mla.py``
``latent_paged_attention`` at one position a row): its events, and the
bytes and operations one call needs.

One call serves one attention sublayer of one pass of a decode program. In
the absorbed form it must read the latent and the rotated key of every live
token of the pass's rows ONCE — ``kv_lora_rank + qk_rope_head_dim`` values
— whatever the number of heads, and for every live token and row it
multiplies ``heads`` absorbed queries of ``rank + rope`` against it and
accumulates ``heads`` weighted latents of ``rank``. Queries and outputs are
left out (a lower bound).

The attention is XLA's, not a kernel with a name of its own: on the "XLA
Ops" line it is the ``while`` that walks the page tables a block at a time,
told from every other loop of the program by what it carries — the running
softmax's float32 accumulator ``f32[rows, 1, heads, rank]`` (the layer scan
and the K-step scan carry nothing of that shape).
"""

from __future__ import annotations

import re


def pattern(rows: int, heads: int, rank: int) -> re.Pattern:
    return re.compile(rf"^%while[.\d]* = \(.*f32\[{rows},1,{heads},{rank}\]")


def bytes_per_call(live_tokens: float, rank: int, rope: int, kv_bytes: int = 2) -> float:
    return live_tokens * (rank + rope) * kv_bytes


def ops_per_call(live_tokens: float, heads: int, rank: int, rope: int) -> float:
    """Scores (``rank + rope`` multiply-adds a head and live token) and the
    weighted sum of the latent (``rank``), two operations each."""
    return 2.0 * heads * ((rank + rope) + rank) * live_tokens
