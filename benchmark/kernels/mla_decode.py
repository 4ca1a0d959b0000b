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

The count is of the WORK, whatever implements it, and ``pattern`` finds the
call by either of two spellings on the "XLA Ops" line. While the attention
is XLA's it has no name of its own: it is the ``while`` that walks the page
tables a block at a time, told from every other loop of the program by what
it carries — the running softmax's float32 accumulator ``f32[rows, 1,
heads, rank]`` (the layer scan and the K-step scan carry nothing of that
shape). A Pallas walk over each row's live pages (ROADMAP A14) leaves no
such loop, so it is to carry the name ``mla_decode_walk`` (the kernel's
``name``, as ``%swa_decode_walk`` is ``kernels/swa_decode.py``'s) and is
found by it: a device call whose instruction starts ``%mla_decode_walk``.
A speculative round's walk is ``mla_spec_walk`` (``kernels/mla_spec.py``),
and a mixed step's or a prefill chunk's takes a third name,
``mla_chunk_walk``, so that neither reader counts it, as neither counts the
``[blocks, 8, ..]`` carry today.
"""

from __future__ import annotations

import re


WALK = "mla_decode_walk"  # the name a Pallas kernel for this call takes


def call_pattern(walk: str, rows: int, positions: int, heads: int, rank: int) -> re.Pattern:
    r"""One call of the latent attention: the device call named ``walk``, or
    XLA's page-walk ``while`` by its ``f32[rows, positions, heads, rank]``
    carry. ``[.\d]* = `` ends the name: ``%<walk>_other`` is another kernel."""
    return re.compile(rf"^(?:%{walk}[.\d]* = "
                      rf"|%while[.\d]* = \(.*f32\[{rows},{positions},{heads},{rank}\])")


def pattern(rows: int, heads: int, rank: int) -> re.Pattern:
    return call_pattern(WALK, rows, 1, heads, rank)


def bytes_per_call(live_tokens: float, rank: int, rope: int, kv_bytes: int = 2) -> float:
    return live_tokens * (rank + rope) * kv_bytes


def ops_per_call(live_tokens: float, heads: int, rank: int, rope: int) -> float:
    """Scores (``rank + rope`` multiply-adds a head and live token) and the
    weighted sum of the latent (``rank``), two operations each."""
    return 2.0 * heads * ((rank + rope) + rank) * live_tokens
