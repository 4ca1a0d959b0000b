"""The latent (MLA) attention of a speculative ROUND (``ops/mla.py``
``latent_paged_attention`` at TWO query positions a row, inside
``jit__decode_spec``): its events, and the bytes and operations one call
needs.

One call serves one attention block of one round: the trunk's layers and
the prediction module's layer each make one a round. In the absorbed form it
must read the latent and the rotated key of every live token of the rows
ONCE — ``kv_lora_rank + qk_rope_head_dim`` values — whatever the number of
heads and of query positions; for every live token and row it multiplies
``positions x heads`` absorbed queries of ``rank + rope`` against it and
accumulates as many weighted latents of ``rank``. Queries, outputs and the
two rows a call writes first are left out (a lower bound).

The count is of the WORK, whatever implements it (``mla_decode.call_pattern``):
while the attention is XLA's, its call on the "XLA Ops" line is the
``while`` that walks the page tables a block at a time, told from every
other loop by what it carries — the running softmax's float32 accumulator
``f32[rows, 2, heads, rank]``; a Pallas walk (ROADMAP A14) is to carry the
name ``mla_spec_walk`` and is found by it. The undrafted decode programs
carry ``[rows, 1, ...]`` or are named ``mla_decode_walk``
(``kernels/mla_decode.py``), and the mixed step carries ``[blocks, 8, ...]``
or is named ``mla_chunk_walk``: neither is counted here.
"""

from __future__ import annotations

import re

from benchmark.kernels import mla_decode

POSITIONS = 2  # [last, draft] a row and round

# The live latents are read once whatever the number of query positions.
bytes_per_call = mla_decode.bytes_per_call


WALK = "mla_spec_walk"  # the name a Pallas kernel for this call takes


def pattern(rows: int, heads: int, rank: int) -> re.Pattern:
    return mla_decode.call_pattern(WALK, rows, POSITIONS, heads, rank)


def ops_per_call(live_tokens: float, heads: int, rank: int, rope: int) -> float:
    """``kernels/mla_decode.py``'s count — scores and the weighted sum of
    the latent, a head and live token — once a query position."""
    return POSITIONS * mla_decode.ops_per_call(live_tokens, heads, rank, rope)
