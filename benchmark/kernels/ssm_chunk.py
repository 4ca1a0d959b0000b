"""The Mamba-2 rule in its chunked form (``ops/ssm.py`` ``ssm_chunk``: a run
of tokens a row, blocks of ``chunk_size``): its events, and the operations
and bytes one call needs.

One call serves one Mamba layer of one prefill or mixed program. Per block
of ``Q`` tokens it multiplies ``C B^T`` a group (``Q x Q x N``, the causal
half of it), applies the masked scores to the writes a head (``Q x Q x P``,
the causal half), sums a block's writes into its end state and reads the
state the block started from (``Q x P x N`` each, a head): two operations a
multiply-add. The bytes it must move: the run's ``xBC`` and ``dt`` in and
its output out (float32 all), and each row's state read and written once a
call.

The rule is XLA's (``jax.named_scope`` ``ssm.chunk`` is in the metadata the
reduced trace cuts off). On the "XLA Ops" line its events are the operations
that name one of its block-shaped float32 arrays — ``[..., Q, Q, groups]``
(the scores; with the heads trailing, under the decay mask), ``[..., Q,
groups, N]`` (``B`` and ``C`` by block), ``[..., Q, groups, heads a group,
...]`` / ``[..., groups, heads a group, P, N]`` (the writes, the block
states), or ``[..., Q, heads]`` (the summed log decays); two axes of ``Q``
alone are not enough: the sampler folds its 16,384 logits a row into ``128 x
128`` — or the state or tail of FEWER rows than the pool has slots (a
prefill row's, gathered and written back; the decode step's has all of
them: ``kernels/ssm_step.py``), less the snapshot copies' (a row with the
layers leading). Containers are left out.
"""

from __future__ import annotations

import re

from benchmark.kernels.gdn_step import NO_WORK, op_kind


def pattern(slots: int, heads: int, p: int, n: int, groups: int, chunk: int,
            conv: int, width: int) -> re.Pattern:
    k = heads // groups
    rows = "|".join(str(r) for r in range(1, slots))
    return re.compile(
        rf"f32\[(\d+,)*{chunk},{chunk},({groups}(,{k})?|{heads})\]"   # scores, the decay mask
        rf"|f32\[(\d+,)*{chunk},{groups},{n}(,1)?\]"                 # B and C by block
        rf"|f32\[(\d+,)*{groups},{k},{p},{n}\]"                      # block states
        rf"|f32\[(\d+,)*{chunk},{groups},{k}(,{p})?\]"              # writes, decays to the end
        rf"|f32\[(\d+,)*{chunk},{heads}\]"                          # the summed log decays
        rf"|f32\[({rows}),{heads},{p},{n}\]"                         # a prefill row's state
        rf"|f32\[({rows}),{width - 1},{conv}\]")                     # and its tail


def is_event(name: str, slots: int, heads: int, p: int, n: int, groups: int,
             chunk: int, conv: int, width: int) -> bool:
    return (op_kind(name) not in NO_WORK
            and bool(pattern(slots, heads, p, n, groups, chunk, conv, width).search(name)))


def ops_per_call(tokens: float, heads: int, p: int, n: int, groups: int, chunk: int) -> float:
    per_token = 2.0 * ((chunk / 2) * n * groups + (chunk / 2) * p * heads + 2 * p * n * heads)
    return tokens * per_token


def bytes_per_call(tokens: float, rows: float, heads: int, p: int, n: int, conv: int) -> float:
    per_token = (conv + heads) * 4 + heads * p * 4               # xBC, dt in; y out
    return tokens * per_token + rows * 2.0 * heads * p * n * 4
