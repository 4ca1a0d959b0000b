"""The gated delta rule's recurrent step (``ops/gated_delta.py``
``gated_delta_step``: one token a row, every slot of the state pool): its
events, and the bytes one call needs.

One call serves one linear-attention layer of one forward pass: a decode
program's, or the decode rows of a mixed step. For every LIVE row it must
read and write the row's state — ``value heads x d_k x d_v`` float32 — and
read the convolution's tail with the row's ``[q | k | v]``, ``z`` and ``[b |
a]`` (float32, as the projections leave them) and write the tail back; the rows of free slots need
nothing. Operations are left out: two multiply-adds a state value, far
under the bytes at any row count.

The step is XLA's, not a kernel with a name of its own. On the "XLA Ops"
line its events are the fusions that read or write the state of EVERY slot
of one layer — an operand or a result of shape ``f32[slots, value heads,
d_k, d_v]``, or the whole pool ``f32[linear layers, slots, value heads, d_k,
d_v]`` written in place — and nothing else in the program has those shapes
(the chunked rule's state is a prefill row's, fewer than ``slots``: an
operation that names both is that rule's write-back). Loops, branches and
tuples that merely carry the pool do no work of their own, and are left
out.
"""

from __future__ import annotations

import re

# Operation kinds that hold no work of their own: they contain others, or
# only name a buffer.
NO_WORK = {"while", "conditional", "call", "tuple", "get-tuple-element", "parameter",
           "bitcast", "copy-start", "slice-start", None}


def op_kind(name: str) -> str | None:
    """``%x.1 = f32[2,3] fusion(...)`` -> ``fusion``; a tuple-typed
    result is skipped over to its closing bracket. None where the text was
    cut before the kind (the loops' long tuples)."""
    rest = name.partition(" = ")[2]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
        else:
            return None
    m = re.match(r"\s*(?:[a-z]\w*\[[^\]]*\])?\s*([a-z][\w\-]*)\(", rest)
    return m.group(1) if m else None


def pattern(slots: int, heads: int, dk: int, dv: int) -> re.Pattern:
    """A layer's state of every slot, or the whole pool."""
    return re.compile(rf"f32\[(\d+,)?{slots},{heads},{dk},{dv}\]")


def fewer_rows(slots: int, heads: int, dk: int, dv: int) -> re.Pattern:
    """The state of fewer rows than the pool has slots: a prefill row's."""
    rows = "|".join(str(r) for r in range(1, slots))
    return re.compile(rf"f32\[({rows}),{heads},{dk},{dv}\]")


def is_event(name: str, slots: int, heads: int, dk: int, dv: int) -> bool:
    """An operation of the step: it touches every slot's state, does work of
    its own, and is not the chunked rule's write of its rows' state into
    the pool (which names both shapes)."""
    return (op_kind(name) not in NO_WORK
            and bool(pattern(slots, heads, dk, dv).search(name))
            and not fewer_rows(slots, heads, dk, dv).search(name))


def bytes_per_call(rows: float, heads: int, dk: int, dv: int, conv_channels: int,
                   width: int, state_bytes: int = 4, act_bytes: int = 4) -> float:
    state = 2.0 * heads * dk * dv * state_bytes                   # read, written
    tail = 2.0 * (width - 1) * conv_channels * act_bytes          # read, written
    row = (conv_channels + heads * dv + 2 * heads) * act_bytes    # [q|k|v], z, [b|a]
    return rows * (state + tail + row)
