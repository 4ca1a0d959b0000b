"""Mixture-of-Experts FFN with static-shape dispatch (Mixtral-style).

TPU-first design: everything is fixed-shape so the whole layer jits once.
Routing is Mixtral's exactly (softmax over ALL expert logits in float32,
top-k selection, selected weights renormalized) so golden parity against
``transformers.MixtralForCausalLM`` holds. Dispatch is GShard-style
capacity-slotted, but built with a single scatter instead of the classic
``[N, E, C]`` one-hot tensor:

- every (token, k) pair gets a slot index inside its expert's queue via a
  cumulative count; pairs past the capacity drop (contribute zero),
- tokens scatter into a ``[E * C (+1 overflow), D]`` buffer (slot indices
  are unique per expert by construction, so the scatter is collision-free),
- experts run as one batched einsum over the leading E axis,
- outputs gather back by the same indices and combine with the gate weights.

Expert parallelism = shard the leading E axis of the expert weights and the
dispatched ``[E, C, D]`` activations over the mesh's ``model`` axis; XLA
inserts the all-to-alls from the shardings (scaling-book recipe). Capacity
``C = clamp(ceil(capacity_factor * N * top_k / E), 1, N)``, with
``capacity_factor <= 0`` (the config default) meaning dropless ``C = N`` —
exact transformers numerics; perf-tuned serving lowers the factor.

That is ``moe_ffn``, a layer that holds every expert. A layer that holds a
SHARE of them (``held_expert_ffn``, below) dispatches the same way but is
dropless by an exact slow path, and its fast path visits only the held
experts that have a row, a turn of a device-side loop each: an expert no
token chose is not read.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from runbookai_tpu.ops.dense import qmm


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Static per-expert queue length for a dispatch of ``n_tokens``.

    ``capacity_factor <= 0`` means dropless: capacity ``n_tokens`` (the
    worst case — every token routes to the same expert), which reproduces
    transformers' ragged gather exactly."""
    if capacity_factor <= 0:
        return n_tokens
    c = math.ceil(capacity_factor * n_tokens * top_k / n_experts)
    return max(1, min(int(c), n_tokens))


def moe_ffn(
    y: jnp.ndarray,          # [B, T, D] (post-norm hidden)
    router: jnp.ndarray,     # [D, E]
    w_gate: Any,             # [E, D, F] (or int8 dict)
    w_up: Any,               # [E, D, F]
    w_down: Any,             # [E, F, D]
    top_k: int,
    capacity_factor: float,
) -> jnp.ndarray:
    """SwiGLU MoE block output (residual NOT added). Mixtral numerics."""
    b, t, d = y.shape
    e = router.shape[-1]
    n = b * t
    cap = expert_capacity(n, e, top_k, capacity_factor)
    x = y.reshape(n, d)

    logits = (x.astype(jnp.float32) @ router.astype(jnp.float32))  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)              # [N, K]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Slot of each (token, k) pair inside its expert's queue: running count
    # of prior assignments to the same expert, in (token, k) order.
    onehot = jax.nn.one_hot(gate_idx.reshape(-1), e, dtype=jnp.int32)  # [N*K, E]
    slot = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    keep = slot < cap
    dest = jnp.where(keep, gate_idx.reshape(-1) * cap + slot, e * cap)

    # Collision-free scatter dispatch (row e*cap+c holds that queue entry;
    # the final row is the shared overflow bin, read back as zeros).
    x_rep = jnp.repeat(x, top_k, axis=0)                           # [N*K, D]
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(x_rep)
    xe = buf[: e * cap].reshape(e, cap, d)                         # [E, C, D]

    # qmm batches [E, C, a] @ [E, a, b] (jnp.matmul leading-axis batching;
    # the int8 dict's [E, 1, b] scale broadcasts) — one int8 semantics.
    act = jax.nn.silu(qmm(xe, w_gate)) * qmm(xe, w_up)
    out_e = qmm(act, w_down)                                       # [E, C, D]

    flat = jnp.concatenate(
        [out_e.reshape(e * cap, d), jnp.zeros((1, d), out_e.dtype)])
    back = flat[dest].reshape(n, top_k, d)                         # [N, K, D]
    combined = jnp.sum(back * gate_vals[..., None].astype(back.dtype), axis=1)
    return combined.reshape(b, t, d)


# --------------------------------------------------------------------------- #
# A layer that holds a SHARE of the experts (expert parallelism, one chip's   #
# side of it), beside experts that compute nothing (identity experts).        #
# --------------------------------------------------------------------------- #


def route_scaled(
    u: jnp.ndarray,            # [N, D] post-norm hidden
    router: jnp.ndarray,       # [D, E_routed + E_zero] float32
    bias: jnp.ndarray,         # [E_routed + E_zero] float32, moves the CHOICE only
    top_k: int,
    scale: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scores ``s = softmax_f32(u W_r)`` over ALL outputs; the ``top_k`` of
    ``s + bias`` are chosen; a chosen expert's weight is ``scale * s``, not
    renormalised. Returns (chosen ids [N, K], their weights [N, K] f32).
    The product runs at ``highest`` precision: it is small, and a score
    decides which experts run."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    return chosen, scale * jnp.take_along_axis(s, chosen, axis=-1)


def route_renormalised(
    u: jnp.ndarray,            # [N, D] post-norm hidden
    router: jnp.ndarray,       # [D, E] float32
    top_k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scores ``p = softmax_f32(u W_g)`` over ALL experts; the ``top_k``
    largest are chosen and their weights renormalised to sum to one
    (``norm_topk_prob``). Returns (chosen ids [N, K], weights [N, K] f32).
    As :func:`route_scaled`, the product runs at ``highest`` precision."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    w, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True)


def route_sigmoid(
    u: jnp.ndarray,            # [N, D] post-norm hidden
    router: jnp.ndarray,       # [D, E] float32
    bias: jnp.ndarray,         # [E] float32, moves the CHOICE only
    top_k: int,
    scale: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scores ``s = sigmoid_f32(u W_r)``, each expert's own; the ``top_k``
    of ``s + bias`` are chosen (one group: no group limit); a chosen
    expert's weight is ``scale * s / sum of the chosen s``. Returns (chosen
    ids [N, K], weights [N, K] f32). As :func:`route_scaled`, the product
    runs at ``highest`` precision."""
    logits = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, scale * w / jnp.sum(w, axis=-1, keepdims=True)


def expert_ffn(x: jnp.ndarray, w_gate: Any, w_up: Any, w_down: Any) -> jnp.ndarray:
    """One expert's two forms: ``SwiGLU`` — ``(silu(x W_gate) * x W_up)
    W_down`` — or, where the model's experts have two matrices (``w_gate``
    None), ``relu(x W_up)^2 W_down``. Leading axes batch (``qmm``)."""
    if w_gate is None:
        with jax.named_scope("moe.relu2"):
            return qmm(jnp.square(jax.nn.relu(qmm(x, w_up))), w_down)
    return qmm(jax.nn.silu(qmm(x, w_gate)) * qmm(x, w_up), w_down)


def shared_expert(u: jnp.ndarray, w_gate: Any, w_up: Any, w_down: Any,
                  gate: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The expert every token runs (:func:`expert_ffn`'s form) — under its
    own sigmoid gate, ``sigmoid(u w_sg)``, where the model has one (``gate``
    [D, 1]). Every share of an expert-parallel group computes it for its
    own tokens; a sum over the shares counts it once."""
    y = expert_ffn(u, w_gate, w_up, w_down)
    if gate is None:
        return y
    g = jax.nn.sigmoid(u.astype(jnp.float32) @ gate.astype(jnp.float32))
    return (g * y.astype(jnp.float32)).astype(u.dtype)


def held_capacity(n_tokens: int, top_k: int, n_outputs: int,
                  factor: int = 8) -> int:
    """Slots a held expert's queue gets on the fast path: ``factor`` times
    its expected load under even routing (``n_tokens * top_k /
    n_outputs``), at least 8, never more than every token. A queue that
    would overflow sends the call down the exact slow path instead
    (:func:`held_expert_ffn`), so the number trades speed only, never a
    token. The slots of an expert no token chose cost nothing but their
    zeros: the fast path does not read that expert."""
    expected = n_tokens * top_k / n_outputs
    return max(1, min(n_tokens, max(8, math.ceil(factor * expected))))


# The share of the held experts with a row above which the fast path reads
# them all in one batched product instead of one a turn (PERF.md section 7
# has the sweep that set it: the two meet at 0.61-0.92 of the held experts).
# That product's place in the program is also what keeps a stack the device
# holds transposed read as it lies (``tests/test_hlo_bytes.py``).
BATCHED_ABOVE = 0.5


def touched_first(counts: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The experts with a count above zero, in their own order, at the front
    of an ``[E]`` list of ids (the rest of it 0), and how many they are."""
    e = counts.shape[0]
    touched = counts > 0
    rank = jnp.cumsum(touched) - 1
    at = touched[None, :] & (rank[None, :] == jnp.arange(e)[:, None])   # [place, expert]
    ids = jnp.sum(jnp.where(at, jnp.arange(e, dtype=jnp.int32)[None, :], 0), axis=1)
    return ids, jnp.sum(touched, dtype=jnp.int32)


def held_expert_ffn(
    u: jnp.ndarray,            # [N, D]
    local: jnp.ndarray,        # [N, K] chosen expert as an index into the held ones; n_held = not held here
    weights: jnp.ndarray,      # [N, K] f32 gate weights of the choices
    w_gate: Any,               # [E_held, D, F], or [L, E_held, D, F] with ``layer``; None: two-matrix experts
    w_up: Any,                 # [E_held, D, F]
    w_down: Any,               # [E_held, F, D]
    cap: int,
    layer=None,                # traced scalar: which layer of stacked weights
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``sum_{chosen j held here} w_j FFN_j(u)``, [N, D] float32, and
    whether the call took the slow path (int32 0 or 1). Dropless. An
    expert is :func:`expert_ffn`: SwiGLU, or ``relu(u W_up)^2 W_down``
    where ``w_gate`` is None — one dispatch for both.

    Only pairs that fell on a held expert are dispatched, so the cost does
    not grow with the experts that live elsewhere. Fast path: every held
    expert's queue has ``cap`` slots; tokens are gathered into ``[E_held,
    cap, D]``, each held expert THAT HAS A ROW runs over its queue — a turn
    of a device-side loop an expert, its matrices read where they lie in
    the stack; an expert without a row is not read, its slots stay zero —
    and the results are scatter-added back under their weights. The trip
    count is what the routing touched (the step record's
    ``experts.touched``): a decode pass of a few rows reads a seventh to a
    quarter of what it holds. Where more than ``BATCHED_ABOVE`` of the held
    experts have a row (a mixed step's hundreds of rows, a chip whose peers
    send it theirs) one batched product over all of them is no slower and
    runs instead, behind a condition on that same count: one algorithm,
    the work following its input. If any queue is longer than
    ``cap`` (decided on the device, ``lax.cond``), the call instead runs
    each held expert over every token under a weight that is zero where it
    was not chosen: slower, the same sum. The caller sends pads and free
    slots to no expert (``local`` = n_held): identical pad rows would all
    queue at one expert and overflow it for nothing.

    Inside a scan over layers, hand in the STACKED weights and ``layer``:
    a ``lax.cond`` materialises its operands, so a layer's slice taken
    outside would be copied (three matrices of every held expert, each
    pass); taken inside a branch it is read in place by the product."""
    n, d = u.shape
    k = local.shape[1]
    e = jax.tree.leaves(w_up)[0].shape[0 if layer is None else 1]
    flat = local.reshape(-1)                                        # [N*K]
    onehot = jax.nn.one_hot(flat, e, dtype=jnp.int32)               # not held -> zeros
    slot = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    counts = jnp.sum(onehot, axis=0)                                # pairs a held expert
    fits = jnp.max(counts) <= cap

    def ffn(x, idx=None):
        at = tuple(i for i in (layer, idx) if i is not None)

        def pick(w):
            return jax.tree.map(lambda a: a[at], w) if at else w

        return expert_ffn(x, None if w_gate is None else pick(w_gate),
                          pick(w_up), pick(w_down))

    def slotted(_):
        dest = jnp.where((flat < e) & (slot < cap), flat * cap + slot, e * cap)
        token = jnp.arange(n * k, dtype=jnp.int32) // k
        src = jnp.full((e * cap + 1,), n, jnp.int32).at[dest].set(token)[:-1]
        w_slot = jnp.zeros((e * cap + 1,), jnp.float32).at[dest].set(
            weights.reshape(-1))[:-1]
        u_pad = jnp.concatenate([u, jnp.zeros((1, d), u.dtype)])
        x = u_pad[src].reshape(e, cap, d)
        ids, n_touched = touched_first(counts)

        def each_touched():
            def turn(i, out_e):
                j = ids[i]
                return out_e.at[j].set(ffn(x[j], j))

            like = jax.eval_shape(ffn, x)
            return jax.lax.fori_loop(0, n_touched, turn,
                                     jnp.zeros(like.shape, like.dtype))

        def combined(out_e):
            return jnp.zeros((n + 1, d), jnp.float32).at[src].add(
                out_e.reshape(e * cap, d).astype(jnp.float32) * w_slot[:, None])

        # The branches hand back the combined rows WITH the pads' row
        # ([N + 1, D]): the batched product then fuses into its scatter-add
        # as it always did, and no second ``f32[N, D]`` conditional sits
        # inside the one the benchmark's readers time.
        out = jax.lax.cond(n_touched > int(BATCHED_ABOVE * e),
                           lambda: combined(ffn(x)),
                           lambda: combined(each_touched()))
        return out[:n]

    def every_token(_):
        w_dense = jnp.zeros((n, e + 1), jnp.float32).at[
            jnp.arange(n)[:, None], local].add(weights)[:, :e]     # [N, E_held]

        def one(acc, i):
            return acc + ffn(u, i).astype(jnp.float32) * w_dense[:, i, None], None

        acc, _ = jax.lax.scan(one, jnp.zeros((n, d), jnp.float32),
                              jnp.arange(e))
        return acc

    return jax.lax.cond(fits, slotted, every_token, None), (~fits).astype(jnp.int32)
