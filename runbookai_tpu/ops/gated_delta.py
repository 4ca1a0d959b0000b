"""The gated delta rule (Gated DeltaNet): a linear-attention layer whose
state is one ``[d_k, d_v]`` float32 matrix a value head and sequence.

Per value head, with ``S`` the state, ``g_t <= 0`` the log decay and
``beta_t`` in (0, 1) the write strength::

    S <- exp(g_t) S
    d  = beta_t (v_t - S^T k_t)
    S <- S + k_t d^T
    o_t = S^T q_t

:func:`gated_delta_step` is that recurrence for one token a row (decode).
:func:`chunk_gated_delta` is the same recurrence over a run of tokens in its
chunked form: blocks of ``block`` tokens, inside a block everything is a few
small matrix products and one unit-triangular solve, and only the state is
carried from block to block (a ``lax.scan``). Both take and return the state
in float32 and compute in float32 (``PRECISION``): the state is a running
sum over a whole sequence, and what is rounded into it stays.

A token that is padding is given ``g = 0``, ``beta = 0`` and ``k = 0`` by
the caller (:func:`mask_pads`): it then leaves the state as it was, whatever
its position in the run.

:func:`causal_conv_tail` is the layer's short causal depthwise convolution
over a run of tokens, carried from call to call by its last ``width - 1``
inputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

# Every product of the rule. On the TPU a float32 product at the default
# precision rounds its operands to bfloat16; the state would then carry that
# rounding from token to token.
PRECISION = jax.lax.Precision.HIGHEST
BLOCK = 64


def l2_normalise(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


def mask_pads(k, g, beta, live):
    """``k, g, beta`` with padding tokens (``live`` false, [..., T]) made
    inert: no decay, no write."""
    return (jnp.where(live[..., None, None], k, 0.0),
            jnp.where(live[..., None], g, 0.0),
            jnp.where(live[..., None], beta, 0.0))


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row. ``q, k`` [B, H, dk] (already normalised and scaled),
    ``v`` [B, H, dv], ``g, beta`` [B, H], ``state`` [B, H, dk, dv] float32.
    Returns (o [B, H, dv] float32, state').

    Both reads are taken from the state as it came in, in one pass over it:
    ``S'^T k = e^g S^T k`` and, with ``d`` the write, ``o = S_new^T q = e^g
    S^T q + (k . q) d``; the update is then one read and one write. Read
    after each of the rule's three lines the state crossed HBM four times a
    layer (seen in the cell's trace)."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    decay = jnp.exp(g.astype(jnp.float32))[..., None]                 # [B, H, 1]
    reads = jnp.einsum("bhkv,bhkn->bhnv", state, jnp.stack([k, q], axis=-1),
                       precision=PRECISION)                           # [B, H, 2, dv]
    d = (v - decay * reads[:, :, 0]) * beta.astype(jnp.float32)[..., None]
    o = decay * reads[:, :, 1] + jnp.sum(k * q, axis=-1, keepdims=True) * d
    state = state * decay[..., None] + k[..., :, None] * d[..., None, :]
    return o, state


def chunk_gated_delta(q, k, v, g, beta, state, block: int = BLOCK):
    """A run of tokens a row, in blocks. ``q, k`` [B, T, H, dk] (normalised
    and scaled), ``v`` [B, T, H, dv], ``g, beta`` [B, T, H], ``state`` [B, H,
    dk, dv] float32; ``T`` a multiple of ``block`` (the caller pads with
    inert tokens). Returns (o [B, T, H, dv] float32, state')."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    n = t // block

    def blocks(x):  # [B, T, H, ...] -> [n, B, H, block, ...]
        x = x.astype(jnp.float32).reshape(b, n, block, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (blocks(x) for x in (q, k, v, g, beta))
    mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=PRECISION)  # noqa: E731
    gc = jnp.cumsum(g, axis=-1)                                  # [n,B,H,C]
    lower = jnp.tril(jnp.ones((block, block), bool))
    strict = jnp.tril(jnp.ones((block, block), bool), -1)
    # exp(gc_i - gc_j) for j <= i, 0 above the diagonal (masked BEFORE the
    # exponential: the differences above it are positive and overflow).
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    a = jnp.where(strict, mm("nbhik,nbhjk->nbhij", k_beta, k) * decay, 0.0)
    # (I + A)^-1, A strictly lower: what the token-by-token substitution of
    # each write's "- S^T k" into the later ones of its block solves.
    eye = jnp.broadcast_to(jnp.eye(block, dtype=jnp.float32), a.shape)
    t_inv = solve_triangular(eye + a, eye, lower=True, unit_diagonal=True)
    u = mm("nbhij,nbhjv->nbhiv", t_inv, v_beta)                  # writes, state aside
    w = mm("nbhij,nbhjk->nbhik", t_inv, k_beta * jnp.exp(gc)[..., None])
    qk = jnp.where(lower, mm("nbhik,nbhjk->nbhij", q, k) * decay, 0.0)
    q_in = q * jnp.exp(gc)[..., None]                            # reads of the carried state
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]            # writes, decayed to the block's end
    g_end = jnp.exp(gc[..., -1])                                 # [n,B,H]

    def one(s, xs):
        u_i, w_i, qk_i, q_i, k_i, g_i = xs
        v_new = u_i - mm("bhik,bhkv->bhiv", w_i, s)
        o = mm("bhik,bhkv->bhiv", q_i, s) + mm("bhij,bhjv->bhiv", qk_i, v_new)
        s = s * g_i[..., None, None] + mm("bhik,bhiv->bhkv", k_i, v_new)
        return s, o

    state, o = jax.lax.scan(one, state.astype(jnp.float32),
                            (u, w, qk, q_in, k_out, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)                # [B,n,C,H,dv]
    return o.reshape(b, t, h, dv), state


def causal_conv_tail(x, tail, weight, n_live, bias=None):
    """Causal depthwise convolution of width ``W`` over a run, then SiLU.
    ``x`` [B, T, C] the run's inputs, ``tail`` [B, W - 1, C] the inputs
    before it, ``weight`` [W, C] (``weight[W - 1]`` multiplies the current
    input), ``n_live`` [B] how many of the run's tokens are real (they come
    first), ``bias`` [C] added before the SiLU where the layer has one.
    Returns (y [B, T, C] in ``x``'s dtype, tail' [B, W - 1, C]: the
    last ``W - 1`` inputs up to the last real token)."""
    width = weight.shape[0]
    t = x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)    # [B, T + W - 1, C]
    y = sum(full[:, i:i + t].astype(jnp.float32) * weight[i].astype(jnp.float32)
            for i in range(width))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    idx = n_live[:, None] + jnp.arange(width - 1)[None, :]       # [B, W - 1]
    new_tail = jnp.take_along_axis(full, idx[..., None], axis=1)
    return jax.nn.silu(y).astype(x.dtype), new_tail.astype(tail.dtype)
