"""The Mamba-2 rule (a selective state-space layer with a scalar decay a
head): its state is one ``[P, N]`` float32 matrix a head and sequence, ``P``
the head's channels and ``N`` the state size.

Per head ``h`` of group ``g = h // (H / G)``, with ``S`` the state, ``dt_t >
0`` the step, ``A < 0`` the head's decay rate and ``D`` its skip::

    S   <- exp(dt_t A) S + dt_t x_t (x) B_t[g]
    y_t  = S C_t[g] + D x_t

:func:`ssm_step` is that recurrence for one token a row (decode);
:func:`ssm_step_live` runs it over the LIVE rows of a whole pool layer in
place, a slot a turn. :func:`ssm_chunk` is the same recurrence over a run of
tokens in its chunked form (SSD): inside a block of ``chunk`` tokens the
quadratic form under the decay mask ``exp(sum_{j<k<=i} dt_k A)``, between
blocks the state's recurrence (a ``lax.scan``). All take and return the state in
float32 and compute in float32 (``PRECISION``): the state is a running sum
over a whole sequence, and what is rounded into it stays.

A token that is padding is given ``dt = 0`` by the caller
(:func:`mask_pads`): no decay, no write, whatever its position in the run.

:func:`gated_group_norm` is the layer's output norm: the gate BEFORE the
norm, the norm taken over each group's channels apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# As ``ops/gated_delta.py``: a float32 product at the TPU's default
# precision rounds its operands to bfloat16.
PRECISION = jax.lax.Precision.HIGHEST


def mask_pads(dt, live):
    """``dt`` [..., T, H] with padding tokens (``live`` false, [..., T])
    made inert."""
    return jnp.where(live[..., None], dt, 0.0)


def _grouped(x, groups):
    """[..., H, P] -> [..., G, H / G, P]."""
    return x.reshape(*x.shape[:-2], groups, x.shape[-2] // groups, x.shape[-1])


def ssm_step(x, b, c, dt, a, d, state):
    """One token a row. ``x`` [R, H, P], ``b, c`` [R, G, N], ``dt`` [R, H]
    (after its softplus; 0: an inert row), ``a, d`` [H], ``state`` [R, H, P,
    N] float32. Returns (y [R, H, P] float32, state').

    The read is taken from the state as it came in: ``S' C = e^{dt A} S C +
    dt x (B . C)``, so the state crosses HBM in one fused pass (read, read,
    written) and not once more for the output."""
    r, h, p = x.shape
    g = b.shape[1]
    x, b, c, dt = (t.astype(jnp.float32) for t in (x, b, c, dt))
    s = state.reshape(r, g, h // g, p, -1)
    decay = _grouped(jnp.exp(dt * a)[..., None], g)                  # [R, G, K, 1]
    xdt = _grouped(x * dt[..., None], g)                             # [R, G, K, P]
    read = jnp.sum(s * c[:, :, None, None, :], axis=-1)              # S C
    y = decay * read + xdt * jnp.sum(b * c, axis=-1)[:, :, None, None]
    s = s * decay[..., None] + xdt[..., None] * b[:, :, None, None, :]
    return (y.reshape(r, h, p) + d[:, None] * x), s.reshape(state.shape)


def ssm_step_live(pool, layer, live, x, b, c, dt, a, d):
    """:func:`ssm_step` over layer ``layer`` of the pool ``[layers, slots,
    H, P, N]``, row ``i`` slot ``i``, in place: only the slots that hold a
    live row (``live`` [slots]) are read and written, a slot a turn of a
    loop whose length the device decides (on the chip a turn costs what its
    2 MB of state cost to cross, so the loop's time follows the live rows
    wherever they sit; blocks of 2 to 16 slots a turn cost up to twice as
    much where the live rows lie scattered: PERF.md, PR 39). A row that is
    not live leaves its state as it was. Returns (y [slots, H, P] float32 —
    zero where nothing ran —, pool')."""
    order = jnp.argsort(~live, stable=True)                          # live slots first
    size = (1, 1, *pool.shape[2:])

    def one(i, carry):
        pool, y = carry
        at = order[i]
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, at, 1, axis=0)  # noqa: E731
        s = jax.lax.dynamic_slice(pool, (layer, at, 0, 0, 0), size)[0]
        y_b, s = ssm_step(cut(x), cut(b), cut(c), cut(dt), a, d, s)
        pool = jax.lax.dynamic_update_slice(pool, s[None].astype(pool.dtype),
                                            (layer, at, 0, 0, 0))
        return pool, jax.lax.dynamic_update_slice_in_dim(y, y_b, at, axis=0)

    y = jnp.zeros(x.shape, jnp.float32)
    pool, y = jax.lax.fori_loop(0, jnp.sum(live, dtype=jnp.int32), one, (pool, y))
    return y, pool


def ssm_chunk(x, b, c, dt, a, d, state, chunk: int):
    """A run of tokens a row, in blocks. ``x`` [R, T, H, P], ``b, c`` [R, T,
    G, N], ``dt`` [R, T, H] (after its softplus; 0: an inert token), ``a, d``
    [H], ``state`` [R, H, P, N] float32; ``T`` a multiple of ``chunk`` (the
    caller pads with inert tokens). Returns (y [R, T, H, P] float32,
    state')."""
    r, t, h, p = x.shape
    g, n = b.shape[2], t // chunk
    k = h // g
    mm = lambda eq, *ops: jnp.einsum(eq, *ops, precision=PRECISION)  # noqa: E731
    x, b, c, dt = (v.astype(jnp.float32).reshape(r, n, chunk, *v.shape[2:])
                   for v in (x, b, c, dt))
    ac = jnp.cumsum(dt * a, axis=2)                                  # [R, n, Q, H], <= 0
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp(ac_i - ac_j) for j <= i, masked BEFORE the exponential (the
    # differences above the diagonal are positive and overflow).
    seg = ac[:, :, :, None, :] - ac[:, :, None, :, :]                # [R, n, i, j, H]
    decay = jnp.exp(jnp.where(lower[None, None, :, :, None], seg, -jnp.inf))
    decay = decay.reshape(r, n, chunk, chunk, g, k)
    xdt = (x * dt[..., None]).reshape(r, n, chunk, g, k, p)
    cb = mm("rnigs,rnjgs->rnijg", c, b)                              # [R, n, i, j, G]
    y = mm("rnijgk,rnjgkp->rnigkp", cb[..., None] * decay, xdt)      # within a block
    to_end = jnp.exp(ac[:, :, -1:, :] - ac).reshape(r, n, chunk, g, k)
    written = mm("rnjgkp,rnjgs->rngkps", xdt * to_end[..., None], b)  # a block's writes, at its end
    g_end = jnp.exp(ac[:, :, -1, :]).reshape(r, n, g, k)

    def one(s, xs):
        w_i, g_i = xs
        return s * g_i[..., None, None] + w_i, s

    state, before = jax.lax.scan(
        one, state.astype(jnp.float32).reshape(r, g, k, p, -1),
        (jnp.moveaxis(written, 1, 0), jnp.moveaxis(g_end, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                              # the state a block starts from
    y = y + (mm("rnigs,rngkps->rnigkp", c, before)
             * jnp.exp(ac).reshape(r, n, chunk, g, k)[..., None])
    y = y.reshape(r, t, h, p) + d[:, None] * x.reshape(r, t, h, p)
    return y, state.reshape(r, h, p, -1)


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """``RMSNorm(y * silu(z))`` with the mean taken over each of ``groups``
    groups of channels apart. ``y, z`` [..., C], ``weight`` [C]; float32."""
    v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    vg = v.reshape(*v.shape[:-1], groups, -1)
    vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True) + eps)
    return vg.reshape(v.shape) * weight.astype(jnp.float32)
