"""Ring attention — sequence/context parallelism over the ICI ring.

Long-context serving and training shard the *sequence* axis across devices;
each device holds a [B, T/n, H, D] slice of Q/K/V. Attention then needs every
(query, key) pair, which ring attention supplies without ever materializing
the full sequence on one chip: K/V shards rotate around the device ring via
``jax.lax.ppermute`` while each device accumulates its queries' attention
online (flash-style running max / normalizer, numerically exact).

Design notes (TPU-first):

- The rotation is a neighbor-exchange — on a TPU slice the ``seq`` mesh axis
  maps onto an ICI ring, so each hop is a nearest-neighbor transfer that
  overlaps with the local block matmul (XLA schedules the ppermute DMA
  concurrently with compute inside the scanned body).
- Causal masking uses *global* positions derived from ``lax.axis_index``, so
  fully-masked blocks still cost one fused matmul — acceptable because the
  dominant regime (n_shards ≪ T_local) amortizes; a skip via ``lax.cond``
  would break the static schedule XLA wants.
- GQA is supported (n_q a multiple of n_kv); K/V travel in their compact
  n_kv form so ring traffic is minimal (the GQA ratio also divides ring
  bandwidth cost by group size vs. MHA).

No reference counterpart: RunbookAI scales context *down* via compaction
(SURVEY.md §5.7); this module is the scale-*out* path the reference lacks.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30

SEQ_AXIS = "seq"


def _mark_varying(x, axis_name):
    """Mark an array device-varying over ``axis_name`` for shard_map's
    varying-manual-axes check (scan carries that start replicated and
    become per-device on the first ppermute)."""
    return jax.lax.pcast(x, (axis_name,), to="varying")


def _flash_block(qf, kb, vb, mask, m, l, acc):
    """One online-softmax accumulation step.

    qf:  [B, T, n_kv, group, d] scaled float32 queries
    kb:  [B, S, n_kv, d] keys for this block; vb same for values
    mask: [B, T, S] bool — True where attention is allowed
    m, l, acc: running max / normalizer / weighted-value accumulators
    """
    scores = jnp.einsum("btkgd,bskd->btkgs", qf, kb)
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum("btkgs,bskd->btkgd", p, vb)
    return m_new, l_new, acc_new


def ring_attention_local(
    q: jnp.ndarray,  # [B, T_local, n_q, d] — this device's query shard
    k: jnp.ndarray,  # [B, T_local, n_kv, d]
    v: jnp.ndarray,  # [B, T_local, n_kv, d]
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
    seg_ids: Optional[jnp.ndarray] = None,  # [B, T_local] segment ids (0 = pad)
) -> jnp.ndarray:
    """Ring attention body — call inside shard_map with the seq axis mapped.

    Returns this device's [B, T_local, n_q, d] output shard. With
    ``seg_ids`` given, attention is additionally blocked across segment
    boundaries (packed sequences) and pad (id 0) keys are masked out.
    """
    b, t_loc, n_q, d = q.shape
    n_kv = k.shape[2]
    group = n_q // n_kv
    n_shards = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    qf = (q.astype(jnp.float32) * scale).reshape(b, t_loc, n_kv, group, d)
    q_pos = my_idx * t_loc + jnp.arange(t_loc)  # [T_local] global positions

    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def block(m, l, acc, kb, vb, sb, hop):
        # After `hop` rotations we hold the shard originally on (my - hop) % n.
        src = (my_idx - hop) % n_shards
        k_pos = src * t_loc + jnp.arange(t_loc)
        mask = jnp.ones((b, t_loc, t_loc), dtype=bool)
        if causal:
            mask = mask & (k_pos[None, None, :] <= q_pos[None, :, None])
        if sb is not None:
            mask = mask & (sb[:, None, :] == seg_ids[:, :, None]) & (sb[:, None, :] > 0)
        return _flash_block(
            qf, kb.astype(jnp.float32), vb.astype(jnp.float32), mask, m, l, acc
        )

    def ring_step(carry, hop):
        m, l, acc, kb, vb, sb = carry
        m, l, acc = block(m, l, acc, kb, vb, sb, hop)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        if sb is not None:
            sb = jax.lax.ppermute(sb, axis_name, perm)
        return (m, l, acc, kb, vb, sb), None

    m0 = jnp.full((b, t_loc, n_kv, group), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, t_loc, n_kv, group), dtype=jnp.float32)
    acc0 = jnp.zeros((b, t_loc, n_kv, group, d), dtype=jnp.float32)
    # The carry becomes device-varying after the first flash update, so the
    # init must be marked varying for shard_map's VMA tracking.
    m0, l0, acc0 = (_mark_varying(x, axis_name) for x in (m0, l0, acc0))
    # n_shards-1 rotated hops; the last shard is consumed without a rotation.
    (m, l, acc, kb, vb, sb), _ = jax.lax.scan(
        ring_step, (m0, l0, acc0, k, v, seg_ids), jnp.arange(n_shards - 1)
    )
    m, l, acc = block(m, l, acc, kb, vb, sb, n_shards - 1)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, t_loc, n_q, d).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, T, n_q, d] — global arrays (sharded by caller or not)
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = SEQ_AXIS,
    causal: bool = True,
    seg_ids: Optional[jnp.ndarray] = None,  # [B, T]
) -> jnp.ndarray:
    """Shard q/k/v over ``mesh[axis_name]`` along T and run ring attention.

    Convenience entry for callers holding unsharded arrays; inside pjit
    programs prefer calling :func:`ring_attention_local` from your own
    shard_map with the rest of the layer.
    """
    spec = P(None, axis_name, None, None)
    seg_spec = P(None, axis_name)
    # Only the seq axis goes manual; data/model stay automatic so DP/TP
    # placements on the same mesh compose.
    smap = partial(jax.shard_map, mesh=mesh, out_specs=spec,
                   axis_names={axis_name})
    if seg_ids is None:
        fn = smap(
            partial(ring_attention_local, axis_name=axis_name, causal=causal),
            in_specs=(spec, spec, spec))
        return fn(q, k, v)

    def body(q, k, v, seg):
        return ring_attention_local(q, k, v, axis_name=axis_name, causal=causal,
                                    seg_ids=seg)

    fn = smap(body, in_specs=(spec, spec, spec, seg_spec))
    return fn(q, k, v, seg_ids)


def full_attention_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    seg_ids: Optional[jnp.ndarray] = None,  # [B, T]
) -> jnp.ndarray:
    """Unsharded GQA attention — the numerics oracle for ring attention tests."""
    b, t, n_q, d = q.shape
    n_kv = k.shape[2]
    group = n_q // n_kv
    qf = (q.astype(jnp.float32) / jnp.sqrt(jnp.float32(d))).reshape(b, t, n_kv, group, d)
    scores = jnp.einsum("btkgd,bskd->btkgs", qf, k.astype(jnp.float32))
    mask = jnp.ones((b, t, t), dtype=bool)
    if causal:
        mask = mask & jnp.tril(jnp.ones((t, t), dtype=bool))[None]
    if seg_ids is not None:
        mask = mask & (seg_ids[:, None, :] == seg_ids[:, :, None]) & (seg_ids[:, None, :] > 0)
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    attn = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("btkgs,bskd->btkgd", attn, v.astype(jnp.float32))
    return out.reshape(b, t, n_q, d).astype(q.dtype)
