"""KV page-split serving: TP past the GQA kv-head count (SURVEY §7 hard 4).

Problem: Megatron TP shards the KV pool on the kv-head axis, so tp is capped
at ``n_kv_heads`` — Llama-3-70B has 8 KV heads, and the v5e-16 target (tp16)
would replicate the entire page pool on every chip (r3 VERDICT weak #6).

The TPU-native fix factors the model parallelism into two mesh axes:

- ``model`` (= ``kv_shards``): shards KV heads, exactly as before.
- ``seq``  (= ``pg_shards``): shards the page pool's TOKEN axis — each
  device owns a contiguous block of physical pages and attends only over
  context tokens stored there.

Query heads shard over BOTH axes (model-major, so every query stays next to
its GQA kv head); each device computes flash partials ``(m, l, acc)`` over
its own pages, and the partials merge across the ``seq`` axis with three
tiny collectives (pmax + 2 psum — payload is B·T·heads·(hd+2) floats, riding
ICI). ``wq``/``wo``/FFN shard over the combined ``(model, seq)`` axes (full
tp-way weight split); ``wk``/``wv`` shard over ``model`` only — their output
is needed by every page shard of the same kv group.

Alignment requirement: ``group % pg_shards == 0`` (so a device's query heads
all map to its kv head). Llama-3-70B: group 8, pg_shards 2 — fine.

This is the serving-side analogue of ring attention's KV sharding
(``parallel/ring_attention.py`` is the train-side one): same math (merge of
flash partials), different topology (static page ownership + psum instead of
a rotating ring — pages are randomly interleaved across shards by the
allocator, so load balance is statistical rather than positional).

No reference counterpart: RunbookAI calls hosted LLM APIs (SURVEY §2.2).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from runbookai_tpu.ops.attention import pool_rows
from runbookai_tpu.parallel.mesh import MODEL_AXIS, SEQ_AXIS

NEG_INF = -1e30


@dataclass(frozen=True)
class KVSplitPlan:
    """How a requested tp factor maps onto (kv_shards, pg_shards)."""

    tp: int
    kv_shards: int  # shards of the KV-head axis  -> mesh 'model'
    pg_shards: int  # shards of the page/token axis -> mesh 'seq'

    @property
    def split(self) -> bool:
        return self.pg_shards > 1


def plan_kv_split(cfg, tp: int) -> KVSplitPlan:
    """Decide the KV layout for ``tp``-way model parallelism.

    tp <= n_kv_heads (divisible): pure head sharding, pg_shards = 1 — the
    existing layout. Otherwise shard heads as far as they go and put the
    remaining factor on the page axis, validating the GQA alignment. This
    replaces the r3 replication *warning* with a planned layout: per-chip
    KV bytes always shrink by the full tp factor.
    """
    if tp <= 1:
        return KVSplitPlan(tp=tp, kv_shards=max(tp, 1), pg_shards=1)
    kv_shards = math.gcd(cfg.n_kv_heads, tp)
    pg_shards = tp // kv_shards
    group = cfg.n_heads // cfg.n_kv_heads
    if pg_shards > 1:
        if cfg.n_heads % tp != 0:
            raise ValueError(
                f"n_heads={cfg.n_heads} not divisible by tp={tp}")
        if group % pg_shards != 0:
            raise ValueError(
                f"KV split needs group ({group}) % pg_shards "
                f"({pg_shards}) == 0 so each device's query heads share "
                f"its kv head; use tp <= {cfg.n_kv_heads * group}")
    return KVSplitPlan(tp=tp, kv_shards=kv_shards, pg_shards=pg_shards)


# ------------------------------------------------------------------ specs

def q_heads_spec() -> P:
    """Query-head axis: model-major over both axes (head h sits on model
    shard h // (n_heads/kv_shards) — next to its GQA kv head)."""
    return P(None, None, (MODEL_AXIS, SEQ_AXIS), None)


def kv_pool_split_sharding(mesh: Mesh) -> NamedSharding:
    """[L, tokens, n_kv, hd]: tokens page-sharded over seq, heads over
    model."""
    return NamedSharding(mesh, P(None, SEQ_AXIS, MODEL_AXIS, None))


# ------------------------------------------------------------- attention

def _partial_flash(
    q,  # [B, T, nql, d] — this device's query heads
    k_loc,  # [tokens_local, nkvl, d] — this device's page slice
    v_loc,
    page_tables,  # [B, max_pages] GLOBAL physical page ids
    ctx_lens,  # [B]
    q_positions,  # [B, T]
    page_size: int,
    block_pages: int,
    pages_local: int,
    my_pg,  # scalar int32 — this device's page-shard index
):
    """Flash partials over locally-owned pages. Mirrors
    ``ops.attention.paged_attention`` exactly, plus a page-ownership mask
    (physical page p lives on shard p // pages_local) and local gather
    indices; returns un-normalized ``(m, l, acc)`` for the seq-axis merge.
    """
    b, t, nql, d = q.shape
    nkvl = k_loc.shape[1]
    group = nql // nkvl
    max_pages = page_tables.shape[1]
    n_blocks = max(1, (max_pages + block_pages - 1) // block_pages)
    block_tokens = block_pages * page_size

    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    qf = q.astype(jnp.float32) * scale
    qf = qf.reshape(b, t, nkvl, group, d)

    def block_step(carry, blk):
        m, l, acc = carry
        page_idx = blk * block_pages + jnp.arange(block_pages)
        phys_blk = jnp.take_along_axis(
            page_tables,
            jnp.broadcast_to(page_idx[None, :], (b, block_pages)) % max_pages,
            axis=1)  # [B, block_pages] global page ids
        owned_pg = (phys_blk // pages_local) == my_pg  # [B, block_pages]
        local_pg = jnp.clip(phys_blk - my_pg * pages_local,
                            0, pages_local - 1)
        token_off = jnp.arange(block_tokens)
        flat_idx = (local_pg[:, token_off // page_size] * page_size
                    + token_off % page_size)  # [B, block_tokens]
        kb = k_loc[flat_idx].astype(jnp.float32)  # [B, bt, nkvl, d]
        vb = v_loc[flat_idx].astype(jnp.float32)

        cache_pos = blk * block_tokens + token_off
        valid = (cache_pos[None, :] < ctx_lens[:, None])[:, None, :]
        causal = cache_pos[None, None, :] <= q_positions[:, :, None]
        owned = owned_pg[:, token_off // page_size][:, None, :]  # [B,1,bt]
        mask = (valid & causal & owned)[:, :, None, None, :]

        scores = jnp.einsum("btkgd,bskd->btkgs", qf, kb)
        scores = jnp.where(mask, scores, NEG_INF)

        m_blk = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        corr = jnp.exp(m - m_new)
        # Zero fully-masked probabilities explicitly: rows where m stays
        # NEG_INF would otherwise contribute exp(0)=1 per masked token
        # (mask [B,T,1,1,block] broadcasts over kv-head/group).
        p = jnp.where(mask, jnp.exp(scores - m_new[..., None]), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "btkgs,bskd->btkgd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, t, nkvl, group), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, t, nkvl, group), dtype=jnp.float32)
    acc0 = jnp.zeros((b, t, nkvl, group, d), dtype=jnp.float32)
    # scan carries inside shard_map must be marked device-varying up front
    # (the body output varies over the mesh axes; jax requires the init to
    # match). _mark_varying is lax.pcast(..., to="varying").
    from runbookai_tpu.parallel.ring_attention import _mark_varying

    m0, l0, acc0 = (_mark_varying(_mark_varying(x, SEQ_AXIS), MODEL_AXIS)
                    for x in (m0, l0, acc0))
    (m, l, acc), _ = jax.lax.scan(block_step, (m0, l0, acc0),
                                  jnp.arange(n_blocks))
    return m, l, acc


def paged_attention_kv_split(
    mesh: Mesh,
    q: jnp.ndarray,  # [B, T, n_q, hd] (sharded (model, seq) on heads)
    k_flat: jnp.ndarray,  # [tokens, n_kv, hd] (seq on tokens, model on heads)
    v_flat: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, max_pages] (replicated)
    ctx_lens: jnp.ndarray,  # [B]
    q_positions: jnp.ndarray,  # [B, T]
    page_size: int,
    block_pages: int = 32,
) -> jnp.ndarray:
    """Paged attention over the (kv-head × page)-sharded pool.

    Each device flashes over its page slice; partials merge across the
    ``seq`` axis with pmax/psum (the ring-attention merge identity), so
    the result equals unsharded :func:`ops.attention.paged_attention`.
    """
    pg_shards = mesh.shape.get(SEQ_AXIS, 1)
    tokens_global = k_flat.shape[0]
    num_pages = tokens_global // page_size
    if num_pages % pg_shards != 0:
        # A page straddling the shard boundary would be silently
        # mis-owned (floored pages_local) — wrong attention, no error.
        raise ValueError(
            f"num_pages={num_pages} must divide by pg_shards={pg_shards}")
    pages_local = num_pages // pg_shards

    def local_fn(q_l, k_l, v_l, tables, ctx, qpos):
        my_pg = jax.lax.axis_index(SEQ_AXIS)
        nql = q_l.shape[2]
        # Every page shard must flash the SAME query heads for the merge
        # to be head-aligned, so gather the model-shard's full head set
        # across ``seq`` (tiny payload: B·T·group·hd). Each chip still
        # reads only its own page slice — the bandwidth term, which is
        # what decode is bound by — and GQA reuses those K/V bytes across
        # all gathered heads.
        q_full = jax.lax.all_gather(q_l, SEQ_AXIS, axis=2, tiled=True)
        m, l, acc = _partial_flash(
            q_full, k_l, v_l, tables, ctx, qpos, page_size=page_size,
            block_pages=block_pages, pages_local=pages_local, my_pg=my_pg)
        m_g = jax.lax.pmax(m, SEQ_AXIS)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, SEQ_AXIS)
        acc_g = jax.lax.psum(acc * corr[..., None], SEQ_AXIS)
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)
        b, t, nkvl, group, d = out.shape
        out = out.reshape(b, t, nkvl * group, d).astype(q_l.dtype)
        # Keep this device's own head slice (model-major tuple sharding:
        # within a model shard, seq-coordinate s owns heads [s·nql, ...)).
        return jax.lax.dynamic_slice_in_dim(out, my_pg * nql, nql, axis=2)

    kv_spec = P(SEQ_AXIS, MODEL_AXIS, None)
    rep = P(None, None)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(q_heads_spec(), kv_spec, kv_spec, rep, P(None), rep),
        out_specs=q_heads_spec(),
    )(q, k_flat, v_flat, page_tables, ctx_lens, q_positions)


def paged_decode_attention_kv_split_pallas(
    mesh: Mesh,
    q: jnp.ndarray,  # [B, n_q, hd] (T=1 decode shape, heads (model,seq))
    k_flat: jnp.ndarray,
    v_flat: jnp.ndarray,
    page_tables: jnp.ndarray,
    ctx_lens: jnp.ndarray,
    page_size: int,
    interpret: bool = False,
    layer=None,  # with it the pools are [L, tokens, ...]: read layer `layer`
) -> jnp.ndarray:
    """Decode attention on the page-split pool via the Pallas partial
    kernel: each device runs the kv-split walk of ``_decode_walk_kernel``
    over its OWN page slice (pages it does not own are neither fetched
    nor counted; owned ones are indexed locally) and the flash partials
    merge across ``seq`` exactly like the XLA path."""
    from runbookai_tpu.ops.paged_attention_pallas import (
        _stacked,
        paged_decode_attention_partial,
    )

    (k_flat, v_flat), layer = _stacked((k_flat, v_flat), layer)
    pg_shards = mesh.shape.get(SEQ_AXIS, 1)
    num_pages = k_flat.shape[1] // page_size
    if num_pages % pg_shards != 0:
        raise ValueError(
            f"num_pages={num_pages} must divide by pg_shards={pg_shards}")
    pages_local = num_pages // pg_shards

    def local_fn(q_l, k_l, v_l, tables, ctx, layer):
        my_pg = jax.lax.axis_index(SEQ_AXIS)
        nql = q_l.shape[1]
        q_full = jax.lax.all_gather(q_l, SEQ_AXIS, axis=1, tiled=True)
        acc, m, l = paged_decode_attention_partial(
            q_full, k_l, v_l, tables, ctx, my_pg.astype(jnp.int32),
            page_size=page_size, pages_local=pages_local,
            interpret=interpret, layer=layer)
        m_g = jax.lax.pmax(m, SEQ_AXIS)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, SEQ_AXIS)
        acc_g = jax.lax.psum(acc * corr[..., None], SEQ_AXIS)
        out = (acc_g / jnp.maximum(l_g[..., None], 1e-30)).astype(q_l.dtype)
        return jax.lax.dynamic_slice_in_dim(out, my_pg * nql, nql, axis=1)

    heads = P(None, (MODEL_AXIS, SEQ_AXIS), None)
    kv_spec = P(None, SEQ_AXIS, MODEL_AXIS, None)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(heads, kv_spec, kv_spec, P(None, None), P(None), P(None)),
        out_specs=heads,
        check_vma=False,  # pallas out_shapes carry no vma info
    )(q, k_flat, v_flat, page_tables, ctx_lens, layer)


# ----------------------------------------------------------------- write

def write_kv_pages_batch_kv_split(
    mesh: Mesh,
    kv_flat: jnp.ndarray,  # [tokens, n_kv, hd] (seq × model sharded)
    new_kv: jnp.ndarray,  # [B, T, n_kv, hd] (model-sharded heads)
    positions: jnp.ndarray,  # [B, T] (replicated)
    page_tables: jnp.ndarray,  # [B, max_pages(+1)] (replicated)
    page_size: int,
    layer=None,
) -> jnp.ndarray:
    """Batch K/V scatter where each device keeps only writes landing in
    its own page slice (out-of-slice destinations drop — they are some
    other device's writes). With ``layer`` (the serving forward)
    ``kv_flat`` is the whole ``[L, tokens, n_kv, hd]`` pool the layer
    scan carries and comes back in that shape, that layer's rows written
    in place (``ops.attention.write_kv_pages_batch``)."""
    shape = kv_flat.shape
    if layer is None:  # one layer's [tokens, n_kv, hd]: layer 0 of one
        kv_flat, layer = kv_flat[None], 0
    pg_shards = mesh.shape.get(SEQ_AXIS, 1)
    if (kv_flat.shape[1] // page_size) % pg_shards != 0:
        raise ValueError(
            f"num_pages={kv_flat.shape[1] // page_size} must divide by "
            f"pg_shards={pg_shards}")
    tokens_local = kv_flat.shape[1] // pg_shards

    def local_fn(kv_l, new_l, pos, tables, ly):
        my_pg = jax.lax.axis_index(SEQ_AXIS)
        b, t = pos.shape
        logical_page = pos // page_size
        offset = pos % page_size
        phys = jnp.take_along_axis(tables, logical_page, axis=1)
        dest = (phys * page_size + offset).reshape(b * t)
        local = dest - my_pg * tokens_local
        # Foreign destinations must map to an out-of-bounds-HIGH sentinel:
        # mode='drop' only drops high indices — a negative index wraps
        # Python-style and would corrupt this shard's mirror slot.
        in_slice = (local >= 0) & (local < tokens_local)
        rows, base = pool_rows(kv_l, ly)
        local = jnp.where(in_slice, base + local, rows.shape[0])
        flat_new = new_l.reshape((b * t,) + new_l.shape[2:])
        return rows.at[local].set(flat_new.astype(rows.dtype),
                                  mode="drop").reshape(kv_l.shape)

    kv_spec = P(None, SEQ_AXIS, MODEL_AXIS, None)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(kv_spec, P(None, None, MODEL_AXIS, None), P(None, None),
                  P(None, None), P()),
        out_specs=kv_spec,
    )(kv_flat, new_kv, positions, page_tables,
      jnp.asarray(layer, jnp.int32)).reshape(shape)
