"""Ragged paged attention — the core serving op.

One code path serves both prefill and decode (decode is T=1): the current
chunk's K/V are scattered into the paged KV pool first, then queries attend
over the pool through the page table with a causal/ragged mask. This mirrors
the semantics of TPU ragged paged attention kernels (PAPERS.md: "Ragged Paged
Attention for TPU") and keeps shapes fully static for XLA.

Two implementations:

- :func:`paged_attention` — portable XLA path: flash-style blockwise
  accumulation (running max / normalizer) over KV-page blocks via ``lax.scan``,
  so HBM traffic per step is O(block) not O(max_seq). Runs on CPU meshes and
  TPU alike.
- A Pallas TPU kernel (``runbookai_tpu.ops.paged_attention_pallas``) selected
  by the engine on real TPU hardware for the decode hot loop.

No reference counterpart — RunbookAI delegates all model execution to hosted
APIs (SURVEY.md §2.9).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# int8 KV cache (kv_dtype=int8): pools are (values int8 [..., hd],
# scales f32 [...]) tuples with one absmax scale per (token, kv head) —
# written once per token, never rescaled (no read-modify-write under
# jit). TPUs accelerate int8 natively (fp8 converts through bf16 on
# v5e), and per-token absmax tracks magnitude better than e4m3's fixed
# exponent range at the same pool bytes (+4/head_dim scale overhead).


def quantize_kv(new_kv: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[..., hd] → (int8 values, f32 absmax-per-vector scales [...])."""
    scale = jnp.max(jnp.abs(new_kv.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(scale, 1e-8) / 127.0
    q = jnp.round(new_kv.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def pool_rows(kv, layer):
    """The pool as rows: its ``[L * tokens, ...]`` view and the first row
    of ``layer`` (a traced scalar inside the layer scan). The serving
    forward carries the WHOLE ``[L, tokens, n_kv, hd]`` pool through its
    scan over layers and the page writers address one layer of it by
    this row offset, so the write is a scatter of a few rows into the
    carried buffer. The reshape only merges the two leading axes (a
    bitcast). ``layer=None``: ``kv`` is one layer's ``[tokens, ...]``
    already."""
    if layer is None:
        return kv, 0
    if isinstance(kv, tuple):  # int8 pool: values + per-vector scales
        return (tuple(a.reshape((-1,) + a.shape[2:]) for a in kv),
                layer * kv[0].shape[1])
    return kv.reshape((-1,) + kv.shape[2:]), layer * kv.shape[1]


def _dequant_gather(kv_flat, flat_idx):
    """Gather pool rows at ``flat_idx``; dequantize when the pool is an
    (int8 values, f32 scales) tuple."""
    if isinstance(kv_flat, tuple):
        vals, scales = kv_flat
        return vals[flat_idx].astype(jnp.float32) \
            * scales[flat_idx][..., None]
    return kv_flat[flat_idx].astype(jnp.float32)


def write_kv_pages(
    kv_flat: jnp.ndarray,  # [num_pages * page_size, n_kv, head_dim]
    new_kv: jnp.ndarray,  # [T, n_kv, head_dim]
    positions: jnp.ndarray,  # [T] absolute token positions in the sequence
    page_table_row: jnp.ndarray,  # [max_pages] physical page ids for this seq
    page_size: int,
) -> jnp.ndarray:
    """Scatter one sequence's new K or V vectors into the flat page pool."""
    logical_page = positions // page_size
    offset = positions % page_size
    dest = page_table_row[logical_page] * page_size + offset  # [T]
    if isinstance(kv_flat, tuple):
        vals, scales = kv_flat
        q, s = quantize_kv(new_kv)
        return vals.at[dest].set(q), scales.at[dest].set(s)
    return kv_flat.at[dest].set(new_kv.astype(kv_flat.dtype))


def write_kv_pages_batch(
    kv_flat: jnp.ndarray,  # [num_pages * page_size, n_kv, head_dim]
    new_kv: jnp.ndarray,  # [B, T, n_kv, head_dim]
    positions: jnp.ndarray,  # [B, T] absolute positions (pads -> trash column)
    page_tables: jnp.ndarray,  # [B, max_pages(+1)] physical page ids per seq
    page_size: int,
    layer=None,
) -> jnp.ndarray:
    """Scatter a whole batch's new K/V in ONE flat scatter.

    Replaces a per-slot Python loop whose program size scaled with
    max_batch_slots (VERDICT r1 weak #6). Sequences own disjoint pages, so
    flattened destinations never collide — except padding rows, whose
    positions resolve through the trailing trash column to the reserved
    null page 0 (PageAllocator.NULL_PAGE), which is never read.

    With ``layer`` (the serving forward), ``kv_flat`` is the WHOLE pool
    ``[L, tokens, n_kv, head_dim]`` and the rows land at
    ``layer * tokens + dest`` of its row view (:func:`pool_rows`): the
    scatter touches B * T rows of the buffer the layer scan carries and
    nothing else, so XLA updates the pool in place. Returns the pool in
    the shape it came in.
    """
    b, t = positions.shape
    logical_page = positions // page_size
    offset = positions % page_size
    phys = jnp.take_along_axis(page_tables, logical_page, axis=1)  # [B, T]
    rows, base = pool_rows(kv_flat, layer)
    dest = base + (phys * page_size + offset).reshape(b * t)
    flat_new = new_kv.reshape((b * t,) + new_kv.shape[2:])
    if isinstance(kv_flat, tuple):  # int8 pool: values + per-vector scales
        vals, scales = rows
        q, s = quantize_kv(flat_new)
        return (vals.at[dest].set(q).reshape(kv_flat[0].shape),
                scales.at[dest].set(s).reshape(kv_flat[1].shape))
    return rows.at[dest].set(flat_new.astype(rows.dtype)).reshape(
        kv_flat.shape)


def paged_attention(
    q: jnp.ndarray,  # [B, T, n_q, head_dim]
    k_flat: jnp.ndarray,  # [num_pages * page_size, n_kv, head_dim]
    v_flat: jnp.ndarray,  # [num_pages * page_size, n_kv, head_dim]
    page_tables: jnp.ndarray,  # [B, max_pages]
    ctx_lens: jnp.ndarray,  # [B] total cached tokens per sequence (incl. chunk)
    q_positions: jnp.ndarray,  # [B, T] absolute positions of the queries
    page_size: int,
    block_pages: int = 32,
    walk_live: bool = False,
    gather_pages: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """Blockwise ragged paged attention. Returns [B, T, n_q, head_dim].

    ``window``: a query at position ``i`` sees key ``j`` iff ``i - j <
    window`` (and ``j <= i``): the mask gets a lower edge, and the walk
    starts at the block that holds the batch's lowest one. The table's
    columns behind a row's window may point anywhere (the manager gives
    those pages back while the sequence lives): they are masked. None: no
    edge is computed and the program is what it was.

    ``walk_live``: stop at the block that holds the batch's longest context
    (a loop whose length the device decides) instead of walking every block
    of the page table: a pool sized for 16k-token sequences costs a batch
    of 3k-token ones a fifth of the gathers. ``gather_pages``: gather WHOLE
    pages (``[page_size, n_kv, head_dim]`` a row of the gather) instead of
    token rows: on the chip a gather of 1 KB rows ran at 92 GB/s (PERF.md,
    PR 31). Raw-dtype pools only."""
    b, t, n_q, d = q.shape
    n_kv = (k_flat[0] if isinstance(k_flat, tuple) else k_flat).shape[1]
    group = n_q // n_kv
    max_pages = page_tables.shape[1]
    n_blocks = max(1, (max_pages + block_pages - 1) // block_pages)
    block_tokens = block_pages * page_size

    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    qf = q.astype(jnp.float32) * scale
    # [B, T, n_kv, group, d] so kv heads broadcast over their query group.
    qf = qf.reshape(b, t, n_kv, group, d)

    def block_step(carry, blk):
        m, l, acc = carry  # [B,T,n_kv,group], same, [B,T,n_kv,group,d]
        page_idx = blk * block_pages + jnp.arange(block_pages)  # [block_pages]
        phys = page_tables[:, :]  # [B, max_pages]
        phys_blk = jnp.take_along_axis(
            phys, jnp.broadcast_to(page_idx[None, :], (b, block_pages)) % max_pages, axis=1
        )  # [B, block_pages]
        token_off = jnp.arange(block_tokens)
        flat_idx = (
            phys_blk[:, token_off // page_size] * page_size + token_off % page_size
        )  # [B, block_tokens]
        if gather_pages:
            def pages_of(flat):
                # ``page_size`` consecutive token rows a gathered row, out of
                # the pool as it lies: a ``[pages, page_size, ...]`` VIEW of a
                # ``[tokens, 2, 256]`` pool was re-laid out whole, 403 MB a
                # side and call (seen in the compiled program).
                slabs = jax.vmap(lambda start: jax.lax.dynamic_slice_in_dim(
                    flat, start, page_size, axis=0))(
                        (phys_blk * page_size).reshape(-1))
                return slabs.reshape(b, block_tokens, *flat.shape[1:]).astype(
                    jnp.float32)

            kb, vb = pages_of(k_flat), pages_of(v_flat)
        else:
            kb = _dequant_gather(k_flat, flat_idx)  # [B, block_tokens, n_kv, d]
            vb = _dequant_gather(v_flat, flat_idx)

        # Absolute cache positions covered by this block (same for every seq).
        cache_pos = blk * block_tokens + token_off  # [block_tokens]
        # Causal + ragged mask: position visible iff < ctx_len and <= q_position.
        valid = (cache_pos[None, :] < ctx_lens[:, None])[:, None, :]  # [B,1,block]
        causal = cache_pos[None, None, :] <= q_positions[:, :, None]  # [B,T,block]
        if window is not None:
            causal &= cache_pos[None, None, :] > q_positions[:, :, None] - window
        mask = (valid & causal)[:, :, None, None, :]  # [B,T,1,1,block]

        scores = jnp.einsum("btkgd,bskd->btkgs", qf, kb)  # [B,T,n_kv,group,block]
        scores = jnp.where(mask, scores, NEG_INF)

        m_blk = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # Renormalize previous accumulator, add this block's contribution.
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum("btkgs,bskd->btkgd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, t, n_kv, group), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, t, n_kv, group), dtype=jnp.float32)
    acc0 = jnp.zeros((b, t, n_kv, group, d), dtype=jnp.float32)
    if walk_live or window is not None:
        live_blocks = jnp.minimum(
            n_blocks, (jnp.max(ctx_lens) + block_tokens - 1) // block_tokens)
        first = 0
        if window is not None:
            # The lowest edge of the batch's live queries (a pad's position
            # is the trash column's, past every context).
            lowest = jnp.min(jnp.where(q_positions < ctx_lens[:, None],
                                       q_positions, jnp.iinfo(jnp.int32).max))
            first = jnp.clip((lowest - window + 1) // block_tokens, 0, live_blocks)
        m, l, acc = jax.lax.fori_loop(
            first, live_blocks, lambda blk, c: block_step(c, blk)[0], (m0, l0, acc0))
    else:
        (m, l, acc), _ = jax.lax.scan(block_step, (m0, l0, acc0), jnp.arange(n_blocks))

    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, t, n_q, d).astype(q.dtype)


def ragged_paged_attention(
    q: jnp.ndarray,  # [N, n_q, head_dim] — flat ragged token batch
    k_flat: jnp.ndarray,  # [num_pages * page_size, n_kv, head_dim]
    v_flat: jnp.ndarray,  # same
    page_tables: jnp.ndarray,  # [R, max_pages] per-ROW page tables
    ctx_lens: jnp.ndarray,  # [R] cached tokens per row (incl. this step's)
    q_positions: jnp.ndarray,  # [N] absolute position of each query token
    row_ids: jnp.ndarray,  # [N] row (sequence) owning each token
    page_size: int,
    block_pages: int = 32,
    ragged_block: int = 8,
    window: int | None = None,
) -> jnp.ndarray:
    """Portable XLA ragged paged attention over a FLAT mixed token batch.

    The segment-masked layout for the unified mixed prefill+decode dispatch
    (PAPERS.md "Ragged Paged Attention"): decode rows contribute 1 token,
    prefill rows a whole chunk, all flattened into one [N] buffer whose
    per-token ``row_ids`` select the page table / context length to attend
    through. Layout contract — each row's token run is contiguous and
    starts at a multiple of ``ragged_block`` (the engine's mixed-batch
    builder pads rows up to it) — so every ``ragged_block``-sized block
    belongs to exactly one row and the flat batch collapses to a
    [N/ragged_block, ragged_block] chunked call of :func:`paged_attention`
    with per-block gathered tables: page blocks are fetched once per
    ``ragged_block`` queries instead of once per token, and the existing
    causal+ragged mask (position < ctx, position ≤ q_position) does the
    segment masking. Pad tokens (trash positions / null rows with
    ``ctx_len = 0``) produce finite garbage that callers discard.

    This is the STANDALONE op (and the layout-contract reference, pinned
    against per-sequence attention by tests/test_mixed_dispatch.py): the
    serving forward does not call it per layer — ``forward_ragged_impl``
    hoists this exact flat→blocked transform above its layer scan so the
    KV-write gathers share it. Change the layout here and there together.

    Returns [N, n_q, head_dim].
    """
    n, n_q, d = q.shape
    rq = ragged_block
    nb = n // rq
    rows = row_ids.reshape(nb, rq)[:, 0]
    out = paged_attention(
        q.reshape(nb, rq, n_q, d), k_flat, v_flat,
        page_tables[rows], ctx_lens[rows], q_positions.reshape(nb, rq),
        page_size, block_pages=block_pages, window=window,
    )
    return out.reshape(n, n_q, d)
