"""Pallas TPU kernel: ragged paged attention for the decode hot loop.

SURVEY.md §7 names this "the single riskiest piece of device code": the XLA
fallback (:mod:`runbookai_tpu.ops.attention`) re-gathers KV through the page
table every step; this kernel instead drives the page-table indirection with
**scalar prefetch** — the grid's K/V block index_maps read the prefetched page
table, so Mosaic pipelines exactly the pages each sequence owns from HBM into
VMEM (double-buffered) and flash-accumulates in VMEM scratch.

Pattern per PAPERS.md "Ragged Paged Attention" + the pallas guide
(PrefetchScalarGridSpec): grid = (batch, pages); for a fixed sequence the page
axis iterates sequentially, carrying (m, l, acc) scratch; the output block is
written on the sequence's last page step. Decode-shaped (T = 1).

Two kernels share the flash-accumulate pattern:

- :func:`paged_decode_attention` — decode-shaped (T = 1), grid (batch, pages).
- :func:`paged_chunk_attention` — T > 1 (chunked prefill and the speculative
  verify forward), grid (batch, q_blocks, pages) with the page axis innermost
  so scratch carries across a sequence's pages; query positions are scalar-
  prefetched for the causal+ragged mask, and the query dimension is blocked
  to bound VMEM scratch (TQ·n_q accumulator rows per step).

Selected by ``EngineConfig.attn_impl = "pallas"``; interpret mode keeps it
testable on CPU meshes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_page_accumulate(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                           base, ctx, n_kv: int, group: int,
                           page_size: int, ks_ref=None, vs_ref=None) -> None:
    """Shared online-softmax accumulation of one K/V page into the
    (m, l, acc) scratch — the body of ALL decode kernels (full-pool,
    kv-split partial, int8-scaled), kept in one place so masking/numerics
    fixes cannot diverge. Masked positions are explicitly zeroed in p
    (exp underflow handles them too, but the explicit mask keeps l exact
    by construction). With ``ks_ref``/``vs_ref`` the K/V page holds int8
    values and these are their per-(token, head) f32 absmax scales,
    applied on the in-VMEM widen (ops/attention.py quantize_kv)."""
    q = q_ref[0].astype(jnp.float32)  # [n_q, hd]
    hd = q.shape[-1]
    scale = 1.0 / (hd ** 0.5)
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    valid = pos < ctx  # [1, page_size]

    m_prev = m_ref[:, :1]  # [n_q, 1]
    l_prev = l_ref[:, :1]
    acc_prev = acc_ref[:]

    s_rows = []
    v_heads = []
    for h in range(n_kv):
        k_h = k_ref[0, :, h, :].astype(jnp.float32)  # [ps, hd]
        if ks_ref is not None:
            k_h = k_h * ks_ref[0, :, h][:, None]
        q_h = q[h * group : (h + 1) * group]  # [group, hd]
        s_h = jax.lax.dot_general(
            q_h * scale, k_h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [group, ps]
        s_rows.append(jnp.where(valid, s_h, NEG_INF))
        v_h = v_ref[0, :, h, :].astype(jnp.float32)  # [ps, hd]
        if vs_ref is not None:
            v_h = v_h * vs_ref[0, :, h][:, None]
        v_heads.append(v_h)
    s = jnp.concatenate(s_rows, axis=0)  # [n_q, ps] (kv-major head order)

    m_blk = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)
    p_blk = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # [1,ps] broadcasts
    l_new = l_prev * alpha + jnp.sum(p_blk, axis=1, keepdims=True)

    pv_rows = []
    for h in range(n_kv):
        p_h = p_blk[h * group : (h + 1) * group]
        pv_rows.append(jax.lax.dot_general(
            p_h, v_heads[h], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ))  # [group, hd]
    pv = jnp.concatenate(pv_rows, axis=0)  # [n_q, hd]

    acc_ref[:] = acc_prev * alpha + pv
    m_ref[:, :1] = m_new
    l_ref[:, :1] = l_new


def _decode_kernel(
    # scalar prefetch:
    page_tables_ref,  # [B, P] int32 (SMEM)
    ctx_lens_ref,  # [B] int32 (SMEM)
    # blocks:
    q_ref,  # [1, n_q, hd]
    k_ref,  # [1, page_size, n_kv, hd]
    v_ref,  # [1, page_size, n_kv, hd]
    o_ref,  # [1, n_q, hd]
    # scratch:
    m_ref,  # [n_q, 128] f32
    l_ref,  # [n_q, 128] f32
    acc_ref,  # [n_q, hd] f32
    *,
    page_size: int,
    n_kv: int,
    group: int,
    pages_per_seq: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_lens_ref[b]
    base = p * page_size

    @pl.when(base < ctx)
    def _accumulate():
        _flash_page_accumulate(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                               base, ctx, n_kv, group, page_size)

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        l_final = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_final).astype(o_ref.dtype)


def _decode_kernel_int8(
    # scalar prefetch:
    page_tables_ref,  # [B, P] int32 (SMEM)
    ctx_lens_ref,  # [B] int32 (SMEM)
    # blocks:
    q_ref,  # [1, n_q, hd]
    k_ref,  # [1, page_size, n_kv, hd] int8
    v_ref,  # [1, page_size, n_kv, hd] int8
    ks_ref,  # [1, page_size, n_kv] f32 absmax scales
    vs_ref,  # [1, page_size, n_kv] f32
    o_ref,  # [1, n_q, hd]
    # scratch:
    m_ref,
    l_ref,
    acc_ref,
    *,
    page_size: int,
    n_kv: int,
    group: int,
    pages_per_seq: int,
):
    """int8-KV decode: identical flash accumulation, values widened and
    scaled in VMEM on load — HBM still moves 1 byte/value."""
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_lens_ref[b]
    base = p * page_size

    @pl.when(base < ctx)
    def _accumulate():
        _flash_page_accumulate(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                               base, ctx, n_kv, group, page_size,
                               ks_ref=ks_ref, vs_ref=vs_ref)

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        l_final = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_final).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, n_q, hd]
    k_flat,  # [num_pages * page_size, n_kv, hd], or (int8 values, scales)
    v_flat,  # same
    page_tables: jnp.ndarray,  # [B, P] int32 (physical page ids; 0 = null)
    ctx_lens: jnp.ndarray,  # [B] int32
    page_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ragged paged attention for decode (one query token per sequence)."""
    if isinstance(k_flat, tuple):
        return _paged_decode_attention_int8(
            q, k_flat, v_flat, page_tables, ctx_lens,
            page_size=page_size, interpret=interpret)
    b, n_q, hd = q.shape
    n_kv = k_flat.shape[1]
    group = n_q // n_kv
    pages_per_seq = page_tables.shape[1]
    k_pages = k_flat.reshape(-1, page_size, n_kv, hd)
    v_pages = v_flat.reshape(-1, page_size, n_kv, hd)

    # Query head order for the kernel is kv-major ([kv0 g0..gN, kv1 g0..], the
    # same grouping the model's reshape uses) — no permutation needed.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, n_q, hd), lambda b_, p_, pt, cl: (b_, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, hd),
                         lambda b_, p_, pt, cl: (pt[b_, p_], 0, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, hd),
                         lambda b_, p_, pt, cl: (pt[b_, p_], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_q, hd), lambda b_, p_, pt, cl: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_q, 128), jnp.float32),  # m
            pltpu.VMEM((n_q, 128), jnp.float32),  # l
            pltpu.VMEM((n_q, hd), jnp.float32),  # acc
        ],
    )
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, n_kv=n_kv, group=group,
        pages_per_seq=pages_per_seq,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_q, hd), q.dtype),
        interpret=interpret,
    )(page_tables, ctx_lens, q, k_pages, v_pages)


def _paged_decode_attention_int8(
    q: jnp.ndarray,  # [B, n_q, hd]
    k_flat: tuple,  # (int8 values [tokens, n_kv, hd], f32 scales [tokens, n_kv])
    v_flat: tuple,
    page_tables: jnp.ndarray,
    ctx_lens: jnp.ndarray,
    page_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode over the int8-scaled pool: same grid/prefetch as the raw
    kernel with two extra per-page scale blocks."""
    b, n_q, hd = q.shape
    k_vals, k_scales = k_flat
    v_vals, v_scales = v_flat
    n_kv = k_vals.shape[1]
    group = n_q // n_kv
    pages_per_seq = page_tables.shape[1]
    k_pages = k_vals.reshape(-1, page_size, n_kv, hd)
    v_pages = v_vals.reshape(-1, page_size, n_kv, hd)
    ks_pages = k_scales.reshape(-1, page_size, n_kv)
    vs_pages = v_scales.reshape(-1, page_size, n_kv)

    kv_map = lambda b_, p_, pt, cl: (pt[b_, p_], 0, 0, 0)  # noqa: E731
    s_map = lambda b_, p_, pt, cl: (pt[b_, p_], 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, n_q, hd), lambda b_, p_, pt, cl: (b_, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, hd), kv_map),
            pl.BlockSpec((1, page_size, n_kv, hd), kv_map),
            pl.BlockSpec((1, page_size, n_kv), s_map),
            pl.BlockSpec((1, page_size, n_kv), s_map),
        ],
        out_specs=pl.BlockSpec((1, n_q, hd),
                               lambda b_, p_, pt, cl: (b_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_q, 128), jnp.float32),
            pltpu.VMEM((n_q, 128), jnp.float32),
            pltpu.VMEM((n_q, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel_int8, page_size=page_size, n_kv=n_kv, group=group,
        pages_per_seq=pages_per_seq,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_q, hd), q.dtype),
        interpret=interpret,
    )(page_tables, ctx_lens, q, k_pages, v_pages, ks_pages, vs_pages)


def _chunk_kernel(
    # scalar prefetch:
    page_tables_ref,  # [B, P] int32 (SMEM)
    ctx_lens_ref,  # [B] int32 (SMEM)
    q_start_ref,  # [B] int32 (SMEM) — absolute position of each row's query 0
    # blocks:
    q_ref,  # [1, TQ, n_q, hd]
    k_ref,  # [1, page_size, n_kv, hd]
    v_ref,  # [1, page_size, n_kv, hd]
    o_ref,  # [1, TQ, n_q, hd]
    # scratch:
    m_ref,  # [TQ*n_q, 128] f32
    l_ref,  # [TQ*n_q, 128] f32
    acc_ref,  # [TQ*n_q, hd] f32
    *,
    page_size: int,
    n_kv: int,
    group: int,
    tq: int,
    pages_per_seq: int,
):
    b = pl.program_id(0)
    qb = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_lens_ref[b]
    base = p * page_size
    # Query positions are contiguous per sequence (wrapper contract), so row
    # positions derive from the scalar start — no vector SMEM reads needed.
    q0 = q_start_ref[b] + qb * tq
    qpos_max = q0 + tq - 1

    @pl.when((base < ctx) & (base <= qpos_max))
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)  # [TQ, n_q, hd]
        hd = q.shape[-1]
        scale = 1.0 / (hd ** 0.5)
        # Row r of a per-kv-head block is query token r // group; mask built
        # entirely from 2D iotas (Mosaic-friendly).
        shape = (tq * group, page_size)
        cache_pos = base + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        qpos_rows = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
        mask = (cache_pos < ctx) & (cache_pos <= qpos_rows)

        m_prev = m_ref[:, :1]  # [TQ*n_q, 1]
        l_prev = l_ref[:, :1]
        acc_prev = acc_ref[:]

        s_rows = []
        v_heads = []
        for h in range(n_kv):
            k_h = k_ref[0, :, h, :].astype(jnp.float32)  # [ps, hd]
            # [TQ, group, hd] -> [TQ*group, hd] rows (t-major within the head)
            q_h = q[:, h * group : (h + 1) * group].reshape(tq * group, hd)
            s_h = jax.lax.dot_general(
                q_h * scale, k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [TQ*group, ps]
            s_rows.append(jnp.where(mask, s_h, NEG_INF))
            v_heads.append(v_ref[0, :, h, :].astype(jnp.float32))  # [ps, hd]
        s = jnp.concatenate(s_rows, axis=0)  # [TQ*n_q, ps] (kv-major blocks)

        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        alpha = jnp.exp(m_prev - m_new)
        # Fully-masked rows keep m == NEG_INF; exp(s - m) would be exp(0)=1
        # there, so zero masked probabilities explicitly (keeps l exact and
        # padded rows normalizing to zero).
        p_blk = jnp.where(jnp.concatenate([mask] * n_kv, axis=0),
                          jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p_blk, axis=1, keepdims=True)

        pv_rows = []
        for h in range(n_kv):
            p_h = p_blk[h * tq * group : (h + 1) * tq * group]
            pv_rows.append(jax.lax.dot_general(
                p_h, v_heads[h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))  # [TQ*group, hd]
        pv = jnp.concatenate(pv_rows, axis=0)

        acc_ref[:] = acc_prev * alpha + pv
        m_ref[:, :1] = m_new
        l_ref[:, :1] = l_new

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        l_final = jnp.maximum(l_ref[:, :1], 1e-30)
        out = acc_ref[:] / l_final  # [TQ*n_q, hd] in kv-major head blocks
        hd = out.shape[-1]
        # Per-head static slices back to [TQ, group, hd] (no 4D transpose).
        for h in range(n_kv):
            blk = out[h * tq * group : (h + 1) * tq * group]
            o_ref[0, :, h * group : (h + 1) * group, :] = (
                blk.reshape(tq, group, hd).astype(o_ref.dtype))


def chunk_q_block(t: int, n_q: int) -> int:
    """Query rows per grid step of :func:`paged_chunk_attention`: about
    1k accumulator rows (TQ * n_q) to bound VMEM scratch, and a multiple
    of 8 — every per-kv-head row block is TQ * group high, and Mosaic
    stacks those blocks (scores, masks, probabilities) along sublanes.
    With a GQA group of 7 an unaligned block (1024 // 28 = 36 rows, 252
    per head) fails to compile: the i1 mask concatenate dies in
    ``tpu.bitcast_vreg`` with "Invalid vector register cast" (TPU v5
    lite, jax 0.9.0). A short chunk pads up to one 8-row block."""
    cap = max(8, (1024 // n_q) // 8 * 8)
    return min(-(-t // 8) * 8, cap)


def paged_chunk_attention(
    q: jnp.ndarray,  # [B, T, n_q, hd]
    k_flat: jnp.ndarray,  # [num_pages * page_size, n_kv, hd]
    v_flat: jnp.ndarray,  # same
    page_tables: jnp.ndarray,  # [B, P] int32 (physical page ids; 0 = null)
    ctx_lens: jnp.ndarray,  # [B] int32 — cache length AFTER the chunk
    q_positions: jnp.ndarray,  # [B, T] int32 absolute positions of the queries
    page_size: int,
    interpret: bool = False,
    q_block: int | None = None,
) -> jnp.ndarray:
    """Ragged paged attention for T>1 chunks (prefill / speculative verify).

    Matches :func:`runbookai_tpu.ops.attention.paged_attention` semantics —
    causal over absolute positions, ragged over per-sequence context lengths —
    under one contract: each sequence's ``q_positions`` row must be contiguous
    ascending (``q_positions[i, t] == q_positions[i, 0] + t``). Both engine
    chunk paths satisfy this (prefill feeds ``range(pos, pos+chunk)``; the
    speculative verify feeds ``range(ctx-1, ctx-1+k)``); prefill's trash-
    position pad tail violates it, but those rows' outputs are discarded and
    their K/V go to the null page.
    """
    b, t, n_q, hd = q.shape
    n_kv = k_flat.shape[1]
    group = n_q // n_kv
    pages_per_seq = page_tables.shape[1]
    k_pages = k_flat.reshape(-1, page_size, n_kv, hd)
    v_pages = v_flat.reshape(-1, page_size, n_kv, hd)
    q_start = q_positions[:, 0].astype(jnp.int32)

    tq = q_block if q_block is not None else chunk_q_block(t, n_q)
    t_pad = ((t + tq - 1) // tq) * tq
    n_qb = t_pad // tq
    if t_pad != t:
        # Padded rows act like later queries (q0 + t): they attend at most the
        # whole context and their outputs are sliced off on return.
        q = jnp.pad(q, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_qb, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, tq, n_q, hd),
                         lambda b_, qb_, p_, pt, cl, qs: (b_, qb_, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, hd),
                         lambda b_, qb_, p_, pt, cl, qs: (pt[b_, p_], 0, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, hd),
                         lambda b_, qb_, p_, pt, cl, qs: (pt[b_, p_], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, n_q, hd),
                               lambda b_, qb_, p_, pt, cl, qs: (b_, qb_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq * n_q, 128), jnp.float32),  # m
            pltpu.VMEM((tq * n_q, 128), jnp.float32),  # l
            pltpu.VMEM((tq * n_q, hd), jnp.float32),  # acc
        ],
    )
    kernel = functools.partial(
        _chunk_kernel, page_size=page_size, n_kv=n_kv, group=group, tq=tq,
        pages_per_seq=pages_per_seq,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t_pad, n_q, hd), q.dtype),
        interpret=interpret,
    )(page_tables, ctx_lens, q_start, q, k_pages, v_pages)
    return out[:, :t]


def paged_ragged_attention(
    q: jnp.ndarray,  # [N, n_q, hd] — flat ragged token batch
    k_flat: jnp.ndarray,  # [num_pages * page_size, n_kv, hd]
    v_flat: jnp.ndarray,  # same
    page_tables: jnp.ndarray,  # [R, P] int32 per-ROW page tables
    ctx_lens: jnp.ndarray,  # [R] int32 cache length incl. this step's tokens
    q_positions: jnp.ndarray,  # [N] int32 absolute query positions
    row_ids: jnp.ndarray,  # [N] int32 row owning each token
    page_size: int,
    ragged_block: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ragged paged attention over a FLAT mixed prefill+decode batch.

    The per-row-ragged extension of :func:`paged_chunk_attention` for the
    unified mixed dispatch (PAPERS.md "Ragged Paged Attention"): decode
    rows feed 1 token, prefill rows a chunk, flattened into one [N]
    buffer. Layout contract (the engine's mixed builder upholds it): each
    row's token run is contiguous ascending and starts at a multiple of
    ``ragged_block``, so every ``ragged_block``-sized q block belongs to
    exactly one row — the flat batch maps onto the chunk kernel's
    (sequence, q_block, page) grid with the q-block axis re-labelled by a
    per-block row gather. Each grid step still scalar-prefetches the
    owning row's page table and flash-accumulates in VMEM, and K/V pages
    are fetched once per ``ragged_block`` queries rather than once per
    token (the reason this beats running the decode kernel at B = N).
    Per-row raggedness is carried by the per-block ``ctx_lens`` /
    ``q_start`` scalars: a pad block (null row, ``ctx_len = 0``) skips
    every accumulation and finalizes to zeros; pad tokens inside a real
    row's last block act as later queries whose outputs the caller
    discards (their K/V writes go to the null page via trash positions).

    Returns [N, n_q, hd].
    """
    n, n_q, hd = q.shape
    rq = ragged_block
    nb = n // rq
    rows = row_ids.reshape(nb, rq)[:, 0]
    return paged_chunk_attention(
        q.reshape(nb, rq, n_q, hd), k_flat, v_flat,
        page_tables[rows], ctx_lens[rows], q_positions.reshape(nb, rq),
        page_size=page_size, interpret=interpret, q_block=rq,
    ).reshape(n, n_q, hd)


def _decode_kernel_partial(
    # scalar prefetch:
    page_tables_ref,  # [B, P] int32 GLOBAL page ids (SMEM)
    ctx_lens_ref,  # [B] int32 (SMEM)
    shard_ref,  # [1] int32 — this device's page-shard index (SMEM)
    # blocks:
    q_ref,  # [1, n_q, hd]
    k_ref,  # [1, page_size, n_kv, hd]  (LOCAL pool slice)
    v_ref,
    # outputs (un-normalized partials for the cross-shard merge):
    acc_out,  # [1, n_q, hd] f32
    m_out,  # [1, n_q, 128] f32
    l_out,  # [1, n_q, 128] f32
    # scratch:
    m_ref,
    l_ref,
    acc_ref,
    *,
    page_size: int,
    n_kv: int,
    group: int,
    pages_per_seq: int,
    pages_local: int,
):
    """KV page-split variant of :func:`_decode_kernel`: the pool ref is
    this device's page SLICE, pages not owned here are skipped (their
    shard contributes them), and the outputs are the flash partials
    ``(acc, m, l)`` — the shard_map wrapper merges across the ``seq``
    axis (``parallel/kv_split.py`` math) and normalizes."""
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_lens_ref[b]
    base = p * page_size
    owned = (page_tables_ref[b, p] // pages_local) == shard_ref[0]

    @pl.when((base < ctx) & owned)
    def _accumulate():
        _flash_page_accumulate(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                               base, ctx, n_kv, group, page_size)

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        acc_out[0] = acc_ref[:]
        m_out[0] = m_ref[:]
        l_out[0] = l_ref[:]


def paged_decode_attention_partial(
    q: jnp.ndarray,  # [B, n_q, hd]
    k_local: jnp.ndarray,  # [pages_local * page_size, n_kv, hd]
    v_local: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, P] GLOBAL page ids
    ctx_lens: jnp.ndarray,  # [B]
    my_pg: jnp.ndarray,  # scalar int32 page-shard index
    page_size: int,
    pages_local: int,
    interpret: bool = False,
):
    """Flash partials over a LOCAL page slice; returns (acc, m, l) with
    m/l padded to lane width (column 0 is the value)."""
    b, n_q, hd = q.shape
    n_kv = k_local.shape[1]
    group = n_q // n_kv
    pages_per_seq = page_tables.shape[1]
    k_pages = k_local.reshape(-1, page_size, n_kv, hd)
    v_pages = v_local.reshape(-1, page_size, n_kv, hd)

    def kv_map(b_, p_, pt, cl, sh):
        # Foreign pages clamp to slot 0 — the ownership predicate skips
        # their accumulation, so the fetched block is never read.
        local = pt[b_, p_] - sh[0] * pages_local
        return (jnp.clip(local, 0, pages_local - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, n_q, hd), lambda b_, p_, pt, cl, sh: (b_, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv, hd), kv_map),
            pl.BlockSpec((1, page_size, n_kv, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, n_q, hd), lambda b_, p_, pt, cl, sh: (b_, 0, 0)),
            pl.BlockSpec((1, n_q, 128), lambda b_, p_, pt, cl, sh: (b_, 0, 0)),
            pl.BlockSpec((1, n_q, 128), lambda b_, p_, pt, cl, sh: (b_, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_q, 128), jnp.float32),
            pltpu.VMEM((n_q, 128), jnp.float32),
            pltpu.VMEM((n_q, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel_partial, page_size=page_size, n_kv=n_kv, group=group,
        pages_per_seq=pages_per_seq, pages_local=pages_local,
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_q, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, n_q, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, n_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(page_tables, ctx_lens, my_pg.reshape(1), q, k_pages, v_pages)
    return acc, m[..., 0], l[..., 0]


# --------------------------------------------------------------------- TP ---
#
# Under a TP mesh the KV pool shards its kv-head axis and q its query-head
# axis (Megatron layout, parallel/sharding.py). XLA's SPMD partitioner can't
# see inside a pallas_call, so an unwrapped kernel would force an all-gather
# of the whole page pool every step — the exact failure VERDICT r2 weak #3
# called out. These wrappers run the kernel per model-axis shard via
# shard_map: each shard holds n_q/tp query heads and their matching n_kv/tp
# kv heads (head blocks are contiguous and kv-major, so GQA groups never
# straddle shards), while page tables and context lengths stay replicated.
# Attention mixes only across the context axis, never across heads — no
# collectives are needed inside the wrap.


def _model_tp(mesh) -> int:
    from runbookai_tpu.parallel.mesh import MODEL_AXIS

    return mesh.shape.get(MODEL_AXIS, 1) if mesh is not None else 1


def tp_shardable(mesh, n_kv: int) -> bool:
    """True when the kernels can run per model-axis shard: the kv-head axis
    must split evenly (matches ``kv_pool_sharding``'s shard-vs-replicate
    decision, so the pool layout and the kernel wrap always agree)."""
    tp = _model_tp(mesh)
    return tp > 1 and n_kv % tp == 0


def paged_decode_attention_tp(
    mesh, q, k_flat, v_flat, page_tables, ctx_lens, page_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """:func:`paged_decode_attention` over a TP mesh (heads sharded)."""
    from jax.sharding import PartitionSpec as P

    from runbookai_tpu.parallel.mesh import MODEL_AXIS

    heads = P(None, MODEL_AXIS, None)
    fn = functools.partial(paged_decode_attention, page_size=page_size,
                           interpret=interpret)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(heads, heads, heads, P(None, None), P(None)),
        out_specs=heads,
        # pallas_call out_shapes carry no varying-mesh-axes info; the wrap
        # itself is collective-free so the vma check adds nothing here.
        check_vma=False,
    )(q, k_flat, v_flat, page_tables, ctx_lens)


def paged_chunk_attention_tp(
    mesh, q, k_flat, v_flat, page_tables, ctx_lens, q_positions,
    page_size: int, interpret: bool = False,
) -> jnp.ndarray:
    """:func:`paged_chunk_attention` over a TP mesh (heads sharded)."""
    from jax.sharding import PartitionSpec as P

    from runbookai_tpu.parallel.mesh import MODEL_AXIS

    kv_heads = P(None, MODEL_AXIS, None)
    q_heads = P(None, None, MODEL_AXIS, None)
    fn = functools.partial(paged_chunk_attention, page_size=page_size,
                           interpret=interpret)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(q_heads, kv_heads, kv_heads, P(None, None), P(None),
                  P(None, None)),
        out_specs=q_heads,
        check_vma=False,
    )(q, k_flat, v_flat, page_tables, ctx_lens, q_positions)
