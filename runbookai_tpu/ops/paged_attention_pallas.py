"""Pallas TPU kernels: ragged paged attention over the engine's KV pool.

SURVEY.md §7 names this "the single riskiest piece of device code": the XLA
fallback (:mod:`runbookai_tpu.ops.attention`) re-gathers KV through the page
table every step; these kernels read the **scalar-prefetched** page table
and fetch exactly the pages a sequence owns into VMEM, flash-accumulating
(m, l, acc) in float32 scratch (PAPERS.md "Ragged Paged Attention").

Two kernels:

- :func:`paged_decode_attention` — decode-shaped (T = 1). **The page walk is
  inside the kernel**: grid = (rows,), and each row loops
  ``cdiv(ctx_lens[row], pages a step x page_size)`` times over ITS page
  table, so an empty slot costs one grid step and a row of 400 tokens the
  work of 400 tokens, whatever the table's width. The pool stays where XLA
  put it (``memory_space=pl.ANY``); a step issues one async copy a live
  page into a double-buffered VMEM group, the next group's copies in
  flight while this one is accumulated. Pages a step comes from the page's
  bytes against a fixed VMEM budget (:func:`decode_pages_per_step`). One
  walk serves every pool: raw pages, int8 pages with scales, and a page-
  split shard that skips the pages it does not own and returns partials.
- :func:`paged_chunk_attention` — T > 1 (chunked prefill and the speculative
  verify forward), grid (batch, q_blocks, pages) with the page axis innermost
  so scratch carries across a sequence's pages, one page a grid step; query
  positions are scalar-prefetched for the causal+ragged mask, and the query
  dimension is blocked to bound VMEM scratch (TQ·n_q accumulator rows per
  step). (ROADMAP A2: the decode walk is what it should take over.)

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

# VMEM the decode walk may spend on page buffers (K and V, two groups
# each), and the most cache positions one step takes: past that a step's
# scores outgrow the vector registers and a row's last, part-filled group
# wastes more than a longer step saves in loop trips.
_DECODE_KV_VMEM_BYTES = 1 << 20
_DECODE_STEP_POSITIONS = 512


def decode_pages_per_step(page_size: int, n_kv: int, hd: int, kv_dtype,
                          pages_per_seq: int) -> int:
    """Pages one step of the decode walk fetches and accumulates: what the
    VMEM budget holds of this pool's pages, at most ``_DECODE_STEP_POSITIONS``
    positions and never more than a row's table has columns."""
    page_bytes = page_size * n_kv * hd * jnp.dtype(kv_dtype).itemsize
    return max(1, min(_DECODE_KV_VMEM_BYTES // (4 * page_bytes),
                      _DECODE_STEP_POSITIONS // page_size, pages_per_seq))


def _flash_accumulate(q, k, v, valid, m_ref, l_ref, acc_ref,
                      k_scale=None, v_scale=None) -> None:
    """Online-softmax accumulation of one group of pages into the (m, l,
    acc) scratch — the body of EVERY decode walk (raw, int8-scaled,
    kv-split partial), kept in one place so masking/numerics fixes cannot
    diverge. ``k``/``v`` are the group as it lies in the pool, ``[columns,
    hd]`` with a column per (position, kv head); ``q`` is every query head
    ``[n_q, hd]`` in float32, already scaled; ``valid [n_q, columns]`` says
    which columns are a live position of the row's own kv head. All heads
    go through one product: a foreign head's column is masked like a dead
    position, so it adds an exact zero. Masked positions are explicitly
    zeroed in p (exp underflow handles them too, but the explicit mask
    keeps l exact by construction). ``k_scale``/``v_scale [1, columns]`` are
    an int8 pool's per-(token, head) absmax scales (ops/attention.py
    quantize_kv): q·(k·s) = (q·k)·s and p·(v·s) = (p·s)·v, so they scale
    the scores and the probabilities, not the pages."""
    s = jax.lax.dot_general(
        q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # [n_q, columns]
    if k_scale is not None:
        s = s * k_scale
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, :1]  # [n_q, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[:, :1] = m_new
    if v_scale is not None:
        p = p * v_scale
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [n_q, hd]


def _decode_walk_kernel(*refs, page_size: int, n_kv: int, group: int,
                        pages_per_step: int, sm_scale: float, scaled: bool,
                        pages_local: int | None):
    """One grid step = one row: walk its live pages, ``pages_per_step`` at a
    time. What a page is and what the row writes at the end are the two
    things the pools differ in:

    - ``scaled``: int8 pages, with ``[page_size, n_kv]`` float32 scales
      (kv heads along lanes, padded to the lane width) fetched beside each
      page;
    - ``pages_local`` (kv-split): the sources are this device's page SLICE
      of that many pages, the table holds GLOBAL ids, pages owned by other
      shards are neither fetched nor counted, and the row writes the flash
      partials ``(acc, m, l)`` for the cross-shard merge
      (``parallel/kv_split.py``) instead of the normalised output.
    """
    partial = pages_local is not None
    n_src = 4 if scaled else 2
    it = iter(refs)
    # scalar prefetch (SMEM): page table [B, P], context lengths [B], and
    # the kv-split shard index [1]
    tables_ref, ctx_ref = next(it), next(it)
    shard = next(it)[0] if partial else None
    q_ref = next(it)  # [1, n_q, hd]
    srcs = [next(it) for _ in range(n_src)]  # k, v[, k scales, v scales]
    outs = [next(it) for _ in range(3 if partial else 1)]
    bufs = [next(it) for _ in range(n_src)]  # [2, pages_per_step, ...]
    sems = next(it)  # DMA [2, n_src]
    m_ref, l_ref, acc_ref = it  # [n_q, 128], [n_q, 128], [n_q, hd] f32

    row = pl.program_id(0)
    ctx = ctx_ref[row]
    n_q, hd = q_ref.shape[1:]
    rows_per_page = page_size * n_kv
    columns = pages_per_step * rows_per_page
    span = pages_per_step * page_size
    last_col = tables_ref.shape[1] - 1

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def page(step, i):
        """Table column ``step * pages_per_step + i`` of this row: whether
        the walk reads it, and its index in the sources."""
        col = step * pages_per_step + i
        live = col * page_size < ctx
        pid = tables_ref[row, jnp.minimum(col, last_col)]
        if partial:
            live = live & (pid // pages_local == shard)
            pid = pid - shard * pages_local
        return live, pid

    def copies(step, slot, wait: bool) -> None:
        """Start, or wait for, the copies of one group into ``slot``."""
        def one(i, _):
            live, pid = page(step, i)

            @pl.when(live)
            def _():
                for s, (src, buf) in enumerate(zip(srcs, bufs)):
                    copy = pltpu.make_async_copy(
                        src.at[pid], buf.at[slot, i], sems.at[slot, s])
                    copy.wait() if wait else copy.start()

            if wait:
                # A page the walk does not read is masked out of p, and
                # 0 x what its buffer held must be 0: never-written VMEM
                # or another row's page may hold a NaN. Zero the V side.
                @pl.when(jnp.logical_not(live))
                def _():
                    for buf in bufs[1::2]:
                        buf[slot, i] = jnp.zeros(buf.shape[2:], buf.dtype)

        jax.lax.fori_loop(0, pages_per_step, one, None)

    # A column of a group is (position, kv head), as the pool lays a page
    # out; query head r reads kv head r // group (kv-major head order, the
    # grouping the model's reshape uses — no permutation needed).
    col = jax.lax.broadcasted_iota(jnp.int32, (1, columns), 1)
    col_pos, col_head = jax.lax.div(col, n_kv), jax.lax.rem(col, n_kv)
    q_row = jax.lax.broadcasted_iota(jnp.int32, (n_q, 1), 0)
    row_head = sum((q_row >= h * group).astype(jnp.int32)
                   for h in range(1, n_kv))
    own_head = col_head == row_head  # [n_q, columns]
    q = q_ref[0].astype(jnp.float32) * sm_scale

    if scaled:
        # Which scale lane (kv head) a column reads, and which position.
        pick = (jax.lax.broadcasted_iota(
            jnp.int32, (bufs[2].shape[-1], columns), 0) == col_head
        ).astype(jnp.float32)
        own_pos = jax.lax.broadcasted_iota(
            jnp.int32, (span, 1), 0) == col_pos  # [span, columns]

    def scale_row(buf, slot):
        """A group's scales ``[pages, page_size, lanes]`` as the row ``[1,
        columns]`` its scores carry them in. The lanes-to-columns move is
        an exact product: x[t, c] = scales[t, head of c] (one term, times
        1), of which column c keeps the entry of its own position."""
        x = jax.lax.dot_general(
            buf[slot].reshape(span, buf.shape[-1]), pick,
            (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)  # [span, columns]
        return jnp.sum(jnp.where(own_pos, x, 0.0), axis=0, keepdims=True)

    n_steps = pl.cdiv(ctx, span)

    @pl.when(n_steps > 0)
    def _first():
        copies(0, 0, wait=False)

    def step(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_steps)
        def _next():
            copies(i + 1, 1 - slot, wait=False)

        copies(i, slot, wait=True)
        valid = own_head & (i * span + col_pos < ctx)
        if partial:
            col_page = jax.lax.div(col, rows_per_page)
            mine = jax.lax.fori_loop(
                0, pages_per_step,
                lambda j, mine: jnp.where(
                    col_page == j, page(i, j)[0].astype(jnp.int32), mine),
                jnp.zeros_like(col))
            valid = valid & (mine > 0)
        _flash_accumulate(
            q, bufs[0][slot].reshape(columns, hd),
            bufs[1][slot].reshape(columns, hd), valid, m_ref, l_ref, acc_ref,
            k_scale=scale_row(bufs[2], slot) if scaled else None,
            v_scale=scale_row(bufs[3], slot) if scaled else None)

    jax.lax.fori_loop(0, n_steps, step, None)

    if partial:
        outs[0][0], outs[1][0], outs[2][0] = acc_ref[:], m_ref[:], l_ref[:]
    else:
        outs[0][0] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
                      ).astype(outs[0].dtype)


def _lane_pad(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` with its minor dimension zero-padded to a multiple of the 128
    lanes: Mosaic copies a page out of an ``ANY``-space operand only in
    whole lane tiles."""
    short = -x.shape[-1] % 128
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)]) if short else x


def _decode_walk(q, k_flat, v_flat, page_tables, ctx_lens, page_size: int,
                 interpret: bool, shard=None, pages_local: int | None = None):
    """Launch :func:`_decode_walk_kernel` over the rows of ``q``. The page
    table is the call's first operand and the result ``[rows, n_q, hd]``:
    the benchmark finds the kernel in a trace by those two shapes
    (``benchmark/kernels/paged_attention_decode.py``). A head narrower than
    the lanes (the test-size models) is zero-padded to them, which leaves
    every score and, once sliced, the output what they were."""
    b, n_q, hd = q.shape
    scaled = isinstance(k_flat, tuple)
    k_vals, k_scales = k_flat if scaled else (k_flat, None)
    v_vals, v_scales = v_flat if scaled else (v_flat, None)
    n_kv = k_vals.shape[1]
    q, k_vals, v_vals = _lane_pad(q), _lane_pad(k_vals), _lane_pad(v_vals)
    hd_lanes = q.shape[-1]
    g = decode_pages_per_step(page_size, n_kv, hd_lanes, k_vals.dtype,
                              page_tables.shape[1])
    # A page as one [positions x kv heads, hd] block: the same bytes as
    # [page_size, n_kv, hd], and every kv head's keys in one operand.
    page = (page_size * n_kv, hd_lanes)
    srcs = [a.reshape(-1, *page) for a in (k_vals, v_vals)]
    bufs = [pltpu.VMEM((2, g, *page), k_vals.dtype)] * 2
    if scaled:
        srcs += [_lane_pad(a.reshape(-1, page_size, n_kv))
                 for a in (k_scales, v_scales)]
        bufs += [pltpu.VMEM((2, g, *srcs[-1].shape[1:]), k_scales.dtype)] * 2
    prefetch = [page_tables, ctx_lens]
    partial = pages_local is not None
    if partial:
        prefetch.append(shard.reshape(1))

    def row_block(width):
        return pl.BlockSpec((1, n_q, width), lambda r, *_: (r, 0, 0))

    out_kinds = ([(hd_lanes, jnp.float32), (128, jnp.float32),
                  (128, jnp.float32)] if partial else [(hd_lanes, q.dtype)])
    outs = pl.pallas_call(
        functools.partial(
            _decode_walk_kernel, page_size=page_size, n_kv=n_kv,
            group=n_q // n_kv, pages_per_step=g, sm_scale=hd ** -0.5,
            scaled=scaled, pages_local=pages_local),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=[row_block(hd_lanes)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(srcs),
            out_specs=[row_block(w) for w, _ in out_kinds],
            scratch_shapes=bufs + [
                pltpu.SemaphoreType.DMA((2, len(srcs))),
                pltpu.VMEM((n_q, 128), jnp.float32),  # m
                pltpu.VMEM((n_q, 128), jnp.float32),  # l
                pltpu.VMEM((n_q, hd_lanes), jnp.float32),  # acc
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, n_q, w), dt)
                   for w, dt in out_kinds],
        interpret=interpret,
    )(*prefetch, q, *srcs)
    out, *stats = outs
    return (out[..., :hd], *stats) if partial else out[..., :hd]


def paged_decode_attention(
    q: jnp.ndarray,  # [B, n_q, hd]
    k_flat,  # [num_pages * page_size, n_kv, hd], or (int8 values, scales)
    v_flat,  # same
    page_tables: jnp.ndarray,  # [B, P] int32 (physical page ids; 0 = null)
    ctx_lens: jnp.ndarray,  # [B] int32
    page_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ragged paged attention for decode (one query token per sequence).
    An int8 pool is ``(values [tokens, n_kv, hd], f32 scales [tokens,
    n_kv])``: HBM still moves 1 byte a value, widened in VMEM."""
    return _decode_walk(q, k_flat, v_flat, page_tables, ctx_lens, page_size,
                        interpret)


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
    """Flash partials over a LOCAL page slice (the kv-split walk of
    :func:`_decode_walk_kernel`); returns (acc, m, l), the shard_map
    wrapper's inputs (``parallel/kv_split.py`` merges across the ``seq``
    axis and normalizes)."""
    acc, m, l = _decode_walk(q, k_local, v_local, page_tables, ctx_lens,
                             page_size, interpret, shard=my_pg,
                             pages_local=pages_local)
    return acc, m[..., 0], l[..., 0]  # m/l are lane-padded: column 0


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
