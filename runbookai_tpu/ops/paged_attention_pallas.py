"""Pallas TPU kernels: ragged paged attention over the engine's KV pool.

SURVEY.md §7 names this "the single riskiest piece of device code": the XLA
fallback (:mod:`runbookai_tpu.ops.attention`) re-gathers KV through the page
table every step; these kernels read the **scalar-prefetched** page table
and fetch exactly the pages a sequence owns into VMEM, flash-accumulating
(m, l, acc) in float32 scratch (PAPERS.md "Ragged Paged Attention").

Two entry points, ONE page walk (:func:`_walk_pages`). **The walk is inside
the kernel**: a grid step loops over ITS row's page table, several pages a
step, so an empty slot costs one grid step and a row of 400 tokens the
work of 400 tokens, whatever the table's width. The pool stays where XLA
put it (``memory_space=pl.ANY``); a step issues one async copy a LIVE page
into a double-buffered VMEM group, the next group's copies in flight while
this one is accumulated (:func:`_flash_accumulate`, the one body). Pages a
step comes from the page's bytes against a fixed VMEM budget
(:func:`decode_pages_per_step`).

**The pool is the one the layer scan carries**, ``[L, tokens, n_kv, hd]``,
and the layer's number one more scalar-prefetch operand: a page's index is
``layer x pages + id`` in the pool's page view, its two leading axes
merged (a bitcast). No layer slice is staged in front of a call: in the
7B cell that was 2 x 50 MB a layer, five times the kernel it fed. A
one-layer pool ``[tokens, n_kv, hd]`` is the same kernel at L = 1, layer
0. Which of the two a forward hands over is :func:`reads_in_place`.

- :func:`paged_decode_attention` — decode-shaped (T = 1), grid = (rows,):
  a row walks ``cdiv(ctx_lens[row], pages a step x page_size)`` steps. All
  kv heads of a group go through one product (a foreign head's column is
  masked like a dead position). One kernel serves every pool: raw pages,
  int8 pages with scales, and a page-split shard that skips the pages it
  does not own and returns partials. A raw pool whose heads are wider than
  the lanes takes the chunk walk at one query a block instead
  (:func:`_walks_by_head`: its page keeps the kv-head axis).
- :func:`paged_chunk_attention` — T > 1 (chunked prefill, the speculative
  verify forward, and through :func:`paged_ragged_attention` the mixed
  step's flat buffer), grid = (rows, query blocks): a block of TQ queries
  walks ``cdiv(min(ctx, q0 + TQ), pages a step x page_size)`` steps — the
  causal bound ends the walk as well as the context, so a prompt's first
  block walks one step and a pad block (``ctx == 0``) none. One product a
  KV HEAD over that head's keys alone (TQ x group rows); the mask is per
  query row (``pos < ctx`` and ``pos <= q0 + t``: positions contiguous, so
  they derive from the scalar-prefetched start). TQ bounds the VMEM
  scratch (:func:`chunk_q_block`).

Who calls them, at which head shapes (query heads x kv heads x head size):
the dense families (``models/llama.py``: Qwen2.5-7B 28 x 4 x 128, Llama-3-8B
32 x 8 x 128, a tp shard's lone kv head), Trinity-Mini's full and sliding
layers (``models/afmoe.py``: 32 x 4 x 128, the window a lower edge of the
walk), and the ONE-TOKEN rows of the two recurrent families' softmax layers
through :func:`paged_layer_attention`: Qwen3-Next (``models/qwen3_next.py``:
16 x 2 x 256, a decode row by kv head) and Nemotron-3-Nano
(``models/nemotron_h.py``: 32 x 2 x 128); their prefill runs keep XLA's
one-row walk. The latent (MLA) families have their own attention over their
own pool (``ops/mla.py``).

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

# VMEM a walk may spend on page buffers (K and V, two groups each), and
# the most cache positions one step takes: past that a step's scores
# outgrow the vector registers and a row's last, part-filled group wastes
# more than a longer step saves in loop trips.
_DECODE_KV_VMEM_BYTES = 1 << 20
_DECODE_STEP_POSITIONS = 512

# A stacked pool that FITS on-chip memory is not left where it lies: in the
# layer scan's body XLA prefetches all L layers of such an operand there
# before every call (``ops/qmm_pallas.py`` ``_ON_CHIP_BYTES``, PR 30), L
# times the slice it would replace. So only a pool that cannot fit there
# is read in place; of any other the kernels get the layer's slice.
_ON_CHIP_BYTES = 128 * 1024 * 1024


def decode_pages_per_step(page_size: int, n_kv: int, hd: int, kv_dtype,
                          pages_per_seq: int) -> int:
    """Pages one step of a walk (the decode kernel's and the chunk
    kernel's) fetches and accumulates: what the VMEM budget holds of this
    pool's pages, at most ``_DECODE_STEP_POSITIONS`` positions and never
    more than a row's table has columns. The chunk walk's score block
    (hundreds of query rows a step) does not enter: cutting the step to it
    was measured twice as slow at 896 rows (PERF.md section 6, PR 36)."""
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


def _flash_init(m_ref, l_ref, acc_ref) -> None:
    """The scratch of a row (a query block) that has accumulated nothing."""
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _flash_output(l_ref, acc_ref):
    """The normalised output; a row that accumulated nothing reads zeros."""
    return acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)


def _walk_pages(n_steps, pages_per_step: int, page, place, srcs, bufs,
                sems, accumulate, first=None) -> None:
    """The page walk of BOTH kernels: ``n_steps`` groups of
    ``pages_per_step`` table columns, one async copy a LIVE page (and
    source) into the group's VMEM slot, the next group's copies in flight
    while ``accumulate(step, slot)`` works on this one. ``page(col)`` says
    whether the walk reads table column ``col`` and where the page lies in
    the sources; ``place(buf, slot, i)`` is the group's ``i``-th page in a
    buffer. A dead column is never fetched. ``first`` (a window's lower
    edge): the group the walk starts at, ``n_steps`` counted from it; None
    is group 0 and adds nothing to the program."""
    def group(step):
        return step if first is None else first + step

    def copies(step, slot, wait: bool) -> None:
        """Start, or wait for, the copies of one group into ``slot``."""
        def one(i, _):
            live, pid = page(group(step) * pages_per_step + i)

            @pl.when(live)
            def _():
                for s, (src, buf) in enumerate(zip(srcs, bufs)):
                    copy = pltpu.make_async_copy(
                        src.at[pid], place(buf, slot, i), sems.at[slot, s])
                    copy.wait() if wait else copy.start()

            if wait:
                # A page the walk does not read is masked out of p, and
                # 0 x what its buffer held must be 0: never-written VMEM
                # or another row's page may hold a NaN. Zero the V side.
                @pl.when(jnp.logical_not(live))
                def _():
                    for buf in bufs[1::2]:
                        dst = place(buf, slot, i)
                        dst[...] = jnp.zeros(dst.shape, dst.dtype)

        jax.lax.fori_loop(0, pages_per_step, one, None)

    @pl.when(n_steps > 0)
    def _first():
        copies(0, 0, wait=False)

    def step(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_steps)
        def _next():
            copies(i + 1, 1 - slot, wait=False)

        copies(i, slot, wait=True)
        accumulate(group(i), slot)

    jax.lax.fori_loop(0, n_steps, step, None)


def _decode_walk_kernel(*refs, page_size: int, n_kv: int, group: int,
                        pages_per_step: int, sm_scale: float, scaled: bool,
                        pages_local: int | None, layer_pages: int,
                        window: int | None = None):
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

    The sources hold every layer's pages, ``layer_pages`` a layer: the
    walk reads those of the scalar-prefetched layer.

    ``window``: the row's query, at ``ctx - 1``, sees the last ``window``
    positions only. The walk starts at the group that holds ``ctx -
    window``, the pages wholly behind it are dead columns (their table
    entries may be anything: the manager gave them back), and the mask
    drops the keys behind it in the edge's own page.
    """
    partial = pages_local is not None
    n_src = 4 if scaled else 2
    it = iter(refs)
    # scalar prefetch (SMEM): page table [B, P], context lengths [B], the
    # kv-split shard index [1], and the layer [1]
    tables_ref, ctx_ref = next(it), next(it)
    shard = next(it)[0] if partial else None
    first_page = next(it)[0] * layer_pages
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

    _flash_init(m_ref, l_ref, acc_ref)
    lo = None if window is None else jnp.maximum(ctx - window, 0)

    def page(col):
        """Table column ``col`` of this row: whether the walk reads it,
        and its index in the sources."""
        live = col * page_size < ctx
        if lo is not None:
            live = live & ((col + 1) * page_size > lo)
        pid = tables_ref[row, jnp.minimum(col, last_col)]
        if partial:
            live = live & (pid // pages_local == shard)
            pid = pid - shard * pages_local
        return live, first_page + pid

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

    def accumulate(i, slot):
        valid = own_head & (i * span + col_pos < ctx)
        if lo is not None:
            valid = valid & (i * span + col_pos >= lo)
        if partial:
            col_page = jax.lax.div(col, rows_per_page)
            mine = jax.lax.fori_loop(
                0, pages_per_step,
                lambda j, mine: jnp.where(
                    col_page == j,
                    page(i * pages_per_step + j)[0].astype(jnp.int32), mine),
                jnp.zeros_like(col))
            valid = valid & (mine > 0)
        _flash_accumulate(
            q, bufs[0][slot].reshape(columns, hd),
            bufs[1][slot].reshape(columns, hd), valid, m_ref, l_ref, acc_ref,
            k_scale=scale_row(bufs[2], slot) if scaled else None,
            v_scale=scale_row(bufs[3], slot) if scaled else None)

    first, n_steps = None, pl.cdiv(ctx, span)
    if lo is not None:
        first = lo // span
        n_steps = n_steps - first
    _walk_pages(n_steps, pages_per_step, page,
                lambda buf, slot, i: buf.at[slot, i], srcs, bufs, sems,
                accumulate, first)

    if partial:
        outs[0][0], outs[1][0], outs[2][0] = acc_ref[:], m_ref[:], l_ref[:]
    else:
        outs[0][0] = _flash_output(l_ref, acc_ref).astype(outs[0].dtype)


def _lane_pad(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` with its minor dimension zero-padded to a multiple of the 128
    lanes: Mosaic copies a page out of an ``ANY``-space operand only in
    whole lane tiles."""
    short = -x.shape[-1] % 128
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)]) if short else x


def _kv_tile(n_kv: int, kv_dtype) -> int:
    """The rows Mosaic slices a page's kv-head axis in: whole sublane
    tiles, the next power of two between a 32-bit word's rows and eight
    words'."""
    packing = 4 // jnp.dtype(kv_dtype).itemsize
    return min(8 * packing, max(packing, pl.next_power_of_2(n_kv)))


def _chunk_pages(pool: jnp.ndarray, page_size: int) -> jnp.ndarray:
    """``pool [(L,) tokens, n_kv, hd]`` as the pages the chunk walk
    copies, ``[(L x) pages, page_size, n_kv, hd]``: the bytes as they lie,
    for the serving shapes (2, 4 or 8 kv heads of 128 or 256 in bf16, 4 or
    8 in fp8). Mosaic
    slices such an operand only in whole sublane tiles (:func:`_kv_tile`),
    so any other head count is zero-padded to them, and a lone head (a tp
    shard's) gives up its axis, ``[pages, page_size, hd]``: XLA lays that
    pool out without one, and padding it would copy the pool a layer."""
    pool = _lane_pad(pool)
    n_kv, hd = pool.shape[-2:]
    if n_kv == 1:
        return pool.reshape(-1, page_size, hd)
    short = -n_kv % _kv_tile(n_kv, pool.dtype)
    pool = jnp.pad(pool, [(0, 0)] * (pool.ndim - 2) + [(0, short), (0, 0)])
    return pool.reshape(-1, page_size, *pool.shape[-2:])


def heads_on_tile(n_kv: int, kv_dtype) -> bool:
    """Whether a pool's kv-head axis is whole sublane tiles
    (:func:`_kv_tile`), or absent: the chunk walk slices a page of such a
    pool as it lies. Any other count (two fp8 heads, three bf16 ones) it
    pads in XLA first, a copy of what it is handed. A family with an XLA
    walk of its own sends such a pool there instead."""
    return n_kv == 1 or n_kv % _kv_tile(n_kv, kv_dtype) == 0


def reads_in_place(pool, mesh=None) -> bool:
    """Whether a forward hands the kernels the pool its layer scan
    carries, ``[L, tokens, n_kv, hd]``, and the layer's number (True) or
    the layer's slice (False): static, by shape alone, as
    ``qmm_pallas.reads_in_place``. In place where the pool (a device's
    shard of it under ``mesh``) cannot fit on-chip memory and its page
    views are the bytes as they lie: what the wrappers must pad in XLA —
    a head narrower than the lanes, an int8 pool's scales, a head count
    off Mosaic's tile — would be padded for EVERY layer before each call,
    a copy of the whole pool where the slice copies one layer."""
    if isinstance(pool, tuple):
        return False
    n_layers, tokens, n_kv, hd = pool.shape
    if mesh is not None:
        from runbookai_tpu.parallel.mesh import SEQ_AXIS

        tokens //= mesh.shape.get(SEQ_AXIS, 1)
        if tp_shardable(mesh, n_kv):
            n_kv //= _model_tp(mesh)
    lies_as_pages = hd % 128 == 0 and heads_on_tile(n_kv, pool.dtype)
    return lies_as_pages and (n_layers * tokens * n_kv * hd
                              * pool.dtype.itemsize >= _ON_CHIP_BYTES)


def _stacked(pools, layer):
    """``(pools [L, tokens, ...], the layer's number as the kernels'
    scalar-prefetch operand i32[1])``: one layer's ``[tokens, ...]``, given
    with no ``layer``, is L = 1 and layer 0."""
    if layer is None:
        return (jax.tree.map(lambda a: a[None], pools),
                jnp.zeros((1,), jnp.int32))
    return pools, jnp.asarray(layer, jnp.int32).reshape(1)


def _decode_walk(q, k_flat, v_flat, page_tables, ctx_lens, page_size: int,
                 interpret: bool, shard=None, pages_local: int | None = None,
                 layer=None, window: int | None = None,
                 name: str | None = None):
    """Launch :func:`_decode_walk_kernel` over the rows of ``q``. The page
    table is the call's first operand and the result ``[rows, n_q, hd]``:
    the benchmark finds the kernel in a trace by those two shapes
    (``benchmark/kernels/paged_attention_decode.py``). A head narrower than
    the lanes (the test-size models) is zero-padded to them, which leaves
    every score and, once sliced, the output what they were. With a
    ``layer`` the pools are ``[L, tokens, ...]`` and the sources their
    page views with L merged in front. ``name``: the call's in a device
    trace (None: the compiler's ``closed_call``)."""
    b, n_q, hd = q.shape
    (k_flat, v_flat), layer = _stacked((k_flat, v_flat), layer)
    scaled = isinstance(k_flat, tuple)
    k_vals, k_scales = k_flat if scaled else (k_flat, None)
    v_vals, v_scales = v_flat if scaled else (v_flat, None)
    n_layers, _, n_kv, _ = k_vals.shape
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
    prefetch.append(layer)

    def row_block(width):
        return pl.BlockSpec((1, n_q, width), lambda r, *_: (r, 0, 0))

    out_kinds = ([(hd_lanes, jnp.float32), (128, jnp.float32),
                  (128, jnp.float32)] if partial else [(hd_lanes, q.dtype)])
    outs = pl.pallas_call(
        functools.partial(
            _decode_walk_kernel, page_size=page_size, n_kv=n_kv,
            group=n_q // n_kv, pages_per_step=g, sm_scale=hd ** -0.5,
            scaled=scaled, pages_local=pages_local,
            layer_pages=srcs[0].shape[0] // n_layers, window=window),
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
        name=name,
    )(*prefetch, q, *srcs)
    out, *stats = outs
    return (out[..., :hd], *stats) if partial else out[..., :hd]


def _walks_by_head(k_flat) -> bool:
    """Whether a decode row walks its pages one KV HEAD at a time: a raw
    pool of several heads each wider than the 128 lanes (Qwen3-Next: 2 of
    256). The decode walk's page ``[page_size x n_kv, hd]`` puts every
    head's keys in the rows of one product, and that view is the bytes as
    they lie only while a head is ONE lane tile: the device tiles the pool's
    two minor axes together, so at 256 a token's second half of head 0 lies
    behind head 1's first, and the merged view of a ``[tokens, 2, 256]``
    pool was a copy of the whole pool a call (805 MB; PERF.md section 6, PR
    31). The chunk walk's page ``[page_size, n_kv, hd]`` keeps both axes:
    such a row is a block of one query of it. Static, by shape."""
    return (not isinstance(k_flat, tuple) and k_flat.shape[-2] > 1
            and k_flat.shape[-1] > 128)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, n_q, hd]
    k_flat,  # [num_pages * page_size, n_kv, hd], or (int8 values, scales)
    v_flat,  # same
    page_tables: jnp.ndarray,  # [B, P] int32 (physical page ids; 0 = null)
    ctx_lens: jnp.ndarray,  # [B] int32
    page_size: int,
    interpret: bool = False,
    layer=None,  # with it the pools are [L, tokens, ...]: read layer `layer`
    window: int | None = None,  # the row sees its last `window` positions
    name: str | None = None,  # the call's name in a device trace
) -> jnp.ndarray:
    """Ragged paged attention for decode (one query token per sequence).
    An int8 pool is ``(values [tokens, n_kv, hd], f32 scales [tokens,
    n_kv])``: HBM still moves 1 byte a value, widened in VMEM. A pool whose
    head is wider than the lanes is walked by kv head
    (:func:`_walks_by_head`): the chunk walk, one query a block where the
    head's ``group`` query rows fill whole sublane tiles. A call with a
    ``window`` and no ``name`` is named for it (``swa_decode_walk``)."""
    if window is not None and name is None:
        name = "swa_decode_walk"
    if _walks_by_head(k_flat):
        group = q.shape[1] // k_flat.shape[-2]
        return paged_chunk_attention(
            q[:, None], k_flat, v_flat, page_tables, ctx_lens,
            (ctx_lens - 1)[:, None], page_size=page_size,
            interpret=interpret, q_block=None if group % 8 else 1,
            layer=layer, window=window, name=name)[:, 0]
    return _decode_walk(q, k_flat, v_flat, page_tables, ctx_lens, page_size,
                        interpret, layer=layer, window=window, name=name)


def _chunk_walk_kernel(tables_ref, ctx_ref, q_start_ref, layer_ref, q_ref,
                       k_src, v_src, o_ref, k_buf, v_buf, sems, q_scr, m_ref,
                       l_ref, acc_ref, *, page_size: int, n_kv: int,
                       group: int, tq: int, pages_per_step: int,
                       sm_scale: float, layer_pages: int,
                       window: int | None = None):
    """One grid step = one block of ``tq`` queries of one row: walk the
    pages it can see, ``pages_per_step`` at a time. The causal bound ends
    the walk as well as the context does, so a prompt's first block walks
    one step and a pad block (``ctx == 0``) none: it writes zeros. The
    sources hold every layer's pages, ``layer_pages`` a layer: the walk
    reads those of the scalar-prefetched layer.

    The scratch rows are kv-major: rows ``[h * tq * group, (h + 1) * tq *
    group)`` are kv head ``h``'s queries, token-major inside the head, and
    each head's block goes through :func:`_flash_accumulate` against ITS
    keys ``[positions, hd]`` alone — at ``tq * n_q`` score rows the
    decode walk's one product over every head would spend ``n_kv - 1``
    parts in ``n_kv`` of its operations on masked columns.

    ``window``: a query at ``p`` sees the keys ``p - window < j <= p``. The
    walk starts at the group that holds the block's FIRST query's edge,
    the pages wholly behind that edge are dead columns, and the mask drops
    the keys behind each query's own."""
    row, qb = pl.program_id(0), pl.program_id(1)
    ctx = ctx_ref[row]
    # Query positions are contiguous per row (the wrapper's contract), so
    # they derive from the scalar start: no vector SMEM reads.
    q0 = q_start_ref[row] + qb * tq
    seen = jnp.minimum(ctx, q0 + tq)  # positions some query of the block sees
    hd = q_ref.shape[-1]
    rows = tq * group
    span = pages_per_step * page_size
    last_col = tables_ref.shape[1] - 1

    _flash_init(m_ref, l_ref, acc_ref)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [tq, n_q, hd]
    for h in range(n_kv):
        q_scr[h * rows:(h + 1) * rows] = (
            q[:, h * group:(h + 1) * group].reshape(rows, hd))

    first_page = layer_ref[0] * layer_pages
    lo = None if window is None else jnp.maximum(q0 - window + 1, 0)

    def page(col):
        live = col * page_size < seen
        if lo is not None:
            live = live & ((col + 1) * page_size > lo)
        return live, first_page + tables_ref[row, jnp.minimum(col, last_col)]

    # The mask is per query ROW of a head's block, built from 2D iotas.
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
    q_pos = q0 + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), group)

    def accumulate(i, slot):
        at = i * span + pos
        valid = (at < ctx) & (at <= q_pos)  # [rows, span]
        if window is not None:
            valid = valid & (at > q_pos - window)
        for h in range(n_kv):
            mine = pl.ds(h * rows, rows)
            k, v = ((buf[slot] if n_kv == 1 else buf[slot, :, h, :])
                    for buf in (k_buf, v_buf))  # [span, hd]
            _flash_accumulate(q_scr[mine], k, v, valid, m_ref.at[mine],
                              l_ref.at[mine], acc_ref.at[mine])

    first, n_steps = None, pl.cdiv(seen, span)
    if lo is not None:
        first = lo // span
        n_steps = n_steps - first
    _walk_pages(
        n_steps, pages_per_step, page,
        lambda buf, slot, i: buf.at[slot, pl.ds(i * page_size, page_size)],
        [k_src, v_src], [k_buf, v_buf], sems, accumulate, first)

    out = _flash_output(l_ref, acc_ref)  # [tq * n_q, hd], kv-major
    # Per-head static slices back to [tq, group, hd] (no 4D transpose).
    for h in range(n_kv):
        o_ref[0, :, h * group:(h + 1) * group, :] = (
            out[h * rows:(h + 1) * rows].reshape(tq, group, hd)
            .astype(o_ref.dtype))


def chunk_q_block(t: int, n_q: int) -> int:
    """Query rows per grid step of :func:`paged_chunk_attention`: about
    1k accumulator rows (TQ * n_q) to bound VMEM scratch, and a multiple
    of 8 — every per-kv-head row block (queries, scores, m, l, acc) is TQ
    * group high and lies at a multiple of that along sublanes. With a
    GQA group of 7 an unaligned block (1024 // 28 = 36 rows, 252 per
    head) was refused: stacking such blocks died in ``tpu.bitcast_vreg``
    with "Invalid vector register cast" (TPU v5 lite, jax 0.9.0). A short
    chunk pads up to one 8-row block."""
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
    layer=None,  # with it the pools are [L, tokens, ...]: read layer `layer`
    window: int | None = None,  # a query sees its last `window` positions
    name: str | None = None,  # the call's name in a device trace
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
    (k_flat, v_flat), layer = _stacked((k_flat, v_flat), layer)
    n_layers, _, n_kv, _ = k_flat.shape
    tq = q_block if q_block is not None else chunk_q_block(t, n_q)
    t_pad = -(-t // tq) * tq
    if t_pad != t:
        # Padded rows act like later queries (q0 + t): they attend at most the
        # whole context and their outputs are sliced off on return.
        q = jnp.pad(q, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    q = _lane_pad(q)
    hd_lanes = q.shape[-1]
    pages = [_chunk_pages(a, page_size) for a in (k_flat, v_flat)]
    g = decode_pages_per_step(page_size, n_kv, hd_lanes, k_flat.dtype,
                              page_tables.shape[1])

    q_blocks = pl.BlockSpec((1, tq, n_q, hd_lanes),
                            lambda r, qb, *_: (r, qb, 0, 0))
    out = pl.pallas_call(
        functools.partial(
            _chunk_walk_kernel, page_size=page_size, n_kv=n_kv,
            group=n_q // n_kv, tq=tq, pages_per_step=g, sm_scale=hd ** -0.5,
            layer_pages=pages[0].shape[0] // n_layers, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, t_pad // tq),
            in_specs=[q_blocks] + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=q_blocks,
            scratch_shapes=[pltpu.VMEM((2, g * page_size, *pages[0].shape[2:]),
                                       k_flat.dtype)] * 2 + [
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((tq * n_q, hd_lanes), jnp.float32),  # q, scaled
                pltpu.VMEM((tq * n_q, 128), jnp.float32),  # m
                pltpu.VMEM((tq * n_q, 128), jnp.float32),  # l
                pltpu.VMEM((tq * n_q, hd_lanes), jnp.float32),  # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=name or (None if window is None else "swa_chunk_walk"),
    )(page_tables, ctx_lens, q_positions[:, 0].astype(jnp.int32), layer, q,
      *pages)
    return out[:, :t, :, :hd]


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
    layer=None,  # with it the pools are [L, tokens, ...]: read layer `layer`
    window: int | None = None,
) -> jnp.ndarray:
    """Ragged paged attention over a FLAT mixed prefill+decode batch.

    The per-row-ragged extension of :func:`paged_chunk_attention` for the
    unified mixed dispatch (PAPERS.md "Ragged Paged Attention"): decode
    rows feed 1 token, prefill rows a chunk, flattened into one [N]
    buffer. Layout contract (the engine's mixed builder upholds it): each
    row's token run is contiguous ascending and starts at a multiple of
    ``ragged_block``, so every ``ragged_block``-sized q block belongs to
    exactly one row — the flat batch maps onto the chunk kernel's
    (rows, query blocks) grid as one block a row, each with its owning
    row's page table and context gathered for it. A block walks the pages
    it can see, and K/V pages are fetched once per ``ragged_block`` queries
    rather than once per token (the reason this beats running the decode
    kernel at B = N). Per-row raggedness is carried by the per-block
    ``ctx_lens`` / ``q_start`` scalars: a pad block (null row, ``ctx_len =
    0``) walks nothing and writes zeros; pad tokens inside a real row's
    last block act as later queries whose outputs the caller discards
    (their K/V writes go to the null page via trash positions).

    Returns [N, n_q, hd].
    """
    n, n_q, hd = q.shape
    rq = ragged_block
    nb = n // rq
    rows = row_ids.reshape(nb, rq)[:, 0]
    return paged_chunk_attention(
        q.reshape(nb, rq, n_q, hd), k_flat, v_flat,
        page_tables[rows], ctx_lens[rows], q_positions.reshape(nb, rq),
        page_size=page_size, interpret=interpret, q_block=rq, layer=layer,
        window=window,
    ).reshape(n, n_q, hd)


def paged_layer_attention(
    q: jnp.ndarray,  # [B, T, n_q, hd]
    pool_k: jnp.ndarray,  # [L, tokens, n_kv, hd], as the layer scan carries it
    pool_v: jnp.ndarray,
    layer,  # which of the L
    page_tables: jnp.ndarray,  # [B, P] int32
    ctx_lens: jnp.ndarray,  # [B] int32, the chunk included
    q_positions: jnp.ndarray,  # [B, T] int32, contiguous a row
    page_size: int,
    window: int | None = None,
    name: str | None = None,  # the call's name in a device trace
) -> jnp.ndarray:
    """``q`` over layer ``layer`` of a raw stacked pool, as a forward's
    layer scan calls the walks: one token a row is the decode walk (every
    slot a grid step; a free one, ``ctx == 0``, fetches nothing), more the
    chunk walk. The kernels get the carried pool and the layer's number
    where they read it in place (:func:`reads_in_place`, static, by the
    pool's shape), else the layer's slice: the same kernel at L = 1."""
    if reads_in_place(pool_k):
        k_walk, v_walk = pool_k, pool_v
    else:
        k_walk, v_walk = (jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)
                          for a in (pool_k, pool_v))
        layer = None
    interpret = jax.default_backend() == "cpu"
    if q.shape[1] == 1:
        return paged_decode_attention(
            q[:, 0], k_walk, v_walk, page_tables, ctx_lens,
            page_size=page_size, interpret=interpret, layer=layer,
            window=window, name=name)[:, None]
    return paged_chunk_attention(
        q, k_walk, v_walk, page_tables, ctx_lens, q_positions,
        page_size=page_size, interpret=interpret, layer=layer, window=window,
        name=name)


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
    layer=None,  # with it the slices are [L, tokens, ...]: read layer `layer`
):
    """Flash partials over a LOCAL page slice (the kv-split walk of
    :func:`_decode_walk_kernel`); returns (acc, m, l), the shard_map
    wrapper's inputs (``parallel/kv_split.py`` merges across the ``seq``
    axis and normalizes)."""
    acc, m, l = _decode_walk(q, k_local, v_local, page_tables, ctx_lens,
                             page_size, interpret, shard=my_pg,
                             pages_local=pages_local, layer=layer)
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
    interpret: bool = False, layer=None,
) -> jnp.ndarray:
    """:func:`paged_decode_attention` over a TP mesh (heads sharded)."""
    from jax.sharding import PartitionSpec as P

    from runbookai_tpu.parallel.mesh import MODEL_AXIS

    (k_flat, v_flat), layer = _stacked((k_flat, v_flat), layer)
    heads = P(None, MODEL_AXIS, None)
    kv_heads = P(None, None, MODEL_AXIS, None)

    def fn(q, k, v, tables, ctx, layer):
        return paged_decode_attention(
            q, k, v, tables, ctx, page_size=page_size, interpret=interpret,
            layer=layer)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(heads, kv_heads, kv_heads, P(None, None), P(None),
                  P(None)),
        out_specs=heads,
        # pallas_call out_shapes carry no varying-mesh-axes info; the wrap
        # itself is collective-free so the vma check adds nothing here.
        check_vma=False,
    )(q, k_flat, v_flat, page_tables, ctx_lens, layer)


def paged_chunk_attention_tp(
    mesh, q, k_flat, v_flat, page_tables, ctx_lens, q_positions,
    page_size: int, interpret: bool = False, layer=None,
) -> jnp.ndarray:
    """:func:`paged_chunk_attention` over a TP mesh (heads sharded)."""
    from jax.sharding import PartitionSpec as P

    from runbookai_tpu.parallel.mesh import MODEL_AXIS

    (k_flat, v_flat), layer = _stacked((k_flat, v_flat), layer)
    q_heads = kv_heads = P(None, None, MODEL_AXIS, None)

    def fn(q, k, v, tables, ctx, q_positions, layer):
        return paged_chunk_attention(
            q, k, v, tables, ctx, q_positions, page_size=page_size,
            interpret=interpret, layer=layer)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(q_heads, kv_heads, kv_heads, P(None, None), P(None),
                  P(None, None), P(None)),
        out_specs=q_heads,
        check_vma=False,
    )(q, k_flat, v_flat, page_tables, ctx_lens, q_positions, layer)
