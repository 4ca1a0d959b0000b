"""Pallas ragged paged decode attention vs the XLA fallback (interpret mode)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.ops.attention import paged_attention, write_kv_pages
from runbookai_tpu.ops.paged_attention_pallas import paged_decode_attention


@pytest.mark.parametrize("ctx_lens_list", [[9, 5], [16, 1], [3, 30]])
def test_pallas_decode_matches_xla(ctx_lens_list):
    rng = np.random.default_rng(0)
    b, n_q, n_kv, hd, ps, pages = 2, 8, 2, 32, 4, 16
    max_pages = 8
    group = n_q // n_kv

    kf = jnp.zeros((pages * ps, n_kv, hd), jnp.float32)
    vf = jnp.zeros((pages * ps, n_kv, hd), jnp.float32)
    tables = np.zeros((b, max_pages), np.int32)
    next_page = 1
    for i, ctx in enumerate(ctx_lens_list):
        need = (ctx + ps - 1) // ps
        tables[i, :need] = np.arange(next_page, next_page + need)
        next_page += need
        k_seq = jnp.asarray(rng.normal(size=(ctx, n_kv, hd)), jnp.float32)
        v_seq = jnp.asarray(rng.normal(size=(ctx, n_kv, hd)), jnp.float32)
        pos = jnp.arange(ctx)
        kf = write_kv_pages(kf, k_seq, pos, jnp.asarray(tables[i]), ps)
        vf = write_kv_pages(vf, v_seq, pos, jnp.asarray(tables[i]), ps)

    q = jnp.asarray(rng.normal(size=(b, 1, n_q, hd)), jnp.float32)
    ctx_arr = jnp.asarray(ctx_lens_list, jnp.int32)
    q_positions = (ctx_arr - 1)[:, None]

    ref = paged_attention(q, kf, vf, jnp.asarray(tables), ctx_arr, q_positions,
                          page_size=ps, block_pages=2)[:, 0]
    out = paged_decode_attention(q[:, 0], kf, vf, jnp.asarray(tables), ctx_arr,
                                 page_size=ps, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pallas_decode_null_pages_are_masked():
    """Table rows full of null page 0 beyond ctx must not contaminate."""
    rng = np.random.default_rng(1)
    b, n_q, n_kv, hd, ps = 1, 4, 2, 32, 4
    kf = jnp.asarray(rng.normal(size=(8 * ps, n_kv, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(8 * ps, n_kv, hd)), jnp.float32)
    # ctx=2: only first 2 positions of page 3 are valid
    tables = jnp.asarray([[3, 0, 0, 0]], jnp.int32)
    ctx = jnp.asarray([2], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, n_q, hd)), jnp.float32)

    out = paged_decode_attention(q, kf, vf, tables, ctx, page_size=ps,
                                 interpret=True)
    # manual reference over the 2 valid positions
    group = n_q // n_kv
    k_valid = kf[3 * ps : 3 * ps + 2]  # [2, n_kv, hd]
    v_valid = vf[3 * ps : 3 * ps + 2]
    qg = q.reshape(b, n_kv, group, hd)
    s = jnp.einsum("bkgd,skd->bkgs", qg, k_valid) / np.sqrt(hd)
    attn = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bkgs,skd->bkgd", attn, v_valid).reshape(b, n_q, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------ the page walk
#
# The decode kernel walks each row's live pages inside the kernel, several a
# step (``decode_pages_per_step``: a value of the shapes), so the cases are
# laid against the step: a context of exactly one step, one more, the whole
# table. Every pool goes through the same cases: raw pages, int8 pages with
# scales, and a two-shard page split whose partials are merged here as
# ``parallel/kv_split.py`` merges them across the mesh.

WALK_PS = 8


def _poison_pool(rng, live, width, n_kv, hd, num_pages):
    """A float32 pool of ``num_pages`` in which every page but the ones
    laid here is poison (NaN), and the rows' tables ``[rows, width]``:
    row ``i`` owns ``live[i]`` pages, in its first columns, and every
    other column points at a poison page."""
    k = np.full((num_pages * WALK_PS, n_kv, hd), np.nan, np.float32)
    v = np.full_like(k, np.nan)
    order = rng.permutation(np.arange(1, num_pages))  # page 0 is null
    tables = np.full((len(live), width), order[-1], np.int32)  # poison
    nxt = 0
    for i, n in enumerate(live):
        for col in range(n):
            page = order[nxt]
            nxt += 1
            tables[i, col] = page
            rows = slice(page * WALK_PS, (page + 1) * WALK_PS)
            k[rows] = rng.normal(size=(WALK_PS, n_kv, hd))
            v[rows] = rng.normal(size=(WALK_PS, n_kv, hd))
    assert nxt < num_pages - 1  # the poison page stays poison
    return jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables)


def _walk_case(ctx_lens, n_kv, group, seed=0, hd=128):
    """Rows of ``ctx_lens`` tokens over a pool in which every page a row
    does NOT own is poison (NaN; an int8 pool's scales), and every table
    column past a row's live pages points at one: fetching a dead column,
    even to mask it, turns the output into NaN."""
    from runbookai_tpu.ops.paged_attention_pallas import decode_pages_per_step

    rng = np.random.default_rng(seed)
    g = decode_pages_per_step(WALK_PS, n_kv, hd, jnp.float32, 10**6)
    width = 2 * g + 1  # two steps and a page: no multiple of the step
    live = [-(-c // WALK_PS) for c in ctx_lens]
    num_pages = 2 * (1 + sum(live) // 2 + 1)  # even: two shards of pages
    k, v, tables = _poison_pool(rng, live, width, n_kv, hd, num_pages)
    q = jnp.asarray(rng.normal(size=(len(ctx_lens), n_kv * group, hd)),
                    jnp.float32)
    return q, k, v, tables, jnp.asarray(ctx_lens, jnp.int32), g, width


def _walk(pool, q, k, v, tables, ctx, interpret=True):
    """(kernel output, XLA reference) for one kind of pool."""
    from runbookai_tpu.ops.attention import quantize_kv
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_partial,
    )

    qpos = jnp.maximum(ctx - 1, 0)[:, None]
    if pool == "int8":
        # Quantize the written pages; a poison page keeps NaN SCALES.
        dead = jnp.isnan(k[:, :1, :1])
        (kq, ks), (vq, vs) = (quantize_kv(jnp.nan_to_num(a)) for a in (k, v))
        k = (kq, jnp.where(dead[..., 0], jnp.nan, ks))
        v = (vq, jnp.where(dead[..., 0], jnp.nan, vs))
    # The XLA gather reads dead columns (it masks them afterwards), so the
    # reference reads a pool whose poison is zeros.
    clean = jax.tree.map(jnp.nan_to_num, (k, v))
    want = paged_attention(q[:, None], *clean, tables, ctx, qpos,
                           page_size=WALK_PS, block_pages=4)[:, 0]
    if pool != "partial":
        return paged_decode_attention(q, k, v, tables, ctx,
                                      page_size=WALK_PS,
                                      interpret=interpret), want
    shards, tokens_local = 2, k.shape[0] // 2
    parts = [paged_decode_attention_partial(
        q, k[s * tokens_local:(s + 1) * tokens_local],
        v[s * tokens_local:(s + 1) * tokens_local], tables, ctx,
        jnp.int32(s), page_size=WALK_PS,
        pages_local=tokens_local // WALK_PS, interpret=interpret)
        for s in range(shards)]
    m_g = jnp.maximum(parts[0][1], parts[1][1])  # parallel/kv_split.py's merge
    corr = [jnp.exp(m - m_g) for _, m, _ in parts]
    l_g = sum(c * l for c, (_, _, l) in zip(corr, parts))
    acc_g = sum(c[..., None] * acc for c, (acc, _, _) in zip(corr, parts))
    return acc_g / jnp.maximum(l_g[..., None], 1e-30), want


def _assert_walk(pool, ctx_lens, n_kv, group, **kw):
    q, k, v, tables, ctx, _, _ = _walk_case(ctx_lens, n_kv, group)
    got, want = _walk(pool, q, k, v, tables, ctx, **kw)
    got, want = np.asarray(got), np.asarray(want)
    live = np.asarray(ctx_lens) > 0
    assert np.all(got[~live] == 0.0)  # an empty slot writes zeros
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)


WALK_POOLS = ["raw", "int8", "partial"]


@pytest.mark.parametrize("pool", WALK_POOLS)
def test_walk_skips_empty_rows(pool):
    """``ctx == 0`` at row 0, in the middle and at the end."""
    _assert_walk(pool, [0, 9, 0, 0, 33, 0], n_kv=4, group=2)


@pytest.mark.parametrize("pool", WALK_POOLS)
@pytest.mark.parametrize("ctx_of", [
    "one", "page", "page+1", "step", "step+1", "table"])
def test_walk_context_edges(pool, ctx_of):
    """A context of 1, a page, a page + 1, exactly one step of the walk,
    one step + 1, and the whole table, beside a short row."""
    from runbookai_tpu.ops.paged_attention_pallas import decode_pages_per_step

    g = decode_pages_per_step(WALK_PS, 4, 128, jnp.float32, 10**6)
    ctx = {"one": 1, "page": WALK_PS, "page+1": WALK_PS + 1,
           "step": g * WALK_PS, "step+1": g * WALK_PS + 1,
           "table": (2 * g + 1) * WALK_PS}[ctx_of]
    _assert_walk(pool, [ctx, 5], n_kv=4, group=2)


@pytest.mark.parametrize("pool", WALK_POOLS)
@pytest.mark.parametrize("n_kv", [4, 1])
def test_walk_gqa_group_seven(pool, n_kv):
    """Qwen2.5-7B's group of 7: four KV heads, and the one a tp 4 shard
    holds."""
    _assert_walk(pool, [3, 0, 150, 41], n_kv=n_kv, group=7)


def test_walk_never_fetches_a_dead_column():
    """The poison the cases above lay is live: a table that points a LIVE
    column at the poison page does turn the row into NaN, so a kernel that
    fetched dead columns would have failed them."""
    q, k, v, tables, ctx, _, _ = _walk_case([20, 5], n_kv=4, group=2)
    poison = tables[0, -1]
    got = paged_decode_attention(q, k, v, tables.at[0, 1].set(poison), ctx,
                                 page_size=WALK_PS, interpret=True)
    assert np.isnan(np.asarray(got[0])).all()
    assert not np.isnan(np.asarray(got[1])).any()


@pytest.mark.parametrize("pool", WALK_POOLS)
def test_walk_under_the_tpu_interpreter(pool):
    """The same walk where never-written VMEM reads as NaN and the copies
    and their semaphores are simulated (``pltpu.InterpretParams``): a
    group's unfetched tail must not reach the output."""
    from jax.experimental.pallas import tpu as pltpu

    _assert_walk(pool, [0, 11, 150], n_kv=4, group=2,
                 interpret=pltpu.InterpretParams())


def test_walk_pages_per_step_follows_the_shapes():
    """Pages a step is a value of the page's bytes, the step's positions
    and the table, not an option."""
    from runbookai_tpu.ops.paged_attention_pallas import decode_pages_per_step

    assert decode_pages_per_step(16, 4, 128, jnp.bfloat16, 513) == 16
    assert decode_pages_per_step(16, 8, 128, jnp.bfloat16, 513) == 8
    assert decode_pages_per_step(16, 1, 128, jnp.bfloat16, 513) == 32
    assert decode_pages_per_step(16, 4, 128, jnp.int8, 513) == 32
    assert decode_pages_per_step(4, 2, 32, jnp.float32, 8) == 8
    assert decode_pages_per_step(16, 64, 256, jnp.float32, 513) == 1


# ----------------------------------------------------------- the chunk walk
#
# The chunk kernel (T > 1) walks the pages a block of ``tq`` queries can
# SEE: those of its row's context AND under its causal bound. The cases lay
# one query block a row, as ``_mixed_step`` lays its buffer out (a row's
# table and context gathered per block), so that a block's own table can
# point every column it must not read at poison.


def _chunk_case(blocks, n_kv, group, tq, t=None, seed=0, hd=128):
    """``blocks``: a ``(q0, live, ctx)`` a row — the first query position,
    how many of the row's ``t`` tokens are real (the rest carry the trash
    position, as the engine's pads do) and the row's context; ``ctx == 0``
    is a pad block on the null row. Every page a row must not read is
    poison (NaN): those behind its dead table columns AND those inside its
    context but past the causal bound of its last query block."""
    from runbookai_tpu.ops.paged_attention_pallas import decode_pages_per_step

    t = t or tq
    rng = np.random.default_rng(seed)
    g = decode_pages_per_step(WALK_PS, n_kv, hd, jnp.float32, 10**6)
    seen = [-(-min(ctx, q0 + t) // WALK_PS) for q0, _, ctx in blocks]
    width = max(2 * g + 1, max(seen))  # two steps and a page, at least
    k, v, tables = _poison_pool(rng, seen, width, n_kv, hd, sum(seen) + 3)
    positions = np.full((len(blocks), t), width * WALK_PS, np.int32)  # trash
    for i, (q0, live, _) in enumerate(blocks):
        positions[i, :live] = q0 + np.arange(live)
    q = jnp.asarray(rng.normal(size=(len(blocks), t, n_kv * group, hd)),
                    jnp.float32)
    return (q, k, v, tables,
            jnp.asarray([ctx for _, _, ctx in blocks], jnp.int32),
            jnp.asarray(positions), g)


def _assert_chunk_walk(blocks, n_kv, group, tq, t=None, interpret=True):
    from runbookai_tpu.ops.paged_attention_pallas import paged_chunk_attention

    q, k, v, tables, ctx, positions, _ = _chunk_case(
        blocks, n_kv, group, tq, t)
    got = paged_chunk_attention(q, k, v, tables, ctx, positions,
                                page_size=WALK_PS, interpret=interpret,
                                q_block=tq)
    # The XLA gather reads dead columns (it masks them afterwards), so the
    # reference reads a pool whose poison is zeros.
    want = paged_attention(q, jnp.nan_to_num(k), jnp.nan_to_num(v), tables,
                           ctx, positions, page_size=WALK_PS, block_pages=4)
    got, want = np.asarray(got), np.asarray(want)
    for i, (_, live, n_ctx) in enumerate(blocks):
        if n_ctx == 0:
            assert np.all(got[i] == 0.0)  # a pad block writes zeros
        np.testing.assert_allclose(got[i, :live], want[i, :live],
                                   rtol=2e-5, atol=2e-5)


def _chunk_step(n_kv):
    """Positions one step of the chunk walk takes, at the cases' shapes."""
    from runbookai_tpu.ops.paged_attention_pallas import decode_pages_per_step

    return WALK_PS * decode_pages_per_step(WALK_PS, n_kv, 128, jnp.float32,
                                            10**6)


@pytest.mark.parametrize("tq", [8, 32])
def test_chunk_walk_skips_pad_blocks(tq):
    """``ctx == 0`` in the first block, in the middle and in the last."""
    pad = (0, 0, 0)
    _assert_chunk_walk([pad, (0, tq, tq + 5), pad, pad,
                        (40, tq, 40 + tq), pad], n_kv=2, group=2, tq=tq)


@pytest.mark.parametrize("n_kv", [4, 1])
def test_chunk_walk_mixed_layout_gqa_group_seven(n_kv):
    """The buffer of a mixed step at Qwen2.5-7B's group of 7 (four KV
    heads, and the one a tp 4 shard holds): decode-shaped blocks (one live
    token, seven pads), an empty slot, then a 22-token chunk behind 128
    cached tokens in three blocks, the last part-filled, and pad blocks.
    The chunk's later tokens lie in the row's pages already: to the first
    two blocks they are past the causal bound, and poison."""
    _assert_chunk_walk(
        [(149, 1, 150), (0, 0, 0), (8, 1, 9), (0, 1, 1),
         (128, 8, 150), (136, 8, 150), (144, 6, 150), (0, 0, 0)],
        n_kv=n_kv, group=7, tq=8)


@pytest.mark.parametrize("tq", [8, 32])
@pytest.mark.parametrize("ctx_of", [
    "one", "page", "page+1", "step", "step+1", "table"])
def test_chunk_walk_context_edges(ctx_of, tq):
    """A context of 1, a page, a page + 1, exactly one step of the walk,
    one step + 1 and the whole table: as a decode-shaped block (its one
    token the context's last) and as the block of queries that ends the
    context."""
    step = _chunk_step(2)
    ctx = {"one": 1, "page": WALK_PS, "page+1": WALK_PS + 1, "step": step,
           "step+1": step + 1, "table": 2 * step + WALK_PS}[ctx_of]
    live = min(ctx, tq)
    _assert_chunk_walk([(ctx - 1, 1, ctx), (ctx - live, live, ctx)],
                       n_kv=2, group=2, tq=tq)


@pytest.mark.parametrize("tq", [8, 32])
def test_chunk_walk_block_straddles_a_page_and_a_step(tq):
    """A query block whose positions cross a page boundary that is also a
    step boundary of the walk: its first queries see one step, its last
    two; once with the context ending at the block, once with 20 more
    tokens of the same chunk behind it (past the causal bound: poison)."""
    q0 = _chunk_step(2) - 3
    _assert_chunk_walk([(q0, tq, q0 + tq), (q0, tq, q0 + tq + 20)],
                       n_kv=2, group=2, tq=tq)


@pytest.mark.parametrize("tq", [8, 32])
@pytest.mark.parametrize("start", [0, 2048])
def test_chunk_walk_prompt_start_and_behind_a_prefix(start, tq):
    """Rows of several query blocks (``paged_chunk_attention`` as
    ``_prefill_step`` calls it): a chunk that starts the prompt — its
    first block walks one step — and one behind a 2,048-token prefix;
    the last block part-filled."""
    t = 2 * tq
    _assert_chunk_walk([(start, t - 3, start + t - 3)],
                       n_kv=2, group=2, tq=tq, t=t)


def test_chunk_walk_at_serving_block_shapes():
    """TQ 32 at the group of 7: 224 rows a kv head, as ``_prefill_step``
    runs Qwen2.5-7B's chunk."""
    _assert_chunk_walk([(0, 32, 32), (300, 32, 340)], n_kv=4, group=7, tq=32)


def test_chunk_walk_never_fetches_past_its_bounds():
    """The poison the cases above lay is live: a LIVE column pointed at
    the poison page does turn the block into NaN, so a kernel that fetched
    a dead column, or one past the causal bound, would have failed them."""
    from runbookai_tpu.ops.paged_attention_pallas import paged_chunk_attention

    q, k, v, tables, ctx, positions, _ = _chunk_case(
        [(16, 8, 40), (0, 5, 5)], n_kv=2, group=2, tq=8)
    poison = tables[0, -1]
    got = paged_chunk_attention(q, k, v, tables.at[0, 1].set(poison), ctx,
                                positions, page_size=WALK_PS,
                                interpret=True, q_block=8)
    assert np.isnan(np.asarray(got[0])).all()
    assert not np.isnan(np.asarray(got[1, :5])).any()


def test_chunk_walk_under_the_tpu_interpreter():
    """The same walk where never-written VMEM reads as NaN and the copies
    and their semaphores are simulated: a group's unfetched tail, and the
    buffers of a block that fetched nothing, must not reach the output."""
    from jax.experimental.pallas import tpu as pltpu

    _assert_chunk_walk([(0, 0, 0), (3, 8, 11), (140, 8, 150), (149, 1, 150)],
                       n_kv=2, group=2, tq=8,
                       interpret=pltpu.InterpretParams())


# ------------------------------------------------- the pool where it lies
#
# The kernels take the pool the layer scan carries, ``[L, tokens, n_kv,
# hd]``, and the layer's number (a traced scalar in the scan's body), where
# they took ``pool[layer]``. The cases give layer ``layer`` of a 3-layer
# pool a case's pages and fill the two other layers with poison (NaN; an
# int8 pool's scales): the call must equal the one on the layer's slice
# BIT FOR BIT, so a walk that read another layer's page — layer 0 for every
# layer, say — turns NaN and fails.

LAYERS = [0, 1, 2]


def _in_layer(pool, layer):
    """``pool [tokens, ...]`` (or an int8 pool's pair) as layer ``layer``
    of three, the other two poison."""
    def stack(a):
        poison = (jnp.full_like(a, jnp.nan) if a.dtype == jnp.float32
                  else jnp.full_like(a, 77))
        return jnp.stack([a if i == layer else poison for i in range(3)])
    return jax.tree.map(stack, pool)


def _int8_pool(k, v):
    """The raw float pool as (int8 values, float32 scales) pairs."""
    from runbookai_tpu.ops.attention import quantize_kv

    return tuple(quantize_kv(jnp.nan_to_num(a)) for a in (k, v))


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("pool", WALK_POOLS)
def test_decode_walk_reads_its_layer_of_the_pool(pool, layer):
    """Decode, every kind of pool, an empty row first, in the middle and
    last: the pool-and-layer call against the call on the slice."""
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_partial,
    )

    q, k, v, tables, ctx, _, _ = _walk_case([0, 9, 0, 0, 150, 0], 4, 2)
    if pool == "int8":
        k, v = _int8_pool(k, v)
    if pool == "partial":
        local = k.shape[0] // 2  # the second shard's page slice
        k, v = k[local:], v[local:]

        def walk(k, v, layer=None):
            return paged_decode_attention_partial(
                q, k, v, tables, ctx, jnp.int32(1), page_size=WALK_PS,
                pages_local=local // WALK_PS, interpret=True, layer=layer)
    else:
        def walk(k, v, layer=None):
            return paged_decode_attention(q, k, v, tables, ctx,
                                          page_size=WALK_PS, interpret=True,
                                          layer=layer)
    want = walk(k, v)
    got = jax.jit(walk)(_in_layer(k, layer), _in_layer(v, layer),
                        jnp.int32(layer))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert not np.isnan(np.asarray(g)).any()
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("entry", ["chunk", "ragged"])
def test_chunk_walk_reads_its_layer_of_the_pool(entry, layer):
    """The chunk kernel as ``_prefill_step`` calls it and, through
    ``paged_ragged_attention``, over a mixed step's flat buffer: pad
    blocks first, in the middle and last."""
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_chunk_attention,
        paged_ragged_attention,
    )

    pad = (0, 0, 0)
    q, k, v, tables, ctx, positions, _ = _chunk_case(
        [pad, (149, 1, 150), pad, pad, (128, 8, 150), (3, 8, 11), pad],
        n_kv=2, group=2, tq=8)
    if entry == "chunk":
        def walk(k, v, layer=None):
            return paged_chunk_attention(
                q, k, v, tables, ctx, positions, page_size=WALK_PS,
                interpret=True, q_block=8, layer=layer)
    else:
        nb, rq = positions.shape
        flat_q = q.reshape(nb * rq, *q.shape[2:])
        rows = jnp.repeat(jnp.arange(nb, dtype=jnp.int32), rq)

        def walk(k, v, layer=None):
            return paged_ragged_attention(
                flat_q, k, v, tables, ctx, positions.reshape(-1), rows,
                page_size=WALK_PS, ragged_block=rq, interpret=True,
                layer=layer)
    want = np.asarray(walk(k, v))
    got = np.asarray(jax.jit(walk)(_in_layer(k, layer), _in_layer(v, layer),
                                   jnp.int32(layer)))
    live = ~np.isnan(want)  # a row's trash-position pads read poison
    assert live.any() and np.array_equal(np.isnan(got), ~live)
    np.testing.assert_array_equal(got[live], want[live])


@pytest.mark.parametrize("entry", ["decode", "int8", "chunk"])
def test_a_one_layer_pool_is_the_same_kernel_at_one_layer(entry):
    """``layer=None`` on ``[tokens, n_kv, hd]`` is L = 1, layer 0."""
    from runbookai_tpu.ops.paged_attention_pallas import paged_chunk_attention

    if entry == "chunk":
        q, k, v, tables, ctx, positions, _ = _chunk_case(
            [(0, 0, 0), (128, 8, 150)], n_kv=2, group=2, tq=8)

        def walk(k, v, layer=None):
            return paged_chunk_attention(
                q, k, v, tables, ctx, positions, page_size=WALK_PS,
                interpret=True, q_block=8, layer=layer)[1]
    else:
        q, k, v, tables, ctx, _, _ = _walk_case([0, 41], 4, 2)
        if entry == "int8":
            k, v = _int8_pool(k, v)

        def walk(k, v, layer=None):
            return paged_decode_attention(q, k, v, tables, ctx,
                                          page_size=WALK_PS, interpret=True,
                                          layer=layer)
    one = jax.tree.map(lambda a: a[None], (k, v))
    got, want = np.asarray(walk(*one, layer=0)), np.asarray(walk(k, v))
    assert not np.isnan(want).any()
    np.testing.assert_array_equal(got, want)


def test_which_pools_are_read_in_place():
    """In place: a pool that cannot fit on-chip memory and whose page
    views are the bytes as they lie. Static, by shape alone."""
    from jax import ShapeDtypeStruct as S

    from runbookai_tpu.ops.paged_attention_pallas import reads_in_place

    bf16, f8 = jnp.bfloat16, jnp.float8_e4m3fn
    assert reads_in_place(S((28, 49152, 4, 128), bf16))  # the 7B cell
    assert reads_in_place(S((32, 49152, 8, 128), f8))  # Llama-3-8B, fp8
    assert reads_in_place(S((28, 196608, 1, 128), bf16))  # a tp 4 shard
    assert not reads_in_place(S((2, 2048, 4, 128), bf16))  # fits on chip
    assert not reads_in_place(S((28, 49152, 4, 64), bf16))  # lanes padded
    assert not reads_in_place(S((28, 98304, 2, 128), f8))  # heads padded
    assert not reads_in_place(  # an int8 pool: its scales are padded
        (S((28, 49152, 4, 128), jnp.int8), S((28, 49152, 4), jnp.float32)))


# ------------------------------------------ two kv heads, where they lie
#
# The recurrent families' softmax layers: Qwen3-Next's 16 query heads over 2
# kv heads of 256 (a decode row walks BY KV HEAD: the chunk walk at one
# query a block, since a page's ``[page_size x n_kv, hd]`` view is not the
# bytes as they lie once a head is wider than the lanes) and
# Nemotron-3-Nano's 32 over 2 of 128 (the decode walk), beside the dense
# cell's 28 over 4 of 128. The same poison pools: every unowned page NaN,
# every column a row must not read at one.

LAYER_WALK_SHAPES = [(16, 2, 256), (32, 2, 128), (28, 4, 128)]  # (n_q, n_kv, hd)
_layer_walk_shapes = pytest.mark.parametrize(
    "n_q,n_kv,hd", LAYER_WALK_SHAPES, ids=["qwen3next", "nemotron", "dense"])


def test_which_decode_rows_walk_by_kv_head():
    """Static, by the pool's shape: several heads, each wider than the
    lanes. An int8 pool's pair keeps the decode walk (its scales)."""
    from jax import ShapeDtypeStruct as S

    from runbookai_tpu.ops.paged_attention_pallas import _walks_by_head

    bf16 = jnp.bfloat16
    assert _walks_by_head(S((3, 131072, 2, 256), bf16))  # Qwen3-Next
    assert not _walks_by_head(S((6, 131072, 2, 128), bf16))  # Nemotron
    assert not _walks_by_head(S((28, 49152, 4, 128), bf16))  # the 7B cell
    assert not _walks_by_head(S((28, 196608, 1, 256), bf16))  # a lone head
    assert not _walks_by_head(
        (S((28, 49152, 4, 256), jnp.int8), S((28, 49152, 4), jnp.float32)))


@_layer_walk_shapes
@pytest.mark.parametrize("layer", [0, 2])
def test_decode_walk_of_a_layer_of_the_stacked_pool(n_q, n_kv, hd, layer):
    """As a forward's layer scan calls it: the stacked ``[L, tokens, n_kv,
    hd]`` pool and a traced ``layer``, the other layers poison, against
    XLA's walk over the layer's slice. Ragged contexts: free slots first,
    among the live rows and last, a context of 1, one ending mid-page, one
    a step of the walk + 1 and one filling the table."""
    from runbookai_tpu.ops.paged_attention_pallas import decode_pages_per_step

    g = decode_pages_per_step(WALK_PS, n_kv, hd, jnp.float32, 10**6)
    ctx_lens = [0, 1, 0, 5 * WALK_PS + 3, g * WALK_PS + 1,
                (2 * g + 1) * WALK_PS, 0]  # (the table is 2g + 1 wide)
    q, k, v, tables, ctx, _, _ = _walk_case(ctx_lens, n_kv, n_q // n_kv, hd=hd)
    got = jax.jit(lambda k, v, layer: paged_decode_attention(
        q, k, v, tables, ctx, page_size=WALK_PS, interpret=True, layer=layer,
        name="paged_decode_walk"))(
            _in_layer(k, layer), _in_layer(v, layer), jnp.int32(layer))
    want = paged_attention(q[:, None], jnp.nan_to_num(k), jnp.nan_to_num(v),
                           tables, ctx, jnp.maximum(ctx - 1, 0)[:, None],
                           page_size=WALK_PS, block_pages=4)[:, 0]
    got, want = np.asarray(got), np.asarray(want)
    live = np.asarray(ctx_lens) > 0
    assert np.all(got[~live] == 0.0)  # a free slot writes zeros
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)


def test_a_walk_is_named_by_its_caller():
    """``name=`` reaches the ``pallas_call`` (what a device trace calls the
    kernel), by either route; None leaves the dense family's calls as they
    were."""
    def names(n_kv, group, hd, **kw):
        q, k, v, tables, ctx, _, _ = _walk_case([9, 0], n_kv, group, hd=hd)
        text = str(jax.make_jaxpr(functools.partial(
            paged_decode_attention, page_size=WALK_PS, interpret=True, **kw))(
                q, k, v, tables, ctx))
        return set(re.findall(r"name=(\w+)", text))

    for shape in [(2, 2, 128), (2, 8, 256)]:  # the decode walk; by kv head
        assert "paged_decode_walk" in names(*shape, name="paged_decode_walk")
        assert "paged_decode_walk" not in names(*shape)


def _build_pool(rng, ctx_lens_list, n_kv, hd, ps, pages, max_pages):
    kf = jnp.zeros((pages * ps, n_kv, hd), jnp.float32)
    vf = jnp.zeros((pages * ps, n_kv, hd), jnp.float32)
    tables = np.zeros((len(ctx_lens_list), max_pages), np.int32)
    next_page = 1
    for i, ctx in enumerate(ctx_lens_list):
        need = (ctx + ps - 1) // ps
        tables[i, :need] = np.arange(next_page, next_page + need)
        next_page += need
        k_seq = jnp.asarray(rng.normal(size=(ctx, n_kv, hd)), jnp.float32)
        v_seq = jnp.asarray(rng.normal(size=(ctx, n_kv, hd)), jnp.float32)
        pos = jnp.arange(ctx)
        kf = write_kv_pages(kf, k_seq, pos, jnp.asarray(tables[i]), ps)
        vf = write_kv_pages(vf, v_seq, pos, jnp.asarray(tables[i]), ps)
    return kf, vf, jnp.asarray(tables)


@pytest.mark.parametrize("t,ctx_lens_list,q_block", [
    (12, [12, 15], None),    # prefill-shaped chunks (ragged ctx >= t)
    (4, [9, 30], None),      # speculative verify: queries end at ctx-1
    (12, [16, 25], 4),       # q-blocking path: 3 query blocks
    (5, [8, 11], 2),         # T not a multiple of the q block -> pad tail
])
def test_pallas_chunk_matches_xla(t, ctx_lens_list, q_block):
    from runbookai_tpu.ops.paged_attention_pallas import paged_chunk_attention

    rng = np.random.default_rng(2)
    b, n_q, n_kv, hd, ps, pages, max_pages = len(ctx_lens_list), 8, 2, 32, 4, 32, 8
    kf, vf, tables = _build_pool(rng, ctx_lens_list, n_kv, hd, ps, pages, max_pages)

    ctx_arr = jnp.asarray(ctx_lens_list, jnp.int32)
    # Contiguous query positions ending at ctx-1 (the engine contract).
    q_positions = (ctx_arr - t)[:, None] + jnp.arange(t)[None, :]
    q = jnp.asarray(rng.normal(size=(b, t, n_q, hd)), jnp.float32)

    ref = paged_attention(q, kf, vf, tables, ctx_arr, q_positions,
                          page_size=ps, block_pages=2)
    out = paged_chunk_attention(q, kf, vf, tables, ctx_arr, q_positions,
                                page_size=ps, interpret=True, q_block=q_block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_engine_pallas_attn_matches_xla_end_to_end():
    """Full continuous-batching cycle with attn_impl='pallas' (interpret on
    CPU): chunked prefill + multi-step decode + speculative verify all ride
    the Pallas kernels and must reproduce the XLA engine's greedy outputs."""
    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["llama3-test"]
    tok = ByteTokenizer()
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)

    def run(attn_impl):
        core = EngineCore(cfg, params, tok, EngineConfig(
            page_size=4, num_pages=64, max_batch_slots=2, prefill_chunk=8,
            max_seq_len=128, block_pages=4, kv_dtype=jnp.float32,
            attn_impl=attn_impl))
        reqs = [EngineRequest(
            prompt_ids=tok.encode(p),
            sampling=SamplingParams(temperature=0.0, max_new_tokens=10))
            for p in ("checkout latency is high and high and high",
                      "pods crashlooping")]
        for r in reqs:
            core.submit(r)
        core.run_until_idle()
        return [r.out_ids for r in reqs]

    assert run("pallas") == run("xla")


def test_write_kv_pages_batch_matches_loop():
    """The single-scatter batched writer equals the per-sequence loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from runbookai_tpu.ops.attention import write_kv_pages, write_kv_pages_batch

    ps, pages, n_kv, hd, b, t = 4, 16, 2, 8, 3, 5
    key = jax.random.PRNGKey(0)
    pool = jnp.zeros((pages * ps, n_kv, hd), jnp.float32)
    new = jax.random.normal(key, (b, t, n_kv, hd))
    # Disjoint tables per sequence + trailing trash column -> null page 0.
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0], [7, 8, 9, 0]], jnp.int32)
    positions = jnp.asarray([[0, 1, 2, 3, 4], [2, 3, 4, 5, 6],
                             [0, 1, 2, 12, 12]], jnp.int32)  # 12 -> trash col

    ref = pool
    for i in range(b):
        ref = write_kv_pages(ref, new[i], positions[i], tables[i], ps)
    got = write_kv_pages_batch(pool, new, positions, tables, ps)
    # Page 0 (null) collects trash nondeterministically; compare real pages.
    np.testing.assert_allclose(np.asarray(got)[ps:], np.asarray(ref)[ps:])
