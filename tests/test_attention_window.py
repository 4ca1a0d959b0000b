"""A window as the lower edge of the page walks: the two Pallas kernels (in
interpret mode) and XLA's walk against plain masked attention.

The cases are laid against the window and against the walk's own units: a
context under the window, at it, one over it; a window that starts in the
middle of a page and of a group of pages; int8 and fp8 pools. Every page
wholly BEHIND a row's window holds poison (NaN) and its table column points
at one poison page: the manager gives those pages back while the sequence
lives, so a walk that fetches one, even to mask it, reads NaN. With
``window=None`` each walk's output is bit-equal to a call that never heard
of windows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.ops.attention import paged_attention, quantize_kv
from runbookai_tpu.ops.paged_attention_pallas import (
    decode_pages_per_step,
    paged_chunk_attention,
    paged_decode_attention,
    paged_ragged_attention,
)

PS, N_KV, GROUP, HD = 8, 2, 4, 128
N_Q = N_KV * GROUP


def plain(q, k, v, q_pos, window):
    """Masked softmax attention of queries ``q`` [T, n_q, hd] at positions
    ``q_pos`` over one sequence's keys and values [S, n_kv, hd], float32."""
    s = k.shape[0]
    qg = q.reshape(len(q_pos), N_KV, GROUP, HD).astype(np.float64)
    scores = np.einsum("tkgd,skd->tkgs", qg, k.astype(np.float64)) / np.sqrt(HD)
    j = np.arange(s)[None, :]
    i = np.asarray(q_pos)[:, None]
    seen = (j <= i) if window is None else (j <= i) & (i - j < window)
    scores = np.where(seen[:, None, None, :], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("tkgs,skd->tkgd", p, v.astype(np.float64)).reshape(
        len(q_pos), N_Q, HD)


def laid_out(rng, ctx_lens, window, width, first_query=None):
    """Sequences of ``ctx_lens`` tokens in a pool whose every other page is
    poison: (k [tokens, n_kv, hd], v, tables [rows, width], the sequences'
    own keys and values). A row's pages wholly behind the window of its
    FIRST query (``first_query[i]``, its last position by default) are not
    laid at all: their columns point at the poison page."""
    pages = [-(-c // PS) for c in ctx_lens]
    num_pages = 2 + sum(pages)
    k = np.full((num_pages * PS, N_KV, HD), np.nan, np.float32)
    v = np.full_like(k, np.nan)
    poison = num_pages - 1
    tables = np.full((len(ctx_lens), width), poison, np.int32)
    order = list(rng.permutation(np.arange(1, num_pages - 1)))
    seqs = []
    for i, c in enumerate(ctx_lens):
        ks = rng.normal(size=(c, N_KV, HD)).astype(np.float32)
        vs = rng.normal(size=(c, N_KV, HD)).astype(np.float32)
        seqs.append((ks, vs))
        q0 = (c - 1) if first_query is None else first_query[i]
        edge = 0 if window is None else max(0, q0 - window + 1)
        for col in range(edge // PS, pages[i]):
            page = order.pop()
            tables[i, col] = page
            n = min(PS, c - col * PS)
            # (what a live page holds past the context is finite: stale rows)
            k[page * PS:(page + 1) * PS] = rng.normal(size=(PS, N_KV, HD))
            v[page * PS:(page + 1) * PS] = rng.normal(size=(PS, N_KV, HD))
            k[page * PS:page * PS + n] = ks[col * PS:col * PS + n]
            v[page * PS:page * PS + n] = vs[col * PS:col * PS + n]
    return jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables), seqs


G = decode_pages_per_step(PS, N_KV, HD, jnp.float32, 10 ** 6)  # pages a group
SPAN = G * PS
# A window that is no multiple of a page nor of a group, and contexts
# under it, at it, one over it, and whole groups past it.
WINDOW = SPAN + PS + 3
CONTEXTS = [WINDOW - 5, WINDOW, WINDOW + 1, 3 * SPAN + 5, 4 * WINDOW + 7]


def test_the_cases_straddle_pages_and_groups():
    assert WINDOW % PS and WINDOW % SPAN and G > 1
    edges = [c - WINDOW for c in CONTEXTS if c > WINDOW]
    assert any(e % PS for e in edges) and any(e // PS % G for e in edges)


@pytest.mark.parametrize("window", [WINDOW, None])
def test_decode_walk_against_plain_attention(window):
    rng = np.random.default_rng(1)
    width = -(-max(CONTEXTS) // PS) + 1
    k, v, tables, seqs = laid_out(rng, CONTEXTS, window, width)
    q = rng.normal(size=(len(CONTEXTS), N_Q, HD)).astype(np.float32)
    ctx = jnp.asarray(CONTEXTS, jnp.int32)
    out = paged_decode_attention(jnp.asarray(q), k, v, tables, ctx, page_size=PS,
                                 interpret=True, window=window)
    # XLA's walk gathers, masked, every page it passes: no poison for it.
    xla = paged_attention(jnp.asarray(q)[:, None], jnp.nan_to_num(k), jnp.nan_to_num(v),
                          tables, ctx, (ctx - 1)[:, None], page_size=PS, block_pages=3,
                          window=window)[:, 0]
    for i, (c, (ks, vs)) in enumerate(zip(CONTEXTS, seqs)):
        ref = plain(q[i:i + 1], ks, vs, [c - 1], window)[0]
        # float32 products against a float64 reference: 1e-5 is rounding.
        np.testing.assert_allclose(np.asarray(out[i]), ref, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(xla[i]), ref, rtol=1e-5, atol=1e-5)


def test_xla_walk_against_plain_attention():
    """XLA's walk gathers every page from the batch's lowest edge on, so
    the pages behind ANOTHER row's edge are zeros here, not poison."""
    rng = np.random.default_rng(2)
    width = -(-max(CONTEXTS) // PS) + 1
    k, v, tables, seqs = laid_out(rng, CONTEXTS, None, width)
    t = 5
    q = rng.normal(size=(len(CONTEXTS), t, N_Q, HD)).astype(np.float32)
    ctx = jnp.asarray(CONTEXTS, jnp.int32)
    pos = ctx[:, None] - t + jnp.arange(t)[None]
    k, v = jnp.nan_to_num(k), jnp.nan_to_num(v)
    out = paged_attention(jnp.asarray(q), k, v, tables, ctx, pos, page_size=PS,
                          block_pages=3, window=WINDOW)
    for i, (c, (ks, vs)) in enumerate(zip(CONTEXTS, seqs)):
        ref = plain(q[i], ks, vs, np.arange(c - t, c), WINDOW)
        np.testing.assert_allclose(np.asarray(out[i]), ref, rtol=1e-5, atol=1e-5)
    full = paged_attention(jnp.asarray(q), k, v, tables, ctx, pos, page_size=PS,
                           block_pages=3)
    assert np.abs(np.asarray(full[-1]) - np.asarray(out[-1])).max() > 1e-3


@pytest.mark.parametrize("window", [WINDOW, None])
def test_chunk_walk_against_plain_attention(window):
    """A chunk whose queries straddle the window's edge, a page's and a
    group's: the walk starts at the FIRST query's edge and each query's own
    edge is in the mask."""
    rng = np.random.default_rng(3)
    t = 2 * PS + 3
    width = -(-max(CONTEXTS) // PS) + 1
    first = [c - t for c in CONTEXTS]
    k, v, tables, seqs = laid_out(rng, CONTEXTS, window, width, first_query=first)
    q = rng.normal(size=(len(CONTEXTS), t, N_Q, HD)).astype(np.float32)
    ctx = jnp.asarray(CONTEXTS, jnp.int32)
    pos = jnp.asarray(first, jnp.int32)[:, None] + jnp.arange(t)[None]
    out = paged_chunk_attention(jnp.asarray(q), k, v, tables, ctx, pos, page_size=PS,
                                interpret=True, window=window, q_block=8)
    for i, (c, (ks, vs)) in enumerate(zip(CONTEXTS, seqs)):
        ref = plain(q[i], ks, vs, np.arange(c - t, c), window)
        np.testing.assert_allclose(np.asarray(out[i]), ref, rtol=1e-5, atol=1e-5)


def test_ragged_walk_takes_the_window():
    """The mixed step's flat buffer: decode rows of one token and a chunk,
    a block of eight queries a grid step, each with its row's edge."""
    rng = np.random.default_rng(4)
    rq, chunk = 8, 16
    ctxs = [WINDOW + 9, 3 * SPAN + 5, 2 * WINDOW]  # two decode rows, one chunk
    first = [ctxs[0] - 1, ctxs[1] - 1, ctxs[2] - chunk]
    width = -(-max(ctxs) // PS) + 1
    k, v, tables, seqs = laid_out(rng, ctxs, WINDOW, width, first_query=first)
    n = 2 * rq + chunk
    q = rng.normal(size=(n, N_Q, HD)).astype(np.float32)
    trash = width * PS
    positions = np.full((n,), trash, np.int32)
    positions[0], positions[rq] = first[0], first[1]
    positions[2 * rq:] = np.arange(first[2], ctxs[2])
    row_ids = np.repeat(np.asarray([0, 1, 2, 2], np.int32), rq)
    out = paged_ragged_attention(
        jnp.asarray(q), k, v, tables, jnp.asarray(ctxs, jnp.int32),
        jnp.asarray(positions), jnp.asarray(row_ids), page_size=PS,
        ragged_block=rq, interpret=True, window=WINDOW)
    for row, at in ((0, [0]), (1, [rq]), (2, list(range(2 * rq, n)))):
        ks, vs = seqs[row]
        ref = plain(q[at], ks, vs, positions[at], WINDOW)
        np.testing.assert_allclose(np.asarray(out)[at], ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_quantised_pools_take_the_window(kind):
    """An int8 pool (values and per-token scales: the decode walk's scaled
    sources) and an fp8 pool, against plain attention over the values the
    pool holds."""
    rng = np.random.default_rng(5)
    width = -(-max(CONTEXTS) // PS) + 1
    k, v, tables, _ = laid_out(rng, CONTEXTS, None, width)
    k, v = jnp.nan_to_num(k), jnp.nan_to_num(v)
    q = rng.normal(size=(len(CONTEXTS), N_Q, HD)).astype(np.float32)
    ctx = jnp.asarray(CONTEXTS, jnp.int32)
    if kind == "int8":
        kq, vq = quantize_kv(k), quantize_kv(v)
        held = [np.asarray(a[0].astype(jnp.float32) * a[1][..., None]) for a in (kq, vq)]
    else:
        kq, vq = k.astype(jnp.float8_e4m3fn), v.astype(jnp.float8_e4m3fn)
        held = [np.asarray(a.astype(jnp.float32)) for a in (kq, vq)]
    out = paged_decode_attention(jnp.asarray(q), kq, vq, tables, ctx, page_size=PS,
                                 interpret=True, window=WINDOW)
    tab = np.asarray(tables)
    for i, c in enumerate(CONTEXTS):
        rows = (tab[i, np.arange(c) // PS] * PS + np.arange(c) % PS)
        ref = plain(q[i:i + 1], held[0][rows], held[1][rows], [c - 1], WINDOW)[0]
        np.testing.assert_allclose(np.asarray(out[i]), ref, rtol=2e-5, atol=2e-5)


def test_no_window_is_bit_equal_to_a_call_without_the_argument():
    rng = np.random.default_rng(6)
    ctxs = [SPAN + 3, 2 * SPAN + 1]
    width = -(-max(ctxs) // PS) + 1
    k, v, tables, _ = laid_out(rng, ctxs, None, width)
    ctx = jnp.asarray(ctxs, jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, 4, N_Q, HD)), jnp.float32)
    pos = ctx[:, None] - 4 + jnp.arange(4)[None]
    for with_arg, without in (
        (paged_decode_attention(q[:, 0], k, v, tables, ctx, PS, interpret=True, window=None),
         paged_decode_attention(q[:, 0], k, v, tables, ctx, PS, interpret=True)),
        (paged_chunk_attention(q, k, v, tables, ctx, pos, PS, interpret=True, window=None),
         paged_chunk_attention(q, k, v, tables, ctx, pos, PS, interpret=True)),
        (paged_attention(q, jnp.nan_to_num(k), jnp.nan_to_num(v), tables, ctx, pos, PS,
                         block_pages=3, window=None),
         paged_attention(q, jnp.nan_to_num(k), jnp.nan_to_num(v), tables, ctx, pos, PS,
                         block_pages=3)),
    ):
        assert np.array_equal(np.asarray(with_arg), np.asarray(without))


def test_no_window_traces_the_program_it_traced_before():
    """The edge is statically absent: the kernels' jaxprs with ``window``
    None hold no operation a windowed one adds (a ``max`` against zero for
    the edge, the compare under the mask)."""
    q = jnp.zeros((2, N_Q, HD), jnp.float32)
    k = jnp.zeros((6 * PS, N_KV, HD), jnp.float32)
    tables = jnp.zeros((2, 5), jnp.int32)
    ctx = jnp.ones((2,), jnp.int32)

    def text(window):
        return str(jax.make_jaxpr(lambda *a: paged_decode_attention(
            *a, page_size=PS, interpret=False, window=window))(q, k, k, tables, ctx))

    assert text(None) == str(jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, page_size=PS, interpret=False))(q, k, k, tables, ctx))
    assert "swa_decode_walk" in text(WINDOW) and "swa_decode_walk" not in text(None)
