"""The KV pool rides the layer scan's carry: the step programs against a
plain per-layer loop.

``models/llama.py`` carries the whole ``[L, tokens, n_kv, hd]`` pool
through its scan over layers: the page write scatters its rows at
``(layer, dest)`` of the carry and the attention reads the layer's slice
of it. The reference here does what that replaced, in the open: a Python
loop over the layers, each taking its own ``[tokens, n_kv, hd]`` slice,
writing it with the per-layer scatter, attending over it with the
per-layer op, and the slices stacked again at the end. Same weights, same
inputs; the sampled tokens and BOTH pools must come out bit for bit, on a
model of three layers whose K/V differ in every layer — so a writer or
reader that took layer 0 for every layer fails — and pad rows may touch
nothing but the null page.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.engine.engine import (
    _RAGGED_BLOCK,
    _decode_multi,
    _decode_step,
    _mixed_step,
    _prefill_step,
)
from runbookai_tpu.models.llama import (
    LlamaConfig,
    ffn_block,
    init_params,
    lm_head_logits,
    qmm,
    rms_norm,
)
from runbookai_tpu.ops.attention import paged_attention, write_kv_pages_batch
from runbookai_tpu.ops.paged_attention_pallas import (
    paged_chunk_attention,
    paged_decode_attention,
)
from runbookai_tpu.ops.rope import apply_rope
from runbookai_tpu.ops.sampling import sample_tokens

CFG = LlamaConfig(
    name="kv-carry-test", vocab_size=262, dim=64, n_layers=3, n_heads=4,
    n_kv_heads=2, ffn_dim=128, max_seq_len=256, rope_theta=10_000.0,
)
PS, PAGES, MAX_PAGES, BLOCK_PAGES = 4, 24, 6, 2
TRASH = MAX_PAGES * PS  # a pad's position: the table's last column, page 0
STATIC = dict(page_size=PS, block_pages=BLOCK_PAGES)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def _pools(seed):
    rng = np.random.default_rng(seed)
    shape = (CFG.n_layers, PAGES * PS, CFG.n_kv_heads, CFG.head_dim)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


def _tables(rows):
    """Disjoint pages per row from page 1 on; column MAX_PAGES is the
    trash column (the null page 0), as is every row of an empty slot."""
    out = np.zeros((len(rows), MAX_PAGES + 1), np.int32)
    nxt = 1
    for i, n_pages in enumerate(rows):
        out[i, :n_pages] = np.arange(nxt, nxt + n_pages)
        nxt += n_pages
    assert nxt <= PAGES
    return jnp.asarray(out)


# ------------------------------------------------------------ reference


@partial(jax.jit, static_argnames=("attn_impl",))
def _ref_layer(hidden, lp, k_l, v_l, positions, tables, ctx_lens, attn_impl):
    """One layer on ITS slice of the pool, with the per-layer ops."""
    b, t = positions.shape
    hd, n_kv, n_q = CFG.head_dim, CFG.n_kv_heads, CFG.n_heads
    x = rms_norm(hidden, lp["attn_norm"], CFG.norm_eps)
    q, k, v = qmm(x, lp["wq"]), qmm(x, lp["wk"]), qmm(x, lp["wv"])
    q = apply_rope(q.reshape(b, t, n_q, hd), positions, CFG.rope_theta,
                   CFG.rope_scaling)
    k = apply_rope(k.reshape(b, t, n_kv, hd), positions, CFG.rope_theta,
                   CFG.rope_scaling)
    v = v.reshape(b, t, n_kv, hd)
    k_l = write_kv_pages_batch(k_l, k, positions, tables, PS)
    v_l = write_kv_pages_batch(v_l, v, positions, tables, PS)
    if attn_impl == "xla":
        attn = paged_attention(q, k_l, v_l, tables, ctx_lens, positions,
                               page_size=PS, block_pages=BLOCK_PAGES)
    elif t == 1:
        attn = paged_decode_attention(q[:, 0], k_l, v_l, tables, ctx_lens,
                                      page_size=PS, interpret=True)[:, None]
    else:
        attn = paged_chunk_attention(q, k_l, v_l, tables, ctx_lens,
                                     positions, page_size=PS, interpret=True)
    hidden = hidden + qmm(attn.reshape(b, t, n_q * hd), lp["wo"])
    y = rms_norm(hidden, lp["mlp_norm"], CFG.norm_eps)
    return hidden + ffn_block(y, lp, CFG), k_l, v_l


def _ref_hidden(params, tokens, positions, kv_k, kv_v, tables, ctx_lens,
                attn_impl):
    h = params["embed"][tokens]
    k_out, v_out = [], []
    for layer in range(CFG.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        h, k_l, v_l = _ref_layer(h, lp, kv_k[layer], kv_v[layer], positions,
                                 tables, ctx_lens, attn_impl)
        k_out.append(k_l)
        v_out.append(v_l)
    return h, jnp.stack(k_out), jnp.stack(v_out)


def _greedy(b):
    """temps, top_ps, top_ks of ``b`` greedy rows."""
    return (jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32),
            jnp.zeros((b,), jnp.int32))


def _sample(logits, key, positions):
    temps, top_ps, top_ks = _greedy(logits.shape[0])
    return sample_tokens(logits, key, temps, top_ps, None, top_ks,
                         positions=positions)


def _assert_pools(got, want, before, touched):
    """Both pools bit for bit; every layer written; and nothing outside
    ``touched`` (the live rows' destinations and the null page) moved."""
    for g, w, b4 in zip(got, want, before):
        g, w, b4 = np.asarray(g), np.asarray(w), np.asarray(b4)
        np.testing.assert_array_equal(g, w)
        moved = np.any(g != b4, axis=(2, 3))  # [L, tokens]
        assert moved.any(axis=1).all(), "a layer of the pool was not written"
        assert set(np.flatnonzero(moved.any(axis=0))) <= touched


def _dests(tables, rows_positions):
    """Pool rows the given (table row, position) pairs write, plus the
    null page every pad lands in."""
    tables = np.asarray(tables)
    out = set(range(PS))
    for row, pos in rows_positions:
        out.add(int(tables[row, pos // PS]) * PS + pos % PS)
    return out


# ------------------------------------------------------------- programs


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_decode_step_matches_per_layer_loop(params, attn_impl):
    kv_k, kv_v = _pools(1)
    ctx = np.array([6, 9, 1, 0], np.int32)  # slot 3 is empty: a pad row
    tables = _tables([2, 3, 1, 0])
    tokens = jnp.asarray([[5], [17], [200], [0]], jnp.int32)
    positions = jnp.asarray(np.maximum(ctx - 1, 0)[:, None])
    ctx_lens = jnp.asarray(ctx)
    key = jax.random.PRNGKey(3)

    h, k_ref, v_ref = _ref_hidden(params, tokens, positions, kv_k, kv_v,
                                  tables, ctx_lens, attn_impl)
    logits_ref = lm_head_logits(params, CFG, h)[:, -1]
    tok_ref = _sample(logits_ref, key, ctx_lens)

    tok, logits, k_new, v_new, _, _ = _decode_step(
        params, CFG, tokens, positions, kv_k + 0, kv_v + 0, tables, ctx_lens,
        *_greedy(4), key, None, jnp.zeros((4,), jnp.int32),
        attn_impl=attn_impl, **STATIC)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_ref))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_ref))
    _assert_pools((k_new, v_new), (k_ref, v_ref), (kv_k, kv_v),
                  _dests(tables, [(i, c - 1) for i, c in enumerate(ctx[:3])]))


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_decode_multi_matches_per_layer_loop(params, attn_impl):
    k_steps = 8
    kv_k, kv_v = _pools(2)
    ctx = np.array([6, 9, 1, 0], np.int32)
    tables = _tables([4, 5, 3, 0])  # pages for ctx + 8 are there
    tokens = jnp.asarray([[5], [17], [200], [0]], jnp.int32)
    positions = jnp.asarray(np.maximum(ctx - 1, 0)[:, None])
    key = jax.random.PRNGKey(4)

    toks_ref = []
    tok_r, pos_r, ctx_r, key_r = tokens, positions, jnp.asarray(ctx), key
    k_ref, v_ref = kv_k, kv_v
    for _ in range(k_steps):
        h, k_ref, v_ref = _ref_hidden(params, tok_r, pos_r, k_ref, v_ref,
                                      tables, ctx_r, attn_impl)
        key_r, sub = jax.random.split(key_r)
        tok = _sample(lm_head_logits(params, CFG, h)[:, -1], sub, ctx_r)
        toks_ref.append(tok)
        tok_r, pos_r, ctx_r = tok[:, None], pos_r + 1, ctx_r + 1

    toks, k_new, v_new, _, _ = _decode_multi(
        params, CFG, tokens, positions, kv_k + 0, kv_v + 0, tables,
        jnp.asarray(ctx), *_greedy(4), key, jnp.zeros((4,), jnp.int32),
        k_steps=k_steps, attn_impl=attn_impl, **STATIC)
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.stack(toks_ref, axis=1)))
    # The empty slot's position walks 0..7 of its all-null table: pages
    # 0 and 1 of logical space, both the null page.
    _assert_pools((k_new, v_new), (k_ref, v_ref), (kv_k, kv_v),
                  _dests(tables, [(i, c - 1 + s) for i, c in enumerate(ctx[:3])
                                  for s in range(k_steps)]))


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_prefill_step_matches_per_layer_loop(params, attn_impl):
    kv_k, kv_v = _pools(3)
    t = 8
    tables = _tables([3, 2])
    # Row 0: a whole chunk from position 4; row 1: five tokens and three
    # pads at the trash position.
    pos = np.full((2, t), TRASH, np.int32)
    pos[0] = np.arange(4, 4 + t)
    pos[1, :5] = np.arange(5)
    tokens = jnp.asarray(np.random.default_rng(5).integers(1, 250, (2, t)),
                         jnp.int32)
    positions, ctx_lens = jnp.asarray(pos), jnp.asarray([12, 5], jnp.int32)
    last_idx = jnp.asarray([7, 4], jnp.int32)

    h, k_ref, v_ref = _ref_hidden(params, tokens, positions, kv_k, kv_v,
                                  tables, ctx_lens, attn_impl)
    logits_ref = lm_head_logits(params, CFG, h)[jnp.arange(2), last_idx]

    logits, k_new, v_new, _ = _prefill_step(
        params, CFG, tokens, kv_k + 0, kv_v + 0, positions, tables, ctx_lens,
        last_idx, jnp.zeros((2,), jnp.int32), attn_impl=attn_impl, **STATIC)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_ref))
    _assert_pools((k_new, v_new), (k_ref, v_ref), (kv_k, kv_v),
                  _dests(tables, [(0, p) for p in range(4, 12)]
                         + [(1, p) for p in range(5)]))


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_mixed_step_matches_per_layer_loop(params, attn_impl):
    """Two decode slots, a free slot, and a prefill row of 11 tokens that
    finishes its prompt and takes the free slot, in one ragged buffer."""
    rq, b, n_pf, pf_tokens = _RAGGED_BLOCK, 3, 1, 16
    n, rows = b * rq + pf_tokens, b + n_pf + 1
    kv_k, kv_v = _pools(4)
    tables = jnp.concatenate(  # rows: slot 0, slot 1, free slot, prefill, null
        [_tables([3, 2, 0, 3]), jnp.zeros((1, MAX_PAGES + 1), jnp.int32)])
    ctx = np.array([10, 7, 0, 11, 0], np.int32)
    tokens = np.zeros((n,), np.int32)
    positions = np.full((n,), TRASH, np.int32)
    row_ids = np.full((n,), rows - 1, np.int32)
    for s in range(2):
        positions[s * rq] = ctx[s] - 1
        row_ids[s * rq: (s + 1) * rq] = s
    off = b * rq
    tokens[off: off + 11] = np.random.default_rng(6).integers(1, 250, 11)
    positions[off: off + 11] = np.arange(11)
    row_ids[off: off + 16] = b
    feed = jnp.asarray([5, 17, 0], jnp.int32)
    dec_idx = jnp.arange(b, dtype=jnp.int32) * rq
    pf_last = jnp.asarray([off + 10], jnp.int32)
    pf_slot_map = jnp.asarray([2], jnp.int32)
    key = jax.random.PRNGKey(7)
    tokens, positions, row_ids, ctx_lens = (
        jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(row_ids),
        jnp.asarray(ctx))

    # Reference: the same flat -> blocked transform above a per-layer loop.
    toks_in = tokens.at[dec_idx].set(feed)
    block_rows = row_ids.reshape(-1, rq)[:, 0]
    h, k_ref, v_ref = _ref_hidden(
        params, toks_in.reshape(-1, rq), positions.reshape(-1, rq), kv_k,
        kv_v, tables[block_rows], ctx_lens[block_rows], attn_impl)
    sel = jnp.concatenate([dec_idx, pf_last])
    logits = lm_head_logits(params, CFG, h.reshape(n, -1)[sel])
    key_dec, key_pf = jax.random.split(key)
    dec_ref = _sample(logits[:b], key_dec, ctx_lens[:b])
    pf_ref = _sample(logits[b:], key_pf, ctx_lens[b:b + n_pf])

    toks_win, pf_toks, feed_new, k_new, v_new, _, _ = _mixed_step(
        params, CFG, tokens, feed, dec_idx, positions, row_ids, kv_k + 0,
        kv_v + 0, tables, ctx_lens, jnp.zeros((rows,), jnp.int32), pf_last,
        *_greedy(b), key, *_greedy(n_pf), pf_slot_map,
        jnp.zeros((n_pf,), jnp.int32), attn_impl=attn_impl,
        ragged_block=rq, **STATIC)
    np.testing.assert_array_equal(np.asarray(toks_win[:, 0]),
                                  np.asarray(dec_ref))
    np.testing.assert_array_equal(np.asarray(pf_toks), np.asarray(pf_ref))
    np.testing.assert_array_equal(
        np.asarray(feed_new), np.asarray(dec_ref.at[2].set(pf_ref[0])))
    _assert_pools((k_new, v_new), (k_ref, v_ref), (kv_k, kv_v),
                  _dests(tables, [(0, 9), (1, 6)]
                         + [(3, p) for p in range(11)]))
