"""Latent (MLA) attention over pages, in the absorbed form.

The cache holds, a token and attention sublayer, the compressed latent
``c`` (``kv_lora_rank`` values, after its norm and scale) and ONE rotated
key ``kr`` (``qk_rope_head_dim`` values) for all heads: two pools. The
latents are ``[sublayers, tokens, 1, rank]``, written by
:func:`runbookai_tpu.ops.attention.write_kv_pages_batch` like any K side.
The rotated keys are ``[layers, tokens, 1, 2 * rope]``: a layer's TWO
attention sublayers keep theirs side by side in one row, written whole by
the same writer (the second sublayer writes ``[kr_0 | kr_1]``, the first
``[kr_0 | 0]``: both see the same tokens), because a 64-value row is half
a TPU lane tile — XLA kept a ``[.., 64]`` pool in a transposed layout for
its scatter and copied all of it back for every gather, and a scatter into
half a row became a loop with three copies of the pool (both seen in the
compiled program). The bytes are the same. Nothing per head is ever stored, and nothing per head is
expanded from it: the up-projection ``W_kvb`` is folded into the query and
into the output instead (:func:`absorb_queries`, :func:`expand_values`), so
a call reads each live token's latent once and uses it twice — as the key
(against the absorbed query) and as the value (weighted sum, expanded
after).

One function serves decode (T = 1) and a prefill chunk (T > 1). It walks
the page tables a block of pages at a time with a running softmax, and
stops at the longest context of the batch (a dynamic trip count), not at
``max_seq_len``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from runbookai_tpu.ops.attention import NEG_INF


def absorb_queries(q_nope: jnp.ndarray, w_kvb: jnp.ndarray) -> jnp.ndarray:
    """``q_nope`` [B, T, H, nope] against the keys' half of ``w_kvb``
    [rank, H, nope + v]: the query in latent space, [B, T, H, rank]."""
    nope = q_nope.shape[-1]
    return jnp.einsum("bthn,chn->bthc", q_nope, w_kvb[..., :nope],
                      preferred_element_type=jnp.float32).astype(q_nope.dtype)


def expand_values(o_lat: jnp.ndarray, w_kvb: jnp.ndarray, nope: int) -> jnp.ndarray:
    """The attended latent [B, T, H, rank] through the values' half of
    ``w_kvb``: [B, T, H, v]."""
    return jnp.einsum("bthc,chv->bthv", o_lat, w_kvb[..., nope:],
                      preferred_element_type=jnp.float32).astype(o_lat.dtype)


def latent_paged_attention(
    q_lat: jnp.ndarray,  # [B, T, H, rank] absorbed queries
    q_rope: jnp.ndarray,  # [B, T, H, rope] rotated
    c_pool: jnp.ndarray,  # [sublayers, tokens, 1, rank] the WHOLE pool
    r_pool: jnp.ndarray,  # [layers, tokens, 1, 2 * rope]: sublayer 2l + i in half i
    sublayer,  # traced scalar: which attention sublayer's rows
    page_tables: jnp.ndarray,  # [B, max_pages(+1)]
    ctx_lens: jnp.ndarray,  # [B] cached tokens per row, this chunk's included
    q_positions: jnp.ndarray,  # [B, T]
    *,
    page_size: int,
    scale: float,
    block_pages: int = 8,
) -> jnp.ndarray:
    """Causal softmax attention in latent space; returns the attended
    latent [B, T, H, rank] (:func:`expand_values` makes values of it).

    Whole pages are gathered straight out of the carried pool, at
    ``sublayer * num_pages + page`` of its page view: no slice of the pool
    is taken, and the view keeps each row's values on the minor axis (a
    view that folded a page's rows INTO the minor axis was a copy of the
    pool, every call), so nothing pool-shaped is copied. Scores and the running softmax are
    float32; the two products run on the pool's stored values.
    """
    b, t, h, rank = q_lat.shape
    rope = q_rope.shape[-1]
    # Pages, as a view that splits the token axis only: [layers * pages,
    # page_size, values]. A page of 16 bf16 rows is one tile of the pool's
    # layout, so the view is a bitcast and a page is one contiguous read.
    num_pages = c_pool.shape[1] // page_size
    c_pages = c_pool.reshape(-1, page_size, rank)
    r_pages = r_pool.reshape(-1, page_size, 2 * rope)
    base, r_base = sublayer * num_pages, (sublayer // 2) * num_pages
    half = (sublayer % 2) * rope
    max_pages = page_tables.shape[1]
    block_tokens = block_pages * page_size
    act = q_lat.dtype
    token_off = jnp.arange(block_tokens)
    # The longest context bounds the walk; rows that end earlier are masked.
    n_blocks = (jnp.max(ctx_lens) + block_tokens - 1) // block_tokens

    def block_step(blk, carry):
        m, l, acc = carry  # [B,T,H], [B,T,H], [B,T,H,rank]
        page_idx = jnp.minimum(blk * block_pages + jnp.arange(block_pages),
                               max_pages - 1)
        phys = page_tables[:, page_idx]  # [B, block_pages]
        cb = c_pages[base + phys].reshape(b, block_tokens, rank).astype(act)
        rb = jax.lax.dynamic_slice_in_dim(
            r_pages[r_base + phys].reshape(b, block_tokens, 2 * rope),
            half, rope, axis=2).astype(act)
        cache_pos = blk * block_tokens + token_off
        mask = ((cache_pos[None, None, :] < ctx_lens[:, None, None])
                & (cache_pos[None, None, :] <= q_positions[:, :, None]))
        scores = (jnp.einsum("bthc,bsc->bths", q_lat, cb,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bthr,bsr->bths", q_rope, rb,
                               preferred_element_type=jnp.float32)) * scale
        scores = jnp.where(mask[:, :, None, :], scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bths,bsc->bthc", p.astype(act), cb,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((b, t, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, t, h), jnp.float32)
    acc0 = jnp.zeros((b, t, h, rank), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block_step, (m0, l0, acc0))
    return (acc / jnp.maximum(l[..., None], 1e-30)).astype(act)
