"""On-device token sampling: greedy / temperature / top-p, plus logit masks.

Runs entirely on device inside the decode step (no host round-trip per token
beyond fetching the sampled ids). Grammar masks from guided decoding are
applied as additive ``-inf`` masks before sampling.

The vocabulary-wide sort that top-p / top-k need runs behind a device-side
condition (``needs_sort`` of the call's temperatures, a ``lax.cond`` inside
the one program): a call whose rows are all greedy takes the argmax and
pays for no sort, soft-max, cumulative sum or draw. One sampling row sends
the whole call down the sorted path, whose tokens are what they always were.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def needs_sort(temperature):
    """Whether a call over rows of these temperatures has a row that samples,
    and so needs the sorted path. The device decides by it inside
    :func:`sample_tokens`; the host counts by it from the NumPy array it
    uploads (``metrics["sampler_sorted_calls"]``). No rows: no sort."""
    return temperature.size > 0 and temperature.max() > 0


@partial(jax.jit, static_argnames=())
def sample_tokens(
    logits: jnp.ndarray,  # [B, vocab] float32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B] float32; 0 -> greedy
    top_p: jnp.ndarray,  # [B] float32 in (0, 1]
    mask: jnp.ndarray | None = None,  # [B, vocab] bool, True = allowed
    top_k: jnp.ndarray | None = None,  # [B] int32; 0 -> disabled
    counts: jnp.ndarray | None = None,  # [B, vocab] int32 token counts
    presence: jnp.ndarray | None = None,  # [B] float32 presence penalty
    frequency: jnp.ndarray | None = None,  # [B] float32 frequency penalty
    seeds: jnp.ndarray | None = None,  # [B] int32; -1 -> batch key
    positions: jnp.ndarray | None = None,  # [B] int32 (seeded-key fold)
    bias: jnp.ndarray | None = None,  # [B, vocab] float32 logit_bias
) -> jnp.ndarray:
    """Sample one token per row. Vectorized top-p via sorted-CDF threshold;
    top-k composes with top-p (a token must survive both filters).

    OpenAI-style penalties (opt-in): ``logits - presence*(count>0) -
    frequency*count`` over the request's token history BEFORE masking and
    greedy selection. Per-request ``seeds`` derive each row's key as
    ``fold_in(PRNGKey(seed), position)`` — reproducible for a given
    (seed, position) regardless of batch composition or engine history;
    rows with seed < 0 keep the dispatch key. ``bias`` ([B, vocab],
    OpenAI logit_bias densified host-side) adds BEFORE penalties, masks,
    and greedy selection.

    Everything behind the greedy selection — scaling, the sort, both
    filters, the draw — is the true branch of one ``lax.cond`` on
    :func:`needs_sort`; the false branch is the argmax. Only ``[B, vocab]``
    logits, ``[B]`` parameters and the key cross it."""
    if bias is not None:
        logits = logits + bias
    if counts is not None:
        pen = jnp.zeros_like(logits)
        if presence is not None:
            pen = pen + presence[:, None] * (counts > 0)
        if frequency is not None:
            pen = pen + frequency[:, None] * counts.astype(logits.dtype)
        logits = logits - pen
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sorted_path():
        # Temperature-scaled distribution (guard t=0 to avoid div-by-zero;
        # those rows take ``greedy`` below).
        safe_t = jnp.maximum(temperature, 1e-6)[:, None]
        scaled = logits / safe_t

        # Top-p: sort descending, keep the smallest prefix with cumprob >= top_p.
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
        cumprobs = jnp.cumsum(sorted_probs, axis=-1)
        # Number of tokens kept per row: first index where cumprob >= top_p, +1.
        # Clamp to the vocab: with top_p=1.0, float32 rounding can leave every
        # cumprob fractionally below 1.0, and an unclamped keep would gather the
        # cutoff out of bounds (NaN -> the filter drops ALL tokens, including
        # grammar-allowed ones).
        keep = jnp.sum(cumprobs < top_p[:, None], axis=-1) + 1  # [B]
        keep = jnp.minimum(keep, logits.shape[-1])
        cutoff = jnp.take_along_axis(sorted_logits, (keep - 1)[:, None], axis=-1)  # [B,1]
        filtered = jnp.where(scaled >= cutoff, scaled, NEG_INF)

        if top_k is not None:
            # Keep the k highest-scaled tokens (rank cutoff on the same sorted
            # array); rows with top_k <= 0 keep the whole vocab.
            k_eff = jnp.where(top_k > 0, top_k, logits.shape[-1])
            k_idx = jnp.clip(k_eff - 1, 0, logits.shape[-1] - 1)
            cutoff_k = jnp.take_along_axis(sorted_logits, k_idx[:, None], axis=-1)
            filtered = jnp.where(scaled >= cutoff_k, filtered, NEG_INF)

        if seeds is None:
            sampled = jax.random.categorical(key, filtered, axis=-1)
        else:
            pos = (positions if positions is not None
                   else jnp.zeros_like(seeds))
            rows = jnp.arange(filtered.shape[0], dtype=jnp.uint32)

            def row_key(seed, p, row):
                seeded = jax.random.fold_in(
                    jax.random.PRNGKey(jnp.maximum(seed, 0)), p)
                batch = jax.random.fold_in(key, row)
                return jax.lax.select(seed >= 0, seeded, batch)

            keys = jax.vmap(row_key)(seeds, pos, rows)
            sampled = jax.vmap(
                lambda k, row: jax.random.categorical(k, row))(keys, filtered)
        return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)

    return jax.lax.cond(needs_sort(temperature), sorted_path, lambda: greedy)
