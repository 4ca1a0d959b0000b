"""The sampler's sorted path behind its device-side condition.

``sample_tokens`` sorts the vocabulary only where a row of the call samples
(``needs_sort``: a ``lax.cond`` inside the one program). What it returns must
be, token for token and for the same key, what the body without the condition
returns: that body is kept HERE as the plain reference, not in the package.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.ops.sampling import NEG_INF, needs_sort, sample_tokens

B, V = 6, 97


@jax.jit
def reference_sample_tokens(logits, key, temperature, top_p, mask=None,
                            top_k=None, counts=None, presence=None,
                            frequency=None, seeds=None, positions=None,
                            bias=None):
    """The sampler as it was before the condition: it sorts every call."""
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

    greedy = jnp.argmax(logits, axis=-1)

    safe_t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / safe_t

    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumprobs = jnp.cumsum(sorted_probs, axis=-1)
    keep = jnp.sum(cumprobs < top_p[:, None], axis=-1) + 1
    keep = jnp.minimum(keep, logits.shape[-1])
    cutoff = jnp.take_along_axis(sorted_logits, (keep - 1)[:, None], axis=-1)
    filtered = jnp.where(scaled >= cutoff, scaled, NEG_INF)

    if top_k is not None:
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


def _logits(seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, V), jnp.float32) * 3.0


def _extras(kind):
    """The optional arguments of one call, and the logits ``argmax`` sees."""
    rng = np.random.default_rng(7)
    logits = _logits()
    if kind == "none":
        return {}, logits
    if kind == "mask":
        mask = rng.random((B, V)) < 0.3
        mask[:, 5] = True  # no row without an allowed token
        return {"mask": jnp.asarray(mask)}, jnp.where(mask, logits, NEG_INF)
    if kind == "bias":
        bias = np.zeros((B, V), np.float32)
        bias[np.arange(B), rng.integers(0, V, B)] = 50.0
        return {"bias": jnp.asarray(bias)}, logits + bias
    if kind == "penalties":
        counts = rng.integers(0, 4, (B, V)).astype(np.int32)
        pres = np.full((B,), 0.7, np.float32)
        freq = np.full((B,), 1.3, np.float32)
        seen = logits - pres[:, None] * (counts > 0) - freq[:, None] * counts
        return ({"counts": jnp.asarray(counts), "presence": jnp.asarray(pres),
                 "frequency": jnp.asarray(freq)}, seen)
    if kind == "top_k":
        return {"top_k": jnp.asarray([0, 1, 3, 0, 10, 2], jnp.int32)}, logits
    if kind == "seeds":
        return ({"seeds": jnp.asarray([-1, 11, -1, 12, 13, -1], jnp.int32),
                 "positions": jnp.asarray([4, 9, 2, 30, 7, 1], jnp.int32)}, logits)
    raise AssertionError(kind)


TEMPS = {
    "all_greedy": [0.0] * B,
    "one_sampling": [0.0, 0.0, 0.0, 0.9, 0.0, 0.0],
    "all_sampling": [0.7, 1.0, 1.3, 0.9, 2.0, 0.5],
}


@pytest.mark.parametrize("rows", sorted(TEMPS))
@pytest.mark.parametrize("kind", ["none", "mask", "bias", "penalties", "top_k", "seeds"])
def test_the_condition_serves_the_parents_tokens(kind, rows):
    extras, seen = _extras(kind)
    temps = jnp.asarray(TEMPS[rows], jnp.float32)
    top_p = jnp.asarray([1.0, 0.9, 0.5, 0.95, 1.0, 0.8], jnp.float32)
    for k in range(3):
        key = jax.random.PRNGKey(100 + k)
        got = sample_tokens(_logits(), key, temps, top_p, **extras)
        want = reference_sample_tokens(_logits(), key, temps, top_p, **extras)
        assert got.dtype == jnp.int32 and got.shape == (B,)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        greedy_rows = np.asarray(temps) <= 0
        np.testing.assert_array_equal(
            np.asarray(got)[greedy_rows],
            np.asarray(jnp.argmax(seen, axis=-1))[greedy_rows])


def test_a_seeded_rows_token_does_not_depend_on_its_neighbours():
    """Row 3 samples from its own seed: its token is the same beside greedy
    rows (one sampling row sends the call down the sorted path), beside
    sampling rows, and under another dispatch key."""
    seeds = jnp.asarray([-1, -1, -1, 12, -1, -1], jnp.int32)
    positions = jnp.asarray([4, 9, 2, 30, 7, 1], jnp.int32)
    top_p = jnp.ones((B,), jnp.float32)
    tokens = set()
    for rows in ("one_sampling", "all_sampling"):
        for k in (1, 2):
            tok = sample_tokens(_logits(), jax.random.PRNGKey(k),
                                jnp.asarray(TEMPS[rows], jnp.float32), top_p,
                                seeds=seeds, positions=positions)
            tokens.add(int(tok[3]))
    assert len(tokens) == 1


@pytest.mark.parametrize("temps,want", [
    ([0.0, 0.0, 0.0], False),
    ([0.0, 0.8, 0.0], True),
    ([1.0, 0.5], True),
    ([-1.0, 0.0], False),
    ([], False),
], ids=["all_zero", "one_row", "all_rows", "negative_is_greedy", "no_rows"])
def test_needs_sort_reads_a_numpy_and_a_jax_array_alike(temps, want):
    host = np.asarray(temps, np.float32)
    assert bool(needs_sort(host)) is want
    assert bool(needs_sort(jnp.asarray(host))) is want
    if len(temps):
        assert bool(jax.jit(needs_sort)(jnp.asarray(host))) is want


def test_the_sort_is_in_a_branch_of_the_compiled_sampler():
    """The cheapest form of tests/test_hlo_bytes.py's check: the sampler
    alone, and the reference above as the control that owns a top-level
    sort."""
    from runbookai_tpu.engine.hlo_bytes import sorts_by_conditional

    args = (_logits(), jax.random.PRNGKey(0), jnp.zeros((B,), jnp.float32),
            jnp.ones((B,), jnp.float32))
    inside, outside = sorts_by_conditional(
        sample_tokens.lower(*args).compile().as_text())
    assert inside >= 1 and outside == 0
    inside, outside = sorts_by_conditional(
        reference_sample_tokens.lower(*args).compile().as_text())
    assert inside == 0 and outside >= 1
