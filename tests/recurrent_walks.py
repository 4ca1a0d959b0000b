"""The recurrent families' forwards with ``attn_impl="pallas"`` against
``"xla"``: one decode pass and one mixed step over the same pools, called as
the engine's step programs call them (``tests/test_qwen3_next.py`` and
``tests/test_nemotron_h.py`` bring the family's module, configuration and
pools). The Pallas decode walk runs interpreted here."""

import jax
import jax.numpy as jnp
import numpy as np

PS, RQ = 16, 8
TRASH = 6 * PS  # the last column of a table 7 wide


def _close(got, want, atol):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=atol, rtol=0)


def _both(call, live_rows, atol):
    """``call(attn_impl) -> (logits, kv_k, kv_v, counts, state)`` under both:
    the live rows' logits within ``atol``; the pools and the state the same
    but for the float32 sums' order (what a layer writes hangs on the
    attention below it), and the first attention layer's keys and values,
    written before any walk, bit for bit. The null page (page 0) is left
    out: free slots and pads write there what their rows came to, and a
    free row's attention is zeros under the kernel, whatever under XLA."""
    want, got = call("xla"), call("pallas")
    _close(np.asarray(got[0])[live_rows], np.asarray(want[0])[live_rows], atol)
    for side in (1, 2):
        np.testing.assert_array_equal(np.asarray(got[side][0]), np.asarray(want[side][0]))
        _close(got[side][:, PS:], want[side][:, PS:], 1e-5)
    _close(got[4], want[4], 1e-5)
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))
    return got


def check_decode_pass_and_mixed_step(module, cfg, params, pools, ids, atol):
    """Sequences of 37 and 20 tokens prefilled into slots 3 and 1 (slots 0
    and 2 free), then (1) a decode pass over the four slots and (2) a mixed
    step: the two decode rows beside one FILLED prefill row (20 tokens of a
    new sequence, into slot 0) and one unfilled."""
    kv_k, kv_v, state = pools
    slots = 4
    prompts = {3: ids(37, 1), 1: ids(20, 2)}
    tables = np.zeros((slots + 3, 7), np.int32)  # 4 slots, 2 prefill rows, the null row
    tables[3, :4], tables[1, :4], tables[4, :4] = [1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]
    tokens = np.zeros((2, 48), np.int32)
    positions = np.full((2, 48), TRASH, np.int32)
    for r, s in enumerate((3, 1)):
        n = len(prompts[s])
        tokens[r, :n], positions[r, :n] = prompts[s], np.arange(n)
    _, kv_k, kv_v, _, state, _ = jax.jit(
        lambda p, *a: module.forward_counted(p, cfg, *a, PS, 2, "xla", state=state,
                                             state_rows=jnp.asarray([3, 1])))(
        params, jnp.asarray(tokens), jnp.asarray(positions), kv_k, kv_v,
        jnp.asarray(tables[[3, 1]]), jnp.asarray([37, 20], jnp.int32))

    # (1) the decode pass: a free slot's position is not under its context
    toks, pos, ctx = (np.zeros((slots, 1), np.int32) for _ in range(3))
    for s, p in prompts.items():
        toks[s], pos[s], ctx[s] = 7 + s, len(p), len(p) + 1

    def decode_pass(impl):
        return jax.jit(lambda p, *a: module.forward_counted(
            p, cfg, *a, PS, 2, impl, state=state))(
                params, jnp.asarray(toks), jnp.asarray(pos), kv_k, kv_v,
                jnp.asarray(tables[:slots]), jnp.asarray(ctx[:, 0]))

    logits = _both(decode_pass, [1, 3], atol)[0]
    assert np.isfinite(np.asarray(logits)).all()

    # (2) the mixed step: the engine's flat buffer (`_run_mixed`)
    n_dec, t_pf, chunk = slots * RQ, 32, ids(20, 3)
    n = n_dec + t_pf
    tokens, positions = np.zeros((n,), np.int32), np.full((n,), TRASH, np.int32)
    row_ids = np.full((n,), slots + 2, np.int32)
    ctx_lens = np.zeros((slots + 3,), np.int32)
    for s, p in prompts.items():
        tokens[s * RQ], positions[s * RQ] = 7 + s, len(p)
        row_ids[s * RQ:(s + 1) * RQ], ctx_lens[s] = s, len(p) + 1
    tokens[n_dec:n_dec + 20], positions[n_dec:n_dec + 20] = chunk, np.arange(20)
    row_ids[n_dec:n_dec + 24], ctx_lens[slots] = slots, 20
    state_rows = jnp.asarray([0, 1, 2, 3, 0, slots, slots], jnp.int32)
    sel_idx = jnp.asarray([*(np.arange(slots) * RQ), n_dec + 19, 0], jnp.int32)

    def mixed_step(impl):
        return jax.jit(lambda p, *a: module.forward_ragged_counted(
            p, cfg, *a, PS, 2, impl, ragged_block=RQ, state=state,
            state_rows=state_rows))(
                params, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(row_ids), kv_k, kv_v, jnp.asarray(tables),
                jnp.asarray(ctx_lens), sel_idx)

    _both(mixed_step, [1, 3, 4], atol)
