"""The Mamba-2 rule (``ops/ssm.py``) against the naive loop over tokens:
the chunked form (SSD) and the recurrent step agree with it and with each
other — across a block boundary, from a non-zero state, with pads masked —
and the step over a pool layer touches the live rows only. The two
forms of an expert (``ops/moe.py``): the two-matrix ``relu^2`` form on both
paths of the held experts' dispatch, and the SwiGLU form bit for bit as it
was."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.ops import gated_delta, moe, ssm

H, P, G, N = 4, 8, 2, 16


def _inputs(b, t, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (b, t, H, P)), b=jax.random.normal(ks[1], (b, t, G, N)),
        c=jax.random.normal(ks[2], (b, t, G, N)),
        dt=jax.nn.softplus(jax.random.normal(ks[3], (b, t, H)) - 2.0),
        a=-jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0),
        d=jax.random.normal(ks[5], (H,)), s0=jax.random.normal(ks[6], (b, H, P, N)))


def _naive(x, b, c, dt, a, d, s0):
    """The rule as written, a token and a head at a time."""
    x, b, c, dt, a, d = (np.asarray(v, np.float64) for v in (x, b, c, dt, a, d))
    s = np.asarray(s0, np.float64).copy()
    y = np.zeros(x.shape)
    for r in range(x.shape[0]):
        for t in range(x.shape[1]):
            for h in range(H):
                g = h // (H // G)
                s[r, h] = np.exp(dt[r, t, h] * a[h]) * s[r, h] + dt[r, t, h] * np.outer(
                    x[r, t, h], b[r, t, g])
                y[r, t, h] = s[r, h] @ c[r, t, g] + d[h] * x[r, t, h]
    return y, s


@pytest.mark.parametrize("t, live, chunk", [(16, 16, 16), (40, 40, 16), (48, 21, 16), (1, 1, 16)],
                         ids=["one_block", "across_blocks", "pads_inside_a_run", "one_token"])
def test_the_chunked_rule_is_the_step_is_the_naive_loop(t, live, chunk):
    v = _inputs(2, t, t)
    mask = jnp.broadcast_to(jnp.arange(t)[None] < live, (2, t))
    dt = ssm.mask_pads(v["dt"], mask)
    y_ref, s_ref = _naive(v["x"][:, :live], v["b"][:, :live], v["c"][:, :live],
                          v["dt"][:, :live], v["a"], v["d"], v["s0"])
    # the step, token after token
    def token(s, xs):
        y_i, s = ssm.ssm_step(*xs, v["a"], v["d"], s)
        return s, y_i

    s, ys = jax.lax.scan(token, v["s0"], tuple(
        jnp.moveaxis(a, 1, 0) for a in (v["x"], v["b"], v["c"], dt)))
    np.testing.assert_allclose(np.asarray(s), s_ref, atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(ys, 0, 1))[:, :live], y_ref,
                               atol=2e-5, rtol=0)
    if t == 1:
        return
    pad = -t % chunk
    padded = [jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
              for a in (v["x"], v["b"], v["c"], dt)]
    y, s = jax.jit(ssm.ssm_chunk, static_argnums=7)(*padded, v["a"], v["d"], v["s0"], chunk)
    np.testing.assert_allclose(np.asarray(s), s_ref, atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(y)[:, :live], y_ref, atol=2e-5, rtol=0)


def test_a_run_in_two_calls_is_the_run_at_once():
    """The state carried between calls: 24 tokens, then 16 from the state
    the first call left."""
    v = _inputs(1, 40, 7)
    run = (v["x"], v["b"], v["c"], v["dt"])
    chunked = jax.jit(ssm.ssm_chunk, static_argnums=7)
    whole, s_whole = chunked(*(jnp.pad(a, ((0, 0), (0, 8)) + ((0, 0),) * (a.ndim - 2))
                                     for a in run), v["a"], v["d"], v["s0"], 16)
    first, s1 = chunked(*(jnp.pad(a[:, :24], ((0, 0), (0, 8)) + ((0, 0),) * (a.ndim - 2))
                                for a in run), v["a"], v["d"], v["s0"], 16)
    second, s2 = chunked(*(a[:, 24:] for a in run), v["a"], v["d"], s1, 16)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_whole), atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([first[:, :24], second], 1)),
                               np.asarray(whole[:, :40]), atol=2e-5, rtol=0)


@pytest.mark.parametrize("live_rows", [[1, 6], [0, 1, 2, 3, 4, 5, 6, 7], []],
                         ids=["two_rows", "every_row", "none"])
def test_the_step_over_a_pool_layer_runs_the_live_rows_in_place(live_rows):
    """``ssm_step_live``: the rows that are live get ``ssm_step``'s state
    and output; every other row's state, and the other layers, are bit for
    bit as they were."""
    slots, layers, layer = 8, 3, 1
    v = _inputs(slots, 1, 3)
    pool = jax.random.normal(jax.random.PRNGKey(9), (layers, slots, H, P, N))
    live = jnp.zeros((slots,), bool).at[jnp.asarray(live_rows, jnp.int32)].set(True)
    dt = ssm.mask_pads(v["dt"], live[:, None])[:, 0]
    y, new = jax.jit(lambda p, lyr: ssm.ssm_step_live(
        p, lyr, live, v["x"][:, 0], v["b"][:, 0], v["c"][:, 0], dt, v["a"], v["d"]))(
            pool, layer)
    y_ref, s_ref = ssm.ssm_step(v["x"][:, 0], v["b"][:, 0], v["c"][:, 0], dt, v["a"], v["d"],
                                pool[layer])
    dead = [r for r in range(slots) if r not in live_rows]
    np.testing.assert_allclose(np.asarray(new[layer])[live_rows], np.asarray(s_ref)[live_rows],
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(y)[live_rows], np.asarray(y_ref)[live_rows],
                               atol=1e-5, rtol=0)
    assert np.array_equal(np.asarray(new[layer])[dead], np.asarray(pool[layer])[dead])
    assert np.array_equal(np.asarray(new)[[0, 2]], np.asarray(pool)[[0, 2]])
    assert not np.asarray(y)[dead].any()  # nothing ran there


def test_the_gate_comes_before_the_norm_and_the_norm_is_a_groups():
    y = jax.random.normal(jax.random.PRNGKey(0), (5, 32))
    z = jax.random.normal(jax.random.PRNGKey(1), (5, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (32,))
    out = np.asarray(ssm.gated_group_norm(y, z, w, 4, 1e-5))
    v = np.asarray(y * jax.nn.silu(z)).reshape(5, 4, 8)
    ref = (v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5)).reshape(5, 32) * np.asarray(w)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=0)
    one_group = np.asarray(ssm.gated_group_norm(y, z, w, 1, 1e-5))
    assert np.abs(one_group - out).max() > 1e-2


def test_the_convolution_takes_a_bias():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    zero = jnp.zeros((2, 3, 6))
    n = jnp.asarray([10, 10])
    plain, tail = gated_delta.causal_conv_tail(x, zero, w, n)
    biased, tail_b = gated_delta.causal_conv_tail(x, zero, w, n, bias=bias)
    pre = sum(jnp.pad(x, ((0, 0), (3, 0), (0, 0)))[:, i:i + 10] * w[i] for i in range(4))
    np.testing.assert_allclose(np.asarray(biased), np.asarray(jax.nn.silu(pre + bias)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(jax.nn.silu(pre)), atol=1e-6)
    assert np.array_equal(np.asarray(tail), np.asarray(tail_b))  # the tail holds INPUTS


# --------------------------------------------------------------------------- #
# ops/moe.py: an expert's two forms through one dispatch                       #
# --------------------------------------------------------------------------- #

E, D, F, K, TOKENS = 4, 16, 24, 2, 12


def _experts(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = {n: jax.random.normal(k, s) / 4 for n, k, s in (
        ("gate", ks[0], (E, D, F)), ("up", ks[1], (E, D, F)), ("down", ks[2], (E, F, D)))}
    u = jax.random.normal(ks[3], (TOKENS, D))
    local = jax.random.randint(ks[4], (TOKENS, K), 0, E + 1)  # E: not held here
    weights = jax.random.uniform(ks[5], (TOKENS, K))
    return w, u, local, weights


def _by_hand(w, u, local, weights, relu2):
    out = np.zeros((TOKENS, D), np.float32)
    for t in range(TOKENS):
        for j in range(K):
            e = int(local[t, j])
            if e == E:
                continue
            if relu2:
                y = np.square(np.maximum(u[t] @ w["up"][e], 0)) @ w["down"][e]
            else:
                y = (jax.nn.silu(u[t] @ w["gate"][e]) * (u[t] @ w["up"][e])) @ w["down"][e]
            out[t] += float(weights[t, j]) * np.asarray(y)
    return out


@pytest.mark.parametrize("cap, path", [(TOKENS, 0), (1, 1)], ids=["slotted", "overflow"])
@pytest.mark.parametrize("relu2", [True, False], ids=["relu2", "swiglu"])
def test_both_forms_of_an_expert_on_both_paths_of_the_dispatch(relu2, cap, path):
    w, u, local, weights = _experts()
    out, overflow = moe.held_expert_ffn(u, local, weights, None if relu2 else w["gate"],
                                        w["up"], w["down"], cap)
    assert int(overflow) == path
    np.testing.assert_allclose(np.asarray(out), _by_hand(w, u, local, weights, relu2),
                               atol=1e-5, rtol=0)
    # stacked weights and a layer: the same sum
    stack = {k: jnp.stack([v * 0, v]) for k, v in w.items()}
    out_l, _ = moe.held_expert_ffn(u, local, weights, None if relu2 else stack["gate"],
                                   stack["up"], stack["down"], cap, layer=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(out_l), np.asarray(out), atol=1e-6, rtol=0)


def test_the_swiglu_form_is_bit_for_bit_what_it_was():
    """The expression ``held_expert_ffn`` and ``shared_expert`` ran before
    an expert had a second form, written out here as it stood."""
    from runbookai_tpu.models.llama import qmm

    w, u, *_ = _experts(1)
    x = jnp.broadcast_to(u[None, :4], (E, 4, D)).astype(jnp.bfloat16)
    wb = {k: v.astype(jnp.bfloat16) for k, v in w.items()}
    before = qmm(jax.nn.silu(qmm(x, wb["gate"])) * qmm(x, wb["up"]), wb["down"])
    assert np.array_equal(np.asarray(moe.expert_ffn(x, wb["gate"], wb["up"], wb["down"])),
                          np.asarray(before))
    one = qmm(jax.nn.silu(qmm(x[0], wb["gate"][0])) * qmm(x[0], wb["up"][0]), wb["down"][0])
    assert np.array_equal(np.asarray(moe.shared_expert(x[0], wb["gate"][0], wb["up"][0],
                                                       wb["down"][0])), np.asarray(one))
    two = moe.shared_expert(x[0], None, wb["up"][0], wb["down"][0])
    ref = jnp.square(jax.nn.relu(x[0] @ wb["up"][0])) @ wb["down"][0]
    assert np.array_equal(np.asarray(two), np.asarray(ref))
