"""The held experts' fast path (``ops/moe.py`` ``held_expert_ffn``) visits
the experts that have a row and no other: the same sum as the exact slow path
and as a plain sum over the pairs, for both forms of an expert, stacked
weights or one layer's, whatever set of experts the routing touches; matrices
of an expert without a row are never read (NaN in them changes nothing); and
the turns its loop makes are the ``touched`` count every ``moe_block`` puts in
the step record."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.models import afmoe, joyai, longcat, nemotron_h, qwen3_next
from runbookai_tpu.models.llama import CONFIGS
from runbookai_tpu.ops import moe

E, D, F, K, TOKENS = 8, 16, 24, 2, 12
CAP = TOKENS  # the longest queue here: one expert picked by every token
TOUCHED = {"none": (), "one": (5,), "a_few": (1, 4, 6), "all": tuple(range(E))}


def _experts(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    w = {n: jax.random.normal(k, s) / 4 for n, k, s in (
        ("gate", ks[0], (E, D, F)), ("up", ks[1], (E, D, F)), ("down", ks[2], (E, F, D)))}
    return w, jax.random.normal(ks[3], (TOKENS, D)), jax.random.uniform(ks[4], (TOKENS, K))


def _routing(touched):
    """``local`` [TOKENS, K]: a token's picks differ, every expert of
    ``touched`` gets a pair and no other does (E: not held here)."""
    local = np.full((TOKENS, K), E, np.int32)
    for t in range(TOKENS if touched else 0):
        for j in range(min(K, len(touched))):
            local[t, j] = touched[(t * K + j) % len(touched)]
    return jnp.asarray(local)


def _by_pair(w, u, local, weights, relu2):
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


@pytest.fixture
def products(monkeypatch):
    """The shape of ``x`` at every ``expert_ffn`` the device RAN (a branch
    not taken and a turn not made run none)."""
    ran, real = [], moe.expert_ffn

    def counted(x, *w):
        jax.debug.callback(lambda shape=x.shape: ran.append(shape))
        return real(x, *w)

    monkeypatch.setattr(moe, "expert_ffn", counted)

    def shapes():
        jax.effects_barrier()
        return list(ran)

    return shapes


@pytest.mark.parametrize("touched", list(TOUCHED), ids=list(TOUCHED))
@pytest.mark.parametrize("stacked", [False, True], ids=["one_layer", "stacked"])
@pytest.mark.parametrize("relu2", [True, False], ids=["relu2", "swiglu"])
def test_the_fast_path_visits_the_experts_that_have_a_row(relu2, stacked, touched, products):
    w, u, weights = _experts()
    held = TOUCHED[touched]
    local = _routing(held)
    assert sorted(set(np.asarray(local).ravel()) - {E}) == list(held)
    idle = jnp.asarray([e not in held for e in range(E)])
    # an expert without a row holds NaN: whoever reads it, shows
    nan = {k: jnp.where(idle[:, None, None], jnp.nan, v) for k, v in w.items()}

    def call(ws, cap):
        if stacked:
            ws = {k: jnp.stack([jnp.full_like(v, jnp.nan), v]) for k, v in ws.items()}
        return moe.held_expert_ffn(u, local, weights, None if relu2 else ws["gate"], ws["up"],
                                   ws["down"], cap, layer=jnp.int32(1) if stacked else None)

    fast, overflow = call(nan, CAP)
    ran = products()
    by_pair = _by_pair(w, u, local, weights, relu2)
    assert int(overflow) == 0 and np.isfinite(np.asarray(fast)).all()
    np.testing.assert_allclose(np.asarray(fast), by_pair, atol=1e-5, rtol=0)
    # the turns are the experts touched; over half of them, one batched product
    if len(held) > moe.BATCHED_ABOVE * E:
        assert ran == [(E, CAP, D)]
    else:
        assert ran == [(CAP, D)] * len(held)
    ids, n_touched = moe.touched_first(jnp.sum(jax.nn.one_hot(local.reshape(-1), E,
                                                              dtype=jnp.int32), axis=0))
    assert int(n_touched) == len(held) and tuple(np.asarray(ids)[:len(held)]) == held
    if held:  # the exact slow path (a queue of one slot overflows) reads every expert
        slow, overflow = call(w, 1)
        assert int(overflow) == 1
        np.testing.assert_allclose(np.asarray(slow), np.asarray(fast), atol=1e-5, rtol=0)


@pytest.mark.parametrize("counts, ids, n", [
    ([0, 0, 0, 0], [0, 0, 0, 0], 0), ([0, 3, 0, 1], [1, 3, 0, 0], 2),
    ([2, 1, 1, 9], [0, 1, 2, 3], 4), ([0, 0, 0, 7], [3, 0, 0, 0], 1)],
    ids=["none", "two", "all", "last"])
def test_touched_first(counts, ids, n):
    got, k = moe.touched_first(jnp.asarray(counts, jnp.int32))
    assert (list(np.asarray(got)), int(k)) == (ids, n)
    assert got.dtype == jnp.int32 and k.dtype == jnp.int32


def _layers(module, name, held):
    cfg = dataclasses.replace(CONFIGS[name], n_experts_held=held, first_expert=0)
    return cfg, module.init_params(jax.random.PRNGKey(5), cfg, jnp.float32)["layers"]


MOE_BLOCKS = {
    "longcat": ("longcat-test", longcat), "joyai": ("joyai-test", joyai),
    "qwen3_next": ("qwen3-next-test", qwen3_next), "nemotron_h": ("nemotron-h-test", nemotron_h),
    "afmoe": ("afmoe-test", afmoe),
}


@pytest.mark.parametrize("family", list(MOE_BLOCKS))
def test_the_turns_are_the_touched_count_of_the_step_record(family, products):
    """One row of twenty live (a decode pass beside its free slots; its four
    picks touch at most half of the eight held): the loop makes as many turns
    as the block counts experts ``touched``."""
    name, module = MOE_BLOCKS[family]
    cfg, w = _layers(module, name, 8)
    u = jax.random.normal(jax.random.PRNGKey(6), (20, cfg.hidden_size), jnp.float32)
    live = jnp.arange(20) < 1
    if module is longcat:
        lp = {k: w[k][0] for k in ("router", "router_bias") + longcat.EXPERT_LEAVES}
        _, counts = longcat.moe_block(u, live, lp, cfg)
    else:
        _, counts = module.moe_block(u, live, w, 1, cfg)
    counts = dict(zip(longcat.EXPERT_COUNTS, map(int, counts)))
    ran = products()
    assert all(len(shape) == 2 for shape in ran)  # no batched product over all eight
    shared = sum("s_up" in name for name in w)  # the expert every token runs, once a block
    assert counts["overflow"] == 0 and 0 < counts["touched"] <= moe.BATCHED_ABOVE * 8
    assert len(ran) - shared == counts["touched"], (ran, counts)
