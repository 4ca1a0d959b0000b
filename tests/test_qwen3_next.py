"""Qwen3-Next (``models/qwen3_next.py``): the program against the plain
reference of its benchmark block (``benchmark/blocks/qwen3next/forward.py``:
float32, the delta rule token by token, no cache, no state pool), at
``qwen3-next-test`` size on seeded weights — LOGITS, not sampled tokens —
the two kinds of state through every step program, and the share tied to
the model.

Tolerances. The program here runs float32 weights, pools and activations,
as the reference does, so the two differ only in the order of float32 sums:
the chunked delta rule (a triangular solve a block of 64, the state carried
between blocks and between calls) against the recurrence, a blockwise
running softmax against one softmax, the slotted expert dispatch against a
sum over experts. ``ATOL`` = 5e-3 is ten times the largest difference seen
(4.8e-4 on logits of magnitude 4, after 150 tokens of recurrence); a wrong
decay, a stale convolution tail, a state not restored or a dropped expert
moves a logit by 1e-1 or more. Served tokens are held to the same number as
a gap to the reference's best logit, the benchmark's ``logit_gap``.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import blocks
from runbookai_tpu.engine.engine import (
    EngineConfig,
    EngineCore,
    _decode_multi,
    _decode_step,
    _prefill_step,
)
from runbookai_tpu.engine.kv_cache import KVCacheManager, StateSnapshots
from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.models import qwen3_next
from runbookai_tpu.models.llama import CONFIGS
from runbookai_tpu.ops import gated_delta, moe
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["qwen3-next-test"]
REF_CFG = dataclasses.asdict(CFG)
BLOCK = blocks.load("qwen3next")
ATOL = 5e-3
PS, PAGES, SEED = 16, 48, 11
STATIC = dict(page_size=PS, block_pages=2, attn_impl="xla", mesh=None, qmm_impl="xla")


@pytest.fixture(scope="module")
def params():
    """As served: ``load_or_init`` with no checkpoint (``init_params``, then
    the control tokens' head columns quiet)."""
    from runbookai_tpu.models import hf_loader

    return hf_loader.load_or_init("qwen3-next-test", None, seed=SEED, dtype=jnp.float32)[1]


def _ids(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, size=n)]


def _pools(slots=4):
    (lk, hk, dk), _ = CFG.kv_pool_spec
    shape = (lk, PAGES * PS, hk, dk)  # two buffers: the step programs donate both
    return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
            qwen3_next.empty_state(CFG, slots))


def _reference(params, ids, n_last):
    return np.asarray(BLOCK.forward.logits(params, REF_CFG, ids, n_last))


def _gap(params, req) -> float:
    """The benchmark's ``logit_gap`` of one served request."""
    served = list(req.all_out_ids)
    ref = _reference(params, (list(req.prompt_ids[:len(req.prompt_ids) - len(req.folded_out_ids)])
                              + served)[:-1], len(served))
    return float((ref.max(axis=1) - ref[np.arange(len(served)), served]).max())


def _engine(params, cfg=CFG, **over):
    ecfg = dict(page_size=PS, num_pages=128, max_batch_slots=4, prefill_chunk=64,
                max_seq_len=1024, speculative=False, kv_dtype=jnp.float32,
                decode_steps_per_dispatch=8, mixed_dispatch=False)
    ecfg.update(over)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(**ecfg), seed=0)


def _request(rid, prompt, max_new=12, **sampling):
    return EngineRequest(request_id=rid, prompt_ids=list(prompt), sampling=SamplingParams(
        max_new_tokens=max_new, temperature=0.0, **sampling))


def _serve(core, requests):
    for r in requests:
        core.submit(r)
    core.run_until_idle()
    return requests


def test_the_blocks_weights_are_the_programs(params):
    """The reference makes its own weights from the seed: the same bits."""
    theirs = BLOCK.weights.make_params(REF_CFG, SEED, False, jnp.float32)
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), params, theirs)))
    a = np.exp(np.asarray(params["layers"]["a_log"]))
    assert 0 < a.min() and a.max() <= 16 and np.ptp(a) > 1  # A ~ U(0, 16), drawn
    assert float(jnp.abs(params["layers"]["conv"]).max()) <= 0.5


def test_the_period_is_three_linear_layers_then_one_full():
    assert (CFG.n_periods, CFG.n_linear_layers) == (2, 6)
    whole = CONFIGS["qwen3-next-80b-a3b-instruct"]
    assert (whole.n_periods, whole.n_linear_layers, whole.conv_channels) == (12, 36, 8192)
    assert whole.kv_pool_spec[0] == (12, 2, 256)
    (s_shape, s_dtype), (c_shape, c_dtype) = whole.state_pool_spec
    assert s_shape == (36, 32, 128, 128) and s_dtype == c_dtype == jnp.float32
    assert c_shape == (36, 3, 8192)
    assert whole.total_params == pytest.approx(79.67e9, rel=1e-3)


@pytest.mark.parametrize("t, live", [(64, 64), (100, 100), (128, 70), (1, 1)],
                         ids=["one_block", "ragged_tail", "pads_inside_a_run", "one_token"])
def test_the_chunked_rule_is_the_recurrence(t, live):
    """``chunk_gated_delta`` (blocks of 64, a triangular solve a block) and
    ``gated_delta_step`` against the four lines of the rule token by token,
    from a non-zero state; pads (``mask_pads``) leave the state alone."""
    b, h, dk, dv = 2, 3, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    q = gated_delta.l2_normalise(jax.random.normal(ks[0], (b, t, h, dk))) / 4.0
    k = gated_delta.l2_normalise(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jax.random.uniform(ks[3], (b, t, h), minval=0.0, maxval=3.0)
    beta = jax.random.uniform(ks[4], (b, t, h))
    s0 = jax.random.normal(ks[5], (b, h, dk, dv))
    mask = jnp.broadcast_to(jnp.arange(t)[None] < live, (b, t))
    k_m, g_m, beta_m = gated_delta.mask_pads(k, g, beta, mask)

    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s_ref, o_ref = jax.lax.scan(token, s0, tuple(
        jnp.moveaxis(a[:, :live], 1, 0) for a in (q, k, v, g, beta)))
    if t == 1:
        o, s = gated_delta.gated_delta_step(q[:, 0], k_m[:, 0], v[:, 0], g_m[:, 0],
                                            beta_m[:, 0], s0)
        o = o[:, None]
    else:
        pad = -t % gated_delta.BLOCK
        o, s = gated_delta.chunk_gated_delta(*(
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k_m, v, g_m, beta_m)), s0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(o[:, :live]),
                               np.asarray(jnp.moveaxis(o_ref, 0, 1)), atol=2e-5, rtol=0)


def test_the_convolution_carries_its_last_inputs():
    """A run convolved in two calls, the second from the first's tail and
    with pads after its real tokens, is the run convolved at once."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    zero = jnp.zeros((2, 3, 8))
    whole, tail = gated_delta.causal_conv_tail(x, zero, w, jnp.asarray([24, 24]))
    first, t1 = gated_delta.causal_conv_tail(x[:, :10], zero, w, jnp.asarray([10, 10]))
    padded = jnp.pad(x[:, 10:], ((0, 0), (0, 6), (0, 0)))
    second, t2 = gated_delta.causal_conv_tail(padded, t1, w, jnp.asarray([14, 14]))
    np.testing.assert_allclose(np.asarray(jnp.concatenate([first, second[:, :14]], 1)),
                               np.asarray(whole), atol=1e-6)
    np.testing.assert_allclose(np.asarray(t2), np.asarray(x[:, -3:]), atol=0)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(x[:, -3:]), atol=0)
    _, kept = gated_delta.causal_conv_tail(padded, t1, w, jnp.asarray([0, 0]))
    np.testing.assert_allclose(np.asarray(kept), np.asarray(t1), atol=0)  # a dead row


def test_one_full_prefill_matches_the_reference(params):
    ids = _ids(70)
    kv_k, kv_v, state = _pools()
    logits, _, _, _, state, _ = qwen3_next.forward_impl(
        params, CFG, jnp.asarray([ids], jnp.int32),
        jnp.arange(70, dtype=jnp.int32)[None], kv_k, kv_v,
        jnp.arange(1, 9, dtype=jnp.int32)[None], jnp.asarray([70]),
        page_size=PS, block_pages=2, state=state, state_rows=jnp.asarray([2]))
    np.testing.assert_allclose(np.asarray(logits[0]), _reference(params, ids, 70),
                               atol=ATOL, rtol=0)
    assert all(float(jnp.abs(a[:, [0, 1, 3]]).max()) == 0 for a in state)  # slot 2 only
    assert all(float(jnp.abs(a[:, 2]).max()) > 0 for a in state)


ATTN_IMPLS = ["xla", "pallas"]  # XLA's page walk; the Pallas decode walk, interpreted


@pytest.mark.parametrize("attn_impl", ATTN_IMPLS)
def test_chunked_prefill_then_decode_through_pool_and_state(params, attn_impl):
    """Two rows prefilled in chunks of 32 by ``_prefill_step`` — across
    chunk and page boundaries, into slots 3 and 1 of the state pool — then
    ``_decode_step`` and the 8-step ``_decode_multi`` with the rows in
    those slots: every logit and every greedy token against ONE full pass
    of the reference. The paged pool holds keys and values of the two
    full-attention layers only, asserted from the live arrays."""
    static = dict(STATIC, attn_impl=attn_impl)
    prompts = [_ids(70, 1), _ids(45, 2)]
    slot_of = [3, 1]
    kv_k, kv_v, state = _pools()
    per_token = (kv_k.nbytes + kv_v.nbytes) / (PAGES * PS)
    assert per_token == CFG.n_periods * 2 * CFG.num_key_value_heads * CFG.head_dim * 4
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6, 0], [7, 8, 9, 10, 11, 12, 0]], jnp.int32)
    trash = 6 * PS
    last = {}
    for lo in range(0, 96, 32):
        tokens = np.zeros((2, 32), np.int32)
        positions = np.full((2, 32), trash, np.int32)
        ctx, last_idx = np.ones((2,), np.int32), np.zeros((2,), np.int32)
        rows = np.full((2,), 4, np.int32)  # a row with nothing to do: dropped
        for r, p in enumerate(prompts):
            n = max(0, min(32, len(p) - lo))
            if n:
                tokens[r, :n], positions[r, :n] = p[lo:lo + n], np.arange(lo, lo + n)
                ctx[r], last_idx[r], rows[r] = lo + n, n - 1, slot_of[r]
        out, kv_k, kv_v, experts, state = _prefill_step(
            params, CFG, jnp.asarray(tokens), kv_k, kv_v, jnp.asarray(positions),
            tables, jnp.asarray(ctx), jnp.asarray(last_idx),
            jnp.zeros((2,), jnp.int32), state=state, state_rows=jnp.asarray(rows), **static)
        assert experts.shape == (5,) and int(experts[1]) == 0  # no identity experts
        for r, p in enumerate(prompts):
            if lo < len(p) <= lo + 32:
                last[r] = np.asarray(out[r])
    for r, p in enumerate(prompts):
        np.testing.assert_allclose(last[r], _reference(params, p, 1)[0], atol=ATOL, rtol=0)
    # decode: rows live in THEIR slots; slots 0 and 2 are free
    table4 = np.zeros((4, 7), np.int32)
    seqs = {slot_of[r]: list(p) for r, p in enumerate(prompts)}
    for r in range(2):
        table4[slot_of[r]] = np.asarray(tables[r])
        seqs[slot_of[r]].append(int(np.argmax(last[r])))

    def feed():
        toks, pos, ctx = (np.zeros((4, 1), np.int32), np.zeros((4, 1), np.int32),
                          np.zeros((4,), np.int32))
        for s, ids in seqs.items():
            toks[s, 0], pos[s, 0], ctx[s] = ids[-1], len(ids) - 1, len(ids)
        return jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(ctx)

    greedy = (jnp.zeros((4,), jnp.float32), jnp.ones((4,), jnp.float32),
              jnp.zeros((4,), jnp.int32))
    toks, pos, ctx = feed()
    tok, logits, kv_k, kv_v, _, experts, state = _decode_step(
        params, CFG, toks, pos, kv_k, kv_v, jnp.asarray(table4), ctx, *greedy,
        jax.random.PRNGKey(0), None, jnp.zeros((4,), jnp.int32), state=state, **static)
    for s, ids in seqs.items():
        np.testing.assert_allclose(np.asarray(logits[s]), _reference(params, ids, 1)[0],
                                   atol=ATOL, rtol=0)
        ids.append(int(tok[s]))
    toks, pos, ctx = feed()
    window, kv_k, kv_v, _, experts, state = _decode_multi(
        params, CFG, toks, pos, kv_k, kv_v, jnp.asarray(table4), ctx, *greedy,
        jax.random.PRNGKey(0), jnp.zeros((4,), jnp.int32), k_steps=8, state=state, **static)
    assert int(experts[:3].sum()) == 2 * 8 * CFG.num_experts_per_tok * CFG.num_hidden_layers
    for s, ids in seqs.items():
        full = ids + [int(t) for t in window[s]]
        ref = _reference(params, full[:-1], 8)
        gaps = ref.max(axis=1) - ref[np.arange(8), full[-8:]]
        assert gaps.max() <= ATOL, (s, gaps)
    assert all(float(jnp.abs(a[:, [0, 2]]).max()) == 0 for a in state)  # free slots untouched


def test_which_rows_take_the_pallas_walk():
    """Static, by shape: one token a row under ``attn_impl="pallas"``, and a
    kv-head axis on Mosaic's tile. A prefill run and a pool of two fp8 heads
    (the benchmark's served fp8 control) keep XLA's walk."""
    S = jax.ShapeDtypeStruct
    q1, q8 = S((4, 1, 16, 256), jnp.bfloat16), S((4, 8, 16, 256), jnp.bfloat16)
    pool, fp8 = (S((3, 1024, 2, 256), dt) for dt in (jnp.bfloat16, jnp.float8_e4m3fn))
    assert qwen3_next.pallas_walks(q1, pool, "pallas")
    assert not qwen3_next.pallas_walks(q1, pool, "xla")
    assert not qwen3_next.pallas_walks(q8, pool, "pallas")
    assert not qwen3_next.pallas_walks(q1, fp8, "pallas")


def test_the_pallas_walk_gives_xlas_decode_pass_and_mixed_step(params):
    """``attn_impl="pallas"`` against ``"xla"``, the forwards called as the
    step programs call them: a decode pass with free slots among the live
    ones, and a mixed step with decode rows beside one filled and one
    unfilled prefill row (``tests/recurrent_walks.py``)."""
    import recurrent_walks

    recurrent_walks.check_decode_pass_and_mixed_step(
        qwen3_next, CFG, params, _pools(), _ids, ATOL)


@pytest.mark.parametrize("mixed, attn_impl", [
    (False, "xla"), (True, "xla"), (True, "pallas")],
    ids=["split", "mixed", "mixed-pallas"])
def test_the_engine_serves_the_references_tokens(params, mixed, attn_impl,
                                                 monkeypatch):
    """Through ``EngineCore`` — admission into a slot of the state pool,
    chunked prefill from and into it, the mixed (ragged) dispatch with
    decode rows beside prefill chunks or the split one, ``_decode_multi``'s
    windows: every served token is the reference's best within ``ATOL``.
    Requests arrive a step apart so that chunks meet decoding rows."""
    # The family calls the decode walk alone: no chunk or ragged kernel is
    # probed for it (``pallas_prefill``).
    from runbookai_tpu.engine import engine
    from runbookai_tpu.ops import paged_attention_pallas

    monkeypatch.setattr(engine, "_probe_pallas_ragged", None)
    monkeypatch.setattr(paged_attention_pallas, "paged_chunk_attention", None)
    engine._probe_pallas_attn_cached.cache_clear()
    core = _engine(params, mixed_dispatch=mixed, attn_impl=attn_impl)
    assert core.ecfg.attn_impl == attn_impl  # the family keeps what was asked
    reqs = [_request(f"r{i}", _ids(n, 3 + i), max_new=14 + 3 * i)
            for i, n in enumerate((150, 40, 200, 97, 64, 130))]
    for r in reqs:
        core.submit(r)
        core.step()
    core.run_until_idle()
    assert (core.metrics["mixed_steps"] > 0) == mixed
    assert [len(r.out_ids) for r in reqs] == [14 + 3 * i for i in range(6)]
    assert max(_gap(params, r) for r in reqs) <= ATOL
    m = core.metrics
    assert m["expert_pairs_zero"] == 0 and m["expert_pairs_absent"] > m["expert_pairs_held"] > 0
    assert m["state_snapshots_taken"] > 0 and m["state_hash_tokens_matched"] == 0
    recs = core.flight.snapshot()
    assert all("state" in s for s in recs)
    # what the decode walk reads: the pages the dispatch's rows held
    assert all(s["kv_pages_live"] > 0 for s in recs
               if s["program"] in ("_decode_multi", "_mixed_step"))
    assert sum(s["state"]["snapshots_taken"] for s in recs) == m["state_snapshots_taken"]
    assert max(s["state"]["slots_live"] for s in recs) == 4


def test_a_request_stopped_mid_dispatch_leaves_its_slot_clean(params):
    """``_decode_multi`` runs a row's tokens past its stop into its slot's
    state (it is not position-addressed). The next request in the SAME
    slot starts from zero all the same: its tokens are the reference's."""
    core = _engine(params, max_batch_slots=1)
    [probe] = _serve(core, [_request("probe", _ids(50, 20), max_new=16)])
    stop = probe.out_ids[3]  # the 3rd token of the first 8-token window
    [stopped] = _serve(core, [_request("stopped", _ids(50, 20), max_new=16,
                                       stop_token_ids=(stop,))])
    assert stopped.out_ids[-1] == stop and len(stopped.out_ids) < 16
    [after] = _serve(core, [_request("after", _ids(61, 21), max_new=12)])
    assert len(after.out_ids) == 12 and _gap(params, after) <= ATOL


def test_n_choices_share_a_prefix_through_snapshots(params):
    """n = 4 choices are four requests with one prompt (``server/
    openai_api.py``). Together and cold they prefill in one batch; the
    next four find the prompt's pages AND the state at a chunk boundary,
    and are restored from it: a fork of a sequence with recurrent state is
    a snapshot copied into another slot."""
    core = _engine(params)
    prompt = _ids(150, 30)
    cold = _serve(core, [_request(f"c{i}", prompt, max_new=10) for i in range(4)])
    assert core.metrics["state_snapshots_restored"] == 0
    warm = _serve(core, [_request(f"w{i}", prompt, max_new=10) for i in range(4)])
    m = core.metrics
    assert m["state_snapshots_restored"] == 4
    # 144 tokens match by hash (9 pages: one token is always prefilled);
    # chunks of 64 left snapshots at 64 and 128: 128 are granted.
    assert (m["state_hash_tokens_matched"], m["state_hash_tokens_granted"]) == (4 * 144, 4 * 128)
    assert [r.cached_tokens for r in warm] == [128] * 4
    assert {tuple(r.out_ids) for r in cold + warm} == {tuple(cold[0].out_ids)}
    assert _gap(params, warm[3]) <= ATOL


def test_a_restored_snapshot_gives_the_cold_logits(params):
    """The same request served from a restored snapshot and served cold,
    by their top log-probabilities at every generated position."""
    shared, tail_a, tail_b = _ids(128, 40), _ids(50, 41), _ids(37, 42)
    warm_core, cold_core = _engine(params), _engine(params)
    _serve(warm_core, [_request("a", shared + tail_a, max_new=4)])
    [warm] = _serve(warm_core, [_request("b", shared + tail_b, max_new=10, logprobs=5)])
    [cold] = _serve(cold_core, [_request("b", shared + tail_b, max_new=10, logprobs=5)])
    assert warm.cached_tokens == 128 and cold.cached_tokens == 0
    assert warm_core.metrics["state_snapshots_restored"] == 1
    assert warm.out_ids == cold.out_ids
    for w, c in zip(warm.out_logprobs, cold.out_logprobs):
        assert [t for t, _ in w["top"]] == [t for t, _ in c["top"]]
        np.testing.assert_allclose([p for _, p in w["top"]], [p for _, p in c["top"]],
                                   atol=1e-4, rtol=0)
    assert _gap(params, warm) <= ATOL


def test_a_hash_match_with_no_snapshot_grants_nothing(params):
    """Pages alone are half the state. With no snapshot pool every match
    is cut to zero, and the request is served right, from the start."""
    bare = dataclasses.replace(CFG, name="qwen3-next-bare", state_snapshots=0)
    core = _engine(params, cfg=bare)
    shared = _ids(128, 50)
    _serve(core, [_request("a", shared + _ids(30, 51), max_new=4)])
    [b] = _serve(core, [_request("b", shared + _ids(30, 52), max_new=8)])
    m = core.metrics
    assert m["state_hash_tokens_matched"] == 128 and m["state_hash_tokens_granted"] == 0
    assert b.cached_tokens == 0 and m["cached_prefix_tokens"] == 0
    assert core.kv.match_prefix(shared + _ids(30, 53)) == 0
    assert _gap(params, b) <= ATOL


def test_preempt_and_resume(params):
    """A preempted sequence gives its slot back; re-admitted, its prompt
    (with what it had generated folded in) matches its own pages and is
    granted them up to a snapshot, recomputed past it."""
    core = _engine(params)
    reqs = [_request("old", _ids(100, 60), max_new=24), _request("young", _ids(90, 61), max_new=24)]
    for r in reqs:
        core.submit(r)
    while not all(len(r.out_ids) >= 5 for r in reqs):
        core.step()
    assert core._preempt_youngest()
    victim = reqs[1]
    assert victim.state_slot is None and victim.slot is None and victim.preemptions == 1
    core.run_until_idle()
    assert core.metrics["state_snapshots_restored"] == 1  # the boundary at 64
    assert core.metrics["state_hash_tokens_granted"] == 64
    assert [len(r.all_out_ids) for r in reqs] == [24, 24]
    assert max(_gap(params, r) for r in reqs) <= ATOL


def test_a_snapshot_leaves_with_its_page_or_before_it():
    """The index alone: a full pool drops a snapshot never restored before
    one that was; a snapshot whose page was recycled is swept."""
    snaps = StateSnapshots(2)
    assert snaps.take(101, page=5) == 0 and snaps.take(102, page=6) == 1
    assert snaps.take(101, page=5) is None  # that boundary has one
    snaps.touch(snaps.lookup(101))
    assert snaps.take(103, page=7) == 1  # 102 went: never restored
    assert snaps.counters["snapshot_evictions"] == 1
    assert snaps.lookup(101) is not None and snaps.lookup(102) is None
    kv = KVCacheManager(n_layers=1, num_pages=8, page_size=4, n_kv_heads=1, head_dim=2,
                        max_seq_len=16, state_snapshots=2)
    tokens = list(range(9))
    kv.add_sequence("s", tokens)
    kv.extend("s", 8)
    assert kv.take_snapshot("s", tokens[:6]) is None  # not a page boundary
    assert kv.take_snapshot("s", tokens[:8]) is not None
    assert kv.match_prefix(tokens) == 8
    kv.release("s")
    kv.allocator.alloc(kv.allocator.free_pages)  # pool pressure recycles the retired pages
    kv.snapshots.sweep(kv.allocator)
    assert len(kv.snapshots) == 0


def test_the_shares_add_up_to_the_whole_layer(params):
    """Guide, section 4: the expert parts of ALL four shares, with the
    shared expert — which every share computes alike — counted once, equal
    the uncut layer's ``MoE(u)`` (the reference's, over every expert)."""
    whole = dataclasses.replace(CFG, n_experts_held=CFG.num_experts, first_expert=0)
    w = qwen3_next.init_params(jax.random.PRNGKey(5), whole, jnp.float32)["layers"]
    u = jax.random.normal(jax.random.PRNGKey(6), (24, CFG.hidden_size), jnp.float32)
    live = jnp.ones((24,), bool)
    m_whole, counts = qwen3_next.moe_block(u, live, w, 1, whole)
    assert int(counts[2]) == 0  # nothing is absent from the uncut layer
    ref = BLOCK.forward.moe(u, w["router"][1], w["e_gate"][1], w["e_up"][1], w["e_down"][1],
                            w["s_gate"][1], w["s_up"][1], w["s_down"][1], w["s_sig"][1],
                            top_k=CFG.num_experts_per_tok, first=0, lowp=None)
    np.testing.assert_allclose(np.asarray(m_whole), np.asarray(ref), atol=1e-5, rtol=0)
    shared = moe.shared_expert(u, w["s_gate"][1], w["s_up"][1], w["s_down"][1], w["s_sig"][1])
    held_n, parts = CFG.n_experts_held, 0
    for first in range(0, CFG.num_experts, held_n):
        share = dataclasses.replace(CFG, first_expert=first)
        sw = dict(w, **{k: w[k][:, first:first + held_n] for k in qwen3_next.EXPERT_LEAVES})
        m_share, c = qwen3_next.moe_block(u, live, sw, 1, share)
        assert int(c[0] + c[2]) == 24 * CFG.num_experts_per_tok and int(c[1]) == 0
        parts = parts + (m_share - shared)
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(m_whole),
                               atol=1e-5, rtol=0)
    chosen, weight = moe.route_renormalised(u, w["router"][1], CFG.num_experts_per_tok)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 1.0, atol=1e-6)  # norm_topk_prob
    assert len(set(map(int, chosen[0]))) == CFG.num_experts_per_tok


@pytest.mark.parametrize("asked, named", [
    (dict(engine_cfg=EngineConfig(num_pages=32)), "prompt-lookup speculation"),
    (dict(engine_cfg=EngineConfig(num_pages=32, speculative=False),
          draft_worker=SimpleNamespace()), "draft-model speculation"),
    (dict(engine_cfg=EngineConfig(num_pages=32, speculative=False, kv_dtype=jnp.int8)),
     "int8 KV pool"),
    (dict(engine_cfg=EngineConfig(num_pages=32, speculative=False),
          lora_registry=SimpleNamespace(stacked=dict)), "LoRA"),
], ids=["speculation", "draft_model", "int8_pool", "lora"])
def test_the_engine_refuses_by_name_what_the_family_does_not_do(params, asked, named):
    with pytest.raises(ValueError, match=named):
        EngineCore(CFG, params, ByteTokenizer(), **asked)


def test_refusals_the_family_states():
    no = CFG.unsupported(lora=True, model_axis=4, seq_axis=2, kv_dtype=jnp.int8,
                         quantized=True, speculative=True, draft=True)
    assert len(no) == 7 and "model axis of 4" in " ".join(no)
    assert "rolled back" in no[0] and "int8 weight-only" in no[-1]
    # an fp8 pool is served: the benchmark's served control runs it
    assert CFG.unsupported(lora=False, model_axis=1, seq_axis=1, kv_dtype=jnp.float8_e4m3fn,
                           quantized=False) == []
    assert CONFIGS["longcat-test"].unsupported(
        lora=False, model_axis=1, seq_axis=1, kv_dtype=jnp.bfloat16, quantized=False,
        speculative=True, draft=True) == []


def test_a_checkpoint_of_the_family_is_refused_by_name(tmp_path):
    from runbookai_tpu.models import hf_loader

    (tmp_path / "config.json").write_text('{"model_type": "qwen3_next"}')
    with pytest.raises(NotImplementedError, match="qwen3-next"):
        hf_loader.load_or_init("qwen3-next-test", str(tmp_path))
    with pytest.raises(NotImplementedError, match="qwen3-next"):
        hf_loader.config_from_hf(tmp_path)
    with pytest.raises(ValueError, match="no int8"):
        hf_loader.load_or_init("qwen3-next-test", None, quantize_int8=True)
    cfg, params = hf_loader.load_or_init("qwen3-next-test", None, seed=SEED, dtype=jnp.float32)
    assert cfg is CFG and params["layers"]["e_gate"].shape == (8, 8, 64, 32)
    assert params["layers"]["w_qkvz"].shape == (6, 64, 2 * 32 + 2 * 64)


@pytest.mark.parametrize("name", ["llama3-test", "longcat-test"])
def test_the_other_families_step_programs_take_no_new_operand(name):
    """``state`` is None for a model whose state is all pages: the step
    programs get no operand for it, hand none back, and lower to the same
    module text whether the keyword is given or not."""
    from runbookai_tpu.models import hf_loader

    cfg, params = hf_loader.load_or_init(name, None, seed=1, dtype=jnp.float32)
    assert cfg.state_pool_spec is None
    core = EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
        num_pages=32, max_batch_slots=2, speculative=False, kv_dtype=jnp.float32))
    assert core._state is None and core.kv.snapshots is None
    b = 2
    greedy = (jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32),
              jnp.zeros((b,), jnp.int32))
    args = (core.params, cfg, jnp.zeros((b, 1), jnp.int32), jnp.zeros((b, 1), jnp.int32),
            core._kv_k, core._kv_v, jnp.zeros((b, core.kv.max_pages_per_seq + 1), jnp.int32),
            jnp.ones((b,), jnp.int32), *greedy, jax.random.PRNGKey(0),
            jnp.zeros((b,), jnp.int32))
    static = dict(page_size=16, block_pages=32, k_steps=8)
    plain = _decode_multi.lower(*args, **static)
    with_kw = _decode_multi.lower(*args, **static, state=None)
    assert plain.as_text() == with_kw.as_text()
    operands = len(jax.tree.leaves(plain.args_info))
    assert operands == len(jax.tree.leaves([a for a in args if a is not cfg]))
    assert len(plain.out_info) == 5  # tokens, two pools, counts, expert counts: no state
    [served] = _serve(core, [_request("r", _ids(30), max_new=9)])
    assert len(served.out_ids) == 9 and "state" not in core.flight.snapshot()[-1]
    assert core.metrics["state_snapshots_taken"] == 0


def test_the_memory_plan_and_healthz_count_the_state_pools(params):
    from runbookai_tpu.engine.memory_plan import plan_serving
    from runbookai_tpu.model.jax_tpu import JaxTpuClient

    cut = CONFIGS["qwen3-next-80b-ep4"]
    plan = plan_serving(cut, max_seq_len=16384, batch=64, weights="bf16")
    assert plan.kv_bytes_per_token_per_chip == 3 * 2 * 2 * 256 * 2  # 6,144 B
    slot = 9 * (32 * 128 * 128 * 4 + 3 * 8192 * 4)  # 19.8 MB
    assert plan.state_pool_bytes == (64 + 16) * slot
    assert 10.84e9 < plan.weight_bytes_per_chip < 10.86e9
    assert "recurrent state pool" in plan.explain()
    assert plan_serving(CONFIGS["llama3-test"], 128).state_pool_bytes == 0
    core = _engine(params)
    info = JaxTpuClient.runtime_info(SimpleNamespace(core=core, cores=[core]))
    small = sum(4 * np.prod(shape) for shape, _ in CFG.state_pool_spec)  # a float32 engine
    assert info["state_pool_bytes"] == (4 + CFG.state_snapshots) * small


def test_runbook_serve_answers_chat_completions_with_the_family(tmp_path):
    """``cli.main.build_server`` — the construction path of ``runbook
    serve`` — with the family's tiny preset and speculation off by a
    serving plan: two chat completions behind one system text over HTTP,
    the second from a snapshot, and what ``/healthz`` and ``/metrics`` say."""
    import http.client
    import json

    from runbookai_tpu.autotune.plan import PlanArtifact, save_plan
    from runbookai_tpu.cli.main import build_server

    plan = save_plan(PlanArtifact(model="qwen3-next-test", topology={},
                                  engine={"speculative": False}), tmp_path / "plan.json")
    path = tmp_path / "serve.yaml"
    path.write_text(json.dumps({"llm": {
        "provider": "jax-tpu", "model": "qwen3-next-test", "dtype": "bfloat16",
        "max_seq_len": 512, "num_pages": 128, "prefill_chunk": 64,
        "max_batch_slots": 4, "plan": str(plan)}}))
    server = build_server(str(path), host="127.0.0.1", port=0)
    server.start_background()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=300)
        system = "You are the on-call assistant. " * 8
        for question in ("why is the pager red", "which deploy was last"):
            conn.request("POST", "/v1/chat/completions", json.dumps({
                "model": "qwen3-next-test", "max_tokens": 6, "temperature": 0,
                "messages": [{"role": "system", "content": system},
                             {"role": "user", "content": question}]}),
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 200, body
            assert body["usage"]["completion_tokens"] >= 1
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["runtime"]["attn_impl"] == "xla"
        assert health["runtime"]["state_pool_bytes"] > 0
        m = health["metrics"]
        assert m["state_snapshots_restored"] == 1
        assert m["state_hash_tokens_granted"] >= 192  # the system text's pages
        assert m["expert_pairs_held"] > 0 and m["expert_pairs_zero"] == 0
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        assert "runbook_state_snapshots_restored_total 1" in text
        assert "runbook_state_hash_tokens_granted_total" in text
    finally:
        server.shutdown()


def test_the_engine_config_without_a_plan_is_refused_at_serve(tmp_path):
    """Speculation is the engine's default; this family says so by name
    before anything is built, rather than serve a rolled-forward state."""
    import json

    from runbookai_tpu.cli.main import build_server

    path = tmp_path / "serve.yaml"
    path.write_text(json.dumps({"llm": {
        "provider": "jax-tpu", "model": "qwen3-next-test", "dtype": "bfloat16",
        "max_seq_len": 512, "num_pages": 64, "max_batch_slots": 2}}))
    with pytest.raises(ValueError, match="prompt-lookup speculation"):
        build_server(str(path), host="127.0.0.1", port=0)


def test_the_example_serve_config_is_taken_as_it_stands():
    from pathlib import Path

    from runbookai_tpu.cli.main import validate_config
    from runbookai_tpu.utils.config import load_config

    root = Path(__file__).resolve().parents[1]
    config = load_config(path=root / "examples" / "serve" / "qwen3-next-80b-ep4.yaml")
    assert [p for p in validate_config(config) if "llm." in p] == []
    cfg = CONFIGS[config.llm.model]
    bench = __import__("json").loads(
        (root / "benchmark" / "configs" / "qwen3-next-80b-ep4-bf16.json").read_text())
    assert {k: getattr(cfg, k) for k in bench["reduced"]} == {k: bench[k] for k in bench["reduced"]}
    assert {k: v for k, v in bench["llm"].items()} == {
        k: getattr(config.llm, k) for k in bench["llm"]}
    assert Path(config.llm.plan).name == "qwen3-next-80b-ep4.plan.json"
    plan = __import__("json").loads((root / "examples" / "serve" / Path(config.llm.plan).name)
                                    .read_text())
    assert plan["engine"]["speculative"] is False


def test_seeded_weights_never_end_an_answer(params):
    tok = ByteTokenizer()
    head = np.asarray(params["lm_head"])
    assert not head[:, sorted(tok.special_ids)].any() and head[:, 255].any()
    core = _engine(params, mixed_dispatch=True)
    reqs = _serve(core, [_request("a", _ids(20, 9), max_new=40),
                         _request("b", _ids(40, 10), max_new=40)])
    assert [len(r.out_ids) for r in reqs] == [40, 40]
