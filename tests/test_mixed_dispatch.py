"""Unified mixed prefill+decode dispatch: parity, dispatch accounting,
ragged attention semantics, and the auto/gating policy.

With prompts and decodes both live, the engine runs ONE ragged forward per
step (engine.py `_mixed_step` / `_run_mixed`): every decode slot feeds one
token, the oldest prefill chunk(s) ride along, and a prefill row completing
its prompt samples its first token inside the same dispatch. These tests
pin the contract that makes that the default on hardware:

- **Byte-identical token streams** vs the classic split path
  (``mixed_dispatch=False``) across stop strings, prefix-cache partial
  hits joining a mixed batch, preemption fired mid-mixed-step,
  speculative/guided forced-sync interplay, and seeded/penalized sampling.
- **Dispatch accounting**: a step serving both phases issues 1 dispatch
  where the split path issues 2 (`mixed_steps` vs
  `prefill_steps`/`decode_dispatches`).
- **Ragged ops**: the flat blocked layout computes exactly what the
  per-sequence reference attention computes, in both the XLA and the
  (interpreted) Pallas path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.engine.engine import _RAGGED_BLOCK, EngineConfig, EngineCore
from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.model.guided import JsonMaskProvider
from runbookai_tpu.models.llama import CONFIGS, init_params
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["llama3-test"]


@pytest.fixture(scope="module")
def setup():
    tok = ByteTokenizer()
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    return tok, params


def make_core(tok, params, *, mixed, guided=False, **kw):
    defaults = dict(
        page_size=4, num_pages=64, max_batch_slots=4, prefill_chunk=8,
        max_seq_len=128, block_pages=4, kv_dtype=jnp.float32,
        mixed_dispatch=mixed,
    )
    defaults.update(kw)
    masker = JsonMaskProvider(tok) if guided else None
    return EngineCore(
        CFG, params, tok, EngineConfig(**defaults),
        mask_fn=masker.mask if masker else None,
        advance_fn=masker.advance if masker else None,
    )


def run_mode(tok, params, specs, *, mixed, guided=False, core_kw=None,
             step_gap=0):
    """Run one engine over ``specs``; returns (core, requests, streams).

    ``step_gap`` staggers submissions so later prompts land while earlier
    requests are already decoding — the condition mixed dispatch exists
    for."""
    core = make_core(tok, params, mixed=mixed, guided=guided,
                     **(core_kw or {}))
    reqs, streams = [], []
    for spec in specs:
        stream = []
        req = EngineRequest(prompt_ids=list(spec["prompt"]),
                            sampling=SamplingParams(**spec["sampling"]))
        req.on_token = stream.append
        reqs.append(req)
        streams.append(stream)
    core.submit(reqs[0])
    for _ in range(step_gap):
        core.step()
    for req in reqs[1:]:
        core.submit(req)
    core.run_until_idle()
    assert core._pending is None, "run_until_idle left a window in flight"
    return core, reqs, streams


def assert_parity(tok, params, specs, *, guided=False, core_kw=None,
                  step_gap=3, expect_mixed=True):
    """Mixed and split dispatch must emit byte-identical streams."""
    c_mix, r_mix, s_mix = run_mode(tok, params, specs, mixed=True,
                                   guided=guided, core_kw=core_kw,
                                   step_gap=step_gap)
    c_split, r_split, s_split = run_mode(tok, params, specs, mixed=False,
                                         guided=guided, core_kw=core_kw,
                                         step_gap=step_gap)
    for a, b, sa, sb in zip(r_mix, r_split, s_mix, s_split):
        oa, ob = c_mix.output_for(a), c_split.output_for(b)
        assert oa.token_ids == ob.token_ids
        assert oa.text == ob.text
        assert oa.finish_reason == ob.finish_reason
        assert sa == sb  # per-request streaming order, token by token
    if expect_mixed:
        assert c_mix.metrics["mixed_steps"] > 0, \
            "mixed dispatch never engaged; test is vacuous"
    assert c_split.metrics["mixed_steps"] == 0
    # Both engines released every page.
    for c in (c_mix, c_split):
        assert not c.kv.seqs
        assert c.kv.allocator.free_pages == c.kv.allocator.num_pages - 1
    return c_mix, c_split


def greedy(prompt, n, **kw):
    return {"prompt": prompt,
            "sampling": dict(temperature=0.0, max_new_tokens=n,
                             stop_token_ids=(), **kw)}


# ------------------------------------------------------------------- parity


def test_parity_staggered_prompts(setup):
    """Prompts arriving while earlier requests decode — the core mixed
    scenario, with staggered finish lengths."""
    tok, params = setup
    specs = [greedy(tok.encode("alpha beta gamma"), 40),
             greedy(tok.encode("incident: api 5xx spike ramping"), 9),
             greedy(tok.encode("restart payments service now"), 6)]
    c_mix, c_split = assert_parity(tok, params, specs)
    # Every generated token is accounted once, discarded overshoot never
    # inflates the counters (first tokens come from prefill/mixed rows).
    emitted = c_mix.metrics["decode_tokens"] + len(specs)
    assert emitted == sum(len(r.all_out_ids) for r in c_mix.finished)


def test_parity_stop_string_and_stop_token(setup):
    """Stops firing mid-stream (one window late under overlap) must
    truncate identically when the first token came from a mixed row."""
    tok, params = setup
    prompt = tok.encode("investigate checkout latency")
    probe = make_core(tok, params, mixed=False)
    ref = EngineRequest(prompt_ids=list(prompt),
                        sampling=SamplingParams(temperature=0.0,
                                                max_new_tokens=24,
                                                stop_token_ids=()))
    probe.submit(ref)
    probe.run_until_idle()
    text = tok.decode(ref.out_ids)
    stop_s = text[6:9]
    assert stop_s
    specs = [greedy(tok.encode("long running neighbor request"), 24),
             {"prompt": prompt,
              "sampling": dict(temperature=0.0, max_new_tokens=24,
                               stop_token_ids=(), stop_strings=(stop_s,))}]
    assert_parity(tok, params, specs)
    stop_t = ref.out_ids[7]
    specs = [greedy(tok.encode("another neighbor keeps going"), 20),
             {"prompt": prompt,
              "sampling": dict(temperature=0.0, max_new_tokens=24,
                               stop_token_ids=(stop_t,))}]
    assert_parity(tok, params, specs)


def test_parity_prefix_cache_partial_hit_joins_mixed_batch(setup):
    """A request whose prompt prefix is already resident starts its
    (shorter) prefill mid-prompt; that partial chunk joins a mixed batch
    and must produce the same stream as the split path."""
    tok, params = setup
    shared = tok.encode("system: you are an SRE agent.")

    def run(mixed):
        core = make_core(tok, params, mixed=mixed, num_pages=128)
        first = EngineRequest(prompt_ids=list(shared),
                              sampling=SamplingParams(temperature=0.0,
                                                      max_new_tokens=4,
                                                      stop_token_ids=()))
        core.submit(first)
        core.run_until_idle()  # publishes the shared prefix pages
        decoder = EngineRequest(prompt_ids=tok.encode("unrelated decode"),
                                sampling=SamplingParams(temperature=0.0,
                                                        max_new_tokens=18,
                                                        stop_token_ids=()))
        core.submit(decoder)
        for _ in range(3):
            core.step()
        joiner = EngineRequest(
            prompt_ids=list(shared) + tok.encode(" summarize the incident"),
            sampling=SamplingParams(temperature=0.0, max_new_tokens=10,
                                    stop_token_ids=()))
        core.submit(joiner)
        core.run_until_idle()
        return core, joiner, decoder

    c_mix, j_mix, d_mix = run(True)
    c_split, j_split, d_split = run(False)
    assert j_mix.cached_tokens > 0  # the partial hit actually happened
    assert j_mix.cached_tokens == j_split.cached_tokens
    assert c_mix.metrics["mixed_steps"] > 0
    assert j_mix.out_ids == j_split.out_ids
    assert d_mix.out_ids == d_split.out_ids


def test_parity_preemption_mid_mixed_step(setup):
    """Pool pressure during a mixed step preempts the youngest decoder
    (draining the overlap window first); recompute must reproduce the
    same streams as the split path."""
    tok, params = setup
    specs = [greedy(tok.encode("x" * 20), 40),
             greedy(tok.encode("y" * 20), 20),
             greedy(tok.encode("w" * 20), 20)]
    core_kw = dict(num_pages=24, admit_headroom_tokens=0)
    c_mix, c_split = assert_parity(tok, params, specs, core_kw=core_kw,
                                   step_gap=4)
    assert c_mix.metrics["preemptions"] + c_split.metrics["preemptions"] > 0


def test_parity_speculative_interplay(setup):
    """Mixed steps never probe speculation (drafting drains the window);
    pure decode steps after the prompt drains must still speculate, and
    streams must match the split path end-to-end."""
    tok, params = setup
    rep = tok.encode("restart the api service; restart the api service; restart")
    specs = [greedy(rep, 40),
             greedy(tok.encode("fresh prompt joining mid-flight"), 10)]
    # step_gap clears the repetitive prompt's 8 prefill chunks and leaves
    # it DECODING (and speculating — k=2 keeps the budget alive) when the
    # fresh prompt joins and forces mixed steps into the middle of it.
    # Seeded weights do not continue the prompt's repetition: a draft
    # exists only where the last served byte happens to repeat an earlier
    # one, about every fourth token here. With the default back-off a run
    # of draftless probes spaces the next ones 2, 4, 8 steps apart, and the
    # mixed engine's four probes all fell between repeats (no draft was
    # ever looked for where one existed — the path was sound, the test's
    # premise was not). Probing every pure decode step meets them.
    c_mix, c_split = assert_parity(
        tok, params, specs,
        core_kw=dict(spec_ngram=1, decode_steps_per_dispatch=2,
                     spec_backoff_rounds=0),
        step_gap=12)
    assert c_mix.metrics["spec_drafted"] > 0
    assert c_split.metrics["spec_drafted"] > 0


def test_guided_keeps_classic_path(setup):
    """Forced-sync consumers pin the step to the classic split path: a
    guided request in the decode batch (or at the prefill head) must
    suppress mixing entirely, and outputs still match the split path."""
    tok, params = setup
    specs = [{"prompt": tok.encode("emit json now:"),
              "sampling": dict(temperature=0.0, max_new_tokens=24,
                               stop_token_ids=(), guided="json")},
             greedy(tok.encode("neighbor prompt arrives later"), 8)]
    c_mix, _ = assert_parity(tok, params, specs, guided=True, step_gap=3,
                             expect_mixed=False)
    assert c_mix.metrics["mixed_steps"] == 0


def test_parity_seeded_penalized_biased(setup):
    """Seeded temperature rows key on (seed, position) — immune to the
    single key split of a mixed step; penalties and logit_bias flow
    through the in-dispatch first-token sampling identically."""
    tok, params = setup
    specs = [{"prompt": tok.encode("seeded sampling one"),
              "sampling": dict(temperature=0.9, top_p=0.9, seed=11,
                               max_new_tokens=14, stop_token_ids=())},
             {"prompt": tok.encode("penalized greedy request"),
              "sampling": dict(temperature=0.0, presence_penalty=0.7,
                               frequency_penalty=0.3, max_new_tokens=12,
                               stop_token_ids=())},
             {"prompt": tok.encode("biased greedy request"),
              "sampling": dict(temperature=0.0, max_new_tokens=10,
                               stop_token_ids=(),
                               logit_bias=((65, 4.0), (66, -100.0)))}]
    assert_parity(tok, params, specs)
    # Regression: a penalized prompt completing INSIDE a mixed dispatch
    # must read a clean count row — the decode-side in-dispatch count add
    # is masked to live slots, else a free slot's garbage-sampled token
    # pollutes the freshly seeded row before the first-token gather
    # (diverged at k=1/forced-sync before the dec_live mask).
    specs = [greedy(tok.encode("anchor request keeps decoding"), 30),
             {"prompt": tok.encode("penalized joiner"),
              "sampling": dict(temperature=0.0, presence_penalty=0.7,
                               frequency_penalty=0.3, max_new_tokens=12,
                               stop_token_ids=())}]
    assert_parity(tok, params, specs,
                  core_kw=dict(overlap_decode=False,
                               decode_steps_per_dispatch=1))


def test_parity_first_token_finishes_request(setup):
    """max_new_tokens=1: the request finishes on the token sampled inside
    the mixed dispatch — slot assignment and immediate finish must agree
    with the split path."""
    tok, params = setup
    specs = [greedy(tok.encode("long neighbor keeps the batch alive"), 16),
             greedy(tok.encode("single token request"), 1)]
    assert_parity(tok, params, specs)


# -------------------------------------------------------- dispatch counting


def test_one_dispatch_per_mixed_step(setup):
    """The acceptance contract: a step serving both phases issues exactly
    ONE dispatch where the split path issues two."""
    tok, params = setup
    for mixed in (True, False):
        core = make_core(tok, params, mixed=mixed)
        dec = EngineRequest(prompt_ids=tok.encode("warm"),
                            sampling=SamplingParams(temperature=0.0,
                                                    max_new_tokens=40,
                                                    stop_token_ids=()))
        core.submit(dec)
        for _ in range(3):
            core.step()
        assert core.decoding  # a live decoder
        core.submit(EngineRequest(
            prompt_ids=tok.encode("prompt burst arriving now"),
            sampling=SamplingParams(temperature=0.0, max_new_tokens=4,
                                    stop_token_ids=())))
        core.step()  # admits; prompt + decode coexist this step
        before = {k: core.metrics[k] for k in
                  ("mixed_steps", "prefill_steps", "decode_dispatches")}
        core.step()
        delta = {k: core.metrics[k] - before[k] for k in before}
        if mixed:
            assert delta == {"mixed_steps": 1, "prefill_steps": 0,
                             "decode_dispatches": 0}, delta
        else:
            assert delta["mixed_steps"] == 0
            assert delta["prefill_steps"] == 1
            assert delta["decode_dispatches"] == 1
        core.run_until_idle()


def test_mixed_token_budget_bounds_prefill_chunk(setup):
    """The per-step prefill share of a mixed dispatch is budget-capped."""
    tok, params = setup
    core = make_core(tok, params, mixed=True,
                     mixed_token_budget=_RAGGED_BLOCK + 4, prefill_chunk=32)
    assert core._mix_pf_tokens == _RAGGED_BLOCK  # budget minus slots, floored
    dec = EngineRequest(prompt_ids=tok.encode("dec"),
                        sampling=SamplingParams(temperature=0.0,
                                                max_new_tokens=60,
                                                stop_token_ids=()))
    core.submit(dec)
    for _ in range(3):
        core.step()
    big = EngineRequest(prompt_ids=tok.encode("b" * 40),
                        sampling=SamplingParams(temperature=0.0,
                                                max_new_tokens=4,
                                                stop_token_ids=()))
    core.submit(big)
    core.step()
    pos = {big.prefill_pos}
    while big.state.value == "prefill":
        p0 = big.prefill_pos
        core.step()
        assert big.prefill_pos - p0 <= _RAGGED_BLOCK
        pos.add(big.prefill_pos)
    assert len(pos) > 2  # the prompt really advanced in bounded chunks
    core.run_until_idle()
    assert len(big.out_ids) == 4


# ------------------------------------------------------------ policy/probe


def test_auto_policy_off_on_cpu(setup):
    tok, params = setup
    auto = make_core(tok, params, mixed=None)
    assert auto._mixed is False  # CPU: compute scales with padded tokens
    forced = make_core(tok, params, mixed=True)
    assert forced._mixed is True


def _hist_count(text):
    lines = [line for line in text.splitlines()
             if line.startswith("runbook_mixed_tokens_per_dispatch_count")]
    return int(lines[0].split()[-1]) if lines else 0


def test_mixed_metrics_registered_and_observed(setup):
    tok, params = setup
    from runbookai_tpu.utils.metrics import get_registry

    count0 = _hist_count(get_registry().render())  # process-global registry
    core, _, _ = run_mode(tok, params,
                          [greedy(ByteTokenizer().encode("warm decode"), 40),
                           greedy(ByteTokenizer().encode("joining prompt"), 6)],
                          mixed=True, step_gap=3)
    assert core.metrics["mixed_steps"] > 0
    assert core.metrics["mixed_tokens"] >= core.metrics["mixed_steps"]
    assert core.metrics["mixed_time_s"] > 0
    text = core.registry.render()
    for name in ("runbook_mixed_dispatch_total",
                 "runbook_mixed_tokens_total",
                 "runbook_mixed_time_seconds_total",
                 "runbook_mixed_tokens_per_dispatch_bucket",
                 "runbook_prefill_dispatch_total",
                 "runbook_decode_dispatch_total"):
        assert name in text, name
    assert (f"runbook_mixed_dispatch_total {core.metrics['mixed_steps']}"
            in text)
    # The histogram actually observed this run's dispatches (it is
    # process-global, so earlier engines' observations persist — delta).
    assert _hist_count(text) - count0 == core.metrics["mixed_steps"]


# --------------------------------------------------------------- ragged ops


def _ragged_case(seed=0):
    """A 3-row mixed batch (decode row, chunk row, short chunk row) plus
    the per-row reference inputs, on a tiny shared page pool."""
    rng = np.random.default_rng(seed)
    page_size, n_kv, n_q, hd = 4, 2, 4, 8
    num_pages, max_pages = 16, 4
    k_flat = rng.standard_normal(
        (num_pages * page_size, n_kv, hd)).astype(np.float32)
    v_flat = rng.standard_normal(
        (num_pages * page_size, n_kv, hd)).astype(np.float32)
    # Rows: ctx 7 decode row (1 query @ pos 6), ctx 8 chunk row (8 queries
    # @ 0..7), ctx 5 chunk row (3 queries @ 2..4, cache partially warm).
    tables = np.array([[1, 2, 0, 0], [3, 4, 0, 0], [5, 6, 0, 0]], np.int32)
    ctx = np.array([7, 8, 5], np.int32)
    rows, qpos = [], []
    rows += [0] * 1 + [0] * 7          # decode row padded to one block
    qpos += [6] + [99] * 7
    rows += [1] * 8                     # full block
    qpos += list(range(8))
    rows += [2] * 3 + [2] * 5           # partial block
    qpos += [2, 3, 4] + [99] * 5
    n = len(rows)
    q = rng.standard_normal((n, n_q, hd)).astype(np.float32)
    real = [0] + list(range(8, 16)) + [16, 17, 18]  # non-pad flat indices
    return (page_size, jnp.asarray(q), jnp.asarray(k_flat),
            jnp.asarray(v_flat), jnp.asarray(tables), jnp.asarray(ctx),
            jnp.asarray(np.array(qpos, np.int32)),
            jnp.asarray(np.array(rows, np.int32)), real)


def _reference_rows(page_size, q, k_flat, v_flat, tables, ctx, qpos, rows,
                    real):
    """Per-sequence paged_attention over each row alone = the semantics
    the ragged entries must reproduce."""
    from runbookai_tpu.ops.attention import paged_attention

    out = {}
    for r in range(tables.shape[0]):
        idx = [i for i in real if int(rows[i]) == r]
        if not idx:
            continue
        qr = q[jnp.asarray(idx)][None]  # [1, T, n_q, hd]
        ref = paged_attention(qr, k_flat, v_flat, tables[r][None],
                              ctx[r][None],
                              qpos[jnp.asarray(idx)][None], page_size,
                              block_pages=2)
        for j, i in enumerate(idx):
            out[i] = np.asarray(ref[0, j])
    return out


def test_ragged_paged_attention_matches_reference():
    from runbookai_tpu.ops.attention import ragged_paged_attention

    case = _ragged_case()
    page_size, q, k_flat, v_flat, tables, ctx, qpos, rows, real = case
    out = ragged_paged_attention(q, k_flat, v_flat, tables, ctx, qpos, rows,
                                 page_size, block_pages=2, ragged_block=8)
    ref = _reference_rows(*case)
    for i, want in ref.items():
        np.testing.assert_allclose(np.asarray(out[i]), want, rtol=1e-5,
                                   atol=1e-5)


def test_pallas_ragged_attention_matches_reference():
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention,
    )

    case = _ragged_case(seed=1)
    page_size, q, k_flat, v_flat, tables, ctx, qpos, rows, real = case
    out = paged_ragged_attention(q, k_flat, v_flat, tables, ctx, qpos, rows,
                                 page_size=page_size, ragged_block=8,
                                 interpret=True)
    ref = _reference_rows(*case)
    for i, want in ref.items():
        np.testing.assert_allclose(np.asarray(out[i]), want, rtol=1e-4,
                                   atol=1e-4)


def test_forward_ragged_matches_forward_impl(setup):
    """The ragged forward entry must reproduce forward_impl's last-token
    logits for the same sequences (decode row + prefill chunk row)."""
    _, params = setup
    from runbookai_tpu.models.llama import forward_impl, forward_ragged_impl

    page_size, rq = 4, _RAGGED_BLOCK
    num_pages = 16
    pool_shape = (CFG.n_layers, num_pages * page_size, CFG.n_kv_heads,
                  CFG.head_dim)
    rng = np.random.default_rng(0)
    kv_k = jnp.asarray(rng.standard_normal(pool_shape), jnp.float32)
    kv_v = jnp.asarray(rng.standard_normal(pool_shape), jnp.float32)
    tables = jnp.asarray([[1, 2, 0], [3, 4, 0], [0, 0, 0]], jnp.int32)
    toks_dec = jnp.asarray([[7]], jnp.int32)     # decode row, ctx 5, pos 4
    toks_pf = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)  # chunk, ctx 5
    ref_dec, _, _ = forward_impl(params, CFG, toks_dec,
                                 jnp.asarray([[4]], jnp.int32), kv_k, kv_v,
                                 tables[:1], jnp.asarray([5], jnp.int32),
                                 page_size=page_size, block_pages=2)
    ref_pf, _, _ = forward_impl(params, CFG, toks_pf,
                                jnp.arange(5, dtype=jnp.int32)[None],
                                kv_k, kv_v, tables[1:2],
                                jnp.asarray([5], jnp.int32),
                                page_size=page_size, block_pages=2)
    # Flat mixed layout: decode block + one prefill block, pads → row 2.
    trash = 2 * page_size  # tables have 2 real columns + trash column
    tokens = np.zeros((2 * rq,), np.int32)
    positions = np.full((2 * rq,), trash, np.int32)
    row_ids = np.array([0] * rq + [1] * rq, np.int32)
    tokens[0] = 7
    positions[0] = 4
    tokens[rq: rq + 5] = [1, 2, 3, 4, 5]
    positions[rq: rq + 5] = range(5)
    out, _, _ = forward_ragged_impl(
        params, CFG, jnp.asarray(tokens), jnp.asarray(positions),
        jnp.asarray(row_ids), kv_k, kv_v, tables,
        jnp.asarray([5, 5, 0], jnp.int32),
        jnp.asarray([0, rq + 4], jnp.int32), page_size=page_size,
        block_pages=2, ragged_block=rq)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(ref_dec[0, -1]), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(out[1]),
                               np.asarray(ref_pf[0, -1]), rtol=2e-4,
                               atol=2e-4)
