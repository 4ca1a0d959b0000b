"""Nemotron-H (``models/nemotron_h.py``): the program against the plain
reference of its benchmark block (``benchmark/blocks/nemotron_h/forward.py``:
float32, the Mamba-2 rule token by token, no cache, no state pool), at
``nemotron-h-test`` size on seeded weights — LOGITS, not sampled tokens —
the two kinds of state through every step program, and the share tied to
the model.

Tolerances. The program here runs float32 weights, pools and activations,
as the reference does, so the two differ only in the order of float32 sums:
the chunked rule (blocks of 16, the state carried between blocks and
between calls) against the recurrence, a blockwise running softmax against
one softmax, the slotted expert dispatch against a sum over experts.
``ATOL`` = 2e-3 is some forty times the largest difference seen (4.5e-6 to
5e-5 on logits of magnitude 4); a wrong decay, a stale convolution tail, a
state not restored or a dropped expert moves a logit by 1e-1 or more.
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
from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.models import nemotron_h
from runbookai_tpu.models.llama import CONFIGS
from runbookai_tpu.ops import moe
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["nemotron-h-test"]
REF_CFG = dataclasses.asdict(CFG)
BLOCK = blocks.load("nemotron_h")
ATOL = 2e-3
PS, PAGES, SEED = 16, 48, 11
STATIC = dict(page_size=PS, block_pages=2, attn_impl="xla", mesh=None, qmm_impl="xla")


@pytest.fixture(scope="module")
def params():
    """As served: ``load_or_init`` with no checkpoint (``init_params``, then
    the control tokens' head columns quiet)."""
    from runbookai_tpu.models import hf_loader

    return hf_loader.load_or_init("nemotron-h-test", None, seed=SEED, dtype=jnp.float32)[1]


def _ids(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, size=n)]


def _pools(slots=4):
    (lk, hk, dk), _ = CFG.kv_pool_spec
    shape = (lk, PAGES * PS, hk, dk)  # two buffers: the step programs donate both
    return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
            nemotron_h.empty_state(CFG, slots))


def _reference(params, ids, n_last):
    return np.asarray(BLOCK.forward.logits_and_margins(params, REF_CFG, ids, n_last)[0])


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
    lp = params["layers"]
    a = np.exp(np.asarray(lp["a_log"]))
    assert 1 <= a.min() and a.max() <= 16 and np.ptp(a) > 1  # A ~ U(1, 16), drawn
    dt = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert 1e-3 <= dt.min() and dt.max() <= 0.1001  # dt_bias is softplus^-1 of (0.001, 0.1)
    assert float(jnp.abs(lp["conv"]).max()) <= 0.5 and float(jnp.abs(lp["conv_bias"]).max()) > 0
    assert "e_gate" not in lp and "s_gate" not in lp  # two matrices an expert


def test_the_pattern_is_single_mixer_layers_run_by_their_runs():
    whole = CONFIGS["nemotron-3-nano-30b-a3b"]
    assert (whole.n_kind("M"), whole.n_kind("E"), whole.n_kind("*")) == (23, 23, 6)
    assert (whole.d_inner, whole.conv_channels) == (4096, 6144)  # NOT expand x hidden
    assert whole.kv_pool_spec[0] == (6, 2, 128)
    (s_shape, s_dtype), (c_shape, c_dtype) = whole.state_pool_spec
    assert s_shape == (23, 64, 64, 128) and s_dtype == c_dtype == jnp.float32
    assert c_shape == (23, 3, 6144)
    assert whole.total_params == pytest.approx(31.58e9, rel=1e-3)
    plan = nemotron_h.layer_plan(whole.hybrid_override_pattern)
    assert plan == (("one", "M"), ("groups", (2, 3, 3, 3, 3, 4)), ("pairs", 4), ("one", "E"))
    # the plan visits every layer of every kind once, in the pattern's order
    for cfg in (whole, CFG):
        seen = []
        layer = {k: (lambda c, i, k=k: seen.append((k, i)) or c) for k in "ME*"}
        with jax.disable_jit():
            nemotron_h.run_plan(nemotron_h.layer_plan(cfg.hybrid_override_pattern), 0, layer)
        at = dict.fromkeys("ME*", 0)
        for (kind, i), want in zip(seen, cfg.hybrid_override_pattern, strict=True):
            assert kind == want and int(i) == at[kind]
            at[kind] += 1
    assert {s[0] for s in nemotron_h.layer_plan(CFG.hybrid_override_pattern)} == {
        "one", "groups", "pairs"}
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        dataclasses.replace(CFG, num_hidden_layers=3)


def test_one_full_prefill_matches_the_reference(params):
    ids = _ids(70)
    kv_k, kv_v, state = _pools()
    logits, _, _, _, state, _ = nemotron_h.forward_impl(
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
    chunk, block and page boundaries, into slots 3 and 1 of the state pool —
    then ``_decode_step`` and the 8-step ``_decode_multi`` with the rows in
    those slots: every logit and every greedy token against ONE full pass
    of the reference. The paged pool holds keys and values of the two
    attention layers only, asserted from the live arrays."""
    static = dict(STATIC, attn_impl=attn_impl)
    prompts = [_ids(70, 1), _ids(45, 2)]
    slot_of = [3, 1]
    kv_k, kv_v, state = _pools()
    per_token = (kv_k.nbytes + kv_v.nbytes) / (PAGES * PS)
    assert per_token == CFG.n_kind("*") * 2 * CFG.num_key_value_heads * CFG.head_dim * 4
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
    assert int(experts[:3].sum()) == 2 * 8 * CFG.num_experts_per_tok * CFG.n_kind("E")
    for s, ids in seqs.items():
        full = ids + [int(t) for t in window[s]]
        ref = _reference(params, full[:-1], 8)
        gaps = ref.max(axis=1) - ref[np.arange(8), full[-8:]]
        assert gaps.max() <= ATOL, (s, gaps)
    assert all(float(jnp.abs(a[:, [0, 2]]).max()) == 0 for a in state)  # free slots untouched


def test_the_pallas_walk_gives_xlas_decode_pass_and_mixed_step(params):
    """``attn_impl="pallas"`` against ``"xla"``, the forwards called as the
    step programs call them: a decode pass with free slots among the live
    ones, and a mixed step with decode rows beside one filled and one
    unfilled prefill row (``tests/recurrent_walks.py``)."""
    import recurrent_walks

    recurrent_walks.check_decode_pass_and_mixed_step(
        nemotron_h, CFG, params, _pools(), _ids, ATOL)


@pytest.mark.parametrize("mixed, attn_impl", [
    (False, "xla"), (True, "xla"), (True, "pallas")],
    ids=["split", "mixed", "mixed-pallas"])
def test_the_engine_serves_the_references_tokens(params, mixed, attn_impl,
                                                 monkeypatch):
    """Through ``EngineCore`` — admission into a slot of the state pool,
    chunked prefill from and into it, the mixed (ragged) dispatch with
    decode rows beside prefill chunks BY SEGMENT or the split one,
    ``_decode_multi``'s windows: every served token is the reference's best
    within ``ATOL``. Requests arrive a step apart so that chunks meet
    decoding rows."""
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
    assert all("state" in s for s in recs) and any(s.get("experts") for s in recs)
    # what the decode walk reads: the pages the dispatch's rows held
    assert all(s["kv_pages_live"] > 0 for s in recs
               if s["program"] in ("_decode_multi", "_mixed_step"))
    assert sum(s["state"]["snapshots_taken"] for s in recs) == m["state_snapshots_taken"]
    assert max(s["state"]["slots_live"] for s in recs) == 4


def test_a_restored_snapshot_gives_the_cold_logits(params):
    """A prefix hit: the same request served from a restored snapshot and
    served cold, by their top log-probabilities at every generated
    position, and against the reference."""
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


def test_the_shares_add_up_to_the_whole_layer(params):
    """Guide, section 4: the expert parts of ALL four shares, with the
    shared expert — which every share computes alike — counted once, equal
    the uncut layer (the reference's, over every expert)."""
    whole = dataclasses.replace(CFG, n_experts_held=CFG.n_routed_experts, first_expert=0)
    w = nemotron_h.init_params(jax.random.PRNGKey(5), whole, jnp.float32)["layers"]
    u = jax.random.normal(jax.random.PRNGKey(6), (24, CFG.hidden_size), jnp.float32)
    live = jnp.ones((24,), bool)
    m_whole, counts = nemotron_h.moe_block(u, live, w, 1, whole)
    assert int(counts[2]) == 0  # nothing is absent from the uncut layer
    ref = BLOCK.forward.moe(u, w["router"][1], w["router_bias"][1], w["e_up"][1], w["e_down"][1],
                            w["s_up"][1], w["s_down"][1], top_k=CFG.num_experts_per_tok,
                            scale=CFG.routed_scaling_factor, first=0, lowp=None)
    np.testing.assert_allclose(np.asarray(m_whole), np.asarray(ref), atol=1e-5, rtol=0)
    shared = moe.shared_expert(u, None, w["s_up"][1], w["s_down"][1])
    held_n, parts = CFG.n_experts_held, 0
    for first in range(0, CFG.n_routed_experts, held_n):
        share = dataclasses.replace(CFG, first_expert=first)
        sw = dict(w, **{k: w[k][:, first:first + held_n] for k in ("e_up", "e_down")})
        m_share, c = nemotron_h.moe_block(u, live, sw, 1, share)
        assert int(c[0] + c[2]) == 24 * CFG.num_experts_per_tok and int(c[1]) == 0
        parts = parts + (m_share - shared)
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(m_whole),
                               atol=1e-5, rtol=0)
    chosen, weight = moe.route_sigmoid(u, w["router"][1], w["router_bias"][1],
                                       CFG.num_experts_per_tok, CFG.routed_scaling_factor)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 2.5, atol=1e-5)  # norm_topk_prob x 2.5
    assert len(set(map(int, chosen[0]))) == CFG.num_experts_per_tok


def test_attention_takes_no_positions(params):
    """No rotary embedding: the attention layer's output for a query is a
    function of WHICH keys precede it, not of where they sit. The block's
    attention over a sequence equals the same over the sequence behind a
    different prefix of the same keys... shifted by pads: rows of a shifted
    copy agree."""
    lp = params["layers"]
    x = jax.random.normal(jax.random.PRNGKey(0), (256, CFG.hidden_size))
    statics = dict(n_heads=CFG.num_attention_heads, n_kv=CFG.num_key_value_heads,
                   hd=CFG.head_dim, lowp=None)
    a = BLOCK.forward.attention(x, lp["wq"][0], lp["wk"][0], lp["wv"][0], lp["wo"][0], **statics)
    # permuting the PAST of the last query leaves its output as it was
    perm = jnp.concatenate([jax.random.permutation(jax.random.PRNGKey(1), 255), jnp.asarray([255])])
    b = BLOCK.forward.attention(x[perm], lp["wq"][0], lp["wk"][0], lp["wv"][0], lp["wo"][0],
                                **statics)
    np.testing.assert_allclose(np.asarray(a[255]), np.asarray(b[255]), atol=1e-5, rtol=0)
    assert "rope" not in nemotron_h.attention_inputs.__code__.co_names


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
    no = CFG.unsupported(lora=True, model_axis=8, seq_axis=2, kv_dtype=jnp.int8,
                         quantized=True, speculative=True, draft=True)
    assert len(no) == 7 and "model axis of 8" in " ".join(no)
    assert "rolled back" in no[0] and "int8 weight-only" in no[-1]
    # an fp8 pool is served: the benchmark's served control runs it
    assert CFG.unsupported(lora=False, model_axis=1, seq_axis=1, kv_dtype=jnp.float8_e4m3fn,
                           quantized=False) == []


def test_a_checkpoint_of_the_family_is_refused_by_name(tmp_path):
    from runbookai_tpu.models import hf_loader

    (tmp_path / "config.json").write_text('{"model_type": "nemotron_h"}')
    with pytest.raises(NotImplementedError, match="nemotron-h"):
        hf_loader.load_or_init("nemotron-h-test", str(tmp_path))
    with pytest.raises(NotImplementedError, match="nemotron-h"):
        hf_loader.config_from_hf(tmp_path)
    with pytest.raises(ValueError, match="no int8"):
        hf_loader.load_or_init("nemotron-h-test", None, quantize_int8=True)
    cfg, params = hf_loader.load_or_init("nemotron-h-test", None, seed=SEED, dtype=jnp.float32)
    assert cfg is CFG and params["layers"]["e_up"].shape == (6, 8, 64, 32)
    assert params["layers"]["w_in"].shape == (6, 64, 64 + (64 + 2 * 2 * 16))
    assert params["layers"]["w_dt"].shape == (6, 64, 4)
    assert not np.asarray(params["lm_head"])[:, 256:262].any()  # quiet control tokens


def test_the_memory_plan_and_healthz_count_the_state_pools(params):
    from runbookai_tpu.engine.memory_plan import plan_serving
    from runbookai_tpu.model.jax_tpu import JaxTpuClient

    cut = CONFIGS["nemotron-3-nano-ep8"]
    plan = plan_serving(cut, max_seq_len=8192, batch=48, weights="bf16")
    assert plan.kv_bytes_per_token_per_chip == 6 * 2 * 2 * 128 * 2  # 6,144 B
    slot = 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 4)  # 49.9 MB
    assert slot == pytest.approx(49.9e6, rel=2e-3)
    assert plan.state_pool_bytes == (48 + 8) * slot
    assert 10.5e9 < plan.weight_bytes_per_chip < 10.53e9
    assert "recurrent state pool" in plan.explain()
    core = _engine(params)
    info = JaxTpuClient.runtime_info(SimpleNamespace(core=core, cores=[core]))
    small = sum(4 * np.prod(shape) for shape, _ in CFG.state_pool_spec)  # a float32 engine
    assert info["state_pool_bytes"] == (4 + CFG.state_snapshots) * small


def test_the_example_serve_config_is_taken_as_it_stands():
    import json
    from pathlib import Path

    from runbookai_tpu.cli.main import validate_config
    from runbookai_tpu.utils.config import load_config

    root = Path(__file__).resolve().parents[1]
    config = load_config(path=root / "examples" / "serve" / "nemotron-3-nano-ep8.yaml")
    assert [p for p in validate_config(config) if "llm." in p] == []
    cfg = CONFIGS[config.llm.model]
    bench = json.loads((root / "benchmark/configs/nemotron-3-nano-30b-ep8-bf16.json").read_text())
    assert {k: getattr(cfg, k) for k in bench["reduced"]} == {k: bench[k] for k in bench["reduced"]}
    assert dict(bench["llm"]) == {k: getattr(config.llm, k) for k in bench["llm"]}
    plan = json.loads((root / "examples" / "serve" / Path(config.llm.plan).name).read_text())
    assert plan["engine"]["speculative"] is False
