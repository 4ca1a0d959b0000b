"""LongCat-Flash (``models/longcat.py``): the program against the plain
reference of its benchmark block (``benchmark/blocks/longcat/forward.py``:
float32, expanded attention, no cache), at ``longcat-test`` size on seeded
weights — LOGITS, not sampled tokens — and the share tied to the model.

Tolerances. The program here runs float32 weights, pool and activations, as
the reference does, so the two differ only in the order of float32 sums:
the absorbed form against the expanded one, a blockwise running softmax
against one softmax, the slotted expert dispatch against a sum over
experts. ``ATOL`` = 2e-4 is twenty times the largest difference seen (5e-6
on logits of magnitude 1); a wrong scale, a missed rope, a dropped expert
or a stale page moves a logit by 1e-2 or more. Served tokens are held to
the same number as a gap to the reference's best logit, the benchmark's
``logit_gap`` (a greedy token is the argmax of the PROGRAM's logits).
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
from runbookai_tpu.models import longcat
from runbookai_tpu.models.llama import CONFIGS
from runbookai_tpu.ops import moe
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["longcat-test"]
REF_CFG = dataclasses.asdict(CFG)
BLOCK = blocks.load("longcat")
ATOL = 2e-4
PS, PAGES, SEED = 16, 48, 11
STATIC = dict(page_size=PS, block_pages=2, attn_impl="xla", mesh=None, qmm_impl="xla")


@pytest.fixture(scope="module")
def params():
    """As served: ``load_or_init`` with no checkpoint (``init_params``, then
    the control tokens' head columns quiet)."""
    from runbookai_tpu.models import hf_loader

    return hf_loader.load_or_init("longcat-test", None, seed=SEED, dtype=jnp.float32)[1]


def _ids(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, CFG.vocab_size, size=n)]


def _pools():
    (lk, hk, dk), (lv, hv, dv) = CFG.kv_pool_spec
    return (jnp.zeros((lk, PAGES * PS, hk, dk), jnp.float32),
            jnp.zeros((lv, PAGES * PS, hv, dv), jnp.float32))


def _reference(params, ids, n_last):
    return np.asarray(BLOCK.forward.logits(params, REF_CFG, ids, n_last))


def _greedy(b):
    return (jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32),
            jnp.zeros((b,), jnp.int32))


def test_the_blocks_weights_are_the_programs(params):
    """The reference makes its own weights from the seed: the same bits."""
    theirs = BLOCK.weights.make_params(REF_CFG, SEED, False, jnp.float32)
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), params, theirs)))
    assert float(jnp.abs(params["layers"]["router_bias"]).max()) > 0  # not zeros


def test_one_full_prefill_matches_the_reference(params):
    ids = _ids(70)
    kv_k, kv_v = _pools()
    logits, _, _ = longcat.forward_impl(
        params, CFG, jnp.asarray([ids], jnp.int32),
        jnp.arange(70, dtype=jnp.int32)[None], kv_k, kv_v,
        jnp.arange(1, 9, dtype=jnp.int32)[None], jnp.asarray([70]),
        page_size=PS, block_pages=2)
    ref = _reference(params, ids, 70)
    np.testing.assert_allclose(np.asarray(logits[0]), ref, atol=ATOL, rtol=0)


def test_chunked_prefill_then_decode_through_the_latent_pages(params):
    """Two rows prefilled in chunks of 32 by ``_prefill_step``, then
    ``_decode_step`` and ``_decode_multi``: every logit and every greedy
    token against ONE full pass of the reference. The pool holds 576-like
    values a token and attention sublayer, asserted from the live arrays."""
    prompts = [_ids(70, 1), _ids(45, 2)]
    kv_k, kv_v = _pools()
    per_token = (kv_k.nbytes + kv_v.nbytes) / (PAGES * PS) / CFG.num_layers
    assert per_token == 2 * (CFG.kv_lora_rank + CFG.qk_rope_head_dim) * 4  # float32 pool
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6, 0], [7, 8, 9, 10, 11, 12, 0]], jnp.int32)
    trash = 6 * PS  # the trailing column: the null page
    last = {}
    for lo in range(0, 96, 32):
        tokens = np.zeros((2, 32), np.int32)
        positions = np.full((2, 32), trash, np.int32)
        ctx, last_idx = np.ones((2,), np.int32), np.zeros((2,), np.int32)
        for r, p in enumerate(prompts):
            n = max(0, min(32, len(p) - lo))
            tokens[r, :n], positions[r, :n] = p[lo:lo + n], np.arange(lo, lo + n)
            ctx[r], last_idx[r] = (lo + n, n - 1) if n else (min(lo, len(p)), 0)
        out, kv_k, kv_v, experts = _prefill_step(
            params, CFG, jnp.asarray(tokens), kv_k, kv_v, jnp.asarray(positions),
            tables, jnp.asarray(ctx), jnp.asarray(last_idx),
            jnp.zeros((2,), jnp.int32), **STATIC)
        live = sum(max(0, min(32, len(p) - lo)) for p in prompts)
        assert int(experts[:3].sum()) == live * CFG.moe_topk * CFG.num_layers
        for r, p in enumerate(prompts):
            if lo < len(p) <= lo + 32:  # this chunk ended row r's prompt
                ref = _reference(params, p, 1)
                np.testing.assert_allclose(np.asarray(out[r]), ref[0], atol=ATOL, rtol=0)
                last[r] = int(np.argmax(ref[0]))
    # one decode step: its logits; then eight more: their tokens
    seqs = [p + [last[r]] for r, p in enumerate(prompts)]
    ctx = jnp.asarray([len(s) for s in seqs], jnp.int32)
    tok, logits, kv_k, kv_v, _, experts = _decode_step(
        params, CFG, jnp.asarray([[s[-1]] for s in seqs], jnp.int32), (ctx - 1)[:, None],
        kv_k, kv_v, tables, ctx, *_greedy(2), jax.random.PRNGKey(0), None,
        jnp.zeros((2,), jnp.int32), **STATIC)
    assert int(experts[:3].sum()) == 2 * CFG.moe_topk * CFG.num_layers
    for r, s in enumerate(seqs):
        ref = _reference(params, s, 1)
        np.testing.assert_allclose(np.asarray(logits[r]), ref[0], atol=ATOL, rtol=0)
        s.append(int(tok[r]))
    ctx = jnp.asarray([len(s) for s in seqs], jnp.int32)
    toks, kv_k, kv_v, _, experts = _decode_multi(
        params, CFG, jnp.asarray([[s[-1]] for s in seqs], jnp.int32), (ctx - 1)[:, None],
        kv_k, kv_v, tables, ctx, *_greedy(2), jax.random.PRNGKey(0),
        jnp.zeros((2,), jnp.int32), k_steps=8, **STATIC)
    assert int(experts[:3].sum()) == 8 * 2 * CFG.moe_topk * CFG.num_layers
    for r, s in enumerate(seqs):
        served = [int(t) for t in toks[r]]
        ref = _reference(params, s + served[:-1], 8)
        gaps = ref.max(axis=1) - ref[np.arange(8), served]
        assert gaps.max() <= ATOL, gaps


def _serve(params, mixed, prompts, max_new=10):
    core = EngineCore(CFG, params, ByteTokenizer(), EngineConfig(
        page_size=PS, num_pages=64, max_batch_slots=4, prefill_chunk=32,
        max_seq_len=256, kv_dtype=jnp.float32, speculative=False,
        mixed_dispatch=mixed))
    reqs = [EngineRequest(prompt_ids=p, sampling=SamplingParams(
        temperature=0.0, max_new_tokens=max_new, stop_token_ids=())) for p in prompts]
    core.submit(reqs[0])
    for _ in range(3):  # the first decodes while the others prefill beside it
        core.step()
    for r in reqs[1:]:
        core.submit(r)
    core.run_until_idle()
    return core, reqs


@pytest.mark.parametrize("mixed", [False, True], ids=["split", "mixed"])
def test_the_engine_serves_the_references_tokens(params, mixed):
    """Through ``EngineCore`` — admission, chunked prefill, the mixed
    (ragged) dispatch or the split one, ``_decode_multi``'s windows, page
    growth: every served token is the reference's best within ``ATOL``."""
    core, reqs = _serve(params, mixed, [_ids(50, 3), _ids(90, 4), _ids(33, 5)])
    assert (core.metrics["mixed_steps"] > 0) == mixed
    for r in reqs:
        served = list(r.out_ids)
        assert len(served) == 10
        ref = _reference(params, list(r.prompt_ids) + served[:-1], len(served))
        gaps = ref.max(axis=1) - ref[np.arange(len(served)), served]
        assert gaps.max() <= ATOL, (mixed, gaps)
    m = core.metrics
    pairs = m["expert_pairs_held"] + m["expert_pairs_zero"] + m["expert_pairs_absent"]
    assert pairs > 0 and pairs % (CFG.moe_topk * CFG.num_layers) == 0
    assert 0 < m["experts_touched"] <= m["expert_pairs_held"]
    recs = [s["experts"] for s in core.flight.snapshot() if "experts" in s]
    assert sum(e["held"] + e["zero"] + e["absent"] for e in recs) == pairs
    assert {p for e in recs for p in e["programs"]} <= {
        "_prefill_step", "_mixed_step", "_decode_step", "_decode_multi"}


def test_the_shares_add_up_to_the_whole_layer(params):
    """Guide, section 4: the held parts of ALL the shares, with the identity
    part — which every share computes alike — counted once, equal the uncut
    layer's ``MoE(u)``; and ``b`` changes which experts are chosen without
    entering a weight."""
    whole = dataclasses.replace(CFG, n_experts_held=CFG.n_routed_experts, first_expert=0)
    full = longcat.init_params(jax.random.PRNGKey(5), whole, jnp.float32)["layers"]
    lp = {k: v[0] for k, v in full.items() if k in ("router", "router_bias")}
    lp.update({k: full[k][0] for k in longcat.EXPERT_LEAVES})
    u = jax.random.normal(jax.random.PRNGKey(6), (24, CFG.hidden_size), jnp.float32)
    live = jnp.ones((24,), bool)
    m_whole, counts = longcat.moe_block(u, live, lp, whole)
    assert int(counts[2]) == 0  # nothing is absent from the uncut layer
    # the uncut layer, by the reference's sum over experts
    ref = BLOCK.forward.moe(u, {k: v[None] for k, v in lp.items()}, 0,
                               dataclasses.asdict(whole), None)
    np.testing.assert_allclose(np.asarray(m_whole), np.asarray(ref), atol=1e-5, rtol=0)
    held_n = CFG.n_experts_held
    chosen, w = moe.route_scaled(u, lp["router"], lp["router_bias"], CFG.moe_topk,
                                 CFG.routed_scaling_factor)
    identity = (jnp.sum(jnp.where(chosen >= CFG.n_routed_experts, w, 0.0), -1,
                        keepdims=True) * u)
    parts = 0
    for first in range(0, CFG.n_routed_experts, held_n):
        share = dataclasses.replace(CFG, first_expert=first)
        slp = dict(lp, **{k: lp[k][first:first + held_n] for k in longcat.EXPERT_LEAVES})
        m_share, c = longcat.moe_block(u, live, slp, share)
        assert int(c[:3].sum()) == 24 * CFG.moe_topk
        parts = parts + (m_share - identity)
    np.testing.assert_allclose(np.asarray(parts + identity), np.asarray(m_whole),
                               atol=1e-5, rtol=0)
    # b moves the choice, and only the choice
    no_b, w0 = moe.route_scaled(u, lp["router"], jnp.zeros_like(lp["router_bias"]),
                                CFG.moe_topk, CFG.routed_scaling_factor)
    assert not np.array_equal(np.sort(np.asarray(chosen)), np.sort(np.asarray(no_b)))
    s = jax.nn.softmax(u @ lp["router"], axis=-1)
    np.testing.assert_allclose(np.asarray(w), CFG.routed_scaling_factor * np.asarray(
        jnp.take_along_axis(s, chosen, axis=-1)), rtol=1e-5)


def test_an_overfull_queue_takes_the_exact_slow_path(params):
    """Dropless: with one slot a held expert (``cap`` 1) most queues
    overflow and the call runs every held expert over every token — the
    same sum as with room for all."""
    lp = {k: v[0] for k, v in params["layers"].items()
          if k in longcat.EXPERT_LEAVES + ("router", "router_bias")}
    u = jax.random.normal(jax.random.PRNGKey(8), (40, CFG.hidden_size), jnp.float32)
    chosen, w = moe.route_scaled(u, lp["router"], lp["router_bias"], CFG.moe_topk, 6.0)
    local = chosen - CFG.first_expert
    held = (local >= 0) & (local < CFG.n_experts_held)
    local, w = jnp.where(held, local, CFG.n_experts_held), jnp.where(held, w, 0.0)
    args = (u, local, w, lp["e_gate"], lp["e_up"], lp["e_down"])
    (roomy, fast), (tight, slow) = (moe.held_expert_ffn(*args, cap=40),
                                    moe.held_expert_ffn(*args, cap=1))
    assert int(jnp.max(jnp.sum(jax.nn.one_hot(local.reshape(-1), CFG.n_experts_held), 0))) > 1
    assert (int(fast), int(slow)) == (0, 1)  # which path ran is counted
    np.testing.assert_allclose(np.asarray(tight), np.asarray(roomy), atol=1e-5, rtol=0)
    assert moe.held_capacity(64, 12, 768) == 8 and moe.held_capacity(4, 12, 768) == 4


def test_pads_queue_at_no_expert(params):
    """Sixty identical pad rows beside four live ones, 8 of 248 routed
    experts held: the pads all pick the same experts and would overflow a
    held one's 8 slots on every call; they are sent nowhere, so the call
    stays on the fast path and counts the live rows' pairs alone."""
    wide = dataclasses.replace(CFG, n_routed_experts=248, zero_expert_num=8)
    lp = {k: params["layers"][k][0] for k in longcat.EXPERT_LEAVES}
    lp["router"] = jax.random.normal(jax.random.PRNGKey(3), (CFG.hidden_size, 256)) / 8.0
    lp["router_bias"] = jnp.zeros((256,), jnp.float32)
    live_u = jax.random.normal(jax.random.PRNGKey(9), (4, CFG.hidden_size), jnp.float32)
    for seed in range(200):  # a pad row whose picks include a held expert
        pad = jax.random.normal(jax.random.PRNGKey(100 + seed), (1, CFG.hidden_size))
        chosen, _ = moe.route_scaled(pad, lp["router"], lp["router_bias"], wide.moe_topk, 6.0)
        if bool(jnp.any((chosen >= wide.first_expert)
                        & (chosen < wide.first_expert + wide.n_experts_held))):
            break
    else:
        raise AssertionError("no pad row found that picks a held expert")
    u = jnp.concatenate([live_u, jnp.tile(pad, (60, 1))])
    assert moe.held_capacity(64, wide.moe_topk, 256) == 8
    m, counts = longcat.moe_block(u, jnp.arange(64) < 4, lp, wide)
    alone, alone_counts = longcat.moe_block(live_u, jnp.ones((4,), bool), lp, wide)
    assert dict(zip(longcat.EXPERT_COUNTS, map(int, counts)))["overflow"] == 0
    assert [int(c) for c in counts] == [int(c) for c in alone_counts]
    np.testing.assert_allclose(np.asarray(m[:4]), np.asarray(alone), atol=1e-5, rtol=0)
    _, every = longcat.moe_block(u, jnp.ones((64,), bool), lp, wide)  # pads as live rows
    assert int(every[4]) == 1


@pytest.mark.parametrize("asked, named", [
    (dict(engine_cfg=EngineConfig(num_pages=32, kv_dtype=jnp.int8)), "int8 KV pool"),
    (dict(lora_registry=SimpleNamespace(stacked=dict)), "LoRA"),
], ids=["int8_pool", "lora"])
def test_the_engine_refuses_by_name_what_the_family_does_not_do(params, asked, named):
    with pytest.raises(ValueError, match=named):
        EngineCore(CFG, params, ByteTokenizer(), **asked)


def test_refusals_the_family_states():
    no = CFG.unsupported(lora=True, model_axis=4, seq_axis=2, kv_dtype=jnp.int8,
                         quantized=True)
    assert len(no) == 5 and "model axis of 4" in " ".join(no)
    assert CFG.unsupported(lora=False, model_axis=1, seq_axis=1,
                           kv_dtype=jnp.float8_e4m3fn, quantized=False) == []
    assert CONFIGS["llama3-test"].unsupported(lora=True, model_axis=4) == []


def test_a_checkpoint_of_the_family_is_refused_by_name(tmp_path):
    from runbookai_tpu.models import hf_loader

    (tmp_path / "config.json").write_text('{"model_type": "longcat_flash"}')
    with pytest.raises(NotImplementedError, match="longcat"):
        hf_loader.load_or_init("longcat-test", str(tmp_path))
    with pytest.raises(NotImplementedError, match="longcat"):
        hf_loader.config_from_hf(tmp_path)
    with pytest.raises(ValueError, match="no int8"):
        hf_loader.load_or_init("longcat-test", None, quantize_int8=True)
    cfg, params = hf_loader.load_or_init("longcat-test", None, seed=SEED, dtype=jnp.float32)
    assert cfg is CFG and params["layers"]["e_gate"].shape == (2, 8, 64, 32)


def test_the_template_copy_renders_what_the_program_renders():
    from benchmark.reference import tokens
    from runbookai_tpu.model.chat_template import build_chat_prompt, format_for_model

    assert format_for_model("anything", CFG.family) == "longcat"
    history = [("user", "first"), ("assistant", "answer")]
    ours = build_chat_prompt("Be brief.", "second?", history=history, fmt="longcat")
    messages = ([{"role": "system", "content": "Be brief."}]
                + [{"role": r, "content": c} for r, c in history]
                + [{"role": "user", "content": "second?"}])
    assert tokens.prompt_ids(messages, CFG.family) == list(ours.encode())
    assert "<|" not in ours  # none of the byte tokenizer's special strings


def test_the_memory_plan_counts_the_latent_pool():
    from runbookai_tpu.engine.memory_plan import plan_serving

    cut = dataclasses.replace(CONFIGS["longcat-flash-chat"], name="cut", num_layers=4,
                              n_experts_held=16, vocab_size=16384)
    plan = plan_serving(cut, max_seq_len=8192, batch=8, weights="bf16")
    assert plan.kv_bytes_per_token_per_chip == 4 * 2 * 576 * 2  # 9,216 B
    assert 10.3e9 < plan.weight_bytes_per_chip < 10.4e9
    assert CONFIGS["longcat-flash-chat"].total_params == pytest.approx(560.66e9, rel=1e-3)


def test_runbook_serve_answers_chat_completions_with_the_family(tmp_path):
    """``cli.main.build_server`` — the construction path of ``runbook
    serve`` — with the family's tiny preset: a chat completion over HTTP,
    through ``EngineCore`` and ``KVCacheManager``, and what ``/healthz``
    says it resolved."""
    import http.client
    import json

    from runbookai_tpu.cli.main import build_server

    path = tmp_path / "serve.yaml"
    path.write_text(json.dumps({"llm": {
        "provider": "jax-tpu", "model": "longcat-test", "dtype": "bfloat16",
        "max_seq_len": 512, "num_pages": 128, "prefill_chunk": 64,
        "max_batch_slots": 4}}))
    server = build_server(str(path), host="127.0.0.1", port=0)
    server.start_background()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)
        conn.request("POST", "/v1/chat/completions", json.dumps({
            "model": "longcat-test", "max_tokens": 6, "temperature": 0,
            "messages": [{"role": "user", "content": "why is the pager red"}]}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200, body
        assert body["usage"]["completion_tokens"] >= 1
        # "SYSTEM:... [Round 0] USER:why is the pager red ASSISTANT:" in bytes
        assert body["usage"]["prompt_tokens"] == len(
            "SYSTEM:You are a helpful assistant. [Round 0] USER:why is the pager red ASSISTANT:")
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["runtime"]["weight_dtype"] == "bfloat16"
        assert health["runtime"]["attn_impl"] == "xla"
        assert sum(health["metrics"][f"expert_pairs_{k}"]
                   for k in ("held", "zero", "absent")) > 0
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        assert 'runbook_expert_pairs_total{kind="zero"}' in text
        assert "runbook_experts_touched_total" in text
    finally:
        server.shutdown()


def test_the_example_serve_config_is_taken_as_it_stands():
    from pathlib import Path

    from runbookai_tpu.cli.main import validate_config
    from runbookai_tpu.engine.memory_plan import plan_serving
    from runbookai_tpu.utils.config import load_config

    root = Path(__file__).resolve().parents[1]
    config = load_config(path=root / "examples" / "serve" / "longcat-flash-ep32.yaml")
    assert [p for p in validate_config(config) if "llm." in p] == []
    cfg = CONFIGS[config.llm.model]
    bench = __import__("json").loads(
        (root / "benchmark" / "configs" / "longcat-flash-ep32-bf16.json").read_text())
    assert {k: getattr(cfg, k) for k in bench["reduced"]} == {k: bench[k] for k in bench["reduced"]}
    assert {k: v for k, v in bench["llm"].items()} == {
        k: getattr(config.llm, k) for k in bench["llm"]}
    plan = plan_serving(cfg, max_seq_len=config.llm.max_seq_len,
                        batch=config.llm.max_batch_slots, weights="bf16")
    assert plan.fits  # even 64 FULL 8k contexts of 9 KB a token (4.8 GB) would
    assert 12.0e9 < plan.weight_bytes_per_chip + config.llm.num_pages * 16 * 9216 < 12.4e9


def test_seeded_weights_never_end_an_answer(params):
    """The random-init path of ``load_or_init`` zeroes the head's columns of
    the tokenizer's control ids — the model's own ``init_params`` knows no
    tokenizer — so the argmax over random logits is never a stop token:
    every seed serves ``max_tokens`` tokens, and a window's work does not
    hang on the seed."""
    tok = ByteTokenizer()
    assert {tok.eos_id, tok.eot_id} <= tok.special_ids == set(range(256, 262))
    head = np.asarray(params["lm_head"])
    assert not head[:, sorted(tok.special_ids)].any() and head[:, 255].any()
    plain = longcat.init_params(jax.random.PRNGKey(SEED), CFG, jnp.float32)
    assert np.asarray(plain["lm_head"])[:, sorted(tok.special_ids)].all()
    assert np.array_equal(np.asarray(plain["lm_head"])[:, :256], head[:, :256])
    _, reqs = _serve(params, True, [_ids(20, 9), _ids(40, 10)], max_new=40)
    assert [len(r.out_ids) for r in reqs] == [40, 40]
