"""JoyAI-LLM-Flash (``models/joyai.py``): the program against the plain
reference of its benchmark block (``benchmark/blocks/joyai/forward.py``:
float32, expanded attention, no cache, a sum over experts), at ``joyai-test``
size on seeded weights — LOGITS, not sampled tokens — the router against a
hand computation, the share tied to the model, and the prediction module's
logits against the block's ``draft_logits``. The rounds the engine runs with
the module as its drafter are ``tests/test_joyai_rounds.py``'s.

Tolerances. The program here runs float32 weights, pool and activations, as
the reference does, so the two differ only in the order of float32 sums: the
absorbed form of latent attention against the expanded one, a blockwise
running softmax against one softmax, the slotted expert dispatch against a
sum over experts. ``ATOL`` = 2e-4 is forty times the largest difference seen
(5.0e-6 on logits of magnitude 3, trunk and module alike); a dropped expert,
a bias that entered a weight, a missing 2.5 or a module row made of the
wrong token moves a logit by 1e-2 or more.
"""

from __future__ import annotations

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import blocks
from runbookai_tpu.engine.engine import EngineConfig, EngineCore
from runbookai_tpu.engine.kv_cache import hash_blocks
from runbookai_tpu.engine.request import EngineRequest, RequestState, SamplingParams
from runbookai_tpu.models import joyai
from runbookai_tpu.models.llama import CONFIGS
from runbookai_tpu.ops import moe
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["joyai-test"]
REF_CFG = dataclasses.asdict(CFG)
BLOCK = blocks.load("joyai")
ATOL = 2e-4
PS, PAGES, SEED = 16, 48, 11


@pytest.fixture(scope="module")
def params():
    """As served: ``load_or_init`` with no checkpoint (``init_params``, then
    the control tokens' head columns quiet)."""
    from runbookai_tpu.models import hf_loader

    return hf_loader.load_or_init("joyai-test", None, seed=SEED, dtype=jnp.float32)[1]


def _ids(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, size=n)]


def _pools():
    (lk, hk, dk), (lv, hv, dv) = CFG.kv_pool_spec
    return (jnp.zeros((lk, PAGES * PS, hk, dk), jnp.float32),
            jnp.zeros((lv, PAGES * PS, hv, dv), jnp.float32))


def _reference(params, ids, n_last):
    return np.asarray(BLOCK.forward.logits(params, REF_CFG, ids, n_last)[0])


def _gap(params, req) -> float:
    """The benchmark's ``logit_gap`` of one served request."""
    served = list(req.all_out_ids)
    prompt = list(req.prompt_ids[:len(req.prompt_ids) - len(req.folded_out_ids)])
    ref = _reference(params, (prompt + served)[:-1], len(served))
    return float((ref.max(axis=1) - ref[np.arange(len(served)), served]).max())


def _engine(params, cfg=CFG, **over):
    ecfg = dict(page_size=PS, num_pages=128, max_batch_slots=4, prefill_chunk=32,
                max_seq_len=512, block_pages=2, speculative=True, kv_dtype=jnp.float32,
                decode_steps_per_dispatch=4, mixed_dispatch=False)
    ecfg.update(over)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(**ecfg), seed=0)


def _request(rid, prompt, max_new=12, **sampling):
    sampling.setdefault("temperature", 0.0)
    sampling.setdefault("stop_token_ids", ())
    return EngineRequest(request_id=rid, prompt_ids=list(prompt), sampling=SamplingParams(
        max_new_tokens=max_new, **sampling))


def _serve(core, requests):
    for r in requests:
        core.submit(r)
    core.run_until_idle()
    return requests


def _draft_gaps(core, params, reqs) -> list[float]:
    """The engine's invariant between dispatches, row by row: with ``n``
    tokens committed, slot ``s`` holds the module's draft of token ``n``.
    Each draft as a gap to the best of the block's ``draft_logits`` over
    the committed tokens — which the module can only match if every one of
    its cache rows ``0 .. n - 2`` was made of the right hidden state and the
    right next token."""
    core._drain_pending()
    drafts = np.asarray(core._draft_toks)
    gaps = []
    for r in reqs:
        if r.state != RequestState.DECODE:
            continue
        committed = list(r.prompt_ids) + list(r.out_ids)
        ref = np.asarray(BLOCK.forward.draft_logits(params, REF_CFG, committed, 1))[0]
        gaps.append(float(ref.max() - ref[drafts[r.slot]]))
    return gaps


def test_the_blocks_weights_are_the_programs(params):
    """The reference makes its own weights from the seed: the same bits,
    the module's projection and norms included."""
    theirs = BLOCK.weights.make_params(REF_CFG, SEED, False, jnp.float32)
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), params, theirs)))
    assert float(jnp.abs(params["layers"]["router_bias"]).max()) > 0  # not zeros
    assert params["mtp"]["proj"].shape == (1, 2 * CFG.hidden_size, CFG.hidden_size)
    # one leading dense FFN, two expert layers and the module's: the stacks
    assert params["layers"]["wq_a"].shape[0] == 4 and params["layers"]["d_gate"].shape[0] == 1
    assert params["layers"]["e_gate"].shape[:2] == (3, CFG.n_experts_held)


@pytest.mark.parametrize("chunks", [(70,), (32, 32, 6)], ids=["whole", "chunked"])
def test_prefill_through_the_latent_pages_matches_the_reference(params, chunks):
    """The trunk over a prompt, in one chunk or three through the paged
    latent pool, then one token decoded through it: every logit against ONE
    full pass of the reference; then the module over the same positions,
    one position behind, against the block's ``draft_logits``."""
    ids = _ids(71)
    kv_k, kv_v = _pools()
    per_token = (kv_k.nbytes + kv_v.nbytes) / (PAGES * PS)
    # a latent a block; the rotated keys two blocks a row: 4 blocks fill 2 rows
    assert per_token == (4 * CFG.kv_lora_rank + 2 * 2 * CFG.qk_rope_head_dim) * 4
    tables = jnp.arange(1, 9, dtype=jnp.int32)[None]
    got, hidden, lo = [], [], 0
    for n in (*chunks, 1):  # the last: a decode step's shape
        at = jnp.arange(lo, lo + n, dtype=jnp.int32)[None]
        logits, kv_k, kv_v, counts, _, h = joyai.forward_counted(
            params, CFG, jnp.asarray([ids[lo:lo + n]], jnp.int32), at, kv_k, kv_v,
            tables, jnp.asarray([lo + n]), page_size=PS, block_pages=2)
        # two expert layers of the trunk: every pick is held or absent
        assert int(counts[0] + counts[2]) == n * CFG.num_experts_per_tok * 2
        got.append(np.asarray(logits[0]))
        hidden.append(h)
        lo += n
    np.testing.assert_allclose(np.concatenate(got), _reference(params, ids, 71),
                               atol=ATOL, rtol=0)
    # the module: position i from h_i and token i + 1, for i = 0 .. 69
    y, kv_k, kv_v, counts = joyai.module_pass(
        params, CFG, jnp.concatenate(hidden, axis=1)[:, :70], jnp.asarray([ids[1:]], jnp.int32),
        jnp.arange(70, dtype=jnp.int32)[None], kv_k, kv_v, tables, jnp.asarray([70]),
        page_size=PS, block_pages=2)
    assert int(counts[0] + counts[2]) == 70 * CFG.num_experts_per_tok
    ref = np.asarray(BLOCK.forward.draft_logits(params, REF_CFG, ids, 70))
    np.testing.assert_allclose(np.asarray(joyai.draft_logits(params, CFG, y))[0], ref,
                               atol=ATOL, rtol=0)


def test_the_router_against_a_hand_computation():
    """Sigmoid scores, each expert's own; the bias moves the CHOICE and
    enters no weight; the chosen are renormalised and scaled by 2.5."""
    u = jnp.asarray([[1.0, -2.0, 0.5]], jnp.float32)
    router = jnp.asarray([[0.2, -0.1, 0.4, 0.0, 0.3],
                          [0.1, 0.3, -0.2, 0.5, 0.0],
                          [-0.3, 0.2, 0.1, 0.1, 0.6]], jnp.float32)
    logit = np.asarray([1.0 * 0.2 - 2.0 * 0.1 - 0.5 * 0.3, -0.1 - 0.6 + 0.1,
                        0.4 + 0.4 + 0.05, -1.0 + 0.05, 0.3 + 0.3])
    s = 1.0 / (1.0 + np.exp(-logit))  # [0.4626, 0.3543, 0.7006, 0.2789, 0.6457]
    chosen, w = moe.route_sigmoid(u, router, jnp.zeros((5,)), 2, 2.5)
    assert sorted(np.asarray(chosen[0]).tolist()) == [2, 4]
    np.testing.assert_allclose(np.sort(np.asarray(w[0])),
                               np.sort(2.5 * s[[2, 4]] / s[[2, 4]].sum()), rtol=1e-6)
    assert float(w.sum()) == pytest.approx(2.5, rel=1e-6)
    # a bias of 0.3 on expert 0 lifts it over expert 4 (0.7626 > 0.6457) ...
    bias = jnp.asarray([0.3, 0.0, 0.0, 0.0, 0.0])
    chosen_b, w_b = moe.route_sigmoid(u, router, bias, 2, 2.5)
    assert sorted(np.asarray(chosen_b[0]).tolist()) == [0, 2]
    # ... and its weight is made of its SCORE, 0.4626, not of 0.7626
    by_expert = dict(zip(np.asarray(chosen_b[0]).tolist(), np.asarray(w_b[0]).tolist()))
    assert by_expert[0] == pytest.approx(2.5 * s[0] / (s[0] + s[2]), rel=1e-6)
    assert by_expert[2] == pytest.approx(2.5 * s[2] / (s[0] + s[2]), rel=1e-6)


def test_the_shares_add_up_to_the_whole_layer():
    """Guide, section 4: the routed parts of the four shares (experts 0-3,
    4-7, 8-11, 12-15 here; 0-63 ... 192-255 at the published width), with
    the shared expert — which every share computes alike — counted once,
    equal the uncut layer's ``MoE(u)``, itself the reference's sum over
    experts."""
    whole = dataclasses.replace(CFG, n_experts_held=CFG.n_routed_experts, first_expert=0)
    lp = joyai.init_params(jax.random.PRNGKey(5), whole, jnp.float32)["layers"]
    u = jax.random.normal(jax.random.PRNGKey(6), (24, CFG.hidden_size), jnp.float32)
    live = jnp.ones((24,), bool)
    m_whole, counts = joyai.moe_block(u, live, lp, 1, whole)
    assert int(counts[2]) == 0 and int(counts[0]) == 24 * CFG.num_experts_per_tok
    ref = BLOCK.forward.moe(u, lp, 1, dataclasses.asdict(whole), None)
    np.testing.assert_allclose(np.asarray(m_whole), np.asarray(ref), atol=1e-5, rtol=0)
    shared = moe.shared_expert(u, lp["s_gate"][1], lp["s_up"][1], lp["s_down"][1])
    held_n, parts = 4, 0
    for first in range(0, CFG.n_routed_experts, held_n):
        share = dataclasses.replace(CFG, n_experts_held=held_n, first_expert=first)
        slp = dict(lp, **{k: lp[k][:, first:first + held_n] for k in joyai.EXPERT_LEAVES})
        m_share, c = joyai.moe_block(u, live, slp, 1, share)
        assert int(c[0] + c[2]) == 24 * CFG.num_experts_per_tok and int(c[1]) == 0
        parts = parts + (m_share - shared)
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(m_whole),
                               atol=1e-5, rtol=0)
    # the share the tiny preset serves (experts 8-15) leaves picks absent
    _, c = joyai.moe_block(u, live, dict(lp, **{k: lp[k][:, 8:] for k in joyai.EXPERT_LEAVES}),
                           1, CFG)
    assert int(c[2]) > 0 and int(c[0]) > 0


def test_pages_are_hashed_with_the_token_after_them():
    """A page's module rows are made of the token AFTER each position, so
    the page's hash folds the first token past it in: two prompts that
    part at a page boundary share one page fewer, and a page with nothing
    after it has no hash yet."""
    a, b = _ids(48, 1), _ids(48, 1)
    b[32] += 1  # the first token of the third page
    assert hash_blocks(a, PS)[:2] == hash_blocks(b, PS)[:2]
    ha, hb = hash_blocks(a, PS, lookahead=1), hash_blocks(b, PS, lookahead=1)
    assert len(ha) == 2  # 48 tokens: the third page has no token after it
    assert ha[0] == hb[0] and ha[1] != hb[1]
    assert len(hash_blocks(a + [7], PS, lookahead=1)) == 3
    assert hash_blocks(a, PS, max_blocks=1, lookahead=1) == ha[:1]


@pytest.mark.parametrize("mixed", [False, True], ids=["split", "mixed"])
@pytest.mark.parametrize("speculative", [False, True], ids=["undrafted", "drafted"])
def test_the_engine_serves_the_references_tokens(params, mixed, speculative):
    """Through ``EngineCore`` — admission, chunked prefill, the mixed
    (ragged) dispatch or the split one, rounds or ``_decode_multi``'s
    windows, page growth: every served token is the reference's best within
    ``ATOL``, and with drafting on every row holds the module's draft."""
    core = _engine(params, mixed_dispatch=mixed, speculative=speculative)
    reqs = [_request(f"r{i}", p, max_new=14)
            for i, p in enumerate([_ids(50, 3), _ids(90, 4), _ids(33, 5)])]
    core.submit(reqs[0])
    for _ in range(3):  # the first decodes while the others prefill beside it
        core.step()
    for r in reqs[1:]:
        core.submit(r)
    while not all(len(r.out_ids) >= 3 for r in reqs):
        core.step()
    if speculative:
        assert max(_draft_gaps(core, params, reqs)) <= ATOL
    core.run_until_idle()
    assert (core.metrics["mixed_steps"] > 0) == mixed
    assert [len(r.out_ids) for r in reqs] == [14] * 3
    assert max(_gap(params, r) for r in reqs) <= ATOL
    recs = core.flight.snapshot()
    programs = {p for s in recs if "experts" in s for p in s["experts"]["programs"]}
    if speculative:
        assert core.metrics["spec_drafted"] > 0
        assert "_decode_spec" in programs and "_decode_multi" not in programs
        assert core.kv.lookahead == 1
    else:
        assert core.metrics["spec_drafted"] == 0 and not any("spec" in s for s in recs)
        assert "_decode_spec" not in programs and core.kv.lookahead == 0


def test_the_modules_logits_after_prefill_a_prefix_hit_and_a_fork(params):
    """The module's cache layer rides the same pages: a second prompt
    behind a shared prefix is granted the pages whose rows it would make
    itself (not the one whose last row saw another next token), four
    choices of one prompt fork off one prefill, and every draft is the
    block's."""
    core = _engine(params)
    shared = _ids(64, 40)
    [a] = _serve(core, [_request("a", shared + _ids(30, 41), max_new=4)])
    b = _request("b", shared + _ids(37, 42), max_new=10)
    core.submit(b)
    while len(b.out_ids) < 1:  # the prefill, its first token, the first draft
        core.step()
    assert b.cached_tokens == 48  # the fourth page's last row saw A's token
    assert max(_draft_gaps(core, params, [b])) <= ATOL
    core.run_until_idle()
    assert _gap(params, b) <= ATOL and _gap(params, a) <= ATOL
    # n = 4 choices are four requests with one prompt (server/openai_api.py)
    prompt = _ids(70, 43)
    cold = [_request(f"c{i}", prompt, max_new=9) for i in range(4)]
    for r in cold:
        core.submit(r)
    while not all(len(r.out_ids) >= 2 for r in cold):
        core.step()
    assert max(_draft_gaps(core, params, cold)) <= ATOL
    core.run_until_idle()
    warm = [_request(f"w{i}", prompt, max_new=9) for i in range(4)]
    for r in warm:
        core.submit(r)
    while not all(len(r.out_ids) >= 2 for r in warm):
        core.step()
    assert [r.cached_tokens for r in warm] == [64] * 4
    assert max(_draft_gaps(core, params, warm)) <= ATOL
    core.run_until_idle()
    assert {tuple(r.out_ids) for r in cold + warm} == {tuple(cold[0].out_ids)}
    assert _gap(params, warm[3]) <= ATOL


def test_the_modules_logits_after_a_preemption_and_re_admission(params):
    """A preempted row's pages go; re-admitted, its prompt with what it had
    generated folded in is prefilled again — trunk rows and module rows —
    and its draft is the block's again."""
    core = _engine(params)
    reqs = [_request("old", _ids(100, 60), max_new=24),
            _request("young", _ids(90, 61), max_new=24)]
    for r in reqs:
        core.submit(r)
    while not all(len(r.out_ids) >= 5 for r in reqs):
        core.step()
    assert core._preempt_youngest()
    victim = reqs[1]
    assert victim.slot is None and victim.preemptions == 1
    while victim.state != RequestState.DECODE:
        core.step()
    assert max(_draft_gaps(core, params, reqs)) <= ATOL
    core.run_until_idle()
    assert [len(r.all_out_ids) for r in reqs] == [24, 24]
    assert max(_gap(params, r) for r in reqs) <= ATOL


@pytest.mark.parametrize("asked, named", [
    (dict(engine_cfg=EngineConfig(num_pages=32, kv_dtype=jnp.int8)), "int8 KV pool"),
    (dict(lora_registry=SimpleNamespace(stacked=dict)), "LoRA"),
    (dict(draft_worker=SimpleNamespace(metrics={})), "separate draft model"),
], ids=["int8_pool", "lora", "draft_worker"])
def test_the_engine_refuses_by_name_what_the_family_does_not_do(params, asked, named):
    with pytest.raises(ValueError, match=named):
        EngineCore(CFG, params, ByteTokenizer(), **asked)


def test_refusals_the_family_states():
    no = CFG.unsupported(lora=True, model_axis=4, seq_axis=2, kv_dtype=jnp.int8,
                         quantized=True, draft=True)
    assert len(no) == 6 and "model axis of 4" in " ".join(no)
    assert CFG.unsupported(lora=False, model_axis=1, seq_axis=1, speculative=True,
                           kv_dtype=jnp.float8_e4m3fn, quantized=False) == []
    with pytest.raises(ValueError, match="sigmoid"):
        dataclasses.replace(CFG, scoring_func="softmax")
    with pytest.raises(ValueError, match="at most one prediction module"):
        dataclasses.replace(CFG, num_nextn_predict_layers=2)


def test_a_checkpoint_of_the_family_is_refused_by_name(tmp_path):
    from runbookai_tpu.models import hf_loader

    (tmp_path / "config.json").write_text('{"model_type": "joyai_llm_flash"}')
    with pytest.raises(NotImplementedError, match="joyai"):
        hf_loader.load_or_init("joyai-test", str(tmp_path))
    with pytest.raises(NotImplementedError, match="joyai"):
        hf_loader.config_from_hf(tmp_path)
    with pytest.raises(ValueError, match="no int8"):
        hf_loader.load_or_init("joyai-test", None, quantize_int8=True)


def test_the_published_sizes_and_the_memory_plan():
    """The arithmetic of the cut (the configuration file states the same):
    4,944.9M parameters, 16,128 B of latent cache a token over 14 blocks."""
    from runbookai_tpu.engine.memory_plan import plan_serving

    cut, whole = CONFIGS["joyai-llm-flash-ep4"], CONFIGS["joyai-llm-flash"]
    assert cut._attention_params == 26_345_472 and cut._expert_params == 4_718_592
    assert cut.total_params == 4_944_919_808  # 9.89 GB in bf16
    norms_and_bias = 14 * (2 * 2048 + 1536 + 512) + 13 * 256 + 3 * 2048 + 2048
    assert cut.total_params - norms_and_bias == 4_944_822_272  # the matrices
    assert whole.total_params == pytest.approx(50.2e9, rel=5e-3)  # 48.9B + the module's 1.25B
    plan = plan_serving(cut, max_seq_len=8192, batch=64, weights="bf16")
    assert plan.kv_bytes_per_token_per_chip == 14 * 576 * 2  # 16,128 B
    assert 9.88e9 < plan.weight_bytes_per_chip < 9.91e9
    assert cut.kv_pool_spec == ((14, 1, 512), (7, 1, 128))


def test_seeded_weights_never_end_an_answer(params):
    tok = ByteTokenizer()
    head = np.asarray(params["lm_head"])
    assert not head[:, sorted(tok.special_ids)].any() and head[:, 255].any()
    [r] = _serve(_engine(params), [_request("long", _ids(20, 9), max_new=40,
                                            stop_token_ids=(tok.eos_id, tok.eot_id))])
    assert len(r.out_ids) == 40


def test_the_example_serve_config_is_taken_as_it_stands():
    import json
    from pathlib import Path

    from runbookai_tpu.cli.main import validate_config
    from runbookai_tpu.utils.config import load_config

    root = Path(__file__).resolve().parents[1]
    config = load_config(path=root / "examples" / "serve" / "joyai-llm-flash-ep4.yaml")
    assert [p for p in validate_config(config) if "llm." in p] == []
    cfg = CONFIGS[config.llm.model]
    bench = json.loads(
        (root / "benchmark" / "configs" / "joyai-llm-flash-ep4-bf16.json").read_text())
    assert {k: getattr(cfg, k) for k in bench["reduced"]} == {k: bench[k] for k in bench["reduced"]}
    assert bench["llm"] == {k: getattr(config.llm, k) for k in bench["llm"]}
    assert bench["engine_plan"] == {"speculative": EngineConfig().speculative}  # the default


def test_runbook_serve_answers_chat_completions_with_the_family(tmp_path):
    """``cli.main.build_server`` — the construction path of ``runbook
    serve`` — with the family's tiny preset: a chat completion over HTTP,
    drafted by the module, and what ``/healthz`` and ``/metrics`` count."""
    import http.client
    import json

    from runbookai_tpu.cli.main import build_server

    path = tmp_path / "serve.yaml"
    path.write_text(json.dumps({"llm": {
        "provider": "jax-tpu", "model": "joyai-test", "dtype": "bfloat16",
        "max_seq_len": 512, "num_pages": 128, "prefill_chunk": 64,
        "max_batch_slots": 4}}))
    server = build_server(str(path), host="127.0.0.1", port=0)
    server.start_background()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)
        conn.request("POST", "/v1/chat/completions", json.dumps({
            "model": "joyai-test", "max_tokens": 12, "temperature": 0,
            "messages": [{"role": "user", "content": "why is the pager red"}]}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200, body
        assert body["usage"]["completion_tokens"] == 12
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["runtime"]["attn_impl"] == "xla"
        m = health["metrics"]
        assert m["spec_drafted"] >= 6 and m["spec_accepted"] <= m["spec_drafted"]
        assert m["decode_tokens"] == m["spec_drafted"] + m["spec_accepted"] == 11
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        assert "runbook_spec_drafted_total" in text and "runbook_spec_accepted_total" in text
        conn.request("GET", "/debug/steps?n=16")
        steps = json.loads(conn.getresponse().read())["steps"]
        assert any(s.get("spec", {}).get("rounds") == 8 for s in steps)
    finally:
        server.shutdown()
