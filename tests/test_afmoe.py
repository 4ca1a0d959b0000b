"""afmoe (``models/afmoe.py``): the program against the plain reference of
its benchmark block (``benchmark/blocks/afmoe/forward.py``: float32, one
full pass, no cache), at ``afmoe-test`` size on seeded weights — LOGITS, not
sampled tokens — with the window layers' rows given back while sequences
live, prefix hits granted or cut back by what the window pool still holds,
and the share tied to the model.

Sizes: a window of 32 tokens (two pages of 16), contexts to five windows and
more, prefill chunks of 24 (no multiple of a page or of the window, so chunks
straddle both edges).

Tolerances. The program here runs float32 weights, pools and activations,
as the reference does, so the two differ only in the order of float32 sums
(a blockwise running softmax against one softmax, the slotted expert
dispatch against a sum over experts). ``ATOL`` = 2e-3 is some forty times
the largest difference seen (under 5e-5 on logits of magnitude 3); a key one
position outside the window, a stale or released row read, a rotated full
layer or a dropped expert moves a logit by 1e-2 to 1: the reference's own
``no_window`` control reads 0.05 or more at every context past the window.
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
from runbookai_tpu.engine.kv_cache import WindowSpec
from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.models import afmoe
from runbookai_tpu.models.llama import CONFIGS
from runbookai_tpu.ops import moe
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["afmoe-test"]
REF_CFG = dataclasses.asdict(CFG)
BLOCK = blocks.load("afmoe")
ATOL = 2e-3
PS, SEED, WINDOW, CHUNK = 16, 11, CFG.sliding_window, 24
BOUND = WINDOW + CHUNK + 2 * PS  # rows a live sequence may hold in the window layers


@pytest.fixture(scope="module")
def params():
    """As served: ``load_or_init`` with no checkpoint (``init_params``, then
    the control tokens' head columns quiet)."""
    from runbookai_tpu.models import hf_loader

    return hf_loader.load_or_init("afmoe-test", None, seed=SEED, dtype=jnp.float32)[1]


def _ids(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, size=n)]


def _reference(params, ids, n_last, lowp=None):
    return np.asarray(BLOCK.forward.logits_and_margins(params, REF_CFG, ids, n_last, lowp)[0])


def _gap(params, req, lowp=None) -> float:
    """The benchmark's ``logit_gap`` of one served request."""
    served = list(req.all_out_ids)
    ref = _reference(params, (list(req.prompt_ids[:len(req.prompt_ids) - len(req.folded_out_ids)])
                              + served)[:-1], len(served), lowp)
    return float((ref.max(axis=1) - ref[np.arange(len(served)), served]).max())


def _engine(params, cfg=CFG, **over):
    ecfg = dict(page_size=PS, num_pages=128, max_batch_slots=4, prefill_chunk=CHUNK,
                max_seq_len=512, speculative=False, kv_dtype=jnp.float32,
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
    theirs = BLOCK.weights.make_params(REF_CFG, SEED, False, jnp.float32)
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), params, theirs)))
    assert params["layers"]["wg"].shape == params["layers"]["wq"].shape  # the output gate


def test_the_published_pattern_and_the_two_groups():
    whole, cut = CONFIGS["trinity-mini"], CONFIGS["trinity-mini-ep8"]
    assert (whole.n_kind(afmoe.SLIDING), whole.n_kind(afmoe.FULL)) == (24, 8)
    assert whole.layer_types == ([afmoe.SLIDING] * 3 + [afmoe.FULL]) * 8  # equals a list
    assert whole.kv_pool_spec[0] == (8, 4, 128) and whole.kv_window_spec == (24, 2048)
    assert whole.total_params == pytest.approx(26.1e9, rel=2e-2)
    assert cut.total_params * 2 == pytest.approx(8.55e9, rel=5e-3)
    assert hash(whole) != hash(cut)  # a static argument of every step program
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(CFG, layer_types=[afmoe.FULL] * 12)
    with pytest.raises(ValueError, match="routes by sigmoid"):
        dataclasses.replace(CFG, score_func="softmax")


def _tables(rows_of_pages, width):
    """Page tables of two halves, each with its trash column."""
    out = np.zeros((len(rows_of_pages), 2 * (width + 1)), np.int32)
    for i, (full, window) in enumerate(rows_of_pages):
        out[i, :len(full)] = full
        out[i, width + 1:width + 1 + len(window)] = window
    return jnp.asarray(out)


def _pools(full_pages, window_pages):
    (lf, h, d), _ = CFG.kv_pool_spec
    lw, _ = CFG.kv_window_spec

    def side():  # two buffers: the callers' step programs donate both
        return {"full": jnp.zeros((lf, full_pages * PS, h, d), jnp.float32),
                "window": jnp.zeros((lw, window_pages * PS, h, d), jnp.float32)}

    return side(), side()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_one_full_prefill_matches_the_reference(params, impl):
    """One pass over 5.5 windows, every position's logits: the mask's lower
    edge on the sliding layers, none on the full ones, rotation on the
    sliding layers only. ``no_window`` (the reference with every layer full)
    is off by more than the tolerance at EVERY position past the window."""
    n = 5 * WINDOW + 17
    ids = _ids(n)
    kv_k, kv_v = _pools(13, 13)
    pages = list(range(1, 13))
    logits, _, _ = afmoe.forward_impl(
        params, CFG, jnp.asarray([ids], jnp.int32), jnp.arange(n, dtype=jnp.int32)[None],
        kv_k, kv_v, _tables([(pages, pages)], 12), jnp.asarray([n]), page_size=PS,
        block_pages=2, attn_impl=impl)
    ref = _reference(params, ids, n)
    np.testing.assert_allclose(np.asarray(logits[0]), ref, atol=ATOL, rtol=0)
    off = np.abs(_reference(params, ids, n, "no_window") - ref).max(axis=1)
    assert off[:WINDOW].max() <= 1e-5 and off[WINDOW + 8:].min() > 10 * ATOL


@pytest.mark.parametrize("mixed", [False, True], ids=["split", "mixed"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_engine_serves_the_references_tokens(params, mixed, impl):
    """Through ``EngineCore``: chunks of 24 that straddle the window's edge
    and a page's, the mixed (ragged) dispatch with decode rows beside a
    chunk or the split one, ``_decode_multi``'s windows, contexts past five
    windows — every served token is the reference's best within ``ATOL``,
    while the manager gives the window layers' rows back: no live sequence
    ever holds more than the bound, whatever its context."""
    core = _engine(params, mixed_dispatch=mixed, attn_impl=impl)
    assert core.ecfg.prefill_batch == CFG.max_prefill_rows == 1  # one prefill width
    reqs = [_request(f"r{i}", _ids(n, 3 + i), max_new=14 + 3 * i)
            for i, n in enumerate((170, 40, 200, 97, 31, 130))]
    peak = 0
    for r in reqs:
        core.submit(r)
        core.step()
    while core.has_work:
        core.step()
        peak = max([peak] + [core.kv.window_rows(s) for s in core.kv.seqs])
    assert (core.metrics["mixed_steps"] > 0) == mixed
    assert [len(r.out_ids) for r in reqs] == [14 + 3 * i for i in range(6)]
    assert max(_gap(params, r) for r in reqs) <= ATOL
    m = core.metrics
    assert m["expert_pairs_zero"] == 0 and m["expert_pairs_absent"] > 0 < m["expert_pairs_held"]
    # rows given back while the sequences lived, and counted
    assert BOUND - 2 * PS < peak <= BOUND == core.kv.window.rows_bound(PS)
    assert m["kv_window_rows_released"] >= (170 + 200 + 97 + 130) - 4 * BOUND
    recs = core.flight.snapshot()
    assert all("window" in s for s in recs)
    assert sum(s["window"]["rows_released"] for s in recs) == m["kv_window_rows_released"]
    assert max(s["window"]["rows_kept_max"] for s in recs) <= BOUND
    long_steps = [s["window"] for s in recs if s["window"]["rows_context"] > 2 * BOUND]
    assert long_steps and all(w["rows_kept"] < w["rows_context"] for w in long_steps)
    # the full group's pages are the contexts, as ever; nothing is left held
    assert core.kv.pages_in_use == 0
    assert core.kv.win_allocator.free_pages == core.kv.window.pages(PS) - 1


def test_a_prefix_hit_longer_than_the_window_gives_the_cold_logits(params):
    """The same request served on a page-hash hit of 4 windows and served
    cold, by their top log-probabilities at every generated position, and
    against the reference: the window layers resume from the last window's
    pages, which the first request's release left resident."""
    shared, tail_a, tail_b = _ids(4 * WINDOW, 40), _ids(50, 41), _ids(37, 42)
    warm_core, cold_core = _engine(params), _engine(params)
    _serve(warm_core, [_request("a", shared + tail_a, max_new=4)])
    [warm] = _serve(warm_core, [_request("b", shared + tail_b, max_new=10, logprobs=5)])
    [cold] = _serve(cold_core, [_request("b", shared + tail_b, max_new=10, logprobs=5)])
    assert warm.cached_tokens == 4 * WINDOW and cold.cached_tokens == 0
    m = warm_core.metrics
    assert m["kv_window_hash_tokens_matched"] == m["kv_window_hash_tokens_granted"] == 4 * WINDOW
    assert warm.out_ids == cold.out_ids
    for w, c in zip(warm.out_logprobs, cold.out_logprobs):
        assert [t for t, _ in w["top"]] == [t for t, _ in c["top"]]
        np.testing.assert_allclose([p for _, p in w["top"]], [p for _, p in c["top"]],
                                   atol=1e-4, rtol=0)
    assert _gap(params, warm) <= ATOL


def test_a_hit_whose_window_rows_were_released_is_cut_back_not_served(params):
    """THE fault the grant exists to exclude. The first request's pages stay
    in the full group's pool (128 pages), but the window pool (25) is run
    over by other traffic, so the window pages of the shared prefix are
    gone. The second request matches all four windows by hash and is granted
    NONE of them — and its logits are the cold ones. Served from the hit,
    its sliding layers would read rows another sequence has since written."""
    shared, tail_a, tail_b = _ids(4 * WINDOW, 50), _ids(20, 51), _ids(37, 52)
    core = _engine(params)
    _serve(core, [_request("a", shared + tail_a, max_new=4)])
    assert core.kv.match_prefix(shared + tail_b) == 4 * WINDOW  # still whole
    _serve(core, [_request(f"x{i}", _ids(150, 60 + i), max_new=4) for i in range(4)])
    assert core.kv.match_prefix(shared + tail_b) == 0  # matched in full, granted none
    before = dict(core.metrics)
    [cut] = _serve(core, [_request("b", shared + tail_b, max_new=10, logprobs=5)])
    [cold] = _serve(_engine(params), [_request("b", shared + tail_b, max_new=10, logprobs=5)])
    m = core.metrics
    assert m["kv_window_hash_tokens_matched"] - before["kv_window_hash_tokens_matched"] == 4 * WINDOW
    assert m["kv_window_hash_tokens_granted"] == before["kv_window_hash_tokens_granted"]
    assert cut.cached_tokens == 0 and cut.out_ids == cold.out_ids
    for w, c in zip(cut.out_logprobs, cold.out_logprobs):
        np.testing.assert_allclose([p for _, p in w["top"]], [p for _, p in c["top"]],
                                   atol=1e-4, rtol=0)
    assert _gap(params, cut) <= ATOL


def test_a_grant_is_cut_back_to_the_deepest_boundary_that_can_resume():
    """The manager alone: of a six-page match whose window pages 0-1 and 4-5
    are resident, the grant is the whole match (the boundary's query sees
    pages 4-5 only); with page 5 gone it falls back to page 2's boundary."""
    from runbookai_tpu.engine.kv_cache import KVCacheManager

    def manager():
        return KVCacheManager(2, 64, PS, 2, 8, max_seq_len=512, dtype=jnp.float32,
                              window=WindowSpec(3, WINDOW, CHUNK, 2))

    ids = _ids(6 * PS + 5, 70)
    kv = manager()
    kv.add_sequence("a", ids)
    kv.extend("a", len(ids))
    held = dict(kv.seqs["a"].win_pages)
    assert sorted(held) == [2, 3, 4, 5, 6]  # the chunk's window, not the context
    kv.release("a", token_ids=ids)
    assert kv.match_prefix(ids) == 6 * PS  # pages 4, 5 resident: resumes at 6
    kv.win_allocator.acquire(held[5])
    kv._win_tokens.pop(held[5])  # page 5's rows are another sequence's now
    assert kv.match_prefix(ids) == 5 * PS  # the boundary at 5 sees pages 3, 4
    kv._win_tokens.pop(held[3])
    assert kv.match_prefix(ids) == 0  # no boundary has its window
    with pytest.raises(ValueError, match="export"):
        kv.export_pages(None, None, ids)
    with pytest.raises(ValueError, match="spill"):
        KVCacheManager(2, 64, PS, 2, 8, max_seq_len=512, spill_pages=4,
                       window=WindowSpec(3, WINDOW, CHUNK, 2))


def test_preemption_and_re_admission(params):
    """A decoding sequence of six windows is preempted (its pages published,
    both groups') and re-admitted: it resumes on its own prefix, and every
    token it serves, before and after, is the reference's."""
    core = _engine(params)
    [victim, other] = [_request("v", _ids(180, 80), max_new=24),
                       _request("o", _ids(70, 81), max_new=24)]
    core.submit(victim)
    core.submit(other)
    while not (victim.out_ids and other.out_ids):
        core.step()
    assert core._preempt_youngest()
    core.run_until_idle()
    assert core.metrics["preemptions"] == 1 and {len(r.all_out_ids) for r in (victim, other)} == {24}
    assert max(_gap(params, r) for r in (victim, other)) <= ATOL
    assert core.metrics["kv_window_hash_tokens_granted"] >= 2 * WINDOW  # its own prefix


def test_n_choices_stops_and_the_fp8_cache_are_served(params):
    """Requests that fork one prompt, a stop id, and the engine's fp8 pool
    (the benchmark's served control: it must SERVE): same lengths, and the
    fp8 cache's tokens near the reference's (a gap the bf16 limit would
    pass; its BYTES are what the benchmark catches)."""
    core = _engine(params)
    prompt = _ids(3 * WINDOW + 5, 90)
    forks = _serve(core, [_request(f"n{i}", prompt, max_new=8) for i in range(3)])
    assert len({tuple(r.out_ids) for r in forks}) == 1
    stop = forks[0].out_ids[3]
    [stopped] = _serve(core, [_request("s", prompt, max_new=8, stop_token_ids=[stop])])
    assert stopped.out_ids[-1] == stop and len(stopped.out_ids) <= 4 and stopped.cached_tokens > 0
    fp8 = _engine(params, kv_dtype=jnp.float8_e4m3fn)
    assert {a.dtype for a in jax.tree.leaves(fp8._kv_k)} == {jnp.dtype(jnp.float8_e4m3fn)}
    [r] = _serve(fp8, [_request("f", _ids(150, 91), max_new=12)])
    assert len(r.out_ids) == 12 and _gap(params, r) < 0.5


def test_the_shares_add_up_to_the_whole_layer():
    """Guide, section 4: the expert parts of ALL eight shares, with the
    shared expert — which every share computes alike — counted once, equal
    the uncut layer (the reference's, over every expert)."""
    whole = dataclasses.replace(CFG, n_experts_held=CFG.num_experts, first_expert=0)
    w = afmoe.init_params(jax.random.PRNGKey(5), whole, jnp.float32)["layers"]
    u = jax.random.normal(jax.random.PRNGKey(6), (24, CFG.hidden_size), jnp.float32)
    live = jnp.ones((24,), bool)
    m_whole, counts = afmoe.moe_block(u, live, w, 1, whole)
    assert int(counts[2]) == 0  # nothing is absent from the uncut layer
    with jax.default_matmul_precision("highest"):
        ref = BLOCK.forward.moe(u, w, 1, dataclasses.asdict(whole), None)
    np.testing.assert_allclose(np.asarray(m_whole), np.asarray(ref), atol=1e-5, rtol=0)
    shared = moe.shared_expert(u, w["s_gate"][1], w["s_up"][1], w["s_down"][1])
    held_n, parts = CFG.num_experts // 8, 0
    for first in range(0, CFG.num_experts, held_n):
        share = dataclasses.replace(CFG, n_experts_held=held_n, first_expert=first)
        sw = dict(w, **{k: w[k][:, first:first + held_n] for k in afmoe.EXPERT_LEAVES})
        m_share, c = afmoe.moe_block(u, live, sw, 1, share)
        assert int(c[0] + c[2]) == 24 * CFG.num_experts_per_tok and int(c[1]) == 0
        parts = parts + (m_share - shared)
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(m_whole),
                               atol=1e-5, rtol=0)
    chosen, weight = moe.route_sigmoid(u, w["router"][1], w["router_bias"][1],
                                       CFG.num_experts_per_tok, CFG.route_scale)
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), CFG.route_scale, rtol=1e-5)


@pytest.mark.parametrize("asked, named", [
    (dict(engine_cfg=EngineConfig(num_pages=32)), "prompt-lookup speculation"),
    (dict(engine_cfg=EngineConfig(num_pages=32, speculative=False),
          draft_worker=SimpleNamespace()), "draft-model speculation"),
    (dict(engine_cfg=EngineConfig(num_pages=32, speculative=False, kv_dtype=jnp.int8)),
     "int8 KV pool"),
    (dict(engine_cfg=EngineConfig(num_pages=32, speculative=False),
          lora_registry=SimpleNamespace(stacked=dict)), "LoRA"),
    (dict(engine_cfg=EngineConfig(num_pages=32, speculative=False, kv_spill_pages=8)),
     "host spill tier"),
], ids=["speculation", "draft_model", "int8_pool", "lora", "spill_tier"])
def test_the_engine_refuses_by_name_what_the_family_does_not_do(params, asked, named):
    with pytest.raises(ValueError, match=named):
        EngineCore(CFG, params, ByteTokenizer(), **asked)


def test_refusals_the_family_states(params):
    no = CFG.unsupported(lora=True, model_axis=8, seq_axis=2, kv_dtype=jnp.int8,
                         quantized=True, speculative=True, draft=True)
    assert len(no) == 7 and "model axis of 8" in " ".join(no)
    assert "window" in no[0] and "int8 weight-only" in no[-1]
    assert CFG.unsupported(lora=False, model_axis=1, seq_axis=1, kv_dtype=jnp.float8_e4m3fn,
                           quantized=False) == []
    core = _engine(params)
    with pytest.raises(ValueError, match="export between replicas"):
        core.export_kv_pages(_ids(40))
    from runbookai_tpu.models import hf_loader

    with pytest.raises(ValueError, match="no int8"):
        hf_loader.load_or_init("afmoe-test", None, quantize_int8=True)


def test_a_checkpoint_of_the_family_loads_by_its_tensor_names(tmp_path, params):
    """A tiny checkpoint the test writes under the published names
    (``self_attn.gate_proj``, ``q_norm`` / ``k_norm``, the four norms,
    ``mlp.router.gate``, ``mlp.expert_bias``, ``mlp.experts.N.*``,
    ``mlp.shared_experts.*``): the loader gives back the tree it was made
    of, with this share's experts and every output of the router."""
    import json

    from safetensors.numpy import save_file

    from runbookai_tpu.models import hf_loader

    lp, k = params["layers"], CFG.num_dense_layers
    tensors = {"model.embed_tokens.weight": params["embed"],
               "model.norm.weight": params["final_norm"],
               "lm_head.weight": params["lm_head"].T}
    for leaf, (suffix, transpose) in hf_loader._AFMOE_LAYER_MAP.items():
        for i in range(CFG.num_hidden_layers):
            tensors[f"model.layers.{i}.{suffix}"] = lp[leaf][i].T if transpose else lp[leaf][i]
    for short, proj in hf_loader._AFMOE_FFN:
        for i in range(k):
            tensors[f"model.layers.{i}.mlp.{proj}.weight"] = lp[f"d_{short}"][i].T
        for e in range(CFG.n_expert_layers):
            at = f"model.layers.{k + e}.mlp"
            tensors[f"{at}.shared_experts.{proj}.weight"] = lp[f"s_{short}"][e].T
            for j in range(CFG.num_experts):  # a checkpoint holds every expert
                held = j - CFG.first_expert
                src = (lp[f"e_{short}"][e, held] if 0 <= held < CFG.n_experts_held
                       else jnp.full_like(lp[f"e_{short}"][e, 0], float(j)))
                tensors[f"{at}.experts.{j}.{proj}.weight"] = src.T
    for e in range(CFG.n_expert_layers):
        tensors[f"model.layers.{k + e}.mlp.router.gate.weight"] = lp["router"][e].T
        tensors[f"model.layers.{k + e}.mlp.expert_bias"] = lp["router_bias"][e]
    save_file({n: np.ascontiguousarray(np.asarray(t, np.float32)) for n, t in tensors.items()},
              str(tmp_path / "model.safetensors"))
    raw = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
           if f.name not in ("name", "family", "n_experts_held", "first_expert",
                             "router_bias_scale")}
    (tmp_path / "config.json").write_text(json.dumps({**raw, "layer_types": list(CFG.layer_types)}))
    whole = hf_loader.config_from_hf(tmp_path, name="from-disk")
    assert whole.n_experts_held == CFG.num_experts and whole.kv_window_spec == CFG.kv_window_spec
    assert whole.max_seq_len == CFG.max_position_embeddings  # NOT clamped to the window
    share = dataclasses.replace(whole, n_experts_held=CFG.n_experts_held,
                                first_expert=CFG.first_expert)
    _, loaded = hf_loader.load_params(tmp_path, share, dtype=jnp.float32)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: a.dtype == b.dtype and bool(jnp.array_equal(a, b)), loaded, params)))


def test_the_memory_plan_and_healthz_count_the_window_pool(params):
    from runbookai_tpu.engine.memory_plan import plan_serving
    from runbookai_tpu.model.jax_tpu import JaxTpuClient

    cut = CONFIGS["trinity-mini-ep8"]
    plan = plan_serving(cut, max_seq_len=17408, batch=16, weights="bf16", prefill_chunk=512)
    assert plan.kv_bytes_per_token_per_chip == 8 * 2 * 4 * 128 * 2  # 16,384 B: 8 full layers
    rows = 16 * 2592 + 16  # every slot at its bound, and the null page
    assert plan.window_pool_bytes == rows * 24 * 2 * 4 * 128 * 2  # 49,152 B a row
    assert plan.window_pool_bytes == pytest.approx(2.04e9, rel=2e-3)
    assert 8.52e9 < plan.weight_bytes_per_chip < 8.56e9
    assert "window layers' pool" in plan.explain()
    core = _engine(params)
    info = JaxTpuClient.runtime_info(SimpleNamespace(core=core, cores=[core]))
    assert info["kv_window_pool_bytes"] == sum(
        a.nbytes for side in (core._kv_k, core._kv_v) for a in jax.tree.leaves(side["window"]))


def test_the_example_serve_config_is_taken_as_it_stands():
    import json
    from pathlib import Path

    from runbookai_tpu.cli.main import validate_config
    from runbookai_tpu.utils.config import load_config

    root = Path(__file__).resolve().parents[1]
    config = load_config(path=root / "examples" / "serve" / "trinity-mini-ep8.yaml")
    assert [p for p in validate_config(config) if "llm." in p] == []
    cfg = CONFIGS[config.llm.model]
    bench = json.loads((root / "benchmark/configs/trinity-mini-ep8-bf16.json").read_text())
    assert {k: getattr(cfg, k) for k in bench["reduced"]} == {k: bench[k] for k in bench["reduced"]}
    assert dict(bench["llm"]) == {k: getattr(config.llm, k) for k in bench["llm"]}
    plan = json.loads((root / "examples" / "serve" / Path(config.llm.plan).name).read_text())
    assert plan["engine"]["speculative"] is False
