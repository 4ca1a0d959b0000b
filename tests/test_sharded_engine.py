"""TP-sharded serving engine on a CPU mesh (VERDICT r1 weak #2 / next #3).

The paged serving forward is a different code path from ``forward_train`` —
the 70B-TP serving claim needs EngineCore itself proven on a >1-device mesh:
sharded params + sharded KV pool through the full continuous-batching cycle
(chunked prefill, batched decode, preemption-by-recompute, prefix cache),
with greedy outputs matching the unsharded engine.
"""

import jax
import jax.numpy as jnp
import pytest

from runbookai_tpu.engine.engine import EngineConfig, EngineCore
from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.models.llama import CONFIGS, init_params
from runbookai_tpu.parallel.mesh import MODEL_AXIS, build_mesh
from runbookai_tpu.parallel.sharding import param_shardings
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["llama3-test"]


@pytest.fixture(scope="module")
def setup():
    tok = ByteTokenizer()
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    mesh = build_mesh(1, 2)  # data=1, model=2 of the 8 virtual CPU devices
    sharded = jax.tree.map(jax.device_put, params, param_shardings(CFG, mesh))
    return tok, params, mesh, sharded


def make_core(tok, params, mesh=None, **kw):
    defaults = dict(
        page_size=4, num_pages=64, max_batch_slots=4, prefill_chunk=8,
        max_seq_len=128, block_pages=4, kv_dtype=jnp.float32,
    )
    defaults.update(kw)
    return EngineCore(CFG, params, tok, EngineConfig(**defaults), mesh=mesh)


def greedy(core, prompts, max_new=8):
    reqs = [
        EngineRequest(prompt_ids=list(p),
                      sampling=SamplingParams(temperature=0.0, max_new_tokens=max_new))
        for p in prompts
    ]
    for r in reqs:
        core.submit(r)
    core.run_until_idle()
    return reqs


def test_kv_pool_is_sharded_on_model_axis(setup):
    tok, params, mesh, sharded = setup
    core = make_core(tok, sharded, mesh=mesh)
    spec = core._kv_k.sharding.spec
    assert spec[2] == MODEL_AXIS, spec
    # Per-device shard holds half the kv heads.
    shard_shape = core._kv_k.addressable_shards[0].data.shape
    assert shard_shape[2] == CFG.n_kv_heads // 2


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_sharded_engine_matches_unsharded_greedy(setup, attn_impl):
    """The TP engine must agree with the unsharded engine on BOTH attention
    backends — ``pallas`` runs per head-shard via shard_map (interpret mode
    on the CPU mesh; Mosaic on hardware). VERDICT r2 next-round #3."""
    tok, params, mesh, sharded = setup
    prompts = [
        tok.encode("investigate high latency in checkout"),
        tok.encode("pods crashlooping in payments namespace"),
        tok.encode("error rate spike after deploy"),
    ]
    ref = greedy(make_core(tok, params), prompts)
    got = greedy(make_core(tok, sharded, mesh=mesh, attn_impl=attn_impl),
                 prompts)
    for r, g in zip(ref, got):
        assert g.out_ids == r.out_ids
        assert g.finish_reason == r.finish_reason


def test_sharded_engine_preemption_cycle(setup):
    """Tiny page pool forces preemption on the sharded engine; every request
    still completes and the KV pool stays sharded across the cycle."""
    tok, params, mesh, sharded = setup
    prompts = [tok.encode("a" * 21), tok.encode("b" * 21)]
    # 19 usable pages: each sequence at full length needs 16, so two can only
    # run together until the pool forces an eviction (same scenario as
    # test_engine.test_forced_preemption_mid_decode, now on the mesh).
    solos = [greedy(make_core(tok, params), [p], max_new=40)[0] for p in prompts]
    core = make_core(tok, sharded, mesh=mesh, num_pages=20, max_batch_slots=2)
    core.ecfg.decode_steps_per_dispatch = 1
    core.ecfg.admit_headroom_tokens = 8
    reqs = greedy(core, prompts, max_new=40)
    assert core.metrics["preemptions"] >= 1, "scenario must actually preempt"
    for r, solo in zip(reqs, solos):
        assert r.all_out_ids == solo.all_out_ids
    assert core.kv.allocator.free_pages == 20 - 1
    assert core._kv_k.sharding.spec[2] == MODEL_AXIS


def test_sharded_prefix_cache_reuse(setup):
    """Second request with a shared page-aligned prefix skips cached pages."""
    tok, params, mesh, sharded = setup
    core = make_core(tok, sharded, mesh=mesh)
    shared = tok.encode("system prompt: you are an SRE agent. " * 2)
    a = greedy(core, [shared + tok.encode("q1")], max_new=4)[0]
    b = greedy(core, [shared + tok.encode("q2")], max_new=4)[0]
    assert a.finish_reason is not None and b.finish_reason is not None
    assert core.metrics["cached_prefix_tokens"] > 0


# --------------------------------------------------------------------- #
# KV page-split serving (tp > n_kv_heads — parallel/kv_split.py)        #
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def kvsplit_setup():
    """llama3-test has n_kv=2, n_heads=4 → tp=4 plans as model=2 × seq=2
    (group 2, pg_shards 2). Per-chip KV bytes shrink by the FULL tp."""
    from runbookai_tpu.parallel.kv_split import plan_kv_split

    tok = ByteTokenizer()
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    plan = plan_kv_split(CFG, 4)
    assert (plan.kv_shards, plan.pg_shards) == (2, 2) and plan.split
    mesh = build_mesh(1, model=plan.kv_shards, seq=plan.pg_shards)
    sharded = jax.tree.map(jax.device_put, params,
                           param_shardings(CFG, mesh))
    return tok, params, mesh, sharded


def test_kv_split_pool_shards_by_full_tp(kvsplit_setup):
    from runbookai_tpu.parallel.mesh import SEQ_AXIS

    tok, params, mesh, sharded = kvsplit_setup
    core = make_core(tok, sharded, mesh=mesh)
    spec = core._kv_k.sharding.spec
    assert spec[1] == SEQ_AXIS and spec[2] == MODEL_AXIS, spec
    ratio = (core._kv_k.nbytes
             // core._kv_k.addressable_shards[0].data.nbytes)
    assert ratio == 4, "per-chip KV bytes must shrink by the full tp"


def test_kv_split_engine_matches_unsharded_greedy(kvsplit_setup):
    """Full continuous-batching cycle on the page-split mesh reproduces
    the unsharded engine's greedy tokens (r3 VERDICT weak #6)."""
    tok, params, mesh, sharded = kvsplit_setup
    prompts = [
        tok.encode("investigate high latency in checkout"),
        tok.encode("pods crashlooping in payments namespace"),
        tok.encode("error rate spike after deploy"),
    ]
    ref = greedy(make_core(tok, params), prompts)
    got = greedy(make_core(tok, sharded, mesh=mesh), prompts)
    for r, g in zip(ref, got):
        assert g.out_ids == r.out_ids
        assert g.finish_reason == r.finish_reason


def test_kv_split_plan_boundaries():
    from runbookai_tpu.parallel.kv_split import plan_kv_split

    class Cfg70B:
        n_kv_heads = 8
        n_heads = 64

    p = plan_kv_split(Cfg70B, 16)
    assert (p.kv_shards, p.pg_shards) == (8, 2) and p.split
    p8 = plan_kv_split(Cfg70B, 8)
    assert (p8.kv_shards, p8.pg_shards) == (8, 1) and not p8.split
    # group=8 caps the page split at 8 → tp 128 ok, beyond raises
    assert plan_kv_split(Cfg70B, 64).pg_shards == 8
    with pytest.raises(ValueError):
        plan_kv_split(Cfg70B, 256)


def test_kv_split_write_never_wraps_into_foreign_slots():
    """Regression (r4 review): a foreign page's destination is NEGATIVE on
    higher seq shards; .at[].set(mode='drop') drops only OOB-HIGH indices
    while negative ones wrap Python-style — a write to page 0 must not
    corrupt shard 1's mirror slot."""
    import numpy as np

    from runbookai_tpu.ops.attention import write_kv_pages_batch
    from runbookai_tpu.parallel.kv_split import (
        write_kv_pages_batch_kv_split,
    )

    mesh = build_mesh(1, model=2, seq=2)
    ps, num_pages, n_kv, hd = 4, 8, 2, 8
    tokens = num_pages * ps
    pool = jnp.zeros((tokens, n_kv, hd), jnp.float32)
    new_kv = jnp.ones((1, 2, n_kv, hd), jnp.float32)
    pos = jnp.asarray([[0, 1]], jnp.int32)
    tables = jnp.asarray([[1, 0, 0, 0]], jnp.int32)  # page 1 -> shard 0
    want = write_kv_pages_batch(pool, new_kv, pos, tables, ps)
    got = write_kv_pages_batch_kv_split(mesh, pool, new_kv, pos, tables, ps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The mirror slots on shard 1 (tokens 16+4..) must remain zero.
    assert float(jnp.abs(got[tokens // 2:]).max()) == 0.0


def test_kv_split_rejects_ragged_page_pool():
    from runbookai_tpu.parallel.kv_split import paged_attention_kv_split

    mesh = build_mesh(1, model=2, seq=2)
    ps, n_kv, hd = 4, 2, 8
    k = jnp.zeros((63 * ps, n_kv, hd), jnp.float32)  # 63 pages, pg=2
    with pytest.raises(ValueError, match="divide"):
        paged_attention_kv_split(
            mesh, jnp.zeros((1, 1, 4, hd), jnp.float32), k, k,
            jnp.zeros((1, 4), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), page_size=ps)


def test_kv_split_pallas_decode_matches_xla(kvsplit_setup):
    """The Pallas partial kernel + seq-merge must equal the XLA kv-split
    path AND the unsharded reference at decode shapes (interpret mode on
    the CPU mesh; Mosaic on hardware)."""
    import numpy as np

    from runbookai_tpu.ops.attention import paged_attention
    from runbookai_tpu.parallel.kv_split import (
        paged_attention_kv_split,
        paged_decode_attention_kv_split_pallas,
    )

    tok, params, mesh, sharded = kvsplit_setup
    rng = np.random.default_rng(5)
    n_q, n_kv, hd, ps = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim, 4
    num_pages, max_pages = 16, 8
    tokens = num_pages * ps
    k_flat = jnp.asarray(rng.normal(size=(tokens, n_kv, hd)), jnp.float32)
    v_flat = jnp.asarray(rng.normal(size=(tokens, n_kv, hd)), jnp.float32)
    ctx_lens = [9, 17]
    tables = np.zeros((2, max_pages), np.int32)
    alloc = list(range(1, 16))
    rng.shuffle(alloc)
    for i, c in enumerate(ctx_lens):
        for p in range((c + ps - 1) // ps):
            tables[i, p] = alloc.pop()
    tables = jnp.asarray(tables)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, n_q, hd)), jnp.float32)

    want = paged_attention(q[:, None], k_flat, v_flat, tables, ctx,
                           (ctx - 1)[:, None], page_size=ps)[:, 0]
    xla = paged_attention_kv_split(mesh, q[:, None], k_flat, v_flat,
                                   tables, ctx, (ctx - 1)[:, None],
                                   page_size=ps, block_pages=4)[:, 0]
    got = paged_decode_attention_kv_split_pallas(
        mesh, q, k_flat, v_flat, tables, ctx, page_size=ps, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla),
                               atol=1e-5, rtol=1e-5)


def test_kv_split_engine_pallas_matches_unsharded(kvsplit_setup):
    """Full engine cycle on the page-split mesh with attn_impl='pallas':
    decode runs the partial kernel, prefill the XLA kv-split path —
    greedy outputs must equal the unsharded engine."""
    tok, params, mesh, sharded = kvsplit_setup
    prompts = [tok.encode("kv split pallas decode parity check")]
    ref = greedy(make_core(tok, params), prompts)
    got = greedy(make_core(tok, sharded, mesh=mesh, attn_impl="pallas"),
                 prompts)
    assert got[0].out_ids == ref[0].out_ids


def test_qmm_probe_runs_under_multidevice_mesh():
    """ADVICE r4 medium: a DP-only multi-device mesh keeps qmm_impl=
    'pallas' in the model forward, so the init-time probe must compile
    the kernel under THAT mesh (replicated operands, GSPMD partitioning)
    — a partitioning failure has to downgrade at init, not crash the
    first dispatch."""
    from runbookai_tpu.engine.engine import (
        _probe_qmm_pallas_cached,
    )

    mesh = build_mesh(data=8)
    assert mesh.size == 8
    assert _probe_qmm_pallas_cached(
        "cpu", 8, 256, 512, "bfloat16", mesh=mesh)


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["stack_read_in_place", "layer_sliced_by_scan"])
def test_engine_int8_dp_mesh_serves(setup, in_place, monkeypatch):
    """int8 weights + multi-device DP-only mesh + qmm auto path: engine
    construction runs the mesh-aware probe and the first dispatch must
    not crash (the ADVICE r4 failure mode) — with the kernel handed the
    replicated stacks whole (a serving model's large ones; the size rule
    set aside for the test model's) and with each layer sliced out."""
    from runbookai_tpu.models.quant import quantize_params
    from runbookai_tpu.ops import qmm_pallas

    from runbookai_tpu.parallel.mesh import replicated

    monkeypatch.setattr(qmm_pallas, "_ON_CHIP_BYTES",
                        0 if in_place else 1 << 40)
    jax.clear_caches()
    tok, params, mesh, _ = setup
    dp_mesh = build_mesh(data=2)
    qparams = quantize_params(params)
    rep = jax.tree.map(
        lambda a: jax.device_put(a, replicated(dp_mesh)), qparams)
    prompts = [tok.encode("dp int8 qmm probe parity")]
    ref = greedy(make_core(tok, qparams), prompts)
    got = greedy(make_core(tok, rep, mesh=dp_mesh, qmm_impl="pallas"),
                 prompts)
    assert got[0].out_ids == ref[0].out_ids


# ------------------------------------------------------------------ #
# The Pallas attention kernels read the carried pool where it lies    #
# ------------------------------------------------------------------ #

LANE_CFG = type(CFG)(
    name="pool-in-place-test", vocab_size=262, dim=512, n_layers=3,
    n_heads=4, n_kv_heads=2, ffn_dim=256, max_seq_len=256,
    rope_theta=10_000.0)


@pytest.mark.parametrize("layout", ["one_device", "tp2", "kv_split_2x2"])
def test_engine_reads_the_pool_in_place(layout, monkeypatch):
    """The full cycle (chunked prefill, mixed steps, multi-step decode)
    with the Pallas kernels handed the whole ``[L, tokens, n_kv, hd]`` pool and the
    layer's number — alone, per head shard under ``shard_map`` and on the
    page-split mesh's partial kernel — equals the XLA engine's greedy
    tokens. Heads of 128 (a page view that is the bytes as they lie); the
    size rule, which at this size says "slice", is set aside as the qmm
    tests set theirs aside. It is read while a program is traced."""
    from runbookai_tpu.ops import paged_attention_pallas

    monkeypatch.setattr(paged_attention_pallas, "_ON_CHIP_BYTES", 0)
    jax.clear_caches()
    tok = ByteTokenizer()
    params = init_params(jax.random.PRNGKey(0), LANE_CFG, dtype=jnp.float32)
    mesh = {"one_device": None, "tp2": build_mesh(1, 2),
            "kv_split_2x2": build_mesh(1, model=2, seq=2)}[layout]
    placed = params if mesh is None else jax.tree.map(
        jax.device_put, params, param_shardings(LANE_CFG, mesh))

    def core(params, mesh=None, **kw):
        return EngineCore(LANE_CFG, params, tok, EngineConfig(
            page_size=4, num_pages=64, max_batch_slots=4, prefill_chunk=8,
            max_seq_len=128, block_pages=4, kv_dtype=jnp.float32, **kw),
            mesh=mesh)

    prompts = [tok.encode("investigate high latency in checkout"),
               tok.encode("pods crashlooping"),
               tok.encode("error rate spike after the deploy of payments")]
    try:
        ref = greedy(core(params), prompts, max_new=24)
        served = core(placed, mesh=mesh, attn_impl="pallas",
                      mixed_dispatch=True)  # (off again on a page split)
        assert paged_attention_pallas.reads_in_place(served._kv_k, mesh)
        # The first request decodes while the others prefill beside it.
        got = [EngineRequest(prompt_ids=list(p), sampling=SamplingParams(
            temperature=0.0, max_new_tokens=24)) for p in prompts]
        served.submit(got[0])
        while not served.decoding:
            served.step()
        for r in got[1:]:
            served.submit(r)
        served.run_until_idle()
    finally:
        jax.clear_caches()
    assert served.metrics["mixed_steps"] > 0 or layout == "kv_split_2x2"
    for r, g in zip(ref, got):
        assert g.out_ids == r.out_ids
