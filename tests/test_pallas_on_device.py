"""Pallas kernels under Mosaic — REAL-hardware compile + numerics proof.

These tests are skipped on CPU (the interpret-mode twin lives in
``test_pallas_kernel.py``) and run when the session's backend is a TPU.
They cover the hazards a paged kernel has — context crossing page
boundaries, a final partial page, TQ padding — pallas-vs-XLA logit parity
on device, and (the ``serving_shapes`` block at the end) every kernel on
the ``runbook serve`` path at the head counts, page-table width and matmul
shapes that Qwen2.5-7B and Llama-3-8B actually serve with.

Run on the chip (the conftest otherwise forces the CPU):

    RUNBOOK_ON_DEVICE=1 python -m pytest tests/test_pallas_on_device.py -q
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.ops.attention import paged_attention
from runbookai_tpu.ops.paged_attention_pallas import (
    paged_chunk_attention,
    paged_decode_attention,
)

on_tpu = jax.devices()[0].platform == "tpu"
pytestmark = pytest.mark.skipif(
    not on_tpu, reason="requires a real TPU backend (Mosaic compile)")


@pytest.fixture
def serving_precision():
    """The precision `runbook serve` runs at: JAX's default. The conftest's
    process-wide "highest" makes Mosaic lower every in-kernel float32 dot
    with fp32 contraction passes — a program serving never runs. The
    tests above keep it (their claim is exact agreement of kernel and XLA
    logic, down to equal greedy tokens); the serving-shape tests take
    this fixture and compare at a tolerance instead."""
    with jax.default_matmul_precision("default"):
        yield


PS = 16  # page size


def _pool(rng, num_pages, n_kv=2, hd=128, dtype=jnp.bfloat16):
    shape = (num_pages * PS, n_kv, hd)
    k = jnp.asarray(rng.normal(size=shape), dtype)
    v = jnp.asarray(rng.normal(size=shape), dtype)
    return k, v


def _tables(ctx_lens, max_pages):
    """Distinct physical pages per sequence (page 0 reserved null)."""
    b = len(ctx_lens)
    out = np.zeros((b, max_pages), dtype=np.int32)
    nxt = 1
    for i, ctx in enumerate(ctx_lens):
        for p in range((ctx + PS - 1) // PS):
            out[i, p] = nxt
            nxt += 1
    return jnp.asarray(out)


@pytest.mark.parametrize("ctx_lens", [
    [PS * 3],           # exact page boundary
    [PS * 2 + 5],       # final partial page
    [1, PS * 4 - 1, PS] # ragged batch incl. 1-token ctx
])
def test_decode_kernel_compiles_and_matches_xla_on_device(ctx_lens):
    rng = np.random.default_rng(0)
    n_kv, group, hd = 2, 2, 128
    b = len(ctx_lens)
    k_flat, v_flat = _pool(rng, num_pages=32, n_kv=n_kv, hd=hd)
    tables = _tables(ctx_lens, max_pages=8)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, n_kv * group, hd)), jnp.bfloat16)

    got = paged_decode_attention(q, k_flat, v_flat, tables, ctx,
                                 page_size=PS, interpret=False)
    want = paged_attention(q[:, None], k_flat, v_flat, tables, ctx,
                           (ctx - 1)[:, None], page_size=PS)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("t,ctx_lens", [
    (8, [PS * 2 + 8]),       # chunk ends mid-page
    (5, [PS + 5, PS * 3]),   # TQ padding (5 % q_block) + ragged rows
    (16, [16, PS * 2 + 16]),
])
def test_chunk_kernel_compiles_and_matches_xla_on_device(t, ctx_lens):
    rng = np.random.default_rng(1)
    n_kv, group, hd = 2, 2, 128
    b = len(ctx_lens)
    k_flat, v_flat = _pool(rng, num_pages=32, n_kv=n_kv, hd=hd)
    tables = _tables(ctx_lens, max_pages=8)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    # chunk = the last t positions of each context (contiguous contract)
    positions = jnp.stack([jnp.arange(c - t, c, dtype=jnp.int32) for c in ctx_lens])
    q = jnp.asarray(rng.normal(size=(b, t, n_kv * group, hd)), jnp.bfloat16)

    got = paged_chunk_attention(q, k_flat, v_flat, tables, ctx, positions,
                                page_size=PS, interpret=False, q_block=4)
    want = paged_attention(q, k_flat, v_flat, tables, ctx, positions,
                           page_size=PS)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_full_forward_logit_parity_pallas_vs_xla_on_device():
    """End-to-end: the model forward with attn_impl='pallas' (Mosaic) vs
    'xla' on the same weights/cache must produce matching logits."""
    from runbookai_tpu.engine.kv_cache import KVCacheManager
    from runbookai_tpu.models.llama import CONFIGS, forward_impl, init_params

    cfg = CONFIGS["llama3-test"]
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    b, t = 2, 24
    kv = {}
    outs = {}
    for impl in ("xla", "pallas"):
        kvm = KVCacheManager(n_layers=cfg.n_layers, num_pages=64, page_size=4,
                             n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                             max_seq_len=64, dtype=jnp.bfloat16)
        tables = np.zeros((b, kvm.max_pages_per_seq + 1), dtype=np.int32)
        for i in range(b):
            rid = f"s{i}"
            kvm.add_sequence(rid)
            kvm.extend(rid, t)
            tables[i, : kvm.max_pages_per_seq] = kvm.page_table_row(rid)
        ids = np.random.default_rng(2).integers(3, 200, size=(b, t))
        positions = np.broadcast_to(np.arange(t, dtype=np.int32), (b, t))
        logits, _, _ = forward_impl(
            params, cfg, jnp.asarray(ids), jnp.asarray(positions),
            kvm.pool.kv_k, kvm.pool.kv_v, jnp.asarray(tables),
            jnp.asarray(np.full((b,), t, dtype=np.int32)),
            page_size=4, attn_impl=impl,
        )
        outs[impl] = np.asarray(logits, np.float32)
        kv[impl] = kvm
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               atol=5e-2, rtol=5e-2)


def test_engine_greedy_equivalence_pallas_vs_xla_on_device():
    """Engine end-to-end on the chip: identical greedy tokens with
    attn_impl='pallas' (Mosaic kernels) and 'xla' on the same weights."""
    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["llama3-test"]
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 250, size=n).tolist() for n in (9, 33, 17)]

    outs = {}
    for impl in ("xla", "pallas"):
        core = EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
            page_size=4, num_pages=128, max_batch_slots=4, prefill_chunk=16,
            max_seq_len=128, kv_dtype=jnp.bfloat16, block_pages=8,
            attn_impl=impl, speculative=False))
        reqs = [EngineRequest(prompt_ids=p,
                              sampling=SamplingParams(temperature=0.0,
                                                      max_new_tokens=12,
                                                      stop_token_ids=()))
                for p in prompts]
        for r in reqs:
            core.submit(r)
        core.run_until_idle()
        outs[impl] = [r.out_ids for r in reqs]
    # bf16 logits can tie-break argmax differently only if numerics diverge
    # materially; identical kernels-vs-XLA math must agree on greedy tokens.
    assert outs["pallas"] == outs["xla"]


def test_moe_engine_on_device():
    """Mixtral-style MoE serving on the chip: the scatter dispatch, batched
    expert einsums, and combine all compile and match greedy across two
    runs (determinism smoke)."""
    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["mixtral-test"]
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    tok = ByteTokenizer()

    def run():
        core = EngineCore(cfg, params, tok, EngineConfig(
            page_size=4, num_pages=128, max_batch_slots=2, prefill_chunk=16,
            max_seq_len=128, kv_dtype=jnp.bfloat16, block_pages=8,
            speculative=False))
        req = EngineRequest(prompt_ids=tok.encode("expert routing on tpu"),
                            sampling=SamplingParams(max_new_tokens=8,
                                                    stop_token_ids=()))
        core.submit(req)
        core.run_until_idle()
        return req.out_ids

    first = run()
    assert len(first) == 8
    assert run() == first


def test_lora_engine_on_device():
    """Per-row LoRA gather + rank-r einsums compile on the chip; the zero
    adapter is bit-exact base, a real adapter changes outputs."""
    import numpy as np

    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.models.lora import LoraRegistry
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["llama3-test"]
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    tok = ByteTokenizer()
    rng = np.random.default_rng(2)
    L, D, r = cfg.n_layers, cfg.dim, 4
    reg = LoraRegistry(cfg, rank=r, targets=("wq", "wv"), dtype=jnp.bfloat16)
    reg.register("tuned", {
        "wq": {"A": rng.normal(size=(L, D, r)) * 0.3,
               "B": rng.normal(size=(L, r, cfg.n_heads * cfg.head_dim)) * 0.3},
    })

    def run(adapter, use_reg):
        core = EngineCore(cfg, params, tok, EngineConfig(
            page_size=4, num_pages=128, max_batch_slots=2, prefill_chunk=16,
            max_seq_len=128, kv_dtype=jnp.bfloat16, block_pages=8,
            speculative=False), lora_registry=reg if use_reg else None)
        req = EngineRequest(prompt_ids=tok.encode("lora on tpu"),
                            sampling=SamplingParams(max_new_tokens=8,
                                                    stop_token_ids=()),
                            adapter=adapter)
        core.submit(req)
        core.run_until_idle()
        return req.out_ids

    base = run(None, use_reg=False)
    assert run(None, use_reg=True) == base   # zero adapter exactness
    assert run("tuned", use_reg=True) != base


@pytest.mark.parametrize("ctx_lens", [[PS * 2 + 5], [1, PS * 4 - 1, PS]])
def test_decode_kernel_fp8_kv_on_device(ctx_lens):
    """Mosaic compiles the decode kernel with fp8 K/V refs (the in-VMEM
    widen) and matches the XLA gather path on the same fp8 pool."""
    rng = np.random.default_rng(2)
    n_kv, group, hd = 2, 2, 128
    b = len(ctx_lens)
    k_flat, v_flat = _pool(rng, num_pages=32, n_kv=n_kv, hd=hd,
                           dtype=jnp.float8_e4m3fn)
    tables = _tables(ctx_lens, max_pages=8)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, n_kv * group, hd)), jnp.bfloat16)

    got = paged_decode_attention(q, k_flat, v_flat, tables, ctx,
                                 page_size=PS, interpret=False)
    want = paged_attention(q[:, None], k_flat, v_flat, tables, ctx,
                           (ctx - 1)[:, None], page_size=PS)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_chunk_kernel_fp8_kv_on_device():
    rng = np.random.default_rng(3)
    n_kv, group, hd, t = 2, 2, 128, 8
    ctx_lens = [PS * 2 + 8, PS + 5]
    b = len(ctx_lens)
    k_flat, v_flat = _pool(rng, num_pages=32, n_kv=n_kv, hd=hd,
                           dtype=jnp.float8_e4m3fn)
    tables = _tables(ctx_lens, max_pages=8)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    positions = jnp.stack(
        [jnp.arange(c - t, c, dtype=jnp.int32) for c in ctx_lens])
    q = jnp.asarray(rng.normal(size=(b, t, n_kv * group, hd)), jnp.bfloat16)

    got = paged_chunk_attention(q, k_flat, v_flat, tables, ctx, positions,
                                page_size=PS, interpret=False, q_block=4)
    want = paged_attention(q, k_flat, v_flat, tables, ctx, positions,
                           page_size=PS)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_qmm_pallas_kernel_on_device():
    """Mosaic compiles the int8 qmm kernel and matches the XLA expression
    at a decode shape (the r4 dequant-fusion lever)."""
    from runbookai_tpu.models.quant import quantize_tensor
    from runbookai_tpu.ops.qmm_pallas import qmm_pallas

    key = jax.random.PRNGKey(0)
    m, k, n = 8, 4096, 4096
    w = jax.random.normal(key, (k, n), jnp.float32) / k**0.5
    wq = quantize_tensor(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.bfloat16)
    ref = (x @ wq["q"].astype(x.dtype)) * wq["s"].astype(x.dtype)
    got = qmm_pallas(x, wq["q"], wq["s"].reshape(1, n), interpret=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_fp8_engine_pallas_on_device():
    """Serving engine with fp8 KV + Pallas attention end-to-end on chip:
    the init probe must keep the kernel path and decode must complete."""
    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["llama3-test"]
    tok = ByteTokenizer()
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    core = EngineCore(cfg, params, tok, EngineConfig(
        page_size=16, num_pages=64, max_batch_slots=2, prefill_chunk=16,
        max_seq_len=128, kv_dtype=jnp.float8_e4m3fn, attn_impl="pallas",
        speculative=False))
    assert core.ecfg.attn_impl == "pallas", "probe downgraded on device"
    req = EngineRequest(prompt_ids=tok.encode("fp8 on device"),
                        sampling=SamplingParams(max_new_tokens=8,
                                                stop_token_ids=()))
    core.submit(req)
    core.run_until_idle()
    assert len(req.out_ids) == 8


def _merge_partials(parts):
    """The cross-shard flash merge of ``parallel/kv_split.py`` (a psum
    under shard_map in serving), on the host: (acc, m, l) a shard."""
    m_g = functools.reduce(jnp.maximum, [m for _, m, _ in parts])
    corr = [jnp.exp(m - m_g) for _, m, _ in parts]
    l_g = sum(c * l for c, (_, _, l) in zip(corr, parts))
    acc_g = sum(c[..., None] * acc for c, (acc, _, _) in zip(corr, parts))
    return acc_g / jnp.maximum(l_g[..., None], 1e-30)


def test_kv_split_partial_kernel_on_device():
    """Mosaic compiles the ownership-masked partial decode kernel; the
    two-shard merge (host-side here, psum under shard_map in serving)
    equals the full-pool kernel."""
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_partial,
    )

    rng = np.random.default_rng(7)
    n_kv, group, hd = 2, 2, 128
    ctx_lens = [PS * 2 + 5, PS]
    b = len(ctx_lens)
    num_pages, pg = 32, 2
    k_flat, v_flat = _pool(rng, num_pages=num_pages, n_kv=n_kv, hd=hd)
    tables = _tables(ctx_lens, max_pages=8)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, n_kv * group, hd)), jnp.bfloat16)

    want = paged_decode_attention(q, k_flat, v_flat, tables, ctx,
                                  page_size=PS, interpret=False)
    pages_local = num_pages // pg
    tokens_local = pages_local * PS
    parts = []
    for s in range(pg):
        k_l = k_flat[s * tokens_local:(s + 1) * tokens_local]
        v_l = v_flat[s * tokens_local:(s + 1) * tokens_local]
        parts.append(paged_decode_attention_partial(
            q, k_l, v_l, tables, ctx, jnp.int32(s), page_size=PS,
            pages_local=pages_local, interpret=False))
    np.testing.assert_allclose(np.asarray(_merge_partials(parts), np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_decode_program_no_dequant_materialization_on_device():
    """HLO byte accounting on REAL Mosaic output (tests/test_hlo_bytes.py
    is the CPU twin): the int8 qmm-pallas decode program must contain no
    wide buffer of any quantized weight's shape — on this backend the
    kernel path is the shipped default and the custom call is opaque, so
    a finding means XLA materialized a dequant around it. Also asserts
    the resident-argument accounting (weights at stored width + KV pool
    + O(batch) operands) holds on the device compiler."""
    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.hlo_bytes import (
        decode_accounting,
        lower_decode,
        quantized_weight_shapes,
        wide_weight_materializations,
    )
    from runbookai_tpu.models.llama import CONFIGS, LlamaConfig, init_params
    from runbookai_tpu.models.quant import quantize_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    # All seven matmuls kernel-eligible (see tests/test_hlo_bytes.py
    # CLEAN_CFG for the tile arithmetic).
    cfg = LlamaConfig(
        name="hlo-clean-test", vocab_size=262, dim=384, n_layers=2,
        n_heads=12, n_kv_heads=4, ffn_dim=1536, max_seq_len=512,
        rope_theta=10_000.0,
    )
    params = quantize_params(
        init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    core = EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
        page_size=16, num_pages=48, max_batch_slots=4, prefill_chunk=16,
        max_seq_len=256, block_pages=4, kv_dtype=jnp.bfloat16,
        attn_impl="pallas", qmm_impl="pallas"))
    assert core.ecfg.qmm_impl == "pallas"  # Mosaic probe kept the kernel
    compiled = lower_decode(core)
    bad = wide_weight_materializations(
        compiled.as_text(), quantized_weight_shapes(core.params))
    assert bad == [], "\n".join(bad)
    acc = decode_accounting(core, compiled)
    assert (0 <= acc["argument_size_in_bytes"] - acc["arguments_expected"]
            < 64 * 1024), acc


def test_xla_int8_decode_fusion_status_on_device():
    """Diagnostic twin: does the DEVICE compiler fuse the XLA int8
    dequant? r3's 1.6%-MFU number says it materialized then. Whatever
    the answer, the qmm-pallas program above must stay clean — this test
    only pins that the detector runs on device HLO and reports a
    deterministic count (re-benchmark the kernel premise if this ever
    reports zero)."""
    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.hlo_bytes import (
        lower_decode,
        quantized_weight_shapes,
        wide_weight_materializations,
    )
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.models.quant import quantize_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["llama3-test"]
    params = quantize_params(
        init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    core = EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
        page_size=16, num_pages=48, max_batch_slots=4, prefill_chunk=16,
        max_seq_len=256, block_pages=4, kv_dtype=jnp.bfloat16,
        qmm_impl="xla"))
    bad = wide_weight_materializations(
        lower_decode(core).as_text(), quantized_weight_shapes(core.params))
    print(f"on-device XLA int8 dequant materializations: {len(bad)}")
    for line in bad[:8]:
        print("  ", line[:140])
    assert isinstance(bad, list)  # diagnostic: count printed for BENCHLOG


def test_int8_kv_decode_kernel_on_device():
    """int8-scaled decode kernel under real Mosaic: extra rank-3 scale
    blocks + in-VMEM widen-multiply compile and match the XLA gather
    path on the same quantized pool (interpret twin:
    tests/test_int8_kv.py::test_int8_decode_kernel_interpret_parity)."""
    from runbookai_tpu.ops.attention import quantize_kv
    from runbookai_tpu.ops.attention import paged_attention as xla_paged

    rng = np.random.default_rng(0)
    n_kv, hd, n_q = 2, 128, 4
    tokens = 8 * PS
    raw = rng.normal(size=(tokens, n_kv, hd)).astype(np.float32)
    vals, scales = quantize_kv(jnp.asarray(raw, jnp.bfloat16))
    pool = (vals, scales)
    ctx_lens = [PS * 3, PS * 2 + 5]
    tables = _tables(ctx_lens, 4)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, n_q, hd)), jnp.bfloat16)

    got = paged_decode_attention(q, pool, pool, tables, ctx, page_size=PS)
    want = xla_paged(q[:, None], pool, pool, tables, ctx,
                     (ctx - 1)[:, None], page_size=PS)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_int8_kv_engine_pallas_on_device():
    """Engine with kv_dtype=int8 + attn pallas on the chip: the probe
    must keep the kernel (or this fails loudly), and greedy must match
    the XLA path."""
    from runbookai_tpu.engine.engine import EngineConfig, EngineCore
    from runbookai_tpu.engine.request import EngineRequest, SamplingParams
    from runbookai_tpu.models.llama import CONFIGS, init_params
    from runbookai_tpu.utils.tokens import ByteTokenizer

    cfg = CONFIGS["llama3-test"]
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    outs = {}
    for impl in ("pallas", "xla"):
        core = EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
            page_size=16, num_pages=64, max_batch_slots=2,
            prefill_chunk=16, max_seq_len=128, kv_dtype=jnp.int8,
            attn_impl=impl, speculative=False))
        if impl == "pallas":
            assert core.ecfg.attn_impl == "pallas", \
                "Mosaic rejected the int8 decode kernel probe on device"
        reqs = [EngineRequest(
            prompt_ids=list(np.random.default_rng(5).integers(
                3, 250, size=21)),
            sampling=SamplingParams(temperature=0.0, max_new_tokens=8,
                                    stop_token_ids=()))]
        for r in reqs:
            core.submit(r)
        core.run_until_idle()
        outs[impl] = [r.out_ids for r in reqs]
    assert outs["pallas"] == outs["xla"]


# ---------------------------------------------------------- serving shapes
#
# What `runbook serve` dispatches at the defaults (utils/config.LLMConfig:
# page_size 16, max_seq_len 8192, max_batch_slots 8, prefill_chunk 512):
# page tables 8192/16 + 1 trash column wide, 8 decode rows, 512-token
# chunks, the ragged mixed buffer at 8 slots x 8 + 512 tokens, and int8
# matmuls with M = 8. Head counts (n_q, n_kv, head_dim): Qwen2.5-7B has
# a GQA group of 7 — query-head slices at sublane offsets 0, 7, 14, 21,
# which no test above reaches — and Llama-3-8B a group of 4.
#
# Tolerance, attention (2e-2 abs and rel): both paths read the same bf16
# pool, run their q.k and p.v products at the MXU's default precision
# (float32 operands rounded to bf16, 2^-8 = 4e-3 relative) and accumulate
# in float32; they differ in blocking — 16-token pages against 512-token
# blocks — so in where the running maximum rescales and the order of the
# sums, and each rounds its output to bf16 once. Values are O(1), so a
# few bf16 steps is 2e-2; a wrong page, head or mask is O(1).

SERVING_HEADS = [(28, 4, 128), (32, 8, 128)]
SERVE_MAX_PAGES = 8192 // PS + 1
ATTN_TOL = dict(atol=2e-2, rtol=2e-2)


@pytest.mark.usefixtures("serving_precision")
@pytest.mark.parametrize("n_q,n_kv,hd", SERVING_HEADS)
def test_decode_kernel_at_serving_shapes(n_q, n_kv, hd):
    rng = np.random.default_rng(10)
    ctx_lens = [1, PS, 100, 513, 700, 1500, 1564, 64]  # 8 slots, ragged
    k_flat, v_flat = _pool(rng, num_pages=320, n_kv=n_kv, hd=hd)
    tables = _tables(ctx_lens, max_pages=SERVE_MAX_PAGES)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(len(ctx_lens), n_q, hd)), jnp.bfloat16)

    got = paged_decode_attention(q, k_flat, v_flat, tables, ctx,
                                 page_size=PS, interpret=False)
    want = paged_attention(q[:, None], k_flat, v_flat, tables, ctx,
                           (ctx - 1)[:, None], page_size=PS)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **ATTN_TOL)


# The benchmark cell's dispatch: 16 slots of which six or so hold a request
# (the others read 0..7 over an 8-pass window), a table 513 columns wide,
# Qwen2.5-7B's 28 / 4 heads — and one row at the full 8192 tokens. Pages a
# row does not own are poison (NaN; an int8 pool's scales) and so is every
# table column past a row's live pages: the walk may not fetch them.
CELL_CTX = [430, 3, 0, 612, 5, 250, 0, 1, 8192, 7, 0, 520, 2, 0, 260, 4]


def _poison_pool(rng, rows_ctx, n_kv=4, hd=128, num_pages=1024,
                 width=SERVE_MAX_PAGES):
    """A bf16 pool and the rows' tables in which every page no row owns,
    and every table column past a row's live pages, is poison (NaN)."""
    live = [-(-c // PS) for c in rows_ctx]
    k = np.full((num_pages * PS, n_kv, hd), np.nan, np.float32)
    v = np.full_like(k, np.nan)
    order = rng.permutation(np.arange(1, num_pages))
    tables = np.full((len(rows_ctx), width), order[-1], np.int32)
    nxt = 0
    for i, n in enumerate(live):
        for col in range(n):
            page = order[nxt]
            nxt += 1
            tables[i, col] = page
            rows = slice(page * PS, (page + 1) * PS)
            k[rows] = rng.normal(size=(PS, n_kv, hd))
            v[rows] = rng.normal(size=(PS, n_kv, hd))
    assert nxt < num_pages - 1
    return (jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16),
            jnp.asarray(tables))


def _cell_case(rng, n_q=28, n_kv=4, hd=128):
    k, v, tables = _poison_pool(rng, CELL_CTX, n_kv, hd)
    q = jnp.asarray(rng.normal(size=(len(CELL_CTX), n_q, hd)), jnp.bfloat16)
    return q, k, v, tables, jnp.asarray(CELL_CTX, jnp.int32)


@pytest.mark.usefixtures("serving_precision")
@pytest.mark.parametrize("pool", ["raw", "int8", "partial"])
def test_decode_walk_at_the_cells_shape(pool):
    from runbookai_tpu.ops.attention import quantize_kv
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_partial,
    )

    q, k, v, tables, ctx = _cell_case(np.random.default_rng(13))
    tol = ATTN_TOL
    if pool == "int8":
        dead = jnp.isnan(k[:, :, 0].astype(jnp.float32))
        (kq, ks), (vq, vs) = (quantize_kv(jnp.nan_to_num(a)) for a in (k, v))
        k = (kq, jnp.where(dead, jnp.nan, ks))
        v = (vq, jnp.where(dead, jnp.nan, vs))
        tol = dict(atol=5e-2, rtol=5e-2)
    # XLA's gather reads dead columns (and masks them afterwards).
    clean = jax.tree.map(jnp.nan_to_num, (k, v))
    want = paged_attention(q[:, None], *clean, tables, ctx,
                           jnp.maximum(ctx - 1, 0)[:, None], page_size=PS)[:, 0]
    if pool == "partial":
        half = k.shape[0] // 2
        parts = [paged_decode_attention_partial(
            q, k[s * half:(s + 1) * half], v[s * half:(s + 1) * half], tables,
            ctx, jnp.int32(s), page_size=PS, pages_local=half // PS,
            interpret=False) for s in range(2)]
        got = _merge_partials(parts)
    else:
        got = paged_decode_attention(q, k, v, tables, ctx, page_size=PS,
                                     interpret=False)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    live = np.asarray(CELL_CTX) > 0
    assert np.all(got[~live] == 0.0)  # an empty slot writes zeros
    np.testing.assert_allclose(got[live], want[live], **tol)


@pytest.mark.usefixtures("serving_precision")
@pytest.mark.parametrize("n_q,n_kv,hd", SERVING_HEADS)
def test_chunk_kernel_at_serving_shapes(n_q, n_kv, hd):
    """One 512-token prefill chunk per row at the query block the engine
    gets (chunk_q_block: 32 rows for 28 and for 32 heads), the second row
    deep into its context. At the parent commit the block for 28 heads
    was 36 rows — 252 per kv head, not a multiple of 8 — and Mosaic
    refused the kernel ("Invalid vector register cast")."""
    rng = np.random.default_rng(11)
    t, ctx_lens = 512, [512, 1200]
    k_flat, v_flat = _pool(rng, num_pages=128, n_kv=n_kv, hd=hd)
    tables = _tables(ctx_lens, max_pages=SERVE_MAX_PAGES)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    positions = jnp.stack(
        [jnp.arange(c - t, c, dtype=jnp.int32) for c in ctx_lens])
    q = jnp.asarray(rng.normal(size=(len(ctx_lens), t, n_q, hd)),
                    jnp.bfloat16)

    got = paged_chunk_attention(q, k_flat, v_flat, tables, ctx, positions,
                                page_size=PS, interpret=False)
    want = paged_attention(q, k_flat, v_flat, tables, ctx, positions,
                           page_size=PS)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **ATTN_TOL)


def _mixed_layout(dec_ctx, pf, rq, pf_tokens=512):
    """The mixed dispatch's flat buffer as ``EngineCore._run_mixed`` lays
    it out: a ``rq``-wide block a decode slot (one live token, an empty
    slot none), then ``pf_tokens`` prefill tokens holding the chunks ``pf``
    (``(first position, length)`` a row, each run padded to whole blocks),
    the rest pad blocks on a null row; pads carry the trash position.
    Returns the rows' contexts, every token's position and row, the real
    tokens' indices and where the pad blocks start."""
    slots = len(dec_ctx)
    n = slots * rq + pf_tokens
    rows_ctx = list(dec_ctx) + [start + ln for start, ln in pf] + [0]
    positions = np.full((n,), (SERVE_MAX_PAGES - 1) * PS, np.int32)  # trash
    row_ids = np.full((n,), len(rows_ctx) - 1, np.int32)  # the null row
    real = []
    for s, c in enumerate(dec_ctx):
        row_ids[s * rq:(s + 1) * rq] = s
        if c:
            positions[s * rq] = c - 1
            real.append(s * rq)
    off = slots * rq
    for j, (start, ln) in enumerate(pf):
        padded = -(-ln // rq) * rq
        positions[off:off + ln] = np.arange(start, start + ln)
        row_ids[off:off + padded] = slots + j
        real.extend(range(off, off + ln))
        off += padded
    return rows_ctx, positions, row_ids, real, off


@pytest.mark.usefixtures("serving_precision")
@pytest.mark.parametrize("n_q,n_kv,hd", SERVING_HEADS)
def test_ragged_kernel_at_serving_shapes(n_q, n_kv, hd):
    """The mixed dispatch's buffer exactly as EngineCore._run_mixed lays
    it out: 8 decode slots (one token in an 8-wide block each, two slots
    empty), then 512 prefill tokens holding two rows' chunks, then pad
    blocks on a null row; pads carry the trash position."""
    from runbookai_tpu.engine.engine import _RAGGED_BLOCK as RQ
    from runbookai_tpu.ops.attention import ragged_paged_attention
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention,
    )

    rng = np.random.default_rng(12)
    dec_ctx = [1, 40, 0, 513, 900, 0, 17, 1564]  # 0 = empty slot
    rows_ctx, positions, row_ids, real, _ = _mixed_layout(
        dec_ctx, [(0, 300), (512, 100)], RQ)
    n = len(positions)
    k_flat, v_flat = _pool(rng, num_pages=320, n_kv=n_kv, hd=hd)
    tables = _tables(rows_ctx, max_pages=SERVE_MAX_PAGES)
    q = jnp.asarray(rng.normal(size=(n, n_q, hd)), jnp.bfloat16)
    args = (q, k_flat, v_flat, tables, jnp.asarray(rows_ctx, jnp.int32),
            jnp.asarray(positions), jnp.asarray(row_ids))

    got = paged_ragged_attention(*args, page_size=PS, ragged_block=RQ,
                                 interpret=False)
    want = ragged_paged_attention(*args, page_size=PS, ragged_block=RQ)
    np.testing.assert_allclose(np.asarray(got, np.float32)[real],
                               np.asarray(want, np.float32)[real],
                               **ATTN_TOL)


@pytest.mark.usefixtures("serving_precision")
def test_ragged_walk_at_the_cells_shape():
    """The benchmark cell's mixed dispatch: 80 eight-token blocks — the 16
    decode slots of ``CELL_CTX`` (one row at the full 8192 tokens, five
    empty), then 512 prefill tokens of two rows (a chunk behind 1,024
    cached tokens, the start of a prompt) and pad blocks on the null row —
    over a table 513 columns wide at Qwen2.5-7B's 28 / 4 heads. Pages no
    row owns are poison, and so is every table column past a row's live
    pages: the chunk walk may not fetch them."""
    from runbookai_tpu.engine.engine import _RAGGED_BLOCK as RQ
    from runbookai_tpu.ops.attention import ragged_paged_attention
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention,
    )

    rng = np.random.default_rng(14)
    rows_ctx, positions, row_ids, real, pads = _mixed_layout(
        CELL_CTX, [(1024, 300), (0, 200)], RQ)
    assert len(positions) // RQ == 80
    k, v, tables = _poison_pool(rng, rows_ctx)
    q = jnp.asarray(rng.normal(size=(len(positions), 28, 128)), jnp.bfloat16)
    rest = (tables, jnp.asarray(rows_ctx, jnp.int32),
            jnp.asarray(positions), jnp.asarray(row_ids))

    got = np.asarray(paged_ragged_attention(
        q, k, v, *rest, page_size=PS, ragged_block=RQ, interpret=False),
        np.float32)
    # XLA's gather reads dead columns (and masks them afterwards).
    want = ragged_paged_attention(q, jnp.nan_to_num(k), jnp.nan_to_num(v),
                                  *rest, page_size=PS, ragged_block=RQ)
    np.testing.assert_allclose(got[real], np.asarray(want, np.float32)[real],
                               **ATTN_TOL)
    assert np.all(got[pads:] == 0.0)  # a pad block writes zeros
    empty = [s * RQ for s, c in enumerate(CELL_CTX) if not c]
    assert np.all(got[empty] == 0.0)  # and so does an empty slot


# The two recurrent cells' softmax layers: 2 kv heads, the one-token rows on
# the decode walk (a prefill run keeps XLA's). Qwen3-Next's decode dispatch
# is 64 slots over a table 1,025 wide at 16 query heads of 256 (a row walks
# by kv head: the chunk walk at one query a block), Nemotron-3-Nano's 48
# over 513 at 32 heads of 128 (the decode walk). The pool is stacked, the
# layer the last, every other layer and every page no row owns poison; most
# slots are free, as in the cells.
RECURRENT_CELLS = [(16, 2, 256, 64, 1025, 3), (32, 2, 128, 48, 513, 6)]
_recurrent = pytest.mark.parametrize(
    "n_q,n_kv,hd,slots,width,layers", RECURRENT_CELLS,
    ids=["qwen3next", "nemotron"])


def _in_last_layer(a, layers):
    out = jnp.full((layers, *a.shape), jnp.nan, a.dtype)
    return out.at[layers - 1].set(a)


@pytest.mark.usefixtures("serving_precision")
@_recurrent
def test_decode_walk_at_the_recurrent_cells_shapes(n_q, n_kv, hd, slots,
                                                   width, layers):
    rng = np.random.default_rng(43)
    ctx_lens = [0] * slots
    for slot, c in zip(rng.permutation(slots),
                       [1, PS, PS + 1, 2500, 3100, 4097, 5000, 8191]):
        ctx_lens[slot] = min(c, (width - 1) * PS)
    k, v, tables = _poison_pool(rng, ctx_lens, n_kv, hd, num_pages=2048,
                                width=width)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, n_q, hd)), jnp.bfloat16)
    got = jax.jit(lambda k, v, layer: paged_decode_attention(
        q, k, v, tables, ctx, page_size=PS, interpret=False, layer=layer,
        name="paged_decode_walk"))(
            _in_last_layer(k, layers), _in_last_layer(v, layers),
            jnp.int32(layers - 1))
    want = paged_attention(q[:, None], jnp.nan_to_num(k), jnp.nan_to_num(v),
                           tables, ctx, jnp.maximum(ctx - 1, 0)[:, None],
                           page_size=PS)[:, 0]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    live = np.asarray(ctx_lens) > 0
    assert np.all(got[~live] == 0.0)  # a free slot writes zeros
    np.testing.assert_allclose(got[live], want[live], **ATTN_TOL)


# The cell's pool where it lies: ``bf16[28, 49152, 4, 128]``, the layer a
# traced scalar as in the layer scan's body, against the same call on the
# layer's slice (what the scan handed the kernels before), BIT FOR BIT.
# The layers tried hold the case's pages, each scaled differently; every
# other layer is poison (NaN; an int8 pool's scales), so a walk that read
# another layer's page fails. That Mosaic takes the page view with the two
# leading axes merged, 86,016 pages, is what these prove.
CELL_LAYERS = [0, 13, 27]
CELL_PAGES = 3072


def _in_cells_pool(pool):
    """``pool [49152, ...]`` (or an int8 pool's pair) as layers 0, 13 and
    27 of 28, times 1, 2 and 3; the other layers poison."""
    def stack(a):
        if a.dtype == jnp.int8:  # values: any; their scales are the poison
            out = jnp.full((28, *a.shape), 77, a.dtype)
            return out.at[jnp.asarray(CELL_LAYERS)].set(a)
        out = jnp.full((28, *a.shape), jnp.nan, a.dtype)
        for i, layer in enumerate(CELL_LAYERS):
            out = out.at[layer].set(a * (i + 1))
        return out
    return jax.tree.map(stack, pool)


def _assert_same_as_on_the_slice(walk, k, v):
    k, v = _in_cells_pool(k), _in_cells_pool(v)
    assert jax.tree.leaves(k)[0].shape == (28, CELL_PAGES * PS, 4, 128)
    in_place = jax.jit(walk)
    outs = []
    for layer in CELL_LAYERS:
        got = in_place(k, v, jnp.int32(layer))
        want = walk(*jax.tree.map(lambda a: a[layer], (k, v)))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))
        outs.append(np.asarray(jax.tree.leaves(got)[0], np.float32))
    assert not np.array_equal(outs[0], outs[1])  # the layers do differ
    return outs[0]


@pytest.mark.usefixtures("serving_precision")
@pytest.mark.parametrize("pool", ["raw", "int8", "partial"])
def test_decode_walk_reads_its_layer_of_the_cells_pool(pool):
    """The decode dispatch of ``test_decode_walk_at_the_cells_shape`` (16
    rows, a table 513 wide, one row at 8192 tokens, five empty) for every
    kind of pool."""
    from runbookai_tpu.ops.attention import quantize_kv
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_partial,
    )

    rng = np.random.default_rng(15)
    k, v, tables = _poison_pool(rng, CELL_CTX, num_pages=CELL_PAGES)
    q = jnp.asarray(rng.normal(size=(len(CELL_CTX), 28, 128)), jnp.bfloat16)
    ctx = jnp.asarray(CELL_CTX, jnp.int32)
    if pool == "int8":
        dead = jnp.isnan(k[:, :, 0].astype(jnp.float32))
        (kq, ks), (vq, vs) = (quantize_kv(jnp.nan_to_num(a)) for a in (k, v))
        k = (kq, jnp.where(dead, jnp.nan, ks))
        v = (vq, jnp.where(dead, jnp.nan, vs))

    def walk(k, v, layer=None):
        if pool != "partial":
            return paged_decode_attention(q, k, v, tables, ctx, page_size=PS,
                                          interpret=False, layer=layer)
        # The second of two shards: its page slice of every layer.
        half = k.shape[-3] // 2
        return paged_decode_attention_partial(
            q, k[..., half:, :, :], v[..., half:, :, :], tables, ctx,
            jnp.int32(1), page_size=PS, pages_local=half // PS,
            interpret=False, layer=layer)

    got = _assert_same_as_on_the_slice(walk, k, v)
    live = np.asarray(CELL_CTX) > 0
    assert not np.isnan(got).any() and np.all(got[~live] == 0.0)


@pytest.mark.usefixtures("serving_precision")
def test_ragged_walk_reads_its_layer_of_the_cells_pool():
    """The mixed dispatch of ``test_ragged_walk_at_the_cells_shape``: 80
    eight-token blocks over the cell's pool."""
    from runbookai_tpu.engine.engine import _RAGGED_BLOCK as RQ
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention,
    )

    rng = np.random.default_rng(16)
    rows_ctx, positions, row_ids, real, pads = _mixed_layout(
        CELL_CTX, [(1024, 300), (0, 200)], RQ)
    assert len(positions) // RQ == 80
    k, v, tables = _poison_pool(rng, rows_ctx, num_pages=CELL_PAGES)
    q = jnp.asarray(rng.normal(size=(len(positions), 28, 128)), jnp.bfloat16)
    rest = (tables, jnp.asarray(rows_ctx, jnp.int32),
            jnp.asarray(positions), jnp.asarray(row_ids))

    def walk(k, v, layer=None):
        return paged_ragged_attention(
            q, k, v, *rest, page_size=PS, ragged_block=RQ, interpret=False,
            layer=layer)[jnp.asarray(real + list(range(pads, len(positions))))]

    got = _assert_same_as_on_the_slice(walk, k, v)
    assert not np.isnan(got).any() and np.all(got[len(real):] == 0.0)


@pytest.mark.usefixtures("serving_precision")
def test_chunk_walk_reads_its_layer_of_the_cells_pool():
    """``_prefill_step``'s call: a 512-token chunk behind 1,024 cached
    tokens and the start of a prompt, at the query block the engine gets
    (32 rows), over the cell's pool."""
    rng = np.random.default_rng(17)
    t, ctx_lens = 512, [1536, 512]
    k, v, tables = _poison_pool(rng, ctx_lens, num_pages=CELL_PAGES)
    ctx = jnp.asarray(ctx_lens, jnp.int32)
    positions = jnp.stack(
        [jnp.arange(c - t, c, dtype=jnp.int32) for c in ctx_lens])
    q = jnp.asarray(rng.normal(size=(2, t, 28, 128)), jnp.bfloat16)

    def walk(k, v, layer=None):
        return paged_chunk_attention(q, k, v, tables, ctx, positions,
                                     page_size=PS, interpret=False,
                                     layer=layer)

    assert not np.isnan(_assert_same_as_on_the_slice(walk, k, v)).any()


@pytest.mark.usefixtures("serving_precision")
@pytest.mark.parametrize("k,n", [(3584, 3584), (3584, 512),
                                 (3584, 18944), (18944, 3584)])
def test_qmm_kernel_at_qwen7b_shapes(k, n):
    """The four int8 matmuls of a Qwen2.5-7B layer (q/o, k/v, gate/up,
    down) at M = 8 decode rows, against the XLA expression llama.qmm
    falls back to.

    Tolerance (5e-2 abs and rel): both sum the same exact bf16 x int8
    products in float32; the kernel scales the float32 sum and rounds
    once, the XLA expression rounds the sum to bf16, the scale to bf16
    and their product to bf16 — three roundings of 2^-8 on outputs that
    are N(0, 1) by construction (|y| up to ~4): 4 * 3 * 4e-3 = 5e-2."""
    from runbookai_tpu.models.quant import quantize_tensor
    from runbookai_tpu.ops.qmm_pallas import qmm_pallas, qmm_pallas_eligible

    m = 8
    assert qmm_pallas_eligible(m, k, n)
    w = jax.random.normal(jax.random.PRNGKey(k + n), (k, n),
                          jnp.float32) / k**0.5
    wq = quantize_tensor(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.bfloat16)
    ref = (x @ wq["q"].astype(x.dtype)) * wq["s"].astype(x.dtype)
    got = qmm_pallas(x, wq["q"], wq["s"].reshape(1, n), interpret=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)


@pytest.mark.usefixtures("serving_precision")
@pytest.mark.parametrize("m", [16, 128])
@pytest.mark.parametrize("k,n", [(3584, 3584), (3584, 512),
                                 (3584, 18944), (18944, 3584)])
def test_qmm_kernel_reads_its_layer_of_the_cells_stack(k, n, m):
    """The call the decode programs make, at the dense cell's size: the
    stacked ``s8[28, K, N]`` array of each of a Qwen2.5-7B layer's seven
    matrices (four shapes) and a layer's number, at the rows of
    ``_decode_multi`` (16) and of ``_decode_spec`` (128), against the XLA
    expression on that layer's slice — the first layer, one inside, the
    last — and not another layer's. int8 values and scales drawn directly
    (a float32 stack to quantize would not fit beside it); the scale makes
    outputs N(0, 1), so the tolerance is ``test_qmm_kernel_at_qwen7b_
    shapes``'s."""
    from runbookai_tpu.ops.qmm_pallas import qmm_pallas, qmm_pallas_eligible

    layers = 28
    assert qmm_pallas_eligible(m, k, n)
    kq, ks, kx = jax.random.split(jax.random.PRNGKey(k + n + m), 3)
    q = jax.random.randint(kq, (layers, k, n), -127, 128, dtype=jnp.int8)
    s = (3 ** 0.5 / (127.0 * k ** 0.5)) * jax.random.uniform(
        ks, (layers, 1, n), jnp.float32, 0.5, 1.5)
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)

    def ref(layer):
        return ((x @ q[layer].astype(x.dtype)) * s[layer].astype(x.dtype)
                ).astype(jnp.float32)

    for layer in (0, 13, 27):
        got = qmm_pallas(x, q, s[layer], jnp.int32(layer), interpret=False)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref(layer)),
                                   atol=5e-2, rtol=5e-2)
    assert not np.allclose(np.asarray(got, np.float32), np.asarray(ref(26)),
                           atol=0.5, rtol=0.5)


@pytest.mark.usefixtures("serving_precision")
def test_forward_logits_pallas_vs_xla_at_qwen7b_widths():
    """Two Qwen2.5-7B-wide int8 layers (hidden 3584, 28/4 heads of 128,
    FFN 18944, q/k/v biases) through the serving forward: a 64-token
    prefill of 8 rows (chunk kernel, two 32-row query blocks), then
    one decode step (decode kernel + the int8 matmul kernel at M = 8),
    kernels against the XLA path on the same weights.

    Tolerance: bf16 activations round after each of ~14 matmuls (4e-3
    each, accumulating like a random walk to ~1.5% of a logit's spread),
    and the maximum over 8 x 2048 logits of such noise is ~4 sigma; a
    wrong head order, page or scale moves logits by their whole spread.
    So: max |difference| below a tenth of the reference's standard
    deviation."""
    from runbookai_tpu.engine.kv_cache import KVCacheManager
    from runbookai_tpu.models.llama import (
        LlamaConfig,
        forward,
        init_params_quantized,
    )

    cfg = LlamaConfig(
        name="qwen7b-width-2layer", vocab_size=2048, dim=3584, n_layers=2,
        n_heads=28, n_kv_heads=4, ffn_dim=18_944, rope_theta=1_000_000.0,
        max_seq_len=2048, qkv_bias=True, family="qwen2")
    params = init_params_quantized(jax.random.PRNGKey(0), cfg,
                                   dtype=jnp.bfloat16)
    b, t = 8, 64
    ids = np.random.default_rng(4).integers(0, cfg.vocab_size, size=(b, t))
    positions = np.broadcast_to(np.arange(t, dtype=np.int32), (b, t))
    logits = {}
    for impl in ("xla", "pallas"):
        kvm = KVCacheManager(
            n_layers=cfg.n_layers, num_pages=128, page_size=PS,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            max_seq_len=1024, dtype=jnp.bfloat16)
        tables = np.zeros((b, kvm.max_pages_per_seq + 1), dtype=np.int32)
        for i in range(b):
            kvm.add_sequence(f"s{i}")
            kvm.extend(f"s{i}", t + 1)
            tables[i, :kvm.max_pages_per_seq] = kvm.page_table_row(f"s{i}")
        kw = dict(page_size=PS, attn_impl=impl, qmm_impl=impl)
        pre, kv_k, kv_v = forward(
            params, cfg, jnp.asarray(ids), jnp.asarray(positions),
            kvm.pool.kv_k, kvm.pool.kv_v, jnp.asarray(tables),
            jnp.full((b,), t, jnp.int32), **kw)
        nxt = jnp.argmax(pre[:, -1], axis=-1).astype(jnp.int32)[:, None]
        dec, _, _ = forward(
            params, cfg, nxt, jnp.full((b, 1), t, jnp.int32), kv_k, kv_v,
            jnp.asarray(tables), jnp.full((b,), t + 1, jnp.int32), **kw)
        logits[impl] = (np.asarray(pre[:, -1], np.float32),
                        np.asarray(nxt), np.asarray(dec[:, -1], np.float32))
    for stage, idx in (("prefill", 0), ("decode", 2)):
        ref, got = logits["xla"][idx], logits["pallas"][idx]
        if stage == "decode" and not np.array_equal(
                logits["xla"][1], logits["pallas"][1]):
            pytest.skip("greedy first tokens differ (a bf16 tie); the "
                        "decode inputs are not comparable")
        assert np.isfinite(got).all()
        assert np.abs(got - ref).max() < 0.1 * ref.std(), (
            stage, float(np.abs(got - ref).max()), float(ref.std()))
