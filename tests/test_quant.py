"""Int8 weight-only quantization: numerics, serving, and TP sharding.

SURVEY.md §7 hard part 4: bf16 70B doesn't fit v5e-16; int8 weight-only is
the memory path. These tests pin the scheme's invariants on the tiny config.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.engine.engine import EngineConfig, EngineCore
from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.models.llama import CONFIGS, forward_train, init_params
from runbookai_tpu.models.quant import (
    LAYER_QUANT_KEYS,
    dequantize_params,
    dequantize_tensor,
    is_quantized,
    quantize_array_np,
    quantize_params,
    quantize_tensor,
    shardings_with_quant,
)
from runbookai_tpu.parallel.mesh import build_mesh
from runbookai_tpu.parallel.sharding import param_shardings
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["llama3-test"]


def test_roundtrip_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 48), dtype=jnp.float32)
    qt = quantize_tensor(w)
    assert qt["q"].dtype == jnp.int8 and qt["s"].shape == (2, 1, 48)
    back = dequantize_tensor(qt)
    # Symmetric rounding error is at most half a quantization step per element.
    assert np.all(np.abs(np.asarray(back - w)) <= np.asarray(qt["s"]) / 2 + 1e-7)


def test_numpy_and_jax_quantizers_agree():
    w = np.random.default_rng(0).normal(size=(3, 16, 8)).astype(np.float32)
    q_np, s_np = quantize_array_np(w)
    qt = quantize_tensor(jnp.asarray(w))
    np.testing.assert_array_equal(q_np, np.asarray(qt["q"]))
    np.testing.assert_allclose(s_np, np.asarray(qt["s"]), rtol=1e-6)


def test_quantize_params_structure_and_bytes():
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    qp = quantize_params(params)
    for k in LAYER_QUANT_KEYS:
        assert is_quantized(qp["layers"][k])
        # int8 payload is 1/4 the float32 bytes.
        assert qp["layers"][k]["q"].nbytes == params["layers"][k].nbytes // 4
    for k in ("attn_norm", "mlp_norm"):
        assert not is_quantized(qp["layers"][k])
    assert not is_quantized(qp["embed"])


def test_scale_after_matmul_equals_dequant_first():
    """(x @ q) * s must equal x @ (q * s) — the qmm identity."""
    params = init_params(jax.random.PRNGKey(1), CFG, dtype=jnp.float32)
    qp = quantize_params(params)
    deq = dequantize_params(qp, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 1, CFG.vocab_size)
    out_q = forward_train(qp, CFG, tokens)
    out_d = forward_train(deq, CFG, tokens)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_d),
                               atol=5e-3, rtol=5e-3)


def test_quantized_close_to_full_precision():
    params = init_params(jax.random.PRNGKey(1), CFG, dtype=jnp.float32)
    qp = quantize_params(params)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 1, CFG.vocab_size)
    full = np.asarray(forward_train(params, CFG, tokens)).ravel()
    quant = np.asarray(forward_train(qp, CFG, tokens)).ravel()
    cos = float(np.dot(full, quant) / (np.linalg.norm(full) * np.linalg.norm(quant)))
    assert cos > 0.99, f"quantized logits diverged: cos={cos:.4f}"


def test_engine_serves_quantized_params():
    tok = ByteTokenizer()
    params = quantize_params(init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32))
    core = EngineCore(CFG, params, tok, EngineConfig(
        page_size=4, num_pages=64, max_batch_slots=2, prefill_chunk=8,
        max_seq_len=128, block_pages=4, kv_dtype=jnp.float32))
    req = EngineRequest(prompt_ids=tok.encode("quantized serving"),
                        sampling=SamplingParams(temperature=0.0, max_new_tokens=6))
    core.submit(req)
    core.run_until_idle()
    assert req.finish_reason is not None and len(req.all_out_ids) >= 1


def test_tp_sharded_quantized_forward_matches():
    """Quantized forward over a (data=2, model=2) mesh == single-device."""
    params = quantize_params(init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 1, CFG.vocab_size)
    ref = forward_train(params, CFG, tokens)

    mesh = build_mesh(2, 2)
    sh = shardings_with_quant(param_shardings(CFG, mesh), params)
    assert isinstance(sh["layers"]["wq"], dict)
    sharded = jax.tree.map(jax.device_put, params, sh)
    out = forward_train(sharded, CFG, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-3, rtol=5e-3)


def test_init_params_quantized_structure_and_magnitude():
    """Direct-int8 random init (bench path for 8B-on-one-chip) matches the
    quantized-leaf format and the scaled-normal init magnitude."""
    from runbookai_tpu.models.llama import init_params_quantized

    p = init_params_quantized(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    for k in LAYER_QUANT_KEYS:
        assert is_quantized(p["layers"][k]), k
        assert p["layers"][k]["q"].dtype == jnp.int8
    # Dequantized std ~ 1/sqrt(fan_in) (same as init_params' scaled normal).
    w = dequantize_tensor(p["layers"]["w_down"])  # fan_in = ffn_dim
    got = float(jnp.std(w))
    want = 1.0 / np.sqrt(CFG.ffn_dim)
    assert 0.5 * want < got < 1.5 * want, (got, want)
    # And it serves through the engine unchanged.
    tok = ByteTokenizer()
    core = EngineCore(CFG, p, tok, EngineConfig(
        page_size=4, num_pages=64, max_batch_slots=2, prefill_chunk=8,
        max_seq_len=128, block_pages=4, kv_dtype=jnp.float32))
    req = EngineRequest(prompt_ids=tok.encode("int8 init"),
                        sampling=SamplingParams(temperature=0.0, max_new_tokens=4))
    core.submit(req)
    core.run_until_idle()
    assert req.finish_reason is not None


def test_param_count_matches_tree():
    """Analytic matmul_params/total_params equal the actual pytree sizes."""
    p = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    total = sum(x.size for x in jax.tree.leaves(p))
    assert total == CFG.total_params, (total, CFG.total_params)
    mm = sum(p["layers"][k].size for k in LAYER_QUANT_KEYS) + p["lm_head"].size
    assert mm == CFG.matmul_params, (mm, CFG.matmul_params)
    # North-star shape sanity: Llama-3-8B is 8.03B params.
    assert abs(CONFIGS["llama3-8b-instruct"].total_params - 8.03e9) < 0.02e9


def test_70b_int8_tp8_memory_plan_fits_v5e():
    """The documented 70B serving plan (int8 weights, tp=8, dp=2 on a
    v5e-16) must arithmetically fit the 16GB/chip HBM budget with KV-pool
    headroom — this is the math the sharded loader implements."""
    from runbookai_tpu.models.llama import CONFIGS

    cfg = CONFIGS["llama3-70b-instruct"]
    tp = 8
    hbm = 16 * 1024**3
    layer_matmul = cfg.matmul_params - cfg.dim * cfg.vocab_size
    int8_shard = layer_matmul / tp                      # 1 byte/param, sharded
    # Per-output-channel f32 scales: 4 bytes per output column (~dim-sized
    # rows); bounded by params/dim * 4.
    scales = layer_matmul / cfg.dim * 4 / tp
    embed = cfg.vocab_size * cfg.dim * 2 / tp           # bf16, vocab-sharded
    head = cfg.vocab_size * cfg.dim * 2 / tp
    norms = (cfg.n_layers * 2 + 1) * cfg.dim * 4        # f32, replicated
    weights_per_chip = int8_shard + scales + embed + head + norms
    assert weights_per_chip < 10.5 * 1024**3            # ~10GB/chip

    # Leaves >= 4GB for the KV pool: 70B GQA (8 kv heads sharded over tp=8
    # -> 1 head/chip), 128 head dim, 80 layers, bf16.
    kv_per_token = 80 * 2 * (cfg.n_kv_heads // tp) * 128 * 2
    budget = hbm - weights_per_chip - 1.5 * 1024**3     # runtime headroom
    tokens = budget / kv_per_token
    assert tokens > 80_000  # >80k pooled tokens/chip, e.g. 10 x 8k contexts


# --------------------------------------------------------------------- #
# Pallas quantized matmul (ops/qmm_pallas.py)                           #
# --------------------------------------------------------------------- #


def test_qmm_pallas_kernel_matches_xla_expression():
    """The streamed-int8 kernel computes exactly (x @ q) * s."""
    from runbookai_tpu.ops.qmm_pallas import qmm_pallas, qmm_pallas_eligible

    key = jax.random.PRNGKey(0)
    for m, k, n in [(8, 512, 1024), (3, 256, 512), (32, 1024, 1536),
                    (13, 96, 128)]:
        assert qmm_pallas_eligible(m, k, n)
        w = jax.random.normal(key, (k, n), jnp.float32) / k**0.5
        wq = quantize_tensor(w)
        x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
        ref = (x @ wq["q"].astype(x.dtype)) * wq["s"].astype(x.dtype)
        got = qmm_pallas(x, wq["q"], wq["s"].reshape(1, n), interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# The 7B cell's seven matrices, a quarter the size with the divisibility
# kept (3584 = 2^9 x 7 -> 896 = 2^7 x 7; 18944 = 2^9 x 37 -> 4736; 512 ->
# 128): wq/wo, wk/wv, w_gate/w_up, w_down.
CELL_SHAPES = [(896, 896), (896, 128), (896, 4736), (4736, 896)]
# ... and their blocks at full size and decode's M = 16.
BLOCKS_7B = {"w_gate": (128, 18944), "w_down": (512, 3584),
             "wq": (512, 3584), "wk": (3584, 512)}


@pytest.fixture
def quarter_blocks(monkeypatch):
    """The kernel's block budget scaled down with the shapes, so that a
    matrix is several blocks along K ((128, 896) for wq and w_down), or
    along N ((896, 128), 37 of them, for w_gate), as at full size. The
    budget is read while a call is traced, so what was traced under
    another budget goes."""
    from runbookai_tpu.ops import qmm_pallas

    monkeypatch.setattr(qmm_pallas, "_BLOCK_BYTES", 256 * 1024)
    jax.clear_caches()
    yield qmm_pallas
    jax.clear_caches()


def _stack(k, n, layers=3):
    w = jax.random.normal(jax.random.PRNGKey(k + n), (layers, k, n),
                          jnp.float32) / k**0.5
    return quantize_tensor(w)  # q [L, K, N] int8, s [L, 1, N] f32


@pytest.mark.parametrize("k,n", CELL_SHAPES)
@pytest.mark.parametrize("m", [1, 16, 17, 128, 256])
def test_qmm_pallas_stacked_call_reads_its_layer(quarter_blocks, m, k, n):
    """The call the decode programs make — the stacked ``[L, K, N]`` array
    and a layer's number — is ``(x @ q[l]) * s[l]`` for every layer of the
    stack, and a wrong number is a wrong answer."""
    qmm_pallas = quarter_blocks
    assert qmm_pallas.blocks(m, k, n) in {(128, 896), (896, 128)}
    wq = _stack(k, n)
    x = jax.random.normal(jax.random.PRNGKey(m), (m, k), jnp.float32)
    refs = [(x @ wq["q"][l].astype(x.dtype)) * wq["s"][l] for l in range(3)]
    for l in range(3):
        got = qmm_pallas.qmm_pallas(x, wq["q"], wq["s"][l], jnp.int32(l),
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(refs[l]),
                                   rtol=2e-5, atol=2e-5)
        wrong = refs[(l + 1) % 3]
        assert not np.allclose(np.asarray(got), np.asarray(wrong),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("k,n", CELL_SHAPES)
def test_qmm_pallas_one_matrix_is_the_stack_of_one(quarter_blocks, k, n):
    """The 2-D call is unchanged: the same kernel at L = 1."""
    qmm_pallas = quarter_blocks
    wq = _stack(k, n)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, k), jnp.float32)
    got = qmm_pallas.qmm_pallas(x, wq["q"][1], wq["s"][1], interpret=True)
    stacked = qmm_pallas.qmm_pallas(x, wq["q"][1:2], wq["s"][1],
                                    jnp.int32(0), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(stacked))
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray((x @ wq["q"][1].astype(x.dtype)) * wq["s"][1]),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k,n", [(896, 896), (896, 4736)])
def test_qmm_pallas_under_the_tpu_interpreter(quarter_blocks, k, n):
    """The same call where never-written VMEM reads as NaN and the copies
    and their semaphores are simulated (``pltpu.InterpretParams``): a
    block multiplied before its copy landed, or a buffer overwritten while
    it is read, would not match. bf16 activations, as served."""
    from jax.experimental.pallas import tpu as pltpu

    qmm_pallas = quarter_blocks
    wq = _stack(k, n)
    x = jax.random.normal(jax.random.PRNGKey(4), (16, k), jnp.bfloat16)
    got = qmm_pallas.qmm_pallas(x, wq["q"], wq["s"][2], jnp.int32(2),
                                interpret=pltpu.InterpretParams())
    ref = (x @ wq["q"][2].astype(x.dtype)).astype(jnp.float32) * wq["s"][2]
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_qmm_pallas_blocks_follow_the_shapes():
    """Block sizes are a value of (M, K, N) and the budgets stated once in
    the file, not an option: whole rows at decode's M, narrower where the
    accumulator of a verify-sized M would not fit; which stacks a forward
    hands over whole is a value of their bytes."""
    from runbookai_tpu.ops.qmm_pallas import blocks, reads_in_place

    assert blocks(16, 3584, 18944) == BLOCKS_7B["w_gate"]
    assert blocks(16, 18944, 3584) == BLOCKS_7B["w_down"]
    assert blocks(16, 3584, 3584) == BLOCKS_7B["wq"]
    assert blocks(16, 3584, 512) == BLOCKS_7B["wk"]
    for m in (128, 256):
        bk, bn = blocks(m, 3584, 18944)
        assert 18944 % bn == 0 and bn % 128 == 0 and bn < 18944
        assert 8 * m * bn <= 8 * 2 ** 20  # the output side's share
        assert 2 * bk * bn + 8 * m * bn <= 15 * 2 ** 20
        assert 3584 % bk == 0 and bk % 128 == 0
    # 28 layers of the 7B cell: the FFN and wq/wo stacks are read in place,
    # wk/wv (51 MB a stack: XLA would prefetch all 28 layers) are not.
    assert reads_in_place(16, (28, 3584, 18944))
    assert reads_in_place(16, (28, 3584, 3584))
    assert not reads_in_place(16, (28, 3584, 512))
    assert not reads_in_place(640, (28, 3584, 18944))  # the mixed step's M


def test_qmm_pallas_eligibility_boundaries():
    from runbookai_tpu.ops.qmm_pallas import MAX_PALLAS_M, qmm_pallas_eligible

    assert qmm_pallas_eligible(1, 32, 128)
    assert not qmm_pallas_eligible(1, 33, 128)  # K not tileable
    assert not qmm_pallas_eligible(1, 32, 64)  # N below one lane tile
    assert not qmm_pallas_eligible(MAX_PALLAS_M + 1, 4096, 14336)  # prefill M


def test_qmm_dispatch_uses_kernel_only_when_eligible():
    """qmm(impl='pallas') must route eligible decode shapes through the
    kernel and silently keep the XLA expression elsewhere — same math."""
    from runbookai_tpu.models.llama import qmm

    key = jax.random.PRNGKey(2)
    # Eligible: [B, T, K] @ [K, N] with N % 128 == 0.
    w = quantize_tensor(jax.random.normal(key, (256, 512), jnp.float32))
    x = jax.random.normal(key, (4, 2, 256), jnp.float32)
    a = qmm(x, w, impl="pallas")
    b = qmm(x, w, impl="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
    # Ineligible (N=64): must still be correct via fallback.
    w2 = quantize_tensor(jax.random.normal(key, (256, 64), jnp.float32))
    np.testing.assert_allclose(np.asarray(qmm(x, w2, impl="pallas")),
                               np.asarray(qmm(x, w2, impl="xla")),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["stack_read_in_place", "layer_sliced_by_scan"])
def test_engine_decode_matches_across_qmm_impls(in_place, monkeypatch):
    """Greedy engine decode with qmm_impl='pallas' reproduces the XLA
    path's tokens on a config whose projections are kernel-eligible, token
    for token: with every int8 stack handed to the kernel whole with the
    layer's number (what a serving model's large stacks get; the test
    model's are far under the size rule, so the rule is set aside), and
    with every matrix sliced out by the layer scan (what its small ones
    get)."""
    from runbookai_tpu.models.llama import LlamaConfig
    from runbookai_tpu.ops import qmm_pallas

    monkeypatch.setattr(qmm_pallas, "_ON_CHIP_BYTES",
                        0 if in_place else 1 << 40)
    jax.clear_caches()
    cfg = LlamaConfig(name="qmm-test", vocab_size=262, dim=128, n_layers=2,
                      n_heads=4, n_kv_heads=2, ffn_dim=256, max_seq_len=512,
                      rope_theta=10_000.0)
    assert qmm_pallas.reads_in_place(2, (2, 128, 256)) is in_place
    tok = ByteTokenizer()
    params = quantize_params(init_params(jax.random.PRNGKey(3), cfg,
                                         dtype=jnp.float32))
    prompt = tok.encode("paged attention decode parity")
    outs = {}
    for impl in ("xla", "pallas"):
        core = EngineCore(cfg, params, tok, EngineConfig(
            page_size=4, num_pages=64, max_batch_slots=2, prefill_chunk=16,
            max_seq_len=256, kv_dtype=jnp.float32, speculative=False,
            qmm_impl=impl))
        req = EngineRequest(prompt_ids=list(prompt),
                            sampling=SamplingParams(max_new_tokens=8,
                                                    stop_token_ids=()))
        core.submit(req)
        core.run_until_idle()
        outs[impl] = req.out_ids
    jax.clear_caches()
    assert outs["pallas"] == outs["xla"], outs


def test_70b_int8_tp16_kv_split_memory_plan():
    """tp=16 on 70B (past the 8 kv heads) now plans as model=8 × seq=2
    (parallel/kv_split.py): weights shard 16-way, the KV pool's TOKEN
    axis picks up the extra factor, and per-chip KV bytes shrink by the
    FULL tp — the r3 replication warning is gone."""
    from runbookai_tpu.models.llama import CONFIGS
    from runbookai_tpu.parallel.kv_split import plan_kv_split

    cfg = CONFIGS["llama3-70b-instruct"]
    plan = plan_kv_split(cfg, 16)
    assert (plan.kv_shards, plan.pg_shards) == (8, 2) and plan.split

    hbm = 16 * 1024**3
    tp = plan.tp
    layer_matmul = cfg.matmul_params - cfg.dim * cfg.vocab_size
    # wq/wo/FFN shard 16-way; wk/wv only 8-way (model axis). wk/wv are
    # 2 * dim * n_kv * hd per layer — a small slice of layer params.
    wkv = cfg.n_layers * 2 * cfg.dim * cfg.n_kv_heads * cfg.head_dim
    int8_shard = (layer_matmul - wkv) / tp + wkv / plan.kv_shards
    scales = layer_matmul / cfg.dim * 4 / tp
    embed = cfg.vocab_size * cfg.dim * 2 / tp
    head = cfg.vocab_size * cfg.dim * 2 / tp
    norms = (cfg.n_layers * 2 + 1) * cfg.dim * 4
    weights_per_chip = int8_shard + scales + embed + head + norms
    assert weights_per_chip < 6 * 1024**3  # ~2x headroom vs the tp8 plan

    # KV pool: heads /8 AND tokens /2 -> per-token bytes on a chip halve
    # relative to the tp8 plan.
    kv_per_token = (cfg.n_layers * 2 * (cfg.n_kv_heads // plan.kv_shards)
                    * cfg.head_dim * 2) / plan.pg_shards
    budget = hbm - weights_per_chip - 1.5 * 1024**3
    tokens = budget / kv_per_token
    assert tokens > 200_000  # >200k pooled tokens/chip at tp16
