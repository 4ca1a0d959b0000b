"""HLO byte accounting: the perf claims, falsifiable without a chip.

VERDICT r4 next-round #2: the int8 serving story rested on byte-count
arguments. These tests pin it to the COMPILED decode program instead:

- the detector (`wide_weight_materializations`) provably flags a forced
  bf16 weight materialization and stays silent on the streaming kernel;
- the engine's real decode dispatch on the XLA int8 path materializes a
  wide copy of EVERY quantized matrix on this backend (the r3 1.6%-MFU
  smoking gun, now structural), while the qmm-pallas path compiles with
  ZERO weight-shaped wide buffers when all matmuls are kernel-eligible;
- the compiled program's resident arguments equal weights-at-stored-width
  + KV pool + O(batch) operands; fp8 KV halves pool argument bytes
  exactly;
- `memory_plan` arithmetic cross-checks against a live engine's actual
  allocations (VERDICT r4 weak #4).

The on-device twins (real Mosaic, no interpret) live in
``test_pallas_on_device.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.engine.engine import EngineConfig, EngineCore
from runbookai_tpu.engine.hlo_bytes import (
    decode_accounting,
    kv_pool_nbytes,
    lower_decode,
    param_nbytes,
    quantized_weight_shapes,
    wide_weight_materializations,
)
from runbookai_tpu.engine.memory_plan import plan_serving
from runbookai_tpu.models.llama import CONFIGS, LlamaConfig, init_params
from runbookai_tpu.models.quant import LAYER_QUANT_KEYS, quantize_params
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["llama3-test"]

# Every matmul kernel-eligible AND Pallas tiles strictly smaller than the
# full matrix, so even the interpret emulation materializes nothing
# weight-shaped: wq/wo (384,384) bk=bn=128; wk/wv (384,128) bk=128;
# w_gate/up (384,1536) bn=512; w_down (1536,384) bk=512.
CLEAN_CFG = LlamaConfig(
    name="hlo-clean-test", vocab_size=262, dim=384, n_layers=2, n_heads=12,
    n_kv_heads=4, ffn_dim=1536, max_seq_len=512, rope_theta=10_000.0,
)


def make_core(cfg=CFG, dtype=jnp.bfloat16, **kw):
    params = quantize_params(init_params(jax.random.PRNGKey(0), cfg,
                                         dtype=dtype))
    d = dict(page_size=4, num_pages=48, max_batch_slots=4, prefill_chunk=8,
             max_seq_len=128, block_pages=4, kv_dtype=jnp.bfloat16)
    d.update(kw)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(**d))


# ------------------------------------------------------------- detector


def test_detector_flags_forced_materialization():
    """A bf16 weight copy forced via optimization_barrier MUST be caught —
    proves the scan isn't vacuous regardless of backend fusion choices."""
    K, N = 512, 1024
    x = jnp.zeros((8, K), jnp.bfloat16)
    q = jnp.zeros((K, N), jnp.int8)
    s = jnp.ones((1, N), jnp.float32)

    def f(x, q, s):
        wide = jax.lax.optimization_barrier(q.astype(x.dtype))
        return (x @ wide) * s.astype(x.dtype)

    txt = jax.jit(f).lower(x, q, s).compile().as_text()
    assert wide_weight_materializations(txt, {(K, N)})


def test_detector_clean_on_streaming_kernel():
    """The Pallas qmm streams [bk, bn] tiles — no full-matrix wide buffer
    exists even in the interpret-emulation lowering."""
    from runbookai_tpu.ops.qmm_pallas import qmm_pallas

    K, N = 512, 1024  # tiles (512, 512): strictly smaller than (K, N)
    x = jnp.zeros((8, K), jnp.bfloat16)
    q = jnp.zeros((K, N), jnp.int8)
    s = jnp.ones((1, N), jnp.float32)
    txt = (jax.jit(lambda x, q, s: qmm_pallas(x, q, s, interpret=True))
           .lower(x, q, s).compile().as_text())
    assert wide_weight_materializations(txt, {(K, N)}) == []


# ------------------------------------------- the engine's real programs


def test_engine_xla_int8_decode_materializes_dequants():
    """The XLA int8 expression materializes a wide copy of EVERY
    quantized matrix in the compiled decode program on this backend —
    the structural form of the r3 1.6%-MFU diagnosis. If this ever
    starts passing with zero findings, XLA learned to fuse the dequant
    and the qmm kernel's premise should be re-benchmarked."""
    core = make_core(qmm_impl="xla")
    bad = wide_weight_materializations(
        lower_decode(core).as_text(), quantized_weight_shapes(core.params))
    assert len(bad) >= len(LAYER_QUANT_KEYS)


def test_engine_qmm_pallas_decode_program_is_clean():
    """THE regression test (VERDICT r4 #2): with every matmul
    kernel-eligible, the compiled decode program contains no wide buffer
    of any quantized weight's shape. A dequant materialization sneaking
    back into the serving path fails this on CPU — no chip needed."""
    core = make_core(cfg=CLEAN_CFG, qmm_impl="pallas")
    assert core.ecfg.qmm_impl == "pallas"  # probe kept the kernel path
    bad = wide_weight_materializations(
        lower_decode(core).as_text(), quantized_weight_shapes(core.params))
    assert bad == [], "\n".join(bad)


def test_engine_xla_same_config_is_dirty():
    """Counterpart to the clean test on the SAME config: the difference
    is the kernel path, not the shapes."""
    core = make_core(cfg=CLEAN_CFG, qmm_impl="xla")
    bad = wide_weight_materializations(
        lower_decode(core).as_text(), quantized_weight_shapes(core.params))
    assert len(bad) >= 1


# ------------------------------------------------------ byte accounting


def test_decode_arguments_equal_weights_plus_kv():
    """Resident inputs of the compiled decode step == weights at stored
    width + KV pool + O(batch) operands (tokens/tables/rng/sampling —
    bounded small)."""
    core = make_core(qmm_impl="xla")
    acc = decode_accounting(core)
    small = acc["argument_size_in_bytes"] - acc["arguments_expected"]
    assert 0 <= small < 64 * 1024, acc
    # XLA's own traffic estimate for one fused decode step stays within a
    # small multiple of resident bytes; a dequant-materializing program
    # multiplies this (documented by the test above).
    assert acc["bytes_accessed"] < 20 * acc["arguments_expected"]


def test_fp8_kv_halves_pool_argument_bytes_exactly():
    core16 = make_core(kv_dtype=jnp.bfloat16, qmm_impl="xla")
    core8 = make_core(kv_dtype=jnp.float8_e4m3fn, qmm_impl="xla")
    assert kv_pool_nbytes(core8) * 2 == kv_pool_nbytes(core16)
    a16 = decode_accounting(core16)
    a8 = decode_accounting(core8)
    assert (a16["argument_size_in_bytes"] - a8["argument_size_in_bytes"]
            == kv_pool_nbytes(core8))


def test_memory_plan_matches_live_allocations():
    """plan_serving's hand arithmetic vs the engine's ACTUAL allocated
    tree and pool (VERDICT r4 weak #4): weights within 15% (the plan
    approximates scale rows), KV bytes/token exact."""
    from runbookai_tpu.engine.hlo_bytes import check_plan

    core = make_core(kv_dtype=jnp.bfloat16)
    plan = plan_serving(CFG, max_seq_len=128, batch=4, tp=1,
                        weights="int8", kv_dtype_bytes=2)
    got = check_plan(core, plan)
    assert got["actual_weight_bytes"] == param_nbytes(core.params)


def test_memory_plan_fp8_kv_cross_check():
    core = make_core(kv_dtype=jnp.float8_e4m3fn)
    plan = plan_serving(CFG, max_seq_len=128, batch=4, tp=1,
                        weights="int8", kv_dtype_bytes=1)
    from runbookai_tpu.engine.hlo_bytes import check_plan

    check_plan(core, plan)
