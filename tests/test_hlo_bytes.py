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
- no decode program with the Pallas matmul owns a layer-sized ``s8[K, N]``
  copy of a matrix the kernel reads in place in the stacked array
  (`layer_weight_copies`), on the CPU and for the described v5e;
- `memory_plan` arithmetic cross-checks against a live engine's actual
  allocations (VERDICT r4 weak #4);
- no step program owns a buffer of the KV pool's shape beyond the pool
  it was given, nor writes a whole layer through
  (`kv_pool_materializations`): the pool rides the layer scan's carry.
  Checked on the CPU's program and on the program compiled for a v5e
  chip that is described, not attached;
- no step program whose pool the Pallas attention kernels read in place
  owns one LAYER of the pool either (`kv_layer_slices`): the slice XLA
  staged on chip in front of every attention call, at the dense cell's
  real size too; a pool that fits on-chip memory keeps the slice;
- the sampler's vocabulary-wide ``sort`` lies in a branch of a
  ``conditional`` in every step program that samples, and none outside
  one (`sorts_by_conditional`), on the CPU, for the described v5e and at
  the dense cell's real size; a sampling request compiles no step program
  a greedy one had not.

- the held experts' fast path reads an expert that has a row where its
  matrices lie in the stack (`held_expert_copies`: no expert's matrix, no
  layer's experts and no stack copied), its loop over the experts touched
  sits behind a condition INSIDE the dispatch's ``conditional``, and that
  ``conditional`` keeps the ``f32[rows, hidden]`` result the benchmark's
  readers find it by (`expert_conditionals`): on the CPU, for the described
  v5e, and at the widths of the one leaf the device keeps transposed.

The on-device twins (real Mosaic, no interpret) live in
``test_pallas_on_device.py``.
"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbookai_tpu.engine import engine as engine_module
from runbookai_tpu.engine.engine import EngineConfig, EngineCore
from runbookai_tpu.engine.hlo_bytes import (
    decode_accounting,
    expert_conditionals,
    held_expert_copies,
    kv_layer_slices,
    kv_pool_materializations,
    kv_pool_nbytes,
    layer_weight_copies,
    lower_decode,
    param_nbytes,
    quantized_weight_shapes,
    sorts_by_conditional,
    wide_weight_materializations,
)
from runbookai_tpu.engine.memory_plan import plan_serving
from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.models.llama import (
    CONFIGS,
    LlamaConfig,
    init_params,
    init_params_quantized,
)
from runbookai_tpu.models.quant import LAYER_QUANT_KEYS, quantize_params
from runbookai_tpu.ops import paged_attention_pallas, qmm_pallas
from runbookai_tpu.utils.tokens import ByteTokenizer

CFG = CONFIGS["llama3-test"]

# Every matmul kernel-eligible AND, under ``miniature_blocks``, Pallas
# blocks strictly smaller than the full matrix, so even the interpret
# emulation materializes nothing weight-shaped: wq/wo (384,384) and wk/wv
# (384,128) in blocks of (128,128); w_gate/up (384,1536) (128,256); w_down
# (1536,384) (256,128).
CLEAN_CFG = LlamaConfig(
    name="hlo-clean-test", vocab_size=262, dim=384, n_layers=2, n_heads=12,
    n_kv_heads=4, ffn_dim=1536, max_seq_len=512, rope_theta=10_000.0,
)


@pytest.fixture
def miniature_blocks(monkeypatch):
    """The kernel's byte budgets scaled down with the models: a block of
    32 KB where the chip's is megabytes, and every stack read in place
    (at serving size only a stack larger than on-chip memory is). The
    budgets are read while a program is traced, so what was traced under
    other budgets goes, before and after."""
    monkeypatch.setattr(qmm_pallas, "_BLOCK_BYTES", 32 * 1024)
    monkeypatch.setattr(qmm_pallas, "_ON_CHIP_BYTES", 0)
    jax.clear_caches()
    yield
    jax.clear_caches()


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


def test_detector_clean_on_streaming_kernel(miniature_blocks):
    """The Pallas qmm streams [bk, bn] blocks — no full-matrix wide buffer
    exists even in the interpret-emulation lowering."""
    K, N = 512, 1024  # blocks (128, 256): strictly smaller than (K, N)
    assert qmm_pallas.blocks(8, K, N) == (128, 256)
    x = jnp.zeros((8, K), jnp.bfloat16)
    q = jnp.zeros((K, N), jnp.int8)
    s = jnp.ones((1, N), jnp.float32)
    txt = (jax.jit(lambda x, q, s: qmm_pallas.qmm_pallas(
        x, q, s, interpret=True)).lower(x, q, s).compile().as_text())
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


def test_engine_qmm_pallas_decode_program_is_clean(miniature_blocks):
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


# ------------------------------------- each int8 matrix is handled once
#
# The layer scan used to hand the Pallas matmul one layer's matrix as its
# ``xs``: XLA sliced ``s8[K, N]`` out of the stacked array in front of
# every call (on the chip 8.4 ms of a 25.4 ms decode pass beside the
# kernel's 6.8: PERF.md section 6, PR 30). The kernel now takes the stack
# and the layer's number.


# The layer scan's body as the TPU's compiler wrote it before (lines of
# ``_decode_multi`` compiled for a described v5e at the 7B cell's widths,
# shortened): the slice of the stack is a fusion that OWNS ``s8[K, N]``.
_SLICED_BODY = """
%fused_computation.7.clone (param_0.813: s8[28,3584,18944], param_1.910: s32[]) -> s8[3584,18944] {
  %param_0.813 = s8[28,3584,18944]{2,1,0:T(8,128)(4,1)} parameter(0)
  %param_1.910 = s32[]{:T(128)} parameter(1)
  %dynamic_slice.7 = s8[1,3584,18944]{2,1,0:T(8,128)(4,1)} dynamic-slice(%param_0.813, %param_1.910, %c, %c), dynamic_slice_sizes={1,3584,18944}
  ROOT %bitcast.7 = s8[3584,18944]{1,0:T(8,128)(4,1)} bitcast(%dynamic_slice.7)
}

%region_1.17 (arg_tuple.1: (s32[], bf16[16,1,3584], s8[28,3584,18944])) -> (s32[], bf16[16,1,3584], s8[28,3584,18944]) {
  %arg_tuple.1 = (s32[], bf16[16,1,3584], s8[28,3584,18944]) parameter(0)
  %get-tuple-element.2054 = s32[]{:T(128)} get-tuple-element(%arg_tuple.1), index=0
  %get-tuple-element.2102 = s8[28,3584,18944]{2,1,0:T(8,128)(4,1)} get-tuple-element(%arg_tuple.1), index=2
  %dynamic-slice_bitcast_fusion.30 = s8[3584,18944]{1,0:T(8,128)(4,1)S(1)} fusion(%get-tuple-element.2102, %get-tuple-element.2054), kind=kLoop, calls=%fused_computation.7.clone
  %qmm_pallas.81 = bf16[16,18944]{1,0:T(8,128)(2,1)S(1)} custom-call(%fusion.139, %dynamic-slice_bitcast_fusion.30, %dynamic-slice_bitcast_fusion.31), custom_call_target="tpu_custom_call"
}
"""


def test_detector_flags_a_layer_sliced_out_of_the_stack():
    """The detector names the fusion that owns one layer's matrix — not
    the stack's own views, nor the values inside the fusion's body — and
    is silent once the kernel's operand is the stack itself."""
    shapes = {(28, 3584, 18944), (3584, 18944), (1, 3584, 18944)}
    bad = layer_weight_copies(_SLICED_BODY, shapes)
    assert [ln.split()[0] for ln in bad] == [
        "%dynamic-slice_bitcast_fusion.30"]
    in_place = "\n".join(
        ln.replace("%dynamic-slice_bitcast_fusion.30,",
                   "%get-tuple-element.2054, %get-tuple-element.2102,")
        for ln in _SLICED_BODY.splitlines()
        if not ln.lstrip().startswith("%dynamic-slice_bitcast_fusion.30"))
    assert "%get-tuple-element.2102," in in_place
    assert layer_weight_copies(in_place, shapes) == []


@pytest.mark.parametrize("program", ["_decode_step", "_decode_multi"])
def test_decode_program_copies_no_layer_matrix(miniature_blocks, program,
                                               monkeypatch):
    """The CPU miniature: every int8 matrix of the decode programs is read
    in place by the kernel (interpret emulation), so no instruction owns a
    layer-sized int8 buffer; the same programs with no stack read in place
    (every matrix rides the scan's ``xs``, as before) own one a matrix."""
    core = make_core(cfg=CLEAN_CFG, qmm_impl="pallas")
    shapes = quantized_weight_shapes(core.params)
    bad = layer_weight_copies(
        lower_decode(core, program=program).as_text(), shapes)
    assert bad == [], "\n".join(bad)
    monkeypatch.setattr(qmm_pallas, "_ON_CHIP_BYTES", 1 << 40)
    jax.clear_caches()
    sliced = layer_weight_copies(
        lower_decode(core, program=program).as_text(), shapes)
    assert len(sliced) >= len(LAYER_QUANT_KEYS)


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


# ------------------------------------------------- the pool is not copied
#
# models/llama.py carries the whole pool through its scan over layers and
# writes it in place. Before that it went in as the scan's xs and came
# back as its stacked ys, which XLA can never alias: every pass copied the
# pool aside and wrote every layer through (on the chip 0.93 s of a 5 s
# trace, and a second pool of temporary memory a program).

STEP_PROGRAMS = ["_decode_step", "_decode_multi", "_mixed_step"]
KV_CFG = LlamaConfig(
    name="hlo-kv-test", vocab_size=262, dim=64, n_layers=4, n_heads=4,
    n_kv_heads=2, ffn_dim=128, max_seq_len=512, rope_theta=10_000.0,
)


@pytest.fixture(scope="module")
def kv_core():
    """Four layers and a pool (1 MB a side) larger than everything else
    a step holds. float32: the CPU widens a bf16 scatter to f32 and back,
    pool and all, which says nothing of the chip."""
    params = init_params(jax.random.PRNGKey(0), KV_CFG, dtype=jnp.float32)
    return EngineCore(KV_CFG, params, ByteTokenizer(), EngineConfig(
        page_size=4, num_pages=512, max_batch_slots=4, prefill_chunk=8,
        max_seq_len=128, block_pages=4, kv_dtype=jnp.float32))


def test_detector_flags_pool_scanned_in_and_stacked_out(kv_core):
    """The structure this guards against, in miniature: the pool as a
    scan's xs and ys. The detector must name the second pool — and stay
    silent on the same writes made into the carry."""
    k = kv_core._kv_k
    rows = jnp.ones((2,) + k.shape[2:], k.dtype)

    def scanned(pool):
        def layer(h, k_l):
            return h + 1.0, k_l.at[jnp.asarray([3, 9])].set(rows * h)
        return jax.lax.scan(layer, 0.0, pool)[1]

    def carried(pool):
        def layer(carry, li):
            h, pool = carry
            flat = pool.reshape((-1,) + pool.shape[2:])
            dest = li * pool.shape[1] + jnp.asarray([3, 9])
            return (h + 1.0, flat.at[dest].set(rows * h).reshape(pool.shape)
                    ), None
        return jax.lax.scan(layer, (0.0, pool),
                            jnp.arange(pool.shape[0]))[0][1]

    assert kv_pool_materializations(
        jax.jit(scanned, donate_argnums=0).lower(k).compile(), kv_core)
    assert kv_pool_materializations(
        jax.jit(carried, donate_argnums=0).lower(k).compile(), kv_core) == []


# The layer scan's body as the TPU's compiler wrote it while the Pallas
# attention kernels took ``pool[li]`` (the lines of ``_decode_multi``
# compiled for a described v5e, shortened, at ``kv_core``'s dims): the
# slice is a fusion that OWNS one layer of the pool, staged on chip
# (``S(1)``), and the kernel's page view a bitcast of it.
_STAGED_BODY = """
%fused_computation.63.clone (param_0.1: f32[4,2048,2,16], param_1.2: s32[]) -> f32[1,2048,2,16] {
  %param_0.1 = f32[4,2048,2,16]{3,2,1,0:T(2,128)} parameter(0)
  %param_1.2 = s32[]{:T(128)} parameter(1)
  ROOT %dynamic-slice.9 = f32[1,2048,2,16]{3,2,1,0:T(2,128)} dynamic-slice(%param_0.1, %param_1.2, %c, %c, %c), dynamic_slice_sizes={1,2048,2,16}
}

%region_1.17 (arg_tuple.1: (s32[], f32[4,2048,2,16])) -> (s32[], f32[4,2048,2,16]) {
  %arg_tuple.1 = (s32[], f32[4,2048,2,16]) parameter(0)
  %get-tuple-element.2054 = s32[]{:T(128)} get-tuple-element(%arg_tuple.1), index=0
  %get-tuple-element.2103 = f32[4,2048,2,16]{3,2,1,0:T(2,128)} get-tuple-element(%arg_tuple.1), index=1
  %constant_dynamic-slice_fusion.15 = f32[1,2048,2,16]{3,2,1,0:T(2,128)S(1)} fusion(%get-tuple-element.2103, %get-tuple-element.2054), kind=kLoop, calls=%fused_computation.63.clone
  %bitcast.249 = f32[512,8,16]{2,1,0:T(8,128)S(1)} bitcast(%constant_dynamic-slice_fusion.15)
  %closed_call.16 = f32[4,4,16]{2,1,0:T(8,128)S(1)} custom-call(%copy-done, %get-tuple-element.2104, %bitcast.249), custom_call_target="tpu_custom_call"
}
"""


def test_detector_flags_a_layer_sliced_out_of_the_pool(kv_core):
    """The detector names the fusion that owns one layer of the pool —
    not its page view, nor the values inside the fusion's body — and is
    silent once the kernel's operand is a page view of the carried pool
    itself."""
    from types import SimpleNamespace

    bad = kv_layer_slices(SimpleNamespace(as_text=lambda: _STAGED_BODY),
                          kv_core)
    assert [ln.split()[0] for ln in bad] == [
        "%constant_dynamic-slice_fusion.15"]
    in_place = "\n".join(
        ln.replace("f32[512,8,16]", "f32[2048,8,16]").replace(
            "bitcast(%constant_dynamic-slice_fusion.15)",
            "bitcast(%get-tuple-element.2103)")
        for ln in _STAGED_BODY.splitlines()
        if not ln.lstrip().startswith("%constant_dynamic-slice_fusion.15"))
    assert "f32[2048,8,16]{2,1,0:T(8,128)S(1)} bitcast(%get-tuple" in in_place
    assert kv_layer_slices(SimpleNamespace(as_text=lambda: in_place),
                           kv_core) == []


def _assert_one_pool(compiled, core):
    bad = kv_pool_materializations(compiled, core)
    assert bad == [], "\n".join(bad)
    # ... and the temporaries (the readers' K and V layer slices and the
    # activations) stay under ONE side of the pool, which a copy of K or
    # of V alone would not.
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < core._kv_k.nbytes, (temp, core._kv_k.nbytes)


@pytest.mark.parametrize("program", STEP_PROGRAMS)
def test_step_program_owns_no_second_kv_pool(kv_core, program):
    _assert_one_pool(lower_decode(kv_core, program=program), kv_core)


@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described and not attached: the TPU's own
    compiler, Mosaic included, with nothing to run on. Described inside
    the fixture, so only the worker given this file loads libtpu."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_core():
    """Head width 128 and 16-token pages, as Mosaic's tiling wants them.
    The bf16 pool is 151 MB a side, more than the chip's 128 MiB of
    on-chip memory: a pool that fits there is prefetched into it whole,
    which at serving size (1.4 GB a side) cannot happen."""
    cfg = LlamaConfig(
        name="hlo-kv-chip-test", vocab_size=262, dim=512, n_layers=3,
        n_heads=4, n_kv_heads=2, ffn_dim=1024, max_seq_len=512,
        rope_theta=10_000.0)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
        page_size=16, num_pages=6144, max_batch_slots=8, prefill_chunk=64,
        max_seq_len=512, block_pages=4, kv_dtype=jnp.bfloat16))


@pytest.mark.parametrize("program", STEP_PROGRAMS)
def test_step_program_for_the_chip_owns_no_second_kv_pool(
        one_chip, chip_core, program, monkeypatch):
    """The same, on what the chip would run: the Pallas attention kernels
    under real Mosaic read the layer's slice of the carried pool, and no
    layout change of the pool may appear in front of the slice or the
    scatter. (``interpret=`` follows the default backend, the CPU here.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = lower_decode(chip_core, program=program, attn_impl="pallas",
                            sharding=one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    _assert_one_pool(compiled, chip_core)
    # ... and no layer of it: the kernels read this pool where it lies.
    assert paged_attention_pallas.reads_in_place(chip_core._kv_k)
    bad = kv_layer_slices(compiled, chip_core)
    assert bad == [], "\n".join(bad)


def test_a_pool_that_fits_on_chip_keeps_the_layer_slice(
        one_chip, chip_core, monkeypatch):
    """The control, RED, and the other side of the shape rule: a stacked
    operand that fits on-chip memory XLA would prefetch there WHOLE before
    every call, so of such a pool the kernels get the layer's slice (the
    same kernel at L = 1) and the program owns a K and a V layer. The
    budget is read while a program is traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(paged_attention_pallas, "_ON_CHIP_BYTES", 1 << 40)
    assert not paged_attention_pallas.reads_in_place(chip_core._kv_k)
    jax.clear_caches()
    try:
        compiled = lower_decode(chip_core, program="_decode_multi",
                                attn_impl="pallas", sharding=one_chip)
    finally:
        jax.clear_caches()
    assert len(kv_layer_slices(compiled, chip_core)) >= 2
    _assert_one_pool(compiled, chip_core)


@pytest.fixture(scope="module")
def dense_cell_core():
    """`qwen7b.chat-open`'s engine at its real size (Qwen2.5-7B-Instruct
    int8, 28 layers, 16 slots, a table 513 wide, 3072 pages: pools of
    ``bf16[28, 49152, 4, 128]``, 1.41 GB a side), the weights zeros."""
    cfg = CONFIGS["qwen2.5-7b-instruct"]
    shapes = jax.eval_shape(lambda: init_params_quantized(
        jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shapes)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
        page_size=16, num_pages=3072, max_batch_slots=16, prefill_chunk=512,
        max_seq_len=8192, kv_dtype=jnp.bfloat16, attn_impl="pallas",
        qmm_impl="pallas", mixed_dispatch=True, decode_steps_per_dispatch=8))


@pytest.fixture(scope="module")
def dense_cell_compiled(one_chip, dense_cell_core):
    """The dense cell's step programs as the chip's compiler makes them,
    each compiled once for the tests that read it (25-35 s a program)."""
    compiled = {}

    def of(program, monkeypatch):
        if program not in compiled:
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            compiled[program] = lower_decode(dense_cell_core, program=program,
                                             sharding=one_chip)
        return compiled[program]

    return of


@pytest.mark.parametrize("program", STEP_PROGRAMS)
def test_dense_cell_step_program_stages_no_layer_of_the_pool(
        dense_cell_core, dense_cell_compiled, program, monkeypatch):
    """What `qwen7b.chat-open` runs, compiled by the chip's own compiler:
    no ``bf16[1, 49152, 4, 128]`` buffer (two of them, 50 MB each, were
    staged on chip in front of every attention call: 3.7 ms of a 19.1 ms
    pass), no second pool, and temporaries far under one layer's slice."""
    core = dense_cell_core
    assert core._kv_k.shape == (28, 49152, 4, 128)
    compiled = dense_cell_compiled(program, monkeypatch)
    txt = compiled.as_text()
    # The page table is the attention call's first operand.
    rows = 80 if program == "_mixed_step" else 16
    assert f"operand_layout_constraints={{s32[{rows},513]" in txt
    assert "49152,4,128]" not in txt.replace("[28,49152,4,128]", "")
    bad = (kv_layer_slices(compiled, core)
           + kv_pool_materializations(compiled, core))
    assert bad == [], "\n".join(bad)


# ---------------------------------------------------- the sampler's sort
#
# `sample_tokens` sorts the vocabulary only where a row of the call samples:
# the sorted path is a branch of a `lax.cond` inside the one program
# (`ops/sampling.py`). At the dense cell's size the sort of
# `f32[16, 152064]` every pass paid was 3.64 ms of 16.04 (PERF.md section 6,
# PR 40). The RED control is the sampler without the condition
# (`tests/test_sampling.py` keeps it), passed in: it owns a top-level sort.

SAMPLING_PROGRAMS = ["_decode_multi", "_mixed_step"]


@pytest.fixture
def unconditional_sampler(monkeypatch):
    """The step programs traced over the sampler as it was: every call sorts."""
    from test_sampling import reference_sample_tokens

    monkeypatch.setattr(engine_module, "sample_tokens", reference_sample_tokens)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _sampler_calls(program):
    return 2 if program == "_mixed_step" else 1


@pytest.mark.parametrize("program", SAMPLING_PROGRAMS)
def test_step_program_sorts_only_behind_the_condition(kv_core, program):
    inside, outside = sorts_by_conditional(
        lower_decode(kv_core, program=program).as_text())
    assert (inside, outside) == (_sampler_calls(program), 0)


@pytest.mark.parametrize("program", SAMPLING_PROGRAMS)
def test_step_program_over_the_old_sampler_sorts_every_call(
        kv_core, program, unconditional_sampler):
    inside, outside = sorts_by_conditional(
        lower_decode(kv_core, program=program).as_text())
    assert (inside, outside) == (0, _sampler_calls(program))


@pytest.mark.parametrize("program", SAMPLING_PROGRAMS)
def test_step_program_for_the_chip_sorts_only_behind_the_condition(
        one_chip, chip_core, program, monkeypatch):
    """On the chip a sort's result is a tuple (values and indices): the
    detector reads that spelling too."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = lower_decode(chip_core, program=program, attn_impl="pallas",
                            sharding=one_chip)
    assert sorts_by_conditional(compiled.as_text()) == (
        _sampler_calls(program), 0)


def test_step_program_for_the_chip_over_the_old_sampler_sorts_every_call(
        one_chip, chip_core, unconditional_sampler, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = lower_decode(chip_core, program="_decode_multi",
                            attn_impl="pallas", sharding=one_chip)
    assert sorts_by_conditional(compiled.as_text()) == (0, 1)


@pytest.mark.parametrize("program", SAMPLING_PROGRAMS)
def test_dense_cell_step_program_sorts_only_behind_the_condition(
        dense_cell_compiled, program, monkeypatch):
    """`qwen7b.chat-open`'s programs: the `f32[16, 152064]` sort (and the
    prompts' `f32[4, 152064]` one) in a branch, the logits handed to the
    conditional as the head wrote them, and temporaries of megabytes (a
    branch that passed a pool through would copy it: PERF.md section 4,
    PR 39)."""
    compiled = dense_cell_compiled(program, monkeypatch)
    txt = compiled.as_text()
    assert sorts_by_conditional(txt) == (_sampler_calls(program), 0)
    assert not [line for line in txt.splitlines()
                if "152064]" in line.split("=", 2)[-1][:48]
                and " copy(" in line]
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


def test_a_sampling_request_compiles_no_step_program():
    """Greedy, sampling, greedy through one engine: the condition is inside
    the one program, so the sampling request meets the programs the first
    greedy one compiled, and the greedy answers are the same before and
    after it."""
    params = init_params(jax.random.PRNGKey(0), KV_CFG, dtype=jnp.float32)
    core = EngineCore(KV_CFG, params, ByteTokenizer(), EngineConfig(
        page_size=4, num_pages=512, max_batch_slots=4, prefill_chunk=8,
        max_seq_len=128, block_pages=4, kv_dtype=jnp.float32,
        mixed_dispatch=True, decode_steps_per_dispatch=4))
    programs = (engine_module._decode_multi, engine_module._mixed_step,
                engine_module._decode_step, engine_module._prefill_step)

    def serve(prompt, **sampling):
        # A second prompt arrives while the first decodes: a mixed step.
        reqs = [EngineRequest(prompt_ids=list(p), sampling=SamplingParams(
            max_new_tokens=12, stop_token_ids=(), **sampling))
            for p in (prompt, prompt[::-1])]
        core.submit(reqs[0])
        for _ in range(3):
            core.step()
        core.submit(reqs[1])
        core.run_until_idle()
        return [r.all_out_ids for r in reqs]

    prompt = list(range(5, 27))
    before = serve(prompt)
    sizes = [p._cache_size() for p in programs]
    calls = core.metrics["sampler_calls"]
    assert sizes[0] >= 1 and sizes[1] >= 1 and calls > 0
    assert core.metrics["sampler_sorted_calls"] == 0
    sampled = serve(prompt, temperature=0.9, top_p=0.9, top_k=20)
    assert core.metrics["sampler_sorted_calls"] > 0
    assert all(sampled)  # (an end-of-sequence token may cut one short)
    sorted_calls = core.metrics["sampler_sorted_calls"]
    assert serve(prompt) == before
    assert core.metrics["sampler_sorted_calls"] == sorted_calls
    assert [p._cache_size() for p in programs] == sizes


@pytest.fixture(scope="module")
def latent_chip_core():
    """The latent (MLA) pool at its published row widths — 512-value
    latents, and a layer's two 64-value rotated keys side by side in one
    128-value row — under the published head sizes, two double layers. The
    latent side is 403 MB: more than on-chip memory, as at serving size."""
    from runbookai_tpu.models import longcat

    cfg = longcat.LongcatConfig(
        name="hlo-latent-chip-test", vocab_size=262, hidden_size=512,
        ffn_hidden_size=1024, expert_ffn_hidden_size=256, num_layers=2,
        num_attention_heads=8, q_lora_rank=256, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_routed_experts=32, zero_expert_num=16, moe_topk=4,
        routed_scaling_factor=6.0, n_experts_held=8,
        max_position_embeddings=512)
    params = longcat.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
        page_size=16, num_pages=6144, max_batch_slots=8, prefill_chunk=64,
        max_seq_len=512, block_pages=4, kv_dtype=jnp.bfloat16))


@pytest.mark.parametrize("program", STEP_PROGRAMS)
def test_latent_step_program_for_the_chip_owns_no_second_pool(
        one_chip, latent_chip_core, program):
    """models/longcat.py's forward, compiled by the TPU's compiler: the
    page walk gathers rows out of the carried pool, the writers scatter
    whole rows into it, and neither side of the pool is copied, re-laid out
    or sliced (a ``[.., 64]`` pool of rotated keys was, every sublayer; a
    page-shaped view of the latents was, every call: ``ops/mla.py``)."""
    compiled = lower_decode(latent_chip_core, program=program, sharding=one_chip)
    _assert_one_pool(compiled, latent_chip_core)


@pytest.fixture(scope="module")
def int8_chip_core():
    """int8 layer matrices whose FFN stacks (9 layers of 2048 x 8192, 151
    MB) are larger than the chip's on-chip memory, as every large stack of
    a serving model is, beside attention stacks that fit it (38 and 5
    MB). The pool is a small one: this fixture is about the weights."""
    cfg = LlamaConfig(
        name="hlo-int8-chip-test", vocab_size=262, dim=2048, n_layers=9,
        n_heads=16, n_kv_heads=2, ffn_dim=8192, max_seq_len=512,
        rope_theta=10_000.0)
    params = init_params_quantized(jax.random.PRNGKey(0), cfg,
                                   dtype=jnp.bfloat16)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
        page_size=16, num_pages=256, max_batch_slots=8, prefill_chunk=64,
        max_seq_len=512, block_pages=4, kv_dtype=jnp.bfloat16,
        decode_steps_per_dispatch=4))


def _in_place_shapes(core, rows):
    layers = core.params["layers"]
    names = {name for name, w in layers.items() if isinstance(w, dict)
             and qmm_pallas.reads_in_place(rows, w["q"].shape)}
    return names, quantized_weight_shapes({n: layers[n] for n in names})


@pytest.mark.parametrize("program", ["_decode_step", "_decode_multi"])
def test_decode_program_for_the_chip_copies_no_layer_matrix(
        one_chip, int8_chip_core, program, monkeypatch):
    """What the chip would run, compiled by its own compiler: the decode
    programs call the Pallas matmul seven times a layer and own no
    ``s8[K, N]`` buffer of a matrix the kernel reads in place, nor a copy
    of its stack (XLA prefetches a stack that fits on-chip memory WHOLE in
    front of every call: those ride the scan's ``xs`` instead, and the
    slice XLA makes of them is not hunted here)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    core = int8_chip_core
    names, shapes = _in_place_shapes(core, core.ecfg.max_batch_slots)
    assert names == {"w_gate", "w_up", "w_down"}
    txt = lower_decode(core, program=program, attn_impl="pallas",
                       qmm_impl="pallas", sharding=one_chip).as_text()
    assert txt.count('custom_call_target="tpu_custom_call"') >= 7
    bad = layer_weight_copies(txt, shapes)
    assert bad == [], "\n".join(bad)


def test_decode_program_for_the_chip_copied_every_layer_matrix(
        one_chip, int8_chip_core, monkeypatch):
    """The control, RED: the program as it was before the kernel took the
    stack — no stack counts as read in place, every matrix rides ``xs`` —
    owns a layer-sized int8 copy of each of the three FFN matrices."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    core = int8_chip_core
    _, shapes = _in_place_shapes(core, core.ecfg.max_batch_slots)
    monkeypatch.setattr(qmm_pallas, "_ON_CHIP_BYTES", 1 << 40)
    jax.clear_caches()
    try:
        txt = lower_decode(core, program="_decode_multi",
                           attn_impl="pallas", qmm_impl="pallas",
                           sharding=one_chip).as_text()
    finally:
        jax.clear_caches()
    assert len(layer_weight_copies(txt, shapes)) >= 3


# --------------------------------------------------------------------------- #
# The held experts' fast path: the experts touched, read where they lie        #
# --------------------------------------------------------------------------- #

# benchmark/layer_metrics/afmoe_expert_ffn_ms.py (and expert_ffn_ms,
# relu2_expert_ffn_ms, spec_expert_ffn_ms): how a reader finds the dispatch
# on the trace's "XLA Ops" line, by the ``conditional``'s result.
READERS_PATTERN = r"^%cond[\w.]* = \(?f32\[{slots},{hidden}\]\)? conditional\("


@pytest.fixture(scope="module")
def expert_core():
    """Held experts whose matrices share their dims with nothing else in the
    model (512 x 384), stacked over two double layers, eight held."""
    from runbookai_tpu.models import longcat

    cfg = longcat.LongcatConfig(
        name="hlo-held-experts-test", vocab_size=262, hidden_size=512,
        ffn_hidden_size=1024, expert_ffn_hidden_size=384, num_layers=2,
        num_attention_heads=8, q_lora_rank=256, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_routed_experts=32, zero_expert_num=16, moe_topk=4,
        routed_scaling_factor=6.0, n_experts_held=8,
        max_position_embeddings=512)
    params = longcat.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
        page_size=16, num_pages=512, max_batch_slots=8, prefill_chunk=64,
        max_seq_len=512, block_pages=4, kv_dtype=jnp.bfloat16))


def _held_expert_program(core, compiled, rows):
    """What every program with the held experts' dispatch must show, the
    dispatch over ``rows`` tokens."""
    text = compiled.as_text()
    layers = core.params["layers"]
    stacks = [tuple(layers[k].shape) for k in ("e_gate", "e_up", "e_down")]
    assert stacks == [(2, 8, 512, 384), (2, 8, 512, 384), (2, 8, 384, 512)]
    assert held_expert_copies(text, stacks) == []
    dispatches = expert_conditionals(text, rows, 512)
    pattern = re.compile(READERS_PATTERN.format(slots=rows, hidden=512))
    assert dispatches and all(pattern.match(line.removeprefix("ROOT "))
                              for line, _ in dispatches), dispatches
    # the loop over the experts touched, behind the fast path's own condition
    assert all(loops >= 1 for _, loops in dispatches), dispatches


@pytest.mark.parametrize("program", ["_decode_step", "_decode_multi"])
def test_decode_program_reads_the_touched_experts_where_they_lie(expert_core, program):
    _held_expert_program(expert_core, lower_decode(expert_core, program=program),
                         expert_core.ecfg.max_batch_slots)


@pytest.mark.parametrize("program", ["_decode_multi", "_mixed_step"])
def test_step_program_for_the_chip_reads_the_touched_experts_where_they_lie(
        one_chip, expert_core, program):
    rows = expert_core.ecfg.max_batch_slots
    if program == "_mixed_step":
        rows = rows * engine_module._RAGGED_BLOCK + expert_core._mix_pf_tokens
    _held_expert_program(expert_core, lower_decode(expert_core, program=program,
                                                   sharding=one_chip), rows)


def test_detector_flags_an_expert_sliced_out_of_the_stack():
    """The control: one expert's matrix taken out of the stack in front of a
    ``lax.cond`` (which materialises its operands) is a copy, and is found."""
    def f(w, x, i, go):
        one = w[1, i]
        return jax.lax.cond(go, lambda m: x @ m, lambda m: x @ (2 * m), one)

    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((2, 8, 512, 384), jnp.bfloat16),
        jax.ShapeDtypeStruct((4, 512), jnp.bfloat16),
        jax.ShapeDtypeStruct((), jnp.int32), jax.ShapeDtypeStruct((), jnp.bool_),
    ).compile().as_text()
    assert held_expert_copies(text, [(2, 8, 512, 384)])


def _scan_of_held_experts(layers, held, d, f, rows, cap, sharding):
    """``held_expert_ffn`` over stacked two-matrix experts inside a scan
    over layers, as a model's layer scan calls it, from shapes alone."""
    from runbookai_tpu.ops import moe

    def run(u, local, weights, w_up, w_down):
        def layer(acc, li):
            out, _ = moe.held_expert_ffn((u + acc).astype(u.dtype), local, weights, None,
                                         w_up, w_down, cap, layer=li)
            return acc + out.astype(acc.dtype), None

        return jax.lax.scan(layer, jnp.zeros_like(u), jnp.arange(layers))[0]

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    return jax.jit(run).lower(
        shape(rows, d), shape(rows, 6, dtype=jnp.int32), shape(rows, 6, dtype=jnp.float32),
        shape(layers, held, d, f), shape(layers, held, f, d)).compile()


def test_a_stack_the_device_keeps_transposed_is_not_copied(one_chip):
    """Nemotron-3-Nano's widths: ``e_up`` ``[.., 2688, 1856]`` has a last
    axis off the 128 lanes, and the device keeps it in the other order. A
    program whose products ALL wanted it row-major was given a copy of the
    whole stack in front of its layer scan (3.7 GB a dispatch at 23
    layers: compiled and read, PR 42); with the batched product beside the
    loop in one program every product reads it as it lies."""
    compiled = _scan_of_held_experts(2, 16, 2688, 1856, 48, 9, one_chip)
    stack_bytes = 2 * 16 * 2688 * 1856 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < stack_bytes // 8
    assert held_expert_copies(compiled.as_text(), [(2, 16, 2688, 1856),
                                                   (2, 16, 1856, 2688)]) == []


# ------------------------------- the recurrent families on the Pallas walk
#
# Qwen3-Next and Nemotron-3-Nano page per-head keys and values of 2 kv heads
# for their softmax layers; with `attn_impl="pallas"` the ONE-TOKEN rows of
# those layers (a decode pass, a mixed step's decode rows) call the decode
# walk on the pool their layer scan carries, and a prefill run keeps XLA's
# one-row walk. The engines below keep what the kernels and the pool see of
# the two benchmark cells — head counts and sizes, 8,192 pages of 16, the
# slots, a table as wide, chunks of 512, 8 passes a dispatch — and cut what
# only a compile's seconds hang on (depth, widths outside attention, experts,
# vocabulary). At the cells' whole size the same programs were compiled by a
# scratch script (CHANGES.md, PR 45: no copy of either pool).


def _recurrent_core(cfg, init_params, slots, max_seq_len):
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    return EngineCore(cfg, params, ByteTokenizer(), EngineConfig(
        page_size=16, num_pages=8192, max_batch_slots=slots,
        prefill_chunk=512, max_seq_len=max_seq_len, kv_dtype=jnp.bfloat16,
        attn_impl="pallas", mixed_dispatch=True, speculative=False,
        decode_steps_per_dispatch=8))


@pytest.fixture(scope="module")
def recurrent_chip_cores():
    """{family: engine}: `qwen3next.sysprompt-open`'s attention (16 query
    heads over 2 kv heads of 256, 64 slots, a table 1,025 wide) over two
    periods, and `nemotron3nano.reason-open`'s (32 over 2 of 128, 48 slots,
    513) over a pattern with two attention layers: pools of
    ``bf16[2, 131072, 2, 256]`` and ``bf16[2, 131072, 2, 128]`` a side."""
    from runbookai_tpu.models import nemotron_h, qwen3_next

    cores = {}
    cfg = dataclasses.replace(
        CONFIGS["qwen3-next-test"], name="hlo-qwen3next-chip-test",
        hidden_size=256, num_attention_heads=16, head_dim=256,
        linear_key_head_dim=128, linear_value_head_dim=128)
    cores["qwen3next"] = _recurrent_core(cfg, qwen3_next.init_params, 64, 16384)
    cfg = dataclasses.replace(
        CONFIGS["nemotron-h-test"], name="hlo-nemotron-chip-test",
        hidden_size=256, num_attention_heads=32, head_dim=128,
        mamba_head_dim=64, ssm_state_size=128, chunk_size=128)
    cores["nemotron"] = _recurrent_core(cfg, nemotron_h.init_params, 48, 8192)
    return cores


def _slot_gathers(txt, core):
    """XLA's walk over the decode rows: its gathers of 32 pages a row for
    every slot (Qwen3-Next) or for 8 live rows a turn (Nemotron's
    `attend_live`). A prefill run's, one row's 32 pages, are not among them."""
    n_kv, hd = core._kv_k.shape[2:]
    rows = "|".join(str(32 * r) for r in (core.ecfg.max_batch_slots, 8))
    return re.findall(rf"bf16\[(?:{rows}),16,{n_kv},{hd}\]", txt)


@pytest.mark.parametrize("program", STEP_PROGRAMS)
@pytest.mark.parametrize("family", ["qwen3next", "nemotron"])
def test_recurrent_step_program_for_the_chip_reads_the_pool_where_it_lies(
        one_chip, recurrent_chip_cores, family, program, monkeypatch):
    """Compiled by the chip's own compiler: the decode walk is in the program
    under its name, no copy or re-laid-out view of either side of the pool
    is (a ``[pages, page_size x n_kv, hd]`` view of a ``[tokens, 2, 256]``
    pool was one, 805 MB a call: PR 31), no layer of it is staged in front of
    the call, and XLA's walk over the decode rows, with its 32-page gathers
    for every slot, is gone; the mixed step's prefill runs keep theirs, one
    row's pages a gather."""
    core = recurrent_chip_cores[family]
    assert core.ecfg.attn_impl == "pallas"
    assert paged_attention_pallas.reads_in_place(core._kv_k)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = lower_decode(core, program=program, sharding=one_chip)
    txt = compiled.as_text()
    assert "%paged_decode_walk" in txt
    _assert_one_pool(compiled, core)
    bad = kv_layer_slices(compiled, core)
    assert bad == [], "\n".join(bad)
    assert _slot_gathers(txt, core) == []
    n_kv, hd = core._kv_k.shape[2:]
    assert (f"bf16[32,16,{n_kv},{hd}]" in txt) == (program == "_mixed_step")


@pytest.mark.parametrize("family", ["qwen3next", "nemotron"])
def test_recurrent_decode_program_on_xlas_walk_gathers_for_every_slot(
        one_chip, recurrent_chip_cores, family, monkeypatch):
    """The control, RED: the same engine's `_decode_multi` with
    `attn_impl="xla"` holds no walk kernel and gathers 32 pages for each of
    the 64 slots, live or free, an iteration (a third of the device's time in
    `qwen3next.sysprompt-open` before PR 45), or for 8 live rows a turn."""
    core = recurrent_chip_cores[family]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    txt = lower_decode(core, program="_decode_multi", attn_impl="xla",
                       sharding=one_chip).as_text()
    assert "%paged_decode_walk" not in txt
    assert _slot_gathers(txt, core)


# The walks' other callers must launch what they launched: the dense cell's
# two kernels and Trinity-Mini's two window kernels, traced at the cells'
# shapes, as digests of their jaxprs' text (it holds no source location, so
# equal text is an unchanged kernel: PR 41). Taken on PR 42's tree and equal
# on PR 45's, whose `name=` and by-head route are statically absent here
# (under the conftest's matmul precision, which the text carries);
# the four compiled step programs (`_decode_multi` / `_mixed_step` of both
# cells, for a described v5e) equalled the parent's instruction for
# instruction too, by a scratch script (CHANGES.md, PR 45). A PR that means to
# change one of these kernels refreshes its digest:
#   hashlib.sha256(str(jax.make_jaxpr(f)(*shapes)).encode()).hexdigest()[:16]
_DENSE_POOL = ((28, 49152, 4, 128), jnp.bfloat16)
_WINDOW_POOL = ((24, 41488, 4, 128), jnp.bfloat16)
KERNEL_DIGESTS = {
    "dense decode": ("f6e0d5d4d56b3f2b", "decode", _DENSE_POOL, (16, 28), 513, None),
    "dense ragged": ("7aa7f059cc0964a4", "ragged", _DENSE_POOL, (640, 28), 513, None),
    "window decode": ("fcd5255e4f40520e", "decode", _WINDOW_POOL, (16, 32), 1089, 2048),
    "window chunk": ("27b3a94a3318c13b", "chunk", _WINDOW_POOL, (80, 32), 1089, 2048),
}


@pytest.mark.parametrize("kernel", list(KERNEL_DIGESTS))
def test_the_other_families_kernels_are_the_parents(kernel):
    from jax import ShapeDtypeStruct as S

    pap = paged_attention_pallas
    digest, entry, (pool, dtype), (rows, n_q), width, window = KERNEL_DIGESTS[kernel]
    i32, hd, layer = jnp.int32, pool[-1], jnp.int32(3)
    k = v = S(pool, dtype)
    if entry == "decode":
        f = lambda q, k, v, t, c: pap.paged_decode_attention(  # noqa: E731
            q, k, v, t, c, 16, layer=layer, window=window)
        args = (S((rows, n_q, hd), dtype), k, v, S((rows, width), i32), S((rows,), i32))
    elif entry == "ragged":
        # (the mixed step's 21 rows: 16 slots, 4 prefill rows, the null row)
        f = lambda q, k, v, t, c, p, r: pap.paged_ragged_attention(  # noqa: E731
            q, k, v, t, c, p, r, 16, layer=layer)
        args = (S((rows, n_q, hd), dtype), k, v, S((21, width), i32), S((21,), i32),
                S((rows,), i32), S((rows,), i32))
    else:
        f = lambda q, k, v, t, c, p: pap.paged_chunk_attention(  # noqa: E731
            q, k, v, t, c, p, 16, layer=layer, window=window)
        args = (S((rows, 8, n_q, hd), dtype), k, v, S((rows, width), i32),
                S((rows,), i32), S((rows, 8), i32))
    text = str(jax.make_jaxpr(f)(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
