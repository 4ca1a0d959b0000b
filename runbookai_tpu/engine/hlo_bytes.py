"""HLO byte accounting: perf claims falsifiable without hardware.

Decode is HBM-bandwidth-bound: per generated token the program must read
each weight matrix once at its STORED width (int8 for quantized leaves)
plus the live KV pages — nothing else of that magnitude. The r3 on-chip
measurement (209.9 tok/s at ~27% of its own roofline) had the signature
of an unfused dequantization: XLA materializing a bf16 copy of each int8
weight, tripling the bytes (read int8 + write bf16 + read bf16). This
module turns that diagnosis from an argument into assertions on the
COMPILED program (VERDICT r4 next-round #2):

- :func:`wide_weight_materializations` scans optimized HLO for any
  instruction materializing a wide-dtype tensor exactly the size of a
  quantized weight (full stacked tensor or per-layer slice) — the
  smoking gun, mechanically detected. Fusion-body lines are excluded:
  values inside a fusion computation are virtual; only fusion roots and
  top-level/loop-body instructions own buffers.
  :func:`layer_weight_copies` hunts the same shapes at the STORED width:
  one layer's int8 matrix copied out of the stacked array in front of
  the Pallas matmul, which reads the stack in place.
- :func:`kv_pool_materializations` hunts a second KV pool or a layer
  written through, :func:`kv_layer_slices` one layer of the pool copied
  out in front of the Pallas attention kernels, which read the carried
  pool in place.
- :func:`sorts_by_conditional` counts the ``sort`` instructions every run
  pays beside those behind a ``conditional`` (the sampler's).
- :func:`held_expert_copies` hunts one held expert's matrix, or a stack of
  them, copied in front of the product that reads it in place;
  :func:`expert_conditionals` finds the held experts' dispatch and the
  loop over the experts touched inside it.
- :func:`lower_decode` lowers+compiles the engine's REAL decode dispatch
  (the same jitted ``_decode_step`` serving uses) without executing it,
  so the analysis covers the program that runs, not a proxy.
- :func:`decode_accounting` reports the compiled program's
  ``memory_analysis()`` / ``cost_analysis()`` next to the mechanical
  expectation (weight bytes at stored width + KV pool + small operands),
  and :func:`check_plan` cross-checks :mod:`~runbookai_tpu.engine.
  memory_plan` arithmetic against a live engine's actual allocations
  (VERDICT r4 weak #4: plans were hand arithmetic, never validated).

The reference has no counterpart (it calls hosted LLM APIs —
SURVEY.md §2.2); this is the TPU serving stack's self-audit.
"""

from __future__ import annotations

import functools
import math
import re
from typing import Any, Iterable

import jax
import jax.numpy as jnp

# Dtype widths as HLO spells them; int8/u8/fp8 (1 byte) are the stored
# widths — materializing THOSE is fine, the hazard is 2+ byte copies.
_WIDE_DTYPES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8}
_NARROW_DTYPES = {"s8", "u8", "f8e4m3fn", "f8e5m2"}

# `%name = dtype[dims]{layout} op(...)` — optimized HLO instruction line.
_INSTR = re.compile(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*([a-z0-9]+)\[([\d,]*)\]")
# `%name (params) -> type {` — a computation's header; parameters of a
# loop body are tuples, so the list nests parentheses.
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{$")
# name, op and operand list of an instruction; the computation a fusion calls.
_NAMED = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S+\s+([\w\-]+)\((.*)")
_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")
# Ops that name a buffer without owning a new one.
_VIEW_OPS = {"parameter", "get-tuple-element", "bitcast"}


def _computations(hlo_text: str) -> dict[str, list[str]]:
    """Optimized HLO as ``computation name -> its instruction lines``."""
    comps: dict[str, list[str]] = {}
    lines: list[str] | None = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if lines is None:
            comp = _COMPUTATION.match(line)
            if comp is not None:
                lines = comps.setdefault(comp.group(1), [])
        elif line == "}":
            lines = None
        else:
            lines.append(line)
    return comps


def _fusion_bodies(comps: dict[str, list[str]]) -> set[str]:
    """Computations whose values are virtual: the bodies fusions call
    (named ``fused_computation*`` or, for a wrapped single op, after it).
    Loop/scan bodies and reduction combinators are NOT among them:
    while-body instructions own buffers (a per-layer dequant or pool
    slice inside the scan over layers is exactly the hazard), and
    combinator regions are scalar so they never match a tensor's dims."""
    bodies = {name for name in comps if "fused" in name}
    for lines in comps.values():
        for line in lines:
            called = _CALLS.search(line)
            if called is not None:
                bodies.add(called.group(1))
    return bodies


def _dims(line: str) -> tuple[str, tuple[int, ...]] | None:
    """(dtype, dims) of an instruction's array result; None for tuples,
    scalars and lines that are no instruction."""
    m = _INSTR.match(line)
    if m is None or not m.group(2):
        return None
    return m.group(1), tuple(int(d) for d in m.group(2).split(","))


def quantized_weight_shapes(params: Any) -> set[tuple[int, ...]]:
    """Dims of every quantized weight tensor, its per-layer slice, and
    the slice's keep-dims form — the exact shapes a materialized dequant
    would take in the compiled program. Matching on full dims tuples
    (not element counts) keeps activation tensors that happen to share a
    product out of the hunt."""
    shapes: set[tuple[int, ...]] = set()

    def visit(node: Any) -> None:
        if isinstance(node, dict):
            if "q" in node and "s" in node and hasattr(node["q"], "shape"):
                q = node["q"]
                shapes.add(tuple(q.shape))
                if q.ndim >= 3:
                    shapes.add(tuple(q.shape[1:]))
                    shapes.add((1,) + tuple(q.shape[1:]))
            else:
                for v in node.values():
                    visit(v)

    visit(params)
    return shapes


def _weight_shaped_buffers(hlo_text: str,
                           weight_shapes: Iterable[tuple[int, ...]],
                           dtypes: Iterable[str]) -> list[str]:
    """Instructions of optimized HLO that own a buffer of one of
    ``dtypes`` with exactly a quantized weight's dims (full stacked
    tensor, per-layer slice, or keep-dims slice). Lines inside fusion
    computations are skipped (virtual values); fusion ROOTS appear at
    their call sites and are caught. Parameters and views of them own
    nothing."""
    targets = {tuple(s) for s in weight_shapes}
    dtypes = set(dtypes)
    comps = _computations(hlo_text)
    virtual = _fusion_bodies(comps)
    bad: list[str] = []
    for name, lines in comps.items():
        if name in virtual:
            continue
        for line in lines:
            result, named = _dims(line), _NAMED.match(line)
            if result is None or named is None or named.group(2) in _VIEW_OPS:
                continue
            if result[0] in dtypes and result[1] in targets:
                bad.append(line[:200])
    return bad


def wide_weight_materializations(
    hlo_text: str, weight_shapes: Iterable[tuple[int, ...]]
) -> list[str]:
    """Offending lines: instructions in optimized HLO whose result is a
    wide-dtype (>= 2 byte) buffer with exactly a quantized weight's dims
    — a dequantized copy, three times the bytes."""
    return _weight_shaped_buffers(hlo_text, weight_shapes, _WIDE_DTYPES)


def layer_weight_copies(
    hlo_text: str, weight_shapes: Iterable[tuple[int, ...]]
) -> list[str]:
    """Offending lines: instructions whose result is a STORED-width (1
    byte) buffer with a quantized weight's dims — one layer's ``s8[K, N]``
    sliced or copied out of the stacked ``[L, K, N]`` array (on the chip
    ``%dynamic-slice_bitcast_fusion = s8[K, N]`` in the layer scan's
    body, in front of every call of the Pallas matmul: each matrix
    handled twice a layer, PERF.md section 6, PR 30), or a copy of the
    stack. The kernel reads the stack in place (``ops/qmm_pallas.py``),
    so a decode program with ``qmm_impl="pallas"`` has none."""
    return _weight_shaped_buffers(hlo_text, weight_shapes, _NARROW_DTYPES)


def lower_decode(core, *, qmm_impl: str | None = None,
                 attn_impl: str | None = None,
                 program: str = "_decode_step", sharding=None):
    """Lower + compile one of the engine's decode-side dispatches — the
    exact jitted function and argument shapes ``EngineCore._run_decode``
    (``"_decode_step"``, or ``"_decode_multi"`` at the configured steps
    per dispatch) and ``EngineCore._run_mixed`` (``"_mixed_step"``) use —
    WITHOUT executing it (donation only applies on execute, so the live
    pool buffers are safe to pass). With ``sharding`` every array goes in
    as its shape on that sharding instead: the way to compile for a
    device that is described and not attached
    (``jax.experimental.topologies``)."""
    from runbookai_tpu.engine import engine

    ecfg = core.ecfg
    b = ecfg.max_batch_slots
    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    f32 = functools.partial(jnp.zeros, dtype=jnp.float32)
    pages = core.kv.max_pages_per_seq + 1
    static = dict(
        page_size=ecfg.page_size, block_pages=ecfg.block_pages,
        attn_impl=attn_impl if attn_impl is not None else ecfg.attn_impl,
        mesh=core.mesh,
        qmm_impl=qmm_impl if qmm_impl is not None else ecfg.qmm_impl,
    )
    key = jax.random.PRNGKey(0)
    if program == "_mixed_step":
        rq = engine._RAGGED_BLOCK
        n = b * rq + core._mix_pf_tokens
        n_pf, rows = core._mix_pf_rows, core._mix_rows
        step, static["ragged_block"] = engine._mixed_step, rq
        args = (core.params, core.cfg, i32((n,)), i32((b,)), i32((b,)),
                i32((n,)), i32((n,)), core._kv_k, core._kv_v,
                i32((rows, pages)), i32((rows,)), i32((rows,)),
                i32((n_pf,)), f32((b,)), f32((b,)), i32((b,)), key,
                f32((n_pf,)), f32((n_pf,)), i32((n_pf,)), i32((n_pf,)),
                i32((n_pf,)))
    else:
        args = (core.params, core.cfg, i32((b, 1)), i32((b, 1)),
                core._kv_k, core._kv_v, i32((b, pages)),
                jnp.ones((b,), jnp.int32), f32((b,)),
                jnp.ones((b,), jnp.float32), i32((b,)), key)
        if program == "_decode_multi":
            step, args = engine._decode_multi, args + (i32((b,)),)
            static["k_steps"] = ecfg.decode_steps_per_dispatch
        elif program == "_decode_step":
            step, args = engine._decode_step, args + (None, i32((b,)))
        else:
            raise ValueError(f"no such decode program: {program!r}")
    traced = {}
    if core._state is not None:  # a model with recurrent state: its pool
        traced["state"] = core._state
        if program == "_mixed_step":
            traced["state_rows"] = i32((rows,))
    if sharding is not None:
        args, traced = jax.tree.map(
            lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=sharding)
                       if isinstance(a, jax.Array) else a), (args, traced))
    return step.lower(*args, **static, **traced).compile()


def kv_pool_shapes(core, n_layers: int | None = None
                   ) -> set[tuple[int, ...]]:
    """Dims a materialized KV pool would take in the compiled program:
    ``[L, tokens, n_kv, hd]`` and its row and page views (the chunk
    walk's ``[pages, page_size, n_kv, hd]``, the decode walk's ``[pages,
    page_size x n_kv, hd]``). int8 pools add the scale arrays' forms.
    With ``n_layers=1``: the dims of ONE layer of it."""
    ps = core.ecfg.page_size
    shapes: set[tuple[int, ...]] = set()
    for leaf in jax.tree.leaves((core._kv_k, core._kv_v)):
        layers, tokens, *rest = leaf.shape
        rows = (n_layers or layers) * tokens
        for lead in ((rows // tokens, tokens), (rows,), (rows // ps, ps)):
            shapes.add(lead + tuple(rest))
        if len(rest) == 2:
            shapes.add((rows // ps, ps * rest[0], rest[1]))
    return shapes


def _writes_rows_in_place(line: str, lines: list[str],
                          comps: dict[str, list[str]],
                          layer_elems: int) -> bool:
    """True for the page write itself: a ``scatter``, or a
    ``dynamic-update-slice`` whose update is smaller than one layer of
    the pool — at the top level or as the root of the fusion ``line``
    calls. Both write their rows into the operand's own buffer."""
    called = _CALLS.search(line)
    if called is not None:
        lines = comps.get(called.group(1), [])
        line = next((ln for ln in lines if ln.startswith("ROOT ")), "")
    named = _NAMED.match(line)
    if named is None:
        return False
    if named.group(2) == "scatter":
        return True
    if named.group(2) != "dynamic-update-slice":
        return False
    update = named.group(3).split(",")[1].split()[-1].lstrip("%")
    shapes = {m.group(1): _dims(ln) for ln in lines
              if (m := _NAMED.match(ln)) is not None}
    found = shapes.get(update)
    return found is not None and math.prod(found[1]) < layer_elems


def kv_pool_materializations(compiled, core) -> list[str]:
    """Offending lines of a compiled step program: instructions outside
    fusion bodies whose result is a buffer of the KV pool's dims
    (:func:`kv_pool_shapes`), other than the program's parameters, views
    of them (``get-tuple-element`` out of the loops' own tuples,
    ``bitcast``) and the page write itself
    (:func:`_writes_rows_in_place`). The pool rides the layer scan's
    carry and is written in place (``models/llama.py``), so a clean
    program has none: a ``copy``, a ``broadcast`` or ``AllocateBuffer``
    (a second pool) or a layer-sized ``dynamic-update-slice`` (a layer
    written through) each move gigabytes a pass at serving size. The
    readers' layer slice is :func:`kv_layer_slices`' to hunt."""
    targets = kv_pool_shapes(core)
    layer_elems = min(math.prod(leaf.shape[1:]) for leaf in
                      jax.tree.leaves((core._kv_k, core._kv_v)))
    comps = _computations(compiled.as_text())
    virtual = _fusion_bodies(comps)
    bad: list[str] = []
    for name, lines in comps.items():
        if name in virtual:
            continue
        for line in lines:
            result, named = _dims(line), _NAMED.match(line)
            if result is None or named is None or result[1] not in targets:
                continue
            if named.group(2) in _VIEW_OPS or _writes_rows_in_place(
                    line, lines, comps, layer_elems):
                continue
            bad.append(line[:200])
    return bad


def kv_layer_slices(compiled, core) -> list[str]:
    """Offending lines of a compiled step program: instructions outside
    fusion bodies that own a buffer of ONE layer of the KV pool —
    ``[tokens, n_kv, hd]``, its keep-dims form or a page view of it. That
    is the readers' layer slice (on the chip ``%dynamic-slice_fusion =
    bf16[1, tokens, n_kv, hd]`` staged on-chip in the layer scan's body,
    twice a layer, in front of a Pallas attention kernel that read 3% of
    it: PERF.md section 6, PR 38). The kernels take the carried pool and
    the layer's number, so a program whose pool they read in place
    (``paged_attention_pallas.reads_in_place``) has none; one whose pool
    fits on-chip memory, or whose readers are XLA's, keeps the slice."""
    return _weight_shaped_buffers(
        compiled.as_text(), kv_pool_shapes(core, n_layers=1),
        {*_WIDE_DTYPES, *_NARROW_DTYPES})


# The computations an instruction calls: one by attribute, or a list.
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)"
    r"|\b(?:branch_computations|called_computations)=\{([^}]*)\}")


# An instruction's op, whatever its result: on the chip a sort's is a tuple
# (values and indices), as a conditional's always is.
_OP = re.compile(
    r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\(.*?\)|\S+)\s+([\w\-]+)\(")


def _op(line: str) -> str | None:
    m = _OP.match(line)
    return m.group(1) if m else None


def _called(line: str) -> list[str]:
    names: list[str] = []
    for one, many in _CALLED.findall(line):
        names += [one] if one else [
            n.strip().lstrip("%") for n in many.split(",")]
    return names


def _reached(comps: dict[str, list[str]], names: Iterable[str]) -> set[str]:
    """The computations ``names`` and every computation they call."""
    todo, seen = list(names), set()
    while todo:
        name = todo.pop()
        if name not in seen and name in comps:
            seen.add(name)
            todo += [n for line in comps[name] for n in _called(line)]
    return seen


def sorts_by_conditional(hlo_text: str) -> tuple[int, int]:
    """(inside, outside): the ``sort`` instructions of a compiled program
    that lie in a branch of a ``conditional`` (in the branch's computation
    or in one it calls), and those that every run of the program pays. The
    sampler sorts the vocabulary only where a row samples
    (``ops/sampling.py``), so a step program has none outside: at the dense
    cell's size a ``sort`` of ``f32[16, 152064]`` outside a branch was 3.64
    ms of a 16.04 ms pass (PERF.md section 6, PR 40)."""
    comps = _computations(hlo_text)
    branch = _reached(comps, [name for lines in comps.values() for line in lines
                              if _op(line) == "conditional" for name in _called(line)])
    inside = outside = 0
    for name, lines in comps.items():
        n = sum(_op(line) == "sort" for line in lines)
        if name in branch:
            inside += n
        else:
            outside += n
    return inside, outside


_LAYOUT = re.compile(r"\{[^{}]*\}")


def held_expert_copies(hlo_text: str,
                       stacks: Iterable[tuple[int, ...]]) -> list[str]:
    """Offending lines: instructions that own a bf16 buffer with the dims of one
    held expert's matrix, of one layer's experts or of a whole stacked
    leaf ``[layers, E_held, in, out]`` (``stacks``; keep-dims forms too).
    The held experts' fast path reads the matrix of an expert that has a
    row where it lies in the stack (``ops/moe.py`` ``held_expert_ffn``): a
    copy in front of the product would be that expert's bytes twice, and a
    stack re-laid out (found: 3.7 GB a dispatch, a leaf whose last axis is
    off the 128 lanes and a program whose products all want it the other
    way; PERF.md section 6, PR 42) every expert's. A fusion NESTED in a
    product's fused computation is the operand read in place, and is
    skipped with the fusion bodies."""
    shapes: set[tuple[int, ...]] = set()
    for stack in stacks:
        for cut in range(len(stack) - 1):
            tail = tuple(stack[cut:])
            shapes |= {(1,) * lead + tail for lead in range(cut + 1)}
    return _weight_shaped_buffers(hlo_text, shapes, {"bf16"})


def expert_conditionals(hlo_text: str, rows: int, hidden: int
                        ) -> list[tuple[str, int]]:
    """(the instruction with its layouts taken out, as a trace names it;
    the ``while`` loops behind the conditionals INSIDE its branches) for
    every ``conditional`` whose result is ``f32[rows, hidden]``: the held
    experts' dispatch (fast path or exact slow path), which the
    benchmark's ``*_expert_ffn_ms`` readers find by that result. The loop
    over the experts touched sits behind the fast path's own condition."""
    comps = _computations(hlo_text)
    found = []
    for lines in comps.values():
        for line in lines:
            if _op(line) != "conditional" or f"f32[{rows},{hidden}]" not in line.split(
                    " conditional(")[0]:
                continue
            inner = [n for name in _reached(comps, _called(line)) for inside in comps[name]
                     if _op(inside) == "conditional" for n in _called(inside)]
            loops = sum(_op(at) == "while" for name in _reached(comps, inner)
                        for at in comps[name])
            found.append((_LAYOUT.sub("", line), loops))
    return found


def param_nbytes(params: Any) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(params))


def kv_pool_nbytes(core) -> int:
    # int8 pools are (values, scales) tuples — sum the pytree leaves.
    return sum(leaf.nbytes
               for leaf in jax.tree.leaves((core._kv_k, core._kv_v)))


def decode_accounting(core, compiled=None) -> dict[str, float]:
    """Mechanical byte accounting of the compiled decode program.

    ``arguments_expected`` is what the program's resident inputs must be
    (weights at stored width + KV pool + O(batch) operands);
    ``bytes_accessed`` is XLA's own traffic estimate for one step. A
    fused program accesses roughly arguments + outputs once; a program
    that materializes weight dequants accesses a multiple of that."""
    compiled = compiled if compiled is not None else lower_decode(core)
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    weights = param_nbytes(core.params)
    kv = kv_pool_nbytes(core)
    return {
        "weights_nbytes": weights,
        "kv_pool_nbytes": kv,
        "arguments_expected": weights + kv,
        "argument_size_in_bytes": ma.argument_size_in_bytes,
        "temp_size_in_bytes": ma.temp_size_in_bytes,
        "output_size_in_bytes": ma.output_size_in_bytes,
        "peak_memory_in_bytes": float(ma.peak_memory_in_bytes),
        "bytes_accessed": float(ca.get("bytes accessed", float("nan"))),
        "flops": float(ca.get("flops", float("nan"))),
    }


def check_plan(core, plan, *, tol: float = 0.15) -> dict[str, float]:
    """Cross-check :func:`~runbookai_tpu.engine.memory_plan.plan_serving`
    arithmetic against the live engine's ACTUAL allocations (single-chip
    plans: tp=1). ``tol`` governs the WEIGHT comparison only (the plan
    approximates scale rows); KV bytes/token is pure layout arithmetic
    with no approximation, so it must match the allocated pool exactly.
    Raises AssertionError with the numbers on divergence."""
    actual_w = param_nbytes(core.params)
    kv_vals = core._kv_k[0] if isinstance(core._kv_k, tuple) else core._kv_k
    pool_tokens = kv_vals.shape[1]
    actual_kv_tok = kv_pool_nbytes(core) / pool_tokens
    got = {
        "plan_weight_bytes": plan.weight_bytes_per_chip,
        "actual_weight_bytes": actual_w,
        "plan_kv_bytes_per_token": plan.kv_bytes_per_token_per_chip,
        "actual_kv_bytes_per_token": actual_kv_tok,
    }
    w_err = abs(plan.weight_bytes_per_chip - actual_w) / max(actual_w, 1)
    kv_err = (abs(plan.kv_bytes_per_token_per_chip - actual_kv_tok)
              / max(actual_kv_tok, 1e-9))
    assert w_err <= tol, (
        f"memory plan weight arithmetic diverges from the allocated tree "
        f"by {w_err:.1%} (> {tol:.0%}): {got}")
    assert kv_err <= 1e-6, (
        f"memory plan KV bytes/token diverges from the allocated pool: {got}")
    return got
