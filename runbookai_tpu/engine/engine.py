"""Continuous-batching serving engine (host loop) over the paged JAX model.

The TPU-native replacement for the reference's hosted-LLM HTTP calls
(``src/model/llm.ts``): requests are admitted mid-flight, prompts prefill in
fixed-size chunks, and all live sequences share one compiled decode step over
a fixed batch of slots (static shapes — the same XLA program every step).

Scheduling policy per :meth:`EngineCore.step`:

1. admit waiting requests while decode slots + KV pages allow;
2. run one prefill chunk for the oldest prefilling request (prefill and
   decode interleave so TTFT of new requests doesn't starve running decodes);
3. run one batched decode step for every decoding request;
4. finish/evict sequences (stop tokens, budgets, grammar end), free pages.

Preemption: if the page pool is exhausted mid-decode the *youngest* request is
preempted by recompute (pages freed, generated tokens folded into its prompt,
re-queued) — forward progress for the rest is preserved.

Static-shape tricks:

- decode always runs with ``B = max_batch_slots``; empty slots carry a null
  page table and ``ctx_len = 0`` (fully masked attention).
- prefill chunks are right-padded to ``prefill_chunk``; pad tokens write their
  K/V into the reserved null page (page 0) via an extra "trash" page-table
  column at logical position ``max_pages``, so they can never corrupt live
  cache state.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from dataclasses import dataclass, fields
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from runbookai_tpu.engine.flight_recorder import (
    PHASE_SPANS,
    FlightRecorder,
    OpenStep,
)
from runbookai_tpu.engine.kv_cache import (
    STATE_COUNTERS,
    WINDOW_COUNTERS,
    WindowSpec,
    KVCacheManager,
    hash_blocks,
)
from runbookai_tpu.engine.request import (
    EngineOutput,
    EngineRequest,
    FinishReason,
    RequestState,
)
from runbookai_tpu.ops.sampling import needs_sort, sample_tokens
from runbookai_tpu.sched import class_label, class_name
from runbookai_tpu.utils import metrics as metrics_mod
from runbookai_tpu.utils.trace import annotate, get_tracer

# Programs this PROCESS compiled, or loaded from the persistent cache (the
# event wraps both), and the seconds that took: jax.monitoring has one
# process-wide listener list, so there is one listener, here. A step
# reads the totals at its two ends; the difference is its ``compile_s``
# (what names a stall as a compile) and adds to the engine's
# ``compile_time_s`` / ``compiles``. Replicas stepping side by side each
# see what the process compiled meanwhile.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_lock = threading.Lock()
_compile_totals = [0, 0.0]


def _on_compile(event: str, duration: float, **_: Any) -> None:
    if event == _COMPILE_EVENT:
        with _compile_lock:
            _compile_totals[0] += 1
            _compile_totals[1] += duration


jax.monitoring.register_event_duration_secs_listener(_on_compile)


@dataclass
class EngineConfig:
    page_size: int = 16
    num_pages: int = 2048
    max_batch_slots: int = 8
    prefill_chunk: int = 256
    max_seq_len: int = 8192
    block_pages: int = 32
    kv_dtype: Any = jnp.bfloat16
    # Reserve this many pages of headroom per admitted sequence so decode can
    # proceed a while before needing new allocations.
    admit_headroom_tokens: int = 64
    # Max decode tokens sampled per device dispatch (amortizes the host sync;
    # clamped to powers of two to bound compile count). Guided requests force 1.
    decode_steps_per_dispatch: int = 8
    # Decode attention implementation: "xla" (portable) | "pallas" (TPU kernel).
    attn_impl: str = "xla"
    # Quantized-matmul implementation for int8 weights: "pallas" streams the
    # int8 tiles through ops/qmm_pallas.py at decode/verify shapes (half the
    # bf16 HBM bytes by construction); "xla" trusts the compiler to fuse the
    # widen into the dot. Single-model-shard only (forward_impl downgrades
    # under a TP mesh); unquantized weights ignore it.
    qmm_impl: str = "xla"
    # Sequences whose prefill chunks run in ONE batched dispatch per step.
    # Under N concurrent submissions, prefill wall-clock drops ~N× vs the
    # one-sequence-per-step serialization (VERDICT r1 weak #5); rows are
    # padded to powers of two to bound distinct compiled programs.
    prefill_batch: int = 4
    # Prompt-lookup speculative decoding (greedy requests): draft the tokens
    # that followed the last occurrence of the trailing n-gram in the
    # sequence's own history, verify all of them in ONE T=K forward (a
    # parallel MXU matmul instead of K sequential decode steps). Agent
    # workloads repeat heavily (tool names, JSON keys, service ids), so
    # acceptance rates are high; a miss still yields one token per dispatch.
    speculative: bool = True
    spec_ngram: int = 3
    # Grammar fast-forward for guided requests: emit mask-forced token runs
    # without per-token decode dispatches by folding them into a prefill
    # chunk. A win where the per-dispatch host sync dominates; a LOSS on
    # CPU, where compute scales with the padded chunk length — None =
    # auto (on for tpu).
    grammar_fast_forward: Optional[bool] = None
    # Overlapped decode pipeline (one-step lag): the sampled token buffer
    # stays device-resident and feeds the next dispatch directly, while a
    # window's tokens are copied to host asynchronously and consumed when
    # the NEXT window is already in flight — detokenization, stop scans and
    # stream emission run behind the device step instead of serializing it.
    # Stop conditions therefore fire one window late (emit-then-truncate:
    # the overshoot window's tokens are discarded, its KV pages reclaimed
    # on finish). Guided/logprob batches, spec verify, preemption and the
    # context-limit boundary force a synchronous drain first, so token
    # streams are byte-identical to ``False`` (forced-sync) mode.
    overlap_decode: bool = True
    # Max rounds to skip re-probing speculation after rounds that produced
    # no usable drafts. Draft construction needs the host-current history,
    # so each probe drains the overlapped window; backing off (1, 2, 4, …
    # up to this cap per consecutive miss) keeps the lag pipeline hot on
    # non-repetitive traffic while repetitive traffic re-enters
    # speculation within a couple of rounds.
    spec_backoff_rounds: int = 8
    # Unified mixed prefill+decode dispatch: whenever prompts and decodes
    # coexist, ONE ragged forward serves every live decode slot (1 token
    # each) plus the oldest prefill chunk(s), and a prefill row completing
    # its prompt samples its first token in the same dispatch — the 2
    # dispatches/step a prompt burst used to cost become 1. None = auto:
    # on for tpu where dispatch latency dominates, off on CPU where compute
    # scales with the padded ragged buffer — the same policy and rationale
    # as grammar_fast_forward. Guided/logprob requests and kv-page-split
    # meshes keep the classic split path (forced-sync semantics).
    mixed_dispatch: Optional[bool] = None
    # Per-step token budget of a mixed dispatch: decode slots (1 token
    # each) + prefill chunk tokens. None = prefill_chunk + max_batch_slots.
    mixed_token_budget: Optional[int] = None
    # Data-parallel engine fleet (engine/fleet.py): construct this many
    # EngineCore replicas, each pinned to a disjoint device slice of the
    # dp axis, behind a prefix-affinity router with a least-loaded
    # tiebreak. 1 = the classic single engine; >1 makes JaxTpuClient (and
    # every surface behind it — OpenAI server, MCP, agent runtime, eval
    # suite) serve through an AsyncFleet. Slots/pages in this config are
    # PER REPLICA. On CPU tier-1 the replicas land on the virtual mesh's
    # devices; on a pod each host builds replicas over its local slice
    # (parallel/multihost.local_replica_range).
    dp_replicas: int = 1
    # Flight recorder (engine/flight_recorder.py): retain the last N
    # per-step records (dispatch kind, tokens, occupancy, queue depth,
    # KV pressure, wall split) in a preallocated ring — O(1) append off
    # the hot path, surfaced via GET /debug/steps. 0 disables recording entirely.
    flight_recorder_steps: int = 512
    # Host-RAM spill tier (engine/kv_cache.HostSpillTier): retain up to
    # this many evicted prefix-cache pages in host memory so a re-sent
    # prompt re-admits them (one upload) instead of re-prefilling. Spill
    # capture runs on the admission/prefill path only — never inside the
    # decode loop. 0 disables the tier. Budgeted by
    # memory_plan.ServingPlan.host_spill_bytes against host RAM, not HBM.
    kv_spill_pages: int = 0
    # Waiting-queue policy (runbookai_tpu/sched/): "wdrr" interleaves
    # priority classes by weighted-deficit stride — a batch flood cannot
    # starve interactive admits AND interactive load cannot starve batch
    # (FCFS within a class; single-class traffic is plain FIFO either
    # way). "priority" keeps the classic strict priority-then-FCFS sort.
    sched_policy: str = "wdrr"
    # Priority class -> admission-share weight (wdrr only). None = the
    # package default {batch: 1, interactive: 8}.
    sched_weights: Optional[dict] = None

    @classmethod
    def from_plan(cls, engine_block: dict, *, default_kv_dtype: Any = None,
                  **overrides) -> "EngineConfig":
        """Construct from a serving-plan artifact's ``engine`` block
        (:mod:`runbookai_tpu.autotune.plan`) — the autotuner's output is
        a first-class config input, not YAML to be re-typed.

        ``engine_block`` keys map 1:1 onto fields; ``kv_dtype`` travels
        as a plan string ("auto"/"bf16"/"fp8"/"int8" — "auto" resolves to
        ``default_kv_dtype``, the activation dtype, exactly the
        ``llm.kv_cache_dtype`` contract). ``overrides`` win over the plan
        (explicit config beats artifact). Unknown keys raise: a plan from
        a newer schema must fail loudly, never half-apply.
        """
        names = {f.name for f in fields(cls)}
        unknown = sorted(set(engine_block) - names - {"kv_dtype"})
        if unknown:
            raise ValueError(
                f"plan engine block has unknown keys: {', '.join(unknown)}")
        kw = {k: v for k, v in engine_block.items() if k != "kv_dtype"}
        name = engine_block.get("kv_dtype")
        if name is not None:
            kw["kv_dtype"] = resolve_kv_dtype(
                name, default_kv_dtype if default_kv_dtype is not None
                else jnp.bfloat16)
        for key in ("attn_impl", "qmm_impl"):
            # EngineConfig serves literal impls only — "auto" is a
            # deployment-time decision (backend, weight width) the caller
            # must make; passing it through would compare false against
            # "pallas" everywhere and silently serve the XLA path.
            if kw.get(key) == "auto" and key not in overrides:
                raise ValueError(
                    f"plan {key} 'auto' must be resolved by the caller "
                    f"(pass {key}=... for the deployment backend)")
        kw.update(overrides)
        return cls(**kw)


def resolve_kv_dtype(name: Optional[str], default: Any) -> Any:
    """The ONE resolver for every kv-dtype spelling a plan or config can
    carry: :meth:`EngineConfig.from_plan` and
    ``from_config`` must allocate the same pool for the same string.
    "auto"/empty/None follow ``default`` (the activation dtype); "bf16"
    pins a bfloat16 pool even on float32 activations; unknown names
    raise instead of silently serving the activation width."""
    if name in (None, "", "auto"):
        return default
    resolved = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn,
                "int8": jnp.int8}.get(name)
    if resolved is None:
        raise ValueError(
            f"kv_dtype {name!r} not one of auto/bf16/fp8/int8")
    return resolved


# The step programs take their forward from the configuration
# (``cfg.forwards()``: ``models/family.py`` ``Family`` is all the engine reads
# of a model), never from a model module by name. Every family's forward has
# ONE signature and returns ``(logits, kv_k, kv_v, experts, state, hidden)``:
# the expert counts of a model with an expert share (five integers,
# ``family.EXPERT_COUNTS``), the state pool of a model with recurrent layers,
# the trunk's last hidden state of a model that drafts for itself — and None,
# no output at all, for any other.
#
# A model with recurrent layers (``cfg.state_pool_spec``: models/
# qwen3_next.py) has a second pool beside the pages, indexed by batch slot:
# ``state``, donated and carried through every step program as the page pool
# is, with ``state_rows`` — the slot of each row of the call, where a row is
# not its own slot — and handed back as one more result, last. For every
# other model both are None: no operand goes in, no result comes out
# (``_with_state``), and the program compiles to what it was.


def _with_state(results: tuple, state) -> tuple:
    """A step program's results, with the state pool last where there is one."""
    return results if state is None else (*results, state)


# A model that brings its own drafter (``cfg.drafter()``: models/joyai.py's
# prediction module) keeps one more layer of the paged pool, written one
# position behind the trunk's: row ``i`` is made of the trunk's hidden state
# at ``i`` and the token at ``i + 1``. Every step program, asked with
# ``self_draft``, therefore runs the module over the positions it fed, once
# the token after each is known (the next prompt token, or the one it just
# sampled), and hands back, last, the module's draft for each row: the
# token after the one it sampled. The invariant between programs: a row
# with ``n`` committed tokens has trunk rows and module rows ``0 .. n - 2``
# and a draft of token ``n``. For every other model, and with speculation
# off, ``self_draft`` is False: no operand, no result, the same program.


def _with_drafts(results: tuple, drafts) -> tuple:
    """A step program's results, with the module's drafts last where the
    model drafts for itself."""
    return results if drafts is None else (*results, drafts)


@partial(jax.jit, donate_argnums=(0,))
def _state_admit(state, snaps, slot, src):
    """Slot ``slot`` of the state pool starts a sequence: from row ``src``
    of the snapshot pool (a prefix hit), or from zero (``src`` < 0)."""
    def one(a, s):
        if s.shape[1]:
            row = jax.lax.dynamic_index_in_dim(s, jnp.maximum(src, 0), 1)
            row = jnp.where(src >= 0, row, jnp.zeros_like(row))
        else:  # no snapshot pool: every sequence starts from zero
            row = jnp.zeros_like(a[:, :1])
        return jax.lax.dynamic_update_slice_in_dim(a, row, slot, axis=1)

    return tuple(one(a, s) for a, s in zip(state, snaps))


@partial(jax.jit, donate_argnums=(0,))
def _state_snapshot(snaps, state, dst, slot):
    """Row ``dst`` of the snapshot pool becomes slot ``slot``'s state."""
    return tuple(jax.lax.dynamic_update_slice_in_dim(
        s, jax.lax.dynamic_index_in_dim(a, slot, 1), dst, axis=1)
        for s, a in zip(snaps, state))


@partial(jax.jit, static_argnames=("cfg", "page_size", "block_pages", "attn_impl",
                                   "mesh", "qmm_impl", "self_draft"),
         donate_argnums=(4, 5, 14), donate_argnames=("state",))
def _decode_step(
    params, cfg, tokens, positions, kv_k, kv_v, tables, ctx_lens,
    temps, top_ps, top_ks, key, mask, adapter_ids, counts=None, pres=None,
    freq=None, seeds=None, bias=None, *, page_size: int,
    block_pages: int, attn_impl: str = "xla", mesh=None, qmm_impl: str = "xla",
    state=None, self_draft: bool = False,
):
    forward, _ = cfg.forwards()
    logits, kv_k, kv_v, experts, state, hidden = forward(
        params, cfg, tokens, positions, kv_k, kv_v, tables, ctx_lens,
        page_size=page_size, block_pages=block_pages, attn_impl=attn_impl,
        mesh=mesh, adapter_ids=adapter_ids, qmm_impl=qmm_impl, state=state,
    )
    tok = sample_tokens(logits[:, -1], key, temps, top_ps, mask, top_ks,
                        counts=counts, presence=pres, frequency=freq,
                        seeds=seeds, positions=ctx_lens, bias=bias)
    if counts is not None:
        counts = counts.at[jnp.arange(tok.shape[0]), tok].add(1)
    drafts = None
    if self_draft:
        module_pass, _, draft_tokens = cfg.drafter()
        y, kv_k, kv_v, module_experts = module_pass(
            params, cfg, hidden, tok[:, None], positions, kv_k, kv_v, tables,
            ctx_lens, page_size, block_pages)
        experts = experts + module_experts
        drafts = draft_tokens(params, cfg, y[:, -1])
    return _with_state(_with_drafts(
        (tok, logits[:, -1], kv_k, kv_v, counts, experts), drafts), state)


@partial(jax.jit,
         static_argnames=("cfg", "page_size", "block_pages", "k_steps", "attn_impl",
                          "mesh", "qmm_impl", "self_draft"),
         donate_argnums=(4, 5, 13), donate_argnames=("state",))
def _decode_multi(
    params, cfg, tokens, positions, kv_k, kv_v, tables, ctx_lens,
    temps, top_ps, top_ks, key, adapter_ids, counts=None, pres=None,
    freq=None, seeds=None, bias=None, *, page_size: int, block_pages: int,
    k_steps: int, attn_impl: str = "xla", mesh=None, qmm_impl: str = "xla",
    state=None, self_draft: bool = False,
):
    """K autoregressive decode steps in ONE dispatch (on-device sampling).

    One host sync per sampled token serializes the device behind the host,
    so the engine amortizes one token fetch over ``k_steps`` tokens. Pages
    for ctx+K must be pre-allocated; per-sequence
    stop conditions are applied host-side after the fetch (tokens past a stop
    are discarded — their KV writes are position-addressed, so accepted tokens
    simply overwrite them later). Penalty ``counts`` and per-request
    ``seeds`` ride the scan carry, so penalized/seeded sampling keeps the
    multi-token amortization. A recurrent ``state`` rides it too, and is
    NOT position-addressed: a row's tokens past its stop have gone into its
    slot's state. That is sound because a stop ends the sequence: the slot
    is zeroed or restored when it is next admitted (``_state_admit``), and
    nothing reads it before.
    """

    forward, _ = cfg.forwards()

    def step(carry, _):
        tokens, positions, kv_k, kv_v, ctx_lens, key, counts, state, y = carry
        logits, kv_k, kv_v, experts, state, hidden = forward(
            params, cfg, tokens, positions, kv_k, kv_v, tables, ctx_lens,
            page_size=page_size, block_pages=block_pages, attn_impl=attn_impl,
            mesh=mesh, adapter_ids=adapter_ids, qmm_impl=qmm_impl, state=state,
        )
        key, sub = jax.random.split(key)
        tok = sample_tokens(logits[:, -1], sub, temps, top_ps, None, top_ks,
                            counts=counts, presence=pres, frequency=freq,
                            seeds=seeds, positions=ctx_lens, bias=bias)
        if counts is not None:
            counts = counts.at[jnp.arange(tok.shape[0]), tok].add(1)
        if self_draft:
            y, kv_k, kv_v, module_experts = cfg.drafter()[0](
                params, cfg, hidden, tok[:, None], positions, kv_k, kv_v,
                tables, ctx_lens, page_size, block_pages)
            experts, y = experts + module_experts, y[:, -1]
        carry = (tok[:, None], positions + 1, kv_k, kv_v, ctx_lens + 1, key,
                 counts, state, y)
        return carry, (tok, experts)

    # The module's output rides the carry: only the last pass's is drafted of.
    y0 = (jnp.zeros((tokens.shape[0], params["embed"].shape[1]),
                    params["embed"].dtype) if self_draft else None)
    (_, positions, kv_k, kv_v, _, _, counts, state, y), (toks, experts) = jax.lax.scan(
        step, (tokens, positions, kv_k, kv_v, ctx_lens, key, counts, state, y0),
        None, length=k_steps,
    )
    if experts is not None:
        experts = jnp.sum(experts, axis=0)  # over the K passes
    drafts = cfg.drafter()[2](params, cfg, y) if self_draft else None
    return _with_state(_with_drafts(
        (toks.T, kv_k, kv_v, counts, experts), drafts), state)  # [B, K]


@partial(jax.jit, static_argnames=("cfg", "page_size", "block_pages", "attn_impl",
                                   "mesh", "qmm_impl", "rounds"),
         donate_argnums=(4, 5))
def _decode_spec(
    params, cfg, tokens, positions, kv_k, kv_v, tables, ctx_lens,
    adapter_ids, page_size: int, block_pages: int, attn_impl: str = "xla",
    mesh=None, qmm_impl: str = "xla", drafts=None, rounds: int = 0,
):
    """Verify a speculated chunk: one T=K forward, greedy argmax per position.

    ``tokens[:, 0]`` is each sequence's real last sampled token; the rest are
    drafts. Causal masking inside :func:`forward_impl` makes position i's
    logits depend only on tokens ≤ i, so the host can accept the longest
    prefix where the model's own argmax agrees with the draft. Rejected
    positions leave garbage K/V exactly like multi-step decode does —
    position-addressed writes are overwritten when the real tokens arrive.

    With ``attn_impl="pallas"`` the T>1 verify forward runs the Pallas chunk
    kernel (``paged_chunk_attention``) — positions are contiguous from
    ``ctx-1``, satisfying the kernel's contiguity contract.

    With ``rounds`` (a model that drafts for itself, ``cfg.drafter()``) the
    program runs that many ROUNDS, the drafts never leaving the device.
    ``tokens`` [B] is each row's last sampled token, ``drafts`` [B] the
    module's draft of the one after it, ``positions`` [B] where the first
    goes, ``ctx_lens`` [B] nonzero for a live row. A round feeds ``[last,
    draft]`` at ``[p, p + 1]``; the trunk's argmaxes are ``a0, a1``; ``a0``
    is committed, and ``a1`` too iff ``draft == a0``; the module runs over
    both positions with the token after each (``a0, a1``) and the output at
    the last committed one makes the next draft. A rejected position leaves
    a trunk row and a module row that the next round overwrites. Returns
    (``[a0, a1, accepted]`` [B, rounds, 3] — one array, one fetch — kv_k,
    kv_v, experts over all rounds, the last token [B], the next draft [B]).
    """
    forward, _ = cfg.forwards()
    if rounds:
        module_pass, _, draft_tokens = cfg.drafter()
        live = ctx_lens > 0

        def one_round(carry, _):
            last, draft, pos, kv_k, kv_v = carry
            fed = jnp.stack([last, draft], axis=1)
            at = jnp.stack([pos, pos + 1], axis=1)
            ctx = jnp.where(live, pos + 2, 0)
            logits, kv_k, kv_v, experts, _, hidden = forward(
                params, cfg, fed, at, kv_k, kv_v, tables, ctx,
                page_size=page_size, block_pages=block_pages,
                attn_impl=attn_impl, mesh=mesh, adapter_ids=adapter_ids,
                qmm_impl=qmm_impl)
            a = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, 2]
            accepted = (draft == a[:, 0]).astype(jnp.int32)
            y, kv_k, kv_v, module_experts = module_pass(
                params, cfg, hidden, a, at, kv_k, kv_v, tables, ctx,
                page_size, block_pages)
            pos = pos + 1 + accepted
            draft = draft_tokens(
                params, cfg,
                jnp.take_along_axis(y, accepted[:, None, None], axis=1)[:, 0])
            last = jnp.take_along_axis(a, accepted[:, None], axis=1)[:, 0]
            return (last, draft, pos, kv_k, kv_v), (a, accepted,
                                                    experts + module_experts)

        (last, draft, _, kv_k, kv_v), (toks, accepted, experts) = jax.lax.scan(
            one_round, (tokens, drafts, positions, kv_k, kv_v), None,
            length=rounds)
        out = jnp.concatenate([toks, accepted[..., None]], axis=-1)
        return (out.transpose(1, 0, 2), kv_k, kv_v, jnp.sum(experts, axis=0),
                last, draft)
    logits, kv_k, kv_v, experts, _, _ = forward(
        params, cfg, tokens, positions, kv_k, kv_v, tables, ctx_lens,
        page_size=page_size, block_pages=block_pages, attn_impl=attn_impl,
        mesh=mesh, adapter_ids=adapter_ids, qmm_impl=qmm_impl,
    )
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32), kv_k, kv_v,
            experts)  # tokens [B, K]


@partial(jax.jit, static_argnames=("cfg", "page_size", "block_pages"),
         donate_argnums=(5, 6))
def _module_step(params, cfg, hidden, tokens, positions, kv_k, kv_v, tables,
                 ctx_lens, *, page_size: int, block_pages: int):
    """The module's row at the LAST position of a prompt, which waits for
    the first sampled token (``_run_prefill`` samples it outside its
    program): ``hidden`` [b, D] the trunk's state there, ``tokens`` [b] the
    token just sampled, ``positions`` [b] (a row that ended no prompt: the
    trash position). Returns (drafts [b], kv_k, kv_v, experts)."""
    module_pass, _, draft_tokens = cfg.drafter()
    y, kv_k, kv_v, experts = module_pass(
        params, cfg, hidden[:, None], tokens[:, None], positions[:, None],
        kv_k, kv_v, tables, ctx_lens, page_size, block_pages)
    return draft_tokens(params, cfg, y[:, 0]), kv_k, kv_v, experts


@partial(jax.jit, static_argnames=("cfg", "page_size", "block_pages", "attn_impl",
                                   "mesh", "qmm_impl"),
         donate_argnums=(3, 4), donate_argnames=("state",))
def _prefill_step(
    params, cfg, tokens, kv_k, kv_v, positions, tables, ctx_lens,
    last_idx, adapter_ids, page_size: int, block_pages: int,
    attn_impl: str = "xla", mesh=None, qmm_impl: str = "xla",
    state=None, state_rows=None, next_tokens=None,
):
    """Prefill one chunk for a BATCH of sequences; returns each row's final
    real-token logits ([B, vocab]). With ``next_tokens`` [B, T] (a model
    that drafts for itself: the prompt's token AFTER each position) the
    module runs over the chunk too, and each row's hidden state at
    ``last_idx`` comes back last: where that position ends a prompt, its
    next token is not known yet and :func:`_module_step` writes its row."""
    forward, _ = cfg.forwards()
    logits, kv_k, kv_v, experts, state, hidden = forward(
        params, cfg, tokens, positions, kv_k, kv_v, tables, ctx_lens,
        page_size=page_size, block_pages=block_pages, attn_impl=attn_impl,
        mesh=mesh, adapter_ids=adapter_ids, qmm_impl=qmm_impl, state=state,
        state_rows=state_rows,
    )
    rows = jnp.arange(logits.shape[0])
    last_hidden = None
    if next_tokens is not None:
        _, kv_k, kv_v, module_experts = cfg.drafter()[0](
            params, cfg, hidden, next_tokens, positions, kv_k, kv_v, tables,
            ctx_lens, page_size, block_pages)
        experts, last_hidden = experts + module_experts, hidden[rows, last_idx]
    return _with_state(_with_drafts(
        (logits[rows, last_idx], kv_k, kv_v, experts), last_hidden), state)


# Row-run alignment of the mixed ragged token buffer: every row's token run
# starts at a multiple of this, so each aligned block belongs to exactly one
# row and the ragged forward collapses to a chunked one with per-block
# gathered tables (ops/attention.ragged_paged_attention's layout contract).
_RAGGED_BLOCK = 8


@partial(jax.jit, static_argnames=("cfg", "page_size", "block_pages",
                                   "attn_impl", "mesh", "qmm_impl",
                                   "ragged_block"),
         donate_argnums=(7, 8, 23), donate_argnames=("state",))
def _mixed_step(
    params, cfg, tokens, feed_toks, dec_idx, positions, row_ids,
    kv_k, kv_v, tables, ctx_lens, adapter_rows, pf_last_idx, temps, top_ps,
    top_ks, key, pf_temps, pf_top_ps, pf_top_ks, pf_slot_map, pf_live,
    dec_live=None, counts=None, pres=None, freq=None, seeds=None, bias=None,
    pf_pres=None, pf_freq=None, pf_seeds=None, pf_bias=None, *,
    page_size: int, block_pages: int, attn_impl: str = "xla", mesh=None,
    qmm_impl: str = "xla", ragged_block: int = _RAGGED_BLOCK,
    state=None, state_rows=None, next_tokens=None,
):
    """ONE unified mixed prefill+decode dispatch (the ragged forward).

    ``tokens`` is the flat ragged buffer with prefill chunks host-filled
    and zeros at the decode positions; each slot's device-resident last
    token (``feed_toks``) is scattered in at ``dec_idx`` so decode inputs
    never visit the host. The forward returns last-token logits for every
    decode slot AND every prefill row; decode rows sample exactly like
    :func:`_decode_step` (feeding the overlap pipeline), and a prefill row
    that completed its prompt samples its FIRST token in this same
    dispatch (``pf_slot_map`` scatters it into the decode feed — TTFT
    loses a whole dispatch). ``pf_slot_map`` rows for non-completing /
    pad prefill rows point out of bounds and drop.

    Penalty counts update in-dispatch for both groups; the rows are
    disjoint (decode slots vs freshly assigned slots) so order is
    irrelevant, matching the split path's semantics. The decode-side add
    is masked by ``dec_live`` (1 = slot holds a live decoder): free
    slots' rows sample garbage logits here, and — unlike the split path,
    where a row is always re-seeded AFTER any such drift and before its
    first read — a prompt completing in THIS dispatch had its row seeded
    pre-dispatch, so an unmasked add would pollute it before the
    first-token gather below reads it.

    With ``next_tokens`` [N] (a model that drafts for itself: the prompt's
    token after each prefill position) the module runs over the same flat
    buffer once the tokens are sampled: a decode row's next token is the
    one it just sampled, and so is that of a prompt's last position. The
    decode rows go undrafted here (one token each), and every row leaves
    the module's cache and a draft behind, which come back last, by slot.
    """
    b = feed_toks.shape[0]
    tokens = tokens.at[dec_idx].set(feed_toks)
    sel_idx = jnp.concatenate([dec_idx, pf_last_idx])
    _, forward_ragged = cfg.forwards()
    logits, kv_k, kv_v, experts, state, hidden = forward_ragged(
        params, cfg, tokens, positions, row_ids, kv_k, kv_v, tables,
        ctx_lens, sel_idx, page_size=page_size, block_pages=block_pages,
        attn_impl=attn_impl, mesh=mesh, adapter_ids=adapter_rows,
        qmm_impl=qmm_impl, ragged_block=ragged_block, state=state,
        state_rows=state_rows,
    )
    dec_logits, pf_logits = logits[:b], logits[b:]
    key_dec, key_pf = jax.random.split(key)
    dec_tok = sample_tokens(dec_logits, key_dec, temps, top_ps, None, top_ks,
                            counts=counts, presence=pres, frequency=freq,
                            seeds=seeds, positions=ctx_lens[:b], bias=bias)
    if counts is not None:
        counts = counts.at[jnp.arange(b), dec_tok].add(dec_live)
    pf_counts = (jnp.take(counts, jnp.clip(pf_slot_map, 0, b - 1), axis=0)
                 if counts is not None else None)
    pf_tok = sample_tokens(pf_logits, key_pf, pf_temps, pf_top_ps, None,
                           pf_top_ks, counts=pf_counts, presence=pf_pres,
                           frequency=pf_freq, seeds=pf_seeds,
                           positions=ctx_lens[b:b + pf_temps.shape[0]],
                           bias=pf_bias)
    if counts is not None:
        counts = counts.at[pf_slot_map, pf_tok].add(pf_live, mode="drop")
    feed_new = dec_tok.at[pf_slot_map].set(pf_tok, mode="drop")
    drafts = None
    if next_tokens is not None:
        _, module_pass_ragged, draft_tokens = cfg.drafter()
        nxt = next_tokens.at[dec_idx].set(dec_tok)
        # (a pad row's index is 0, a decode position: it keeps what is there)
        nxt = nxt.at[pf_last_idx].set(
            jnp.where(pf_slot_map < b, pf_tok, nxt[pf_last_idx]))
        y, kv_k, kv_v, module_experts = module_pass_ragged(
            params, cfg, hidden, nxt, positions, row_ids, kv_k, kv_v, tables,
            ctx_lens, page_size, block_pages, ragged_block)
        experts = experts + module_experts
        d = draft_tokens(params, cfg, y[sel_idx])
        drafts = d[:b].at[pf_slot_map].set(d[b:], mode="drop")
    return _with_state(_with_drafts(
        (dec_tok[:, None], pf_tok, feed_new, kv_k, kv_v, counts, experts),
        drafts), state)


@functools.lru_cache(maxsize=8)
def _probe_pallas_attn_cached(backend: str, n_kv: int, n_q: int,
                              head_dim: int, page_size: int,
                              kv_dtype_name: str, act_dtype_name: str,
                              chunk_t: int = 4,
                              kv_split: bool = False) -> bool:
    """One small compile-and-run of each attention kernel the engine WILL
    dispatch, at its real block shapes — GQA group, head width, page
    size, dtypes and the chunk kernel's query block (``chunk_t`` is one
    full block of it; 0: the family dispatches none) — so a kernel Mosaic
    refuses stops engine construction with Mosaic's own message instead of
    failing the first request. Nothing is caught: selecting a kernel that does not compile
    is an error, never a reason to serve another path under the same
    name. The call is the step programs': a STACKED ``[L, tokens, n_kv,
    hd]`` pool (two layers stand for any depth) and a layer's number.
    Callers pass PER-SHARD n_kv/n_q (the shard_map-local shapes); on a
    page-split mesh the PARTIAL kernel is what decode runs, so that is
    probed too. Successes are cached per process — tests build many
    engines."""
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_chunk_attention,
        paged_decode_attention,
    )

    kv_dtype = jnp.dtype(kv_dtype_name)
    act_dtype = jnp.dtype(act_dtype_name)
    interp = backend == "cpu"
    kv = jnp.zeros((2, 2 * page_size, n_kv, head_dim), kv_dtype)
    tables = jnp.zeros((1, 2), jnp.int32)
    layer = jnp.ones((), jnp.int32)

    q1 = jnp.zeros((1, n_q, head_dim), act_dtype)
    out = paged_decode_attention(q1, kv, kv, tables,
                                 jnp.ones((1,), jnp.int32),
                                 page_size=page_size, interpret=interp,
                                 layer=layer)
    # runbook: noqa[RBK002] — probe barrier: the compile/execute must
    # finish (or raise) before serving trusts the decode kernel.
    jax.block_until_ready(out)

    t = chunk_t
    if t:
        qt = jnp.zeros((1, t, n_q, head_dim), act_dtype)
        positions = jnp.arange(t, dtype=jnp.int32)[None]
        out = paged_chunk_attention(qt, kv, kv, tables,
                                    jnp.full((1,), t, jnp.int32), positions,
                                    page_size=page_size, interpret=interp,
                                    layer=layer)
        # runbook: noqa[RBK002] — probe barrier: chunk-kernel lowering must
        # prove out before prefill dispatches it.
        jax.block_until_ready(out)
    if kv_split:
        from runbookai_tpu.ops.paged_attention_pallas import (
            paged_decode_attention_partial,
        )

        out = paged_decode_attention_partial(
            q1, kv, kv, tables, jnp.ones((1,), jnp.int32),
            jnp.int32(0), page_size=page_size, pages_local=1,
            interpret=interp, layer=layer)
        # runbook: noqa[RBK002] — probe barrier: the PARTIAL kernel is
        # the program a page-split mesh actually runs; prove it here.
        jax.block_until_ready(out)
    return True


def _shard_heads(model_cfg, mesh) -> tuple[int, int]:
    """(n_kv, n_q) as one shard_map shard sees them."""
    from runbookai_tpu.parallel.mesh import MODEL_AXIS

    kv_sh = mesh.shape.get(MODEL_AXIS, 1) if mesh is not None else 1
    kv_sh = max(1, min(kv_sh, model_cfg.n_kv_heads))
    if model_cfg.n_kv_heads % kv_sh or model_cfg.n_heads % kv_sh:
        kv_sh = 1  # unshardable heads replicate; kernel sees full shapes
    return model_cfg.n_kv_heads // kv_sh, model_cfg.n_heads // kv_sh


def _probe_pallas_attn(model_cfg, ecfg, act_dtype, mesh=None) -> None:
    from runbookai_tpu.ops.paged_attention_pallas import chunk_q_block
    from runbookai_tpu.parallel.mesh import SEQ_AXIS

    kv_split = mesh is not None and mesh.shape.get(SEQ_AXIS, 1) > 1
    n_kv, n_q = _shard_heads(model_cfg, mesh)
    chunk_t = (chunk_q_block(ecfg.prefill_chunk, n_q)
               if model_cfg.pallas_prefill else 0)
    _probe_pallas_attn_cached(jax.default_backend(), n_kv, n_q,
                              model_cfg.head_dim, ecfg.page_size,
                              jnp.dtype(ecfg.kv_dtype).name,
                              jnp.dtype(act_dtype).name,
                              chunk_t=chunk_t, kv_split=kv_split)


@functools.lru_cache(maxsize=8)
def _probe_pallas_attn_int8_cached(backend: str, n_kv: int, n_q: int,
                                   head_dim: int, page_size: int,
                                   act_dtype_name: str) -> bool:
    """The int8-scaled decode kernel (tuple pool: int8 values + f32
    per-token scales, two layers stacked, and a layer's number) at the
    engine's shapes; raises what Mosaic raises. Decode only: chunked
    prefill routes to XLA for int8."""
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )

    kv_vals = jnp.zeros((2, 2 * page_size, n_kv, head_dim), jnp.int8)
    kv_scales = jnp.zeros((2, 2 * page_size, n_kv), jnp.float32)
    tables = jnp.zeros((1, 2), jnp.int32)
    q1 = jnp.zeros((1, n_q, head_dim), jnp.dtype(act_dtype_name))
    out = paged_decode_attention(
        q1, (kv_vals, kv_scales), (kv_vals, kv_scales), tables,
        jnp.ones((1,), jnp.int32), page_size=page_size,
        interpret=backend == "cpu", layer=jnp.ones((), jnp.int32))
    # runbook: noqa[RBK002] — probe barrier: int8 widen-multiply must
    # lower (or raise) before serving reads int8 pages through it.
    jax.block_until_ready(out)
    return True


def _probe_pallas_attn_int8(model_cfg, ecfg, act_dtype) -> None:
    _probe_pallas_attn_int8_cached(
        jax.default_backend(), model_cfg.n_kv_heads, model_cfg.n_heads,
        model_cfg.head_dim, ecfg.page_size, jnp.dtype(act_dtype).name)


@functools.lru_cache(maxsize=8)
def _probe_qmm_pallas_cached(backend: str, m: int, k: int, n: int,
                             act_dtype_name: str, mesh=None) -> bool:
    """The int8 qmm kernel at the model's real (K, N), called as the decode
    programs call it — a STACKED ``[L, K, N]`` operand (two layers stand
    for any depth: the kernel's code does not depend on L) and a layer's
    number; raises what Mosaic raises. One shape is representative: the
    lowering concern is the int8 copy/convert pattern, not a particular
    multiple-of-128 block count.

    With a multi-device ``mesh`` the operands are committed replicated on
    it first, so the probe exercises the same GSPMD partitioning of the
    Mosaic custom call that the engine's compiled steps will — a DP-only
    mesh keeps qmm_impl="pallas", and a partitioning failure must surface
    here, not at the first real dispatch."""
    from runbookai_tpu.ops.qmm_pallas import qmm_pallas

    x = jnp.zeros((m, k), jnp.dtype(act_dtype_name))
    q = jnp.zeros((2, k, n), jnp.int8)
    s = jnp.zeros((1, n), jnp.float32)
    layer = jnp.ones((), jnp.int32)
    if mesh is not None and mesh.size > 1:
        from runbookai_tpu.parallel.mesh import replicated

        rep = replicated(mesh)
        x, q, s, layer = (jax.device_put(a, rep) for a in (x, q, s, layer))
    # runbook: noqa[RBK002] — probe barrier: one qmm compile at the real
    # (K, N) proves the Mosaic int8 dot before the first live dispatch.
    jax.block_until_ready(
        qmm_pallas(x, q, s, layer, interpret=backend == "cpu"))
    return True


def _probe_qmm_pallas(model_cfg, ecfg, act_dtype, mesh=None) -> None:
    from runbookai_tpu.ops.qmm_pallas import qmm_pallas_eligible

    m = ecfg.max_batch_slots
    k, n = model_cfg.dim, model_cfg.ffn_dim
    if not qmm_pallas_eligible(m, k, n):
        # The kernel would never engage on this model's main matmuls —
        # qmm falls back per-shape, so there is nothing to probe.
        return
    if mesh is not None and mesh.size <= 1:
        mesh = None  # single-device mesh == no mesh for partitioning
    _probe_qmm_pallas_cached(jax.default_backend(), m, k, n,
                             jnp.dtype(act_dtype).name, mesh=mesh)


@functools.lru_cache(maxsize=8)
def _probe_pallas_ragged_cached(backend: str, n_kv: int, n_q: int,
                                head_dim: int, page_size: int,
                                kv_dtype_name: str,
                                act_dtype_name: str) -> bool:
    """The ragged mixed-dispatch kernel path (``paged_ragged_attention``
    — the chunk kernel at the blocked ragged layout with per-block
    gathered tables) at a representative 2-row mix (one decode-shaped
    row, one chunk-shaped row), over layer 1 of a two-layer pool; raises
    what Mosaic raises."""
    from runbookai_tpu.ops.paged_attention_pallas import (
        paged_ragged_attention,
    )

    rq = _RAGGED_BLOCK
    kv = jnp.zeros((2, 2 * page_size, n_kv, head_dim),
                   jnp.dtype(kv_dtype_name))
    tables = jnp.zeros((2, 2), jnp.int32)
    q = jnp.zeros((2 * rq, n_q, head_dim), jnp.dtype(act_dtype_name))
    row_ids = jnp.repeat(jnp.arange(2, dtype=jnp.int32), rq)
    q_pos = jnp.concatenate(
        [jnp.zeros((rq,), jnp.int32), jnp.arange(rq, dtype=jnp.int32)])
    out = paged_ragged_attention(
        q, kv, kv, tables, jnp.asarray([1, rq], jnp.int32), q_pos,
        row_ids, page_size=page_size, ragged_block=rq,
        interpret=backend == "cpu", layer=jnp.ones((), jnp.int32))
    # runbook: noqa[RBK002] — probe barrier: the ragged mixed-dispatch
    # kernel must lower (or raise) before mixed traffic relies on it.
    jax.block_until_ready(out)
    return True


def _probe_pallas_ragged(model_cfg, ecfg, act_dtype, mesh=None) -> None:
    n_kv, n_q = _shard_heads(model_cfg, mesh)
    _probe_pallas_ragged_cached(
        jax.default_backend(), n_kv, n_q,
        model_cfg.head_dim, ecfg.page_size, jnp.dtype(ecfg.kv_dtype).name,
        jnp.dtype(act_dtype).name)


def _set_mask_row(mask: np.ndarray, i: int, m: np.ndarray) -> None:
    """Install a grammar mask as row ``i`` of the batch mask. The grammar
    speaks the tokenizer's vocabulary; a model whose (padded) vocabulary
    is wider has ids the tokenizer cannot spell, and none of them is
    admissible."""
    mask[i, :m.shape[0]] = m
    mask[i, m.shape[0]:] = False


@partial(jax.jit, donate_argnums=(0,))
def _seed_count_row(counts, row, ids, n):
    """Reset one slot's penalty-count row to the histogram of ``ids[:n]``
    (ids padded to a power of two host-side to bound compile count).
    Used on RE-admission after preemption, where the generated-so-far
    history must be restored; fresh assignments batch-zero instead."""
    live = (jnp.arange(ids.shape[0]) < n).astype(jnp.int32)
    hist = jnp.zeros((counts.shape[1],), jnp.int32).at[ids].add(live)
    return counts.at[row].set(hist)


@partial(jax.jit, donate_argnums=(0,))
def _reset_count_rows(counts, row_mask):
    """Zero every row where ``row_mask`` — ONE dispatch for a whole
    prefill batch of fresh penalized assignments."""
    return jnp.where(row_mask[:, None], 0, counts)


@partial(jax.jit, donate_argnums=(0,))
def _bump_counts_batch(counts, rows, toks, live):
    """counts[rows[i], toks[i]] += live[i] — ONE dispatch for the whole
    first-token batch (live masks out unpenalized/pad rows)."""
    return counts.at[rows, toks].add(live.astype(jnp.int32))


# The legacy step-counter dict keys re-exported as Prometheus counters via
# scrape-time callbacks: (metrics-dict key, metric name, help). Module-level
# so the fleet can re-bind the same names to cross-replica sums — one table,
# no drift between single-engine and fleet exports.
LEGACY_COUNTER_EXPORTS: tuple[tuple[str, str, str], ...] = (
    ("decode_tokens", "runbook_decode_tokens_total",
     "Tokens sampled by decode dispatches"),
    ("decode_steps", "runbook_decode_steps_total",
     "Decode dispatches"),
    ("prefill_tokens", "runbook_prefill_tokens_total",
     "Prompt tokens prefilled"),
    ("preemptions", "runbook_preemptions_total",
     "Requests preempted by recompute under pool pressure"),
    ("cached_prefix_tokens", "runbook_cached_prefix_tokens_total",
     "Prompt tokens served from the prefix cache"),
    ("spec_drafted", "runbook_spec_drafted_total",
     "Speculative tokens drafted"),
    ("spec_accepted", "runbook_spec_accepted_total",
     "Speculative tokens accepted"),
    ("sampler_calls", "runbook_sampler_calls_total",
     "Calls of the sampler by dispatched programs (one a decode pass, two "
     "a mixed step, one a prefill's first tokens)"),
    ("sampler_sorted_calls", "runbook_sampler_sorted_calls_total",
     "Of those, the calls with a row that samples: they sort the "
     "vocabulary, an all-greedy call takes the argmax"),
    ("grammar_forced_tokens", "runbook_grammar_forced_tokens_total",
     "Tokens emitted by grammar fast-forward without a dispatch"),
    ("decode_time_s", "runbook_decode_time_seconds_total",
     "Wall-clock spent in decode dispatches"),
    ("prefill_time_s", "runbook_prefill_time_seconds_total",
     "Wall-clock spent in prefill dispatches"),
    ("decode_dispatch_time_s", "runbook_decode_dispatch_seconds_total",
     "Decode wall-clock blocked on device work (dispatch issue + "
     "token egress wait)"),
    ("decode_host_time_s", "runbook_decode_host_overhead_seconds",
     "Decode wall-clock spent on host work (input prep, "
     "detokenization, stop scans, stream emission)"),
    ("decode_host_overlap_s",
     "runbook_decode_host_overlapped_seconds_total",
     "Host decode work that ran while a dispatch was in flight"),
    ("prefill_steps", "runbook_prefill_dispatch_total",
     "Pure prefill dispatches"),
    ("decode_dispatches", "runbook_decode_dispatch_total",
     "Pure decode dispatches (single, multi-step, and spec-verify)"),
    ("mixed_steps", "runbook_mixed_dispatch_total",
     "Unified mixed prefill+decode dispatches (one ragged forward "
     "serving both phases)"),
    ("mixed_tokens", "runbook_mixed_tokens_total",
     "Real tokens processed by mixed dispatches"),
    ("mixed_time_s", "runbook_mixed_time_seconds_total",
     "Wall-clock spent building and issuing mixed dispatches"),
    ("experts_touched", "runbook_experts_touched_total",
     "Held experts that got at least one live token in a forward pass, "
     "summed over layers and passes (models with an expert share)"),
    ("expert_overflows", "runbook_expert_overflows_total",
     "Expert layers of a forward pass whose dispatch overflowed a held "
     "expert's slots and ran every token through every held expert "
     "instead (exact, slower)"),
    ("state_snapshots_taken", "runbook_state_snapshots_taken_total",
     "Recurrent-state snapshots taken where a prefill chunk ended on a "
     "page boundary (models with recurrent layers)"),
    ("state_snapshots_restored", "runbook_state_snapshots_restored_total",
     "Admissions whose slot state was restored from a snapshot"),
    ("state_snapshot_evictions", "runbook_state_snapshot_evictions_total",
     "Snapshots dropped because the snapshot pool was full"),
    ("state_hash_tokens_matched", "runbook_state_hash_tokens_matched_total",
     "Prompt tokens admissions matched by page hash (models with "
     "recurrent layers)"),
    ("state_hash_tokens_granted", "runbook_state_hash_tokens_granted_total",
     "Of those, the tokens granted: up to a boundary with a snapshot"),
    ("kv_window_rows_released", "runbook_kv_window_rows_released_total",
     "Token rows of the window layers' pool given back behind the window "
     "while their sequence lived (models with window layers)"),
    ("kv_window_hash_tokens_matched",
     "runbook_kv_window_hash_tokens_matched_total",
     "Prompt tokens admissions matched by page hash (models with window "
     "layers)"),
    ("kv_window_hash_tokens_granted",
     "runbook_kv_window_hash_tokens_granted_total",
     "Of those, the tokens granted: up to a boundary whose window pages "
     "were still resident"),
)

def export_expert_pairs(reg, value_of: Callable[[str], float]) -> None:
    """``runbook_expert_pairs_total{kind=held|zero|absent}`` as scrape-time
    callbacks over ``value_of(metrics-dict key)`` — one engine's dict, or a
    fleet's sum."""
    pairs = reg.counter(
        "runbook_expert_pairs_total",
        "Token-expert pairs of live tokens by where the chosen expert is: "
        "held by this process, an identity (zero-computation) expert, or "
        "absent (another process's share); summed over layers",
        labels=("kind",))
    pairs.labels(kind="held").set_function(
        lambda: value_of("expert_pairs_held"))
    pairs.labels(kind="zero").set_function(
        lambda: value_of("expert_pairs_zero"))
    pairs.labels(kind="absent").set_function(
        lambda: value_of("expert_pairs_absent"))


_TOPK_LOGPROBS = 20  # OpenAI's top_logprobs ceiling; one compiled shape


@partial(jax.jit, static_argnames=())
def _token_logprobs(logits, toks):
    """Per-row logprob of the sampled token + top-K alternatives, computed
    on device so only [B, K+1] floats cross the host link (fetching the
    full [B, vocab] row per token would dwarf the decode step itself)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    rows = jnp.arange(logp.shape[0])
    chosen = logp[rows, toks]
    top_lp, top_ids = jax.lax.top_k(logp, _TOPK_LOGPROBS)
    return chosen, top_ids, top_lp


def _dispatch_stat(entry: Optional[dict]) -> dict[str, int]:
    """A dispatch annotation's stats: its number in the ledger, by which
    the profiler's clock joins the records'; nothing with the recorder
    off."""
    return {} if entry is None else {"dispatch": entry["n"]}


@dataclass
class _PendingDecode:
    """One in-flight decode window awaiting host consumption.

    ``toks_dev`` is the [B, K] device token buffer of the issued dispatch
    (its last column is already wired into the next dispatch's feed); the
    host copy is started asynchronously at issue time and consumed by
    :meth:`EngineCore._drain` one scheduler round later. ``reqs`` snapshots
    (request, slot) at dispatch time so a slot reassigned before the drain
    can never misroute tokens."""

    toks_dev: jax.Array  # [B, K]
    reqs: list[tuple[EngineRequest, int]]
    req_ids: frozenset[str]
    k: int
    # The dispatch's expert counts (None: the family counts none), fetched
    # with the tokens, and what the step record attributes them to.
    experts_dev: Optional[jax.Array] = None
    program: str = ""
    # The dispatch's ledger entry (its number and ``t_issued``; None with
    # the recorder off): the drain stamps it ready and counts its tokens.
    dispatch: Optional[dict] = None


@dataclass
class _SlotInputs:
    """Epoch-cached device inputs for a decode dispatch.

    Everything here is a pure function of the slot→request mapping (the
    scheduler epoch, bumped on admit/finish/preempt) and of the sequences'
    page lists (the KV manager's table version, bumped on growth), so a
    steady-state decode step reuses the uploaded arrays and does zero
    O(B·pages) page-table or O(B·vocab) bias rebuild work."""

    key: tuple[int, int]  # (scheduler epoch, kv table version)
    tables: jax.Array  # [B, max_pages + 1] int32, device
    adapters: jax.Array  # [B] int32, device
    temps: jax.Array
    top_ps: jax.Array
    top_ks: jax.Array
    pres: jax.Array
    freq: jax.Array
    seeds: jax.Array
    bias: Optional[jax.Array]
    use_pen: bool
    use_seed: bool
    use_bias: bool
    sorts: bool  # needs_sort(temps): a decode row samples


class EngineCore:
    """Synchronous stepping core. Drive with :meth:`step` until idle."""

    def __init__(
        self,
        model_cfg,
        params: Any,
        tokenizer: Any,
        engine_cfg: Optional[EngineConfig] = None,
        mask_fn: Optional[Callable[[EngineRequest], Optional[np.ndarray]]] = None,
        advance_fn: Optional[Callable[[EngineRequest, int], bool]] = None,
        seed: int = 0,
        tracer=None,
        mesh=None,
        lora_registry=None,
        draft_worker=None,
        replica_idx: Optional[int] = None,
    ):
        self.cfg = model_cfg
        self.ecfg = engine_cfg or EngineConfig()
        # Fleet membership (engine/fleet.py): replica ``i`` namespaces every
        # admitted request id with ``r{i}-`` so two replicas admitting the
        # same caller id can never collide in the shared Tracer/registry,
        # and stamps its index on trace records. None = standalone engine.
        self.replica_idx = replica_idx
        self._rid_prefix = f"r{replica_idx}-" if replica_idx is not None else ""
        self.params = params
        # Multi-LoRA: the stacked adapter pytree rides inside params so the
        # compiled steps see one tree; per-dispatch adapter_ids rows select
        # each sequence's adapter (models/lora.py).
        self.lora = lora_registry
        if lora_registry is not None:
            self.params = dict(params)
            self.params["lora"] = lora_registry.stacked()
        self.tokenizer = tokenizer
        # Draft-model speculation (engine/draft.py): the worker drafts k-1
        # tokens per spec round; prompt-lookup remains the fallback for
        # requests it cannot cover.
        self.draft = draft_worker
        self.tracer = tracer if tracer is not None else get_tracer()
        # Guided decoding hooks: mask_fn returns the allowed-token mask for a
        # request (or None), advance_fn feeds a sampled token to the grammar
        # automaton and returns True when the grammar has completed.
        self.mask_fn = mask_fn
        self.advance_fn = advance_fn
        # The config is resolved ONCE, here, into what the compiled steps
        # will run; self.ecfg after this block is what /healthz and
        # chip_smoke report. Two kinds of rule apply, and only two:
        # static ones (a combination the forward has no kernel plumbing
        # for — the same conditions models/llama.py tests at trace time)
        # rewrite the selection; everything still selected is then
        # compiled once at its real block shapes, and a kernel the
        # backend refuses raises out of this constructor. The caller's
        # config is copied, not mutated.
        import dataclasses as _dc

        from runbookai_tpu.models.quant import is_quantized
        from runbookai_tpu.parallel.mesh import MODEL_AXIS as _MODEL
        from runbookai_tpu.parallel.mesh import SEQ_AXIS as _SEQ

        act_dtype = self.params["embed"].dtype
        _kv_split_mesh = mesh is not None and mesh.shape.get(_SEQ, 1) > 1
        _model_tp = mesh.shape.get(_MODEL, 1) if mesh is not None else 1
        # int8 KV (values + per-token absmax scales, ops/attention.py):
        # the DECODE kernel reads int8 pages + scales directly; chunked
        # prefill runs the XLA gather path, the per-head-shard shard_map
        # wrappers have no scale plumbing, and the page-split layout
        # refuses.
        _kv_int8 = jnp.dtype(self.ecfg.kv_dtype) == jnp.int8
        # What the configuration's family does not do yet is refused here,
        # by name, before anything is built (the dataclass says what).
        refused = model_cfg.unsupported(
            lora=lora_registry is not None, model_axis=_model_tp,
            seq_axis=mesh.shape.get(_SEQ, 1) if mesh is not None else 1,
            kv_dtype=self.ecfg.kv_dtype,
            quantized=any(is_quantized(v)
                          for v in self.params["layers"].values()),
            speculative=self.ecfg.speculative,
            draft=draft_worker is not None)
        if refused:
            raise ValueError(
                f"model {model_cfg.name!r} (family {model_cfg.family!r}) "
                f"does not support: {'; '.join(refused)}")
        rows = model_cfg.max_prefill_rows
        if rows is not None and rows < self.ecfg.prefill_batch:
            # The configuration bounds the prefill rows of a dispatch (one
            # compiled width where its prompts prefill alone for seconds).
            self.ecfg = _dc.replace(self.ecfg, prefill_batch=rows)
        if not model_cfg.pallas_attention and self.ecfg.attn_impl == "pallas":
            # The Pallas kernels read per-head K/V pages; this family's
            # forward has its own attention over its own pool (the latent
            # cache of ``ops/mla.py``: LongCat, JoyAI). The recurrent
            # families' softmax layers page per-head K/V and take the decode
            # walk, probed below at their own head count and size.
            self.ecfg = _dc.replace(self.ecfg, attn_impl="xla")
        if _kv_int8 and _kv_split_mesh:
            raise ValueError(
                "kv_dtype=int8 is not supported on a KV page-split "
                "mesh (seq axis > 1); use fp8 KV for split serving")
        if self.ecfg.attn_impl == "pallas" and _model_tp > 1 and (
                _kv_int8 or model_cfg.n_kv_heads % _model_tp):
            # TP mesh without a per-shard kernel: int8 pools, or GQA
            # heads that do not divide the axis (the pool replicates).
            self.ecfg = _dc.replace(self.ecfg, attn_impl="xla")
        if self.ecfg.qmm_impl == "pallas" and (
                _model_tp > 1 or _kv_split_mesh):
            # The Pallas qmm is per-device code; TP matmuls are
            # partitioned by XLA SPMD from sharding annotations.
            self.ecfg = _dc.replace(self.ecfg, qmm_impl="xla")

        if self.ecfg.attn_impl == "pallas":
            if _kv_int8:
                _probe_pallas_attn_int8(model_cfg, self.ecfg, act_dtype)
            else:
                _probe_pallas_attn(model_cfg, self.ecfg, act_dtype,
                                   mesh=mesh)
        if self.ecfg.qmm_impl == "pallas" and any(
                is_quantized(v) for v in self.params["layers"].values()):
            _probe_qmm_pallas(model_cfg, self.ecfg, act_dtype, mesh=mesh)

        # Unified mixed prefill+decode dispatch: resolve the auto policy
        # (on where dispatch latency dominates, off on CPU where compute
        # scales with the padded ragged buffer). The kv page-split mesh
        # keeps the classic split path — the ragged layout has no
        # page-shard plumbing. int8 KV needs no ragged probe: mixed steps
        # are T>1 chunks, which int8 pools serve via the XLA gather path.
        mixed = self.ecfg.mixed_dispatch
        if mixed is None:
            mixed = jax.default_backend() == "tpu"
        if mixed and _kv_split_mesh:
            mixed = False
        if (mixed and self.ecfg.attn_impl == "pallas" and not _kv_int8
                and model_cfg.pallas_prefill):
            _probe_pallas_ragged(model_cfg, self.ecfg, act_dtype,
                                 mesh=mesh)
        self._mixed = bool(mixed)
        # Mixed-batch geometry (fixed shapes → one compiled mixed program
        # in steady state): decode section = one aligned block per slot,
        # prefill section = the chunk token budget rounded up to blocks,
        # plus one reserved null row for padding blocks.
        budget = (self.ecfg.mixed_token_budget
                  or (self.ecfg.prefill_chunk + self.ecfg.max_batch_slots))
        pf_budget = max(_RAGGED_BLOCK,
                        budget - self.ecfg.max_batch_slots)
        self._mix_pf_tokens = -(-pf_budget // _RAGGED_BLOCK) * _RAGGED_BLOCK
        self._mix_pf_rows = max(1, self.ecfg.prefill_batch)
        self._mix_rows = (self.ecfg.max_batch_slots + self._mix_pf_rows
                          + 1)

        # Sharded serving: with a mesh, the KV pool shards its kv-head axis
        # over the TP (``model``) axis alongside the Megatron param shardings
        # (``params`` must already be device_put by the caller — see
        # JaxTpuClient.from_config). Page tables / tokens stay host-built and
        # replicated; XLA inserts the collectives inside the compiled steps.
        self.mesh = mesh
        kv_sharding = None
        if mesh is not None:
            from runbookai_tpu.parallel.sharding import kv_pool_sharding

            kv_sharding = kv_pool_sharding(model_cfg, mesh)

        # A model with a prediction module of its own is its own drafter
        # (models/joyai.py): chosen by the model, with speculation on; no
        # option selects it and prompt lookup is not its fallback.
        self._mtp = bool(model_cfg.self_draft and self.ecfg.speculative)
        (pool_layers, pool_heads, pool_dim), v_side = model_cfg.kv_pool_spec
        # Recurrent layers keep their state a SLOT, not a token: a second
        # pool beside the pages, and a pool of snapshots behind prefix hits.
        state_spec = model_cfg.state_pool_spec
        # Window layers keep the last ``window`` positions only: a second
        # group of the pool, with its own pages (``cfg.kv_window_spec``:
        # the layers in it and the window; engine/kv_cache.py WindowSpec).
        window_spec = model_cfg.kv_window_spec
        self.kv = KVCacheManager(
            n_layers=pool_layers,
            num_pages=self.ecfg.num_pages,
            page_size=self.ecfg.page_size,
            n_kv_heads=pool_heads,
            head_dim=pool_dim,
            v_side=v_side,
            max_seq_len=self.ecfg.max_seq_len,
            dtype=self.ecfg.kv_dtype,
            sharding=kv_sharding,
            spill_pages=self.ecfg.kv_spill_pages,
            state_snapshots=(model_cfg.state_snapshots if state_spec
                             else None),
            # The module's row of a position is made of the NEXT token too.
            lookahead=1 if self._mtp else 0,
            window=(WindowSpec(
                n_layers=window_spec[0], window=window_spec[1],
                max_step=max(self.ecfg.prefill_chunk,
                             self.ecfg.decode_steps_per_dispatch + 1),
                slots=self.ecfg.max_batch_slots) if window_spec else None),
        )
        self._kv_k = self.kv.pool.kv_k
        self._kv_v = self.kv.pool.kv_v
        self.seed = seed  # recorded so an online rebuild replays it
        # The engine's own device state lives where its params and pool
        # live. Left uncommitted it lands on the process's default device,
        # so replica i of a fleet would bounce its sampling key, feed
        # tokens and penalty counts through device 0 on every dispatch.
        if mesh is not None:
            from runbookai_tpu.parallel.mesh import replicated

            _home = partial(jax.device_put, device=replicated(mesh))
        else:
            def _home(x):
                return x
        self._key = _home(jax.random.PRNGKey(seed))
        # ``[linear layers, slots, ...]`` a leaf, zeroed or restored when a
        # slot is admitted; the snapshot pool has the same leaves with its
        # rows in the slots' place. None: the model has no such state.
        self._state = self._snaps = None
        if state_spec:
            def pool(rows):
                return tuple(_home(jnp.zeros((shape[0], rows, *shape[1:]), dt))
                             for shape, dt in state_spec)

            self._state = pool(self.ecfg.max_batch_slots)
            self._snaps = pool(model_cfg.state_snapshots)

        # OpenAI repetition penalties: device-resident per-slot token
        # counts, seeded at slot assignment from the (folded) prompt and
        # updated inside the decode dispatches — zero per-step host
        # traffic. Rows for unpenalized requests drift and are never
        # read; each assignment re-seeds its row.
        self._tok_counts = _home(jnp.zeros(
            (self.ecfg.max_batch_slots, model_cfg.vocab_size), jnp.int32))

        self.waiting: list[EngineRequest] = []
        self.prefilling: list[EngineRequest] = []
        self.decoding: list[EngineRequest] = []
        self.finished: list[EngineRequest] = []
        # Admission-order policy (sched/wdrr.py): stride interleave of
        # priority classes, or None for the classic strict-priority sort.
        self._sched = None
        if self.ecfg.sched_policy == "wdrr":
            from runbookai_tpu.sched.wdrr import WeightedDeficitScheduler

            self._sched = WeightedDeficitScheduler(self.ecfg.sched_weights)
        elif self.ecfg.sched_policy != "priority":
            raise ValueError(
                f"sched_policy {self.ecfg.sched_policy!r} not one of "
                f"wdrr/priority")
        # SLO feedback controller (sched/feedback.py), attached by the
        # client when llm.sched.feedback is on; None = no behavior change.
        self.feedback = None
        self._slots: list[Optional[EngineRequest]] = [None] * self.ecfg.max_batch_slots
        self._last_token: dict[str, int] = {}
        # Overlapped decode pipeline state: the device-resident feed of each
        # slot's last sampled token (input side — no host round-trip), the
        # in-flight window awaiting async egress, the scheduler epoch that
        # keys the cached dispatch inputs, and the speculation re-probe
        # backoff (each probe costs a drain).
        self._feed_toks = _home(
            jnp.zeros((self.ecfg.max_batch_slots,), jnp.int32))
        # The module's draft of each slot's NEXT token (the one after
        # ``_feed_toks``'), device-resident like the feed; None: the model
        # has no drafter of its own, or speculation is off.
        self._draft_toks = (_home(jnp.zeros((self.ecfg.max_batch_slots,),
                                            jnp.int32)) if self._mtp else None)
        self._pending: Optional[_PendingDecode] = None
        self._sched_epoch = 0
        self._slot_cache: Optional[_SlotInputs] = None
        self._spec_backoff = 0
        self._spec_miss_streak = 0
        # Wall-clock already booked by nested drains (lets _run_decode add
        # only its own un-booked time to decode_time_s — no double count).
        self._drain_time_acc = 0.0
        # Serving metrics (BASELINE.md contract: TTFT + tokens/sec/chip).
        # This dict stays the single source of truth for the step counters
        # (/healthz contract, reset_metrics, tests); the registry re-exports
        # it via scrape-time callbacks in _install_metrics.
        # decode_time_s remains the total decode wall; the dispatch/host/
        # overlap components split it so the pipeline's win is attributable
        # (host emission used to be silently booked as decode time).
        # mixed_* split: a mixed step books its wall under mixed_time_s
        # (NOT prefill_time_s/decode_time_s — those keep their pure-step
        # semantics for the /healthz and PromQL contracts); the drained
        # decode window's egress/emission stays booked as decode_* like
        # any other window. prefill_steps / decode_dispatches /
        # mixed_steps count DISPATCHES, making the 2-dispatches→1 win of
        # mixed steps directly observable.
        # kv_pages_imported/exported count location-addressed page moves
        # (cross-replica pulls, prefill→decode handoffs, spill readmits);
        # kv_spill_readmits is the subset that came back from the host
        # spill tier.
        self.metrics = {"decode_tokens": 0, "decode_steps": 0, "prefill_tokens": 0,
                        "preemptions": 0, "decode_time_s": 0.0, "prefill_time_s": 0.0,
                        "cached_prefix_tokens": 0, "spec_drafted": 0, "spec_accepted": 0,
                        "decode_dispatch_time_s": 0.0, "decode_host_time_s": 0.0,
                        "decode_host_overlap_s": 0.0, "prefill_steps": 0,
                        "decode_dispatches": 0, "mixed_steps": 0,
                        "mixed_tokens": 0, "mixed_time_s": 0.0,
                        "kv_pages_imported": 0, "kv_pages_exported": 0,
                        "kv_spill_readmits": 0,
                        "compile_time_s": 0.0, "compiles": 0,
                        # Token-expert pairs by where they fell, and held
                        # experts touched, summed over layers and passes
                        # (a family with no expert share leaves them 0).
                        "expert_pairs_held": 0, "expert_pairs_zero": 0,
                        "expert_pairs_absent": 0, "experts_touched": 0,
                        "expert_overflows": 0,
                        # Calls of ``sample_tokens`` by dispatched programs
                        # and, of them, those that took the sorted path:
                        # counted on the host, from the temperatures it
                        # uploads, by the function the device decides by.
                        "sampler_calls": 0, "sampler_sorted_calls": 0,
                        # A model with recurrent state (the KV manager's
                        # StateSnapshots counts them; 0 for any other):
                        # snapshots taken at page boundaries, admissions
                        # restored from one, snapshots evicted by a full
                        # pool, and the prompt tokens admissions MATCHED
                        # by page hash beside those they were GRANTED.
                        **{"state_" + k: 0 for k in STATE_COUNTERS},
                        # A model with window layers (the KV manager counts
                        # them; 0 for any other): rows of the window pool
                        # given back behind the window, and the prompt
                        # tokens admissions matched beside those granted.
                        **{"kv_window_" + k: 0 for k in WINDOW_COUNTERS}}
        # Expert counts of dispatches that fetched no token of their own
        # (a prefill chunk that completed no prompt): (program, passes,
        # device array), riding the next token fetch.
        self._experts_parked: list[tuple[str, int, jax.Array]] = []
        # Flight-recorder mark for page transfers: imports/exports happen
        # BETWEEN steps (under the engine lock, not inside step()), so the
        # per-step record reports the delta since the last recorded step
        # rather than an intra-step delta that would always read 0.
        self._flight_kv_mark = (0, 0)
        self._state_mark: dict[str, int] = {}
        self._window_mark = 0
        # Of the prefill chunks dispatched since the last record: the
        # query-key pairs and the distinct key rows inside the window.
        self._window_chunks = [0, 0]
        # Workload-fingerprint tap (runbookai_tpu/obs): called once per
        # finishing request from _observe_finish with the EngineRequest.
        # None = no observer; the callee appends to a bounded deque — one
        # O(1) call off the dispatch path, never inside a dispatch.
        self.workload_tap = None
        # Fault-injection seam (runbookai_tpu/chaos): called at the TOP
        # of step(), under the AsyncEngine lock, before any pool
        # mutation. A hook may raise (replica crash — the loop's
        # _fail_live_requests path runs) or stall (replica wedge); hooks
        # are one-shot and clear themselves. None (the default) costs
        # one attribute check per step.
        self.chaos_hook = None
        self.registry = metrics_mod.get_registry()
        # Flight recorder: one bounded record per step (what was the
        # engine DOING on the slow steps?). The step thread is the only
        # writer; /debug/steps snapshots under the AsyncEngine lock.
        self.flight = FlightRecorder(self.ecfg.flight_recorder_steps)
        # The step being run (None between steps and with the recorder
        # off), and what the next record will carry: requests first
        # admitted, and lifecycle records of requests retired, since the
        # last recorded step (an abort lands between steps).
        self._open: Optional[OpenStep] = None
        self._admitted_log: list[list] = []
        self._finished_log: list[dict] = []
        # The dispatch whose tokens are being emitted (its ledger entry).
        self._emitting: Optional[dict] = None
        self._install_metrics()

    def _install_metrics(self) -> None:
        """Register the engine's Prometheus-facing metrics.

        Per-request latency histograms are observed directly at the
        scheduling points (admission, first token, finish); live-state
        gauges and the legacy step counters are scrape-time callbacks, so
        there is exactly one source of truth and zero per-step overhead.
        Registration is get-or-create and ``set_function`` replaces the
        previous callback, so rebuilding an engine in-process (tests,
        a supervisor's rebuild) re-binds the gauges to the newest core. A
        standalone engine also clears any per-replica labeled callbacks a
        previous FLEET left behind (fleet.py's ``_install_metrics``
        re-binds them when a fleet is current): without this, falling
        back from dp>1 to a single engine would keep scraping the dead
        replicas' cores — and pinning their params — forever.
        """
        reg, m = self.registry, metrics_mod
        if self.replica_idx is None:
            for name in ("runbook_replica_running_requests",
                         "runbook_replica_waiting_requests",
                         "runbook_replica_kv_pool_utilization",
                         "runbook_replica_decode_tokens_total",
                         "runbook_router_imbalance_ratio",
                         # Multi-model rollups (fleet/multimodel.py):
                         # falling back to one engine must release the
                         # dead groups' cores exactly like the replica
                         # gauges above.
                         "runbook_model_running_requests",
                         "runbook_model_waiting_requests",
                         "runbook_model_kv_pool_utilization",
                         "runbook_model_decode_tokens_total"):
                stale = reg.get(name)
                if stale is not None:
                    stale.clear_functions()
        self.hist_ttft = reg.histogram(
            "runbook_ttft_seconds", "Time to first token per request",
            buckets=m.TTFT_BUCKETS)
        self.hist_tpot = reg.histogram(
            "runbook_tpot_seconds",
            "Per-token decode latency (e2e minus TTFT over generated-1)",
            buckets=m.TPOT_BUCKETS)
        self.hist_e2e = reg.histogram(
            "runbook_e2e_seconds", "Request end-to-end latency",
            buckets=m.E2E_BUCKETS)
        self.hist_queue_wait = reg.histogram(
            "runbook_queue_wait_seconds",
            "Submission-to-admission wait (first admission only)",
            buckets=m.QUEUE_WAIT_BUCKETS)
        # Per-class scheduling surface (sched/): queue-wait and admit
        # counts by priority class — the starvation signal the WDRR
        # policy is judged on (docs/observability.md PromQL).
        self.hist_class_queue_wait = reg.histogram(
            "runbook_sched_queue_wait_seconds",
            "Submission-to-admission wait per priority class (first "
            "admission only)", labels=("cls",),
            buckets=m.QUEUE_WAIT_BUCKETS)
        self._m_class_admits = reg.counter(
            "runbook_sched_admits_total",
            "Requests admitted to prefill, per priority class",
            labels=("cls",))
        self.hist_mixed_tokens = reg.histogram(
            "runbook_mixed_tokens_per_dispatch",
            "Real (unpadded) tokens per unified mixed prefill+decode "
            "dispatch", buckets=m.MIXED_TOKENS_BUCKETS)
        # Live scheduler/pool state: plain attribute reads, safe from the
        # scrape thread without the step lock (at worst one step stale).
        reg.gauge("runbook_running_requests",
                  "Requests holding a decode slot"
                  ).set_function(lambda: len(self.decoding))
        reg.gauge("runbook_waiting_requests",
                  "Requests queued or prefilling"
                  ).set_function(lambda: len(self.waiting)
                                 + len(self.prefilling))
        g_cls_wait = reg.gauge(
            "runbook_sched_waiting_requests",
            "Requests queued or prefilling, per priority class",
            labels=("cls",))
        g_cls_wait.clear_functions()
        for label in ("interactive", "batch", "other"):
            g_cls_wait.labels(cls=label).set_function(
                lambda lb=label: float(sum(
                    1 for r in list(self.waiting) + list(self.prefilling)
                    if class_label(r.priority) == lb)))
        reg.gauge("runbook_kv_pages_total", "KV pool size in pages"
                  ).set_function(lambda: self.kv.allocator.num_pages)
        reg.gauge("runbook_kv_pages_in_use",
                  "KV pages referenced by live sequences"
                  ).set_function(lambda: self.kv.pages_in_use)
        reg.gauge("runbook_kv_pages_cached",
                  "Retired-but-resident prefix-cache pages"
                  ).set_function(lambda: self.kv.allocator.cached_pages)
        # Host spill tier (0s when disabled): captures vs LRU drops — the
        # difference is how much evicted prefix KV stays readmittable.
        reg.counter("runbook_kv_spill_pages_total",
                    "KV pages captured into the host spill tier at "
                    "eviction time").set_function(
            lambda: float(self.kv.spill.pages_spilled
                          if self.kv.spill else 0))
        reg.counter("runbook_kv_spill_evictions_total",
                    "Spill-tier pages dropped by its LRU bound"
                    ).set_function(
            lambda: float(self.kv.spill.evictions if self.kv.spill else 0))
        reg.gauge("runbook_kv_pool_utilization",
                  "Fraction of allocatable KV pages held by live sequences"
                  ).set_function(self.kv.utilization)
        reg.gauge("runbook_prefix_cache_hit_ratio",
                  "Cached prompt tokens / (cached + prefilled) since start"
                  ).set_function(self._prefix_hit_ratio)
        for key, name, help_text in LEGACY_COUNTER_EXPORTS:
            reg.counter(name, help_text).set_function(
                lambda k=key: float(self.metrics.get(k, 0)))
        export_expert_pairs(reg, lambda k: float(self.metrics.get(k, 0)))
        reg.gauge("runbook_decode_overlap_ratio",
                  "Fraction of host decode work hidden behind device "
                  "execution by the lagged pipeline (0 in forced-sync mode)"
                  ).set_function(self._overlap_ratio)

    def _overlap_ratio(self) -> float:
        host = self.metrics.get("decode_host_time_s", 0.0)
        return (self.metrics.get("decode_host_overlap_s", 0.0) / host
                if host > 0 else 0.0)

    def _prefix_hit_ratio(self) -> float:
        cached = self.metrics.get("cached_prefix_tokens", 0)
        total = cached + self.metrics.get("prefill_tokens", 0)
        return cached / total if total else 0.0

    # ------------------------------------------------------------------ API

    def reset_metrics(self) -> None:
        """Forget what was counted so far: step counters, the TTFT and
        TPOT histograms and the flight ring restart from zero. Callers
        that warm an idle engine up before a window they read (the
        autotuner's measured refinement, the soak gate) call this between
        the two; nothing on the serving path does."""
        for key, value in self.metrics.items():
            self.metrics[key] = type(value)()
        # The flight recorder reports page-transfer DELTAS against this
        # mark; zeroing the counters without it would make the next
        # recorded step report a negative import delta.
        self._flight_kv_mark = (0, 0)
        self._state_mark = {}
        self._window_mark = 0
        self.kv.window_counters = dict.fromkeys(WINDOW_COUNTERS, 0)
        if self.kv.snapshots is not None:
            self.kv.snapshots.reset_counters()
        self.hist_ttft.reset()
        self.hist_tpot.reset()
        self.flight.reset()

    def refresh_lora(self) -> None:
        """Pick up adapters registered after engine construction."""
        if self.lora is not None:
            self.params = dict(self.params)
            self.params["lora"] = self.lora.stacked()

    @contextlib.contextmanager
    def _span(self, phase: str, **meta):
        """One phase of a step on both clocks: its seconds in the open
        step's ``phases`` (time.monotonic()), and the same two boundaries
        as a span on the profiler's (``PHASE_SPANS``, ``meta`` its stats;
        a no-op without a profiler session). Outside a recorded step only
        the latter.

        The always-on counters keep their own perf_counter() stamps
        beside these (``t_build`` / ``t_issue`` / ``t_fetch`` below:
        ``decode_dispatch_time_s``, ``decode_host_time_s``,
        ``decode_host_overlap_s`` and the ``*_time_s``, which are
        Prometheus series, ``/healthz`` and the record's ``dispatch_s`` /
        ``host_s`` / ``overlap_s``). They count with the recorder off,
        when no phase is timed, and they cut a step otherwise: the decode
        side only, its host time as build plus emit in one, and the part
        of that an in-flight window hid. Neither set derives from the
        other."""
        name = PHASE_SPANS.get(phase)
        with annotate(name, **meta) if name else contextlib.nullcontext():
            step = self._open
            if step is None:
                yield
                return
            step.enter(phase)
            try:
                yield
            finally:
                step.exit()

    # The dispatch as a span (flight_recorder.DispatchLedger). Every helper
    # takes the entry ``_dispatching`` returned, which is None with the
    # recorder off, and then does nothing.

    def _dispatching(self, program: str, k: int = 0, rows: int = 0,
                     ctx_lens: Optional[np.ndarray] = None,
                     prefill_tokens: int = 0) -> Optional[dict]:
        """Before a step program is called in a recorded step: name it in
        the open step, with the pages its decode rows hold, and open its
        ledger entry."""
        if self._open is None:
            return None
        pages = 0 if ctx_lens is None else self._pages_held(ctx_lens)
        self._open.dispatched(program, k, rows, pages)
        return self.flight.dispatches.open(program, k, rows, pages,
                                           prefill_tokens)

    def _note_window_chunks(self, rows) -> None:
        """The prefill chunks ``(request, tokens, new context)`` of a
        dispatch, as the window layers' walks see them (a model with window
        layers, a recorded step): what the next record's ``window`` says of
        their work."""
        if self.kv.window is None or self._open is None:
            return
        for _, chunk, new_ctx in rows:
            pairs, seen = self.kv.window.chunk_work(new_ctx - chunk, chunk)
            self._window_chunks[0] += pairs
            self._window_chunks[1] += seen

    def _sampling(self, calls: int, sorts) -> None:
        """Count ``calls`` calls of ``sample_tokens`` by the program about
        to be dispatched, over rows whose temperatures ``needs_sort`` read
        as ``sorts``."""
        self.metrics["sampler_calls"] += calls
        if sorts:
            self.metrics["sampler_sorted_calls"] += calls

    def _issued(self, entry: Optional[dict], result: jax.Array,
                emits: bool = True) -> None:
        if entry is not None:
            self.flight.dispatches.issued(entry, result, emits)

    @contextlib.contextmanager
    def _fetching(self, entry: Optional[dict]):
        """The fetch phase around the ``device_get`` that consumes a
        dispatch's result: stamps the dispatch ready as it returns, and
        any dispatch issued before it and never fetched (a prefill chunk
        short of its prompt's end, the window in flight under a mixed
        step's first-token fetch) as its own result arrives, which the
        fetch was about to wait for anyway."""
        ledger = self.flight.dispatches
        # (a dispatch issued before a ``flight.reset()`` is of no ledger now)
        if entry is None or not ledger.mine(entry):
            with self._span("fetch"):
                yield
            return
        with self._span("fetch", dispatch=entry["n"]):
            for earlier, result in ledger.before(entry):
                # runbook: noqa[RBK002] — sanctioned sync: the fetch below
                # waits for this result first in any case (one device,
                # dispatches in order); waiting here gives it its own stamp.
                jax.block_until_ready(result)
                ledger.ready(earlier)
            yield
            ledger.ready(entry)

    @contextlib.contextmanager
    def _emitting_from(self, entry: Optional[dict], last: bool = True):
        """The emit phase of one dispatch's tokens: ``_emit_token`` counts
        them to it; after its ``last`` the entry goes to the next step
        record."""
        with self._span("emit"):
            self._emitting = entry
            try:
                yield
            finally:
                self._emitting = None
        if last and entry is not None:
            self.flight.dispatches.emitted(entry)

    def _first_token(self, req: EngineRequest, token: int) -> None:
        """Emit the token a request's prefill sampled: its first, unless
        it was preempted (the TTFT is the first admission's)."""
        if req.first_token_time is None:
            req.first_token_time = time.perf_counter()
            self.hist_ttft.observe(req.first_token_time - req.arrival_time)
            if self._open is not None:
                ledger = self.flight.dispatches
                req.rode_mark = (ledger, ledger.at(req.first_token_time))
                req.rode_tokens = {}
        self._emit_token(req, token)

    def submit(self, req: EngineRequest) -> None:
        req.t_enqueued = time.monotonic()
        if self._rid_prefix and not req.request_id.startswith(self._rid_prefix):
            # Replica namespace: the engine-internal id gains the r{idx}-
            # prefix (tracer JSONL, KV seq ids, abort lookups); the
            # caller's x-request-id travels separately as trace_id and is
            # echoed unchanged.
            req.request_id = self._rid_prefix + req.request_id
        if not req.prompt_ids:
            req.prompt_ids = [self.tokenizer.bos_id]
        if req.adapter is not None:
            if self.lora is None:
                raise ValueError(
                    f"request names adapter {req.adapter!r} but the engine "
                    f"has no LoRA registry")
            req.adapter_idx = self.lora.index_of(req.adapter)
            # Hot-loaded adapter: the registry knows the name before the
            # params tree has its row. An out-of-range gather would CLAMP
            # inside jit and silently serve the wrong adapter — refresh
            # here instead (submit runs under the same lock as step()).
            rows = next(iter(self.params["lora"].values()))["A"].shape[1]
            if req.adapter_idx >= rows:
                self.refresh_lora()
        if req.guided_state is None and req.sampling.guided and self.mask_fn:
            pass  # guided_state initialized lazily by the mask provider
        req.state = RequestState.WAITING
        self.waiting.append(req)
        if self.tracer.enabled:
            # Timeline anchor: the enqueue event opens the request's span
            # tree (`runbook timeline`); engine.admit and engine.request
            # close the queue-wait and lifetime edges against it.
            meta = {"request": req.request_id,
                    "prompt_tokens": len(req.prompt_ids)}
            if self.replica_idx is not None:
                meta["replica"] = self.replica_idx
            if req.trace_id is not None:
                meta["trace_id"] = req.trace_id
            self.tracer.event("engine.enqueue", **meta)

    @property
    def has_work(self) -> bool:
        # An in-flight lagged window counts as work: its tokens still need
        # host consumption even if every owning request already finished.
        return bool(self.waiting or self.prefilling or self.decoding
                    or self._pending is not None)

    def flush(self) -> None:
        """Drain the in-flight lagged decode window (if any), emitting its
        tokens and settling metrics. Shutdown/idle hook — a no-op when the
        pipeline is already drained."""
        self._drain_pending()

    def discard_inflight(self) -> None:
        """Crash recovery only: drop the in-flight window WITHOUT fetching
        (the device may be poisoned — a drain would raise again and wedge
        ``has_work`` forever). Callers must have failed/aborted the owning
        requests first; the window's tokens are lost with it, and its
        dispatch reaches no record."""
        self._pending = None
        if self.flight.dispatches is not None:
            self.flight.dispatches.in_flight.clear()

    # ------------------------------------------------- page import / export

    def export_kv_pages(self, prompt_ids: list[int],
                        hashes: Optional[list[int]] = None,
                        hash_seed: int = 0, skip_blocks: int = 0,
                        max_pages: Optional[int] = None):
        """Stage this replica's resident pages for ``prompt_ids``'s prefix
        (cross-replica pull / prefill→decode handoff). MUST run under the
        AsyncEngine step lock — it reads the live pool arrays. Returns an
        :class:`~runbookai_tpu.engine.kv_cache.ExportedPages` or None
        (nothing to export — the planned pages were evicted/re-registered
        since the probe; the chain re-walk under the lock is the
        staleness guard)."""
        out = self.kv.export_pages(
            self._kv_k, self._kv_v, prompt_ids, hashes=hashes,
            hash_seed=hash_seed, skip_blocks=skip_blocks,
            max_pages=max_pages)
        if out is not None:
            out.src_replica = self.replica_idx
            self.metrics["kv_pages_exported"] += out.num_pages
        return out

    def import_kv_pages(self, exported) -> int:
        """Install exported pages into this replica's pool (digest-checked,
        retired→matchable). MUST run under the AsyncEngine step lock; the
        pool arrays are functionally updated so the next dispatch serves
        the imported bytes. Returns pages imported."""
        self._kv_k, self._kv_v, n = self.kv.import_pages(
            self._kv_k, self._kv_v, exported)
        if n:
            self.metrics["kv_pages_imported"] += n
        return n

    def _trash_pos(self) -> int:
        return self.kv.max_pages_per_seq * self.ecfg.page_size

    def _pages_held(self, ctx_lens: np.ndarray) -> int:
        """KV pages the rows of a dispatch hold, from the context lengths
        it is given (0 for a row with no request): what the decode
        kernel's page walk reads (the step record's ``kv_pages_live``)."""
        return int((-(-ctx_lens // self.ecfg.page_size)).sum())

    def _adapter_ids_for_slots(self) -> np.ndarray:
        """Per-slot LoRA adapter rows (0 = base) for a decode dispatch."""
        ids = np.zeros((self.ecfg.max_batch_slots,), dtype=np.int32)
        for req in self.decoding:
            ids[req.slot] = req.adapter_idx
        return ids

    def _tables_for(self, reqs: list[Optional[EngineRequest]]) -> np.ndarray:
        """[N, max_pages + 1] page tables with the trailing trash column;
        for a model with window layers a second such half beside it, the
        window group's (the family's forward splits the row in two)."""
        n = len(reqs)
        half = self.kv.max_pages_per_seq + 1
        windowed = self.kv.window is not None
        out = np.zeros((n, half * (2 if windowed else 1)), dtype=np.int32)
        for i, r in enumerate(reqs):
            if r is not None and r.request_id in self.kv.seqs:
                out[i, : half - 1] = self.kv.page_table_row(r.request_id)
                if windowed:
                    out[i, half: 2 * half - 1] = self.kv.window_table_row(
                        r.request_id)
        return out

    # ------------------------------------------------- overlapped pipeline

    def _bump_epoch(self) -> None:
        """Invalidate the cached decode dispatch inputs. Called wherever
        the slot→request mapping changes: slot assignment, finish,
        preemption. Page-table growth invalidates separately through
        ``kv.version`` (part of the same cache key)."""
        self._sched_epoch += 1

    def _lead(self, req: EngineRequest) -> int:
        """Tokens scheduled for ``req`` in the in-flight window but not yet
        consumed on host — the host's view of the sequence lags the device
        by this much while the pipeline is primed."""
        p = self._pending
        if (p is not None and req.state == RequestState.DECODE
                and req.request_id in p.req_ids):
            return p.k
        return 0

    def _slot_inputs(self) -> _SlotInputs:
        """Device inputs for a decode dispatch, rebuilt only when the
        scheduler epoch or a page table moved (zero steady-state host
        prep)."""
        key = (self._sched_epoch, self.kv.version)
        si = self._slot_cache
        if si is not None and si.key == key:
            return si
        b = self.ecfg.max_batch_slots
        temps = np.zeros((b,), dtype=np.float32)
        top_ps = np.ones((b,), dtype=np.float32)
        top_ks = np.zeros((b,), dtype=np.int32)
        pres = np.zeros((b,), dtype=np.float32)
        freq = np.zeros((b,), dtype=np.float32)
        seeds = np.full((b,), -1, dtype=np.int32)
        use_pen = any(r.sampling.penalized for r in self.decoding)
        use_seed = any(r.sampling.seed is not None for r in self.decoding)
        use_bias = any(r.sampling.logit_bias for r in self.decoding)
        bias = (np.zeros((b, self.cfg.vocab_size), dtype=np.float32)
                if use_bias else None)
        for req in self.decoding:
            i = req.slot
            temps[i] = req.sampling.temperature
            top_ps[i] = req.sampling.top_p
            top_ks[i] = req.sampling.top_k
            pres[i] = req.sampling.presence_penalty
            freq[i] = req.sampling.frequency_penalty
            if req.sampling.seed is not None:
                seeds[i] = req.sampling.seed & 0x7FFFFFFF
            if bias is not None:
                for tok_id, b_val in req.sampling.logit_bias:
                    bias[i, tok_id] = b_val
        si = _SlotInputs(
            key=key,
            tables=jnp.asarray(self._tables_for(self._slots)),
            adapters=jnp.asarray(self._adapter_ids_for_slots()),
            temps=jnp.asarray(temps), top_ps=jnp.asarray(top_ps),
            top_ks=jnp.asarray(top_ks), pres=jnp.asarray(pres),
            freq=jnp.asarray(freq), seeds=jnp.asarray(seeds),
            bias=jnp.asarray(bias) if bias is not None else None,
            use_pen=use_pen, use_seed=use_seed, use_bias=use_bias,
            sorts=bool(needs_sort(temps)),
        )
        self._slot_cache = si
        return si

    def _fetch_tokens(self, toks_dev: jax.Array, experts_dev=None,
                      program: str = "", passes: int = 1,
                      dispatch: Optional[dict] = None) -> np.ndarray:
        """THE decode-loop token egress. Every decode path (lagged drain,
        forced-sync, guided k=1, speculative verify) consumes its sampled
        tokens through this single point; the host copy was started
        asynchronously at dispatch time, so in the lagged pipeline this
        wait is bounded by whatever device time the host failed to hide.
        The dispatch's expert counts (``experts_dev``, five integers) come
        over in the same ``device_get``, with those of any earlier
        dispatch that fetched no token (``_experts_parked``). ``dispatch``
        is the ledger entry of the dispatch whose tokens these are."""
        with self._fetching(dispatch):
            parked, self._experts_parked = self._experts_parked, []
            if experts_dev is not None:
                parked.append((program, passes, experts_dev))
            # runbook: noqa[RBK002] — sanctioned sync: the async-egress
            # consumption point — the one token fetch in the decode loop
            # (prefill TTFT and the logprob triple keep their own fetches).
            toks, counts = jax.device_get((toks_dev, [e for _, _, e in parked]))
        for (prog, n_pass, _), c in zip(parked, counts):
            self._note_experts(prog, n_pass, c)
        return np.asarray(toks)

    def _note_experts(self, program: str, passes: int, counts) -> None:
        """Book one dispatch's expert counts (models/family.py
        ``EXPERT_COUNTS``): the engine's totals, and the record of the step
        that fetched them."""
        held, zero, absent, touched, overflow = (int(c) for c in counts)
        for key, n in (("expert_pairs_held", held), ("expert_pairs_zero", zero),
                       ("expert_pairs_absent", absent),
                       ("experts_touched", touched),
                       ("expert_overflows", overflow)):
            self.metrics[key] += n
        if self._open is not None:
            self._open.fetched_experts(program, passes, held, zero, absent,
                                       touched, overflow)

    def _drain(self, pending: _PendingDecode, overlapped: bool) -> np.ndarray:
        """Consume one decode window: fetch its tokens and emit them.

        Stop conditions fire here — one window late in the lagged pipeline
        (emit-then-truncate: a request finishing mid-window discards the
        rest of its row, and a finished request's rows in any already-issued
        overshoot window are discarded at that window's drain; the overshoot
        KV writes land in pages reclaimed on finish and are never published).
        ``overlapped`` marks emission work running while the next dispatch
        executes on device — the time the pipeline hides."""
        t0 = time.perf_counter()
        toks_host = self._fetch_tokens(pending.toks_dev, pending.experts_dev,
                                       pending.program, pending.k,
                                       pending.dispatch)
        pending.experts_dev = None  # booked once, whoever fetches again
        t_fetch = time.perf_counter()
        emitted = 0
        with self._emitting_from(pending.dispatch):
            for step_idx in range(pending.k):
                for req, slot in pending.reqs:
                    if req.state == RequestState.DECODE:
                        self._emit_token(req, int(toks_host[slot, step_idx]))
                        emitted += 1
        t_emit = time.perf_counter()
        self.metrics["decode_tokens"] += emitted
        self.metrics["decode_steps"] += pending.k
        self.metrics["decode_dispatch_time_s"] += t_fetch - t0
        self.metrics["decode_host_time_s"] += t_emit - t_fetch
        if overlapped:
            self.metrics["decode_host_overlap_s"] += t_emit - t_fetch
        self.metrics["decode_time_s"] += t_emit - t0
        self._drain_time_acc += t_emit - t0
        return toks_host

    def _drain_pending(self) -> None:
        """Synchronously settle the in-flight window (reconciliation point:
        speculation drafting, guided masks, preemption folds, context-limit
        finishes and shutdown all need the host view current first)."""
        pending, self._pending = self._pending, None
        if pending is not None:
            self._drain(pending, overlapped=False)

    # ------------------------------------------------------------ scheduling

    def _admit(self) -> None:
        free_slots = sum(s is None for s in self._slots)
        in_flight = len(self.prefilling)
        # Admission order (FCFS within a class either way; ordering by
        # arrival_time keeps re-queued preempted requests ahead of
        # same-priority newcomers): the weighted-deficit scheduler
        # interleaves classes in weight proportion — a batch flood can no
        # longer starve interactive admits, and steady interactive load
        # can no longer starve batch (sched/wdrr.py) — while the classic
        # "priority" policy keeps the strict priority-then-FCFS sort.
        if len(self.waiting) > 1:
            if self._sched is not None:
                self.waiting = self._sched.order(self.waiting)
            else:
                self.waiting.sort(key=lambda r: (-r.priority,
                                                 r.arrival_time))
        while self.waiting and (free_slots - in_flight) > 0:
            req = self.waiting[0]
            # Headroom never exceeds what the request could actually generate;
            # an otherwise-idle engine admits with zero headroom so a request
            # that only fits exactly still makes progress (preemption has
            # nothing to evict in that case anyway).
            # Remaining budget, not the full one: a preempted request that
            # already generated most of its tokens must not head-of-line
            # block admission reserving headroom it can never use.
            headroom = min(self.ecfg.admit_headroom_tokens,
                           max(req.sampling.max_new_tokens - req.num_generated, 0))
            idle = not (self.prefilling or self.decoding)
            if idle:
                headroom = 0
            if req.block_hashes is None:
                # Seeded by the LoRA adapter row: adapter KV differs for
                # the same tokens, so each adapter gets its own prefix-
                # cache namespace (base = seed 0).
                req.block_hashes = hash_blocks(req.prompt_ids,
                                               self.ecfg.page_size,
                                               seed=req.adapter_idx,
                                               lookahead=self.kv.lookahead)
            if self.kv.spill is not None:
                # Spill-tier readmit: blocks evicted from HBM but still in
                # host RAM come back as ordinary prefix pages, so the
                # probe below sees them as hits instead of re-prefilling.
                self._kv_k, self._kv_v, back = self.kv.readmit_spilled(
                    self._kv_k, self._kv_v, req.prompt_ids,
                    hashes=req.block_hashes, hash_seed=req.adapter_idx)
                if back:
                    self.metrics["kv_spill_readmits"] += back
                    self.metrics["kv_pages_imported"] += back
            ok, matched = self.kv.probe_admit(req.prompt_ids, headroom,
                                              hashes=req.block_hashes,
                                              hash_seed=req.adapter_idx)
            if not ok:
                if idle:
                    # Idle engine, zero headroom, retired prefix pages count
                    # as free — if it still doesn't fit, no future release
                    # can ever make it fit. Fail it rather than spinning
                    # has_work forever (liveness: surfaced by the priority
                    # preemption test, but reachable by any oversized
                    # prompt or a recompute cycle whose folded prompt
                    # outgrew the pool).
                    self.waiting.pop(0)
                    req.state = RequestState.FAILED
                    req.finish_reason = FinishReason.ABORTED
                    self._observe_finish(req)
                    self.finished.append(req)
                    if req.done_event is not None:
                        req.done_event.set()
                    continue
                break
            self.waiting.pop(0)
            if self._sched is not None:
                # Advance the class's stride pass for the ACTUAL admission
                # (ordering alone never charges a class).
                self._sched.commit(req.priority)
            # Reuse resident pages for the shared prompt prefix (same system
            # prompt across agent iterations): prefill resumes at the first
            # novel token.
            cached = self.kv.add_sequence(req.request_id, req.prompt_ids,
                                          hashes=req.block_hashes,
                                          matched=matched,
                                          hash_seed=req.adapter_idx)
            if self._state is not None:
                self._admit_state(req)
            req.state = RequestState.PREFILL
            req.prefill_pos = cached
            cls = class_label(req.priority)
            if not req.folded_out_ids:
                # First admission only: a preempted request re-matching
                # its OWN published pages is recompute avoidance, not a
                # prompt-cache hit the client should be billed less for.
                req.cached_tokens = cached
                req.t_admitted = time.perf_counter()
                wait_s = req.t_admitted - req.arrival_time
                self.hist_queue_wait.observe(wait_s)
                self.hist_class_queue_wait.labels(cls=cls).observe(wait_s)
                if self.flight.enabled:
                    self._admitted_log.append(
                        [req.request_id, wait_s, len(req.prompt_ids), cached])
            self._m_class_admits.labels(cls=cls).inc()
            self.metrics["cached_prefix_tokens"] += cached
            self.prefilling.append(req)
            in_flight += 1
            if self.tracer.enabled:
                meta = {"request": req.request_id, "cached_tokens": cached,
                        "cls": class_name(req.priority),
                        "queue_ms": round((time.perf_counter()
                                           - req.arrival_time) * 1e3, 3)}
                if self.replica_idx is not None:
                    meta["replica"] = self.replica_idx
                if req.trace_id is not None:
                    meta["trace_id"] = req.trace_id
                self.tracer.event("engine.admit", **meta)

    def _admit_state(self, req: EngineRequest) -> None:
        """Give an admitted sequence its slot of the state pool — the
        batch slot it will decode in, held from here on, since prefill
        already runs from and into it — and start it: from the snapshot
        its prefix hit was granted with, or from zero."""
        held = {r.state_slot for r in self.prefilling}
        req.state_slot = next(i for i, s in enumerate(self._slots)
                              if s is None and i not in held)
        src = self.kv.seqs[req.request_id].restore_from
        with annotate("engine.state_restore"):
            self._state = _state_admit(
                self._state, self._snaps, jnp.int32(req.state_slot),
                jnp.int32(-1 if src is None else src))
        self._sync_state_counters()

    def _snapshot_state(self, req: EngineRequest, new_ctx: int) -> None:
        """A prefill chunk of ``req`` was just issued and ends at
        ``new_ctx``: on a page boundary, keep the state it leaves behind,
        tied to the hash of the page that ends there (issued after the
        chunk's program, so the device copies what the chunk wrote)."""
        if self._state is None or new_ctx % self.ecfg.page_size:
            return
        dst = self.kv.take_snapshot(req.request_id, req.prompt_ids[:new_ctx],
                                    hashes=req.block_hashes)
        if dst is not None:
            with annotate("engine.state_snapshot"):
                self._snaps = _state_snapshot(
                    self._snaps, self._state, jnp.int32(dst),
                    jnp.int32(req.state_slot))
        self._sync_state_counters()

    def _sync_state_counters(self) -> None:
        for name, count in self.kv.snapshots.counters.items():
            self.metrics["state_" + name] = count

    def _state_rows(self, reqs, n: int) -> Optional[jax.Array]:
        """The state-pool slot of each of ``n`` rows (``reqs`` first, then
        pads, which get a slot out of range: read as any, never written)."""
        if self._state is None:
            return None
        rows = np.full((n,), self.ecfg.max_batch_slots, dtype=np.int32)
        for i, r in enumerate(reqs):
            if r is not None:
                rows[i] = r.slot if r.slot is not None else r.state_slot
        return jnp.asarray(rows)

    def _keep_state(self, results: tuple) -> tuple:
        """A step program's results less the state pool it hands back last
        (a model with one), which becomes the engine's."""
        if self._state is None:
            return results
        *rest, self._state = results
        return tuple(rest)

    def _keep_drafts(self, results: tuple) -> tuple:
        """A step program's results less the module's drafts, by slot,
        which it hands back last (a model that drafts for itself)."""
        if not self._mtp:
            return results
        *rest, self._draft_toks = results
        return tuple(rest)

    def _next_tokens(self, req: EngineRequest, lo: int, hi: int) -> list[int]:
        """The prompt's token AFTER each position ``lo .. hi - 1`` (0 past
        its end: the first sampled token, which the step program or
        ``_module_step`` puts there)."""
        nxt = req.prompt_ids[lo + 1: hi + 1]
        return nxt + [0] * (hi - lo - len(nxt))

    def _free_slot(self, req: EngineRequest) -> int:
        """The batch slot a request that finished its prompt decodes in:
        the one its state is in, else the lowest free."""
        if req.state_slot is not None:
            return req.state_slot
        return self._slots.index(None)

    @staticmethod
    def _fold_into_prompt(req: EngineRequest, prefill_pos: int) -> None:
        """Fold generated tokens into the prompt. They move to
        folded_out_ids (not out_ids) so ctx_len never double-counts them
        and the output/budget accounting still sees every generated token.
        ``prefill_pos`` says how much of the new prompt already has K/V in
        the pool (0 for preemption-recompute; the written length for the
        grammar fast-forward, which keeps its pages)."""
        req.prompt_ids = req.prompt_ids + req.out_ids
        req.folded_out_ids = req.folded_out_ids + req.out_ids
        req.out_ids = []
        req.block_hashes = None
        req.prefill_pos = prefill_pos

    def _preempt_youngest(self) -> bool:
        """Evict the lowest-priority, most recently arrived decoding
        request (recompute on re-admission)."""
        if not self.decoding:
            return False
        # Folding generated tokens into the prompt needs the host view
        # complete: settle the in-flight lagged window before choosing a
        # victim (the drained tokens may even finish someone and free the
        # pages this preemption was about to chase).
        self._drain_pending()
        if not self.decoding:
            return False
        victim = max(self.decoding,
                     key=lambda r: (-r.priority, r.arrival_time))
        self.decoding.remove(victim)
        if victim.slot is not None:
            self._slots[victim.slot] = None
            victim.slot = None
        victim.state_slot = None  # re-admission restores or recomputes
        # Publish the victim's full pages before freeing: re-admission will
        # match its own prefix and recompute only the tail.
        self.kv.release(victim.request_id, token_ids=self._kv_valid_tokens(victim))
        if self.draft is not None:
            self.draft.release(victim.request_id)
        self._fold_into_prompt(victim, prefill_pos=0)
        victim.state = RequestState.WAITING
        self.waiting.insert(0, victim)
        victim.preemptions += 1
        self.metrics["preemptions"] += 1
        self._bump_epoch()
        return True

    def _kv_valid_tokens(self, req: EngineRequest) -> list[int]:
        """Tokens whose K/V has actually been written to the pool.

        Prefilled prompt tokens plus every generated token that was fed back
        (all but the last emitted one — its KV write happens on the *next*
        decode dispatch, which never runs for a finishing sequence).
        """
        valid = req.prompt_ids[: req.prefill_pos]
        if req.out_ids:
            valid = valid + req.out_ids[:-1]
        return valid

    def _observe_finish(self, req: EngineRequest) -> None:
        """Latency histograms + trace correlation for a finishing request.

        Idempotent via ``finish_time``: force_finish re-runs the cleanup of
        a partially-finished request after an abort crash, and one request
        must never observe twice."""
        if req.finish_time is not None:
            return
        now = time.perf_counter()
        req.finish_time = now
        self.hist_e2e.observe(now - req.arrival_time)
        if req.first_token_time is not None and req.num_generated > 1:
            self.hist_tpot.observe((now - req.first_token_time)
                                   / (req.num_generated - 1))
        if self.flight.enabled or self.tracer.enabled:
            self._retire(req, now)
        if self.workload_tap is not None:
            # Workload fingerprinting (obs/): sample the finished request.
            # Best-effort — observation must never fail a request.
            try:
                self.workload_tap(req)
            except Exception:  # noqa: BLE001 — observer errors stay silent
                pass

    def _retire(self, req: EngineRequest, now: float) -> None:
        """The request's lifecycle record (``LIFECYCLE_FIELDS``), built
        once: the next step record's ``finished`` gets it, and the
        tracer's ``engine.request`` line — which ties the engine's view
        back to the server's x-request-id (``trace_id``) — is written
        from it, so the two cannot disagree."""
        life = {
            "id": req.request_id, "trace_id": req.trace_id,
            "t_received": (req.arrival_time if req.t_received is None
                           else req.t_received),
            "t_enqueued": req.t_enqueued, "t_admitted": req.t_admitted,
            "t_first_token": req.first_token_time,
            "t_first_write": req.t_first_write, "t_finished": now,
            "prompt_tokens": len(req.prompt_ids) - len(req.folded_out_ids),
            "cached_tokens": req.cached_tokens,
            "generated": req.num_generated,
            "preemptions": req.preemptions,
            "reason": req.finish_reason.value if req.finish_reason else None,
            "max_emit_gap_s": req.max_emit_gap_s,
        }
        if self.flight.enabled:
            ledger, mark = req.rode_mark or (None, None)
            # (a ring reset since the first token took the mark's sums)
            life["rode"] = (ledger.rode(mark, now, req.rode_tokens)
                            if ledger is self.flight.dispatches else None)
        req.lifecycle = life
        # The handler thread may have flushed the first chunk between the
        # read above and the assignment (EngineRequest.mark_first_write).
        life["t_first_write"] = req.t_first_write
        if self.flight.enabled:
            self._finished_log.append(life)
        if self.tracer.enabled:
            meta = {"request": req.request_id,
                    **{k: v for k, v in life.items()
                       if k != "id" and v is not None}}
            if self.replica_idx is not None:
                meta["replica"] = self.replica_idx
            if req.ttft_ms is not None:
                meta["ttft_ms"] = round(req.ttft_ms, 3)
            self.tracer.event("engine.request", **meta)

    def _finish(self, req: EngineRequest, reason: FinishReason) -> None:
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        self._observe_finish(req)
        self._bump_epoch()
        if req.slot is not None:
            self._slots[req.slot] = None
            req.slot = None
        req.state_slot = None
        if req in self.decoding:
            self.decoding.remove(req)
        if req in self.prefilling:
            self.prefilling.remove(req)
        self.kv.release(req.request_id, token_ids=self._kv_valid_tokens(req))
        if self.draft is not None:
            self.draft.release(req.request_id)
        self._last_token.pop(req.request_id, None)
        self.finished.append(req)
        if req.done_event is not None:
            req.done_event.set()

    def force_finish(self, req: EngineRequest) -> None:
        """Best-effort finish for crash recovery: every cleanup step runs
        independently (pool removal, slot, KV pages, last-token map), so a
        corrupted core still ends with the request out of the live pools
        and its awaiter unblocked. Normal paths use :meth:`_finish`."""
        for pool in (self.waiting, self.prefilling, self.decoding):
            if req in pool:
                pool.remove(req)
        self._bump_epoch()
        if req.slot is not None and req.slot < len(self._slots):
            self._slots[req.slot] = None
            req.slot = None
        req.state_slot = None
        try:
            if req.request_id in self.kv.seqs:
                self.kv.release(req.request_id,
                                token_ids=self._kv_valid_tokens(req))
        except Exception:  # noqa: BLE001 — release itself may be poisoned
            pass
        self._last_token.pop(req.request_id, None)
        req.state = RequestState.FINISHED
        req.finish_reason = req.finish_reason or FinishReason.ABORTED
        try:
            self._observe_finish(req)
        except Exception:  # noqa: BLE001 — metrics must not block recovery
            pass
        if req not in self.finished:
            self.finished.append(req)
        if req.done_event is not None:
            req.done_event.set()

    def abort(self, request_id: str) -> bool:
        """Abort a live request (streaming consumer went away): frees its
        batch slot and KV pages immediately so concurrent requests are not
        starved by a generation nobody is draining. Returns False when the
        request is unknown or already finished."""
        for pool in (self.waiting, self.prefilling, self.decoding):
            for req in pool:
                if req.request_id == request_id:
                    if req in self.waiting:
                        self.waiting.remove(req)
                        req.state = RequestState.FINISHED
                        req.finish_reason = FinishReason.ABORTED
                        self._observe_finish(req)
                        self.finished.append(req)
                        if req.done_event is not None:
                            req.done_event.set()
                    else:
                        self._finish(req, FinishReason.ABORTED)
                    return True
        return False

    # --------------------------------------------------------------- prefill

    def _run_prefill(self) -> None:
        """One BATCHED prefill dispatch: chunks for up to ``prefill_batch``
        sequences in a single forward. Serializing prefill one sequence per
        step made TTFT degrade linearly under concurrent submissions
        (VERDICT r1 weak #5); batching restores near-constant TTFT while the
        per-row chunking still bounds dispatch latency for decode overlap.
        """
        t0 = time.perf_counter()
        # Row selection grows the page tables (and may preempt, whose drain
        # books its own fetch and emit): build, like the arrays below.
        with self._span("build"):
            rows: list[tuple[EngineRequest, int, int]] = []  # (req, chunk, new_ctx)
            for req in list(self.prefilling[: max(1, self.ecfg.prefill_batch)]):
                chunk_len = min(self.ecfg.prefill_chunk,
                                len(req.prompt_ids) - req.prefill_pos)
                new_ctx = req.prefill_pos + chunk_len
                if self.kv.spill is not None:
                    # Capture the retired pages this extension would evict into
                    # the host spill tier BEFORE they are recycled (the one
                    # point evicted bytes are still addressable).
                    alloc = self.kv.seqs.get(req.request_id)
                    need = (alloc.pages_needed(new_ctx, self.ecfg.page_size)
                            if alloc is not None else 0)
                    if need:
                        self.kv.spill_evictable(self._kv_k, self._kv_v, need)
                try:
                    self.kv.extend(req.request_id, new_ctx)
                except MemoryError:
                    if rows:
                        # Run what fits; this request retries next step. Keep
                        # scanning — a later request's (smaller) extension may
                        # still fit this dispatch (ADVICE r2: breaking here
                        # head-of-line blocked the rest of the batch). Liveness:
                        # the HEAD request always fails with rows empty (FIFO
                        # scan), taking the preempt/abort path below — and a
                        # skipped request reaches the head in bounded steps as
                        # earlier rows finish, so no request starves.
                        continue
                    if self._preempt_youngest():
                        return  # retry next step
                    self.prefilling.remove(req)
                    self._finish(req, FinishReason.ABORTED)
                    return
                rows.append((req, chunk_len, new_ctx))
        if not rows:
            return
        self._note_window_chunks(rows)

        # Pad the row count to a power of two so the compile count stays
        # O(log prefill_batch); pad rows write to the null page and attend
        # over one masked key (ctx 1 avoids an all-masked softmax).
        b = 1
        while b < len(rows):
            b *= 2
        with self._span("build"):
            t = self.ecfg.prefill_chunk
            tokens = np.zeros((b, t), dtype=np.int32)
            positions = np.full((b, t), self._trash_pos(), dtype=np.int32)
            ctx_lens = np.ones((b,), dtype=np.int32)
            last_idx = np.zeros((b,), dtype=np.int32)
            adapter_ids = np.zeros((b,), dtype=np.int32)
            tables = self._tables_for([r for r, _, _ in rows] +
                                      [None] * (b - len(rows)))
            next_tokens = np.zeros((b, t), dtype=np.int32) if self._mtp else None
            for i, (req, chunk_len, new_ctx) in enumerate(rows):
                tokens[i, :chunk_len] = req.prompt_ids[req.prefill_pos:new_ctx]
                positions[i, :chunk_len] = np.arange(req.prefill_pos, new_ctx)
                ctx_lens[i] = new_ctx
                last_idx[i] = chunk_len - 1
                adapter_ids[i] = req.adapter_idx
                if self._mtp:
                    next_tokens[i, :chunk_len] = self._next_tokens(
                        req, req.prefill_pos, new_ctx)
            tables, ctx_dev = jnp.asarray(tables), jnp.asarray(ctx_lens)

        pf_meta: dict[str, Any] = {"batch": len(rows),
                                   "tokens": int(sum(c for _, c, _ in rows))}
        if self.tracer.enabled:
            # Request attribution for `runbook timeline`: which sequences'
            # chunks rode this dispatch (built only when tracing is on).
            pf_meta["requests"] = [r.request_id for r, _, _ in rows]
        dispatch = self._dispatching("_prefill_step",
                                     prefill_tokens=pf_meta["tokens"])
        with self.tracer.span("engine.prefill", **pf_meta), \
                annotate("prefill", **_dispatch_stat(dispatch)), \
                self._span("issue"):
            results = self._keep_state(_prefill_step(
                self.params, self.cfg, jnp.asarray(tokens), self._kv_k, self._kv_v,
                jnp.asarray(positions), tables, ctx_dev, jnp.asarray(last_idx),
                jnp.asarray(adapter_ids),
                page_size=self.ecfg.page_size, block_pages=self.ecfg.block_pages,
                attn_impl=self.ecfg.attn_impl, mesh=self.mesh,
                qmm_impl=self.ecfg.qmm_impl, state=self._state,
                state_rows=self._state_rows([r for r, _, _ in rows], b),
                next_tokens=(jnp.asarray(next_tokens) if self._mtp else None),
            ))
            # (a model that drafts for itself: each row's last hidden state)
            last_logits, self._kv_k, self._kv_v, experts, *last_hidden = results
        if dispatch is not None:
            # A chunk short of every prompt's end emits nothing and is
            # waited on by a later fetch: the ledger holds one element of
            # its result until then, not ``[rows, vocab]``.
            emits = any(new_ctx >= len(req.prompt_ids) for req, _, new_ctx in rows)
            self._issued(dispatch, last_logits if emits else last_logits[:1, :1],
                         emits)
        for req, _, new_ctx in rows:
            self._snapshot_state(req, new_ctx)
        if experts is not None:
            self._experts_parked.append(("_prefill_step", 1, experts))

        done_rows: list[tuple[int, EngineRequest]] = []
        self.metrics["prefill_steps"] += 1
        for i, (req, chunk_len, new_ctx) in enumerate(rows):
            req.prefill_pos = new_ctx
            self.metrics["prefill_tokens"] += chunk_len
            if req.prefill_pos >= len(req.prompt_ids):
                done_rows.append((i, req))

        if done_rows:
            # Slot assignment FIRST: penalized rows need their count row
            # prepared before the first sampled token, and the gather
            # below maps prefill rows to slots. Counts track GENERATED
            # tokens only (OpenAI's c[j] counts previously *sampled*
            # tokens — prompt content is never penalized): fresh
            # assignments batch-zero their rows in one dispatch;
            # re-admissions after preemption restore the generated-so-far
            # histogram (rare path, per-request).
            fresh_pen_rows = np.zeros((self.ecfg.max_batch_slots,),
                                      dtype=bool)
            for i, req in done_rows:
                # Publish the prompt's full pages so concurrent/following
                # requests with the same prefix skip their prefill.
                self.kv.register_prefix(req.request_id, req.prompt_ids,
                                        hashes=req.block_hashes)
                self.prefilling.remove(req)
                slot = self._free_slot(req)
                self._slots[slot] = req
                req.slot = slot
                req.state = RequestState.DECODE
                self.decoding.append(req)
                if req.sampling.penalized:
                    if req.all_out_ids:
                        self._seed_counts_for(req)
                    else:
                        fresh_pen_rows[slot] = True
            self._bump_epoch()  # slot→request mapping changed
            if fresh_pen_rows.any():
                self._tok_counts = _reset_count_rows(
                    self._tok_counts, jnp.asarray(fresh_pen_rows))

            # Sample every completed row's first output token in ONE batched
            # dispatch + sync (per-row sampling would re-serialize the TTFT
            # win for short prompts finishing together).
            with self._span("build"):
                temps = np.zeros((b,), dtype=np.float32)
                top_ps = np.ones((b,), dtype=np.float32)
                top_ks = np.zeros((b,), dtype=np.int32)
                need_mask = False
                mask = np.ones((b, self.cfg.vocab_size), dtype=bool)
                use_pen = any(req.sampling.penalized for _, req in done_rows)
                use_seed = any(req.sampling.seed is not None
                               for _, req in done_rows)
                use_bias = any(req.sampling.logit_bias for _, req in done_rows)
                pres = np.zeros((b,), dtype=np.float32)
                freq = np.zeros((b,), dtype=np.float32)
                seeds = np.full((b,), -1, dtype=np.int32)
                bias = (np.zeros((b, self.cfg.vocab_size), dtype=np.float32)
                        if use_bias else None)
                slot_map = np.zeros((b,), dtype=np.int32)
                for i, req in done_rows:
                    temps[i] = req.sampling.temperature
                    top_ps[i] = req.sampling.top_p
                    top_ks[i] = req.sampling.top_k
                    pres[i] = req.sampling.presence_penalty
                    freq[i] = req.sampling.frequency_penalty
                    slot_map[i] = req.slot
                    if req.sampling.seed is not None:
                        seeds[i] = req.sampling.seed & 0x7FFFFFFF
                    if bias is not None:
                        for tok_id, b_val in req.sampling.logit_bias:
                            bias[i, tok_id] = b_val
                    if self.mask_fn and req.sampling.guided:
                        m = self.mask_fn(req)
                        if m is not None:
                            _set_mask_row(mask, i, m)
                            need_mask = True
                counts_rows = (jnp.take(self._tok_counts,
                                        jnp.asarray(slot_map), axis=0)
                               if use_pen else None)
                self._key, sub = jax.random.split(self._key)
                self._sampling(1, needs_sort(temps))
                toks = sample_tokens(
                    last_logits, sub, jnp.asarray(temps), jnp.asarray(top_ps),
                    jnp.asarray(mask) if need_mask else None,
                    jnp.asarray(top_ks),
                    counts=counts_rows,
                    presence=jnp.asarray(pres) if use_pen else None,
                    frequency=jnp.asarray(freq) if use_pen else None,
                    seeds=jnp.asarray(seeds) if use_seed else None,
                    positions=jnp.asarray(ctx_lens) if use_seed else None,
                    bias=jnp.asarray(bias) if use_bias else None,
                )
                # Wire the first tokens into the device-resident decode feed
                # before fetching them: row i scatters to its slot, pad rows
                # scatter out of bounds and drop (fixed shape per prefill
                # width, so no extra compile per batch composition).
                feed_idx = np.full((b,), self.ecfg.max_batch_slots,
                                   dtype=np.int32)
                for i, req in done_rows:
                    feed_idx[i] = req.slot
                self._feed_toks = self._feed_toks.at[jnp.asarray(feed_idx)].set(
                    toks, mode="drop")
                if self._mtp:
                    # The module's row at each prompt's last position, now
                    # that the token after it exists, and the first draft.
                    at = np.full((b,), self._trash_pos(), dtype=np.int32)
                    for i, req in done_rows:
                        at[i] = len(req.prompt_ids) - 1
                    drafts, self._kv_k, self._kv_v, module_experts = _module_step(
                        self.params, self.cfg, last_hidden[0], toks,
                        jnp.asarray(at), self._kv_k, self._kv_v, tables, ctx_dev,
                        page_size=self.ecfg.page_size,
                        block_pages=self.ecfg.block_pages)
                    self._draft_toks = self._draft_toks.at[
                        jnp.asarray(feed_idx)].set(drafts, mode="drop")
                    self._experts_parked.append(("_module_step", 1, module_experts))
            with self._fetching(dispatch):
                # runbook: noqa[RBK002] — sanctioned sync: the one batched
                # first-token fetch per prefill dispatch (TTFT emission point).
                toks_host = np.asarray(jax.device_get(toks))
            lp_pairs = [(i, req) for i, req in done_rows
                        if req.sampling.logprobs]
            if lp_pairs:
                self._append_logprob_entries(
                    lp_pairs, toks_host, _token_logprobs(last_logits, toks))
            if use_pen:
                # ONE batched scatter for every penalized first token —
                # per-request bumps would re-serialize the TTFT win the
                # batched sampling above exists for.
                live = np.zeros((b,), dtype=np.int32)
                for i, req in done_rows:
                    if req.sampling.penalized:
                        live[i] = 1
                self._tok_counts = _bump_counts_batch(
                    self._tok_counts, jnp.asarray(slot_map),
                    jnp.asarray(toks_host.astype(np.int32)),
                    jnp.asarray(live))
            with self._emitting_from(dispatch):
                for i, req in done_rows:
                    self._first_token(req, int(toks_host[i]))
        self.metrics["prefill_time_s"] += time.perf_counter() - t0

    def _seed_counts_for(self, req: EngineRequest,
                         slot: Optional[int] = None) -> None:
        """Restore the request's slot row to its GENERATED-token histogram
        (OpenAI penalties count sampled tokens, never the prompt); ids pad
        to powers of two so compile count stays O(log len). ``slot``
        overrides ``req.slot`` for the mixed dispatch, which prepares the
        row BEFORE the in-dispatch first-token sampling assigns it."""
        ids = req.all_out_ids
        n = max(1, len(ids))
        padded_len = 1
        while padded_len < n:
            padded_len *= 2
        padded = np.zeros((padded_len,), dtype=np.int32)
        padded[: len(ids)] = ids
        self._tok_counts = _seed_count_row(
            self._tok_counts,
            jnp.int32(req.slot if slot is None else slot),
            jnp.asarray(padded), jnp.int32(len(ids)))

    # ---------------------------------------------------------------- decode

    @staticmethod
    def _append_logprob_entries(pairs, toks_h, scored) -> None:
        """Attach one {token_id, logprob, top} record per (row, request)
        pair from a scored batch (single host fetch for the triple)."""
        # runbook: noqa[RBK002] — sanctioned sync: one [B, K+1] fetch per
        # dispatch for logprob requests (full-vocab rows would dwarf it).
        chosen, top_ids, top_lp = jax.device_get(scored)
        chosen, top_ids, top_lp = (np.asarray(chosen), np.asarray(top_ids),
                                   np.asarray(top_lp))
        for i, req in pairs:
            n = min(req.sampling.logprobs, _TOPK_LOGPROBS)
            req.out_logprobs.append({
                "token_id": int(toks_h[i]),
                "logprob": float(chosen[i]),
                "top": [(int(t), float(p))
                        for t, p in zip(top_ids[i, :n], top_lp[i, :n])],
            })

    def _score_logprobs(self, last_logits, toks, toks_h, reqs) -> None:
        """Top-K logprobs for requests that asked (k==1 dispatches only —
        _pick_k forces that). Raw model distribution, pre-mask. ``reqs``
        is the dispatch-time snapshot: a request finishing on this very
        token must still get the token's entry."""
        pairs = [(slot, r) for r, slot in reqs if r.sampling.logprobs]
        if not pairs:
            return
        self._append_logprob_entries(pairs, toks_h,
                                     _token_logprobs(last_logits, toks))

    def _emit_token(self, req: EngineRequest, token: int) -> None:
        """Record a sampled token and apply finish rules."""
        if self._open is not None:
            now = time.monotonic()
            if (req.last_emit_time is not None
                    and now - req.last_emit_time > req.max_emit_gap_s):
                req.max_emit_gap_s = now - req.last_emit_time
            req.last_emit_time = now
        dispatch = self._emitting
        if dispatch is not None:
            dispatch["tokens"] += 1
            # (the token AT t_first_token opens the interval ``rode`` is of)
            if req.rode_tokens is not None and req.num_generated:
                program = dispatch["program"]
                req.rode_tokens[program] = req.rode_tokens.get(program, 0) + 1
        req.out_ids.append(token)
        if req.on_token is not None:
            req.on_token(token)
        self._last_token[req.request_id] = token
        grammar_done = False
        if self.advance_fn and req.sampling.guided:
            grammar_done = self.advance_fn(req, token)
        stop_ids = set(req.sampling.stop_token_ids) | {self.tokenizer.eos_id, self.tokenizer.eot_id}
        if token in stop_ids:
            self._finish(req, FinishReason.STOP_TOKEN)
        elif grammar_done:
            self._finish(req, FinishReason.GRAMMAR_END)
        elif req.num_generated >= req.sampling.max_new_tokens:
            self._finish(req, FinishReason.MAX_TOKENS)
        elif req.sampling.stop_strings:
            # Tail-only slices: all_out_ids would copy O(N) per emitted token.
            tail = self.tokenizer.decode(
                (req.folded_out_ids[-32:] + req.out_ids[-32:])[-32:])
            if any(s in tail for s in req.sampling.stop_strings):
                self._finish(req, FinishReason.STOP_STRING)

    def _pick_k(self, per_step: int = 1) -> int:
        """Decode steps per dispatch: 1 when any guided request needs
        per-token masks, else the largest power of two ≤ config that fits
        every sequence's remaining max_seq headroom, a step taking up to
        ``per_step`` positions of it (a speculative round: two) — 0 where
        not even one such step fits."""
        if any(r.sampling.forced_sync for r in self.decoding):
            return 1
        k = max(1, self.ecfg.decode_steps_per_dispatch)
        # Scheduled (lead-adjusted) lengths: the in-flight window's tokens
        # occupy context the host hasn't consumed yet.
        remaining = min(self.ecfg.max_seq_len - (r.ctx_len + self._lead(r))
                        for r in self.decoding)
        while k > 1 and (k * per_step > remaining):
            k //= 2
        if k * per_step > remaining:
            return 0
        # power-of-two clamp bounds distinct compiled programs
        p = 1
        while p * 2 <= k:
            p *= 2
        return p

    def _draft_for(self, req: EngineRequest, max_draft: int) -> list[int]:
        """Prompt-lookup draft: tokens that followed the most recent earlier
        occurrence of the sequence's trailing n-gram (vectorized search)."""
        n = self.ecfg.spec_ngram
        hist = req.prompt_ids[: req.prefill_pos] + req.out_ids
        if max_draft < 1 or len(hist) <= n:
            return []
        # Cap the lookback so per-dispatch host cost stays bounded on long
        # agent contexts; recent repeats dominate acceptance anyway.
        arr = np.asarray(hist[-2048:], dtype=np.int64)
        tail = arr[-n:]
        windows = np.lib.stride_tricks.sliding_window_view(arr[:-1], n)
        hits = np.nonzero((windows == tail).all(axis=1))[0]
        if hits.size == 0:
            return []
        start = int(hits[-1]) + n
        return arr[start : start + max_draft].tolist()

    def _grow_pages_for_decode(self, k: int) -> None:
        """Ensure every decoding sequence has pages for its scheduled
        context (ctx + in-flight lead) + k tokens, preempting the youngest
        (or aborting) under pool pressure. Preemption drains the lagged
        window first (the fold needs the host view complete), so the
        lead — and each target — may legitimately shrink mid-loop."""
        for req in list(self.decoding):
            while (
                req.state == RequestState.DECODE
                and not self.kv.can_extend(
                    req.request_id, req.ctx_len + self._lead(req) + k)
            ):
                # _preempt_youngest may evict ``req`` itself — the state guard
                # above then exits the loop. Its internal drain may even
                # FINISH ``req`` (a stop was sitting in the lagged window),
                # so re-check before declaring the pool unfixable.
                if not self._preempt_youngest():
                    if req.state == RequestState.DECODE:
                        self._finish(req, FinishReason.ABORTED)
                    break
            if req.state == RequestState.DECODE and req.request_id in self.kv.seqs:
                # Growth invalidates the cached dispatch tables by itself:
                # kv.version is part of the _SlotInputs cache key.
                self.kv.extend(req.request_id,
                               req.ctx_len + self._lead(req) + k)

    def _run_decode_spec(self, drafts: dict[str, list[int]], k: int) -> None:
        """Speculative dispatch: feed [last, draft...] as one T=k chunk and
        accept the agreeing prefix."""
        t0 = time.perf_counter()
        with self._span("build"):
            self._grow_pages_for_decode(k)
        if not self.decoding:
            return

        b = self.ecfg.max_batch_slots
        with self._span("build"):
            tokens = np.zeros((b, k), dtype=np.int32)
            positions = np.zeros((b, k), dtype=np.int32)
            ctx_lens = np.zeros((b,), dtype=np.int32)
            feeds: dict[str, list[int]] = {}
            for req in self.decoding:
                i = req.slot
                draft = drafts.get(req.request_id, [])[: k - 1]
                feed = [self._last_token[req.request_id]] + draft
                feed = feed + [feed[-1]] * (k - len(feed))  # pad rows to T=k
                feeds[req.request_id] = feed
                tokens[i] = feed
                positions[i] = np.arange(req.ctx_len - 1, req.ctx_len - 1 + k)
                ctx_lens[i] = req.ctx_len + k - 1  # keys written for all fed tokens
                self.metrics["spec_drafted"] += len(draft)
            si = self._slot_inputs()

        spec_meta: dict[str, Any] = {"k": k, "batch": len(self.decoding)}
        if self.tracer.enabled:
            spec_meta["requests"] = [r.request_id for r in self.decoding]
        dispatch = self._dispatching("_decode_spec", k, len(self.decoding),
                                     ctx_lens)
        with self.tracer.span("engine.decode_spec", **spec_meta), \
                annotate("decode_spec", **_dispatch_stat(dispatch)), \
                self._span("issue"):
            t_issue = time.perf_counter()
            toks, self._kv_k, self._kv_v, experts = _decode_spec(
                self.params, self.cfg, jnp.asarray(tokens), jnp.asarray(positions),
                self._kv_k, self._kv_v, si.tables, jnp.asarray(ctx_lens),
                si.adapters,
                page_size=self.ecfg.page_size, block_pages=self.ecfg.block_pages,
                attn_impl=self.ecfg.attn_impl, mesh=self.mesh,
                qmm_impl=self.ecfg.qmm_impl,
            )
            self._issued(dispatch, toks)
            toks_host = self._fetch_tokens(toks, experts, "_decode_spec",
                                           dispatch=dispatch)  # [B, k]
            t_fetch = time.perf_counter()

        emitted = 0
        with self._emitting_from(dispatch):
            for req in list(self.decoding):
                i = req.slot
                feed = feeds[req.request_id]
                draft = drafts.get(req.request_id, [])[: k - 1]
                self._emit_token(req, int(toks_host[i, 0]))
                emitted += 1
                j = 1
                while (req.state == RequestState.DECODE and j <= len(draft)
                       and feed[j] == int(toks_host[i, j - 1])):
                    self._emit_token(req, int(toks_host[i, j]))
                    emitted += 1
                    self.metrics["spec_accepted"] += 1
                    j += 1
        # Re-arm the device-resident feed with each survivor's last
        # accepted token (the verify argmax buffer's last column is not the
        # accepted tail); pad rows scatter out of bounds and drop.
        feed_idx = np.full((b,), b, dtype=np.int32)
        feed_val = np.zeros((b,), dtype=np.int32)
        for req in self.decoding:
            feed_idx[req.slot] = req.slot
            feed_val[req.slot] = self._last_token[req.request_id]
        self._feed_toks = self._feed_toks.at[jnp.asarray(feed_idx)].set(
            jnp.asarray(feed_val), mode="drop")
        t_end = time.perf_counter()
        self.metrics["decode_tokens"] += emitted
        self.metrics["decode_steps"] += 1
        self.metrics["decode_dispatches"] += 1
        self.metrics["decode_dispatch_time_s"] += t_fetch - t_issue
        self.metrics["decode_host_time_s"] += (
            (t_issue - t0) + (t_end - t_fetch))
        self.metrics["decode_time_s"] += t_end - t0

    def _run_spec_rounds(self, rounds: int) -> None:
        """``rounds`` speculative rounds in ONE dispatch, drafted by the
        model's own prediction module on the device, with one fetch: a row
        comes back with ``rounds`` to ``2 * rounds`` tokens. The host view
        is current (the caller drained the window), pages are reserved for
        the most a row can take, and what a row's stop leaves over is
        dropped, as a multi-step window's is."""
        t0 = time.perf_counter()
        with self._span("build"):
            self._grow_pages_for_decode(2 * rounds)
        if not self.decoding:
            return
        b = self.ecfg.max_batch_slots
        with self._span("build"):
            positions = np.zeros((b,), dtype=np.int32)
            ctx_lens = np.zeros((b,), dtype=np.int32)
            for req in self.decoding:
                positions[req.slot] = req.ctx_len - 1
                ctx_lens[req.slot] = req.ctx_len
            si = self._slot_inputs()
        rows = list(self.decoding)
        spec_meta: dict[str, Any] = {"k": rounds, "batch": len(rows)}
        if self.tracer.enabled:
            spec_meta["requests"] = [r.request_id for r in rows]
        dispatch = self._dispatching("_decode_spec", rounds, len(rows),
                                     ctx_lens)
        with self.tracer.span("engine.decode_spec", **spec_meta), \
                annotate("decode_spec", **_dispatch_stat(dispatch)), \
                self._span("issue"):
            t_issue = time.perf_counter()
            (out, self._kv_k, self._kv_v, experts, self._feed_toks,
             self._draft_toks) = _decode_spec(
                self.params, self.cfg, self._feed_toks, jnp.asarray(positions),
                self._kv_k, self._kv_v, si.tables, jnp.asarray(ctx_lens),
                si.adapters,
                page_size=self.ecfg.page_size, block_pages=self.ecfg.block_pages,
                attn_impl=self.ecfg.attn_impl, mesh=self.mesh,
                qmm_impl=self.ecfg.qmm_impl, drafts=self._draft_toks,
                rounds=rounds,
            )
            self._issued(dispatch, out)
            out_host = self._fetch_tokens(out, experts, "_decode_spec",
                                          rounds, dispatch)  # [B, rounds, 3]
            t_fetch = time.perf_counter()

        drafted = accepted = 0
        with self._emitting_from(dispatch):
            for req in rows:
                for a0, a1, took in out_host[req.slot].tolist():
                    if req.state != RequestState.DECODE:
                        break  # a stop: the rest of the row is dropped
                    drafted += 1
                    self._emit_token(req, a0)
                    if took and req.state == RequestState.DECODE:
                        self._emit_token(req, a1)
                        accepted += 1
        t_end = time.perf_counter()
        if self._open is not None:
            self._open.spec = {"rounds": rounds, "drafted": drafted,
                               "accepted": accepted, "rows": len(rows)}
        self.metrics["spec_drafted"] += drafted
        self.metrics["spec_accepted"] += accepted
        self.metrics["decode_tokens"] += drafted + accepted
        self.metrics["decode_steps"] += rounds
        self.metrics["decode_dispatches"] += 1
        self.metrics["decode_dispatch_time_s"] += t_fetch - t_issue
        self.metrics["decode_host_time_s"] += (
            (t_issue - t0) + (t_end - t_fetch))
        self.metrics["decode_time_s"] += t_end - t0

    def _grammar_fast_forward(self, req: EngineRequest) -> None:
        """Emit a grammar-FORCED token run without per-token model dispatches.

        Schema-guided documents are dominated by deterministic stretches
        (object keys, quotes, separators — with a byte tokenizer well over
        half the bytes): wherever the mask admits exactly ONE token there is
        nothing to sample, so decoding them one host round-trip at a time
        is pure overhead. Probe the grammar on a COPY, and when a run
        of ≥4 forced tokens exists, emit the whole run at once and fold it
        (with the pending last token) into the prompt — the prefill path
        then writes their K/V in chunked batches and samples the next free
        token with the post-run mask. The same fold preemption uses, minus
        the page release.
        """
        enabled = self.ecfg.grammar_fast_forward
        if enabled is None:
            enabled = jax.default_backend() == "tpu"
        if not enabled:
            return
        if not (self.mask_fn and self.advance_fn and req.sampling.guided):
            return
        if req.sampling.logprobs:
            return  # forced runs surface no logits to score
        if req.sampling.stop_strings:
            # Forced runs would bypass the stop-string tail scan; rare for
            # guided requests, so just leave them on the per-token path.
            return
        budget = req.sampling.max_new_tokens - req.num_generated
        if budget <= 0:
            return
        orig = req.guided_state
        if orig is None:
            self.mask_fn(req)  # provider initializes the machine lazily
            orig = req.guided_state
            if orig is None:
                return
        probe = orig.copy()
        req.guided_state = probe
        forced: list[int] = []
        cap = min(budget, 4 * self.ecfg.prefill_chunk,
                  self.ecfg.max_seq_len - req.ctx_len - 1)
        stop_ids = set(req.sampling.stop_token_ids) | {
            self.tokenizer.eos_id, self.tokenizer.eot_id}
        try:
            while len(forced) < cap:
                m = self.mask_fn(req)
                if m is None:
                    break
                ids = np.nonzero(m)[0]
                if ids.size != 1 or int(ids[0]) in stop_ids:
                    break  # stop tokens take the normal emit/finish path
                tok = int(ids[0])
                forced.append(tok)
                if self.advance_fn(req, tok):
                    break  # grammar completed inside the run
        except Exception:
            req.guided_state = orig  # surface provider bugs, state restored
            raise
        if len(forced) < 4:
            req.guided_state = orig  # not worth a fold: restore
            return
        # Commit: the advanced probe IS the new grammar state. Forced tokens
        # are counted separately (not in decode_tokens: their K/V cost lands
        # in the prefill fold, so booking them as decode throughput would
        # inflate the BASELINE decode-tok/s metric).
        req.out_ids.extend(forced)
        if req.on_token is not None:
            for tok in forced:
                req.on_token(tok)
        self._last_token[req.request_id] = forced[-1]
        self.metrics["grammar_forced_tokens"] = (
            self.metrics.get("grammar_forced_tokens", 0) + len(forced))
        # Fold emitted-but-unprocessed tokens (the pending last token + the
        # forced run) into the prompt BEFORE any finish: _kv_valid_tokens /
        # prefix publication must only ever claim tokens whose K/V exists.
        written = req.ctx_len - len(forced) - 1  # tokens with K/V in the pool
        self._fold_into_prompt(req, prefill_pos=written)
        self.decoding.remove(req)
        if req.slot is not None:
            self._slots[req.slot] = None
            req.slot = None
        # Slot freed without a finish: invalidate the cached dispatch
        # inputs or the next decode would read a stale table whose freed
        # row still points at this request's live pages.
        self._bump_epoch()
        if req.num_generated >= req.sampling.max_new_tokens:
            self._finish(req, FinishReason.MAX_TOKENS)
            return
        req.state = RequestState.PREFILL
        self.prefilling.append(req)

    # ------------------------------------------------------- mixed dispatch

    def _can_mix(self) -> bool:
        """True when this step can run as ONE unified mixed dispatch.

        Forced-sync consumers (guided masks, logprob attachment) and
        sequences at the context limit keep the classic split path — their
        reconciliation rules (docs/decode_pipeline.md) are defined against
        it. The prefill HEAD is checked rather than skipped so FIFO
        fairness survives: a guided prompt at the head falls the whole
        step back to the classic path instead of starving behind mixers.
        """
        if not (self._mixed and self.prefilling and self.decoding):
            return False
        if any(r.sampling.forced_sync for r in self.decoding):
            return False
        if any(r.ctx_len + self._lead(r) + 1 > self.ecfg.max_seq_len
               for r in self.decoding):
            return False
        return not self.prefilling[0].sampling.forced_sync

    def _run_mixed(self) -> bool:
        """One ragged dispatch: every live decode slot (1 token each) plus
        the oldest prefill chunk(s), within the mixed token budget.

        Decode rows behave exactly like a k=1 :meth:`_run_decode` window
        (device-resident feed in, overlap pipeline out); prefill rows
        advance their chunk, and rows completing their prompt sample the
        FIRST output token inside the same dispatch (TTFT saves a whole
        dispatch). Returns False when reconciliation (drains, preemption,
        pool pressure) left nothing to mix — the caller then falls back to
        the classic split path for this step; the dispatch has not been
        issued and any prefill page extensions done here are idempotent
        under the classic chunk sizes.
        """
        t0 = time.perf_counter()
        acc0 = self._drain_time_acc
        # Same all-budget-covered tail rule as _run_decode: a dispatch
        # whose decode rows would all be overshoot is pure waste.
        if self._pending is not None and all(
                r.num_generated + self._lead(r) >= r.sampling.max_new_tokens
                for r in self.decoding):
            self._drain_pending()
        if not self._can_mix():
            return False
        with self._span("build"):
            rq = _RAGGED_BLOCK
            b = self.ecfg.max_batch_slots
            # Prefill row selection: FIFO, chunked, budget- and row-capped.
            # Stopping (not skipping) at the first ineligible/unfittable
            # request preserves admission order; the classic path serves it.
            pf_rows: list[tuple[EngineRequest, int, int]] = []
            used = 0
            for req in list(self.prefilling[: self._mix_pf_rows]):
                if req.sampling.forced_sync:
                    break
                room = self._mix_pf_tokens - used
                if room < 1:
                    break
                chunk = min(self.ecfg.prefill_chunk,
                            len(req.prompt_ids) - req.prefill_pos, room)
                new_ctx = req.prefill_pos + chunk
                try:
                    self.kv.extend(req.request_id, new_ctx)
                except MemoryError:
                    break  # run what fits; classic preempts when nothing does
                pf_rows.append((req, chunk, new_ctx))
                used += -(-chunk // rq) * rq
            if not pf_rows:
                return False
            # Decode page growth AFTER the prefill extends, mirroring the
            # classic step order (prefill dispatch precedes decode). The
            # internal preemption/drain may finish or evict decoders — or the
            # whole decode side — so re-check before committing to the mix.
            self._grow_pages_for_decode(1)
            if not self.decoding:
                return False
        self._note_window_chunks(pf_rows)

        t_build = time.perf_counter()
        with self._span("build"):
            n = b * rq + self._mix_pf_tokens
            n_pf = self._mix_pf_rows
            pad_row = self._mix_rows - 1
            trash = self._trash_pos()
            tokens = np.zeros((n,), dtype=np.int32)
            positions = np.full((n,), trash, dtype=np.int32)
            row_ids = np.full((n,), pad_row, dtype=np.int32)
            ctx_lens = np.zeros((self._mix_rows,), dtype=np.int32)
            adapters = np.zeros((self._mix_rows,), dtype=np.int32)
            dec_idx = np.arange(b, dtype=np.int32) * rq
            dec_live = np.zeros((b,), dtype=np.int32)
            for req in self.decoding:
                s = req.slot
                ec = req.ctx_len + self._lead(req)  # scheduled context
                positions[s * rq] = ec - 1
                row_ids[s * rq: (s + 1) * rq] = s
                ctx_lens[s] = ec
                adapters[s] = req.adapter_idx
                dec_live[s] = 1
            pf_last = np.zeros((n_pf,), dtype=np.int32)
            next_tokens = np.zeros((n,), dtype=np.int32) if self._mtp else None
            off = b * rq
            for j, (req, chunk, new_ctx) in enumerate(pf_rows):
                r = b + j
                tokens[off: off + chunk] = req.prompt_ids[req.prefill_pos:new_ctx]
                if self._mtp:
                    next_tokens[off: off + chunk] = self._next_tokens(
                        req, req.prefill_pos, new_ctx)
                positions[off: off + chunk] = np.arange(req.prefill_pos, new_ctx)
                row_ids[off: off + (-(-chunk // rq) * rq)] = r
                ctx_lens[r] = new_ctx
                adapters[r] = req.adapter_idx
                pf_last[j] = off + chunk - 1
                off += -(-chunk // rq) * rq
            tables = self._tables_for(
                list(self._slots) + [r for r, _, _ in pf_rows]
                + [None] * (n_pf - len(pf_rows)) + [None])

            # Completions are host-known before the dispatch: precompute the
            # slot each will take (same lowest-free-slot order the classic
            # path uses) so penalty count rows can be prepared NOW — the
            # in-dispatch first-token sampling reads them.
            done: list[tuple[int, EngineRequest, int]] = []
            free = [i for i, s in enumerate(self._slots) if s is None]
            for j, (req, chunk, new_ctx) in enumerate(pf_rows):
                if new_ctx >= len(req.prompt_ids):
                    # A sequence with recurrent state decodes where its
                    # state is (no other prefilling request holds it).
                    slot = (free[0] if req.state_slot is None
                            else req.state_slot)
                    free.remove(slot)
                    done.append((j, req, slot))
            fresh_pen = np.zeros((b,), dtype=bool)
            for j, req, slot in done:
                if req.sampling.penalized:
                    if req.all_out_ids:
                        self._seed_counts_for(req, slot=slot)
                    else:
                        fresh_pen[slot] = True
            if fresh_pen.any():
                self._tok_counts = _reset_count_rows(
                    self._tok_counts, jnp.asarray(fresh_pen))

            si = self._slot_inputs()
            pf_temps = np.zeros((n_pf,), dtype=np.float32)
            pf_top_ps = np.ones((n_pf,), dtype=np.float32)
            pf_top_ks = np.zeros((n_pf,), dtype=np.int32)
            pf_pres = np.zeros((n_pf,), dtype=np.float32)
            pf_freq = np.zeros((n_pf,), dtype=np.float32)
            pf_seeds = np.full((n_pf,), -1, dtype=np.int32)
            pf_slot_map = np.full((n_pf,), b, dtype=np.int32)  # b → dropped
            pf_live = np.zeros((n_pf,), dtype=np.int32)
            pf_use_pen = any(req.sampling.penalized for _, req, _ in done)
            pf_use_seed = any(req.sampling.seed is not None
                              for _, req, _ in done)
            pf_use_bias = any(req.sampling.logit_bias for _, req, _ in done)
            pf_bias = (np.zeros((n_pf, self.cfg.vocab_size), dtype=np.float32)
                       if pf_use_bias else None)
            for j, req, slot in done:
                pf_temps[j] = req.sampling.temperature
                pf_top_ps[j] = req.sampling.top_p
                pf_top_ks[j] = req.sampling.top_k
                pf_pres[j] = req.sampling.presence_penalty
                pf_freq[j] = req.sampling.frequency_penalty
                pf_slot_map[j] = slot
                if req.sampling.penalized:
                    pf_live[j] = 1
                if req.sampling.seed is not None:
                    pf_seeds[j] = req.sampling.seed & 0x7FFFFFFF
                if pf_bias is not None:
                    for tok_id, b_val in req.sampling.logit_bias:
                        pf_bias[j, tok_id] = b_val
            use_pen = si.use_pen or pf_use_pen

            real_tokens = len(self.decoding) + sum(c for _, c, _ in pf_rows)
            dec_snapshot = list(self.decoding)
            inflight = self._pending is not None
            self._key, sub = jax.random.split(self._key)

        mix_meta: dict[str, Any] = {"batch": len(dec_snapshot),
                                    "prefill_rows": len(pf_rows),
                                    "tokens": int(real_tokens)}
        if self.tracer.enabled:
            mix_meta["requests"] = (
                [r.request_id for r in dec_snapshot]
                + [r.request_id for r, _, _ in pf_rows])
        dispatch = self._dispatching(
            "_mixed_step", 1, len(dec_snapshot), ctx_lens,
            prefill_tokens=real_tokens - len(dec_snapshot))
        self._sampling(1, si.sorts)  # the decode rows', then the prompts'
        self._sampling(1, needs_sort(pf_temps))
        with self.tracer.span("engine.mixed", **mix_meta), \
                annotate("mixed", **_dispatch_stat(dispatch)), \
                self._span("issue"):
            t_issue = time.perf_counter()
            (toks_win, pf_toks, feed_new, self._kv_k, self._kv_v,
             counts_out, experts) = self._keep_drafts(self._keep_state(_mixed_step(
                self.params, self.cfg, jnp.asarray(tokens), self._feed_toks,
                jnp.asarray(dec_idx), jnp.asarray(positions),
                jnp.asarray(row_ids), self._kv_k, self._kv_v,
                jnp.asarray(tables), jnp.asarray(ctx_lens),
                jnp.asarray(adapters), jnp.asarray(pf_last),
                si.temps, si.top_ps, si.top_ks, sub,
                jnp.asarray(pf_temps), jnp.asarray(pf_top_ps),
                jnp.asarray(pf_top_ks), jnp.asarray(pf_slot_map),
                jnp.asarray(pf_live),
                dec_live=jnp.asarray(dec_live) if use_pen else None,
                counts=self._tok_counts if use_pen else None,
                pres=si.pres if si.use_pen else None,
                freq=si.freq if si.use_pen else None,
                seeds=si.seeds if si.use_seed else None,
                bias=si.bias if si.use_bias else None,
                pf_pres=jnp.asarray(pf_pres) if pf_use_pen else None,
                pf_freq=jnp.asarray(pf_freq) if pf_use_pen else None,
                pf_seeds=jnp.asarray(pf_seeds) if pf_use_seed else None,
                pf_bias=jnp.asarray(pf_bias) if pf_use_bias else None,
                page_size=self.ecfg.page_size,
                block_pages=self.ecfg.block_pages,
                attn_impl=self.ecfg.attn_impl, mesh=self.mesh,
                qmm_impl=self.ecfg.qmm_impl, ragged_block=rq,
                state=self._state,
                state_rows=self._state_rows(
                    list(self._slots) + [r for r, _, _ in pf_rows],
                    self._mix_rows),
                next_tokens=(jnp.asarray(next_tokens) if self._mtp else None),
            )))
        self._issued(dispatch, toks_win)
        for req, _, new_ctx in pf_rows:
            self._snapshot_state(req, new_ctx)
        if counts_out is not None:
            self._tok_counts = counts_out
        self._feed_toks = feed_new

        pending = _PendingDecode(
            toks_dev=toks_win,
            reqs=[(r, r.slot) for r in dec_snapshot],
            req_ids=frozenset(r.request_id for r in dec_snapshot),
            k=1, experts_dev=experts, program="_mixed_step",
            dispatch=dispatch,
        )
        if hasattr(toks_win, "copy_to_host_async"):
            toks_win.copy_to_host_async()

        # Prefill bookkeeping (chunk advance, completions join decode).
        self.metrics["prefill_tokens"] += sum(c for _, c, _ in pf_rows)
        for req, chunk, new_ctx in pf_rows:
            req.prefill_pos = new_ctx
        for j, req, slot in done:
            self.kv.register_prefix(req.request_id, req.prompt_ids,
                                    hashes=req.block_hashes)
            self.prefilling.remove(req)
            self._slots[slot] = req
            req.slot = slot
            req.state = RequestState.DECODE
            self.decoding.append(req)
        if done:
            self._bump_epoch()  # slot→request mapping changed
            with self._fetching(dispatch):
                # runbook: noqa[RBK002] — sanctioned sync: the one batched
                # mixed-step first-token fetch (TTFT emission; decode rows
                # stay device-resident in the overlap window).
                pf_host = np.asarray(jax.device_get(pf_toks))
            with self._emitting_from(dispatch, last=False):
                for j, req, slot in done:
                    self._first_token(req, int(pf_host[j]))

        # Decode rows ride the overlap pipeline exactly like _run_decode.
        if self.ecfg.overlap_decode:
            prev, self._pending = self._pending, pending
            if prev is not None:
                self._drain(prev, overlapped=True)
        else:
            self._drain(pending, overlapped=False)

        self.metrics["mixed_steps"] += 1
        self.metrics["mixed_tokens"] += real_tokens
        self.hist_mixed_tokens.observe(real_tokens)
        # Host-prep attribution mirrors _run_decode: build work counts as
        # (overlappable) host decode time; the drained window's fetch/emit
        # was already booked as decode_* inside _drain. mixed_time_s books
        # only this step's own un-drained wall, so pure-step counters keep
        # their /healthz + PromQL semantics.
        self.metrics["decode_host_time_s"] += t_issue - t_build
        if inflight:
            self.metrics["decode_host_overlap_s"] += t_issue - t_build
        self.metrics["mixed_time_s"] += (
            (time.perf_counter() - t0) - (self._drain_time_acc - acc0))
        return True

    def _run_decode(self) -> None:
        if not self.decoding:
            # Tail flush: every row of the in-flight window finished or
            # aborted since its dispatch — consume (and discard) so device
            # state and metrics settle even with nothing left to schedule.
            self._drain_pending()
            return
        t0 = time.perf_counter()
        acc0 = self._drain_time_acc
        # The token budget is host-known: when the in-flight window already
        # covers every sequence's max_new_tokens, a new dispatch would be
        # all-overshoot (every row discarded at drain). Drain instead —
        # this is the common stream tail, e.g. a batch finishing together.
        if self._pending is not None and all(
                r.num_generated + self._lead(r) >= r.sampling.max_new_tokens
                for r in self.decoding):
            self._drain_pending()
            if not self.decoding:
                return
        overlap = self.ecfg.overlap_decode
        # Reconciliation: paths that must see the host view current before
        # the next dispatch can even be BUILT — per-token grammar masks and
        # logprob attachment (k=1 fetch), forced-sync mode, and sequences
        # whose scheduled context hits the limit (finish precedes growth).
        need_sync = (not overlap) or any(
            r.sampling.forced_sync for r in self.decoding)
        if not need_sync and any(
                r.ctx_len + self._lead(r) + 1 > self.ecfg.max_seq_len
                for r in self.decoding):
            need_sync = True
        if need_sync:
            self._drain_pending()
            # Sequences at the context limit finish before K is chosen.
            for req in list(self.decoding):
                if req.ctx_len + 1 > self.ecfg.max_seq_len:
                    self._finish(req, FinishReason.MAX_TOKENS)
            # Grammar fast-forward may move guided requests back to prefill
            # (their next tokens are forced — no sampling needed).
            for req in list(self.decoding):
                self._grammar_fast_forward(req)
            if not self.decoding:
                return
        k = self._pick_k()
        # Speculation serves all-greedy batches: the verify forward takes
        # plain argmaxes. Penalized greedy shifts the argmax per position
        # as counts evolve and it has no count plumbing — multi-step
        # handles these; logit_bias likewise shifts the verify argmax.
        spec = self.ecfg.speculative and all(
            r.sampling.temperature == 0.0 and not r.sampling.guided
            and not r.sampling.logprobs and not r.sampling.penalized
            and not r.sampling.logit_bias for r in self.decoding)
        if spec and self._mtp:
            # The model's own module drafts, on the device, several rounds
            # a dispatch. The rounds are built from the host's view, so
            # the lagged window (a mixed step's) is settled first.
            self._drain_pending()
            rounds = self._pick_k(per_step=2) if self.decoding else 0
            if rounds:
                self._run_spec_rounds(rounds)
            if rounds or not self.decoding:
                return
        # Prompt-lookup speculation: one T=k verify
        # forward replaces k sequential decode steps when any draft exists.
        # Drafting needs the host-current history, so each probe drains the
        # lagged window; a draftless probe backs off re-probing so
        # non-repetitive traffic keeps the overlap instead of paying a
        # drain every step.
        elif k > 1 and spec:
            if self._spec_backoff > 0:
                self._spec_backoff -= 1
            else:
                self._drain_pending()
                if not self.decoding:
                    return
                with self._span("draft"):
                    if self.draft is not None:
                        committed = [
                            (r.request_id,
                             r.prompt_ids[: r.prefill_pos] + r.out_ids)
                            for r in self.decoding]
                        drafts = self.draft.draft(committed, k - 1)
                        for r in self.decoding:  # prompt-lookup fallback
                            if not drafts.get(r.request_id):
                                drafts[r.request_id] = self._draft_for(
                                    r, k - 1)
                        self.metrics.update(self.draft.metrics)
                    else:
                        drafts = {r.request_id: self._draft_for(r, k - 1)
                                  for r in self.decoding}
                # Worth it only when most of the batch drafts (nonempty
                # decoding list makes this imply at least one draft): an
                # undrafted request gets 1 token from a spec dispatch vs k
                # from multi-step.
                if 2 * sum(bool(d) for d in drafts.values()) >= len(self.decoding):
                    self._spec_miss_streak = 0
                    self._run_decode_spec(drafts, k)
                    return
                self._spec_miss_streak += 1
                self._spec_backoff = min(
                    max(0, self.ecfg.spec_backoff_rounds),
                    2 ** (self._spec_miss_streak - 1))
        # Grow pages to cover scheduled ctx + K for every sequence; preempt
        # on pressure (preemption drains the lagged window internally).
        with self._span("build"):
            self._grow_pages_for_decode(k)
        if not self.decoding:
            self.metrics["decode_time_s"] += (
                (time.perf_counter() - t0) - (self._drain_time_acc - acc0))
            return

        b = self.ecfg.max_batch_slots
        inflight = self._pending is not None
        t_build = time.perf_counter()
        with self._span("build"):
            si = self._slot_inputs()
            positions = np.zeros((b, 1), dtype=np.int32)
            ctx_lens = np.zeros((b,), dtype=np.int32)
            need_mask = False
            mask = None
            if self.mask_fn and any(r.sampling.guided for r in self.decoding):
                mask = np.ones((b, self.cfg.vocab_size), dtype=bool)
            for req in self.decoding:
                i = req.slot
                ec = req.ctx_len + self._lead(req)  # scheduled context
                positions[i, 0] = ec - 1  # position of the token being fed
                ctx_lens[i] = ec
                if mask is not None and req.sampling.guided:
                    m = self.mask_fn(req)
                    if m is not None:
                        _set_mask_row(mask, i, m)
                        need_mask = True
            self._key, sub = jax.random.split(self._key)
            pen_kw = dict(
                counts=self._tok_counts if si.use_pen else None,
                pres=si.pres if si.use_pen else None,
                freq=si.freq if si.use_pen else None,
                seeds=si.seeds if si.use_seed else None,
                bias=si.bias if si.use_bias else None,
            )
            # Device-resident token feedback: each slot's last sampled token
            # never visits the host on the input side.
            tokens_dev = self._feed_toks[:, None]

        dec_meta: dict[str, Any] = {"k": k, "batch": len(self.decoding)}
        if self.tracer.enabled:
            dec_meta["requests"] = [r.request_id for r in self.decoding]
        program = "_decode_step" if k == 1 else "_decode_multi"
        dispatch = self._dispatching(program, k, len(self.decoding), ctx_lens)
        self._sampling(k, si.sorts)
        with self.tracer.span("engine.decode", **dec_meta), \
                annotate("decode", **_dispatch_stat(dispatch)), \
                self._span("issue"):
            t_issue = time.perf_counter()
            last_logits = None
            if k == 1:
                (toks, last_logits, self._kv_k, self._kv_v,
                 counts_out, experts) = self._keep_drafts(self._keep_state(_decode_step(
                    self.params, self.cfg, tokens_dev, jnp.asarray(positions),
                    self._kv_k, self._kv_v, si.tables, jnp.asarray(ctx_lens),
                    si.temps, si.top_ps, si.top_ks, sub,
                    jnp.asarray(mask) if need_mask else None,
                    si.adapters, **pen_kw,
                    page_size=self.ecfg.page_size, block_pages=self.ecfg.block_pages,
                    attn_impl=self.ecfg.attn_impl, mesh=self.mesh,
                    qmm_impl=self.ecfg.qmm_impl, state=self._state,
                    self_draft=self._mtp,
                )))
                self._feed_toks = toks
                toks_win = toks[:, None]  # [B, 1]
            else:
                (toks_win, self._kv_k, self._kv_v, counts_out,
                 experts) = self._keep_drafts(self._keep_state(_decode_multi(
                    self.params, self.cfg, tokens_dev, jnp.asarray(positions),
                    self._kv_k, self._kv_v, si.tables, jnp.asarray(ctx_lens),
                    si.temps, si.top_ps, si.top_ks, sub,
                    si.adapters, **pen_kw,
                    page_size=self.ecfg.page_size, block_pages=self.ecfg.block_pages,
                    k_steps=k, attn_impl=self.ecfg.attn_impl, mesh=self.mesh,
                    qmm_impl=self.ecfg.qmm_impl, state=self._state,
                    self_draft=self._mtp,
                )))
                self._feed_toks = toks_win[:, -1]
            if counts_out is not None:
                self._tok_counts = counts_out
            t_done = time.perf_counter()
        self._issued(dispatch, toks_win)

        pending = _PendingDecode(
            toks_dev=toks_win,
            reqs=[(r, r.slot) for r in self.decoding],
            req_ids=frozenset(r.request_id for r in self.decoding),
            k=k, experts_dev=experts, program=program, dispatch=dispatch,
        )
        # Start the token egress behind the (async) dispatch: by the time
        # the window is drained, the DMA has had a full device step to land.
        if hasattr(toks_win, "copy_to_host_async"):
            toks_win.copy_to_host_async()
        self.metrics["decode_host_time_s"] += t_issue - t_build
        if inflight:
            # Input prep ran while the previous window executed on device.
            self.metrics["decode_host_overlap_s"] += t_issue - t_build
        self.metrics["decode_dispatch_time_s"] += t_done - t_issue
        self.metrics["decode_dispatches"] += 1

        if need_sync:
            # Forced-sync: consume this window before returning (guided
            # masks / logprob attachment need the tokens before the next
            # dispatch can be built anyway). Logprob entries attach BEFORE
            # emission: _finish (inside the drain) wakes streaming
            # consumers, and their tail flush must never observe the final
            # token's entry still missing.
            if k == 1 and any(r.sampling.logprobs for r, _ in pending.reqs):
                toks_host = self._fetch_tokens(pending.toks_dev,
                                               dispatch=dispatch)
                self._score_logprobs(last_logits, toks_win[:, 0],
                                     toks_host[:, 0], pending.reqs)
            self._drain(pending, overlapped=False)
        else:
            # One-step lag: park this window and consume the PREVIOUS one —
            # its emission (detokenize, stop scans, stream callbacks) runs
            # while this window executes on device.
            prev, self._pending = self._pending, pending
            if prev is not None:
                self._drain(prev, overlapped=True)
        self.metrics["decode_time_s"] += (
            (time.perf_counter() - t0) - (self._drain_time_acc - acc0))

    # ------------------------------------------------------------------ step

    # ``finished`` high-water trim: a days-long server must not retain
    # every EngineRequest (prompt/output ids, logprobs) for process
    # lifetime — the 600s soak measured ~0.4 MB/s RSS growth from
    # exactly this. Recent entries stay addressable for callers that
    # inspect the tail.
    _FINISHED_HIGH_WATER = 4096
    _FINISHED_KEEP = 1024

    def step(self) -> list[EngineRequest]:
        """One scheduler iteration; returns requests finished during it.

        With prompts and decodes both live (and mixed dispatch enabled),
        the step runs as ONE unified ragged dispatch; otherwise — or when
        mixing bails during reconciliation — the classic split
        prefill-then-decode pair runs, at most one dispatch each."""
        if self.chaos_hook is not None:
            # Fault-injection seam (runbookai_tpu/chaos): runs before any
            # pool mutation so an injected crash leaves a consistent core
            # for the supervisor's failover sweep.
            self.chaos_hook(self)
        if len(self.finished) > self._FINISHED_HIGH_WATER:
            del self.finished[: -self._FINISHED_KEEP]
        before = len(self.finished)
        recording = self.flight.enabled
        if recording:
            m = self.metrics
            pre = (m["prefill_steps"], m["decode_dispatches"],
                   m["mixed_steps"], m["prefill_tokens"],
                   m["decode_tokens"], m["decode_dispatch_time_s"],
                   m["decode_host_time_s"], m["decode_host_overlap_s"],
                   m["preemptions"], m["sampler_calls"],
                   m["sampler_sorted_calls"])
            self._open = OpenStep()
        compiles0, compile_s0 = _compile_totals
        try:
            # ``step`` is the number this step's record will get: the join
            # between a span on the profiler's clock and the record.
            with annotate("engine.step", step=self.flight.total_steps):
                with self._span("admit"):
                    self._admit()
                if not (self._can_mix() and self._run_mixed()):
                    if self.prefilling:
                        self._run_prefill()
                    self._run_decode()
                if self.feedback is not None:
                    # SLO feedback (sched/feedback.py): every interval
                    # window the controller moves the mixed-dispatch
                    # prefill share one level against the live TPOT burn.
                    # None (the default) = untouched.
                    self.feedback.on_step(self)
                if self.kv.window is not None:
                    for name, count in self.kv.window_counters.items():
                        self.metrics["kv_window_" + name] = count
                compiles, compile_s = _compile_totals
                if compiles != compiles0:
                    self.metrics["compiles"] += compiles - compiles0
                    self.metrics["compile_time_s"] += compile_s - compile_s0
                if recording:
                    self._record_step(pre, compile_s - compile_s0)
        finally:
            self._open = None
        return self.finished[before:]

    def _record_step(self, pre: tuple, compile_s: float) -> None:
        """Close the open step into its flight record (O(1): one dict +
        ring slot).

        Dispatch kind derives from the PR 4 counters' deltas — ``mixed``
        for the unified ragged step, ``prefill+decode`` when the classic
        split path ran both dispatches, ``idle`` for a drain/admit-only
        step. Token counts follow the metrics dict's semantics: decode
        tokens book at window DRAIN, one window late under overlap."""
        m = self.metrics
        step = self._open
        d_prefill = m["prefill_steps"] - pre[0]
        d_decode = m["decode_dispatches"] - pre[1]
        d_mixed = m["mixed_steps"] - pre[2]
        if d_mixed:
            kind = "mixed"
        elif d_prefill and d_decode:
            kind = "prefill+decode"
        elif d_prefill:
            kind = "prefill"
        elif d_decode:
            kind = "decode"
        else:
            kind = "idle"
        batch = len(self.decoding)
        # Per-class batch occupancy: who holds the decode slots this step
        # (the starvation picture /debug/steps is read for — a batch
        # flood squeezing interactive out shows up here first).
        classes: dict[str, int] = {}
        for r in self.decoding:
            label = class_name(r.priority)
            classes[label] = classes.get(label, 0) + 1
        prefill_tokens = m["prefill_tokens"] - pre[3]
        decode_tokens = m["decode_tokens"] - pre[4]
        rec = {
            "ts": round(time.time(), 6),
            "kind": kind,
            "classes": classes,
            "tokens": prefill_tokens + decode_tokens,
            "batch": batch,
            "occupancy": round(batch / self.ecfg.max_batch_slots, 4),
            "queue_depth": len(self.waiting) + len(self.prefilling),
            "kv_free_pages": self.kv.allocator.free_pages,
            "kv_utilization": round(self.kv.utilization(), 4),
            "dispatch_s": round(m["decode_dispatch_time_s"] - pre[5], 6),
            "host_s": round(m["decode_host_time_s"] - pre[6], 6),
            "overlap_s": round(m["decode_host_overlap_s"] - pre[7], 6),
            "preemptions": m["preemptions"] - pre[8],
            "program": step.programs,
            "k": step.k,
            "rows": step.rows,
            "kv_pages_live": step.kv_pages_live,
            "prefill_tokens": prefill_tokens,
            "decode_tokens": decode_tokens,
            "compile_s": round(compile_s, 6),
            "admitted": self._admitted_log,
            "finished": self._finished_log,
            "dispatches": self.flight.dispatches.take_log(),
            "sampler": {"calls": m["sampler_calls"] - pre[9],
                        "sorted": m["sampler_sorted_calls"] - pre[10]},
        }
        if step.experts is not None:
            rec["experts"] = step.experts
        if step.spec is not None:
            rec["spec"] = step.spec
        if self._state is not None:
            # Slots that hold a sequence's state (decoding or prefilling),
            # and the snapshot counters' growth over this step.
            rec["state"] = {
                "slots_live": len(self.decoding) + len(self.prefilling),
                **{k: m["state_" + k] - self._state_mark.get(k, 0)
                   for k in STATE_COUNTERS}}
            self._state_mark = {k: m["state_" + k] for k in STATE_COUNTERS}
        if self.kv.window is not None:
            # Over the sequences that live (the dispatch's rows): the token
            # rows the window layers hold for them, their contexts, and the
            # rows this step gave back behind the window; over the decoding
            # ones, the rows inside the window (what a decode pass's walk
            # must read a layer); of the step's prefill chunks, the
            # query-key pairs and distinct key rows inside the window.
            live = [r for r in self.decoding + self.prefilling
                    if r.request_id in self.kv.seqs]
            released = m["kv_window_rows_released"]
            width = self.kv.window.window
            kept = [self.kv.window_rows(r.request_id) for r in live]
            rec["window"] = {
                "rows_kept": sum(kept),
                "rows_context": sum(self.kv.seqs[r.request_id].ctx_len
                                    for r in live),
                "rows_kept_max": max(kept, default=0),
                "rows_released": released - self._window_mark,
                "rows_seen": sum(min(r.ctx_len + self._lead(r), width)
                                 for r in self.decoding),
                "chunk_pairs": self._window_chunks[0],
                "chunk_rows_seen": self._window_chunks[1]}
            self._window_mark = released
            self._window_chunks = [0, 0]
        self._admitted_log, self._finished_log = [], []
        # Page transfers land BETWEEN steps (cross-replica pulls, disagg
        # handoffs, spill readmits run under the engine lock outside
        # step()), so these deltas are measured against the LAST RECORDED
        # step, not this step's start — otherwise every pull would be
        # invisible in /debug/steps.
        imported, exported = (m["kv_pages_imported"],
                              m["kv_pages_exported"])
        rec["kv_imported"] = imported - self._flight_kv_mark[0]
        rec["kv_exported"] = exported - self._flight_kv_mark[1]
        self._flight_kv_mark = (imported, exported)
        if self.replica_idx is not None:
            rec["replica"] = self.replica_idx
        # The step's two ends last, so that ``other`` — the wall no phase
        # accounts for — holds everything down to this record's making.
        t_end = time.monotonic()
        wall = t_end - step.t_start
        phases = step.phases
        phases["other"] = wall - sum(phases.values())
        rec["t_start"], rec["t_end"] = step.t_start, t_end
        rec["wall_s"] = round(wall, 6)
        rec["phases"] = {k: round(v, 6) for k, v in phases.items()}
        self.flight.append(rec)

    def run_until_idle(self, max_steps: int = 100_000) -> list[EngineRequest]:
        done: list[EngineRequest] = []
        for _ in range(max_steps):
            if not self.has_work:
                break
            done.extend(self.step())
        return done

    def output_for(self, req: EngineRequest) -> EngineOutput:
        # Strip the stop token from the visible text.
        ids = req.all_out_ids  # includes tokens folded by preemption
        stop_ids = set(req.sampling.stop_token_ids) | {self.tokenizer.eos_id, self.tokenizer.eot_id}
        text_ids = ids[:-1] if ids and ids[-1] in stop_ids else ids
        text = self.tokenizer.decode(text_ids)
        if req.finish_reason == FinishReason.STOP_STRING:
            # OpenAI semantics: the matched stop sequence is NOT part of
            # the returned content (clients split on it).
            cut = min((i for i in (text.find(s)
                                   for s in req.sampling.stop_strings)
                       if i >= 0), default=-1)
            if cut >= 0:
                text = text[:cut]
        logprobs = None
        if req.sampling.logprobs:
            # OpenAI invariant: logprobs.content aligns 1:1 with the
            # tokens of message.content — entries for the stripped stop
            # token / cut stop-string tail must not leak through.
            logprobs = list(req.out_logprobs[: len(text_ids)])
            if req.finish_reason == FinishReason.STOP_STRING:
                # Byte-accurate trim via id_to_bytes: per-token decode()
                # would yield U+FFFD for multi-byte characters split
                # across tokens and miscount against the joint text.
                budget = len(text.encode("utf-8"))
                kept = acc = 0
                for e in logprobs:
                    acc += len(self.tokenizer.id_to_bytes(e["token_id"]))
                    if acc > budget:
                        break
                    kept += 1
                logprobs = logprobs[:kept]
        return EngineOutput(
            request_id=req.request_id,
            token_ids=list(ids),
            text=text,
            finish_reason=req.finish_reason or FinishReason.ABORTED,
            ttft_ms=req.ttft_ms,
            decode_tokens=req.num_generated,
            elapsed_s=time.perf_counter() - req.arrival_time,
            logprobs=logprobs,
            cached_tokens=req.cached_tokens,
        )
