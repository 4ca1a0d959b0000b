"""What a model family is to the rest of the program: the base its
configuration dataclass inherits (:class:`Family`), the joint registry of
configurations (``CONFIGS``), the table of what a family refuses, the adapter
that makes the step programs' forwards out of a family's stack, and the few
pieces every family file shares.

The arrows point one way: ``ops/`` <- this module <- one file a family <-
``models/__init__.py`` (which imports the six, so the registry is whole
whichever of them is asked for) <- ``engine/``. A family file imports this
module and ``ops/``; nothing here imports a family file.

**To add a family**: one file beside this one — a frozen dataclass that
inherits :class:`Family` and declares what differs from the defaults below,
its ``_forward_hidden``, its ``init_params``, its entries ``register``ed —
and one import line in ``models/__init__.py``.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from runbookai_tpu.ops.dense import rms_norm

Params = dict[str, Any]

# What a step program counts in the expert layers, summed over layers, of
# the LIVE tokens it ran (pads and free slots left out): token-expert pairs
# that fell on experts held here, on identity experts, on experts that live
# elsewhere, held experts that got at least one pair, and expert layers
# whose dispatch overflowed its slots and took the slow path
# (``ops/moe.held_expert_ffn``).
EXPERT_COUNTS = ("held", "zero", "absent", "touched", "overflow")

# Why a family with a state pool serves neither kind of speculation.
NO_ROLLBACK = "a rejected draft would need the recurrent state rolled back"

# Every servable configuration by name, in the order the family files are
# imported (``models/__init__.py``). ONE dict: ``benchmark/serving.py``
# writes a cell's configuration into it and ``hf_loader.load_or_init`` reads
# it back, so a family file adds to it (:func:`register`) and never copies it.
CONFIGS: dict[str, "Family"] = {}
# The families' dataclasses, in the same order (what ``hf_loader`` asks which
# of them a checkpoint's ``model_type`` belongs to).
FAMILIES: list[type] = []


def register(configs: dict[str, "Family"]) -> dict[str, "Family"]:
    """A family file's own entries join the registry; returns them."""
    for cfg in configs.values():
        if type(cfg) not in FAMILIES:
            FAMILIES.append(type(cfg))
    CONFIGS.update(configs)
    return configs


def get_config(name: str) -> "Family":
    if name not in CONFIGS:
        raise KeyError(f"Unknown model {name!r}; known: {sorted(CONFIGS)}")
    return CONFIGS[name]


class Family:
    """All the engine, the memory plan and the loader read of a family.

    A configuration is a frozen dataclass that inherits this class (which
    adds no field: ``dataclasses.fields`` and ``asdict`` of a configuration
    are its published sizes and nothing else). Everything below is read
    plainly, as an attribute or a call, never through ``getattr`` with a
    default: the defaults are here.

    *Sizes*, each a field or a property of the dataclass: ``name``,
    ``family`` (the chat template it renders), ``vocab_size``, ``dim``,
    ``n_layers``, ``n_heads``, ``norm_eps``, ``max_seq_len``,
    ``tie_embeddings``, ``matmul_params`` (the ``N`` of the decode-FLOPs
    model ``2 N``), ``total_params`` (every weight held here); where
    ``pallas_attention``, also ``n_kv_heads`` and ``head_dim`` (what the
    engine probes the kernels at); where ``state_pool_spec``, also
    ``state_snapshots``.

    *The pools*: :attr:`kv_pool_spec`, ``kv_window_spec``,
    ``state_pool_spec``. *The program*: :meth:`forwards`, :meth:`drafter`,
    ``self_draft``, ``pallas_attention``, ``pallas_prefill``,
    ``max_prefill_rows``. *What it refuses*: ``one_path``,
    ``no_prompt_lookup``, ``no_draft_model`` (:meth:`unsupported`). *The
    weights*: :meth:`init_params`, :meth:`weight_bytes_per_chip`,
    ``family_name``, ``hf_model_types``, ``checkpoint_tensors``,
    :meth:`claims`, :meth:`from_hf`.
    """

    # An untied head unless the dataclass says otherwise.
    tie_embeddings = False

    # ---- the pools ------------------------------------------------------

    @property
    def kv_pool_spec(self) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
        """The paged pool's two sides, each (layers, heads, values a head)."""
        raise NotImplementedError

    # (layers, window) of a second group of the paged pool whose queries see
    # their last ``window`` positions only (``engine/kv_cache.WindowSpec``).
    kv_window_spec: Optional[tuple[int, int]] = None
    # State that is not token rows in pages: the arrays of a pool indexed by
    # batch slot, each (shape a slot with its layers leading, dtype).
    state_pool_spec: Optional[tuple] = None

    # ---- the program ----------------------------------------------------

    # The engine's Pallas attention kernels read this family's pages (False:
    # attention is the family's own over its own pool, ``attn_impl`` "xla").
    pallas_attention = True
    # Under ``attn_impl="pallas"`` its prefill rows call a Pallas kernel too,
    # as its decode rows do (False: no chunk or ragged kernel is probed).
    pallas_prefill = True
    # The most sequences whose prefill chunks share one dispatch (None: the
    # engine's ``prefill_batch``).
    max_prefill_rows: Optional[int] = None
    # The model brings its own drafter (:meth:`drafter`).
    self_draft = False

    def forwards(self) -> tuple[Callable, Callable]:
        """(forward, ragged forward) as the step programs call them: one
        signature and one result for every family (:func:`serving_forwards`)."""
        raise NotImplementedError

    def drafter(self) -> Optional[tuple[Callable, Callable, Callable]]:
        """(module pass, ragged module pass, draft tokens): what a
        speculative round runs beside the forward. None: no drafter."""
        return None

    # ---- what it refuses ------------------------------------------------

    # The forward is one path on one chip: bf16 or float32 weights of the
    # family's own seeded recipe, no LoRA rows, no mesh axis, no scaled pool.
    one_path = False
    # Its refusal of prompt-lookup / draft-model speculation, in its own
    # words (None: served).
    no_prompt_lookup: Optional[str] = None
    no_draft_model: Optional[str] = None

    def unsupported(self, *, lora: bool = False, model_axis: int = 1,
                    seq_axis: int = 1, kv_dtype=None, quantized: bool = False,
                    speculative: bool = False, draft: bool = False) -> list[str]:
        """What this family's forward does not do yet, of what the engine
        was asked for — refused by name at engine init, never served
        wrong. A family that declares nothing refuses nothing: what the
        engine resolves or refuses for it is the engine's own table."""
        no = []
        if speculative and self.no_prompt_lookup:
            no.append(self.no_prompt_lookup)
        if draft and self.no_draft_model:
            no.append(self.no_draft_model)
        if not self.one_path:
            return no
        if lora:
            no.append("LoRA adapters")
        if model_axis > 1:
            no.append(f"a model axis of {model_axis} (tensor/expert "
                      f"parallelism across chips)")
        if seq_axis > 1:
            no.append("the KV page-split (seq) mesh axis")
        if kv_dtype is not None and jnp.dtype(kv_dtype) == jnp.int8:
            no.append("an int8 KV pool (per-token scales)")
        if quantized:
            no.append("int8 weight-only matrices")
        return no

    # ---- the weights ----------------------------------------------------

    # The family's name in a message, the ``model_type``s of a checkpoint's
    # ``config.json`` that are its (:meth:`claims`), and — where no loader is
    # written yet — the tensor names one would have to know.
    family_name = ""
    hf_model_types: tuple[str, ...] = ()
    checkpoint_tensors: Optional[str] = None

    @classmethod
    def claims(cls, model_type: str) -> bool:
        """Whether a checkpoint of ``model_type`` is this family's: one with
        a loader claims its types exactly; one without, every type that
        STARTS with one of its own (and :meth:`from_hf` refuses it by name)."""
        if cls.checkpoint_tensors:
            return model_type.startswith(cls.hf_model_types)
        return model_type in cls.hf_model_types

    def init_params(self, key: jax.Array, dtype=jnp.bfloat16,
                    quantized: bool = False) -> Params:
        """Seeded random weights (``quantized``: the matrices directly in
        int8, where the family serves them)."""
        raise NotImplementedError

    def weight_bytes_per_chip(self, tp: int, weights: str, kv_shards: int) -> float:
        """The weights one chip of ``tp`` holds. A family with no layout
        across chips (``one_path``): the whole share, as stored."""
        return self.total_params * 2

    @classmethod
    def from_hf(cls, raw: dict, name: str) -> "Family":
        """The configuration of a checkpoint's ``config.json``."""
        raise NotImplementedError(
            f"model_type {raw.get('model_type')!r}: the {cls.family_name} "
            f"family runs on seeded random weights only (models/"
            f"{cls.__module__.rsplit('.', 1)[-1]}.py); no checkpoint loader yet")


def lm_head_logits(params: Params, cfg, hidden) -> jnp.ndarray:
    """Final norm + (tied or untied) LM head, float32 logits."""
    h = rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (h @ head).astype(jnp.float32)


def _stacked_normal(key, shape, fan_in, dtype):
    """A stacked matrix sampled one ``[in, out]`` slice at a time (a key a
    slice), so the float32 transient is a slice's, not the leaf's: 16 held
    experts of four layers are 3.2 GB in float32, beside 10 GB of weights."""
    lead, mat = shape[:-2], shape[-2:]
    keys = jax.random.split(key, math.prod(lead))

    def one(k):
        return (jax.random.normal(k, mat, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    return jax.lax.map(one, keys).reshape(shape)


def serving_forwards(hidden: Callable, hidden_ragged: Optional[Callable] = None,
                     head: Callable = lm_head_logits) -> tuple[Callable, Callable]:
    """The two callables :meth:`Family.forwards` returns, built from a
    family's stack. Both return ``(logits, kv_k', kv_v', expert counts,
    state', hidden)``: the counts of a family with an expert share
    (``EXPERT_COUNTS``), the state pool of one that has one, the trunk's
    last hidden state (before the final norm) for one that drafts for
    itself — None for every other, and None is an empty pytree: no operand
    goes into and no result comes out of a compiled program for it.

    ``hidden(params, cfg, tokens, positions, kv_k, kv_v, page_tables,
    ctx_lens, page_size, block_pages, **kw)`` is the stack over one paged
    chunk ``[B, T]`` without the head, returning ``(hidden [B, T, D], kv_k',
    kv_v'[, counts[, state']])``. ``kw`` holds what the family's
    declarations say it takes (:func:`_operands`); the rest the engine has
    refused or resolved for it.

    The ragged forward runs ONE flat token batch ``[N]`` (the engine's mixed
    dispatch; ``models/llama.py`` ``forward_ragged_impl`` states the layout
    contract). Every row's run starts at a multiple of ``ragged_block``, so
    each block belongs to one row and the whole stack runs as a ``[N /
    ragged_block, ragged_block]`` chunked forward with per-BLOCK gathered
    tables. A family whose mixers lay the buffer out otherwise hands its own
    ``hidden_ragged(params, cfg, tokens, positions, row_ids, kv_k, kv_v,
    page_tables, ctx_lens, page_size, block_pages, ragged_block, **kw)``.
    """

    def results(params, cfg, out, select=None):
        h, kv_k, kv_v, *rest = out
        counts, state = (*rest, None, None)[:2]
        if select is not None:
            h = h.reshape(-1, h.shape[-1])
        logits = head(params, cfg, h if select is None else h[select])
        return logits, kv_k, kv_v, counts, state, h if cfg.self_draft else None

    def forward(params, cfg, tokens, positions, kv_k, kv_v, page_tables,
                ctx_lens, page_size, block_pages=32, attn_impl="xla", mesh=None,
                adapter_ids=None, qmm_impl="xla", *, state=None, state_rows=None):
        kw = _operands(cfg, attn_impl, mesh, adapter_ids, qmm_impl, state, state_rows)
        return results(params, cfg, hidden(
            params, cfg, tokens, positions, kv_k, kv_v, page_tables, ctx_lens,
            page_size, block_pages, **kw))

    def forward_ragged(params, cfg, tokens, positions, row_ids, kv_k, kv_v,
                       page_tables, ctx_lens, sel_idx, page_size, block_pages=32,
                       attn_impl="xla", mesh=None, adapter_ids=None,
                       qmm_impl="xla", ragged_block=8, *, state=None,
                       state_rows=None):
        kw = _operands(cfg, attn_impl, mesh, adapter_ids, qmm_impl, state, state_rows)
        if hidden_ragged is not None:
            return results(params, cfg, hidden_ragged(
                params, cfg, tokens, positions, row_ids, kv_k, kv_v, page_tables,
                ctx_lens, page_size, block_pages, ragged_block, **kw), sel_idx)
        nb = tokens.shape[0] // ragged_block
        block_rows = row_ids.reshape(nb, ragged_block)[:, 0]
        chunk = (tokens.reshape(nb, ragged_block), positions.reshape(nb, ragged_block),
                 kv_k, kv_v, page_tables[block_rows], ctx_lens[block_rows])
        if kw.get("adapter_ids") is not None:
            kw["adapter_ids"] = kw["adapter_ids"][block_rows]
        return results(params, cfg, hidden(
            params, cfg, *chunk, page_size, block_pages, **kw), sel_idx)

    return forward, forward_ragged


def _operands(cfg: Family, attn_impl, mesh, adapter_ids, qmm_impl, state,
              state_rows) -> dict:
    """Which of the serving signature's operands a family's stack takes, by
    what it declares."""
    kw = {}
    if cfg.pallas_attention:
        kw["attn_impl"] = attn_impl
    if not cfg.one_path:
        kw.update(mesh=mesh, adapter_ids=adapter_ids, qmm_impl=qmm_impl)
    if cfg.state_pool_spec is not None:
        kw.update(state=state, state_rows=state_rows)
    return kw
