"""Llama-3 family in JAX — pure-functional, scan-stacked, paged-KV native.

Design (TPU-first, no reference counterpart — RunbookAI calls hosted APIs):

- Params are a plain pytree with all transformer layers **stacked on a leading
  axis** and the forward pass runs ``lax.scan`` over them: one compiled layer
  body regardless of depth (32/80 layers), which keeps XLA compile times flat
  and makes TP sharding specs uniform.
- A single forward covers chunked prefill and decode (decode is T=1): the
  chunk's K/V are scattered into the paged pool, then queries attend over the
  pool via :func:`runbookai_tpu.ops.attention.paged_attention` or the Pallas
  kernels of :mod:`runbookai_tpu.ops.paged_attention_pallas`.
- The KV pool, ``[n_layers, num_pages * page_size, n_kv, head_dim]`` a side,
  rides the layer scan's CARRY whole and is updated in place, layer by
  layer: each layer's scatter writes its B*T rows at ``(layer, dest)``, so
  a step program holds ONE pool — the donated one — and nothing
  pool-shaped is copied and no layer is written through, whatever the
  program (decode, multi-step decode, verify, prefill, mixed).
  ``engine/hlo_bytes.kv_pool_materializations`` holds the compiled programs
  to that. Attention reads the layer's slice of the carry, which the chip
  stages in on-chip memory for the kernels. The pool's shape outside the
  forward is unchanged.
- GQA (n_kv_heads < n_heads), RMSNorm in float32, bf16 weights by default,
  logits in float32 for stable sampling/grammar masking.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from runbookai_tpu.models.family import (  # noqa: F401 — get_config: this module's API
    CONFIGS,
    Family,
    Params,
    get_config,
    lm_head_logits,
    register,
    serving_forwards,
)
from runbookai_tpu.ops.attention import paged_attention, write_kv_pages_batch
from runbookai_tpu.ops.dense import qmm, rms_norm
from runbookai_tpu.ops.moe import moe_ffn
from runbookai_tpu.ops.rope import apply_rope


@dataclass(frozen=True)
class LlamaConfig(Family):
    name: str
    vocab_size: int
    dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    ffn_dim: int
    rope_theta: float = 500_000.0
    # Llama-3.1 long-context rope scaling (NTK-by-parts): tuple
    # (factor, low_freq_factor, high_freq_factor, original_max_pos) or
    # None. Set from HF config.json's rope_scaling (rope_type "llama3").
    rope_scaling: Optional[tuple] = None
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    # Qwen2-family attention: biases on the q/k/v projections only (HF
    # ``Qwen2Attention``); Llama/Mistral run bias-free. The scan-stacked
    # layer dict simply carries three extra [L, heads*hd] leaves.
    qkv_bias: bool = False
    # Model family ("llama" | "qwen2" | "mistral" | "mixtral") — drives the
    # chat template. Set from HF config.json's authoritative ``model_type``
    # by the loader; name sniffing is only the fallback for bare names.
    family: str = "llama"
    # Mixture-of-Experts (Mixtral): 0 = dense FFN. When > 0 the FFN leaves
    # gain a leading expert axis ([L, E, D, F]) plus a router [L, D, E],
    # and the block runs :func:`runbookai_tpu.ops.moe.moe_ffn`. Expert
    # parallelism shards the E axis over the mesh's model axis.
    n_experts: int = 0
    top_k_experts: int = 2
    # Per-expert queue headroom. 0 (default) = dropless: capacity N, exact
    # Mixtral/transformers numerics at E× the buffer cost. Perf-tuned
    # serving can trade exactness for smaller dispatch buffers by setting
    # e.g. 1.25–2.0 (token-expert assignments past the capacity drop).
    capacity_factor: float = 0.0

    family_name = "llama"
    hf_model_types = ("llama", "qwen2", "mistral", "mixtral")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def kv_pool_spec(self) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
        """The pool's two sides, each (layers, heads, values a head)."""
        side = (self.n_layers, self.n_kv_heads, self.head_dim)
        return side, side

    def forwards(self):
        return forward_counted, forward_ragged_counted

    def init_params(self, key, dtype=jnp.bfloat16, quantized=False) -> Params:
        # int8 leaves are sampled directly: a 7B bf16 tree (15 GB) plus the
        # float32 temporaries of quantizing it cannot exist on a 16 GB chip.
        return (init_params_quantized if quantized else init_params)(key, self, dtype)

    def weight_bytes_per_chip(self, tp: int, weights: str, kv_shards: int) -> float:
        """This family is laid out across chips: wk/wv shard ``kv_shards``-way
        only, everything else full-tp; the norms are replicated."""
        layer_matmul = self.matmul_params - self.dim * self.vocab_size
        wkv = self.n_layers * 2 * self.dim * self.n_kv_heads * self.head_dim
        emb_head = 2 * self.vocab_size * self.dim  # embed + lm head (or tied x2)
        if weights == "int8":
            per_chip = ((layer_matmul - wkv) / max(tp, 1)
                        + wkv / max(kv_shards, 1)
                        + layer_matmul / self.dim * 4 / max(tp, 1)  # scales
                        + emb_head * 2 / max(tp, 1))  # bf16
        else:
            per_chip = ((layer_matmul - wkv) * 2 / max(tp, 1)
                        + wkv * 2 / max(kv_shards, 1)
                        + emb_head * 2 / max(tp, 1))
        return per_chip + (self.n_layers * 2 + 1) * self.dim * 4

    @classmethod
    def from_hf(cls, raw: dict, name: str) -> "LlamaConfig":
        model_type = raw.get("model_type", "llama")
        # Llama-3.1-style long-context rope scaling (rope_type "llama3").
        # Other scaling schemes (linear/dynamic/yarn) would silently produce
        # wrong logits past the original context if dropped — refuse loudly,
        # matching the unsupported-model_type behavior.
        rs = raw.get("rope_scaling") or {}
        rope_scaling = None
        rs_type = rs.get("rope_type", rs.get("type"))
        if rs_type == "llama3":
            rope_scaling = (
                float(rs["factor"]),
                float(rs.get("low_freq_factor", 1.0)),
                float(rs.get("high_freq_factor", 4.0)),
                int(rs.get("original_max_position_embeddings", 8192)),
            )
        elif rs_type not in (None, "default"):
            raise ValueError(
                f"rope_scaling type {rs_type!r} not supported (only 'llama3'); "
                f"loading without it would silently change long-context numerics")
        return cls(
            name=name,
            vocab_size=raw["vocab_size"],
            dim=raw["hidden_size"],
            n_layers=raw["num_hidden_layers"],
            n_heads=raw["num_attention_heads"],
            n_kv_heads=raw.get("num_key_value_heads", raw["num_attention_heads"]),
            ffn_dim=raw["intermediate_size"],
            rope_theta=raw.get("rope_theta", 500_000.0),
            rope_scaling=rope_scaling,
            norm_eps=raw.get("rms_norm_eps", 1e-5),
            # The Llama block declares no window (a family that does serves it
            # as one: models/afmoe.py): its sliding-window checkpoints (Mistral
            # v0.1) are served with full attention — exact only up to the window,
            # so the window clamps the serveable context rather than silently
            # changing semantics past it.
            max_seq_len=min(raw.get("max_position_embeddings", 8192),
                            raw.get("sliding_window") or 1 << 30),
            tie_embeddings=raw.get("tie_word_embeddings", False),
            qkv_bias=model_type == "qwen2",
            family=model_type,
            n_experts=raw.get("num_local_experts", 0) if model_type == "mixtral" else 0,
            top_k_experts=raw.get("num_experts_per_tok", 2),
        )

    @property
    def matmul_params(self) -> int:
        """Analytic count of params that participate in matmuls *per token*
        (layer projections + LM head; excludes the embedding gather) — the
        ``N`` in the decode-FLOPs model ``2·N`` used for MFU reporting. For
        MoE this counts the ``top_k`` ACTIVE experts (the FLOPs actually
        spent per token), not the full expert bank."""
        D, hd = self.dim, self.head_dim
        ffn_mult = self.top_k_experts if self.n_experts else 1
        per_layer = (
            D * self.n_heads * hd          # wq
            + 2 * D * self.n_kv_heads * hd  # wk, wv
            + self.n_heads * hd * D         # wo
            + ffn_mult * 3 * D * self.ffn_dim  # active FFN experts
            + (D * self.n_experts if self.n_experts else 0)  # router
        )
        return self.n_layers * per_layer + D * self.vocab_size

    @property
    def total_params(self) -> int:
        """All weights, including every expert (the memory-side count)."""
        D = self.dim
        embed = self.vocab_size * D * (1 if self.tie_embeddings else 2)
        norms = self.n_layers * 2 * D + D
        ffn_mult = self.n_experts if self.n_experts else 1
        ffn_delta = (ffn_mult - (self.top_k_experts if self.n_experts else 1)
                     ) * 3 * D * self.ffn_dim * self.n_layers
        return (self.matmul_params - D * self.vocab_size + embed + norms
                + ffn_delta)


# This family's entries of the registry (``family.CONFIGS``, which this
# module's ``CONFIGS`` IS: the other families' files add theirs to it).
register({
    "llama3-8b-instruct": LlamaConfig(
        name="llama3-8b-instruct", vocab_size=128_256, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_dim=14_336,
    ),
    "llama3-70b-instruct": LlamaConfig(
        name="llama3-70b-instruct", vocab_size=128_256, dim=8192, n_layers=80,
        n_heads=64, n_kv_heads=8, ffn_dim=28_672,
    ),
    "llama3-1b-bench": LlamaConfig(
        # Small-dim stand-in for quick single-chip bench sanity runs.
        name="llama3-1b-bench", vocab_size=128_256, dim=2048, n_layers=16,
        n_heads=32, n_kv_heads=8, ffn_dim=8192,
    ),
    # Llama-3.1/3.2: same blocks with NTK-by-parts rope scaling for 128k
    # contexts; 3.2 ties embeddings. (8B dims match llama3-8b.)
    "llama3.1-8b-instruct": LlamaConfig(
        name="llama3.1-8b-instruct", vocab_size=128_256, dim=4096,
        n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14_336,
        max_seq_len=131_072, rope_scaling=(8.0, 1.0, 4.0, 8192),
    ),
    "llama3.1-70b-instruct": LlamaConfig(
        name="llama3.1-70b-instruct", vocab_size=128_256, dim=8192,
        n_layers=80, n_heads=64, n_kv_heads=8, ffn_dim=28_672,
        max_seq_len=131_072, rope_scaling=(8.0, 1.0, 4.0, 8192),
    ),
    # Llama-3.3-70B ships the 3.1-70B architecture exactly (dims, rope
    # scaling, 128k window) — served under its own name for HF parity.
    "llama3.3-70b-instruct": LlamaConfig(
        name="llama3.3-70b-instruct", vocab_size=128_256, dim=8192,
        n_layers=80, n_heads=64, n_kv_heads=8, ffn_dim=28_672,
        max_seq_len=131_072, rope_scaling=(8.0, 1.0, 4.0, 8192),
    ),
    "llama3.2-1b-instruct": LlamaConfig(
        name="llama3.2-1b-instruct", vocab_size=128_256, dim=2048,
        n_layers=16, n_heads=32, n_kv_heads=8, ffn_dim=8192,
        max_seq_len=131_072, rope_scaling=(32.0, 1.0, 4.0, 8192),
        tie_embeddings=True,
    ),
    "llama3.2-3b-instruct": LlamaConfig(
        name="llama3.2-3b-instruct", vocab_size=128_256, dim=3072,
        n_layers=28, n_heads=24, n_kv_heads=8, ffn_dim=8192,
        max_seq_len=131_072, rope_scaling=(32.0, 1.0, 4.0, 8192),
        tie_embeddings=True,
    ),
    "llama3-test": LlamaConfig(
        # Tiny config for CPU tests; vocab matches the byte tokenizer (262).
        # max_seq_len covers real agent/orchestrator prompts (byte tokenizer:
        # 1 token per byte), so live-eval e2e runs fit without truncation.
        name="llama3-test", vocab_size=262, dim=64, n_layers=2, n_heads=4,
        n_kv_heads=2, ffn_dim=128, max_seq_len=8192, rope_theta=10_000.0,
    ),
    # Qwen2 family: identical block structure with q/k/v projection biases
    # and ChatML prompts (HF ``Qwen2ForCausalLM``; config.json model_type
    # "qwen2"). Serving/training/TP paths are shared with Llama.
    "qwen2-7b-instruct": LlamaConfig(
        name="qwen2-7b-instruct", vocab_size=152_064, dim=3584, n_layers=28,
        n_heads=28, n_kv_heads=4, ffn_dim=18_944, rope_theta=1_000_000.0,
        max_seq_len=32_768, qkv_bias=True, family="qwen2",
    ),
    # Qwen2.5-7B ships the same architecture/dims as Qwen2-7B (vocab,
    # qkv biases, theta) — served under its own name for HF parity.
    "qwen2.5-7b-instruct": LlamaConfig(
        name="qwen2.5-7b-instruct", vocab_size=152_064, dim=3584,
        n_layers=28, n_heads=28, n_kv_heads=4, ffn_dim=18_944,
        rope_theta=1_000_000.0, max_seq_len=32_768, qkv_bias=True,
        family="qwen2",
    ),
    "qwen2.5-14b-instruct": LlamaConfig(
        name="qwen2.5-14b-instruct", vocab_size=152_064, dim=5120,
        n_layers=48, n_heads=40, n_kv_heads=8, ffn_dim=13_824,
        rope_theta=1_000_000.0, max_seq_len=32_768, qkv_bias=True,
        family="qwen2",
    ),
    "qwen2.5-32b-instruct": LlamaConfig(
        name="qwen2.5-32b-instruct", vocab_size=152_064, dim=5120,
        n_layers=64, n_heads=40, n_kv_heads=8, ffn_dim=27_648,
        rope_theta=1_000_000.0, max_seq_len=32_768, qkv_bias=True,
        family="qwen2",
    ),
    "qwen2-test": LlamaConfig(
        name="qwen2-test", vocab_size=262, dim=64, n_layers=2, n_heads=4,
        n_kv_heads=2, ffn_dim=128, max_seq_len=8192, rope_theta=10_000.0,
        qkv_bias=True, family="qwen2",
    ),
    # Mistral v0.3: Llama block structure exactly (GQA, no bias), different
    # dims/vocab/theta. Sliding-window variants (v0.1) are served with full
    # attention — exact for contexts ≤ the window (4096).
    "mistral-7b-instruct": LlamaConfig(
        name="mistral-7b-instruct", vocab_size=32_768, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_dim=14_336, rope_theta=1_000_000.0,
        max_seq_len=32_768, family="mistral",
    ),
    # Mixtral 8x7B: Mistral attention + 8-expert top-2 MoE FFN. Serving on
    # v5e needs int8 + TP/EP (47B total params); the test config exercises
    # the identical code path on CPU.
    "mixtral-8x7b-instruct": LlamaConfig(
        name="mixtral-8x7b-instruct", vocab_size=32_000, dim=4096,
        n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14_336,
        rope_theta=1_000_000.0, max_seq_len=32_768, family="mixtral",
        n_experts=8, top_k_experts=2,
    ),
    "mixtral-test": LlamaConfig(
        name="mixtral-test", vocab_size=262, dim=64, n_layers=2, n_heads=4,
        n_kv_heads=2, ffn_dim=128, max_seq_len=8192, rope_theta=10_000.0,
        family="mixtral", n_experts=4, top_k_experts=2,
    ),
})


def _layer_shapes(cfg: LlamaConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The stacked layer matrices as ``name -> (shape, fan_in)`` — the
    single source of truth shared by the bf16 and direct-int8 inits. MoE
    configs put a leading expert axis on the FFN leaves (+ a router, which
    stays un-quantized — it's tiny and routing is precision-critical)."""
    L, D, KV, F = cfg.n_layers, cfg.dim, cfg.n_kv_heads, cfg.ffn_dim
    H, hd = cfg.n_heads, cfg.head_dim
    shapes = {
        "wq": ((L, D, H * hd), D),
        "wk": ((L, D, KV * hd), D),
        "wv": ((L, D, KV * hd), D),
        "wo": ((L, H * hd, D), H * hd),
    }
    if cfg.n_experts:
        E = cfg.n_experts
        shapes.update({
            "w_gate": ((L, E, D, F), D),
            "w_up": ((L, E, D, F), D),
            "w_down": ((L, E, F, D), F),
            "router": ((L, D, E), D),
        })
    else:
        shapes.update({
            "w_gate": ((L, D, F), D),
            "w_up": ((L, D, F), D),
            "w_down": ((L, F, D), F),
        })
    return shapes


def _build_params(key: jax.Array, cfg: LlamaConfig, dtype,
                  layer_factory=None) -> Params:
    """Shared init skeleton; ``layer_factory(key, shape, fan_in)`` makes the
    seven stacked layer matrices (default: the same scaled-normal ``dense``
    used for embed/lm_head; the int8 init passes ``qdense``)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    L, D = cfg.n_layers, cfg.dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                / jnp.sqrt(fan_in)).astype(dtype)

    if layer_factory is None:
        layer_factory = dense
    shapes = _layer_shapes(cfg)
    ks = jax.random.split(k_layers, len(shapes))
    layers: dict[str, Any] = {
        # The router stays in the dense dtype even under int8 init —
        # routing logits are precision-critical and the tensor is tiny.
        name: (dense if name == "router" else layer_factory)(k, shape, fan_in)
        for k, (name, (shape, fan_in)) in zip(ks, shapes.items())
    }
    layers["attn_norm"] = jnp.ones((L, D), dtype=jnp.float32)
    layers["mlp_norm"] = jnp.ones((L, D), dtype=jnp.float32)
    if cfg.qkv_bias:
        hd = cfg.head_dim
        layers["bq"] = jnp.zeros((L, cfg.n_heads * hd), dtype=dtype)
        layers["bk"] = jnp.zeros((L, cfg.n_kv_heads * hd), dtype=dtype)
        layers["bv"] = jnp.zeros((L, cfg.n_kv_heads * hd), dtype=dtype)
    params: Params = {
        "embed": dense(k_embed, (cfg.vocab_size, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype=jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, (D, cfg.vocab_size), D)
    return params


def init_params(key: jax.Array, cfg: LlamaConfig, dtype=jnp.bfloat16) -> Params:
    """Random-init params (scaled normal). Layer weights stacked on axis 0."""
    return _build_params(key, cfg, dtype)


def init_params_quantized(key: jax.Array, cfg: LlamaConfig,
                          dtype=jnp.bfloat16) -> Params:
    """Random-init params with the seven layer matrices directly in int8.

    For big-model benchmarking on one chip: 8B bf16 is ~16GB and cannot be
    materialized then quantized on a 16GB-HBM v5e. Sampling ``q`` uniform
    int8 with a per-channel scale chosen so the dequantized std matches the
    scaled-normal init (1/sqrt(fan_in)) gives the same matmul cost and
    magnitude as quantizing real weights, without the bf16 intermediate.
    Leaves match :mod:`runbookai_tpu.models.quant` (``{"q": int8, "s": f32}``).
    """

    def qdense(key, shape, fan_in):
        q = jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)
        # uniform[-127,127] has std 127/sqrt(3); scale to std 1/sqrt(fan_in)
        scale = float(3 ** 0.5 / (127.0 * fan_in ** 0.5))
        s = jnp.full(shape[:-2] + (1, shape[-1]), scale, dtype=jnp.float32)
        return {"q": q, "s": s}

    return _build_params(key, cfg, dtype, qdense)


def ffn_block(y: jnp.ndarray, lp: dict, cfg: LlamaConfig,
              qmm_impl: str = "xla") -> jnp.ndarray:
    """SwiGLU FFN (dense) or Mixtral MoE, by config — shared by the paged
    serving forward, the dense training forward, and the pipeline stages.
    Residual is added by the caller."""
    if cfg.n_experts:
        return moe_ffn(y, lp["router"], lp["w_gate"], lp["w_up"],
                       lp["w_down"], cfg.top_k_experts, cfg.capacity_factor)
    mm = partial(qmm, impl=qmm_impl)
    return mm(jax.nn.silu(mm(y, lp["w_gate"])) * mm(y, lp["w_up"]),
              lp["w_down"])


def _forward_hidden(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B, T] int32 token ids for the current chunk
    positions: jnp.ndarray,  # [B, T] absolute positions (pad with pos of last real)
    kv_k: jnp.ndarray,  # [n_layers, num_pages * page_size, n_kv, head_dim]
    kv_v: jnp.ndarray,  # same
    page_tables: jnp.ndarray,  # [B, max_pages]
    ctx_lens: jnp.ndarray,  # [B] cache length AFTER this chunk
    page_size: int,
    block_pages: int = 32,
    attn_impl: str = "xla",
    mesh=None,
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] int32 LoRA rows
    qmm_impl: str = "xla",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Transformer stack over one paged chunk, WITHOUT the LM head.

    Returns (hidden [B, T, D], kv_k', kv_v'). Shared by
    :func:`forward_impl` (full [B, T, vocab] logits) and
    :func:`forward_ragged_impl` (mixed prefill+decode batches, which gather
    the few rows they need before paying for the vocab projection).

    The scan over layers carries ``(hidden, kv_k, kv_v)``; its ``xs`` are
    the layer's parameters and its number — less the int8 matrices the
    Pallas matmul reads in place in their stacked arrays. The page
    writers (the flat scatter, and kv-split's ``shard_map`` one) take the
    whole pool and that number and write their rows in place, and the
    Pallas attention kernels read their pages from it the same way
    (``paged_attention_pallas.reads_in_place``); XLA's readers take the
    layer's slice of the carry.
    """
    b, t = tokens.shape
    hd, n_kv = cfg.head_dim, cfg.n_kv_heads
    h = params["embed"][tokens]  # [B, T, D]
    lora = params.get("lora")  # {leaf: {"A": [L,N,in,r], "B": [L,N,r,out]}}
    if lora is not None and adapter_ids is None:
        adapter_ids = jnp.zeros((b,), jnp.int32)  # zero adapter = base

    if lora is not None:
        from runbookai_tpu.models.lora import apply_lora  # deferred: cycle

    # KV page-split serving (parallel/kv_split.py): a serving mesh with a
    # seq axis shards the page pool's token axis past the GQA head count;
    # page writes and attention then run as shard_map with a flash-partial
    # merge across the seq axis.
    kv_split_active = False
    if mesh is not None:
        from runbookai_tpu.parallel.mesh import SEQ_AXIS

        kv_split_active = mesh.shape.get(SEQ_AXIS, 1) > 1
    # int8 KV pools are (values, scales) tuples — XLA gather path only.
    # Checked BEFORE any page write: the kv-split writer has no scale
    # plumbing and would fail opaquely on a tuple mid-scan. (The engine
    # refuses this combination at init; this covers direct callers.)
    kv_quantized = isinstance(kv_k, tuple)
    if kv_quantized and kv_split_active:
        raise ValueError("int8 KV is not supported with the KV "
                         "page-split mesh")

    # The Pallas qmm runs per-device code; under a TP mesh the layer
    # matmuls are partitioned by XLA SPMD (sharding annotations, not
    # shard_map), so the kernel path is single-model-shard only. DP-only
    # meshes keep it: the weights are replicated per device.
    if qmm_impl == "pallas" and mesh is not None:
        from runbookai_tpu.parallel.mesh import MODEL_AXIS

        if mesh.shape.get(MODEL_AXIS, 1) > 1 or kv_split_active:
            qmm_impl = "xla"
    mm = partial(qmm, impl=qmm_impl)
    # An int8 matrix that the Pallas kernel reads in place at this
    # program's M (the decode programs; a static shape test,
    # ``reads_in_place``) does NOT ride the scan's ``xs``: a scan's
    # per-layer slice does not fuse into a custom call, so XLA copied
    # ``s8[K, N]`` out of the stack before every call and each matrix was
    # handled twice a layer. Those reach ``layer_step`` stacked, with the
    # layer's number. Their scales, every other leaf, and every leaf of a
    # program the kernel does not cover (mixed and prefill M) are ``xs``.
    layers, stacked = params["layers"], {}
    if qmm_impl == "pallas":
        from runbookai_tpu.ops.qmm_pallas import reads_in_place

        stacked = {name: w["q"] for name, w in layers.items()
                   if isinstance(w, dict) and w["q"].ndim == 3
                   and reads_in_place(b * t, w["q"].shape)}
        layers = {name: {"s": w["s"]} if name in stacked else w
                  for name, w in layers.items()}

    # Whether the Pallas attention kernels read the carried pool where it
    # lies: static, by the pool's shape.
    in_place = False
    if attn_impl == "pallas":
        from runbookai_tpu.ops.paged_attention_pallas import reads_in_place

        in_place = reads_in_place(kv_k, mesh)

    def layer_step(carry, layer_in):
        # The pool rides the CARRY, whole: a scan's stacked output can
        # never share its scanned input's buffer, so handing the pool in
        # as ``xs`` and taking it back as ``ys`` copied all of it every
        # pass and wrote every layer through again.
        hidden, kv_k, kv_v = carry
        lp, lp_lora, li = layer_in
        lp = {**lp, **{name: {"q": q, "s": lp[name]["s"], "layer": li}
                       for name, q in stacked.items()}}
        x = rms_norm(hidden, lp["attn_norm"], cfg.norm_eps)
        q, k, v = mm(x, lp["wq"]), mm(x, lp["wk"]), mm(x, lp["wv"])
        if lp_lora is not None:
            q = q + apply_lora(x, lp_lora, "wq", adapter_ids)
            k = k + apply_lora(x, lp_lora, "wk", adapter_ids)
            v = v + apply_lora(x, lp_lora, "wv", adapter_ids)
        if cfg.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(b, t, cfg.n_heads, hd)
        k = k.reshape(b, t, n_kv, hd)
        v = v.reshape(b, t, n_kv, hd)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)

        # Scatter the whole batch's K/V into layer ``li`` of the pool in
        # one scatter (program size stays flat as max_batch_slots grows;
        # disjoint page ownership makes flattened destinations
        # collision-free).
        if kv_split_active:
            from runbookai_tpu.parallel.kv_split import (
                write_kv_pages_batch_kv_split,
            )

            kv_k = write_kv_pages_batch_kv_split(
                mesh, kv_k, k, positions, page_tables, page_size, layer=li)
            kv_v = write_kv_pages_batch_kv_split(
                mesh, kv_v, v, positions, page_tables, page_size, layer=li)
        else:
            kv_k = write_kv_pages_batch(kv_k, k, positions, page_tables,
                                        page_size, layer=li)
            kv_v = write_kv_pages_batch(kv_v, v, positions, page_tables,
                                        page_size, layer=li)
        # The Pallas kernels take the carried pool and the layer's
        # number: they walk a row's (a query block's) live pages inside
        # the kernel, 16 copies a source in flight out of HBM (decode
        # since PR 28, chunks since PR 36), at ``layer x pages + id`` of
        # the pool's page view, a bitcast. A slice in front of them is a
        # copy XLA stages on chip whole — 2 x 50 MB a layer in the 7B
        # cell, five times the decode kernel it fed (PERF.md section 6,
        # PR 38). XLA's readers gather out of the slice, and so do the
        # kernels where the pool is not read in place (one that fits
        # on-chip memory, or whose page view must be padded): the same
        # kernel at L = 1.
        def layer_slice(pool):  # int8 pools are (values, scales)
            return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, li, keepdims=False), pool)

        k_pages, v_pages = layer_slice(kv_k), layer_slice(kv_v)
        k_walk, v_walk, layer = ((kv_k, kv_v, li) if in_place
                                 else (k_pages, v_pages, None))

        # int8 pools: the decode kernel reads int8 pages + scales
        # directly (widened in VMEM); chunked prefill is compute-bound
        # and stays on the XLA gather path; the per-head-shard shard_map
        # path has no scale plumbing (mesh model>1 falls back below).
        use_pallas = (attn_impl == "pallas" and not kv_split_active
                      and (not kv_quantized or t == 1))
        shardable = False
        if use_pallas and kv_quantized and mesh is not None:
            from runbookai_tpu.parallel.mesh import MODEL_AXIS

            if mesh.shape.get(MODEL_AXIS, 1) > 1:
                use_pallas = False
        elif use_pallas and mesh is not None:
            from runbookai_tpu.ops.paged_attention_pallas import tp_shardable
            from runbookai_tpu.parallel.mesh import MODEL_AXIS

            # On a TP mesh the kernel must run per head-shard (shard_map);
            # when GQA kv heads don't divide the axis the pool replicates
            # (kv_pool_sharding) and the XLA gather path is the honest
            # fallback rather than an implicit every-step all-gather.
            shardable = tp_shardable(mesh, n_kv)
            if mesh.shape.get(MODEL_AXIS, 1) > 1 and not shardable:
                use_pallas = False
        if use_pallas:
            from runbookai_tpu.ops.paged_attention_pallas import (
                paged_chunk_attention,
                paged_chunk_attention_tp,
                paged_decode_attention,
                paged_decode_attention_tp,
            )

            # Interpret mode on CPU keeps the kernel path testable on the
            # virtual mesh; on TPU this compiles under Mosaic.
            interp = jax.default_backend() == "cpu"
            if shardable:
                if t == 1:
                    attn = paged_decode_attention_tp(
                        mesh, q[:, 0], k_walk, v_walk, page_tables,
                        ctx_lens, page_size=page_size, interpret=interp,
                        layer=layer)[:, None]
                else:
                    attn = paged_chunk_attention_tp(
                        mesh, q, k_walk, v_walk, page_tables, ctx_lens,
                        positions, page_size=page_size, interpret=interp,
                        layer=layer)
            elif t == 1:
                attn = paged_decode_attention(
                    q[:, 0], k_walk, v_walk, page_tables, ctx_lens,
                    page_size=page_size, interpret=interp, layer=layer,
                )[:, None]
            else:
                attn = paged_chunk_attention(
                    q, k_walk, v_walk, page_tables, ctx_lens, positions,
                    page_size=page_size, interpret=interp, layer=layer)
        elif kv_split_active:
            from runbookai_tpu.parallel.kv_split import (
                paged_attention_kv_split,
                paged_decode_attention_kv_split_pallas,
            )

            if attn_impl == "pallas" and t == 1:
                # Decode hot loop on the Pallas partial kernel (ownership-
                # masked local pages + seq-axis flash merge); chunked
                # prefill stays on the XLA kv-split path (compute-bound).
                attn = paged_decode_attention_kv_split_pallas(
                    mesh, q[:, 0], k_walk, v_walk, page_tables, ctx_lens,
                    page_size=page_size,
                    interpret=jax.default_backend() == "cpu",
                    layer=layer)[:, None]
            else:
                attn = paged_attention_kv_split(
                    mesh, q, k_pages, v_pages, page_tables, ctx_lens,
                    positions, page_size=page_size, block_pages=block_pages)
        else:
            attn = paged_attention(
                q, k_pages, v_pages, page_tables, ctx_lens, positions,
                page_size=page_size, block_pages=block_pages,
            )
        ctx = attn.reshape(b, t, cfg.n_heads * hd)
        o = mm(ctx, lp["wo"])
        if lp_lora is not None:
            o = o + apply_lora(ctx, lp_lora, "wo", adapter_ids)
        hidden = hidden + o

        y = rms_norm(hidden, lp["mlp_norm"], cfg.norm_eps)
        hidden = hidden + ffn_block(y, lp, cfg, qmm_impl=qmm_impl)
        return (hidden, kv_k, kv_v), None

    (h, kv_k, kv_v), _ = jax.lax.scan(
        layer_step, (h, kv_k, kv_v),
        (layers, lora, jnp.arange(cfg.n_layers, dtype=jnp.int32)),
    )
    return h, kv_k, kv_v


# The step programs' pair (``LlamaConfig.forwards``): the one serving
# signature and six-field result of ``family.serving_forwards``.
forward_counted, forward_ragged_counted = serving_forwards(_forward_hidden)


def forward_impl(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B, T] int32 token ids for the current chunk
    positions: jnp.ndarray,  # [B, T] absolute positions (pad with pos of last real)
    kv_k: jnp.ndarray,  # [n_layers, num_pages * page_size, n_kv, head_dim]
    kv_v: jnp.ndarray,  # same
    page_tables: jnp.ndarray,  # [B, max_pages]
    ctx_lens: jnp.ndarray,  # [B] cache length AFTER this chunk
    page_size: int,
    block_pages: int = 32,
    attn_impl: str = "xla",
    mesh=None,
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] int32 LoRA rows
    qmm_impl: str = "xla",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One forward chunk. Returns (logits [B, T, vocab] f32, kv_k', kv_v').

    Raw (un-jitted) implementation so callers can inline it inside their own
    compiled step functions — nested jit inside lax.scan hangs some remote
    compile backends. ``attn_impl="pallas"`` selects the Pallas ragged paged
    decode kernel when T == 1; with a TP ``mesh`` the kernel runs per
    model-axis shard via shard_map (falling back to the XLA gather path only
    when GQA heads don't divide the axis — the pool replicates there too).
    Donate ``kv_k``/``kv_v`` at the jit call site for in-place page updates:
    the pool is the layer scan's carry and each layer scatters its rows
    into it, so with the donation the program writes B*T rows a layer into
    the caller's buffer and allocates no second pool (without it, XLA
    copies the pool once, on entry).
    """
    return forward_counted(
        params, cfg, tokens, positions, kv_k, kv_v, page_tables, ctx_lens,
        page_size, block_pages, attn_impl, mesh, adapter_ids, qmm_impl)[:3]


def forward_ragged_impl(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [N] int32 flat ragged token batch
    positions: jnp.ndarray,  # [N] absolute positions (pads: trash position)
    row_ids: jnp.ndarray,  # [N] int32 row (sequence) owning each token
    kv_k: jnp.ndarray,
    kv_v: jnp.ndarray,
    page_tables: jnp.ndarray,  # [R, max_pages(+1)] per-ROW page tables
    ctx_lens: jnp.ndarray,  # [R] cache length AFTER this step, per row
    sel_idx: jnp.ndarray,  # [S] flat token indices whose logits are wanted
    page_size: int,
    block_pages: int = 32,
    attn_impl: str = "xla",
    mesh=None,
    adapter_ids: Optional[jnp.ndarray] = None,  # [R] int32 LoRA rows, per row
    qmm_impl: str = "xla",
    ragged_block: int = 8,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Mixed prefill+decode forward over ONE flat ragged token batch.

    The serving entry for the unified mixed dispatch (PAPERS.md "Ragged
    Paged Attention"): decode rows contribute one token each and prefill
    rows a whole chunk, flattened into a single [N] buffer with per-token
    ``row_ids`` selecting each token's page-table row / context length /
    adapter.

    Layout contract (the engine's builder upholds it): every row's token
    run is contiguous ascending and starts at a multiple of
    ``ragged_block``; pad tokens carry the trash position (their K/V land
    in the reserved null page) and either their run's row id or a
    dedicated null row with ``ctx_len = 0``. Under that alignment each
    ``ragged_block``-sized block belongs to exactly one row, so the whole
    stack runs as a [N/ragged_block, ragged_block] chunked forward with
    per-BLOCK gathered tables — the same transform
    :func:`runbookai_tpu.ops.attention.ragged_paged_attention` and the
    Pallas ``paged_ragged_attention`` apply per attention call, hoisted
    above the layer scan (``family.serving_forwards``, for every family
    alike) so KV writes and page loads share it.

    Returns (logits [S, vocab] f32 for the ``sel_idx`` tokens only — the
    vocab projection is paid for S rows, not N — kv_k', kv_v').
    """
    return forward_ragged_counted(
        params, cfg, tokens, positions, row_ids, kv_k, kv_v, page_tables,
        ctx_lens, sel_idx, page_size, block_pages, attn_impl, mesh, adapter_ids,
        qmm_impl, ragged_block)[:3]


forward = partial(jax.jit, static_argnames=("cfg", "page_size", "block_pages",
                                            "attn_impl", "mesh",
                                            "qmm_impl"))(forward_impl)


def dense_causal_attention(cfg: LlamaConfig, b: int, t: int):
    """Default training attention: materialized causal softmax over [T, T]."""
    hd, n_kv, n_q = cfg.head_dim, cfg.n_kv_heads, cfg.n_heads
    group = n_q // n_kv
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))

    def attn_fn(q, k, v):
        qg = (q * (1.0 / jnp.sqrt(jnp.float32(hd)))).reshape(b, t, n_kv, group, hd)
        scores = jnp.einsum("btkgd,bskd->btkgs", qg.astype(jnp.float32),
                            k.astype(jnp.float32))
        scores = jnp.where(causal[None, :, None, None, :], scores, -1e30)
        attn = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("btkgs,bskd->btkgd", attn, v).reshape(b, t, n_q, hd)

    return attn_fn


def transformer_layer(hidden, lp, cfg: LlamaConfig, positions, attn_fn,
                      lora_lp=None, adapter_ids=None):
    """One pre-norm attention + SwiGLU block — shared by every forward path
    (dense training, sequence-parallel ring, pipeline stages). ``lora_lp``
    (one layer's stacked adapters) + ``adapter_ids`` apply per-row LoRA,
    exactly as the serving forward does — the fine-tuning path trains the
    same tree serving gathers from."""
    b, t = hidden.shape[:2]
    hd, n_kv, n_q = cfg.head_dim, cfg.n_kv_heads, cfg.n_heads
    if lora_lp is not None:
        from runbookai_tpu.models.lora import apply_lora
    x = rms_norm(hidden, lp["attn_norm"], cfg.norm_eps)
    q, k, v = qmm(x, lp["wq"]), qmm(x, lp["wk"]), qmm(x, lp["wv"])
    if lora_lp is not None:
        q = q + apply_lora(x, lora_lp, "wq", adapter_ids)
        k = k + apply_lora(x, lora_lp, "wk", adapter_ids)
        v = v + apply_lora(x, lora_lp, "wv", adapter_ids)
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = apply_rope(q.reshape(b, t, n_q, hd), positions, cfg.rope_theta,
                   cfg.rope_scaling)
    k = apply_rope(k.reshape(b, t, n_kv, hd), positions, cfg.rope_theta,
                   cfg.rope_scaling)
    v = v.reshape(b, t, n_kv, hd)
    ctx = attn_fn(q, k, v).reshape(b, t, n_q * hd)
    o = qmm(ctx, lp["wo"])
    if lora_lp is not None:
        o = o + apply_lora(ctx, lora_lp, "wo", adapter_ids)
    hidden = hidden + o
    y = rms_norm(hidden, lp["mlp_norm"], cfg.norm_eps)
    return hidden + ffn_block(y, lp, cfg)


def forward_train(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,  # [B, T] absolute positions
    attn_fn=None,  # (q [B,T,n_q,hd], k [B,T,n_kv,hd], v) -> [B,T,n_q,hd]
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] LoRA rows
) -> jnp.ndarray:
    """Training-mode forward: dense causal attention over [B, T], no KV cache.

    Used by the fine-tuning path and the multi-chip dry-run; shares every
    parameter and norm with the serving forward, differing only in attention
    materialization (XLA fuses the masked softmax; sequence fits in one pass).
    ``attn_fn`` swaps the attention implementation while keeping the rest of
    the layer identical — the sequence-parallel path passes ring attention
    here (``parallel/sequence_parallel.py``) so the two forwards cannot drift.
    """
    b, t = tokens.shape
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (b, t))
    if attn_fn is None:
        attn_fn = dense_causal_attention(cfg, b, t)

    h = params["embed"][tokens]
    lora = params.get("lora")
    if lora is not None and adapter_ids is None:
        adapter_ids = jnp.zeros((b,), jnp.int32)

    def layer_step(hidden, layer_in):
        lp, lp_lora = layer_in
        return transformer_layer(hidden, lp, cfg, positions, attn_fn,
                                 lora_lp=lp_lora,
                                 adapter_ids=adapter_ids), None

    h, _ = jax.lax.scan(layer_step, h, (params["layers"], lora))
    return lm_head_logits(params, cfg, h)
