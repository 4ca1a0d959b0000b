"""LongCat-Flash in JAX: latent (MLA) attention over a paged latent cache,
the shortcut-connected DOUBLE layer, and an expert layer that holds a share
of the routed experts beside identity ("zero-computation") experts.

Source: ``meituan-longcat/LongCat-Flash-Chat`` ``config.json`` (the field
names below are that file's, so a configuration file that copies it is
checked key by key). One published layer, with ``h`` the residual stream::

    for i in (0, 1):
        a = h + MLA_i(RMSNorm(h; g_in_i))
        u = RMSNorm(a; g_post_i)
        if i == 0: m = MoE(u)      # the shortcut: from the FIRST sublayer's u
        h = a + FFN_i(u)           # dense SwiGLU
    h = h + m                      # joins after the second dense FFN

``MLA``: ``cq = RMSNorm(x Wqa) sqrt(D / q_rank)``; ``q = cq Wqb``, per head
``[q_nope | q_rope]``; ``[c | kr] = x Wkva``; ``c = RMSNorm(c) sqrt(D /
kv_rank)``; RoPE on ``kr`` (ONE for all heads) and ``q_rope``;
``[k_nope_h | v_h] = c Wkvb``; scores over ``sqrt(nope + rope)``. The cache
holds ``c`` and ``kr`` — ``kv_rank + rope`` values a token and attention
sublayer, nothing per head — and attention runs in the absorbed form
(:mod:`runbookai_tpu.ops.mla`), for decode and for a prefill chunk alike.

``MoE``: ``s = softmax_f32(u Wr)`` over routed + identity outputs; the
``moe_topk`` of ``s + b`` are chosen (``b`` moves the choice only);
``w_j = routed_scaling_factor * s_j``, not renormalised; ``m = sum_{chosen
routed j} w_j SwiGLU_j(u) + (sum_{chosen identity j} w_j) u``.

**The share.** ``n_experts_held`` experts from ``first_expert`` on live
here (one chip of an expert-parallel group). The router keeps every
output and every pick; this chip computes its own experts' part and the
identity part for its tokens, and what the absent experts would add is
left out — no code stands in for the other chips or their exchange.

The serving contract is :mod:`runbookai_tpu.models.family`'s
(``serving_forwards`` over :func:`_forward_hidden`), one ``lax.scan`` body
per (double) layer, the pool riding the scan's carry and written in place
at ``(2 * layer + i, dest)``. The pool is a pair, ``[2L, tokens, 1,
kv_rank]`` and ``[L, tokens, 1, 2 * rope]`` (a layer's two rotated keys in
one row), token rows on axis 1 of both, so page tables, prefix hashes,
export/import and spill see nothing new.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from runbookai_tpu.models.family import (
    EXPERT_COUNTS,
    Family,
    Params,
    _stacked_normal,
    register,
    serving_forwards,
)
from runbookai_tpu.ops.attention import write_kv_pages_batch
from runbookai_tpu.ops.dense import qmm, rms_norm
from runbookai_tpu.ops.mla import (
    absorb_queries,
    expand_values,
    latent_paged_attention,
)
from runbookai_tpu.ops.moe import held_capacity, held_expert_ffn, route_scaled
from runbookai_tpu.ops.rope import apply_rope


@dataclass(frozen=True)
class LongcatConfig(Family):
    name: str
    vocab_size: int
    hidden_size: int
    ffn_hidden_size: int
    expert_ffn_hidden_size: int
    num_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    zero_expert_num: int
    moe_topk: int
    routed_scaling_factor: float
    # The share of the routed experts this process holds: experts
    # ``first_expert .. first_expert + n_experts_held - 1`` of every layer.
    n_experts_held: int
    first_expert: int = 0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10_000_000.0
    max_position_embeddings: int = 131_072
    # Random init only: the balance bias b ~ N(0, scale^2). A checkpoint's b
    # is what its balancing left; zeros would make choice and weight agree,
    # which they do not in one.
    router_bias_scale: float = 1e-3
    family: str = "longcat"

    # The engine's Pallas attention kernels read per-head K/V pages; the
    # latent cache has none, so attention here is the XLA path whatever
    # ``attn_impl`` asks for (the engine resolves it to "xla" and says so).
    pallas_attention = False
    one_path = True
    family_name = "longcat"
    hf_model_types = ("longcat",)
    checkpoint_tensors = "MLA and expert tensor names"

    @property
    def dim(self) -> int:
        return self.hidden_size

    @property
    def n_layers(self) -> int:
        return self.num_layers

    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def kv_pool_spec(self) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
        """The pool's two sides, each (layers, heads, values a head): the
        latents of 2L attention sublayers, and the rotated keys, a layer's
        two sublayers side by side in one row (``ops/mla.py`` says why)."""
        return ((2 * self.num_layers, 1, self.kv_lora_rank),
                (self.num_layers, 1, 2 * self.qk_rope_head_dim))

    def forwards(self):
        return forward_counted, forward_ragged_counted

    def init_params(self, key, dtype=jnp.bfloat16, quantized=False) -> Params:
        return init_params(key, self, dtype)

    @property
    def _sublayer_params(self) -> int:
        d, h = self.hidden_size, self.num_attention_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        mla = (d * self.q_lora_rank + self.q_lora_rank * h * qk
               + d * (self.kv_lora_rank + self.qk_rope_head_dim)
               + self.kv_lora_rank * h * (self.qk_nope_head_dim + self.v_head_dim)
               + h * self.v_head_dim * d)
        return mla + 3 * d * self.ffn_hidden_size

    @property
    def _expert_params(self) -> int:
        return 3 * self.hidden_size * self.expert_ffn_hidden_size

    @property
    def matmul_params(self) -> int:
        """Params in matmuls per token, a held expert counted for its
        expected share of a token's picks (llama.py's ``N`` of ``2 N``)."""
        d, outputs = self.hidden_size, self.n_routed_experts + self.zero_expert_num
        per_layer = (2 * self._sublayer_params + d * outputs
                     + self.moe_topk * self.n_experts_held / outputs
                     * self._expert_params)
        return int(self.num_layers * per_layer + d * self.vocab_size)

    @property
    def total_params(self) -> int:
        """Every weight held HERE (the memory-side count)."""
        d, outputs = self.hidden_size, self.n_routed_experts + self.zero_expert_num
        per_layer = (2 * self._sublayer_params + d * outputs + outputs
                     + self.n_experts_held * self._expert_params
                     + 2 * (2 * d + self.q_lora_rank + self.kv_lora_rank))
        return self.num_layers * per_layer + 2 * d * self.vocab_size + d


CONFIGS: dict[str, LongcatConfig] = register({
    # The published model (config.json): 28 double layers, every routed
    # expert held. 560B parameters: no single process of this repo holds
    # it; it is the entry a cut configuration is checked against.
    "longcat-flash-chat": LongcatConfig(
        name="longcat-flash-chat", vocab_size=131_072, hidden_size=6144,
        ffn_hidden_size=12_288, expert_ffn_hidden_size=2048, num_layers=28,
        num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_routed_experts=512, zero_expert_num=256, moe_topk=12,
        routed_scaling_factor=6.0, n_experts_held=512,
    ),
    # One chip's share of it in a 32-chip expert-parallel group, cut to one
    # v5e chip (examples/serve/longcat-flash-ep32.yaml; the benchmark's
    # configuration file states the same cut): 4 of 28 layers, experts 0-15
    # of 512, an eighth of the vocabulary. 10.35 GB in bf16.
    "longcat-flash-ep32": LongcatConfig(
        name="longcat-flash-ep32", vocab_size=16_384, hidden_size=6144,
        ffn_hidden_size=12_288, expert_ffn_hidden_size=2048, num_layers=4,
        num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_routed_experts=512, zero_expert_num=256, moe_topk=12,
        routed_scaling_factor=6.0, n_experts_held=16,
    ),
    # Tiny, for CPU tests: byte-tokenizer vocabulary, 8 of 24 routed experts
    # held (the middle share), 8 identity experts, 2 double layers.
    "longcat-test": LongcatConfig(
        name="longcat-test", vocab_size=262, hidden_size=64,
        ffn_hidden_size=128, expert_ffn_hidden_size=32, num_layers=2,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=24, zero_expert_num=8, moe_topk=4,
        routed_scaling_factor=6.0, n_experts_held=8, first_expert=8,
        rope_theta=10_000.0, max_position_embeddings=8192,
        router_bias_scale=2e-2,
    ),
})


def leaf_shapes(cfg: LongcatConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The stacked matrices as ``name -> (shape, fan_in)``, in init order.
    Per-sublayer leaves are ``[L, 2, in, out]``; the expert layer's are a
    layer's (``[L, ...]``), the held experts on their own axis."""
    L, d, h = cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    f, fe, e = cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size, cfg.n_experts_held
    return {
        "wq_a": ((L, 2, d, cfg.q_lora_rank), d),
        "wq_b": ((L, 2, cfg.q_lora_rank, h * qk), cfg.q_lora_rank),
        "wkv_a": ((L, 2, d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), d),
        "wkv_b": ((L, 2, cfg.kv_lora_rank,
                   h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), cfg.kv_lora_rank),
        "wo": ((L, 2, h * cfg.v_head_dim, d), h * cfg.v_head_dim),
        "w_gate": ((L, 2, d, f), d),
        "w_up": ((L, 2, d, f), d),
        "w_down": ((L, 2, f, d), f),
        "e_gate": ((L, e, d, fe), d),
        "e_up": ((L, e, d, fe), d),
        "e_down": ((L, e, fe, d), fe),
    }


def init_params(key: jax.Array, cfg: LongcatConfig, dtype=jnp.bfloat16) -> Params:
    """Random-init params, leaf by leaf. The router is float32 (a score
    decides which experts run); its bias is drawn at ``router_bias_scale``."""
    k_embed, k_layers, k_head, k_router = jax.random.split(key, 4)
    L, d = cfg.num_layers, cfg.hidden_size
    outputs = cfg.n_routed_experts + cfg.zero_expert_num
    shapes = leaf_shapes(cfg)
    sample = jax.jit(_stacked_normal, static_argnums=(1, 2, 3))
    layers: dict[str, Any] = {
        name: sample(k, shape, fan_in, jnp.dtype(dtype))
        for k, (name, (shape, fan_in)) in zip(
            jax.random.split(k_layers, len(shapes)), shapes.items())}
    k_w, k_b = jax.random.split(k_router)
    layers["router"] = (jax.random.normal(k_w, (L, d, outputs), jnp.float32)
                        / jnp.sqrt(jnp.float32(d)))
    layers["router_bias"] = cfg.router_bias_scale * jax.random.normal(
        k_b, (L, outputs), jnp.float32)
    for name, width in (("in_norm", d), ("post_norm", d),
                        ("q_norm", cfg.q_lora_rank), ("kv_norm", cfg.kv_lora_rank)):
        layers[name] = jnp.ones((L, 2, width), jnp.float32)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    return {"embed": dense(k_embed, (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": dense(k_head, (d, cfg.vocab_size), d)}


EXPERT_LEAVES = ("e_gate", "e_up", "e_down")


def moe_block(u: jnp.ndarray, live: jnp.ndarray, lp: dict, cfg: LongcatConfig,
              experts: Optional[dict] = None, layer=None,
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``MoE(u)`` of this share for ``u`` [N, D], and its counts
    (``EXPERT_COUNTS``) over the tokens ``live`` [N]. ``lp`` is one layer's
    router and bias, and its experts unless ``experts`` hands in the
    stacked ``EXPERT_LEAVES`` with ``layer`` (the serving scan does:
    ``ops/moe.held_expert_ffn`` says why)."""
    experts = lp if experts is None else experts
    n = u.shape[0]
    held_n = cfg.n_experts_held
    outputs = cfg.n_routed_experts + cfg.zero_expert_num
    chosen, w = route_scaled(u, lp["router"], lp["router_bias"],
                             cfg.moe_topk, cfg.routed_scaling_factor)
    local = chosen - cfg.first_expert
    held = (local >= 0) & (local < held_n)
    zero = chosen >= cfg.n_routed_experts
    lv = live[:, None]
    # Only live tokens queue at an expert: what a pad adds is never read.
    local = jnp.where(held & lv, local, held_n)
    m, overflow = held_expert_ffn(
        u, local, jnp.where(held, w, 0.0), experts["e_gate"], experts["e_up"],
        experts["e_down"], held_capacity(n, cfg.moe_topk, outputs), layer=layer)
    # An identity expert returns its input: one multiply-add for all of them.
    m = m + jnp.sum(jnp.where(zero, w, 0.0), axis=-1, keepdims=True) \
        * u.astype(jnp.float32)
    touched = jnp.zeros((held_n + 1,), jnp.int32).at[local].max(1)[:held_n]
    counts = jnp.stack([jnp.sum(lv & held), jnp.sum(lv & zero),
                        jnp.sum(lv & ~held & ~zero), jnp.sum(touched), overflow])
    return m.astype(u.dtype), counts.astype(jnp.int32)


def _forward_hidden(params, cfg, tokens, positions, kv_k, kv_v, page_tables,
                    ctx_lens, page_size, block_pages):
    """The stack over one paged chunk, without the head: (hidden [B, T, D],
    kv_k', kv_v', expert counts [len(EXPERT_COUNTS)])."""
    if "lora" in params:
        raise ValueError("the longcat forward has no LoRA rows")
    if isinstance(kv_k, tuple):
        raise ValueError("the longcat forward has no int8 (scaled) KV pool")
    b, t = tokens.shape
    d, n_h, eps = cfg.hidden_size, cfg.num_attention_heads, cfg.rms_norm_eps
    nope, rope, rank = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    q_scale = math.sqrt(d / cfg.q_lora_rank)  # mla_scale_q_lora
    kv_scale = math.sqrt(d / rank)  # mla_scale_kv_lora
    attn_scale = 1.0 / math.sqrt(nope + rope)
    h = params["embed"][tokens]
    live = (positions < ctx_lens[:, None]).reshape(b * t)
    # The scan runs over layer NUMBERS and every leaf is indexed where it is
    # used, ``w[layer, i]`` out of the stacked array: a product reads such
    # a slice in place. Handed in as the scan's ``xs``, a layer's ``[2, ...]``
    # slice had two readers and XLA copied it out first — every dense
    # matrix, every pass (seen in the compiled program).
    w = params["layers"]
    experts = {k: w[k] for k in EXPERT_LEAVES}

    def mla(x, li, i, kv_k, kv_v, kr_first):
        sub = 2 * li + i
        cq = rms_norm(qmm(x, w["wq_a"][li, i]), w["q_norm"][li, i] * q_scale, eps)
        q = qmm(cq, w["wq_b"][li, i]).reshape(b, t, n_h, nope + rope)
        q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
        ckr = qmm(x, w["wkv_a"][li, i])
        c = rms_norm(ckr[..., :rank], w["kv_norm"][li, i] * kv_scale, eps)
        kr = apply_rope(ckr[:, :, None, rank:], positions, cfg.rope_theta)
        kv_k = write_kv_pages_batch(kv_k, c[:, :, None, :], positions,
                                    page_tables, page_size, layer=sub)
        # One whole row of the layer's rotated keys: [kr_0 | kr_1].
        kr_row = jnp.concatenate(
            [kr, jnp.zeros_like(kr)] if kr_first is None else [kr_first, kr],
            axis=-1)
        kv_v = write_kv_pages_batch(kv_v, kr_row, positions, page_tables,
                                    page_size, layer=li)
        w_kvb = w["wkv_b"][li, i].reshape(rank, n_h, nope + cfg.v_head_dim)
        o_lat = latent_paged_attention(
            absorb_queries(q[..., :nope], w_kvb), q_rope, kv_k, kv_v, sub,
            page_tables, ctx_lens, positions, page_size=page_size,
            scale=attn_scale, block_pages=block_pages)
        o = expand_values(o_lat, w_kvb, nope).reshape(b, t, n_h * cfg.v_head_dim)
        return qmm(o, w["wo"][li, i]), kv_k, kv_v, kr

    def layer_step(carry, li):
        hidden, kv_k, kv_v, counts = carry
        m = kr = None
        for i in (0, 1):
            x = rms_norm(hidden, w["in_norm"][li, i], eps)
            o, kv_k, kv_v, kr = mla(x, li, i, kv_k, kv_v, kr)
            a = hidden + o
            u = rms_norm(a, w["post_norm"][li, i], eps)
            if i == 0:  # the shortcut: the experts run beside the rest
                route = {"router": w["router"][li],
                         "router_bias": w["router_bias"][li]}
                m, c = moe_block(u.reshape(b * t, d), live, route, cfg,
                                 experts=experts, layer=li)
                counts = counts + c
            hidden = a + qmm(jax.nn.silu(qmm(u, w["w_gate"][li, i]))
                             * qmm(u, w["w_up"][li, i]), w["w_down"][li, i])
        return (hidden + m.reshape(b, t, d), kv_k, kv_v, counts), None

    (h, kv_k, kv_v, counts), _ = jax.lax.scan(
        layer_step, (h, kv_k, kv_v, jnp.zeros((len(EXPERT_COUNTS),), jnp.int32)),
        jnp.arange(cfg.num_layers, dtype=jnp.int32))
    return h, kv_k, kv_v, counts


# The step programs' pair (``LongcatConfig.forwards``).
forward_counted, forward_ragged_counted = serving_forwards(_forward_hidden)


def forward_impl(params: Params, cfg: LongcatConfig, *chunk, **kw):
    """One forward chunk, the serving signature (``family.serving_forwards``):
    (logits [B, T, vocab] f32, kv_k', kv_v'). ``kv_k`` is the latent pool,
    ``kv_v`` the rotated keys'."""
    return forward_counted(params, cfg, *chunk, **kw)[:3]


def forward_ragged_impl(params: Params, cfg: LongcatConfig, *batch, **kw):
    """Mixed prefill+decode forward over one flat ragged batch, the serving
    signature: (logits [S, vocab] f32, kv_k', kv_v')."""
    return forward_ragged_counted(params, cfg, *batch, **kw)[:3]
