"""JoyAI-LLM-Flash in JAX: latent (MLA) attention over a paged latent cache,
one leading dense layer and then expert layers (sigmoid scores, a bias
that moves the choice only, the chosen renormalised and scaled, a shared
expert beside the routed ones), and the model's own multi-token-prediction
module, which the engine uses as the drafter of its speculative rounds.

Source: ``jdopensource/JoyAI-LLM-Flash`` ``config.json`` (``model_type``
``joyai_llm_flash``; the field names below are that file's, so a
configuration file that copies it is checked key by key). With ``h`` the
residual stream, every norm RMSNorm::

    layer l:  a = h + MLA(RMSNorm(h));  h = a + F_l(RMSNorm(a))
    F_l = SwiGLU (intermediate_size)    for l < first_k_dense_replace
    F_l = MoE                           after

``MLA``: ``cq = RMSNorm(x Wqa)``; ``q = cq Wqb``, per head ``[q_nope |
q_rope]``; ``[c | kr] = x Wkva``; ``c = RMSNorm(c)``; RoPE on ``kr`` (ONE
for all heads) and ``q_rope``; ``[k_nope_h | v_h] = c Wkvb``; scores over
``sqrt(nope + rope)``. No scale factor on either latent (LongCat has two).
The cache holds ``c`` and ``kr`` and attention runs in the absorbed form
(:mod:`runbookai_tpu.ops.mla`).

``MoE``: ``s = sigmoid_f32(u Wr)``; the ``num_experts_per_tok`` largest of
``s + b`` are chosen (``n_group`` = ``topk_group`` = 1: no group limit);
``w_j = routed_scaling_factor * s_j / sum_chosen s``; ``m = sum_{chosen j}
w_j SwiGLU_j(u) + SwiGLU_shared(u)``.

**The prediction module** (DeepSeek-V3 section 2.2, depth 1; embedding and
head shared with the trunk). For position ``i`` with the trunk's last-layer
output ``h_i`` (before the final norm) and the NEXT token ``t_{i+1}``::

    x = Wp [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]
    y = Layer_mtp(x)           # one MLA + MoE layer, its own cache layer
    logits_{i+2} = Head(RMSNorm(y))

Its argmax is the draft of token ``i + 2``. Its cache row ``i`` is made of
``h_i`` and ``t_{i+1}``, so it is written one token behind the trunk's
(:func:`module_pass`; ``engine/engine.py`` says who calls it when).

**The share.** ``n_experts_held`` experts from ``first_expert`` on live
here (one chip of an expert-parallel group). The router keeps every output
and every pick; this chip computes its own experts' part and the shared
expert for its tokens, and what the absent experts would add is left out —
no code stands in for the other chips or their exchange.

**Layout.** Attention blocks are numbered ``0 .. L + M - 1`` (``L`` trunk
layers, then the ``M`` = ``num_nextn_predict_layers`` modules) and their
matrices stacked on that axis; expert layers are numbered ``0 .. L - K + M
- 1`` (``K`` = ``first_k_dense_replace``); the leading dense FFNs ``0 .. K
- 1``. The module's layer is one more layer of the same stacks and of the
same paged pool. The pool is ``models/longcat.py``'s pair:
latents ``[L + M, tokens, 1, kv_rank]``, and rotated keys ``[(L + M + 1) //
2, tokens, 1, 2 * rope]`` — blocks ``2j`` and ``2j + 1`` keep theirs side
by side in one row (``ops/mla.py`` says why a 64-value row will not do).
Here the two halves of a row belong to different layers, so a block reads
the row it is about to write and puts its half in (:func:`_write_rope`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

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
from runbookai_tpu.ops.attention import pool_rows, write_kv_pages_batch
from runbookai_tpu.ops.dense import qmm, rms_norm
from runbookai_tpu.ops.mla import (
    absorb_queries,
    expand_values,
    latent_paged_attention,
)
from runbookai_tpu.ops.moe import (
    held_capacity,
    held_expert_ffn,
    route_sigmoid,
    shared_expert,
)
from runbookai_tpu.ops.rope import apply_rope


@dataclass(frozen=True)
class JoyaiConfig(Family):
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    # The share of the routed experts this process holds: experts
    # ``first_expert .. first_expert + n_experts_held - 1`` of every layer.
    n_experts_held: int
    first_expert: int = 0
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 1
    # Stated by the published config and held to it (``__post_init__``):
    # the forward computes exactly this routing and no other.
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    moe_layer_freq: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32_000_000.0
    max_position_embeddings: int = 131_072
    # Random init only: the balance bias b ~ N(0, scale^2), as longcat's.
    router_bias_scale: float = 1e-3
    # The chat template the family renders (model/chat_template.py).
    family: str = "qwen2"

    # As longcat: attention is this module's, over its own latent pool.
    pallas_attention = False
    one_path = True
    no_draft_model = "a separate draft model beside its own prediction module"
    family_name = "joyai"
    hf_model_types = ("joyai",)
    checkpoint_tensors = "MLA, expert and prediction-module tensor names"

    def __post_init__(self):
        routing = (self.scoring_func, self.topk_method, self.norm_topk_prob,
                   self.n_group, self.topk_group, self.moe_layer_freq)
        if routing != ("sigmoid", "noaux_tc", True, 1, 1, 1):
            raise ValueError(
                f"{self.name}: the joyai forward routes by sigmoid scores, "
                f"bias on choice, one group, renormalised, every layer "
                f"past the dense ones; the configuration asks for {routing}")
        if self.first_k_dense_replace < 1 or self.num_nextn_predict_layers > 1:
            raise ValueError(
                f"{self.name}: at least one leading dense layer and at most "
                f"one prediction module (chained modules are not written)")

    @property
    def dim(self) -> int:
        return self.hidden_size

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

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
    def n_attention(self) -> int:
        """Attention blocks, the module's included."""
        return self.num_hidden_layers + self.num_nextn_predict_layers

    @property
    def n_expert_layers(self) -> int:
        return (self.num_hidden_layers - self.first_k_dense_replace
                + self.num_nextn_predict_layers)

    @property
    def kv_pool_spec(self) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
        """The pool's two sides, each (layers, heads, values a head): a
        latent an attention block, and the rotated keys, two blocks a row.
        The module's block is counted whether or not the engine drafts: the
        pool's shape is the model's (``speculative`` is the engine's, and a
        plan may turn it on for a pool already built), and the module's
        weights are loaded either way."""
        return ((self.n_attention, 1, self.kv_lora_rank),
                ((self.n_attention + 1) // 2, 1, 2 * self.qk_rope_head_dim))

    @property
    def self_draft(self) -> bool:
        """The model brings its own drafter (``drafter()``)."""
        return self.num_nextn_predict_layers > 0

    def forwards(self):
        """The serving pair; the trunk's last hidden state (before the
        final norm) comes back as its sixth result: the module reads it."""
        return forward_counted, forward_ragged_counted

    def drafter(self):
        """(module pass, ragged module pass, draft tokens): what a
        speculative round runs beside the forward."""
        return module_pass, module_pass_ragged, draft_tokens

    def init_params(self, key, dtype=jnp.bfloat16, quantized=False) -> Params:
        return init_params(key, self, dtype)

    # ---- counts (the memory plan's and the MFU model's) ----------------

    @property
    def _attention_params(self) -> int:
        d, h = self.hidden_size, self.num_attention_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return (d * self.q_lora_rank + self.q_lora_rank * h * qk
                + d * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * h * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d)

    @property
    def _expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    @property
    def _shared_params(self) -> int:
        return self.n_shared_experts * self._expert_params

    @property
    def matmul_params(self) -> int:
        """Params in matmuls per token of ONE trunk pass, a held expert
        counted for its expected share of a token's picks."""
        d = self.hidden_size
        expert_layer = (d * self.n_routed_experts + self._shared_params
                        + self.num_experts_per_tok * self.n_experts_held
                        / self.n_routed_experts * self._expert_params)
        return int(self.num_hidden_layers * self._attention_params
                   + self.first_k_dense_replace * 3 * d * self.intermediate_size
                   + (self.num_hidden_layers - self.first_k_dense_replace)
                   * expert_layer + d * self.vocab_size)

    @property
    def total_params(self) -> int:
        """Every weight held HERE (the memory-side count), module included."""
        d = self.hidden_size
        norms = 2 * d + self.q_lora_rank + self.kv_lora_rank
        expert_layer = (d * self.n_routed_experts + self.n_routed_experts
                        + self._shared_params
                        + self.n_experts_held * self._expert_params)
        module = self.num_nextn_predict_layers * (2 * d * d + 3 * d)
        return (self.n_attention * (self._attention_params + norms)
                + self.first_k_dense_replace * 3 * d * self.intermediate_size
                + self.n_expert_layers * expert_layer + module
                + 2 * d * self.vocab_size + d)


_WIDTHS = dict(hidden_size=2048, intermediate_size=7168,
               moe_intermediate_size=768, num_attention_heads=32,
               q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
               qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=256,
               num_experts_per_tok=8, routed_scaling_factor=2.5)

CONFIGS: dict[str, JoyaiConfig] = register({
    # The published model (config.json): 40 layers, every routed expert
    # held, one prediction module. 48B parameters: no single process of
    # this repo holds it; it is the entry a cut configuration is checked
    # against.
    "joyai-llm-flash": JoyaiConfig(
        name="joyai-llm-flash", vocab_size=129_280, num_hidden_layers=40,
        n_experts_held=256, **_WIDTHS),
    # One chip of a four-chip host that is one of three pipeline stages
    # (examples/serve/joyai-llm-flash-ep4.yaml; the benchmark's
    # configuration file states the same cut): the leading dense layer and
    # 12 of the 39 expert layers, experts 0-63 of 256, the module whole,
    # the whole vocabulary. 9.89 GB in bf16.
    "joyai-llm-flash-ep4": JoyaiConfig(
        name="joyai-llm-flash-ep4", vocab_size=129_280, num_hidden_layers=13,
        n_experts_held=64, **_WIDTHS),
    # Tiny, for CPU tests: byte-tokenizer vocabulary, one dense and two
    # expert layers, 8 of 16 routed experts held (the second half), the
    # module.
    "joyai-test": JoyaiConfig(
        name="joyai-test", vocab_size=262, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=16, num_experts_per_tok=4, routed_scaling_factor=2.5,
        n_experts_held=8, first_expert=8, rope_theta=10_000.0,
        max_position_embeddings=8192, router_bias_scale=2e-2),
})


def leaf_shapes(cfg: JoyaiConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The stacked matrices as ``name -> (shape, fan_in)``, in init order:
    attention blocks ``[A, in, out]``, expert layers ``[E, ...]`` with the
    held experts on their own axis, the leading dense FFNs ``[K, ...]``."""
    a, e, k = cfg.n_attention, cfg.n_expert_layers, cfg.first_k_dense_replace
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    f, fe, held = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.n_experts_held
    fs = cfg.n_shared_experts * fe
    return {
        "wq_a": ((a, d, cfg.q_lora_rank), d),
        "wq_b": ((a, cfg.q_lora_rank, h * qk), cfg.q_lora_rank),
        "wkv_a": ((a, d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), d),
        "wkv_b": ((a, cfg.kv_lora_rank,
                   h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), cfg.kv_lora_rank),
        "wo": ((a, h * cfg.v_head_dim, d), h * cfg.v_head_dim),
        "d_gate": ((k, d, f), d),
        "d_up": ((k, d, f), d),
        "d_down": ((k, f, d), f),
        "s_gate": ((e, d, fs), d),
        "s_up": ((e, d, fs), d),
        "s_down": ((e, fs, d), fs),
        "e_gate": ((e, held, d, fe), d),
        "e_up": ((e, held, d, fe), d),
        "e_down": ((e, held, fe, d), fe),
    }


def init_params(key: jax.Array, cfg: JoyaiConfig, dtype=jnp.bfloat16) -> Params:
    """Random-init params, leaf by leaf. The router is float32 (a score
    decides which experts run); its bias is drawn at ``router_bias_scale``."""
    k_embed, k_layers, k_head, k_router, k_mtp = jax.random.split(key, 5)
    d, e, m = cfg.hidden_size, cfg.n_expert_layers, cfg.num_nextn_predict_layers
    shapes = leaf_shapes(cfg)
    sample = jax.jit(_stacked_normal, static_argnums=(1, 2, 3))
    layers: dict[str, Any] = {
        name: sample(k, shape, fan_in, jnp.dtype(dtype))
        for k, (name, (shape, fan_in)) in zip(
            jax.random.split(k_layers, len(shapes)), shapes.items())}
    k_w, k_b = jax.random.split(k_router)
    layers["router"] = (jax.random.normal(k_w, (e, d, cfg.n_routed_experts),
                                          jnp.float32) / jnp.sqrt(jnp.float32(d)))
    layers["router_bias"] = cfg.router_bias_scale * jax.random.normal(
        k_b, (e, cfg.n_routed_experts), jnp.float32)
    for name, width in (("in_norm", d), ("post_norm", d),
                        ("q_norm", cfg.q_lora_rank), ("kv_norm", cfg.kv_lora_rank)):
        layers[name] = jnp.ones((cfg.n_attention, width), jnp.float32)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    return {"embed": dense(k_embed, (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": dense(k_head, (d, cfg.vocab_size), d),
            # The module's own: the projection of [embedding ; hidden], the
            # two norms in front of it, the norm in front of the shared head.
            "mtp": {"proj": dense(k_mtp, (m, 2 * d, d), 2 * d),
                    "e_norm": jnp.ones((m, d), jnp.float32),
                    "h_norm": jnp.ones((m, d), jnp.float32),
                    "final_norm": jnp.ones((m, d), jnp.float32)}}


EXPERT_LEAVES = ("e_gate", "e_up", "e_down")


def moe_block(u: jnp.ndarray, live: jnp.ndarray, w: dict, e, cfg: JoyaiConfig,
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``MoE(u)`` of this share for ``u`` [N, D] in expert layer ``e`` (a
    traced scalar, or a number) of the stacked leaves ``w``, and its counts
    (``family.EXPERT_COUNTS``; no identity experts here, so ``zero`` is 0)
    over the tokens ``live`` [N]."""
    n = u.shape[0]
    held_n = cfg.n_experts_held
    chosen, wts = route_sigmoid(u, w["router"][e], w["router_bias"][e],
                                cfg.num_experts_per_tok,
                                cfg.routed_scaling_factor)
    local = chosen - cfg.first_expert
    held = (local >= 0) & (local < held_n)
    lv = live[:, None]
    # Only live tokens queue at an expert: what a pad adds is never read.
    local = jnp.where(held & lv, local, held_n)
    m, overflow = held_expert_ffn(
        u, local, jnp.where(held, wts, 0.0), w["e_gate"], w["e_up"],
        w["e_down"], held_capacity(n, cfg.num_experts_per_tok,
                                   cfg.n_routed_experts), layer=e)
    m = m + shared_expert(u, w["s_gate"][e], w["s_up"][e],
                          w["s_down"][e]).astype(jnp.float32)
    touched = jnp.zeros((held_n + 1,), jnp.int32).at[local].max(1)[:held_n]
    counts = jnp.stack([jnp.sum(lv & held), jnp.int32(0), jnp.sum(lv & ~held),
                        jnp.sum(touched), overflow])
    return m.astype(u.dtype), counts.astype(jnp.int32)


def _write_rope(r_pool, kr, positions, page_tables, page_size: int, block):
    """Put attention block ``block``'s rotated keys ``kr`` [B, T, 1, rope]
    into its half of the rows at ``positions``: the rows are read, the half
    replaced, and the WHOLE rows written back — a scatter into half a row
    is what XLA turned into a loop over copies of the pool (``ops/mla.py``).
    The other half is the neighbouring layer's and is kept as it is."""
    b, t = positions.shape
    rope = kr.shape[-1]
    phys = jnp.take_along_axis(page_tables, positions // page_size, axis=1)
    rows, base = pool_rows(r_pool, block // 2)
    dest = base + (phys * page_size + positions % page_size).reshape(b * t)
    old = rows[dest]  # [B * T, 1, 2 * rope]
    new = kr.reshape(b * t, 1, rope).astype(rows.dtype)
    first = jnp.concatenate([new, old[..., rope:]], axis=-1)
    second = jnp.concatenate([old[..., :rope], new], axis=-1)
    whole = jnp.where(block % 2 == 0, first, second)
    return rows.at[dest].set(whole).reshape(r_pool.shape)


def _layer(w, cfg: JoyaiConfig, hidden, live, block, ffn, positions, kv_k,
           kv_v, page_tables, ctx_lens, page_size, block_pages):
    """One layer over a paged chunk: attention block ``block``, then the
    FFN ``ffn`` = ("dense", k) or ("experts", e). Returns (hidden', kv_k',
    kv_v', expert counts). Every leaf is indexed where it is used, out of
    the stacked array, so a product reads its slice in place."""
    b, t, d = hidden.shape
    n_h, eps = cfg.num_attention_heads, cfg.rms_norm_eps
    nope, rope, rank = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    x = rms_norm(hidden, w["in_norm"][block], eps)
    cq = rms_norm(qmm(x, w["wq_a"][block]), w["q_norm"][block], eps)
    q = qmm(cq, w["wq_b"][block]).reshape(b, t, n_h, nope + rope)
    q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    ckr = qmm(x, w["wkv_a"][block])
    c = rms_norm(ckr[..., :rank], w["kv_norm"][block], eps)
    kr = apply_rope(ckr[:, :, None, rank:], positions, cfg.rope_theta)
    kv_k = write_kv_pages_batch(kv_k, c[:, :, None, :], positions,
                                page_tables, page_size, layer=block)
    kv_v = _write_rope(kv_v, kr, positions, page_tables, page_size, block)
    w_kvb = w["wkv_b"][block].reshape(rank, n_h, nope + cfg.v_head_dim)
    o_lat = latent_paged_attention(
        absorb_queries(q[..., :nope], w_kvb), q_rope, kv_k, kv_v, block,
        page_tables, ctx_lens, positions, page_size=page_size,
        scale=1.0 / math.sqrt(nope + rope), block_pages=block_pages)
    o = expand_values(o_lat, w_kvb, nope).reshape(b, t, n_h * cfg.v_head_dim)
    a = hidden + qmm(o, w["wo"][block])
    u = rms_norm(a, w["post_norm"][block], eps)
    kind, i = ffn
    if kind == "dense":
        m = qmm(jax.nn.silu(qmm(u, w["d_gate"][i])) * qmm(u, w["d_up"][i]),
                w["d_down"][i])
        counts = jnp.zeros((len(EXPERT_COUNTS),), jnp.int32)
    else:
        m, counts = moe_block(u.reshape(b * t, d), live, w, i, cfg)
        m = m.reshape(b, t, d)
    return a + m, kv_k, kv_v, counts


def _check(params, kv_k) -> None:
    if "lora" in params:
        raise ValueError("the joyai forward has no LoRA rows")
    if isinstance(kv_k, tuple):
        raise ValueError("the joyai forward has no int8 (scaled) KV pool")


def _forward_hidden(params, cfg, tokens, positions, kv_k, kv_v, page_tables,
                    ctx_lens, page_size, block_pages):
    """The trunk over one paged chunk, without the head: (hidden [B, T, D],
    kv_k', kv_v', expert counts [len(EXPERT_COUNTS)]). The leading dense
    layers run one by one; the expert layers are one ``lax.scan`` over
    their numbers, the pool riding its carry."""
    _check(params, kv_k)
    b, t = tokens.shape
    w, k = params["layers"], cfg.first_k_dense_replace
    h = params["embed"][tokens]
    live = (positions < ctx_lens[:, None]).reshape(b * t)
    paged = (page_tables, ctx_lens, page_size, block_pages)
    counts = jnp.zeros((len(EXPERT_COUNTS),), jnp.int32)
    for i in range(k):
        h, kv_k, kv_v, _ = _layer(w, cfg, h, live, i, ("dense", i), positions,
                                  kv_k, kv_v, *paged)

    def layer_step(carry, block):
        hidden, kv_k, kv_v, counts = carry
        hidden, kv_k, kv_v, c = _layer(
            w, cfg, hidden, live, block, ("experts", block - k), positions,
            kv_k, kv_v, *paged)
        return (hidden, kv_k, kv_v, counts + c), None

    (h, kv_k, kv_v, counts), _ = jax.lax.scan(
        layer_step, (h, kv_k, kv_v, counts),
        jnp.arange(k, cfg.num_hidden_layers, dtype=jnp.int32))
    return h, kv_k, kv_v, counts


def module_pass(params, cfg, hidden, tokens, positions, kv_k, kv_v,
                page_tables, ctx_lens, page_size, block_pages=32):
    """The prediction module over one paged chunk: ``hidden`` [B, T, D] the
    trunk's output at ``positions``, ``tokens`` [B, T] the token AFTER each
    of them. Writes its cache rows at ``positions`` (``ctx_lens`` counts
    them) and returns (y [B, T, D] — :func:`draft_tokens` makes drafts of
    it — kv_k', kv_v', expert counts)."""
    _check(params, kv_k)
    b, t = tokens.shape
    m, eps = params["mtp"], cfg.rms_norm_eps
    x = jnp.concatenate(
        [rms_norm(params["embed"][tokens], m["e_norm"][0], eps),
         rms_norm(hidden, m["h_norm"][0], eps)], axis=-1)
    live = (positions < ctx_lens[:, None]).reshape(b * t)
    return _layer(params["layers"], cfg, qmm(x, m["proj"][0]), live,
                  cfg.num_hidden_layers,
                  ("experts", cfg.num_hidden_layers - cfg.first_k_dense_replace),
                  positions, kv_k, kv_v, page_tables, ctx_lens, page_size,
                  block_pages)


def module_pass_ragged(params, cfg, hidden, tokens, positions, row_ids, kv_k,
                       kv_v, page_tables, ctx_lens, page_size, block_pages=32,
                       ragged_block=8):
    """:func:`module_pass` over the mixed step's flat buffer (``hidden``
    [N, D], ``tokens`` and ``positions`` [N]), run as ``[N / ragged_block,
    ragged_block]`` with per-block gathered tables, as the forward is."""
    n = tokens.shape[0]
    nb = n // ragged_block
    block_rows = row_ids.reshape(nb, ragged_block)[:, 0]
    y, kv_k, kv_v, counts = module_pass(
        params, cfg, hidden.reshape(nb, ragged_block, -1),
        tokens.reshape(nb, ragged_block), positions.reshape(nb, ragged_block),
        kv_k, kv_v, page_tables[block_rows], ctx_lens[block_rows], page_size,
        block_pages)
    return y.reshape(n, -1), kv_k, kv_v, counts


def draft_logits(params, cfg, y) -> jnp.ndarray:
    """The shared head over the module's output, under the module's own
    final norm: float32 logits of the token two ahead."""
    h = rms_norm(y, params["mtp"]["final_norm"][0], cfg.rms_norm_eps)
    return (h @ params["lm_head"]).astype(jnp.float32)


def draft_tokens(params, cfg, y) -> jnp.ndarray:
    """The module's greedy draft for each row of ``y`` [N, D]."""
    return jnp.argmax(draft_logits(params, cfg, y), axis=-1).astype(jnp.int32)


# The step programs' pair (``JoyaiConfig.forwards``).
forward_counted, forward_ragged_counted = serving_forwards(_forward_hidden)


def forward_impl(params: Params, cfg: JoyaiConfig, *chunk, **kw):
    """One forward chunk, the serving signature (``family.serving_forwards``):
    (logits [B, T, vocab] f32, kv_k', kv_v'). ``kv_k`` is the latent pool,
    ``kv_v`` the rotated keys'."""
    return forward_counted(params, cfg, *chunk, **kw)[:3]
