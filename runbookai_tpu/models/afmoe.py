"""Arcee's ``afmoe`` in JAX: sliding-WINDOW attention layers beside full
ones, sparse experts past a few leading dense layers, and of the experts a
share held by this process.

Source: ``arcee-ai/Trinity-Mini`` ``config.json`` (``model_type`` ``afmoe``;
the field names below are that file's, so a configuration file that copies
it is checked key by key), and the published ``modeling_afmoe.py`` for what
no key says (listed under ``assumed`` in the benchmark's configuration
file). Layer ``l`` of ``num_hidden_layers``, ``x`` the residual stream,
every norm RMSNorm with eps ``rms_norm_eps``::

    x = E[ids] * sqrt(hidden_size)                      (mup_enabled)
    h = N1(x);  q = Nq(h Wq), k = Nk(h Wk)  per head;  v = h Wv
    sliding layer: q, k rotated (rope_theta, the whole head); full: NOT
    a = softmax(q k / sqrt(head_dim), causal [and i - j < sliding_window]) v
    x = x + N2((a * sigmoid(h Wg)) Wo)
    u = N3(x);  x = x + N4(F_l(u))
    F_l = SwiGLU(intermediate_size) for l < num_dense_layers, MoE after
    MoE(u) = sum_{chosen, held j} w_j SwiGLU_j(u) + SwiGLU_shared(u)
    s = sigmoid_f32(u Wr); chosen = the num_experts_per_tok largest of s + b
    w_j = route_scale * s_j / sum of the chosen s
    logits = Nf(x) W_head                               (untied, unscaled)

``layer_types`` is a period of ``global_attn_every_n_layers - 1`` sliding
layers and one full layer. **Two groups of layers, two pools**
(``kv_pool_spec``, ``kv_window_spec``): a full layer's keys and values are
paged for every position, a sliding layer's for the positions its next
queries can still see — the KV manager gives the rest back while the
sequence lives (``engine/kv_cache.py`` ``WindowSpec``). ``kv_k`` and
``kv_v`` are each ``{"full": [layers, tokens, kv, hd], "window": ...}`` and
a row of ``page_tables`` is two halves, the full group's columns and then
the window group's. The window is the walks' lower edge
(``ops/paged_attention_pallas.py``, ``ops/attention.py``): the same kernels
as the dense family's, with ``window`` given.

**The share.** ``n_experts_held`` experts from ``first_expert`` on live here
(one chip of an expert-parallel group). The router keeps every output and
every pick; this chip computes its own experts' part and the shared expert
for its tokens, and what the absent experts would add is left out — no code
stands in for the other chips or their exchange.

The stack runs the leading layers one by one up to the first whole period
of expert layers, then ONE ``lax.scan`` over the periods, the sliding layers
of a period a loop inside it: six layer bodies compiled for 32 layers.
Every leaf is indexed where it is used, and both pools ride the loops'
carry and are written in place.
"""

from __future__ import annotations

import dataclasses
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
from runbookai_tpu.ops.attention import paged_attention, write_kv_pages_batch
from runbookai_tpu.ops.dense import qmm, rms_norm
from runbookai_tpu.ops.moe import (
    held_capacity,
    held_expert_ffn,
    route_sigmoid,
    shared_expert,
)
from runbookai_tpu.ops.rope import apply_rope

SLIDING, FULL = "sliding_attention", "full_attention"


class LayerTypes(tuple):
    """``layer_types`` as a hashable field that still equals the list a
    configuration file states it as."""

    def __eq__(self, other):
        return tuple(self) == tuple(other) if isinstance(other, (list, tuple)) else False

    def __ne__(self, other):
        return not self == other

    __hash__ = tuple.__hash__


@dataclass(frozen=True)
class AfmoeConfig(Family):
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    num_dense_layers: int
    layer_types: tuple
    sliding_window: int
    global_attn_every_n_layers: int
    route_scale: float
    # The share of the experts this process holds: experts ``first_expert
    # .. first_expert + n_experts_held - 1`` of every expert layer.
    n_experts_held: int
    first_expert: int = 0
    num_shared_experts: int = 1
    # Stated by the published config and held to it (``__post_init__``):
    # the forward computes exactly this routing and no other.
    score_func: str = "sigmoid"
    route_norm: bool = True
    n_group: int = 1
    topk_group: int = 1
    num_expert_groups: int = 1
    num_limited_groups: int = 1
    mup_enabled: bool = True
    rope_theta: float = 10_000.0
    rope_scaling: Optional[tuple] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131_072
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    # Published and read by no layer of a forward pass (the training loss's
    # balance coefficient, a kernel choice, the family's name).
    load_balance_coeff: float = 1e-3
    use_grouped_mm: bool = True
    model_type: str = "afmoe"
    # The seeded router's bias on the choice (a checkpoint's is learnt).
    router_bias_scale: float = 1e-3
    family: str = "qwen2"  # the chat template: ChatML (assumed)

    # (4 or 8 KV heads of 128 in bf16: the dense family's pool shape, so the
    # engine's Pallas attention kernels read this family's pages.)
    # (The host spill tier and page export between replicas are refused
    # where they are asked for: ``engine/kv_cache.py``.)
    one_path = True
    no_prompt_lookup = ("prompt-lookup speculation (a verify chunk over a "
                        "window has not been proven)")
    no_draft_model = "draft-model speculation"
    family_name = "afmoe"
    hf_model_types = ("afmoe",)
    # The most sequences whose prefill chunks share one dispatch. A long
    # prompt prefills alone for seconds (16k tokens: 32 chunks), so a second
    # arrival joins it while nothing decodes, and ``_prefill_step`` would
    # run at 1, 2 and 4 rows: three programs of 32 layers (half a minute of
    # set-up each), and which of them a warm-up meets depends on its
    # timing (the first chip runs met two of the three and compiled the
    # third inside the measured window). One row: one program. Prompts that
    # wait meanwhile join through the mixed step as soon as a row decodes.
    max_prefill_rows = 1

    def __post_init__(self):
        object.__setattr__(self, "layer_types", LayerTypes(self.layer_types))
        n = self.global_attn_every_n_layers
        want = [FULL if (l + 1) % n == 0 else SLIDING
                for l in range(self.num_hidden_layers)]
        if list(self.layer_types) != want or self.num_hidden_layers % n:
            raise ValueError(
                f"{self.name}: layer_types is not whole periods of {n - 1} "
                f"sliding layers and a full one")
        routing = (self.score_func, self.route_norm, self.n_group, self.topk_group,
                   self.num_expert_groups, self.num_limited_groups,
                   self.num_shared_experts, self.hidden_act, self.rope_scaling,
                   self.tie_word_embeddings)
        if routing != ("sigmoid", True, 1, 1, 1, 1, 1, "silu", None, False):
            raise ValueError(
                f"{self.name}: the afmoe forward routes by sigmoid scores, bias "
                f"on choice, one group, renormalised, one shared expert, SwiGLU, "
                f"plain rotary positions, an untied head; the configuration "
                f"asks for {routing}")
        if not 0 <= self.first_expert <= self.num_experts - self.n_experts_held:
            raise ValueError(f"{self.name}: the held experts are not among "
                             f"the {self.num_experts}")

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
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def n_kind(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

    @property
    def kv_pool_spec(self) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
        """The paged pool of the FULL-attention layers: the two sides, each
        (layers, heads, values a head). The sliding layers' is
        ``kv_window_spec``."""
        side = (self.n_kind(FULL), self.num_key_value_heads, self.head_dim)
        return side, side

    @property
    def kv_window_spec(self) -> tuple[int, int]:
        """(layers, window) of the group whose queries see their last
        ``window`` positions only: a pool of its own, the same heads."""
        return self.n_kind(SLIDING), self.sliding_window

    def forwards(self):
        return forward_counted, forward_ragged_counted

    def init_params(self, key, dtype=jnp.bfloat16, quantized=False) -> Params:
        return init_params(key, self, dtype)

    @classmethod
    def from_hf(cls, raw: dict, name: str) -> "AfmoeConfig":
        """Every key of ``config.json`` that is a field of the dataclass,
        every expert held."""
        fields = {f.name for f in dataclasses.fields(cls)} - {"name", "family"}
        return cls(name=name, n_experts_held=raw["num_experts"],
                   **{k: v for k, v in raw.items() if k in fields})

    # ---- counts (the memory plan's and the MFU model's) ----------------

    @property
    def _attention_params(self) -> int:
        """q, the output gate and o (hidden x heads x head_dim each), k
        and v."""
        d, hd = self.hidden_size, self.head_dim
        return (3 * d * self.num_attention_heads * hd
                + 2 * d * self.num_key_value_heads * hd)

    @property
    def _expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    @property
    def matmul_params(self) -> int:
        """Params in matmuls per token, a held expert counted for its
        expected share of a token's picks (llama.py's ``N`` of ``2 N``)."""
        d = self.hidden_size
        picks = self.num_experts_per_tok * self.n_experts_held / self.num_experts
        expert_layer = (d * self.num_experts + self.num_shared_experts
                        * self._expert_params + picks * self._expert_params)
        return int(self.num_hidden_layers * self._attention_params
                   + self.num_dense_layers * 3 * d * self.intermediate_size
                   + self.n_expert_layers * expert_layer + d * self.vocab_size)

    @property
    def total_params(self) -> int:
        """Every weight held HERE (the memory-side count)."""
        d = self.hidden_size
        norms = 4 * d + 2 * self.head_dim
        expert_layer = (d * self.num_experts + self.num_experts
                        + (self.num_shared_experts + self.n_experts_held)
                        * self._expert_params)
        return (self.num_hidden_layers * (self._attention_params + norms)
                + self.num_dense_layers * 3 * d * self.intermediate_size
                + self.n_expert_layers * expert_layer
                + 2 * d * self.vocab_size + d)


def _pattern(layers: int, every: int) -> tuple[str, ...]:
    return tuple(FULL if (l + 1) % every == 0 else SLIDING for l in range(layers))


_PUBLISHED = dict(
    hidden_size=2048, intermediate_size=6144, moe_intermediate_size=1024,
    num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=4,
    head_dim=128, num_experts=128, num_experts_per_tok=8, num_dense_layers=2,
    layer_types=_pattern(32, 4), sliding_window=2048,
    global_attn_every_n_layers=4, route_scale=2.826)

CONFIGS: dict[str, AfmoeConfig] = register({
    # The published model (config.json): every expert held. 26B
    # parameters: no single process of this repo holds it; it is the entry
    # a cut configuration is checked against.
    "trinity-mini": AfmoeConfig(
        name="trinity-mini", vocab_size=200_192, n_experts_held=128, **_PUBLISHED),
    # One chip's share of it where the eight chips of one host share each
    # layer, at its WHOLE depth (examples/serve/trinity-mini-ep8.yaml; the
    # benchmark's configuration file states the same cut): experts 0-15 of
    # 128, an eighth of the vocabulary. 8.55 GB in bf16.
    "trinity-mini-ep8": AfmoeConfig(
        name="trinity-mini-ep8", vocab_size=25_024, n_experts_held=16, **_PUBLISHED),
    # Tiny, for CPU tests: byte-tokenizer vocabulary, three periods of three
    # sliding layers and a full one, a window of two pages, two leading
    # dense layers, 8 of 16 experts held (the second half), two heads a
    # group.
    "afmoe-test": AfmoeConfig(
        name="afmoe-test", vocab_size=262, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=12, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, num_experts=16, num_experts_per_tok=4,
        num_dense_layers=2, layer_types=_pattern(12, 4), sliding_window=32,
        global_attn_every_n_layers=4, route_scale=2.826, n_experts_held=8,
        first_expert=8, max_position_embeddings=8192, router_bias_scale=2e-2),
})


def leaf_shapes(cfg: AfmoeConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The stacked matrices as ``name -> (shape, fan_in)``, in init order:
    attention ``[L, in, out]``, the leading dense FFNs ``[K, ...]``, expert
    layers ``[E, ...]`` with the held experts on their own axis."""
    L, k, e, d = (cfg.num_hidden_layers, cfg.num_dense_layers, cfg.n_expert_layers,
                  cfg.hidden_size)
    hq = cfg.num_attention_heads * cfg.head_dim
    hkv = cfg.num_key_value_heads * cfg.head_dim
    f, fe, held = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.n_experts_held
    fs = cfg.num_shared_experts * fe
    return {
        "wq": ((L, d, hq), d), "wk": ((L, d, hkv), d), "wv": ((L, d, hkv), d),
        "wg": ((L, d, hq), d), "wo": ((L, hq, d), hq),
        "d_gate": ((k, d, f), d), "d_up": ((k, d, f), d), "d_down": ((k, f, d), f),
        "s_gate": ((e, d, fs), d), "s_up": ((e, d, fs), d), "s_down": ((e, fs, d), fs),
        "e_gate": ((e, held, d, fe), d), "e_up": ((e, held, d, fe), d),
        "e_down": ((e, held, fe, d), fe),
    }


NORMS = ("norm1", "norm2", "norm3", "norm4")  # pre/post attention, pre/post FFN


def init_params(key: jax.Array, cfg: AfmoeConfig, dtype=jnp.bfloat16) -> Params:
    """Random-init params, leaf by leaf. The matrices are normal over
    sqrt(fan-in); the router is float32 (a score decides which experts run)
    and its bias drawn at ``router_bias_scale``; every norm's weight ones."""
    k_embed, k_layers, k_head, k_router = jax.random.split(key, 4)
    L, e, d = cfg.num_hidden_layers, cfg.n_expert_layers, cfg.hidden_size
    shapes = leaf_shapes(cfg)
    sample = jax.jit(_stacked_normal, static_argnums=(1, 2, 3))
    layers: dict[str, Any] = {
        name: sample(k, shape, fan_in, jnp.dtype(dtype))
        for k, (name, (shape, fan_in)) in zip(
            jax.random.split(k_layers, len(shapes)), shapes.items())}
    k_w, k_b = jax.random.split(k_router)
    layers["router"] = (jax.random.normal(k_w, (e, d, cfg.num_experts), jnp.float32)
                        / jnp.sqrt(jnp.float32(d)))
    layers["router_bias"] = cfg.router_bias_scale * jax.random.normal(
        k_b, (e, cfg.num_experts), jnp.float32)
    for name in NORMS:
        layers[name] = jnp.ones((L, d), jnp.float32)
    layers["q_norm"] = jnp.ones((L, cfg.head_dim), jnp.float32)
    layers["k_norm"] = jnp.ones((L, cfg.head_dim), jnp.float32)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    return {"embed": dense(k_embed, (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": dense(k_head, (d, cfg.vocab_size), d)}


EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
# Slots a held expert's queue gets, in expected loads under even routing
# (``ops/moe.held_capacity``; a longer queue takes the exact slow path). Four,
# as qwen3_next.py: a mixed step counts its pads among its tokens.
SLOT_FACTOR = 4


def moe_block(u: jnp.ndarray, live: jnp.ndarray, w: dict, e, cfg: AfmoeConfig,
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``MoE(u)`` of this share for ``u`` [N, D] in expert layer ``e`` (a
    traced scalar, or a number) of the stacked leaves ``w``, and its counts
    (``family.EXPERT_COUNTS``; ``zero`` always 0) over the tokens ``live``
    [N]."""
    n = u.shape[0]
    held_n = cfg.n_experts_held
    chosen, wts = route_sigmoid(u, w["router"][e], w["router_bias"][e],
                                cfg.num_experts_per_tok, cfg.route_scale)
    local = chosen - cfg.first_expert
    held = (local >= 0) & (local < held_n)
    lv = live[:, None]
    # Only live tokens queue at an expert: what a pad adds is never read.
    local = jnp.where(held & lv, local, held_n)
    with jax.named_scope("moe.afmoe"):
        m, overflow = held_expert_ffn(
            u, local, jnp.where(held, wts, 0.0), w["e_gate"], w["e_up"],
            w["e_down"], held_capacity(n, cfg.num_experts_per_tok, cfg.num_experts,
                                       factor=SLOT_FACTOR), layer=e)
    m = m + shared_expert(u, w["s_gate"][e], w["s_up"][e],
                          w["s_down"][e]).astype(jnp.float32)
    touched = jnp.zeros((held_n + 1,), jnp.int32).at[local].max(1)[:held_n]
    counts = jnp.stack([jnp.sum(lv & held), jnp.int32(0), jnp.sum(lv & ~held),
                        jnp.sum(touched), overflow])
    return m.astype(u.dtype), counts.astype(jnp.int32)


def split_tables(page_tables: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """A row of the engine's page tables is two halves: the full group's
    columns (with their trash column), then the window group's."""
    half = page_tables.shape[1] // 2
    return {"full": page_tables[:, :half], "window": page_tables[:, half:]}


def attend(q, pool_k, pool_v, gi, tables, ctx_lens, positions, page_size,
           block_pages, window, attn_impl):
    """``q`` [B, T, H, hd] over layer ``gi`` of its group's pool: the Pallas
    walks (one token a row: the decode walk; more: the chunk walk, positions
    contiguous a row) or XLA's, each with the group's ``window`` as its
    lower edge (None: the full group)."""
    if attn_impl == "pallas":
        from runbookai_tpu.ops.paged_attention_pallas import paged_layer_attention

        # (the carried pool and the layer's number where the kernels read it
        # in place, else the layer's slice: static, by the pool's shape)
        return paged_layer_attention(q, pool_k, pool_v, gi, tables, ctx_lens,
                                     positions, page_size, window=window)
    k_pages, v_pages = (jax.lax.dynamic_index_in_dim(a, gi, keepdims=False)
                        for a in (pool_k, pool_v))
    return paged_attention(q, k_pages, v_pages, tables, ctx_lens, positions,
                           page_size=page_size, block_pages=block_pages,
                           window=window)


def _layer(w, cfg: AfmoeConfig, hidden, live, l, kind: str, gi, ffn, positions,
           kv_k, kv_v, tables, ctx_lens, page_size, block_pages, attn_impl):
    """Layer ``l`` over a paged chunk: attention of ``kind`` (static) over
    layer ``gi`` of ITS group's pool, then the FFN ``ffn`` = ("dense", k) or
    ("experts", e). Returns (hidden', kv_k', kv_v', expert counts). Every
    leaf is indexed where it is used, out of the stacked array, so a product
    reads its slice in place."""
    b, t, d = hidden.shape
    eps, hd = cfg.rms_norm_eps, cfg.head_dim
    group = "window" if kind == SLIDING else "full"
    h = rms_norm(hidden, w["norm1"][l], eps)
    # (the norms of q and k run over each head's values)
    q = rms_norm(qmm(h, w["wq"][l]).reshape(b, t, cfg.num_attention_heads, hd),
                 w["q_norm"][l], eps)
    k = rms_norm(qmm(h, w["wk"][l]).reshape(b, t, cfg.num_key_value_heads, hd),
                 w["k_norm"][l], eps)
    v = qmm(h, w["wv"][l]).reshape(b, t, cfg.num_key_value_heads, hd)
    if kind == SLIDING:  # a full layer carries no position of its own
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    pool_k = write_kv_pages_batch(kv_k[group], k, positions, tables[group],
                                  page_size, layer=gi)
    pool_v = write_kv_pages_batch(kv_v[group], v, positions, tables[group],
                                  page_size, layer=gi)
    kv_k, kv_v = {**kv_k, group: pool_k}, {**kv_v, group: pool_v}
    with jax.named_scope("attn.window" if kind == SLIDING else "attn.global"):
        a = attend(q, pool_k, pool_v, gi, tables[group], ctx_lens, positions,
                   page_size, block_pages,
                   cfg.sliding_window if kind == SLIDING else None, attn_impl)
    gate = jax.nn.sigmoid(qmm(h, w["wg"][l]).astype(jnp.float32))
    a = (a.reshape(b, t, -1).astype(jnp.float32) * gate).astype(hidden.dtype)
    x = hidden + rms_norm(qmm(a, w["wo"][l]), w["norm2"][l], eps)
    u = rms_norm(x, w["norm3"][l], eps)
    which, i = ffn
    if which == "dense":
        m = qmm(jax.nn.silu(qmm(u, w["d_gate"][i])) * qmm(u, w["d_up"][i]),
                w["d_down"][i])
        counts = jnp.zeros((len(EXPERT_COUNTS),), jnp.int32)
    else:
        m, counts = moe_block(u.reshape(b * t, d), live, w, i, cfg)
        m = m.reshape(b, t, d)
    return x + rms_norm(m, w["norm4"][l], eps), kv_k, kv_v, counts


def _check(params, kv_k) -> None:
    if "lora" in params:
        raise ValueError("the afmoe forward has no LoRA rows")
    if any(isinstance(pool, tuple) for pool in kv_k.values()):
        raise ValueError("the afmoe forward has no int8 (scaled) KV pool")


def _forward_hidden(params, cfg, tokens, positions, kv_k, kv_v, page_tables,
                    ctx_lens, page_size, block_pages, attn_impl):
    """The stack over one paged chunk ``[B, T]``, without the head: (hidden
    [B, T, D], kv_k', kv_v', expert counts [len(EXPERT_COUNTS)])."""
    _check(params, kv_k)
    b, t = tokens.shape
    w, k = params["layers"], cfg.num_dense_layers
    n = cfg.global_attn_every_n_layers
    h = params["embed"][tokens]
    if cfg.mup_enabled:
        h = (h.astype(jnp.float32) * math.sqrt(cfg.hidden_size)).astype(h.dtype)
    live = (positions < ctx_lens[:, None]).reshape(b * t)
    tables = split_tables(page_tables)

    def layer(carry, l, kind, gi):
        hidden, kv_k, kv_v, counts = carry
        ffn = ("dense", l) if isinstance(l, int) and l < k else ("experts", l - k)
        hidden, kv_k, kv_v, c = _layer(
            w, cfg, hidden, live, l, kind, gi, ffn, positions, kv_k, kv_v, tables,
            ctx_lens, page_size, block_pages, attn_impl)
        return hidden, kv_k, kv_v, counts + c

    carry = (h, kv_k, kv_v, jnp.zeros((len(EXPERT_COUNTS),), jnp.int32))
    # The leading layers, one by one, up to the first whole period of
    # expert layers; then the periods, one scan.
    head = -(-k // n) * n
    at = {SLIDING: 0, FULL: 0}
    for l in range(head):
        kind = cfg.layer_types[l]
        carry = layer(carry, l, kind, at[kind])
        at[kind] += 1

    def period(carry, p):
        l0 = head + p * n

        def sliding(j, c):
            return layer(c, l0 + j, SLIDING, at[SLIDING] + p * (n - 1) + j)

        carry = jax.lax.fori_loop(0, n - 1, sliding, carry)
        return layer(carry, l0 + n - 1, FULL, at[FULL] + p), None

    periods = (cfg.num_hidden_layers - head) // n
    if periods:
        carry, _ = jax.lax.scan(period, carry, jnp.arange(periods, dtype=jnp.int32))
    return carry


# The step programs' pair (``AfmoeConfig.forwards``). The mixed step runs
# the whole stack as ``[N / ragged_block, ragged_block]`` with per-block
# gathered tables (``family.serving_forwards``), a block of queries its row's
# window.
forward_counted, forward_ragged_counted = serving_forwards(_forward_hidden)


def forward_impl(params: Params, cfg: AfmoeConfig, *chunk, **kw):
    """One forward chunk ``[B, T]`` (decode: T = 1; a prefill chunk a row),
    the serving signature: (logits [B, T, vocab] f32, kv_k', kv_v')."""
    return forward_counted(params, cfg, *chunk, **kw)[:3]
