"""Qwen3-Next in JAX: a PERIOD of unlike layers — three Gated DeltaNet
(linear attention) layers, then one gated softmax-attention layer — each
followed by an expert layer with a shared expert, of which this process
holds a share.

Source: ``Qwen/Qwen3-Next-80B-A3B-Instruct`` ``config.json`` (``model_type``
``qwen3_next``; the field names below are that file's, so a configuration
file that copies it is checked key by key). With ``RMSNorm1(x; w) = x *
rsqrt(mean(x^2) + eps) * (1 + w)``, the family's zero-centred norm, layer
``i`` of ``num_hidden_layers`` is::

    h = h + Mixer_i(RMSNorm1(h))
    h = h + MoE(RMSNorm1(h))

and the mixer is full attention where ``(i + 1) % full_attention_interval
== 0``, Gated DeltaNet otherwise; a final ``RMSNorm1``, an untied head.

*Gated attention* (``num_attention_heads`` query and ``num_key_value_heads``
KV heads of ``head_dim``): ``[q | gate] = x Wq`` per head; ``k = x Wk``, ``v
= x Wv``; ``q, k = RMSNorm1`` per head; rotary on the first
``partial_rotary_factor`` of a head's values; softmax attention over
``sqrt(head_dim)``; ``out = (attn * sigmoid(gate)) Wo``. Its keys and values
live in the paged pool, one pool layer a PERIOD.

*Gated DeltaNet* (``linear_num_key_heads`` key heads and
``linear_num_value_heads`` value heads): ``[q, k, v, z] = x Wqkvz``, ``[b, a]
= x Wba``; ``[q, k, v]`` pass a causal depthwise convolution of width
``linear_conv_kernel_dim`` and SiLU; ``q, k`` L2-normalised per head, ``q /
sqrt(d_k)``, a key head serving ``value heads / key heads`` value heads;
``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; the
gated delta rule (:mod:`runbookai_tpu.ops.gated_delta`) per value head;
``o = RMSNorm(o; w) * SiLU(z)`` per head (plain weight); ``out = o Wout``.
Its state is NOT token rows: a float32 ``[d_k, d_v]`` matrix a value head
and the convolution's last inputs (float32 too), a SEQUENCE. They live in a pool indexed
by the engine's batch slot (``state_pool_spec``), beside the paged pool.

*MoE*: ``p = softmax_f32(x Wg)`` over ``num_experts``; the
``num_experts_per_tok`` largest, renormalised to sum to one; ``y = sum_j w_j
SwiGLU_j(x) + sigmoid(x w_sg) SwiGLU_shared(x)``.

**The share.** ``n_experts_held`` experts from ``first_expert`` on live here
(one chip of an expert-parallel group). The router keeps every output and
every pick; this chip computes its own experts' part and the shared expert
for its tokens, and what the absent experts would add is left out — no code
stands in for the other chips or their exchange.

The serving contract is :mod:`runbookai_tpu.models.family`'s
(``serving_forwards``) with its two keywords ``state`` (the state pool) and
``state_rows`` (which slot each row of the call is; None: row ``i`` is slot
``i``) in use, and the pool as the call left it in the result. One
``lax.scan`` over PERIODS, the four layers of
a period unrolled in its body; every leaf is indexed where it is used
(``models/longcat.py`` says why), and the two pools ride the
scan's carry and are written in place.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from runbookai_tpu.models.family import (
    EXPERT_COUNTS,
    NO_ROLLBACK,
    Family,
    Params,
    _stacked_normal,
    register,
    serving_forwards,
)
from runbookai_tpu.ops.attention import (
    paged_attention,
    pool_rows,
    write_kv_pages_batch,
)
from runbookai_tpu.ops.dense import qmm, rms_norm
from runbookai_tpu.ops.gated_delta import (
    BLOCK,
    causal_conv_tail,
    chunk_gated_delta,
    gated_delta_step,
    l2_normalise,
    mask_pads,
)
from runbookai_tpu.ops.moe import (
    held_capacity,
    held_expert_ffn,
    route_renormalised,
    shared_expert,
)
from runbookai_tpu.ops.rope import apply_rope


@dataclass(frozen=True)
class Qwen3NextConfig(Family):
    name: str
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_experts: int
    num_experts_per_tok: int
    # The share of the experts this process holds: experts ``first_expert
    # .. first_expert + n_experts_held - 1`` of every layer.
    n_experts_held: int
    first_expert: int = 0
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 0.25
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10_000_000.0
    max_position_embeddings: int = 262_144
    # Of the published config and unused by any layer: every layer is an
    # expert layer (``decoder_sparse_step`` 1, no ``mlp_only_layers``).
    intermediate_size: int = 5120
    decoder_sparse_step: int = 1
    # State snapshots the KV manager keeps behind prefix hits (the
    # configuration sizes the pool; ``engine/kv_cache.py`` says what for).
    state_snapshots: int = 16
    family: str = "qwen2"  # the chat template: the family renders ChatML

    # ``attn_impl="pallas"`` is the Pallas decode walk over the paged pool
    # for the one-token rows (2 kv heads of 256, a group of 8 query rows a
    # head: :func:`attend`); a prefill run keeps XLA's one-row walk, so the
    # engine probes no chunk kernel for this family.
    pallas_prefill = False
    one_path = True
    no_prompt_lookup = f"prompt-lookup speculation ({NO_ROLLBACK})"
    no_draft_model = f"draft-model speculation ({NO_ROLLBACK})"
    family_name = "qwen3-next"
    hf_model_types = ("qwen3_next",)
    checkpoint_tensors = "linear-attention and expert tensor names"

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
    def n_periods(self) -> int:
        return self.num_hidden_layers // self.full_attention_interval

    @property
    def n_linear_layers(self) -> int:
        return self.num_hidden_layers - self.n_periods

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def kv_pool_spec(self) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
        """The paged pool: keys and values of the FULL-attention layers
        only, one pool layer a period."""
        side = (self.n_periods, self.num_key_value_heads, self.head_dim)
        return side, side

    @property
    def state_pool_spec(self) -> tuple[tuple[tuple[int, ...], Any], ...]:
        """The state pool's arrays as (shape a slot, dtype), each with the
        linear layers leading: the engine allocates ``[linear layers, slots,
        *shape]``. The delta rule's matrices and the convolution's tail,
        both float32 (the tail holds the layer's inputs as its projection
        computed them: :func:`gdn_project`)."""
        return (((self.n_linear_layers, self.linear_num_value_heads,
                  self.linear_key_head_dim, self.linear_value_head_dim),
                 jnp.float32),
                ((self.n_linear_layers, self.linear_conv_kernel_dim - 1,
                  self.conv_channels), jnp.float32))

    def forwards(self):
        return forward_counted, forward_ragged_counted

    def init_params(self, key, dtype=jnp.bfloat16, quantized=False) -> Params:
        return init_params(key, self, dtype)

    # ---- counts (the memory plan's and the MFU model's) ----------------

    @property
    def _moe_params(self) -> int:
        """Router, shared expert and its gate, the layer's two norms."""
        d = self.hidden_size
        return (d * self.num_experts + 3 * d * self.shared_expert_intermediate_size
                + d + 2 * d)

    @property
    def _expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    @property
    def _attn_params(self) -> int:
        d, hd = self.hidden_size, self.head_dim
        return (d * self.num_attention_heads * 2 * hd
                + 2 * d * self.num_key_value_heads * hd
                + self.num_attention_heads * hd * d + 2 * hd)

    @property
    def _linear_params(self) -> int:
        d, hv = self.hidden_size, self.linear_num_value_heads
        vd = hv * self.linear_value_head_dim
        return (d * (self.conv_channels + vd) + d * 2 * hv
                + self.linear_conv_kernel_dim * self.conv_channels + 2 * hv
                + self.linear_value_head_dim + vd * d)

    @property
    def matmul_params(self) -> int:
        """Params in matmuls per token, a held expert counted for its
        expected share of a token's picks (llama.py's ``N`` of ``2 N``)."""
        mixers = (self.n_periods * self._attn_params
                  + self.n_linear_layers * self._linear_params)
        picks = self.num_experts_per_tok * self.n_experts_held / self.num_experts
        return int(mixers + self.num_hidden_layers
                   * (self._moe_params + picks * self._expert_params)
                   + self.hidden_size * self.vocab_size)

    @property
    def total_params(self) -> int:
        """Every weight held HERE (the memory-side count)."""
        return (self.n_periods * self._attn_params
                + self.n_linear_layers * self._linear_params
                + self.num_hidden_layers
                * (self._moe_params + self.n_experts_held * self._expert_params)
                + 2 * self.hidden_size * self.vocab_size + self.hidden_size)


_PUBLISHED = dict(
    hidden_size=2048, num_attention_heads=16, num_key_value_heads=2,
    head_dim=256, linear_num_key_heads=16, linear_num_value_heads=32,
    linear_key_head_dim=128, linear_value_head_dim=128,
    moe_intermediate_size=512, shared_expert_intermediate_size=512,
    num_experts=512, num_experts_per_tok=10)

CONFIGS: dict[str, Qwen3NextConfig] = register({
    # The published model (config.json): 48 layers, every expert held. 80B
    # parameters: no single process of this repo holds it; it is the entry
    # a cut configuration is checked against.
    "qwen3-next-80b-a3b-instruct": Qwen3NextConfig(
        name="qwen3-next-80b-a3b-instruct", vocab_size=151_936,
        num_hidden_layers=48, n_experts_held=512, **_PUBLISHED),
    # One chip's share of it where four chips (one host) share each layer,
    # cut to one v5e chip (examples/serve/qwen3-next-80b-ep4.yaml; the
    # benchmark's configuration file states the same cut): 12 of 48 layers
    # (three periods), experts 0-127 of 512, a quarter of the vocabulary.
    # 10.85 GB in bf16.
    "qwen3-next-80b-ep4": Qwen3NextConfig(
        name="qwen3-next-80b-ep4", vocab_size=37_984, num_hidden_layers=12,
        n_experts_held=128, **_PUBLISHED),
    # Tiny, for CPU tests: byte-tokenizer vocabulary, two periods, 8 of 32
    # experts held (the second share of four), two value heads a key head.
    "qwen3-next-test": Qwen3NextConfig(
        name="qwen3-next-test", vocab_size=262, hidden_size=64,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=32, num_experts_per_tok=4, n_experts_held=8,
        first_expert=8, rope_theta=10_000.0, max_position_embeddings=8192,
        intermediate_size=128, state_snapshots=4),
})


def leaf_shapes(cfg: Qwen3NextConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The stacked matrices as ``name -> (shape, fan_in)``, in init order:
    the expert layer's by LAYER, attention's by PERIOD, the linear mixer's
    by LINEAR layer (``3 * period + j``)."""
    L, P, Ll, d = (cfg.num_hidden_layers, cfg.n_periods, cfg.n_linear_layers,
                   cfg.hidden_size)
    hd, fe, fs = cfg.head_dim, cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    vd = cfg.linear_num_value_heads * cfg.linear_value_head_dim
    return {
        "wq": ((P, d, cfg.num_attention_heads * 2 * hd), d),
        "wk": ((P, d, cfg.num_key_value_heads * hd), d),
        "wv": ((P, d, cfg.num_key_value_heads * hd), d),
        "wo": ((P, cfg.num_attention_heads * hd, d), cfg.num_attention_heads * hd),
        "w_qkvz": ((Ll, d, cfg.conv_channels + vd), d),
        "w_ba": ((Ll, d, 2 * cfg.linear_num_value_heads), d),
        "w_out": ((Ll, vd, d), vd),
        "e_gate": ((L, cfg.n_experts_held, d, fe), d),
        "e_up": ((L, cfg.n_experts_held, d, fe), d),
        "e_down": ((L, cfg.n_experts_held, fe, d), fe),
        "s_gate": ((L, d, fs), d),
        "s_up": ((L, d, fs), d),
        "s_down": ((L, fs, d), fs),
        "s_sig": ((L, d, 1), d),
    }


def init_params(key: jax.Array, cfg: Qwen3NextConfig, dtype=jnp.bfloat16) -> Params:
    """Random-init params, leaf by leaf. The matrices are normal over
    sqrt(fan-in); the router is float32 (a score decides which experts run);
    ``A_log``, ``dt_bias``, the convolution and the norms are drawn as the
    published initialisation draws them: ``A ~ U(0, 16)`` and its log,
    ``dt_bias`` ones, the depthwise convolution ``U(-1/2, 1/2)`` (a fan-in
    of ``linear_conv_kernel_dim`` = 4), zero-centred norm weights zero, the
    gated norm's ones."""
    k_embed, k_layers, k_head, k_router, k_gdn = jax.random.split(key, 5)
    L, P, Ll, d = (cfg.num_hidden_layers, cfg.n_periods, cfg.n_linear_layers,
                   cfg.hidden_size)
    shapes = leaf_shapes(cfg)
    sample = jax.jit(_stacked_normal, static_argnums=(1, 2, 3))
    layers: dict[str, Any] = {
        name: sample(k, shape, fan_in, jnp.dtype(dtype))
        for k, (name, (shape, fan_in)) in zip(
            jax.random.split(k_layers, len(shapes)), shapes.items())}
    layers["router"] = (jax.random.normal(k_router, (L, d, cfg.num_experts),
                                          jnp.float32) / jnp.sqrt(jnp.float32(d)))
    k_a, k_conv = jax.random.split(k_gdn)
    hv = cfg.linear_num_value_heads
    layers["a_log"] = jnp.log(jax.random.uniform(
        k_a, (Ll, hv), jnp.float32, minval=1e-3, maxval=16.0))
    layers["dt_bias"] = jnp.ones((Ll, hv), jnp.float32)
    layers["conv"] = jax.random.uniform(
        k_conv, (Ll, cfg.linear_conv_kernel_dim, cfg.conv_channels), jnp.float32,
        minval=-0.5, maxval=0.5).astype(dtype)
    layers["g_norm"] = jnp.ones((Ll, cfg.linear_value_head_dim), jnp.float32)
    layers["in_norm"] = jnp.zeros((L, d), jnp.float32)
    layers["post_norm"] = jnp.zeros((L, d), jnp.float32)
    layers["q_norm"] = jnp.zeros((P, cfg.head_dim), jnp.float32)
    layers["k_norm"] = jnp.zeros((P, cfg.head_dim), jnp.float32)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    return {"embed": dense(k_embed, (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.zeros((d,), jnp.float32),
            "lm_head": dense(k_head, (d, cfg.vocab_size), d)}


EXPERT_LEAVES = ("e_gate", "e_up", "e_down")
# Slots a held expert's queue gets, in expected loads under even routing
# (``ops/moe.held_capacity``; a longer queue takes the exact slow path). Four:
# a mixed step counts its pads among its 1,024 tokens, so the live load is
# about half the expected one already, and with 128 small experts held the
# batched product's cost is its slots (128 x 160 rows of 2048 at eight).
SLOT_FACTOR = 4


def empty_state(cfg: Qwen3NextConfig, slots: int) -> tuple[jnp.ndarray, ...]:
    """A zeroed state pool of ``slots`` slots (``state_pool_spec``)."""
    return tuple(jnp.zeros((shape[0], slots, *shape[1:]), dtype)
                 for shape, dtype in cfg.state_pool_spec)


def moe_block(u: jnp.ndarray, live: jnp.ndarray, w: dict, li,
              cfg: Qwen3NextConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``MoE(u)`` of this share for ``u`` [N, D] — the held experts' part
    and the shared expert — and its counts (``EXPERT_COUNTS``; ``zero``
    always 0) over the tokens ``live`` [N]. ``w`` holds the STACKED leaves,
    ``li`` the layer (``ops/moe.held_expert_ffn`` says why)."""
    n = u.shape[0]
    held_n = cfg.n_experts_held
    chosen, weight = route_renormalised(u, w["router"][li], cfg.num_experts_per_tok)
    local = chosen - cfg.first_expert
    held = (local >= 0) & (local < held_n)
    lv = live[:, None]
    # Only live tokens queue at an expert: what a pad adds is never read.
    local = jnp.where(held & lv, local, held_n)
    m, overflow = held_expert_ffn(
        u, local, jnp.where(held, weight, 0.0), w["e_gate"], w["e_up"],
        w["e_down"], held_capacity(n, cfg.num_experts_per_tok, cfg.num_experts,
                                   factor=SLOT_FACTOR), layer=li)
    m = m + shared_expert(u, w["s_gate"][li], w["s_up"][li], w["s_down"][li],
                          w["s_sig"][li]).astype(jnp.float32)
    touched = jnp.zeros((held_n + 1,), jnp.int32).at[local].max(1)[:held_n]
    counts = jnp.stack([jnp.sum(lv & held), jnp.int32(0), jnp.sum(lv & ~held),
                        jnp.sum(touched), overflow])
    return m.astype(u.dtype), counts.astype(jnp.int32)


def _norm1(x, weight, eps):
    return rms_norm(x, 1.0 + weight, eps)


def attention_inputs(x, w, pi, cfg, positions):
    """The full-attention mixer's projections over ``x`` [B, T, D]: (q after
    its norm and rotary [B, T, H, hd], the output gate [B, T, H, hd], k
    likewise [B, T, KV, hd], v [B, T, KV, hd])."""
    b, t, _ = x.shape
    n_h, n_kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    rot = int(hd * cfg.partial_rotary_factor)
    qg = qmm(x, w["wq"][pi]).reshape(b, t, n_h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = qmm(x, w["wk"][pi]).reshape(b, t, n_kv, hd)
    v = qmm(x, w["wv"][pi]).reshape(b, t, n_kv, hd)
    q = _norm1(q, w["q_norm"][pi], cfg.rms_norm_eps)
    k = _norm1(k, w["k_norm"][pi], cfg.rms_norm_eps)

    def rotary(y):
        return jnp.concatenate(
            [apply_rope(y[..., :rot], positions, cfg.rope_theta), y[..., rot:]],
            axis=-1)

    return rotary(q), gate, rotary(k), v


def pallas_walks(q, kv_k, attn_impl: str) -> bool:
    """Whether :func:`attend` runs the Pallas decode walk: ``attn_impl=
    "pallas"``, one token a row, and a kv-head axis on Mosaic's tile. Two
    fp8 heads are under it: the kernels would pad them, a copy of what they
    are handed a call, so that pool takes XLA's walk. Static, by shape."""
    from runbookai_tpu.ops.paged_attention_pallas import heads_on_tile

    return (attn_impl == "pallas" and q.shape[1] == 1
            and heads_on_tile(kv_k.shape[-2], kv_k.dtype))


def attend(q, pi, kv_k, kv_v, page_tables, ctx_lens, positions, page_size,
           block_pages, attn_impl="xla"):
    """Softmax attention of ``q`` [B, T, H, hd] over period ``pi``'s pages
    (a row's positions contiguous; a pad at the trash position, its output
    dropped by the caller).

    One token a row (a decode pass, a mixed step's decode rows) is the
    Pallas decode walk where :func:`pallas_walks` says so, over the pool the
    scan carries, ``pi`` the kernel's layer operand: a row reads its own
    live pages, no other's, and a free slot (its position not under its
    context) is one empty grid step that writes zeros.

    A prefill run, and every row under ``attn_impl="xla"``, is XLA's walk: it
    gathers its pages out of the WHOLE pool's row view, the period's pages
    found by shifting the table (a layer's slice handed to the loop was
    copied out first, 67 MB a side, seen in the compiled program),
    ``block_pages`` pages for every row, live or free, as far as the batch's
    longest context. Over one row's run that is 2x ahead of the chunk walk
    (PERF.md section 6, PR 43); over 64 slots with 14 live it was a third
    of the device's time."""
    if pallas_walks(q, kv_k, attn_impl):
        from runbookai_tpu.ops.paged_attention_pallas import paged_layer_attention

        live = positions[:, 0] < ctx_lens
        return paged_layer_attention(
            q, kv_k, kv_v, pi, page_tables, jnp.where(live, ctx_lens, 0),
            positions, page_size, name="paged_decode_walk")
    shifted = page_tables + pi * (kv_k.shape[1] // page_size)
    return paged_attention(
        q, pool_rows(kv_k, pi)[0], pool_rows(kv_v, pi)[0], shifted, ctx_lens,
        positions, page_size=page_size, block_pages=block_pages, walk_live=True,
        gather_pages=True)


def attention_output(attn, gate, w, pi):
    """``(attn * sigmoid(gate)) Wo``."""
    attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(attn.dtype)
    return qmm(attn.reshape(*attn.shape[:-2], -1), w["wo"][pi])


def gated_attention(x, w, pi, cfg, positions, kv_k, kv_v, page_tables,
                    ctx_lens, page_size, block_pages, attn_impl="xla"):
    """The full-attention mixer of period ``pi`` over ``x`` [B, T, D]:
    (out [B, T, D], kv_k', kv_v')."""
    q, gate, k, v = attention_inputs(x, w, pi, cfg, positions)
    kv_k = write_kv_pages_batch(kv_k, k, positions, page_tables, page_size, layer=pi)
    kv_v = write_kv_pages_batch(kv_v, v, positions, page_tables, page_size, layer=pi)
    attn = attend(q, pi, kv_k, kv_v, page_tables, ctx_lens, positions, page_size,
                  block_pages, attn_impl)
    return attention_output(attn, gate, w, pi), kv_k, kv_v


def gdn_project(x, w, li):
    """The linear mixer's two input products over ``x`` [..., D]:
    (``[q | k | v]`` before the convolution, ``z``, ``[b | a]``), each as
    the product's float32 accumulator left it. Rounded to bfloat16 here,
    what feeds the delta rule cost the served tokens a logit gap of up to
    3.2 on the chip where the reference in fp8 reads 3.4 (PERF.md section
    2): the rule solves a linear system in the keys it has written, and
    their rounding is amplified by its conditioning."""
    def product(m):
        return jnp.dot(x, m, preferred_element_type=jnp.float32)

    qkvz = product(w["w_qkvz"][li])
    split = qkvz.shape[-1] - w["w_out"].shape[-2]
    return qkvz[..., :split], qkvz[..., split:], product(w["w_ba"][li])


def gdn_recur(mixed, ba, live, w, li, cfg, s_rows, tail_rows):
    """The recurrence of linear layer ``li`` over runs of tokens, a run a
    row: ``mixed`` [R, T, C], ``ba`` [R, T, 2 Hv], ``live`` [R, T] (a row's
    real tokens come first), ``s_rows`` [R, Hv, dk, dv] and ``tail_rows`` [R,
    W - 1, C] each row's state going in. Returns (o [R, T, Hv, dv] float32
    before the gated norm, s_rows', tail_rows'). One token a row takes the
    recurrent step, more the chunked rule."""
    r, t, _ = mixed.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    y, tail_rows = causal_conv_tail(mixed, tail_rows, w["conv"][li],
                                    jnp.sum(live, axis=1, dtype=jnp.int32))
    q = l2_normalise(y[..., :hk * dk].reshape(r, t, hk, dk)) / math.sqrt(dk)
    k = l2_normalise(y[..., hk * dk:2 * hk * dk].reshape(r, t, hk, dk))
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    v = y[..., 2 * hk * dk:].reshape(r, t, hv, dv)
    baf = ba.astype(jnp.float32)
    beta = jax.nn.sigmoid(baf[..., :hv])
    g = -jnp.exp(w["a_log"][li]) * jax.nn.softplus(baf[..., hv:] + w["dt_bias"][li])
    k, g, beta = mask_pads(k, g, beta, live)
    if t == 1:
        o, s_rows = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                     beta[:, 0], s_rows)
        return o[:, None], s_rows, tail_rows
    pad = -t % BLOCK

    def padded(a):  # inert tokens: zero k, g and beta leave the state alone
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    o, s_rows = chunk_gated_delta(*(padded(a) for a in (q, k, v, g, beta)), s_rows)
    return o[:, :t], s_rows, tail_rows


def gdn_output(o, z, w, li, cfg):
    """``RMSNorm(o; w) * SiLU(z)`` per value head, then ``Wout``."""
    lead = z.shape[:-1]
    zh = z.reshape(*lead, cfg.linear_num_value_heads, cfg.linear_value_head_dim)
    gated = (rms_norm(o, w["g_norm"][li], cfg.rms_norm_eps)
             * jax.nn.silu(zh.astype(jnp.float32)))
    return qmm(gated.reshape(*lead, -1).astype(w["w_out"].dtype), w["w_out"][li])


def _state_layer(state, li):
    return tuple(jax.lax.dynamic_index_in_dim(a, li, keepdims=False) for a in state)


def _put_state_layer(state, li, new):
    return tuple(jax.lax.dynamic_update_index_in_dim(a, n.astype(a.dtype), li, 0)
                 for a, n in zip(state, new))


def _forward_hidden(params, cfg, tokens, positions, kv_k, kv_v, page_tables,
                    ctx_lens, page_size, block_pages, state, linear_mixer,
                    full_mixer=None, attn_impl="xla"):
    """The stack over one paged chunk ``[B, T]``, without the head: (hidden
    [B, T, D], kv_k', kv_v', expert counts, state'). ``linear_mixer(x, live,
    li, state) -> (out, state')`` runs a linear layer over the normed
    hidden: the two forwards lay the same tokens out as runs differently.
    ``full_mixer(x, pi, kv_k, kv_v) -> (out, kv_k', kv_v')`` likewise for a
    full-attention layer (None: :func:`gated_attention` over the chunk as
    it is laid out)."""
    if "lora" in params:
        raise ValueError("the qwen3-next forward has no LoRA rows")
    if isinstance(kv_k, tuple):
        raise ValueError("the qwen3-next forward has no int8 (scaled) KV pool")
    b, t = tokens.shape
    d, eps = cfg.hidden_size, cfg.rms_norm_eps
    interval = cfg.full_attention_interval
    w = params["layers"]
    h = params["embed"][tokens]
    live = positions < ctx_lens[:, None]
    live_flat = live.reshape(b * t)

    def period(carry, pi):
        hidden, kv_k, kv_v, state, counts = carry
        for j in range(interval):
            li = pi * interval + j
            x = _norm1(hidden, w["in_norm"][li], eps)
            if j == interval - 1 and full_mixer is not None:
                o, kv_k, kv_v = full_mixer(x, pi, kv_k, kv_v)
            elif j == interval - 1:
                o, kv_k, kv_v = gated_attention(
                    x, w, pi, cfg, positions, kv_k, kv_v, page_tables,
                    ctx_lens, page_size, block_pages, attn_impl)
            else:
                o, state = linear_mixer(x, live, pi * (interval - 1) + j, state)
            hidden = hidden + o
            u = _norm1(hidden, w["post_norm"][li], eps)
            m, c = moe_block(u.reshape(b * t, d), live_flat, w, li, cfg)
            hidden = hidden + m.reshape(b, t, d)
            counts = counts + c
        return (hidden, kv_k, kv_v, state, counts), None

    (h, kv_k, kv_v, state, counts), _ = jax.lax.scan(
        period, (h, kv_k, kv_v, state,
                 jnp.zeros((len(EXPERT_COUNTS),), jnp.int32)),
        jnp.arange(cfg.n_periods, dtype=jnp.int32))
    return h, kv_k, kv_v, counts, state


def _head(params, cfg, hidden):
    return (_norm1(hidden, params["final_norm"], cfg.rms_norm_eps)
            @ params["lm_head"]).astype(jnp.float32)


def _row_state(state, li, rows):
    """Layer ``li``'s state of the slots ``rows``, gathered out of the pool
    (a slot out of range reads the last slot: its row is a pad, and what it
    computes is dropped)."""
    return tuple(a[li, jnp.clip(rows, 0, a.shape[1] - 1)] for a in state)


def hidden_chunk(params, cfg, tokens, positions, kv_k, kv_v, page_tables,
                 ctx_lens, page_size, block_pages, attn_impl="xla", *, state,
                 state_rows=None):
    """The stack over one chunk ``[B, T]`` (decode: T = 1; a prefill chunk
    a row): (hidden [B, T, D], kv_k', kv_v', expert counts, state'). Row
    ``i`` runs from and writes back slot ``state_rows[i]`` of the state pool
    (None: slot ``i``, the decode programs; a slot out of range is a pad
    row's and is dropped)."""
    w = params["layers"]

    def linear_mixer(x, live, li, state):
        mixed, z, ba = gdn_project(x, w, li)
        if state_rows is None:  # every slot, in place
            o, *new = gdn_recur(mixed, ba, live, w, li, cfg, *_state_layer(state, li))
            state = _put_state_layer(state, li, new)
        else:
            o, *new = gdn_recur(mixed, ba, live, w, li, cfg,
                                *_row_state(state, li, state_rows))
            state = tuple(a.at[li, state_rows].set(n.astype(a.dtype), mode="drop")
                          for a, n in zip(state, new))
        return gdn_output(o, z, w, li, cfg), state

    return _forward_hidden(
        params, cfg, tokens, positions, kv_k, kv_v, page_tables, ctx_lens,
        page_size, block_pages, state, linear_mixer, attn_impl=attn_impl)


def hidden_ragged(params, cfg, tokens, positions, row_ids, kv_k, kv_v,
                  page_tables, ctx_lens, page_size, block_pages, ragged_block,
                  attn_impl="xla", *, state, state_rows):
    """The stack over the mixed step's flat ragged batch, llama.py's layout:
    (hidden [N / ragged_block, ragged_block, D], kv_k', kv_v', expert counts,
    state'). The buffer is the engine's: one block of ``ragged_block``
    tokens a decode slot first (slot ``s``'s token at ``s * ragged_block``),
    then the prefill rows' chunks, each from a multiple of ``ragged_block``.
    The projections, the expert layers and the page writes run over the
    flat buffer (as ``[N / ragged_block, ragged_block]`` with per-block
    gathered tables, ``family.serving_forwards``' layout); both mixers run
    it by SEGMENT: the decode tokens as one-token rows of every slot, and
    each FILLED prefill row's chunk gathered into a run of its own — a
    linear layer from the state of its slot (``state_rows[row]``) and
    written back to it, a full-attention layer over its own page table."""
    n = tokens.shape[0]
    rq = ragged_block
    nb = n // rq
    slots = state[0].shape[1]
    n_dec = slots * rq                       # the decode section's tokens
    n_pf = page_tables.shape[0] - slots - 1  # prefill rows (then one null row)
    t_pf = n - n_dec
    w = params["layers"]
    # Where each prefill row's chunk lies in the prefill section.
    pf_ids = row_ids[n_dec:]
    pf_rows = slots + jnp.arange(n_pf, dtype=jnp.int32)
    start = jnp.argmax(pf_ids[None, :] == pf_rows[:, None], axis=1)
    idx = start[:, None] + jnp.arange(t_pf)[None, :]          # [n_pf, t_pf]
    idx_c = jnp.minimum(idx, t_pf - 1)
    pf_slots = state_rows[slots:slots + n_pf]
    # The entries of a row's run that are its own (the run of a row whose
    # chunk is shorter than the budget runs on into its neighbour's), and
    # how many rows have a chunk at all: the engine fills them in order.
    filled = (pf_ids[idx_c] == pf_rows[:, None]) & (idx < t_pf)
    rows_filled = jnp.sum(jnp.any(filled, axis=1), dtype=jnp.int32)

    def linear_mixer(x, live, li, state):
        x, live = x.reshape(n, -1), live.reshape(n)
        mixed, z, ba = gdn_project(x, w, li)
        dec = lambda a: a[:n_dec].reshape(slots, rq, *a.shape[1:])[:, :1]  # noqa: E731
        o_dec, *new = gdn_recur(dec(mixed), dec(ba), dec(live), w, li, cfg,
                                *_state_layer(state, li))
        state = _put_state_layer(state, li, new)
        in_row = filled & live[n_dec:][idx_c]
        o = jnp.zeros((n + 1, *o_dec.shape[2:]), jnp.float32)
        o = o.at[jnp.arange(slots) * rq].set(o_dec[:, 0])

        def prefill_row(j, carry):
            # One prefill row's chunk as a run of its own. The engine fills
            # the rows in order, so the loop runs the rows that HAVE a chunk
            # and no more: laid out all at once, every row cost a run of
            # the whole chunk budget, chunk or no chunk (the triangular
            # solves of three empty rows were 18 ms of a mixed step).
            state, o = carry
            at, slot = idx_c[j], pf_slots[j][None]
            o_j, *new = gdn_recur(mixed[n_dec:][at][None], ba[n_dec:][at][None],
                                  in_row[j][None], w, li, cfg,
                                  *_row_state(state, li, slot))
            state = tuple(a.at[li, slot].set(nw.astype(a.dtype), mode="drop")
                          for a, nw in zip(state, new))
            return state, o.at[jnp.where(in_row[j], n_dec + idx[j], n)].set(o_j[0])

        state, o = jax.lax.fori_loop(0, rows_filled, prefill_row, (state, o))
        o = o[:n]
        return gdn_output(o, z, w, li, cfg).reshape(nb, rq, -1), state

    block_rows = row_ids.reshape(nb, rq)[:, 0]
    block_tables, block_pos = page_tables[block_rows], positions.reshape(nb, rq)
    trash = (page_tables.shape[1] - 1) * page_size  # a pad's position

    def full_mixer(x, pi, kv_k, kv_v):
        # Keys and values are written block by block, as llama.py's ragged
        # forward writes them. The queries attend by SEGMENT: every decode
        # slot's one token as a row of its own, and each filled prefill row
        # as one run over ITS pages. As blocks of ``ragged_block`` queries,
        # the 64 blocks of one 512-token chunk each gathered the row's
        # pages again, all 128 blocks to the batch's longest context (14.6
        # ms a layer of a mixed step where a decode pass's walk took 7).
        q, gate, k, v = attention_inputs(x, w, pi, cfg, block_pos)
        kv_k = write_kv_pages_batch(kv_k, k, block_pos, block_tables, page_size, layer=pi)
        kv_v = write_kv_pages_batch(kv_v, v, block_pos, block_tables, page_size, layer=pi)
        q = q.reshape(n, *q.shape[2:])
        dec = attend(q[:n_dec].reshape(slots, rq, *q.shape[1:])[:, :1], pi, kv_k,
                     kv_v, page_tables[:slots], ctx_lens[:slots],
                     positions[:n_dec].reshape(slots, rq)[:, :1], page_size, block_pages,
                     attn_impl)
        attn = jnp.zeros((n + 1, *q.shape[1:]), q.dtype)
        attn = attn.at[jnp.arange(slots) * rq].set(dec[:, 0])
        def prefill_row(j, attn):
            at, row = idx_c[j], slots + j
            pos = jnp.where(filled[j], positions[n_dec:][at], trash)
            out = attend(q[n_dec:][at][None], pi, kv_k, kv_v, page_tables[row][None],
                         ctx_lens[row][None], pos[None], page_size, block_pages)
            return attn.at[jnp.where(filled[j], n_dec + idx[j], n)].set(out[0])

        attn = jax.lax.fori_loop(0, rows_filled, prefill_row, attn)
        out = attention_output(attn[:n], gate.reshape(n, *gate.shape[2:]), w, pi)
        return out.reshape(nb, rq, -1), kv_k, kv_v

    return _forward_hidden(
        params, cfg, tokens.reshape(nb, rq), block_pos, kv_k, kv_v, block_tables,
        ctx_lens[block_rows], page_size, block_pages, state, linear_mixer,
        full_mixer)


# The step programs' pair (``Qwen3NextConfig.forwards``): the family's own
# ragged body and its own head (the zero-centred norm).
forward_counted, forward_ragged_counted = serving_forwards(
    hidden_chunk, hidden_ragged, head=_head)
# One forward chunk, the serving signature and result: (logits, kv_k', kv_v',
# expert counts, state', None).
forward_impl = forward_counted
