"""Nemotron-H in JAX: a PATTERN of three kinds of layers, each ONE mixer
behind one norm — Mamba-2 state-space layers (``M``), expert layers (``E``)
and softmax-attention layers (``*``) — of whose experts this process holds
a share.

Source: ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` ``config.json``
(``model_type`` ``nemotron_h``; the field names below are that file's, so a
configuration file that copies it is checked key by key). Layer ``i`` of
``num_hidden_layers``, its kind the ``i``-th letter of
``hybrid_override_pattern``::

    h = h + mixer_i(RMSNorm(h))

plain RMSNorm weights, eps ``layer_norm_epsilon``; a final RMSNorm, an
untied head. No attention+FFN pair anywhere.

*M, Mamba-2* (``d_inner = mamba_num_heads x mamba_head_dim``, NOT ``expand x
hidden``; ``G = n_groups``, ``N = ssm_state_size``, the convolution over
``d_inner + 2 G N`` channels): ``[z | xBC | dt] = u W_in`` (kept as two column
blocks, ``w_in`` and ``w_dt``); ``xBC = silu(conv(xBC) + b)``, causal
depthwise of width ``conv_kernel``; ``[x | B | C]`` split, head ``h`` reading
group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)`` a head; the rule of
:mod:`runbookai_tpu.ops.ssm`; ``y = RMSNorm_g(y * silu(z))``, the gate BEFORE
the norm, the norm over each group's channels apart; ``out = y W_out``. Its
state is NOT token rows: a float32 ``[head_dim, N]`` matrix a head and the
convolution's last inputs, a SEQUENCE. They live in a pool indexed by the
engine's batch slot (``state_pool_spec``), beside the paged pool.

*\\*, attention* (``num_attention_heads`` query and ``num_key_value_heads``
KV heads of ``head_dim``): ``q, k, v = u Wq, u Wk, u Wv``, no bias, no norm,
NO rotary embedding (the Mamba layers carry position); causal softmax over
``sqrt(head_dim)``; ``out = attn Wo``. Its keys and values live in the paged
pool, one pool layer an attention layer.

*E, experts*: ``s = sigmoid_f32(u W_r)`` over ``n_routed_experts``; the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias``; weights
``routed_scaling_factor * s_j / sum of the chosen s``; ``sum_j w_j W_down,j
relu(W_up,j u)^2`` plus the shared expert ``W_down relu(W_up u)^2``, ungated.

**The share.** ``n_experts_held`` experts from ``first_expert`` on live here
(one chip of an expert-parallel group). The router keeps every output and
every pick; this chip computes its own experts' part and the shared expert
for its tokens, and what the absent experts would add is left out — no code
stands in for the other chips or their exchange.

The serving contract is ``models/qwen3_next.py``'s. The
weights are stacked BY KIND and the stack runs the pattern's runs
(:func:`layer_plan`): a loop over ``EM`` pairs, and a scan over the groups
``(EM)^n *`` whose inner loop's length the device reads — seven layer bodies
compiled for 52 layers, not 52. Every leaf is indexed where it is used, and
the two pools ride the loops' carry and are written in place.
"""

from __future__ import annotations

import re
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
# The other recurrent family: the same walks over the paged pool and the
# same state pool's rows (the one import between two family files).
from runbookai_tpu.models.qwen3_next import (  # noqa: F401 — empty_state: this module's API
    _row_state,
    attend,
    empty_state,
    pallas_walks,
)
from runbookai_tpu.ops.attention import write_kv_pages_batch
from runbookai_tpu.ops.dense import qmm, rms_norm
from runbookai_tpu.ops.gated_delta import causal_conv_tail
from runbookai_tpu.ops.moe import (
    held_capacity,
    held_expert_ffn,
    route_sigmoid,
    shared_expert,
)
from runbookai_tpu.ops.ssm import (
    gated_group_norm,
    mask_pads,
    ssm_chunk,
    ssm_step,
    ssm_step_live,
)

KINDS = "ME*"


@dataclass(frozen=True)
class NemotronHConfig(Family):
    name: str
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    hybrid_override_pattern: str
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    # The share of the experts this process holds: experts ``first_expert
    # .. first_expert + n_experts_held - 1`` of every expert layer.
    n_experts_held: int
    first_expert: int = 0
    conv_kernel: int = 4
    chunk_size: int = 128
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262_144
    # Of the published config, and what they say of the forward: one
    # routing group (no group limit), the chosen weights renormalised, one
    # shared expert, biases on the convolution only.
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    n_shared_experts: int = 1
    use_conv_bias: bool = True
    use_bias: bool = False
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    # Published and read by no layer: ``d_inner`` is heads x head size (not
    # ``expand`` x hidden), no layer is a dense FFN of ``intermediate_size``,
    # and attention applies no rotary embedding.
    expand: int = 2
    intermediate_size: int = 1856
    rope_theta: float = 10_000.0
    partial_rotary_factor: float = 1.0
    # The seeded router's bias on the choice (a checkpoint's is learnt).
    router_bias_scale: float = 1e-3
    # State snapshots the KV manager keeps behind prefix hits.
    state_snapshots: int = 8
    family: str = "qwen2"  # the chat template: ChatML (assumed)

    # ``attn_impl="pallas"`` is the Pallas decode walk over the paged pool
    # for the one-token rows (2 kv heads of 128, a group of 16 query rows a
    # head: :func:`attend_live`); a prefill run keeps XLA's one-row walk, so
    # the engine probes no chunk kernel for this family.
    pallas_prefill = False
    one_path = True
    no_prompt_lookup = f"prompt-lookup speculation ({NO_ROLLBACK})"
    no_draft_model = f"draft-model speculation ({NO_ROLLBACK})"
    family_name = "nemotron-h"
    hf_model_types = ("nemotron_h",)
    checkpoint_tensors = "Mamba-2 mixer and expert tensor names"

    def __post_init__(self):
        p = self.hybrid_override_pattern
        if len(p) != self.num_hidden_layers or set(p) - set(KINDS):
            raise ValueError(
                f"{self.name}: hybrid_override_pattern {p!r} is not "
                f"{self.num_hidden_layers} letters of {KINDS!r}")

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
        return self.layer_norm_epsilon

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    def n_kind(self, kind: str) -> int:
        return self.hybrid_override_pattern.count(kind)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def kv_pool_spec(self) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
        """The paged pool: keys and values of the attention layers only."""
        side = (self.n_kind("*"), self.num_key_value_heads, self.head_dim)
        return side, side

    @property
    def state_pool_spec(self) -> tuple[tuple[tuple[int, ...], Any], ...]:
        """The state pool's arrays as (shape a slot, dtype), each with the
        Mamba layers leading: the engine allocates ``[Mamba layers, slots,
        *shape]``. The rule's matrices and the convolution's tail, both
        float32 (the tail holds the layer's inputs as its projection
        computed them: :func:`ssm_project`)."""
        m = self.n_kind("M")
        return (((m, self.mamba_num_heads, self.mamba_head_dim,
                  self.ssm_state_size), jnp.float32),
                ((m, self.conv_kernel - 1, self.conv_channels), jnp.float32))

    def forwards(self):
        return forward_counted, forward_ragged_counted

    def init_params(self, key, dtype=jnp.bfloat16, quantized=False) -> Params:
        return init_params(key, self, dtype)

    # ---- counts (the memory plan's and the MFU model's) ----------------

    @property
    def _mamba_params(self) -> int:
        """``W_in``, ``W_out``, the convolution and its bias, ``A_log``,
        ``dt_bias``, ``D``, the gated norm, the layer's norm."""
        d, h = self.hidden_size, self.mamba_num_heads
        return (d * (self.d_inner + self.conv_channels + h) + self.d_inner * d
                + (self.conv_kernel + 1) * self.conv_channels + 3 * h
                + self.d_inner + d)

    @property
    def _attn_params(self) -> int:
        d, hd = self.hidden_size, self.head_dim
        return (2 * d * self.num_attention_heads * hd
                + 2 * d * self.num_key_value_heads * hd + d)

    @property
    def _moe_params(self) -> int:
        """Router and its bias, the shared expert, the layer's norm."""
        d = self.hidden_size
        return (d * self.n_routed_experts + self.n_routed_experts
                + 2 * d * self.moe_shared_expert_intermediate_size + d)

    @property
    def _expert_params(self) -> int:
        return 2 * self.hidden_size * self.moe_intermediate_size

    @property
    def matmul_params(self) -> int:
        """Params in matmuls per token, a held expert counted for its
        expected share of a token's picks (llama.py's ``N`` of ``2 N``)."""
        picks = self.num_experts_per_tok * self.n_experts_held / self.n_routed_experts
        return int(self.n_kind("M") * self._mamba_params
                   + self.n_kind("*") * self._attn_params
                   + self.n_kind("E") * (self._moe_params
                                         + picks * self._expert_params)
                   + self.hidden_size * self.vocab_size)

    @property
    def total_params(self) -> int:
        """Every weight held HERE (the memory-side count)."""
        return (self.n_kind("M") * self._mamba_params
                + self.n_kind("*") * self._attn_params
                + self.n_kind("E") * (self._moe_params + self.n_experts_held
                                      * self._expert_params)
                + 2 * self.hidden_size * self.vocab_size + self.hidden_size)


_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
_PUBLISHED = dict(
    hidden_size=2688, num_hidden_layers=52, hybrid_override_pattern=_PATTERN,
    num_attention_heads=32, num_key_value_heads=2, head_dim=128,
    mamba_num_heads=64, mamba_head_dim=64, n_groups=8, ssm_state_size=128,
    moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
    n_routed_experts=128, num_experts_per_tok=6)

CONFIGS: dict[str, NemotronHConfig] = register({
    # The published model (config.json): every expert held. 31.6B
    # parameters: no single process of this repo holds it; it is the entry
    # a cut configuration is checked against.
    "nemotron-3-nano-30b-a3b": NemotronHConfig(
        name="nemotron-3-nano-30b-a3b", vocab_size=131_072,
        n_experts_held=128, **_PUBLISHED),
    # One chip's share of it where the eight chips of one host share each
    # layer, at its WHOLE depth (examples/serve/nemotron-3-nano-ep8.yaml;
    # the benchmark's configuration file states the same cut): experts
    # 0-15 of 128, an eighth of the vocabulary. 10.5 GB in bf16.
    "nemotron-3-nano-ep8": NemotronHConfig(
        name="nemotron-3-nano-ep8", vocab_size=16_384, n_experts_held=16,
        **_PUBLISHED),
    # Tiny, for CPU tests: byte-tokenizer vocabulary, a pattern that holds
    # all three kinds and every kind of run, 8 of 32 experts held (the
    # second share of four), two heads a group.
    "nemotron-h-test": NemotronHConfig(
        name="nemotron-h-test", vocab_size=262, hidden_size=64,
        num_hidden_layers=14, hybrid_override_pattern="MEM*EMEM*EMEME",
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
        n_routed_experts=32, num_experts_per_tok=4, n_experts_held=8,
        first_expert=8, chunk_size=16, max_position_embeddings=8192,
        intermediate_size=32, router_bias_scale=2e-2, state_snapshots=4),
})


def layer_plan(pattern: str) -> tuple[tuple, ...]:
    """The pattern as the runs the stack loops over, in order:
    ``("groups", counts)`` — for each count, that many ``EM`` pairs and then
    an attention layer (at least two such groups running) —, ``("pairs",
    n)`` — ``n >= 2`` ``EM`` pairs — and ``("one", kind)``."""
    plan, i = [], 0
    while i < len(pattern):
        groups = re.match(r"(?:(?:EM)+\*){2,}", pattern[i:])
        pairs = re.match(r"(?:EM){2,}", pattern[i:])
        if groups:
            plan.append(("groups", tuple(len(g) // 2 for g in
                                         groups[0].split("*")[:-1])))
            i += groups.end()
        elif pairs:
            plan.append(("pairs", pairs.end() // 2))
            i += pairs.end()
        else:
            plan.append(("one", pattern[i]))
            i += 1
    return tuple(plan)


def run_plan(plan, carry, layer: dict):
    """Run the stack: ``layer[kind](carry, i) -> carry`` is layer ``i`` OF
    ITS KIND (an int or a traced scalar)."""
    at = dict.fromkeys(KINDS, 0)

    def pair(e0, m0):
        return lambda j, c: layer["M"](layer["E"](c, e0 + j), m0 + j)

    for kind, arg in plan:
        if kind == "one":
            carry = layer[arg](carry, at[arg])
            at[arg] += 1
            continue
        if kind == "pairs":
            carry = jax.lax.fori_loop(0, arg, pair(at["E"], at["M"]), carry)
            n = arg
        else:
            counts = jnp.asarray(arg, jnp.int32)
            starts = jnp.cumsum(counts) - counts

            def group(c, xs, e0=at["E"], m0=at["M"], a0=at["*"]):
                gi, start, count = xs
                c = jax.lax.fori_loop(0, count, pair(e0 + start, m0 + start), c)
                return layer["*"](c, a0 + gi), None

            carry, _ = jax.lax.scan(
                group, carry, (jnp.arange(len(arg), dtype=jnp.int32), starts, counts))
            n = sum(arg)
            at["*"] += len(arg)
        at["E"] += n
        at["M"] += n
    return carry


def leaf_shapes(cfg: NemotronHConfig) -> dict[str, tuple[tuple[int, ...], int]]:
    """The stacked matrices as ``name -> (shape, fan_in)``, in init order,
    each stacked over the layers OF ITS KIND."""
    m, e, a, d = cfg.n_kind("M"), cfg.n_kind("E"), cfg.n_kind("*"), cfg.hidden_size
    hq = cfg.num_attention_heads * cfg.head_dim
    hkv = cfg.num_key_value_heads * cfg.head_dim
    fe, fs = cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size
    return {
        "w_in": ((m, d, cfg.d_inner + cfg.conv_channels), d),   # [z | xBC]
        "w_dt": ((m, d, cfg.mamba_num_heads), d),               # [dt]
        "w_out": ((m, cfg.d_inner, d), cfg.d_inner),
        "wq": ((a, d, hq), d),
        "wk": ((a, d, hkv), d),
        "wv": ((a, d, hkv), d),
        "wo": ((a, hq, d), hq),
        "e_up": ((e, cfg.n_experts_held, d, fe), d),
        "e_down": ((e, cfg.n_experts_held, fe, d), fe),
        "s_up": ((e, d, fs), d),
        "s_down": ((e, fs, d), fs),
    }


def init_params(key: jax.Array, cfg: NemotronHConfig, dtype=jnp.bfloat16) -> Params:
    """Random-init params, leaf by leaf. The matrices are normal over
    sqrt(fan-in); the router is float32 (a score decides which experts run)
    and its bias drawn at ``router_bias_scale``; the Mamba layer's own
    leaves as the published initialisation draws them: ``A ~ U(1, 16)`` and
    its log, ``dt_bias`` the inverse softplus of ``dt`` log-uniform over
    (``time_step_min`` 0.001, ``time_step_max`` 0.1), ``D`` ones, the
    depthwise convolution and its bias ``U(-1/2, 1/2)`` (a fan-in of
    ``conv_kernel`` = 4), every norm's weight ones."""
    k_embed, k_layers, k_head, k_router, k_ssm = jax.random.split(key, 5)
    m, e, a, d = cfg.n_kind("M"), cfg.n_kind("E"), cfg.n_kind("*"), cfg.hidden_size
    shapes = leaf_shapes(cfg)
    sample = jax.jit(_stacked_normal, static_argnums=(1, 2, 3))
    layers: dict[str, Any] = {
        name: sample(k, shape, fan_in, jnp.dtype(dtype))
        for k, (name, (shape, fan_in)) in zip(
            jax.random.split(k_layers, len(shapes)), shapes.items())}
    k_r, k_b = jax.random.split(k_router)
    layers["router"] = (jax.random.normal(k_r, (e, d, cfg.n_routed_experts),
                                          jnp.float32) / jnp.sqrt(jnp.float32(d)))
    layers["router_bias"] = cfg.router_bias_scale * jax.random.normal(
        k_b, (e, cfg.n_routed_experts), jnp.float32)
    k_a, k_dt, k_conv, k_cb = jax.random.split(k_ssm, 4)
    h = cfg.mamba_num_heads
    layers["a_log"] = jnp.log(jax.random.uniform(
        k_a, (m, h), jnp.float32, minval=1.0, maxval=16.0))
    dt = jnp.exp(jax.random.uniform(k_dt, (m, h), jnp.float32,
                                    minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    layers["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
    layers["d_skip"] = jnp.ones((m, h), jnp.float32)
    layers["conv"] = jax.random.uniform(
        k_conv, (m, cfg.conv_kernel, cfg.conv_channels), jnp.float32,
        minval=-0.5, maxval=0.5).astype(dtype)
    layers["conv_bias"] = jax.random.uniform(
        k_cb, (m, cfg.conv_channels), jnp.float32, minval=-0.5,
        maxval=0.5).astype(dtype)
    layers["g_norm"] = jnp.ones((m, cfg.d_inner), jnp.float32)
    layers["m_norm"] = jnp.ones((m, d), jnp.float32)
    layers["e_norm"] = jnp.ones((e, d), jnp.float32)
    layers["a_norm"] = jnp.ones((a, d), jnp.float32)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    return {"embed": dense(k_embed, (cfg.vocab_size, d), d), "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32),
            "lm_head": dense(k_head, (d, cfg.vocab_size), d)}


# Slots a held expert's queue gets, in expected loads under even routing
# (``ops/moe.held_capacity``; a longer queue takes the exact slow path). Four,
# as qwen3_next.py: a mixed step counts its pads among its tokens (seven of
# a decode block's eight), so the live load is well under the expected one.
SLOT_FACTOR = 4


def moe_block(u: jnp.ndarray, live: jnp.ndarray, w: dict, e,
              cfg: NemotronHConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``MoE(u)`` of this share for ``u`` [N, D] in expert layer ``e`` of
    the stacked leaves ``w`` — the held experts' part and the shared expert,
    two-matrix ``relu^2`` both — and its counts (``EXPERT_COUNTS``; ``zero``
    always 0) over the tokens ``live`` [N]."""
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
        u, local, jnp.where(held, wts, 0.0), None, w["e_up"], w["e_down"],
        held_capacity(n, cfg.num_experts_per_tok, cfg.n_routed_experts,
                      factor=SLOT_FACTOR), layer=e)
    m = m + shared_expert(u, None, w["s_up"][e], w["s_down"][e]).astype(jnp.float32)
    touched = jnp.zeros((held_n + 1,), jnp.int32).at[local].max(1)[:held_n]
    counts = jnp.stack([jnp.sum(lv & held), jnp.int32(0), jnp.sum(lv & ~held),
                        jnp.sum(touched), overflow])
    return m.astype(u.dtype), counts.astype(jnp.int32)


def attention_inputs(x, w, ai, cfg):
    """Attention layer ``ai``'s projections over ``x`` [B, T, D]: (q [B, T,
    H, hd], k, v [B, T, KV, hd]). No bias, no norm, no rotary embedding."""
    b, t, _ = x.shape
    hd = cfg.head_dim
    return (qmm(x, w["wq"][ai]).reshape(b, t, cfg.num_attention_heads, hd),
            qmm(x, w["wk"][ai]).reshape(b, t, cfg.num_key_value_heads, hd),
            qmm(x, w["wv"][ai]).reshape(b, t, cfg.num_key_value_heads, hd))


# Rows a turn of :func:`attend_live` walks.
ATTEND_ROWS = 8


def attend_live(q, ai, kv_k, kv_v, page_tables, ctx_lens, positions, live,
                page_size, block_pages, attn_impl="xla"):
    """``qwen3_next.attend`` for one token a slot (``q`` [S, 1, H, hd]),
    zero where the slot is free. The Pallas decode walk takes every slot as
    it is: a free one costs it an empty grid step. XLA's walk (the page walk
    out of the WHOLE pool's row view, the layer's pages found by shifting the
    table) runs over the LIVE rows only (``live`` [S]), ``ATTEND_ROWS`` of
    them a turn of a loop whose length the device decides. That walk
    gathers ``block_pages`` pages a row and block whether the row is live or
    free and as far as the longest context of its rows: over all 48 slots
    with five rows live it was a third of a decode pass on the chip (1.2 ms
    a layer, its two gathers at 111 GB/s; PERF.md, PR 39)."""
    if pallas_walks(q, kv_k, attn_impl):
        return attend(q, ai, kv_k, kv_v, page_tables, ctx_lens, positions,
                      page_size, block_pages, attn_impl)
    s = q.shape[0]
    rows = ATTEND_ROWS
    while s % rows:
        rows -= 1
    order = jnp.argsort(~live, stable=True)                          # live rows first

    def one(i, out):
        at = jax.lax.dynamic_slice_in_dim(order, i * rows, rows)
        o = attend(q[at], ai, kv_k, kv_v, page_tables[at], ctx_lens[at], positions[at],
                   page_size, block_pages)
        return out.at[at].set(o)

    turns = (jnp.sum(live, dtype=jnp.int32) + rows - 1) // rows
    return jax.lax.fori_loop(0, turns, one, jnp.zeros_like(q))


def attention_output(attn, w, ai):
    return qmm(attn.reshape(*attn.shape[:-2], -1), w["wo"][ai])


def attention(x, w, ai, cfg, positions, kv_k, kv_v, page_tables, ctx_lens,
              page_size, block_pages, attn_impl="xla"):
    """Attention layer ``ai`` over ``x`` [B, T, D]: (out, kv_k', kv_v'). One
    token a row is a decode pass (:func:`attend_live`)."""
    q, k, v = attention_inputs(x, w, ai, cfg)
    kv_k = write_kv_pages_batch(kv_k, k, positions, page_tables, page_size, layer=ai)
    kv_v = write_kv_pages_batch(kv_v, v, positions, page_tables, page_size, layer=ai)
    if x.shape[1] == 1:
        attn = attend_live(q, ai, kv_k, kv_v, page_tables, ctx_lens, positions,
                           positions[:, 0] < ctx_lens, page_size, block_pages,
                           attn_impl)
    else:  # a prefill run: XLA's walk, whatever ``attn_impl``
        attn = attend(q, ai, kv_k, kv_v, page_tables, ctx_lens, positions, page_size,
                      block_pages)
    return attention_output(attn, w, ai), kv_k, kv_v


def ssm_project(x, w, mi):
    """The Mamba mixer's input product over ``x`` [..., D]: (``z``, ``xBC``
    before the convolution, ``dt`` before its bias), as the product's
    float32 accumulator left them: ``dt`` scales a decay that multiplies up
    over the whole sequence, and the state is a float32 sum of what ``x`` and
    ``B`` write. ``W_in`` is kept as two column blocks, ``[z | xBC]`` and
    ``[dt]``: at 10,304 columns, not a multiple of the chip's 128 lanes, the
    device keeps the stack in another order than the product reads, and
    every dispatch copied all 1.27 GB of it (seen in the compiled program)."""
    zxbc = jnp.dot(x, w["w_in"][mi], preferred_element_type=jnp.float32)
    d_inner = w["w_out"].shape[-2]
    return (zxbc[..., :d_inner], zxbc[..., d_inner:],
            jnp.dot(x, w["w_dt"][mi], preferred_element_type=jnp.float32))


def ssm_inputs(xbc, dt, live, tail_rows, w, mi, cfg):
    """What the rule takes, from the projections of runs of tokens, a run a
    row (``xbc`` [R, T, C], ``dt`` [R, T, H], ``live`` [R, T], a row's real
    tokens first; ``tail_rows`` [R, W - 1, C]): (x [R, T, H, P], B, C [R, T,
    G, N], dt [R, T, H] — 0 at a pad —, A [H], D [H], tail_rows')."""
    r, t, _ = xbc.shape
    g, n = cfg.n_groups, cfg.ssm_state_size
    y, tail_rows = causal_conv_tail(
        xbc, tail_rows, w["conv"][mi], jnp.sum(live, axis=1, dtype=jnp.int32),
        bias=w["conv_bias"][mi])
    x = y[..., :cfg.d_inner].reshape(r, t, cfg.mamba_num_heads, cfg.mamba_head_dim)
    b = y[..., cfg.d_inner:cfg.d_inner + g * n].reshape(r, t, g, n)
    c = y[..., cfg.d_inner + g * n:].reshape(r, t, g, n)
    dt = mask_pads(jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"][mi]), live)
    return x, b, c, dt, -jnp.exp(w["a_log"][mi]), w["d_skip"][mi], tail_rows


def ssm_recur(xbc, dt, live, w, mi, cfg, s_rows, tail_rows):
    """The recurrence of Mamba layer ``mi`` over runs of tokens, a run a
    row, ``s_rows`` [R, H, P, N] and ``tail_rows`` [R, W - 1, C] each row's
    state going in. Returns (y [R, T, H, P] float32 before the gated norm,
    s_rows', tail_rows'). One token a row takes the recurrent step, more the
    chunked rule."""
    x, b, c, dt, a, d, tail_rows = ssm_inputs(xbc, dt, live, tail_rows, w, mi, cfg)
    t = x.shape[1]
    if t == 1:
        with jax.named_scope("ssm.step"):
            y, s_rows = ssm_step(x[:, 0], b[:, 0], c[:, 0], dt[:, 0], a, d, s_rows)
        return y[:, None], s_rows, tail_rows
    pad = -t % cfg.chunk_size

    def padded(v):  # inert tokens: dt 0 leaves the state alone
        return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))

    with jax.named_scope("ssm.chunk"):
        y, s_rows = ssm_chunk(*(padded(v) for v in (x, b, c, dt)), a, d, s_rows,
                              cfg.chunk_size)
    return y[:, :t], s_rows, tail_rows


def ssm_decode(xbc, dt, live, w, mi, cfg, state):
    """One token a slot, row ``i`` slot ``i`` (``xbc`` [S, 1, C], ``dt`` [S,
    1, H], ``live`` [S, 1]): the convolution's tails of every slot (3 x C
    values each), the rule's matrices of the LIVE rows only, in place in the
    pool. Returns (y [S, 1, H, P] float32, state')."""
    pool, tails = state
    x, b, c, dt, a, d, tail_rows = ssm_inputs(
        xbc, dt, live, jax.lax.dynamic_index_in_dim(tails, mi, keepdims=False),
        w, mi, cfg)
    with jax.named_scope("ssm.step"):
        y, pool = ssm_step_live(pool, mi, live[:, 0], x[:, 0], b[:, 0], c[:, 0],
                                dt[:, 0], a, d)
    tails = jax.lax.dynamic_update_index_in_dim(
        tails, tail_rows.astype(tails.dtype), mi, 0)
    return y[:, None], (pool, tails)


def ssm_output(y, z, w, mi, cfg):
    """``RMSNorm_g(y * silu(z))`` over each group apart, then ``W_out``."""
    gated = gated_group_norm(y.reshape(*z.shape), z, w["g_norm"][mi],
                             cfg.n_groups, cfg.layer_norm_epsilon)
    return qmm(gated.astype(w["w_out"].dtype), w["w_out"][mi])


def _put_row_state(state, mi, rows, new):
    return tuple(a.at[mi, rows].set(n.astype(a.dtype), mode="drop")
                 for a, n in zip(state, new))


def _forward_hidden(params, cfg, tokens, positions, kv_k, kv_v, page_tables,
                    ctx_lens, page_size, block_pages, state, ssm_mixer,
                    attn_mixer=None, attn_impl="xla"):
    """The stack over one paged chunk ``[B, T]``, without the head: (hidden
    [B, T, D], kv_k', kv_v', expert counts, state'). ``ssm_mixer(x, live,
    mi, state) -> (out, state')`` runs a Mamba layer over the normed hidden:
    the two forwards lay the same tokens out as runs differently.
    ``attn_mixer(x, ai, kv_k, kv_v) -> (out, kv_k', kv_v')`` likewise for an
    attention layer (None: :func:`attention` over the chunk as it is laid
    out)."""
    if "lora" in params:
        raise ValueError("the nemotron-h forward has no LoRA rows")
    if isinstance(kv_k, tuple):
        raise ValueError("the nemotron-h forward has no int8 (scaled) KV pool")
    b, t = tokens.shape
    d, eps = cfg.hidden_size, cfg.layer_norm_epsilon
    w = params["layers"]
    live = positions < ctx_lens[:, None]
    live_flat = live.reshape(b * t)

    def m_layer(carry, mi):
        hidden, kv_k, kv_v, state, counts = carry
        o, state = ssm_mixer(rms_norm(hidden, w["m_norm"][mi], eps), live, mi, state)
        return hidden + o.astype(hidden.dtype), kv_k, kv_v, state, counts

    def e_layer(carry, ei):
        hidden, kv_k, kv_v, state, counts = carry
        u = rms_norm(hidden, w["e_norm"][ei], eps)
        m, c = moe_block(u.reshape(b * t, d), live_flat, w, ei, cfg)
        return hidden + m.reshape(b, t, d), kv_k, kv_v, state, counts + c

    def a_layer(carry, ai):
        hidden, kv_k, kv_v, state, counts = carry
        x = rms_norm(hidden, w["a_norm"][ai], eps)
        if attn_mixer is not None:
            o, kv_k, kv_v = attn_mixer(x, ai, kv_k, kv_v)
        else:
            o, kv_k, kv_v = attention(x, w, ai, cfg, positions, kv_k, kv_v,
                                      page_tables, ctx_lens, page_size, block_pages,
                                      attn_impl)
        return hidden + o.astype(hidden.dtype), kv_k, kv_v, state, counts

    h, kv_k, kv_v, state, counts = run_plan(
        layer_plan(cfg.hybrid_override_pattern),
        (params["embed"][tokens], kv_k, kv_v, state,
         jnp.zeros((len(EXPERT_COUNTS),), jnp.int32)),
        {"M": m_layer, "E": e_layer, "*": a_layer})
    return h, kv_k, kv_v, counts, state


def hidden_chunk(params, cfg, tokens, positions, kv_k, kv_v, page_tables,
                 ctx_lens, page_size, block_pages, attn_impl="xla", *, state,
                 state_rows=None):
    """The stack over one chunk ``[B, T]`` (decode: T = 1; a prefill chunk
    a row): (hidden [B, T, D], kv_k', kv_v', expert counts, state'). Row
    ``i`` runs from and writes back slot ``state_rows[i]`` of the state pool
    (None: slot ``i``, the decode programs; a slot out of range is a pad
    row's and is dropped)."""
    w = params["layers"]
    rows = (jnp.arange(tokens.shape[0], dtype=jnp.int32) if state_rows is None
            else state_rows)

    def ssm_mixer(x, live, mi, state):
        z, xbc, dt = ssm_project(x, w, mi)
        if state_rows is None and x.shape[1] == 1:  # every slot, in place
            y, state = ssm_decode(xbc, dt, live, w, mi, cfg, state)
        else:
            y, *new = ssm_recur(xbc, dt, live, w, mi, cfg,
                                *_row_state(state, mi, rows))
            state = _put_row_state(state, mi, rows, new)
        return ssm_output(y, z, w, mi, cfg), state

    return _forward_hidden(
        params, cfg, tokens, positions, kv_k, kv_v, page_tables, ctx_lens,
        page_size, block_pages, state, ssm_mixer, attn_impl=attn_impl)


def hidden_ragged(params, cfg, tokens, positions, row_ids, kv_k, kv_v,
                  page_tables, ctx_lens, page_size, block_pages, ragged_block,
                  attn_impl="xla", *, state, state_rows):
    """The stack over the mixed step's flat ragged batch, llama.py's layout:
    (hidden [N / ragged_block, ragged_block, D], kv_k', kv_v', expert counts,
    state'). As ``qwen3_next.hidden_ragged``: the projections, the expert
    layers and the page writes run over the flat buffer; both mixers run it
    by SEGMENT — the decode tokens as one-token rows of every slot (the
    Mamba rule over the live ones, in place), and each FILLED prefill row's
    chunk gathered into a run of its own, a Mamba layer from the state of
    its slot (``state_rows[row]``) and written back to it, an attention
    layer over its own page table."""
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

    def dec(a):  # the decode section as one-token rows of every slot
        return a[:n_dec].reshape(slots, rq, *a.shape[1:])[:, :1]

    def ssm_mixer(x, live, mi, state):
        x, live = x.reshape(n, -1), live.reshape(n)
        z, xbc, dt = ssm_project(x, w, mi)
        y_dec, state = ssm_decode(dec(xbc), dec(dt), dec(live), w, mi, cfg, state)
        in_row = filled & live[n_dec:][idx_c]
        y = jnp.zeros((n + 1, *y_dec.shape[2:]), jnp.float32)
        y = y.at[jnp.arange(slots) * rq].set(y_dec[:, 0])

        def prefill_row(j, carry):
            # One prefill row's chunk as a run of its own; the loop runs the
            # rows that HAVE a chunk and no more.
            state, y = carry
            at, slot = idx_c[j], pf_slots[j][None]
            y_j, *new = ssm_recur(xbc[n_dec:][at][None], dt[n_dec:][at][None],
                                  in_row[j][None], w, mi, cfg,
                                  *_row_state(state, mi, slot))
            state = _put_row_state(state, mi, slot, new)
            return state, y.at[jnp.where(in_row[j], n_dec + idx[j], n)].set(y_j[0])

        state, y = jax.lax.fori_loop(0, rows_filled, prefill_row, (state, y))
        return ssm_output(y[:n], z, w, mi, cfg).reshape(nb, rq, -1), state

    block_rows = row_ids.reshape(nb, rq)[:, 0]
    block_tables, block_pos = page_tables[block_rows], positions.reshape(nb, rq)
    trash = (page_tables.shape[1] - 1) * page_size  # a pad's position

    def attn_mixer(x, ai, kv_k, kv_v):
        # Keys and values are written block by block; the queries attend by
        # SEGMENT (qwen3_next.py says what blocks of queries cost).
        q, k, v = attention_inputs(x, w, ai, cfg)
        kv_k = write_kv_pages_batch(kv_k, k, block_pos, block_tables, page_size, layer=ai)
        kv_v = write_kv_pages_batch(kv_v, v, block_pos, block_tables, page_size, layer=ai)
        q = q.reshape(n, *q.shape[2:])
        dec_pos = positions[:n_dec].reshape(slots, rq)[:, :1]
        out = attend_live(dec(q), ai, kv_k, kv_v, page_tables[:slots], ctx_lens[:slots],
                          dec_pos, dec_pos[:, 0] < ctx_lens[:slots], page_size, block_pages,
                          attn_impl)
        attn = jnp.zeros((n + 1, *q.shape[1:]), q.dtype)
        attn = attn.at[jnp.arange(slots) * rq].set(out[:, 0])

        def prefill_row(j, attn):
            at, row = idx_c[j], slots + j
            pos = jnp.where(filled[j], positions[n_dec:][at], trash)
            out = attend(q[n_dec:][at][None], ai, kv_k, kv_v, page_tables[row][None],
                         ctx_lens[row][None], pos[None], page_size, block_pages)
            return attn.at[jnp.where(filled[j], n_dec + idx[j], n)].set(out[0])

        attn = jax.lax.fori_loop(0, rows_filled, prefill_row, attn)
        return attention_output(attn[:n], w, ai).reshape(nb, rq, -1), kv_k, kv_v

    return _forward_hidden(
        params, cfg, tokens.reshape(nb, rq), block_pos, kv_k, kv_v, block_tables,
        ctx_lens[block_rows], page_size, block_pages, state, ssm_mixer, attn_mixer)


# The step programs' pair (``NemotronHConfig.forwards``): the family's own
# ragged body.
forward_counted, forward_ragged_counted = serving_forwards(hidden_chunk, hidden_ragged)
# One forward chunk, the serving signature and result: (logits, kv_k', kv_v',
# expert counts, state', None).
forward_impl = forward_counted
