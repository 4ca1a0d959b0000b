"""Pipeline parallelism — GPipe-style microbatch schedule over a ``pipe`` axis.

The scan-stacked layer dimension is the natural stage boundary: each device
on the ``pipe`` mesh axis holds ``n_layers / n_stages`` contiguous layers
(the leading layer axis is simply sharded over ``pipe``), and activations
flow stage→stage with ``jax.lax.ppermute`` — a nearest-neighbor ICI hop, the
same primitive ring attention uses on ``seq``.

Schedule: plain GPipe fill-drain over ``M`` microbatches. The whole pipeline
runs as ONE compiled SPMD program of ``M + S - 1`` ticks (a ``lax.scan``):
at tick ``t`` stage ``s`` processes microbatch ``t - s`` (predicated with
``where`` — XLA-friendly static control flow, no per-stage programs to
launch). Bubble fraction is the usual ``(S-1)/(M+S-1)``; raise ``M`` to
amortize.

Embedding runs on stage 0, the LM head on the last stage; intermediate
logits never materialize anywhere else (the head matmul is applied once to
the collected hidden buffer, then masked + psum'd so every device returns
the same logits — convenient for loss computation under DP on top).

SURVEY.md §2.10 lists PP as the optional extension beyond the north-star TP
configs; it exists so depth-dominated models (Llama-3-70B's 80 layers) can
trade TP collective volume for pipeline bubbles on narrow meshes. No
reference counterpart (the reference executes no models).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from runbookai_tpu.models.llama import (
    LlamaConfig,
    dense_causal_attention,
    lm_head_logits,
    transformer_layer,
)
from runbookai_tpu.parallel.mesh import PIPE_AXIS
from runbookai_tpu.parallel.ring_attention import _mark_varying


def _pipeline_local(params, tokens_mb, cfg: LlamaConfig, axis_name: str):
    """Run the GPipe schedule on this stage's layer slice (inside shard_map).

    params["layers"] leaves arrive sharded to [L/S, ...]; tokens_mb is the
    replicated [M, mb, T] microbatched token array.
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    m_total, mb, t = tokens_mb.shape
    positions = jnp.arange(t, dtype=jnp.int32)[None, :]
    attn_fn = dense_causal_attention(cfg, mb, t)
    is_first = stage == 0
    is_last = stage == n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]  # no wraparound

    def stage_apply(act):
        def step(h, lp):
            return transformer_layer(h, lp, cfg, positions, attn_fn), None

        h, _ = jax.lax.scan(step, act, params["layers"])
        return h

    def tick(carry, tk):
        act_in, out_buf = carry
        m_idx = tk - stage  # which microbatch this stage handles at this tick
        valid = (m_idx >= 0) & (m_idx < m_total)
        m_clip = jnp.clip(m_idx, 0, m_total - 1)
        tok = jax.lax.dynamic_index_in_dim(tokens_mb, m_clip, 0, keepdims=False)
        emb = params["embed"][tok]
        h_out = stage_apply(jnp.where(is_first, emb, act_in))
        stored = jax.lax.dynamic_update_index_in_dim(out_buf, h_out, m_clip, 0)
        out_buf = jnp.where(valid & is_last, stored, out_buf)
        act_next = jax.lax.ppermute(h_out, axis_name, perm)
        return (act_next, out_buf), None

    dtype = params["embed"].dtype
    act0 = _mark_varying(jnp.zeros((mb, t, cfg.dim), dtype), axis_name)
    out0 = _mark_varying(jnp.zeros((m_total, mb, t, cfg.dim), dtype), axis_name)
    n_ticks = m_total + n_stages - 1
    (act, out_buf), _ = jax.lax.scan(tick, (act0, out0), jnp.arange(n_ticks))

    logits = lm_head_logits(params, cfg, out_buf.reshape(m_total * mb, t, cfg.dim))
    logits = jnp.where(is_last, logits, 0.0)
    # Only the last stage holds real logits; psum broadcasts them pipe-wide.
    return jax.lax.psum(logits, axis_name)


def forward_train_pp(
    params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B, T]
    mesh: Mesh,
    n_microbatches: int = 4,
    axis_name: str = PIPE_AXIS,
) -> jnp.ndarray:
    """Dense causal forward with layers pipelined over ``mesh[axis_name]``.

    Numerically equivalent to ``models.llama.forward_train``; requires
    ``n_layers % n_stages == 0`` and ``B % n_microbatches == 0``. Returns
    replicated [B, T, vocab] float32 logits.
    """
    n_stages = mesh.shape[axis_name]
    if cfg.n_layers % n_stages:
        raise ValueError(f"{cfg.n_layers} layers not divisible by {n_stages} stages")
    b, t = tokens.shape
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible by {n_microbatches} microbatches")
    tokens_mb = tokens.reshape(n_microbatches, b // n_microbatches, t)

    param_specs = {
        "embed": P(),
        "layers": P(axis_name),  # prefix spec: leading layer axis → stages
        "final_norm": P(),
    }
    if not cfg.tie_embeddings:
        param_specs["lm_head"] = P()

    fn = jax.shard_map(
        partial(_pipeline_local, cfg=cfg, axis_name=axis_name),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        # Manual over this axis only — data/model placements stay
        # automatic so TP-sharded weights compose without gathering.
        axis_names={axis_name},
    )
    logits = fn(params, tokens_mb)
    return logits.reshape(b, t, -1)


def pp_param_shardings(cfg: LlamaConfig, mesh: Mesh,
                       axis_name: str = PIPE_AXIS) -> dict:
    """NamedShardings for pipeline training: every stacked layer leaf's
    leading layer axis shards over ``pipe`` (each stage materializes only
    its own L/S layers — and, with the optimizer state following the same
    placement, only its own Adam moments); embed/head/final-norm replicate."""
    from jax.sharding import NamedSharding

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    layer_keys = ["wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                  "attn_norm", "mlp_norm"]
    if cfg.qkv_bias:
        layer_keys += ["bq", "bk", "bv"]
    if cfg.n_experts:
        layer_keys.append("router")
    shardings = {
        "embed": ns(),
        "layers": {k: ns(axis_name) for k in layer_keys},
        "final_norm": ns(),
    }
    if not cfg.tie_embeddings:
        shardings["lm_head"] = ns()
    return shardings


def loss_fn_pp(params, cfg: LlamaConfig, tokens: jnp.ndarray, pad_id: int,
               mesh: Mesh, n_microbatches: int = 4) -> jnp.ndarray:
    """Mean next-token cross-entropy through the GPipe forward — the
    differentiable training entry (VERDICT r2 next-round #9: the backward
    flows through the whole schedule: scan ticks, ppermute hops
    (transposed to the reverse permutation), stage masks, and the psum'd
    head). Uses the same ``masked_cross_entropy`` as the dense trainer."""
    from runbookai_tpu.train.trainer import masked_cross_entropy

    logits = forward_train_pp(params, cfg, tokens[:, :-1], mesh,
                              n_microbatches=n_microbatches)
    return masked_cross_entropy(logits, tokens[:, 1:], pad_id)
