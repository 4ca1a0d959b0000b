"""Sequence-parallel transformer forward — the long-context execution path.

Shards the *token* axis of one (possibly very long) sequence batch across the
``seq`` mesh axis: every per-token op (embeds, norms, QKV/MLP matmuls, logits)
runs on the local shard untouched, and the only cross-device exchange is the
K/V rotation inside :func:`ring_attention_local`. Context length therefore
scales linearly with the number of chips on the ring — the scale-*out*
answer to the reference's scale-*down* compaction machinery (SURVEY.md §5.7).

Composes with TP on the same mesh: only the ``seq`` axis goes manual in the
shard_map (``axis_names``); ``data``/``model`` stay automatic, so TP-sharded
weights keep their ``parallel/sharding.py`` placements and XLA inserts the
TP collectives as usual.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from runbookai_tpu.models.llama import LlamaConfig, forward_train
from runbookai_tpu.parallel.mesh import SEQ_AXIS
from runbookai_tpu.parallel.ring_attention import ring_attention_local


def _forward_local(params, tokens, cfg: LlamaConfig, axis_name: str):
    """Transformer forward on a [B, T_local] token shard (inside shard_map).

    Reuses the dense ``forward_train`` layer stack verbatim — only positions
    (offset by the shard index) and the attention implementation (ring) differ.
    """
    b, t_loc = tokens.shape
    my_idx = jax.lax.axis_index(axis_name)
    positions = my_idx * t_loc + jnp.arange(t_loc, dtype=jnp.int32)[None, :]
    return forward_train(
        params, cfg, tokens,
        positions=jnp.broadcast_to(positions, (b, t_loc)),
        attn_fn=partial(ring_attention_local, axis_name=axis_name, causal=True),
    )


def forward_train_sp(
    params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [B, T] with T divisible by the seq-axis size
    mesh: Mesh,
    axis_name: str = SEQ_AXIS,
) -> jnp.ndarray:
    """Dense causal forward with the sequence sharded over ``mesh[axis_name]``.

    Numerically equivalent to ``models.llama.forward_train`` (same params,
    same math); returns [B, T, vocab] float32 logits sharded along T.
    """
    tok_spec = P(None, axis_name)
    fn = jax.shard_map(
        partial(_forward_local, cfg=cfg, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(), tok_spec),
        out_specs=P(None, axis_name, None),
        # Manual over this axis only — data/model placements stay
        # automatic so TP-sharded weights compose without gathering.
        axis_names={axis_name},
    )
    return fn(params, tokens)
