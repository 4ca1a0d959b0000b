"""The seeded weights of the afmoe block, made again by the reference's own
copy of the recipe the served path runs (``models/hf_loader.py``
``load_or_init`` without a checkpoint: ``models/afmoe.py`` ``init_params``,
then ``quiet_control_tokens``).

``jax.random.PRNGKey(seed)`` split four ways (embedding, layers, head,
router); the layer key split once per stacked matrix in the order of
``layer_shapes``; a stacked matrix's key split once per ``[in, out]`` slice,
each slice normal / sqrt(fan_in) rounded to the weights' dtype; the router
float32, normal / sqrt(D); the balance bias float32, normal times
``router_bias_scale``; every norm ones (the four of a layer, the two over a
head's values, the final one); the head's columns of the byte tokenizer's
six control ids (256-261) zero, so that seeded weights never end an answer.
The same calls of ``jax.random`` give the same bits, so nothing is handed
over.

Stacks: attention ``0 .. L - 1``, dense FFNs ``0 .. K - 1`` (the leading
layers), expert layers ``0 .. L - K - 1``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORMS = ("norm1", "norm2", "norm3", "norm4")


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    L, k, d = cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["hidden_size"]
    e = L - k
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    f, fe, held = cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["n_experts_held"]
    fs = cfg["num_shared_experts"] * fe
    return {"wq": ((L, d, hq), d), "wk": ((L, d, hkv), d), "wv": ((L, d, hkv), d),
            "wg": ((L, d, hq), d), "wo": ((L, hq, d), hq),
            "d_gate": ((k, d, f), d), "d_up": ((k, d, f), d), "d_down": ((k, f, d), f),
            "s_gate": ((e, d, fs), d), "s_up": ((e, d, fs), d), "s_down": ((e, fs, d), fs),
            "e_gate": ((e, held, d, fe), d), "e_up": ((e, held, d, fe), d),
            "e_down": ((e, held, fe, d), fe)}


def _stacked(key, shape, fan_in, dtype):
    lead, mat = shape[:-2], shape[-2:]

    def one(k):
        return (jax.random.normal(k, mat, jnp.float32) / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    return jax.lax.map(one, jax.random.split(key, math.prod(lead))).reshape(shape)


def _dense(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)


def make_params(cfg: dict, seed: int, quantized: bool, dtype=jnp.bfloat16) -> dict:
    """The parameter tree of ``cfg`` from ``seed``."""
    if quantized:
        raise ValueError("the afmoe block states bf16 weights; it has no int8 leaves")
    k_embed, k_layers, k_head, k_router = jax.random.split(jax.random.PRNGKey(seed), 4)
    L, d, outputs = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["num_experts"]
    e = L - cfg["num_dense_layers"]
    shapes = layer_shapes(cfg)
    stacked = jax.jit(_stacked, static_argnums=(1, 2, 3))
    layers = {name: stacked(k, shape, fan_in, jnp.dtype(dtype))
              for k, (name, (shape, fan_in)) in zip(jax.random.split(k_layers, len(shapes)),
                                                   shapes.items())}
    k_w, k_b = jax.random.split(k_router)
    layers["router"] = jax.random.normal(k_w, (e, d, outputs), jnp.float32) / jnp.sqrt(jnp.float32(d))
    layers["router_bias"] = cfg["router_bias_scale"] * jax.random.normal(k_b, (e, outputs), jnp.float32)
    for name in NORMS:
        layers[name] = jnp.ones((L, d), jnp.float32)
    layers["q_norm"] = jnp.ones((L, cfg["head_dim"]), jnp.float32)
    layers["k_norm"] = jnp.ones((L, cfg["head_dim"]), jnp.float32)
    head = _dense(k_head, (d, cfg["vocab_size"]), d, dtype)
    quiet = jnp.asarray([t for t in range(256, 262) if t < cfg["vocab_size"]], jnp.int32)
    return {"embed": _dense(k_embed, (cfg["vocab_size"], d), d, dtype), "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32), "lm_head": head.at[:, quiet].set(0)}
