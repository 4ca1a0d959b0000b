"""The seeded weights of the longcat block, made again by the reference's
own copy of the recipe the served path runs (``models/hf_loader.py``
``load_or_init`` without a checkpoint: ``models/longcat.py`` ``init_params``,
then ``quiet_control_tokens``).

``jax.random.PRNGKey(seed)`` split four ways (embedding, layers, head,
router); the layer key split once per stacked matrix in the order of
``layer_shapes``; a stacked matrix's key split once per ``[in, out]`` slice,
each slice normal / sqrt(fan_in) rounded to the weights' dtype; the router
float32, normal / sqrt(D); the balance bias float32, normal times
``router_bias_scale``; the head's columns of the byte tokenizer's six control
ids (256-261) zero, so that seeded weights never end an answer. The same
calls of ``jax.random`` give the same bits, so nothing is handed over.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    L, d, h = cfg["num_layers"], cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, fe, e = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"], cfg["n_experts_held"]
    return {"wq_a": ((L, 2, d, qr), d), "wq_b": ((L, 2, qr, h * (nope + rope)), qr),
            "wkv_a": ((L, 2, d, kr + rope), d), "wkv_b": ((L, 2, kr, h * (nope + v)), kr),
            "wo": ((L, 2, h * v, d), h * v),
            "w_gate": ((L, 2, d, f), d), "w_up": ((L, 2, d, f), d), "w_down": ((L, 2, f, d), f),
            "e_gate": ((L, e, d, fe), d), "e_up": ((L, e, d, fe), d), "e_down": ((L, e, fe, d), fe)}


def _stacked(key, shape, fan_in, dtype):
    lead, mat = shape[:-2], shape[-2:]

    def one(k):
        return (jax.random.normal(k, mat, jnp.float32) / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)

    return jax.lax.map(one, jax.random.split(key, math.prod(lead))).reshape(shape)


def _dense(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)


def make_params(cfg: dict, seed: int, quantized: bool, dtype=jnp.bfloat16) -> dict:
    """The parameter tree of ``cfg`` from ``seed`` (double layers stacked)."""
    if quantized:
        raise ValueError("the longcat block states bf16 weights; it has no int8 leaves")
    k_embed, k_layers, k_head, k_router = jax.random.split(jax.random.PRNGKey(seed), 4)
    L, d = cfg["num_layers"], cfg["hidden_size"]
    outputs = cfg["n_routed_experts"] + cfg["zero_expert_num"]
    shapes = layer_shapes(cfg)
    stacked = jax.jit(_stacked, static_argnums=(1, 2, 3))
    layers = {name: stacked(k, shape, fan_in, jnp.dtype(dtype))
              for k, (name, (shape, fan_in)) in zip(jax.random.split(k_layers, len(shapes)),
                                                   shapes.items())}
    k_w, k_b = jax.random.split(k_router)
    layers["router"] = jax.random.normal(k_w, (L, d, outputs), jnp.float32) / jnp.sqrt(jnp.float32(d))
    layers["router_bias"] = cfg["router_bias_scale"] * jax.random.normal(k_b, (L, outputs), jnp.float32)
    for name, width in (("in_norm", d), ("post_norm", d), ("q_norm", cfg["q_lora_rank"]),
                        ("kv_norm", cfg["kv_lora_rank"])):
        layers[name] = jnp.ones((L, 2, width), jnp.float32)
    head = _dense(k_head, (d, cfg["vocab_size"]), d, dtype)
    quiet = jnp.asarray([t for t in range(256, 262) if t < cfg["vocab_size"]], jnp.int32)
    return {"embed": _dense(k_embed, (cfg["vocab_size"], d), d, dtype), "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32), "lm_head": head.at[:, quiet].set(0)}
