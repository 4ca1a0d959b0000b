"""The seeded weights of the joyai block, made again by the reference's own
copy of the recipe the served path runs (``models/hf_loader.py``
``load_or_init`` without a checkpoint: ``models/joyai.py`` ``init_params``,
then ``quiet_control_tokens``).

``jax.random.PRNGKey(seed)`` split five ways (embedding, layers, head,
router, prediction module); the layer key split once per stacked matrix in
the order of ``layer_shapes``; a stacked matrix's key split once per ``[in,
out]`` slice, each slice normal / sqrt(fan_in) rounded to the weights'
dtype; the router float32, normal / sqrt(D); the balance bias float32,
normal times ``router_bias_scale``; the module's projection of ``[embedding
; hidden]`` normal / sqrt(2 D); every norm ones; the head's columns of the
byte tokenizer's six control ids (256-261) zero, so that seeded weights
never end an answer. The same calls of ``jax.random`` give the same bits,
so nothing is handed over.

Stacks: attention blocks ``0 .. L + M - 1`` (the trunk's ``L`` layers, then
the ``M`` prediction modules), expert layers ``0 .. L - K + M - 1`` (``K``
leading dense layers have none), dense FFNs ``0 .. K - 1``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def stacks(cfg: dict) -> tuple[int, int, int, int]:
    """(attention blocks, expert layers, dense FFNs, modules)."""
    L, k, m = cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["num_nextn_predict_layers"]
    return L + m, L - k + m, k, m


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    a, e, k, _ = stacks(cfg)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, fe, held = cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["n_experts_held"]
    fs = cfg["n_shared_experts"] * fe
    return {"wq_a": ((a, d, qr), d), "wq_b": ((a, qr, h * (nope + rope)), qr),
            "wkv_a": ((a, d, kr + rope), d), "wkv_b": ((a, kr, h * (nope + v)), kr),
            "wo": ((a, h * v, d), h * v),
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
    """The parameter tree of ``cfg`` from ``seed``, prediction module included."""
    if quantized:
        raise ValueError("the joyai block states bf16 weights; it has no int8 leaves")
    k_embed, k_layers, k_head, k_router, k_mtp = jax.random.split(jax.random.PRNGKey(seed), 5)
    a, e, _, m = stacks(cfg)
    d, outputs = cfg["hidden_size"], cfg["n_routed_experts"]
    shapes = layer_shapes(cfg)
    stacked = jax.jit(_stacked, static_argnums=(1, 2, 3))
    layers = {name: stacked(k, shape, fan_in, jnp.dtype(dtype))
              for k, (name, (shape, fan_in)) in zip(jax.random.split(k_layers, len(shapes)),
                                                   shapes.items())}
    k_w, k_b = jax.random.split(k_router)
    layers["router"] = jax.random.normal(k_w, (e, d, outputs), jnp.float32) / jnp.sqrt(jnp.float32(d))
    layers["router_bias"] = cfg["router_bias_scale"] * jax.random.normal(k_b, (e, outputs), jnp.float32)
    for name, width in (("in_norm", d), ("post_norm", d), ("q_norm", cfg["q_lora_rank"]),
                        ("kv_norm", cfg["kv_lora_rank"])):
        layers[name] = jnp.ones((a, width), jnp.float32)
    head = _dense(k_head, (d, cfg["vocab_size"]), d, dtype)
    quiet = jnp.asarray([t for t in range(256, 262) if t < cfg["vocab_size"]], jnp.int32)
    ones = jnp.ones((m, d), jnp.float32)
    return {"embed": _dense(k_embed, (cfg["vocab_size"], d), d, dtype), "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32), "lm_head": head.at[:, quiet].set(0),
            "mtp": {"proj": _dense(k_mtp, (m, 2 * d, d), 2 * d, dtype),
                    "e_norm": ones, "h_norm": ones, "final_norm": ones}}
