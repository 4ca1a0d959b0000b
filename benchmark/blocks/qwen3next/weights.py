"""The seeded weights of the qwen3next block, made again by the reference's
own copy of the recipe the served path runs (``models/hf_loader.py``
``load_or_init`` without a checkpoint: ``models/qwen3_next.py``
``init_params``, then ``quiet_control_tokens``).

``jax.random.PRNGKey(seed)`` split five ways (embedding, layers, head,
router, linear mixer); the layer key split once per stacked matrix in the
order of ``layer_shapes``; a stacked matrix's key split once per ``[in,
out]`` slice, each slice normal / sqrt(fan_in) rounded to the weights'
dtype; the router float32, normal / sqrt(D). As the published
initialisation draws them: ``A ~ U(0, 16)`` (from 0.001, so that its log is
finite) and ``A_log`` its log, ``dt_bias`` ones, the depthwise convolution
``U(-1/2, 1/2)`` (fan-in 4), the zero-centred norms' weights zero, the gated
norm's ones. The head's columns of the byte tokenizer's six control ids
(256-261) are zero, so that seeded weights never end an answer. The same
calls of ``jax.random`` give the same bits, so nothing is handed over.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sizes(cfg: dict) -> dict:
    """The derived sizes both files of the block use."""
    L, interval = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return {"periods": L // interval, "linear": L - L // interval,
            "kd": kd, "vd": vd, "conv": 2 * kd + vd}


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    z = sizes(cfg)
    L, P, Ll, d = cfg["num_hidden_layers"], z["periods"], z["linear"], cfg["hidden_size"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    fe, fs, e = (cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"],
                 cfg["n_experts_held"])
    return {"wq": ((P, d, h * 2 * hd), d), "wk": ((P, d, kv * hd), d),
            "wv": ((P, d, kv * hd), d), "wo": ((P, h * hd, d), h * hd),
            "w_qkvz": ((Ll, d, z["conv"] + z["vd"]), d),
            "w_ba": ((Ll, d, 2 * cfg["linear_num_value_heads"]), d),
            "w_out": ((Ll, z["vd"], d), z["vd"]),
            "e_gate": ((L, e, d, fe), d), "e_up": ((L, e, d, fe), d),
            "e_down": ((L, e, fe, d), fe),
            "s_gate": ((L, d, fs), d), "s_up": ((L, d, fs), d),
            "s_down": ((L, fs, d), fs), "s_sig": ((L, d, 1), d)}


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
        raise ValueError("the qwen3next block states bf16 weights; it has no int8 leaves")
    k_embed, k_layers, k_head, k_router, k_gdn = jax.random.split(jax.random.PRNGKey(seed), 5)
    z = sizes(cfg)
    L, P, Ll, d = cfg["num_hidden_layers"], z["periods"], z["linear"], cfg["hidden_size"]
    hv = cfg["linear_num_value_heads"]
    shapes = layer_shapes(cfg)
    stacked = jax.jit(_stacked, static_argnums=(1, 2, 3))
    layers = {name: stacked(k, shape, fan_in, jnp.dtype(dtype))
              for k, (name, (shape, fan_in)) in zip(jax.random.split(k_layers, len(shapes)),
                                                   shapes.items())}
    layers["router"] = (jax.random.normal(k_router, (L, d, cfg["num_experts"]), jnp.float32)
                        / jnp.sqrt(jnp.float32(d)))
    k_a, k_conv = jax.random.split(k_gdn)
    layers["a_log"] = jnp.log(jax.random.uniform(k_a, (Ll, hv), jnp.float32,
                                                 minval=1e-3, maxval=16.0))
    layers["dt_bias"] = jnp.ones((Ll, hv), jnp.float32)
    layers["conv"] = jax.random.uniform(
        k_conv, (Ll, cfg["linear_conv_kernel_dim"], z["conv"]), jnp.float32,
        minval=-0.5, maxval=0.5).astype(dtype)
    layers["g_norm"] = jnp.ones((Ll, cfg["linear_value_head_dim"]), jnp.float32)
    layers["in_norm"] = jnp.zeros((L, d), jnp.float32)
    layers["post_norm"] = jnp.zeros((L, d), jnp.float32)
    layers["q_norm"] = jnp.zeros((P, cfg["head_dim"]), jnp.float32)
    layers["k_norm"] = jnp.zeros((P, cfg["head_dim"]), jnp.float32)
    head = _dense(k_head, (d, cfg["vocab_size"]), d, dtype)
    quiet = jnp.asarray([t for t in range(256, 262) if t < cfg["vocab_size"]], jnp.int32)
    return {"embed": _dense(k_embed, (cfg["vocab_size"], d), d, dtype), "layers": layers,
            "final_norm": jnp.zeros((d,), jnp.float32), "lm_head": head.at[:, quiet].set(0)}
