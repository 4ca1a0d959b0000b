"""The seeded weights of the nemotron_h block, made again by the reference's
own copy of the recipe the served path runs (``models/hf_loader.py``
``load_or_init`` without a checkpoint: ``models/nemotron_h.py``
``init_params``, then ``quiet_control_tokens``).

``jax.random.PRNGKey(seed)`` split five ways (embedding, layers, head,
router, Mamba mixer); the layer key split once per stacked matrix in the
order of ``layer_shapes``, each matrix stacked over the layers OF ITS KIND
(the letters of ``hybrid_override_pattern``); a stacked matrix's key split
once per ``[in, out]`` slice, each slice normal / sqrt(fan_in) rounded to
the weights' dtype; the router key split in two: the router float32, normal
/ sqrt(D), and its bias on the choice normal x ``router_bias_scale``. The
Mamba key split four ways, as the published initialisation draws them: ``A ~
U(1, 16)`` and ``A_log`` its log; ``dt`` log-uniform over (0.001, 0.1) and
``dt_bias`` its inverse softplus; the depthwise convolution and its bias
``U(-1/2, 1/2)`` (fan-in 4); ``D`` ones; every norm's weight ones. The
head's columns of the byte tokenizer's six control ids (256-261) are zero, so
that seeded weights never end an answer. The same calls of ``jax.random``
give the same bits, so nothing is handed over.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sizes(cfg: dict) -> dict:
    """The derived sizes the files of the block use."""
    pattern = cfg["hybrid_override_pattern"]
    d_inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return {"M": pattern.count("M"), "E": pattern.count("E"), "*": pattern.count("*"),
            "d_inner": d_inner,
            "conv": d_inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]}


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    z, d = sizes(cfg), cfg["hidden_size"]
    m, e, a = z["M"], z["E"], z["*"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    fe, fs = cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    held = cfg["n_experts_held"]
    return {"w_in": ((m, d, z["d_inner"] + z["conv"]), d),  # W_in's columns [z | xBC]
            "w_dt": ((m, d, cfg["mamba_num_heads"]), d),    # and [dt], a leaf of their own
            "w_out": ((m, z["d_inner"], d), z["d_inner"]),
            "wq": ((a, d, hq), d), "wk": ((a, d, hkv), d), "wv": ((a, d, hkv), d),
            "wo": ((a, hq, d), hq),
            "e_up": ((e, held, d, fe), d), "e_down": ((e, held, fe, d), fe),
            "s_up": ((e, d, fs), d), "s_down": ((e, fs, d), fs)}


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
        raise ValueError("the nemotron_h block states bf16 weights; it has no int8 leaves")
    k_embed, k_layers, k_head, k_router, k_ssm = jax.random.split(jax.random.PRNGKey(seed), 5)
    z, d, h = sizes(cfg), cfg["hidden_size"], cfg["mamba_num_heads"]
    m, e, a = z["M"], z["E"], z["*"]
    shapes = layer_shapes(cfg)
    stacked = jax.jit(_stacked, static_argnums=(1, 2, 3))
    layers = {name: stacked(k, shape, fan_in, jnp.dtype(dtype))
              for k, (name, (shape, fan_in)) in zip(jax.random.split(k_layers, len(shapes)),
                                                   shapes.items())}
    k_r, k_b = jax.random.split(k_router)
    layers["router"] = (jax.random.normal(k_r, (e, d, cfg["n_routed_experts"]), jnp.float32)
                        / jnp.sqrt(jnp.float32(d)))
    layers["router_bias"] = cfg["router_bias_scale"] * jax.random.normal(
        k_b, (e, cfg["n_routed_experts"]), jnp.float32)
    k_a, k_dt, k_conv, k_cb = jax.random.split(k_ssm, 4)
    layers["a_log"] = jnp.log(jax.random.uniform(k_a, (m, h), jnp.float32, minval=1.0, maxval=16.0))
    dt = jnp.exp(jax.random.uniform(k_dt, (m, h), jnp.float32,
                                    minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    layers["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    layers["d_skip"] = jnp.ones((m, h), jnp.float32)
    layers["conv"] = jax.random.uniform(k_conv, (m, cfg["conv_kernel"], z["conv"]), jnp.float32,
                                        minval=-0.5, maxval=0.5).astype(dtype)
    layers["conv_bias"] = jax.random.uniform(k_cb, (m, z["conv"]), jnp.float32,
                                             minval=-0.5, maxval=0.5).astype(dtype)
    layers["g_norm"] = jnp.ones((m, z["d_inner"]), jnp.float32)
    layers["m_norm"] = jnp.ones((m, d), jnp.float32)
    layers["e_norm"] = jnp.ones((e, d), jnp.float32)
    layers["a_norm"] = jnp.ones((a, d), jnp.float32)
    head = _dense(k_head, (d, cfg["vocab_size"]), d, dtype)
    quiet = jnp.asarray([t for t in range(256, 262) if t < cfg["vocab_size"]], jnp.int32)
    return {"embed": _dense(k_embed, (cfg["vocab_size"], d), d, dtype), "layers": layers,
            "final_norm": jnp.ones((d,), jnp.float32), "lm_head": head.at[:, quiet].set(0)}
