"""The seeded weights, made again by the reference's own copy of the recipe.

The served path makes its weights with ``load_or_init(..., seed)``:
``jax.random.PRNGKey(seed)`` split three ways (embedding, layers, head),
the layer key split once per stacked matrix in the order wq, wk, wv, wo,
w_gate, w_up, w_down; a quantized matrix is uniform int8 in
[-127, 127] with one scale ``sqrt(3) / (127 sqrt(fan_in))``, a dense one
is normal / sqrt(fan_in) rounded to bfloat16. The same calls of
``jax.random`` give the same bits, so nothing is handed over.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], int]]:
    L, D, KV, F, H = (cfg["n_layers"], cfg["dim"], cfg["n_kv_heads"],
                      cfg["ffn_dim"], cfg["n_heads"])
    hd = D // H
    if cfg.get("n_experts"):
        raise ValueError("the reference has no expert block yet (PERF.md section 7)")
    return {"wq": ((L, D, H * hd), D), "wk": ((L, D, KV * hd), D),
            "wv": ((L, D, KV * hd), D), "wo": ((L, H * hd, D), H * hd),
            "w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
            "w_down": ((L, F, D), F)}


def _dense(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, dtype=jnp.float32)
            / jnp.sqrt(fan_in)).astype(dtype)


def _qdense(key, shape, fan_in):
    q = jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)
    scale = float(3 ** 0.5 / (127.0 * fan_in ** 0.5))
    return {"q": q, "s": jnp.full(shape[:-2] + (1, shape[-1]), scale,
                                  dtype=jnp.float32)}


def make_params(cfg: dict, seed: int, quantized: bool, dtype=jnp.bfloat16) -> dict:
    """The parameter tree of ``cfg`` from ``seed`` (stacked layers)."""
    k_embed, k_layers, k_head = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = layer_shapes(cfg)
    ks = jax.random.split(k_layers, len(shapes))
    layers = {}
    for k, (name, (shape, fan_in)) in zip(ks, shapes.items()):
        layers[name] = (_qdense(k, shape, fan_in) if quantized
                        else _dense(k, shape, fan_in, dtype))
    L, D = cfg["n_layers"], cfg["dim"]
    layers["attn_norm"] = jnp.ones((L, D), jnp.float32)
    layers["mlp_norm"] = jnp.ones((L, D), jnp.float32)
    if cfg.get("qkv_bias"):
        hd = D // cfg["n_heads"]
        layers["bq"] = jnp.zeros((L, cfg["n_heads"] * hd), dtype)
        layers["bk"] = jnp.zeros((L, cfg["n_kv_heads"] * hd), dtype)
        layers["bv"] = jnp.zeros((L, cfg["n_kv_heads"] * hd), dtype)
    params = {"embed": _dense(k_embed, (cfg["vocab_size"], D), D, dtype),
              "layers": layers, "final_norm": jnp.ones((D,), jnp.float32)}
    if not cfg.get("tie_embeddings"):
        params["lm_head"] = _dense(k_head, (D, cfg["vocab_size"]), D, dtype)
    return params
