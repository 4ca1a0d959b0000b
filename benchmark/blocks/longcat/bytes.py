"""The longcat block's bytes: what one chip's share of LongCat-Flash keeps
on the device, and the least one decode pass over it must read from HBM.

A pass reads every matrix outside the routed experts once — two MLA blocks
and two dense SwiGLU FFNs a layer, the float32 router, the head — plus the
latent cache of the live tokens of its rows: ``kv_lora_rank +
qk_rope_head_dim`` values a token and attention sublayer, two sublayers a
layer. The held experts' matrices are left OUT of this lower bound: which
of them a pass touches is the router's to say (the step record's
``experts.touched`` counts them), and no honest pass reads less than the
rest. The embedding is a gather of ``rows`` rows and is left out.

Resident are the same matrices AND every held expert, the embedding, the
head and the WHOLE latent pool, each at the bytes per value the
configuration file states under ``precision`` (the router at float32's
four): the lower bound ``correct`` holds the live device arrays to.

No ``attention_bytes_per_call``: that is the dense block's Pallas kernel's;
this block's decode attention is counted by ``kernels/mla_decode.py``.
"""

from __future__ import annotations

# Whole programs on the "XLA Modules" line that are pure decode, with the
# passes over the weights one run of each makes (None: ``decode_steps``).
PROGRAMS = {"jit__decode_multi": None, "jit__decode_step": 1, "jit__decode_spec": 1}
ROUTER_BYTES = 4  # float32, whatever the matrices are


def sublayer_params(model: dict) -> int:
    """One MLA block and one dense FFN."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope, v = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    qr, kr = model["q_lora_rank"], model["kv_lora_rank"]
    mla = d * qr + qr * h * (nope + rope) + d * (kr + rope) + kr * h * (nope + v) + h * v * d
    return mla + 3 * d * model["ffn_hidden_size"]


def router_params(model: dict) -> int:
    return model["hidden_size"] * (model["n_routed_experts"] + model["zero_expert_num"])


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["expert_ffn_hidden_size"]


def latent_bytes(model: dict, tokens: float, kv_bytes_per_value: int = 2) -> float:
    return (2.0 * model["num_layers"] * tokens
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * kv_bytes_per_value)


def step_bytes(model: dict, live_tokens: float) -> float:
    layers = model["num_layers"]
    weights = layers * (2 * sublayer_params(model) * 2 + router_params(model) * ROUTER_BYTES)
    head = model["hidden_size"] * model["vocab_size"] * 2
    return weights + head + latent_bytes(model, live_tokens)


def resident_bytes(model: dict, llm: dict, precision: dict) -> int:
    wide, mat = precision["embedding_and_head_bytes"], precision["layer_matrix_bytes"]
    per_layer = ((2 * sublayer_params(model) + model["n_experts_held"] * expert_params(model)) * mat
                 + router_params(model) * ROUTER_BYTES)
    embed_and_head = 2 * model["vocab_size"] * model["hidden_size"] * wide
    pool_tokens = llm["num_pages"] * llm["page_size"]
    return int(model["num_layers"] * per_layer + embed_and_head
               + latent_bytes(model, pool_tokens, precision["kv_bytes"]))
