"""The joyai block's bytes: what one chip's share of JoyAI-LLM-Flash keeps
on the device, and the least one speculative ROUND over it must read from
HBM.

Resident are every matrix held here — an MLA block a layer and one for the
prediction module, the leading dense FFN, a float32 router and a shared
expert an expert layer (the module's included), every HELD expert, the
module's projection of ``[embedding ; hidden]`` — the embedding, the head,
and the WHOLE latent pool, the module's cache layer included: ``kv_lora_rank
+ qk_rope_head_dim`` values a token and attention block. Each at the bytes
per value the configuration file states under ``precision`` (the router at
float32's four); norms and the router's bias are left out: the lower bound
``correct`` holds the live device arrays to.

The pure decode program of this block is ``jit__decode_spec``, which runs
``decode_steps`` rounds a call (``engine/engine.py``). A round is one pass
of the trunk over two positions a row, one pass of the module over the
same two, and the head twice (the trunk's logits; then, after the argmax
that decides what the module is fed, the draft's): ``step_bytes`` counts
every matrix outside the routed experts once, the head twice, and the
latent cache of the live tokens once an attention block. The held experts'
matrices are left OUT of this lower bound: which of them a round touches
is the router's to say (the step record's ``experts.touched`` counts them).
The embedding is a gather of a few rows and is left out.

No ``attention_bytes_per_call``: that is the dense block's Pallas kernel's;
this block's attention is counted by ``kernels/mla_spec.py``.
"""

from __future__ import annotations

# Whole programs on the "XLA Modules" line that are pure decode, with the
# rounds one run of each makes (None: ``decode_steps``).
PROGRAMS = {"jit__decode_spec": None}
ROUTER_BYTES = 4  # float32, whatever the matrices are


def stacks(model: dict) -> tuple[int, int, int, int]:
    """(attention blocks, expert layers, dense FFNs, modules)."""
    L, k = model["num_hidden_layers"], model["first_k_dense_replace"]
    m = model["num_nextn_predict_layers"]
    return L + m, L - k + m, k, m


def attention_params(model: dict) -> int:
    d, h = model["hidden_size"], model["num_attention_heads"]
    nope, rope, v = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    qr, kr = model["q_lora_rank"], model["kv_lora_rank"]
    return d * qr + qr * h * (nope + rope) + d * (kr + rope) + kr * h * (nope + v) + h * v * d


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def router_params(model: dict) -> int:
    return model["hidden_size"] * model["n_routed_experts"]


def matrix_params_outside_experts(model: dict) -> int:
    """Every bf16 matrix but the routed experts, the embedding and the head."""
    a, e, k, m = stacks(model)
    d = model["hidden_size"]
    return (a * attention_params(model) + k * 3 * d * model["intermediate_size"]
            + e * model["n_shared_experts"] * expert_params(model) + m * 2 * d * d)


def latent_token_bytes(model: dict, kv_bytes_per_value: int = 2) -> int:
    """One token's rows of the pool: a latent an attention block, and the
    rotated keys two blocks a row (an odd count leaves half a row unused)."""
    a = stacks(model)[0]
    return ((a * model["kv_lora_rank"] + (a + 1) // 2 * 2 * model["qk_rope_head_dim"])
            * kv_bytes_per_value)


def step_bytes(model: dict, live_tokens: float) -> float:
    e = stacks(model)[1]
    head = model["hidden_size"] * model["vocab_size"] * 2
    return (matrix_params_outside_experts(model) * 2 + e * router_params(model) * ROUTER_BYTES
            + 2 * head + live_tokens * latent_token_bytes(model))


def resident_bytes(model: dict, llm: dict, precision: dict) -> int:
    wide, mat = precision["embedding_and_head_bytes"], precision["layer_matrix_bytes"]
    e = stacks(model)[1]
    held = e * model["n_experts_held"] * expert_params(model)
    weights = ((matrix_params_outside_experts(model) + held) * mat
               + e * router_params(model) * ROUTER_BYTES
               + 2 * model["vocab_size"] * model["hidden_size"] * wide)
    pool_tokens = llm["num_pages"] * llm["page_size"]
    return int(weights + pool_tokens * latent_token_bytes(model, precision["kv_bytes"]))
