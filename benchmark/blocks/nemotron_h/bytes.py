"""The nemotron_h block's bytes: what one chip's share of Nemotron-H keeps
on the device, and the least one decode pass over it must read from HBM.

Resident are every matrix held here (Mamba mixers, attention, routers,
shared experts, every HELD expert), the embedding and the head, the WHOLE
paged pool of keys and values (the attention layers only), and the two
pools of recurrent state — the per-slot state pool and the snapshot pool
behind prefix hits, each a float32 ``[head_dim, state size]`` matrix a head
and Mamba layer plus the convolution's last inputs — each at the bytes per
value the configuration file states under ``precision`` (the router and its
bias, the norms, ``A_log``, ``dt_bias`` and ``D`` at float32's four): the
lower bound ``correct`` holds the live device arrays to.

A pass reads every matrix outside the routed experts once, the head, and
the keys and values of the live tokens of its rows. Left OUT of this lower
bound: the held experts' matrices (which of them a pass touches is the
router's to say; the step record's ``experts.touched`` counts them) and the
rows' recurrent state (``kernels/ssm_step.py`` counts it, for its own
share). The embedding is a gather of ``rows`` rows and is left out.

No ``attention_bytes_per_call``: that is the dense block's Pallas kernel's;
this block's attention is XLA's page walk.
"""

from __future__ import annotations

# Whole programs on the "XLA Modules" line that are pure decode, with the
# passes over the weights one run of each makes (None: ``decode_steps``).
PROGRAMS = {"jit__decode_multi": None, "jit__decode_step": 1}
F32 = 4  # router, norms, A_log, dt_bias, D: float32 whatever the matrices are


def counts(model: dict) -> dict:
    """Layers by kind and the Mamba mixer's derived widths."""
    pattern = model["hybrid_override_pattern"]
    d_inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    return {"M": pattern.count("M"), "E": pattern.count("E"), "*": pattern.count("*"),
            "d_inner": d_inner,
            "conv": d_inner + 2 * model["n_groups"] * model["ssm_state_size"]}


def mamba_matrix_params(model: dict) -> int:
    """``W_in``, ``W_out``, the depthwise convolution and its bias."""
    c, d = counts(model), model["hidden_size"]
    return (d * (c["d_inner"] + c["conv"] + model["mamba_num_heads"]) + c["d_inner"] * d
            + (model["conv_kernel"] + 1) * c["conv"])


def attention_matrix_params(model: dict) -> int:
    d, hd = model["hidden_size"], model["head_dim"]
    return 2 * d * model["num_attention_heads"] * hd + 2 * d * model["num_key_value_heads"] * hd


def shared_expert_params(model: dict) -> int:
    return 2 * model["hidden_size"] * model["moe_shared_expert_intermediate_size"]


def expert_params(model: dict) -> int:
    return 2 * model["hidden_size"] * model["moe_intermediate_size"]


def f32_params(model: dict) -> int:
    """A norm a layer; ``A_log``, ``dt_bias``, ``D`` and the gated norm a
    Mamba layer; the router and its bias an expert layer; the final norm."""
    c, d = counts(model), model["hidden_size"]
    return ((c["M"] + c["E"] + c["*"]) * d
            + c["M"] * (3 * model["mamba_num_heads"] + c["d_inner"])
            + c["E"] * (d + 1) * model["n_routed_experts"] + d)


def state_slot_bytes(model: dict, precision: dict) -> int:
    """One slot of the state pool (or one snapshot): every Mamba layer's
    matrices and convolution tail."""
    c = counts(model)
    matrices = (model["mamba_num_heads"] * model["mamba_head_dim"] * model["ssm_state_size"]
                * precision["state_bytes"])
    tail = (model["conv_kernel"] - 1) * c["conv"] * precision["conv_state_bytes"]
    return c["M"] * (matrices + tail)


def kv_token_bytes(model: dict, kv_bytes_per_value: int = 2) -> int:
    return (counts(model)["*"] * 2 * model["num_key_value_heads"] * model["head_dim"]
            * kv_bytes_per_value)


def matrix_params_outside_experts(model: dict) -> int:
    c = counts(model)
    return (c["M"] * mamba_matrix_params(model) + c["*"] * attention_matrix_params(model)
            + c["E"] * shared_expert_params(model))


def step_bytes(model: dict, live_tokens: float) -> float:
    head = model["hidden_size"] * model["vocab_size"] * 2
    return (matrix_params_outside_experts(model) * 2 + f32_params(model) * F32 + head
            + live_tokens * kv_token_bytes(model))


def resident_bytes(model: dict, llm: dict, precision: dict) -> int:
    wide, mat = precision["embedding_and_head_bytes"], precision["layer_matrix_bytes"]
    held = counts(model)["E"] * model["n_experts_held"] * expert_params(model)
    weights = ((matrix_params_outside_experts(model) + held) * mat + f32_params(model) * F32
               + 2 * model["vocab_size"] * model["hidden_size"] * wide)
    states = ((llm["max_batch_slots"] + model["state_snapshots"])
              * state_slot_bytes(model, precision))
    pool = llm["num_pages"] * llm["page_size"] * kv_token_bytes(model, precision["kv_bytes"])
    return int(weights + states + pool)
