"""The qwen3next block's bytes: what one chip's share of Qwen3-Next keeps on
the device, and the least one decode pass over it must read from HBM.

Resident are every matrix held here (mixers, routers, shared experts, every
HELD expert), the embedding and the head, the WHOLE paged pool of keys and
values (the full-attention layers only: one pool layer a period), and the
two pools of recurrent state — the per-slot state pool and the snapshot
pool behind prefix hits, each a float32 ``[d_k, d_v]`` matrix a value head
and linear layer plus the convolution's last inputs — each at the bytes per
value the configuration file states under ``precision`` (the router, the
norms, ``A_log`` and ``dt_bias`` at float32's four): the lower bound
``correct`` holds the live device arrays to.

A pass reads every matrix outside the routed experts once, the head, and
the keys and values of the live tokens of its rows. Left OUT of this lower
bound: the held experts' matrices (which of them a pass touches is the
router's to say; the step record's ``experts.touched`` counts them) and the
rows' recurrent state (``kernels/gdn_step.py`` counts it, for its own
share). The embedding is a gather of ``rows`` rows and is left out.

No ``attention_bytes_per_call``: that is the dense block's Pallas kernel's;
this block's attention is XLA's page walk.
"""

from __future__ import annotations

# Whole programs on the "XLA Modules" line that are pure decode, with the
# passes over the weights one run of each makes (None: ``decode_steps``).
PROGRAMS = {"jit__decode_multi": None, "jit__decode_step": 1}
F32 = 4  # router, norms, A_log, dt_bias: float32 whatever the matrices are


def counts(model: dict) -> dict:
    """Layers by kind and the mixer's derived widths."""
    L = model["num_hidden_layers"]
    periods = L // model["full_attention_interval"]
    kd = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    vd = model["linear_num_value_heads"] * model["linear_value_head_dim"]
    return {"layers": L, "full": periods, "linear": L - periods, "conv": 2 * kd + vd, "vd": vd}


def attention_matrix_params(model: dict) -> int:
    d, hd = model["hidden_size"], model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return d * h * 2 * hd + 2 * d * kv * hd + h * hd * d


def linear_matrix_params(model: dict) -> int:
    """``Wqkvz``, ``Wba``, ``Wout`` and the depthwise convolution."""
    c, d = counts(model), model["hidden_size"]
    return (d * (c["conv"] + c["vd"]) + d * 2 * model["linear_num_value_heads"]
            + c["vd"] * d + model["linear_conv_kernel_dim"] * c["conv"])


def shared_expert_params(model: dict) -> int:
    d = model["hidden_size"]
    return 3 * d * model["shared_expert_intermediate_size"] + d


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def f32_params(model: dict) -> int:
    """Router, two norms a layer, q/k norms a full layer, ``A_log``,
    ``dt_bias`` and the gated norm a linear layer, the final norm."""
    c, d = counts(model), model["hidden_size"]
    return (c["layers"] * (d * model["num_experts"] + 2 * d)
            + c["full"] * 2 * model["head_dim"]
            + c["linear"] * (2 * model["linear_num_value_heads"]
                             + model["linear_value_head_dim"]) + d)


def layer_params(model: dict, full: bool) -> tuple[int, int]:
    """(outside the routed experts' mixer, the rest outside them): the two
    terms a layer's count is written as (PERF.md section 4)."""
    d = model["hidden_size"]
    mixer = (attention_matrix_params(model) + 2 * model["head_dim"] if full
             else linear_matrix_params(model) + 2 * model["linear_num_value_heads"]
             + model["linear_value_head_dim"])
    return mixer, d * model["num_experts"] + shared_expert_params(model) + 2 * d


def state_slot_bytes(model: dict, precision: dict) -> int:
    """One slot of the state pool (or one snapshot): every linear layer's
    matrices and convolution tail."""
    c = counts(model)
    matrices = (model["linear_num_value_heads"] * model["linear_key_head_dim"]
                * model["linear_value_head_dim"] * precision["state_bytes"])
    tail = (model["linear_conv_kernel_dim"] - 1) * c["conv"] * precision["conv_state_bytes"]
    return c["linear"] * (matrices + tail)


def kv_token_bytes(model: dict, kv_bytes_per_value: int = 2) -> int:
    return (counts(model)["full"] * 2 * model["num_key_value_heads"] * model["head_dim"]
            * kv_bytes_per_value)


def matrix_params_outside_experts(model: dict) -> int:
    c = counts(model)
    return (c["full"] * attention_matrix_params(model)
            + c["linear"] * linear_matrix_params(model)
            + c["layers"] * shared_expert_params(model))


def step_bytes(model: dict, live_tokens: float) -> float:
    head = model["hidden_size"] * model["vocab_size"] * 2
    return (matrix_params_outside_experts(model) * 2 + f32_params(model) * F32 + head
            + live_tokens * kv_token_bytes(model))


def resident_bytes(model: dict, llm: dict, precision: dict) -> int:
    wide, mat = precision["embedding_and_head_bytes"], precision["layer_matrix_bytes"]
    held = counts(model)["layers"] * model["n_experts_held"] * expert_params(model)
    weights = ((matrix_params_outside_experts(model) + held) * mat + f32_params(model) * F32
               + 2 * model["vocab_size"] * model["hidden_size"] * wide)
    states = ((llm["max_batch_slots"] + model["state_snapshots"])
              * state_slot_bytes(model, precision))
    pool = llm["num_pages"] * llm["page_size"] * kv_token_bytes(model, precision["kv_bytes"])
    return int(weights + states + pool)
