"""The afmoe block's bytes: what one chip's share of Trinity-Mini keeps on
the device, and the least one decode pass over it must read from HBM.

Resident are every matrix held here — attention with its output gate a
layer, the leading dense FFNs, a float32 router and a shared expert an
expert layer, every HELD expert — the embedding, the head, and the paged
keys and values in TWO groups: the full-attention layers' pool, every
position of ``num_pages`` pages, and the sliding layers' pool, which holds
for each of ``max_batch_slots`` sequences the ``sliding_window`` positions
its queries see, the prefill chunk being written and a page's slack at
either end (``window_rows_bound``), never a whole context. Each at the bytes
per value the configuration file states under ``precision`` (router, its
bias and the norms at float32's four): the lower bound ``correct`` holds the
live device arrays to. A program that kept every position of every layer
could not hold these contexts in the stated bytes; a program that kept
fewer window rows than its queries see would be caught by the logits.

A pass reads every matrix outside the routed experts once, the head, and of
the live tokens' keys and values what its queries see: every position in
the full layers, at most ``sliding_window`` in the sliding ones.
``step_bytes`` is given ONE total of live tokens, so it counts the sliding
layers at ``min(live_tokens, sliding_window)`` rows, which no batch can
undercut. Left OUT of this lower bound: the held experts' matrices (which
of them a pass touches is the router's to say; the step record's
``experts.touched`` counts them). The embedding is a gather and is left out.

No ``attention_bytes_per_call``: that is the dense block's; this block's two
walks are counted by ``kernels/swa_decode.py`` and ``kernels/swa_chunk.py``.
"""

from __future__ import annotations

# Whole programs on the "XLA Modules" line that are pure decode, with the
# passes over the weights one run of each makes (None: ``decode_steps``).
PROGRAMS = {"jit__decode_multi": None, "jit__decode_step": 1}
F32 = 4  # router, its bias, the norms: float32 whatever the matrices are
SLIDING, FULL = "sliding_attention", "full_attention"


def layers_of(model: dict, kind: str) -> int:
    return sum(1 for t in model["layer_types"] if t == kind)


def attention_params(model: dict) -> int:
    """q, the output gate, o; k, v."""
    d, hd = model["hidden_size"], model["head_dim"]
    return 3 * d * model["num_attention_heads"] * hd + 2 * d * model["num_key_value_heads"] * hd


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def dense_ffn_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def expert_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["num_dense_layers"]


def f32_params(model: dict) -> int:
    """Four norms and the two head norms a layer; the router and its bias
    an expert layer; the final norm."""
    d = model["hidden_size"]
    return (model["num_hidden_layers"] * (4 * d + 2 * model["head_dim"])
            + expert_layers(model) * (d + 1) * model["num_experts"] + d)


def matrix_params_outside_experts(model: dict) -> int:
    """Every bf16 matrix but the routed experts, the embedding and the head."""
    return (model["num_hidden_layers"] * attention_params(model)
            + model["num_dense_layers"] * dense_ffn_params(model)
            + expert_layers(model) * model["num_shared_experts"] * expert_params(model))


def kv_layer_token_bytes(model: dict, kv_bytes_per_value: int = 2) -> int:
    """One token's keys and values in ONE layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * kv_bytes_per_value


def window_rows_bound(model: dict, llm: dict) -> int:
    """The most token rows a live sequence holds in the sliding layers."""
    return model["sliding_window"] + llm["prefill_chunk"] + 2 * llm["page_size"]


def step_bytes(model: dict, live_tokens: float) -> float:
    head = model["hidden_size"] * model["vocab_size"] * 2
    per_layer = kv_layer_token_bytes(model)
    kv = (layers_of(model, FULL) * live_tokens
          + layers_of(model, SLIDING) * min(live_tokens, model["sliding_window"])) * per_layer
    return matrix_params_outside_experts(model) * 2 + f32_params(model) * F32 + head + kv


def resident_bytes(model: dict, llm: dict, precision: dict) -> int:
    wide, mat = precision["embedding_and_head_bytes"], precision["layer_matrix_bytes"]
    held = expert_layers(model) * model["n_experts_held"] * expert_params(model)
    weights = ((matrix_params_outside_experts(model) + held) * mat + f32_params(model) * F32
               + 2 * model["vocab_size"] * model["hidden_size"] * wide)
    per_layer = kv_layer_token_bytes(model, precision["kv_bytes"])
    full = llm["num_pages"] * llm["page_size"] * layers_of(model, FULL) * per_layer
    window = (llm["max_batch_slots"] * window_rows_bound(model, llm)
              * layers_of(model, SLIDING) * per_layer)
    return int(weights + full + window)
