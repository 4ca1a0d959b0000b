"""One decode step (one pass over the weights) of the whole model: the
bytes it must read from HBM — and the bytes the configuration keeps on the
device whatever runs.

A step reads every weight it touches once — the int8 layer matrices with
their scales' worth left out, the bf16 head — plus the keys and values of
the live tokens of its rows, in every layer. The embedding is a gather of
``rows`` rows and is left out. A lower bound: no honest step reads less.

Resident are the same weights, the embedding, and the WHOLE key-value
pool, each at the bytes per value that the configuration file states
under ``precision``: the lower bound that ``correct`` holds the program's
live device arrays to (``reference/check.py``).
"""

from __future__ import annotations

# Whole programs on the "XLA Modules" line that are pure decode, with the
# passes over the weights one run of each makes (None: ``decode_steps`` of
# the config). A speculative verify is ONE pass, over up to ``decode_steps``
# positions of every row at once.
PROGRAMS = {"jit__decode_multi": None, "jit__decode_step": 1, "jit__decode_spec": 1}


def weight_bytes(model: dict, matrix_bytes: int = 1, head_bytes: int = 2) -> int:
    d, f, layers = model["dim"], model["ffn_dim"], model["n_layers"]
    hd = d // model["n_heads"]
    q, kv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    return layers * (attn + 3 * d * f) * matrix_bytes + d * model["vocab_size"] * head_bytes


def kv_bytes(model: dict, tokens: float, kv_bytes_per_value: int = 2) -> float:
    hd = model["dim"] // model["n_heads"]
    return 2.0 * tokens * model["n_kv_heads"] * hd * kv_bytes_per_value * model["n_layers"]


def step_bytes(model: dict, live_tokens: float) -> float:
    return weight_bytes(model) + kv_bytes(model, live_tokens)


def resident_bytes(model: dict, llm: dict, precision: dict) -> int:
    """Layer matrices, embedding, head and the whole pool, at the stated
    bytes per value (scales, norms and biases left out: a lower bound)."""
    wide = precision["embedding_and_head_bytes"]
    embed = 0 if model.get("tie_embeddings") else model["vocab_size"] * model["dim"] * wide
    pool_tokens = llm["num_pages"] * llm["page_size"]
    return int(weight_bytes(model, precision["layer_matrix_bytes"], wide) + embed
               + kv_bytes(model, pool_tokens, precision["kv_bytes"]))
