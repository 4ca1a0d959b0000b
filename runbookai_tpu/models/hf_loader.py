"""HF checkpoint loading: safetensors → stacked JAX pytrees (+ sharded put).

New construction (SURVEY.md §5.4 — the reference never loads weights). Reads a
HuggingFace Llama directory (``config.json`` + ``*.safetensors``), transposes
``[out, in]`` projection weights to this build's ``[in, out]`` convention,
stacks per-layer weights on a leading axis for the scan-based forward, and —
when a mesh is supplied — ``device_put``s each leaf with its TP/DP
``NamedSharding`` so 70B-class checkpoints stream straight to their shards
without materializing the full model on one host/chip.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from runbookai_tpu.models.family import CONFIGS, FAMILIES, Family

# Our layer-stacked param leaf -> (HF template, transpose?)
_LAYER_MAP = {
    "wq": ("model.layers.{i}.self_attn.q_proj.weight", True),
    "wk": ("model.layers.{i}.self_attn.k_proj.weight", True),
    "wv": ("model.layers.{i}.self_attn.v_proj.weight", True),
    "wo": ("model.layers.{i}.self_attn.o_proj.weight", True),
    "w_gate": ("model.layers.{i}.mlp.gate_proj.weight", True),
    "w_up": ("model.layers.{i}.mlp.up_proj.weight", True),
    "w_down": ("model.layers.{i}.mlp.down_proj.weight", True),
    "attn_norm": ("model.layers.{i}.input_layernorm.weight", False),
    "mlp_norm": ("model.layers.{i}.post_attention_layernorm.weight", False),
}

# Qwen2-only bias leaves (1-D per layer, no transpose).
_BIAS_MAP = {
    "bq": "model.layers.{i}.self_attn.q_proj.bias",
    "bk": "model.layers.{i}.self_attn.k_proj.bias",
    "bv": "model.layers.{i}.self_attn.v_proj.bias",
}


# Which family a checkpoint's ``model_type`` belongs to is the families' to
# say (``Family.hf_model_types``; :func:`supported_model_types` lists those
# with a loader here). Four share the Llama block (pre-norm GQA attention +
# SwiGLU); qwen2 adds q/k/v projection biases, mixtral swaps the dense FFN for
# an 8-expert top-2 MoE. A window is served as a window where the family
# declares one (``afmoe``: models/afmoe.py, its sliding layers over a pool of
# their own); the Llama block declares none, so a Mistral sliding-window
# checkpoint loads and is served with full attention, exact for contexts up to
# the window.

# afmoe tensor names (``modeling_afmoe.py``): leaf -> (template, transpose).
# Attention and the four norms a layer; the dense FFN of the leading layers;
# router, balance bias, shared expert and routed experts of the rest.
_AFMOE_LAYER_MAP = {
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wg": ("self_attn.gate_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "q_norm": ("self_attn.q_norm.weight", False),
    "k_norm": ("self_attn.k_norm.weight", False),
    "norm1": ("input_layernorm.weight", False),
    "norm2": ("post_attention_layernorm.weight", False),
    "norm3": ("pre_mlp_layernorm.weight", False),
    "norm4": ("post_mlp_layernorm.weight", False),
}
_AFMOE_FFN = (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj"))


def load_afmoe_params(model_dir: str | Path, cfg, dtype=jnp.bfloat16):
    """Stacked params (``models/afmoe.py`` ``leaf_shapes``) from an HF
    ``afmoe`` directory: the held experts ``first_expert ..`` of every
    expert layer, the router float32 with all its outputs."""
    idx = _ShardIndex(Path(model_dir))
    L, k = cfg.num_hidden_layers, cfg.num_dense_layers

    def get(i: int, suffix: str, transpose: bool) -> np.ndarray:
        w = idx.get(f"model.layers.{i}.{suffix}")
        return w.T if transpose else w

    layers: dict[str, Any] = {}
    for leaf, (suffix, transpose) in _AFMOE_LAYER_MAP.items():
        layers[leaf] = _put(np.stack([get(i, suffix, transpose) for i in range(L)]),
                            jnp.float32 if "norm" in leaf else dtype)
    held = range(cfg.first_expert, cfg.first_expert + cfg.n_experts_held)
    for short, proj in _AFMOE_FFN:
        layers[f"d_{short}"] = _put(np.stack(
            [get(i, f"mlp.{proj}.weight", True) for i in range(k)]), dtype)
        layers[f"s_{short}"] = _put(np.stack(
            [get(i, f"mlp.shared_experts.{proj}.weight", True)
             for i in range(k, L)]), dtype)
        layers[f"e_{short}"] = _put(np.stack(
            [np.stack([get(i, f"mlp.experts.{e}.{proj}.weight", True) for e in held])
             for i in range(k, L)]), dtype)
    layers["router"] = _put(np.stack(
        [get(i, "mlp.router.gate.weight", True) for i in range(k, L)]), jnp.float32)
    layers["router_bias"] = _put(np.stack(
        [get(i, "mlp.expert_bias", False) for i in range(k, L)]), jnp.float32)
    return cfg, {"embed": _put(idx.get("model.embed_tokens.weight"), dtype),
                 "layers": layers,
                 "final_norm": _put(idx.get("model.norm.weight"), jnp.float32),
                 "lm_head": _put(idx.get("lm_head.weight").T, dtype)}


def supported_model_types() -> tuple[str, ...]:
    """The ``model_type``s of the families with a loader here."""
    return tuple(t for fam in FAMILIES if not fam.checkpoint_tensors
                 for t in fam.hf_model_types)


# The loaders of the families whose leaves are not the Llama block's
# (:func:`load_params` holds that one), by ``Family.family_name``.
_OWN_LEAVES = {"afmoe": load_afmoe_params}


def config_from_hf(model_dir: str | Path, name: str = "hf-model") -> Family:
    raw = json.loads((Path(model_dir) / "config.json").read_text())
    model_type = raw.get("model_type", "llama")
    for fam in FAMILIES:
        if fam.claims(model_type):
            return fam.from_hf(raw, name)
    raise ValueError(
        f"model_type {model_type!r} not supported; known: "
        f"{supported_model_types()}")


class _ShardIndex:
    """Maps tensor name -> safetensors file, loading files lazily."""

    def __init__(self, model_dir: Path):
        self.dir = model_dir
        index_file = model_dir / "model.safetensors.index.json"
        self._handles: dict[str, Any] = {}
        if index_file.is_file():
            index = json.loads(index_file.read_text())
            self.weight_map = dict(index["weight_map"])
        else:
            shards = sorted(model_dir.glob("*.safetensors"))
            if not shards:
                raise FileNotFoundError(f"no .safetensors files under {model_dir}")
            from safetensors import safe_open

            self.weight_map = {}
            for shard in shards:
                with safe_open(str(shard), framework="numpy") as f:
                    for key in f.keys():
                        self.weight_map[key] = shard.name

    def get(self, name: str) -> np.ndarray:
        from safetensors import safe_open

        fname = self.weight_map[name]
        handle = self._handles.get(fname)
        if handle is None:
            handle = safe_open(str(self.dir / fname), framework="numpy")
            self._handles[fname] = handle
        return handle.get_tensor(name)


def _put(arr: np.ndarray, dtype, sharding=None) -> jax.Array:
    x = jnp.asarray(arr, dtype=dtype)
    if sharding is not None:
        x = jax.device_put(x, sharding)
    return x


def load_params(
    model_dir: str | Path,
    cfg: Optional[Family] = None,
    dtype=jnp.bfloat16,
    shardings: Optional[dict[str, Any]] = None,
    quantize_int8: bool = False,
) -> tuple[Family, Any]:
    """Load stacked params from an HF Llama directory.

    ``shardings``, when given, is a pytree-shaped dict matching the params
    structure whose leaves are ``NamedSharding``s (see
    :func:`runbookai_tpu.parallel.sharding.param_shardings`; pass it through
    :func:`runbookai_tpu.models.quant.shardings_with_quant` when quantizing).
    ``quantize_int8`` converts the big layer matrices to int8 on the host so
    the bf16 tensors never reach device HBM (70B must load this way on v5e).
    """
    from runbookai_tpu.models.quant import LAYER_QUANT_KEYS, quantize_array_np

    model_dir = Path(model_dir)
    cfg = cfg or config_from_hf(model_dir)
    own_leaves = _OWN_LEAVES.get(cfg.family_name)
    if own_leaves is not None:  # a family with leaves of its own, on one chip
        _one_chip_only(cfg, quantize_int8, shardings)
        return own_leaves(model_dir, cfg, dtype)
    idx = _ShardIndex(model_dir)
    sh = shardings or {}

    def shard_of(*path):
        node: Any = sh
        for p in path:
            if not isinstance(node, dict) or p not in node:
                return None
            node = node[p]
        return node

    params: dict[str, Any] = {}
    params["embed"] = _put(
        idx.get("model.embed_tokens.weight"), dtype, shard_of("embed")
    )
    layers: dict[str, Any] = {}

    def store(leaf: str, stacked: np.ndarray) -> None:
        """Place one stacked leaf (quantizing the big matrices on request)."""
        if quantize_int8 and leaf in LAYER_QUANT_KEYS:
            q, s = quantize_array_np(stacked)
            leaf_sh = shard_of("layers", leaf)
            if not isinstance(leaf_sh, dict):
                leaf_sh = {"q": leaf_sh, "s": None}
            layers[leaf] = {
                "q": _put(q, jnp.int8, leaf_sh.get("q")),
                "s": _put(s, jnp.float32, leaf_sh.get("s")),
            }
            return
        leaf_dtype = jnp.float32 if leaf.endswith("norm") else dtype
        layers[leaf] = _put(stacked, leaf_dtype, shard_of("layers", leaf))

    layer_map = dict(_LAYER_MAP)
    if cfg.n_experts:
        for k in ("w_gate", "w_up", "w_down"):
            layer_map.pop(k)
    for leaf, (tmpl, transpose) in layer_map.items():
        mats = []
        for i in range(cfg.n_layers):
            w = idx.get(tmpl.format(i=i))
            mats.append(w.T if transpose else w)
        store(leaf, np.stack(mats))
    if cfg.n_experts:
        # Mixtral MoE FFN: experts stacked on a leading E axis per layer
        # (HF w1=gate, w3=up, w2=down, all [out, in] → transposed), plus
        # the router (never quantized — tiny and precision-critical).
        for leaf, part in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2")):
            tmpl = ("model.layers.{i}.block_sparse_moe.experts.{e}."
                    + part + ".weight")
            store(leaf, np.stack([
                np.stack([idx.get(tmpl.format(i=i, e=e)).T
                          for e in range(cfg.n_experts)])
                for i in range(cfg.n_layers)]))
        layers["router"] = _put(
            np.stack([idx.get(
                f"model.layers.{i}.block_sparse_moe.gate.weight").T
                for i in range(cfg.n_layers)]),
            dtype, shard_of("layers", "router"))
    if cfg.qkv_bias:
        for leaf, tmpl in _BIAS_MAP.items():
            stacked = np.stack([idx.get(tmpl.format(i=i))
                                for i in range(cfg.n_layers)])
            layers[leaf] = _put(stacked, dtype, shard_of("layers", leaf))
    params["layers"] = layers
    params["final_norm"] = _put(idx.get("model.norm.weight"), jnp.float32, shard_of("final_norm"))
    if not cfg.tie_embeddings:
        params["lm_head"] = _put(
            idx.get("lm_head.weight").T, dtype, shard_of("lm_head")
        )
    return cfg, params


def _one_chip_only(cfg: Family, quantize_int8: bool, shardings) -> None:
    if quantize_int8 or shardings:
        raise ValueError(
            f"model {cfg.name!r} (family {cfg.family_name}) serves bf16 or "
            f"float32 weights on one chip: no int8 matrices, no mesh")


def quiet_control_tokens(params: Any, vocab_size: int) -> Any:
    """Seeded weights with the head's columns of the byte tokenizer's control
    ids (``ByteTokenizer.special_ids``: begin/end of text, headers, eot,
    pad) set to zero, so that greedy decoding over a RANDOM head never ends
    an answer: a checkpoint ends one where it learnt to, a random head where
    the seed happens to put a stop id on top — two of a vocabulary's rows,
    so one answer in twenty of a few hundred tokens at 16,384 rows, and the
    seed then sets how much work a stream of requests is.

    For the random-init path only, whatever the family. Applied today where
    a family's seeded recipe is new (longcat): the dense families' recipe is
    held bit for bit by ``benchmark/blocks/dense/weights.py`` and the tests
    of ``init_params``, and at their 128k-152k rows a stop id tops a random
    head once in some 70,000 tokens; moving them is a benchmark change."""
    from runbookai_tpu.utils.tokens import ByteTokenizer

    quiet = sorted(t for t in ByteTokenizer().special_ids if t < vocab_size)
    head = params["lm_head"].at[:, jnp.asarray(quiet, jnp.int32)].set(0)
    return {**params, "lm_head": head}


def load_or_init(
    model_name: str,
    model_path: Optional[str | Path],
    dtype=jnp.bfloat16,
    shardings: Optional[dict[str, Any]] = None,
    seed: int = 0,
    quantize_int8: bool = False,
) -> tuple[Family, Any]:
    """Load from ``model_path`` when present, else random-init ``model_name``.

    Random init keeps every serving path exercisable in the no-egress
    environment (BASELINE.md configs run with real weights when provided).
    """
    known = CONFIGS.get(model_name)
    if (known is not None and known.checkpoint_tensors and model_path
            and Path(model_path).exists()):
        raise NotImplementedError(
            f"model {model_name!r}: no loader for checkpoints of the "
            f"{known.family_name} family yet ({known.checkpoint_tensors}); "
            f"leave llm.model_path unset to serve seeded random weights")
    if model_path and Path(model_path).exists():
        from runbookai_tpu.models.checkpoint import is_checkpoint, load_checkpoint

        if is_checkpoint(model_path):
            # Orbax checkpoint (possibly pre-quantized): restores straight to
            # the sharded placement, no host-side safetensors pass.
            cfg, params = load_checkpoint(model_path, shardings=shardings, dtype=dtype)
            from runbookai_tpu.models.quant import is_quantized, quantize_params

            if quantize_int8 and not any(
                is_quantized(v) for v in params["layers"].values()
            ):
                params = quantize_params(params)
                if shardings:
                    params = jax.tree.map(
                        lambda x, s: jax.device_put(x, s) if s is not None else x,
                        params, shardings, is_leaf=lambda x: x is None)
            return cfg, params
        cfg = config_from_hf(model_path, name=model_name)
        return load_params(model_path, cfg, dtype=dtype, shardings=shardings,
                           quantize_int8=quantize_int8)
    if model_name not in CONFIGS:
        raise KeyError(
            f"unknown model {model_name!r} and no checkpoint at "
            f"{str(model_path)!r}; known configs: {sorted(CONFIGS)}")
    cfg = CONFIGS[model_name]
    key = jax.random.PRNGKey(seed)
    if cfg.one_path:  # the families whose seeded recipe is this repo's own
        _one_chip_only(cfg, quantize_int8, shardings)
        return cfg, quiet_control_tokens(cfg.init_params(key, dtype), cfg.vocab_size)
    params = cfg.init_params(key, dtype, quantized=quantize_int8)
    if shardings:
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s) if s is not None else x,
            params,
            shardings,
            is_leaf=lambda x: x is None,
        )
    return cfg, params
