"""Sharded model-weight checkpoints (orbax) — fast reload for serving.

The reference has no model weights at all (SURVEY.md §5.4: "model-weights
checkpointing does not exist; the TPU build needs weight loading — new
construction"). Loading 70B from HF safetensors and re-quantizing on every
boot costs minutes of host time; this module converts once and restores
directly to sharded device arrays:

    HF safetensors ──load_or_init(quantize_int8=...)──▶ params pytree
    params pytree  ──save_checkpoint──▶ orbax dir (config.json + pytree/)
    orbax dir      ──load_checkpoint(shardings=...)──▶ sharded device arrays

Quantized ``{"q": int8, "s": f32}`` leaves are plain arrays to orbax, so
int8 checkpoints round-trip unchanged. Restore places each leaf directly on
its TP shard (no full-host materialization) when ``shardings`` is given.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Optional

import jax

from runbookai_tpu.models.llama import LlamaConfig

_CONFIG_FILE = "config.json"
_TREE_DIR = "pytree"


def save_checkpoint(path: str | Path, cfg: LlamaConfig, params: Any) -> Path:
    """Write ``config.json`` + the params pytree under ``path``."""
    import orbax.checkpoint as ocp

    path = Path(path).absolute()
    path.mkdir(parents=True, exist_ok=True)
    (path / _CONFIG_FILE).write_text(json.dumps(dataclasses.asdict(cfg), indent=2))
    ckptr = ocp.StandardCheckpointer()
    tree_path = path / _TREE_DIR
    ckptr.save(tree_path, params, force=True)
    ckptr.wait_until_finished()
    return path


def checkpoint_config(path: str | Path) -> LlamaConfig:
    data = json.loads((Path(path) / _CONFIG_FILE).read_text())
    # JSON round-trips tuples as lists; the config must stay hashable (it
    # is a static jit argument) and ==-comparable with the original.
    if data.get("rope_scaling") is not None:
        data["rope_scaling"] = tuple(data["rope_scaling"])
    return LlamaConfig(**data)


def is_checkpoint(path: Optional[str | Path]) -> bool:
    return bool(path) and (Path(path) / _CONFIG_FILE).is_file() \
        and (Path(path) / _TREE_DIR).exists()


def load_checkpoint(
    path: str | Path,
    shardings: Optional[Any] = None,
    dtype=None,
) -> tuple[LlamaConfig, Any]:
    """Restore ``(cfg, params)``; leaves land on their shards directly.

    ``shardings`` is the (possibly quant-expanded) ``param_shardings`` tree;
    missing/None entries restore unsharded. ``dtype`` optionally casts
    floating-point leaves on restore (int8 payloads are never cast).
    """
    import jax.numpy as jnp
    import orbax.checkpoint as ocp

    path = Path(path).absolute()
    cfg = checkpoint_config(path)
    ckptr = ocp.StandardCheckpointer()
    meta = ckptr.metadata(path / _TREE_DIR).item_metadata.tree

    def spec_for(leaf_meta, sh):
        target_dtype = leaf_meta.dtype
        if (dtype is not None and jnp.issubdtype(target_dtype, jnp.floating)
                and target_dtype != jnp.float32):  # norms stay f32
            target_dtype = dtype
        return jax.ShapeDtypeStruct(leaf_meta.shape, target_dtype, sharding=sh)

    fallback = False
    if shardings is None:
        target = jax.tree.map(lambda m: spec_for(m, None), meta)
    else:
        try:
            target = jax.tree.map(spec_for, meta, shardings,
                                  is_leaf=lambda x: x is None)
        except ValueError:
            # Structure mismatch (e.g. quant-expanded shardings against an
            # unquantized checkpoint). Restoring the whole tree unsharded is
            # an OOM/perf cliff at 70B scale, so warn loudly and reshard
            # leaf-by-leaf after restore where specs still line up.
            import warnings
            warnings.warn(
                f"load_checkpoint({path}): shardings tree does not match the "
                "checkpoint structure; restoring unsharded and resharding "
                "matching leaves with device_put. Re-convert the checkpoint "
                "to silence this.", stacklevel=2)
            target = jax.tree.map(lambda m: spec_for(m, None), meta)
            fallback = True
    params = ckptr.restore(path / _TREE_DIR, target)
    if fallback:
        flat_sh = {tuple(map(str, p)): s for p, s in
                   jax.tree_util.tree_flatten_with_path(
                       shardings, is_leaf=lambda x: x is None)[0] if s is not None}
        flat_pm = jax.tree_util.tree_flatten_with_path(params)[0]
        moved = {tuple(map(str, p)): jax.device_put(v, flat_sh[tuple(map(str, p))])
                 for p, v in flat_pm if tuple(map(str, p)) in flat_sh}
        params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params),
            [moved.get(tuple(map(str, p)), v) for p, v in flat_pm])
    return cfg, params


def convert_hf_to_checkpoint(
    model_path: str | Path,
    out_path: str | Path,
    model_name: str = "hf-model",
    quantize_int8: bool = False,
    dtype=None,
    allow_random_init: bool = False,
) -> Path:
    """One-time conversion: HF safetensors → (optionally int8) orbax dir.

    Raises ``FileNotFoundError`` for a missing ``model_path`` — falling
    through to random init here would write a valid-looking checkpoint of
    garbage weights with no error. ``allow_random_init=True`` opts into
    that fallback explicitly (CI / no-egress smoke checkpoints).
    """
    import jax.numpy as jnp

    from runbookai_tpu.models.hf_loader import load_or_init

    if not Path(model_path).exists():
        if not allow_random_init:
            raise FileNotFoundError(
                f"weights convert: model_path does not exist: {model_path} "
                "(pass --random-init to write a random-weights checkpoint)")
        if model_name == "hf-model":
            # Random init asked for and no model named: the tiny test
            # model, said here and not left to the loader (an unknown
            # name given on purpose raises there).
            model_name = "llama3-test"

    cfg, params = load_or_init(
        model_name, model_path, dtype=dtype or jnp.bfloat16,
        quantize_int8=quantize_int8,
    )
    return save_checkpoint(out_path, cfg, params)
