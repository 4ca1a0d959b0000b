"""The seam a model family is (``models/family.py``): every configuration is
a ``Family`` and declares what the engine, the memory plan and the loader
read; ``forwards()`` has one signature and one six-field result for all six
families; the registry is one dict; and the imports under ``models/`` point
one way."""

import ast
import dataclasses
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import runbookai_tpu.models
from runbookai_tpu.models import family, hf_loader, llama

SIX = ["llama3-test", "longcat-test", "qwen3-next-test", "joyai-test",
       "nemotron-h-test", "afmoe-test"]
# Everything ``engine/engine.py``, ``engine/memory_plan.py`` and
# ``models/hf_loader.py`` read of a configuration.
SEAM = ["name", "family", "vocab_size", "dim", "n_layers", "n_heads", "norm_eps",
        "max_seq_len", "tie_embeddings", "matmul_params", "total_params",
        "kv_pool_spec", "kv_window_spec", "state_pool_spec", "pallas_attention",
        "pallas_prefill", "max_prefill_rows", "self_draft", "forwards", "drafter",
        "unsupported", "one_path", "no_prompt_lookup", "no_draft_model",
        "init_params", "weight_bytes_per_chip", "family_name", "hf_model_types",
        "checkpoint_tensors", "claims", "from_hf"]
# The registry at the parent commit, in its order.
PARENT_NAMES = [
    "llama3-8b-instruct", "llama3-70b-instruct", "llama3-1b-bench",
    "llama3.1-8b-instruct", "llama3.1-70b-instruct", "llama3.3-70b-instruct",
    "llama3.2-1b-instruct", "llama3.2-3b-instruct", "llama3-test",
    "qwen2-7b-instruct", "qwen2.5-7b-instruct", "qwen2.5-14b-instruct",
    "qwen2.5-32b-instruct", "qwen2-test", "mistral-7b-instruct",
    "mixtral-8x7b-instruct", "mixtral-test", "longcat-flash-chat",
    "longcat-flash-ep32", "longcat-test", "qwen3-next-80b-a3b-instruct",
    "qwen3-next-80b-ep4", "qwen3-next-test", "joyai-llm-flash",
    "joyai-llm-flash-ep4", "joyai-test", "nemotron-3-nano-30b-a3b",
    "nemotron-3-nano-ep8", "nemotron-h-test", "trinity-mini", "trinity-mini-ep8",
    "afmoe-test"]
PS, PAGES, SLOTS, RQ = 16, 4, 2, 8


def _pools(cfg):
    """Zeroed pools of ``PAGES`` pages (and the null page) as the
    configuration declares them, and one row's page table a slot."""
    rows = (PAGES + 1) * PS
    (kl, kh, kd), (vl, vh, vd) = cfg.kv_pool_spec
    kv_k, kv_v = jnp.zeros((kl, rows, kh, kd)), jnp.zeros((vl, rows, vh, vd))
    tables = np.zeros((SLOTS, PAGES + 1), np.int32)
    tables[0, :2], tables[1, :2] = [1, 2], [3, 4]
    if cfg.kv_window_spec:  # two groups of the pool, a table's two halves
        window = jnp.zeros((cfg.kv_window_spec[0], rows, kh, kd))
        kv_k, kv_v = ({"full": side, "window": window} for side in (kv_k, kv_v))
        tables = np.concatenate([tables, tables], axis=1)
    state = None
    if cfg.state_pool_spec:
        state = tuple(jnp.zeros((shape[0], SLOTS, *shape[1:]), dtype)
                      for shape, dtype in cfg.state_pool_spec)
    return kv_k, kv_v, jnp.asarray(tables), state


@pytest.mark.parametrize("name", SIX)
def test_a_family_declares_what_the_engine_reads_and_serves_one_result(name):
    cfg = family.CONFIGS[name]
    assert isinstance(cfg, family.Family)
    # no field is the base's: the dataclass's fields are its published sizes
    assert not hasattr(family.Family, "__dataclass_fields__")
    missing = [a for a in SEAM if not hasattr(type(cfg), a)
               and a not in {f.name for f in dataclasses.fields(cfg)}]
    assert missing == []
    assert type(cfg) in family.FAMILIES and cfg.family_name
    # a loader, or the tensor names one would need: never both, never neither
    assert (cfg.checkpoint_tensors is None) == (name in ("llama3-test", "afmoe-test"))

    params = hf_loader.load_or_init(name, None, seed=3, dtype=jnp.float32)[1]
    kv_k, kv_v, tables, state = _pools(cfg)
    forward, forward_ragged = cfg.forwards()
    t = 5
    tokens = jnp.asarray(np.arange(2 * t).reshape(2, t) % 250, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (2, t))
    out = forward(params, cfg, tokens, positions, kv_k, kv_v, tables,
                  jnp.asarray([t, t]), page_size=PS, block_pages=2, state=state,
                  state_rows=None if state is None else jnp.asarray([0, 1]))
    assert len(out) == 6
    logits, _, _, experts, new_state, hidden = out
    assert logits.shape == (2, t, cfg.vocab_size) and logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()
    assert (new_state is None) == (cfg.state_pool_spec is None)
    assert cfg.self_draft == (cfg.drafter() is not None)
    assert (hidden is None) == (cfg.drafter() is None)
    assert (experts is None) == (name == "llama3-test")
    if hidden is not None:
        assert hidden.shape == (2, t, cfg.dim)

    # the mixed step's flat buffer: a block a decode slot, then one prefill
    # row's chunk of 8 tokens, the null row last
    n = SLOTS * RQ + RQ
    flat_tokens = jnp.zeros((n,), jnp.int32).at[SLOTS * RQ:].set(7)
    trash = tables.shape[1] // (2 if cfg.kv_window_spec else 1) * PS - PS
    flat_pos = np.full((n,), trash, np.int32)
    flat_pos[0], flat_pos[RQ] = t, t
    flat_pos[SLOTS * RQ:] = np.arange(RQ)
    row_ids = np.repeat(np.arange(SLOTS + 1), RQ).astype(np.int32)
    row_tables = jnp.concatenate([tables, tables[:1] * 0, tables[:1] * 0])
    if cfg.state_pool_spec:  # the prefill row's chunk goes into slot 0's state
        state = tuple(jnp.zeros_like(a) for a in new_state)
    ragged = forward_ragged(
        params, cfg, flat_tokens, jnp.asarray(flat_pos), jnp.asarray(row_ids), kv_k,
        kv_v, row_tables, jnp.asarray([t + 1, t + 1, 0, 0]),
        jnp.asarray([0, RQ], jnp.int32), page_size=PS, block_pages=2,
        ragged_block=RQ, state=state,
        state_rows=None if state is None else jnp.asarray([0, 1, SLOTS, SLOTS]))
    assert len(ragged) == 6 and ragged[0].shape == (2, cfg.vocab_size)
    assert (ragged[4] is None) == (cfg.state_pool_spec is None)
    assert (ragged[5] is None) == (cfg.drafter() is None)
    if ragged[5] is not None:
        assert ragged[5].shape == (n, cfg.dim)


def test_the_registry_is_one_dict(tmp_path):
    assert llama.CONFIGS is family.CONFIGS
    assert [n for n in family.CONFIGS if n in PARENT_NAMES] == PARENT_NAMES
    assert llama.get_config("afmoe-test") is family.CONFIGS["afmoe-test"]
    with pytest.raises(KeyError, match="Unknown model"):
        family.get_config("no-such-model")
    # what ``benchmark/serving.py register()`` relies on: an entry written
    # after import is what the loader serves
    added = dataclasses.replace(family.CONFIGS["longcat-test"], name="longcat-added",
                                num_layers=1)
    llama.CONFIGS[added.name] = added
    try:
        assert family.get_config(added.name) is added
        cfg, params = hf_loader.load_or_init(added.name, None, seed=1, dtype=jnp.float32)
        assert cfg is added and params["layers"]["e_gate"].shape[0] == 1
    finally:
        del family.CONFIGS[added.name]
    assert hf_loader.supported_model_types() == (
        "llama", "qwen2", "mistral", "mixtral", "afmoe")
    (tmp_path / "config.json").write_text('{"model_type": "llama4"}')
    with pytest.raises(ValueError, match="not supported"):
        hf_loader.config_from_hf(tmp_path)


def test_imports_under_models_point_one_way():
    """No function body under ``models/`` imports a family file or
    ``family.py``; no family file imports another but the stated pair (the
    two recurrent families); ``family.py`` imports none of them; and nothing
    under ``ops/`` or ``parallel/`` imports ``runbookai_tpu.models`` inside a
    function."""
    package = Path(runbookai_tpu.models.__file__).parent
    families = {"llama", "longcat", "qwen3_next", "joyai", "nemotron_h", "afmoe"}
    prefix = "runbookai_tpu.models."

    def imported(node):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "runbookai_tpu.models":
                return {a.name for a in node.names}
            if node.module.startswith(prefix):
                return {node.module[len(prefix):]}
        if isinstance(node, ast.Import):
            return {a.name[len(prefix):] for a in node.names if a.name.startswith(prefix)}
        return set()

    deferred, between = {}, {}
    for path in sorted(package.glob("*.py")):
        tree = ast.parse(path.read_text())
        for fn in ast.walk(tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                inside = set().union(*(imported(n) for n in ast.walk(fn)))
                if inside & (families | {"family"}):
                    deferred[f"{path.name}:{fn.lineno}"] = sorted(inside)
        top = set().union(*(imported(n) for n in tree.body))
        if path.stem in families | {"family"} and top & families:
            between[path.stem] = sorted(top & families)
    assert deferred == {}
    assert between == {"nemotron_h": ["qwen3_next"]}

    lower = {}
    for layer in ("ops", "parallel"):
        for path in sorted((package.parent / layer).glob("*.py")):
            for fn in ast.walk(ast.parse(path.read_text())):
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    hits = [ast.unparse(n) for n in ast.walk(fn)
                            if isinstance(n, (ast.Import, ast.ImportFrom))
                            and "runbookai_tpu.models" in ast.unparse(n)]
                    if hits:
                        lower[f"{layer}/{path.name}:{fn.lineno}"] = hits
    assert lower == {}
    assert not any("runbookai_tpu.models" in ast.unparse(n)
                   for path in (package.parent / "ops").glob("*.py")
                   for n in ast.walk(ast.parse(path.read_text()))
                   if isinstance(n, (ast.Import, ast.ImportFrom)))
