"""The system under test, as the benchmark takes it from the program:
``cli.main.build_server`` — the one construction path of ``runbook serve``
— fed a serve config rendered from a file under ``benchmark/configs/``,
served in this process on a loopback port and spoken to over HTTP only.
"""

from __future__ import annotations

import dataclasses
import http.client
import importlib
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
RUN_DIR = ROOT / ".benchmark_run"  # plans, records, traces (git-ignored)

# LlamaConfig fields a configuration file states (compared with CONFIGS[base]).
MODEL_KEYS = ("vocab_size", "dim", "n_layers", "n_heads", "n_kv_heads",
              "ffn_dim", "rope_theta", "norm_eps", "max_seq_len",
              "qkv_bias", "tie_embeddings", "family", "n_experts",
              "top_k_experts", "capacity_factor")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_json() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(bench: dict, workload: str) -> tuple[dict, dict, dict, dict]:
    """(cell entry, configuration file, traffic file, cell file) by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / config_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    cell_path = BENCH / "cells" / f"{workload}.json"
    extra = load_json(cell_path) if cell_path.is_file() else {}
    return cell, config, traffic, extra


def model_config(config: dict, rehearsal: bool = False):
    """The ``LlamaConfig`` a configuration file describes. Checked against
    ``CONFIGS[base]``: every stated key outside ``reduced`` must equal the
    program's transcription of the published config."""
    from runbookai_tpu.models.llama import CONFIGS

    if rehearsal:
        return CONFIGS[config["rehearsal"]["base"]]
    base = CONFIGS[config["base"]]
    stated = {k: config[k] for k in MODEL_KEYS if k in config}
    for key, value in stated.items():
        if key not in config["reduced"] and getattr(base, key) != value:
            raise ValueError(
                f"{config['name']}: {key}={value!r} differs from "
                f"CONFIGS[{config['base']!r}].{key}={getattr(base, key)!r} "
                f"and is not listed under 'reduced'")
    return dataclasses.replace(base, name=config["name"], **stated)


def reference_cfg(model_cfg) -> dict:
    """The sizes the plain reference needs, as a plain dict."""
    return {k: getattr(model_cfg, k) for k in MODEL_KEYS}


def register(model_cfg, seed: int) -> None:
    """Make the configuration servable by name, with weights from ``seed``
    and text for every id of its vocabulary: ``CONFIGS`` gains the entry,
    ``load_or_init`` — which ``fleet/build.py`` calls without a seed — gets
    this run's, and the served byte tokenizer, which has text for 262 ids,
    gets ``reference/tokens.py``'s one character an id (a checkpoint's
    tokenizer has text for all of them; without it the stream carries no
    text for most tokens, and nothing could be timed or compared)."""
    import functools

    from benchmark.reference import tokens
    from runbookai_tpu.models import hf_loader
    from runbookai_tpu.models.llama import CONFIGS
    from runbookai_tpu.utils.tokens import ByteTokenizer

    CONFIGS[model_cfg.name] = model_cfg
    if not isinstance(hf_loader.load_or_init, functools.partial):
        hf_loader.load_or_init = functools.partial(
            hf_loader.load_or_init, seed=seed % (2 ** 31))
    ByteTokenizer.id_to_bytes = lambda self, tid: tokens.vocabulary_bytes(tid)
    ByteTokenizer.decode = lambda self, ids: tokens.vocabulary_text(ids)


def render_serve_config(config: dict, model_name: str, rehearsal: bool,
                        overrides: dict | None = None) -> Path:
    """Write the serve config (JSON is YAML) the server is built from."""
    llm = dict(config["rehearsal"]["llm"] if rehearsal else config["llm"])
    llm.update(provider="jax-tpu", model=model_name, **(overrides or {}))
    RUN_DIR.mkdir(parents=True, exist_ok=True)
    if config.get("engine_plan"):
        # Engine settings with no llm.* spelling (speculative...) reach
        # `runbook serve` through a serving plan only: write one.
        from runbookai_tpu.autotune.plan import PlanArtifact, save_plan

        plan = PlanArtifact(model=model_name, topology={},
                            engine=dict(config["engine_plan"]))
        llm["plan"] = str(save_plan(plan, RUN_DIR / f"plan.{config['name']}.json"))
    path = RUN_DIR / f"serve.{config['name']}.yaml"
    path.write_text(json.dumps({"llm": llm}, indent=1) + "\n")
    return path


def build(config_path: Path):
    from runbookai_tpu.cli.main import build_server

    server = build_server(str(config_path), host="127.0.0.1", port=0)
    server.start_background()
    return server


class Http:
    """GETs against the server under test (stdlib)."""

    def __init__(self, port: int):
        self.port = port

    def get(self, path: str) -> bytes:
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=60)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path} answered {resp.status}: {body[:300]!r}")
            return body
        finally:
            conn.close()

    def healthz(self) -> dict:
        return json.loads(self.get("/healthz"))

    def steps(self, n: int = 512) -> list[dict]:
        return json.loads(self.get(f"/debug/steps?n={n}"))["steps"]

    def metrics_text(self) -> str:
        return self.get("/metrics").decode()


def wrap_spans(spans: dict) -> list[str]:
    """Traced run only: wrap the methods ``spans.json`` lists in a
    ``TraceAnnotation`` from this side. Returns the paths that no longer
    resolve (named on an earlier line, not fatal)."""
    import jax

    missing = []
    for path, name in spans.get("wrap", {}).items():
        mod_name, attr_path = path.split(":")
        try:
            owner = importlib.import_module(mod_name)
            *parents, leaf = attr_path.split(".")
            for p in parents:
                owner = getattr(owner, p)
            fn = getattr(owner, leaf)
        except (ImportError, AttributeError):
            missing.append(path)
            continue

        def wrapped(*a, _fn=fn, _name=name, **kw):
            with jax.profiler.TraceAnnotation(_name):
                return _fn(*a, **kw)

        setattr(owner, leaf, wrapped)
    return missing
