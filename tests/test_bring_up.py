"""What the chip bring-up repaired, at CPU size.

Each test here failed (or could not be written) at the parent commit:
``chip_smoke.py``'s rehearsal and refusal, a guided request on a model
whose vocabulary is wider than the tokenizer's, the int8 loader without a
wide intermediate, an unknown model name, the compile-cache helper, and
the server stopping every thread it started.
"""

import json
import threading

import jax
import jax.numpy as jnp
import pytest

from runbookai_tpu.engine.engine import EngineConfig, EngineCore
from runbookai_tpu.engine.request import EngineRequest, SamplingParams
from runbookai_tpu.model.guided import JsonMaskProvider
from runbookai_tpu.models import hf_loader
from runbookai_tpu.models.llama import LlamaConfig, init_params
from runbookai_tpu.utils import compile_cache
from runbookai_tpu.utils.tokens import ByteTokenizer


@pytest.fixture
def no_placed_cache(monkeypatch):
    """The entry points under test place the compile cache; put the
    process's environment back afterwards (tier-1 itself keeps no cache —
    conftest turns the persistent cache off)."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.delenv(compile_cache._MIN_SECS_VAR, raising=False)


# ------------------------------------------------------------- chip_smoke


def test_chip_smoke_refuses_without_a_tpu(no_placed_cache, capsys):
    """No TPU: non-zero exit before any work, and no result on stdout."""
    import chip_smoke

    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "not a TPU" in captured.err


def test_chip_smoke_rehearsal_end_to_end(no_placed_cache, tmp_path, capsys):
    """The smoke's own code path at llama3-test size: build_server from the
    checked-in config, every request kind over HTTP, /healthz runtime
    block, profiler trace, clean shutdown. It reports its platform and
    never carries the chip's pass ("ok")."""
    import chip_smoke

    rc = chip_smoke.main(["--rehearse-cpu", "--out", str(tmp_path)])
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1  # the report; the verdict line is a TPU run's
    result = json.loads(lines[-1])
    assert rc == 0, result["phases"]
    assert result["rehearsal_passed"] is True
    assert "ok" not in result
    assert result["device"]["platform"] == "cpu"
    assert result["model"] == "llama3-test"
    assert result["weight_dtype"] == "int8"
    assert result["allocator"] in ("native", "python")
    assert set(result["phases"]) == {
        "build", "plain", "widths", "burst", "guided", "repeat", "steady",
        "trace", "healthz", "shutdown"}
    assert result["phases"]["repeat"]["cached_tokens"] > 0
    assert result["phases"]["trace"]["file"].endswith(".xplane.pb")
    # The server stopped what it started (at the parent commit the
    # incident-monitor thread outlived OpenAIServer.shutdown()).
    alive = [t.name for t in threading.enumerate()
             if t.name.startswith(chip_smoke.SERVER_THREADS)]
    assert alive == []


def test_chip_smoke_verdict_has_the_drivers_keys_and_no_others():
    """The last line of a TPU run is read by the driver: exactly "ok" and
    "device" = {platform, kind, count}, the device as JAX reports it (PR 21
    was refused once for carrying the whole report on that line)."""
    import chip_smoke

    devices = jax.devices()
    line = json.loads(json.dumps(chip_smoke.verdict(True, devices)))
    assert line == {"ok": True,
                    "device": {"platform": devices[0].platform,
                               "kind": devices[0].device_kind,
                               "count": len(devices)}}
    assert chip_smoke.verdict(False, devices)["ok"] is False


# --------------------------------------- grammar mask narrower than vocab


def test_guided_request_on_vocab_wider_than_tokenizer():
    """Every full-width model without a tokenizer file serves with the
    262-id byte tokenizer; its grammar masks are 262 wide. At the parent
    commit the first guided token raised ``could not broadcast input
    array from shape (262,) into shape (1024,)`` out of step()."""
    cfg = LlamaConfig(name="wide-vocab-test", vocab_size=1024, dim=64,
                      n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128,
                      max_seq_len=256, rope_theta=10_000.0)
    tok = ByteTokenizer()
    assert tok.vocab_size < cfg.vocab_size
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    masker = JsonMaskProvider(tok)
    core = EngineCore(cfg, params, tok, EngineConfig(
        page_size=4, num_pages=128, max_batch_slots=2, prefill_chunk=16,
        max_seq_len=256, kv_dtype=jnp.float32),
        mask_fn=masker.mask, advance_fn=masker.advance)
    req = EngineRequest(
        prompt_ids=tok.encode("answer in json"),
        sampling=SamplingParams(temperature=0.0, max_new_tokens=48,
                                stop_token_ids=(), guided="json"))
    core.submit(req)
    core.run_until_idle()
    # Only ids the tokenizer can spell were admissible — first token
    # (prefill-side mask) and every later one (decode-side mask).
    assert req.all_out_ids and max(req.all_out_ids) < tok.vocab_size
    json.loads(core.output_for(req).text)


# ------------------------------------------------------------- the loader


def test_int8_random_init_never_holds_a_wide_copy(monkeypatch):
    """``load_or_init(..., quantize_int8=True)`` without a checkpoint
    samples int8 leaves directly. The parent built the whole model in
    bf16 and then quantized it — 15 GB for a 7B on a 16 GB chip."""
    def wide(*args, **kwargs):
        raise AssertionError("a full-width tree was built")

    monkeypatch.setattr("runbookai_tpu.models.llama.init_params", wide)
    monkeypatch.setattr("runbookai_tpu.models.quant.quantize_params", wide)
    cfg, params = hf_loader.load_or_init("qwen2-test", None,
                                         quantize_int8=True)
    assert cfg.name == "qwen2-test"
    layers = params["layers"]
    for leaf in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert layers[leaf]["q"].dtype == jnp.int8
        assert layers[leaf]["s"].dtype == jnp.float32
    assert params["embed"].dtype == jnp.bfloat16


def test_unknown_model_name_raises():
    """The parent served llama3-test under any name it did not know."""
    with pytest.raises(KeyError, match="no-such-model"):
        hf_loader.load_or_init("no-such-model", None)


# ------------------------------------------------------ the compile cache


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/placed/by/the/driver")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.ensure_compile_cache() == "/placed/by/the/driver"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_fixed_directory(no_placed_cache):
    import os

    path = compile_cache.ensure_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_compile_cache"
    assert os.environ[compile_cache.ENV_VAR] == path
    assert jax.config.jax_compilation_cache_dir == path
    # Fixed: a second call, or another process, lands on the same path.
    assert compile_cache.ensure_compile_cache() == path
