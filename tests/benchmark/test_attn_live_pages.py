"""``attn_live_pages_per_call``: the reader of the step records'
``kv_pages_live`` (``benchmark/layer_metrics/``), by hand and without its
input."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmark.layer_metrics._common import load_metric_file

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "attn_live_pages_per_call"


def reader():
    return load_metric_file(ROOT / "benchmark" / "layer_metrics" / f"{NAME}.py")


def step(number, t_start, t_end, program, pages=None):
    s = {"step": number, "t_start": t_start, "t_end": t_end,
         "wall_s": t_end - t_start, "phases": {"fetch": 0.0},
         "program": list(program), "rows": 2 if program else 0}
    if pages is not None:
        s["kv_pages_live"] = pages
    return s


def run_of(steps):
    return {"t0": 100.0, "seconds": 10.0, "steps": steps, "trace": None}


def test_the_mean_is_over_the_windows_decode_dispatches():
    steps = [
        step(0, 99.0, 99.9, ["_decode_multi"], 1000),  # before the window
        step(1, 100.0, 100.4, ["_decode_multi"], 160),
        step(2, 100.4, 100.9, ["_mixed_step"], 900),  # the chunk kernel's
        step(3, 100.9, 101.0, [], 0),  # idle
        step(4, 101.0, 101.5, ["_prefill_step", "_decode_step"], 190),
        step(5, 101.5, 102.0, ["_decode_multi"], 220),
        step(6, 109.9, 110.5, ["_decode_multi"], 5000),  # ends in the drain
    ]
    assert reader().read(run_of(steps)) == pytest.approx((160 + 190 + 220) / 3)


@pytest.mark.parametrize("steps", [
    [],
    [step(1, 100.0, 100.4, ["_decode_multi"])],  # the parent: no field
    [step(1, 100.0, 100.4, ["_mixed_step"], 900)],  # no decode dispatch
    [{"step": 3, "ts": 1.0, "kind": "decode", "batch": 2, "wall_s": 0.4}],
])
def test_without_its_input_it_reads_nothing(steps):
    assert reader().read(run_of(steps)) is None


def test_it_is_declared_as_the_issue_gave_it():
    entry = {m["name"]: m for m in BENCH["per_layer"]}[NAME]
    mod = reader()
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        NAME, entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert entry == {"name": NAME, "unit": "pages", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "tpot_p50_ms", "workloads": ["qwen7b.chat-open"]}
    # appended when it came (PR 31 found it last; later PRs appended theirs):
    # once, and with nothing of its layer or its cell moved by it
    assert [m["name"] for m in BENCH["per_layer"]].count(NAME) == 1
    assert "kernels" in {m["layer"] for m in BENCH["per_layer"] if m["name"] != NAME}
