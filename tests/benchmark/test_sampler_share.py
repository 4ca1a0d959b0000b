"""``sampler_sorted_call_share``: the reader of the ``/healthz`` counters
``sampler_calls`` / ``sampler_sorted_calls`` (``benchmark/layer_metrics/``),
by hand, without its input, as ``BENCHMARK.json`` declares it, and on one
CPU rehearsal of the dense cell.

No TPU topology is described here, at import or later.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
NAME = "sampler_sorted_call_share"

sys.path.insert(0, str(ROOT))
from benchmark.layer_metrics._common import load_metric_file  # noqa: E402


def reader():
    return load_metric_file(ROOT / "benchmark" / "layer_metrics" / f"{NAME}.py")


def run_of(before, after):
    return {"health_before": {"metrics": before}, "health_after": {"metrics": after}}


@pytest.mark.parametrize("before,after,want", [
    ({"sampler_calls": 40, "sampler_sorted_calls": 0},
     {"sampler_calls": 2_440, "sampler_sorted_calls": 0}, 0.0),  # every cell's traffic
    ({"sampler_calls": 40, "sampler_sorted_calls": 8},
     {"sampler_calls": 440, "sampler_sorted_calls": 108}, 25.0),  # of the WINDOW's calls
    ({"sampler_calls": 0}, {"sampler_calls": 16, "sampler_sorted_calls": 16}, 100.0),
], ids=["all_greedy", "a_quarter", "every_call"])
def test_the_share_is_of_the_windows_calls(before, after, want):
    assert reader().read(run_of(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({}, {}),  # the parent: a program without the counters
    ({"decode_tokens": 5}, {"decode_tokens": 905}),
    ({"sampler_calls": 7, "sampler_sorted_calls": 0},
     {"sampler_calls": 7, "sampler_sorted_calls": 0}),  # no call in the window
], ids=["no_counters", "other_counters", "no_call"])
def test_without_a_counted_call_it_reads_nothing(before, after):
    assert reader().read(run_of(before, after)) is None


def test_it_is_declared_as_the_file_says():
    """Found by NAME: what a later PR appends behind it is no fault of this
    entry's."""
    entry = {m["name"]: m for m in BENCH["per_layer"]}[NAME]
    mod = reader()
    assert entry == {"name": mod.NAME, "unit": mod.UNIT, "better": "lower",
                     "source": mod.SOURCE, "layer": mod.LAYER, "moves": mod.MOVES}
    # No ``workloads``: every cell reports ``tpot_p50_ms`` and calls the sampler.
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_a_cpu_rehearsal_reads_zero(tmp_path):
    """The dense cell's traffic is temperature 0: every call of its window
    took the argmax. (In a copy of the benchmark's files: a run keeps its
    plans and records under its own root, and other files' rehearsals run
    beside this one in other workers.)"""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "qwen7b.chat-open",
         "--seed", "4000000007", "--seconds", "3", "--trace", "1", "--rehearse-cpu"],
        cwd=tmp_path, env=ENV, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0
    assert line["metrics"][NAME] == {"value": 0.0, "unit": "%"}
