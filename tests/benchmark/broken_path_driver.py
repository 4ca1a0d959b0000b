"""Drive a rehearsal run with the timed path broken underneath: every
token the decode loop fetches from the device is altered where it is
produced. ``correct`` has to come out false (tests/benchmark)."""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np  # noqa: E402

from benchmark import run  # noqa: E402
from runbookai_tpu.engine.engine import EngineCore  # noqa: E402

_fetch = EngineCore._fetch_tokens


def _altered(self, toks_dev):
    return np.array(_fetch(self, toks_dev)) ^ 1  # its neighbour in the vocabulary


EngineCore._fetch_tokens = _altered

if __name__ == "__main__":
    sys.exit(run.main())
