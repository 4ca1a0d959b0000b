"""Real-weights discovery: the on-ramp from random-init to measured quality.

The benchmark and eval run random-init weights in the no-egress build
environment — identical compute, but the QUALITY axis (eval pass@1, speculation
acceptance) is meaningless until a real checkpoint is in play. VERDICT r4
next-round #3 asks for (a) automatic pickup of a real checkpoint the moment
one exists and (b) an explicit marker in every eval artifact until
then, so "quality: unmeasured" is stated rather than implied.

Protocol once weights exist (see docs/WEIGHTS.md for the full recipe):

    export RUNBOOK_WEIGHTS=/path/to/checkpoints   # dir of dirs, or one model
    runbook eval --live                           # pass@1 against threshold 0.7

``RUNBOOK_WEIGHTS`` may point at a single HF/orbax checkpoint directory or
at a parent directory containing one subdirectory per model config name.
Reference: scoring threshold from the reference's ``src/eval/scoring.ts``
(pass at total >= 0.7) and ``docs/INVESTIGATION_EVAL.md``.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "RUNBOOK_WEIGHTS"
QUALITY_UNMEASURED = "unmeasured (random weights)"


def discover_weights(model_name: Optional[str] = None,
                     configured: Optional[str] = None) -> Optional[str]:
    """Resolve a real-weights path, or None to random-init.

    An explicitly configured path (``llm.model_path`` in config) wins;
    otherwise ``$RUNBOOK_WEIGHTS`` is tried — first as a parent holding a
    ``<model_name>/`` subdirectory, then as the checkpoint dir itself.
    """
    if configured and Path(configured).exists():
        return str(configured)
    root = os.environ.get(ENV_VAR)
    if not root:
        return None
    p = Path(root)
    if model_name and (p / model_name).exists():
        return str(p / model_name)
    return str(p) if p.exists() else None


def quality_marker(weights_path: Optional[str]) -> str:
    """The honesty string carried in every eval artifact."""
    if weights_path:
        return f"real weights: {weights_path}"
    return QUALITY_UNMEASURED
