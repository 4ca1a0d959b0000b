"""The model families. Importing any ``runbookai_tpu.models.*`` imports the
six family files, in this order, so the registry (``family.CONFIGS``) is
whole whichever of them is asked for: a family is one file and one line here
(``family.Family``'s docstring is the contract).
"""

from runbookai_tpu.models import (  # noqa: F401, I001 — the registry's order
    llama,
    longcat,
    qwen3_next,
    joyai,
    nemotron_h,
    afmoe,
)
