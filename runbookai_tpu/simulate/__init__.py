"""Incident + traffic simulators.

Incident half (``generator.py``): generated fault scenarios for the
fixture providers. Reference parity:
``scripts/simulate/setup-incidents.sh`` provisions real broken
infrastructure (a failing Lambda + forced CloudWatch alarm, optional
live PagerDuty incident) so investigations run against something the
agent has never seen (``docs/SIMULATE_INCIDENTS.md``). This repo's
equivalent is credential-free and TPU-CI-friendly: a seeded generator
perturbs the simulated-provider fixtures (``tools/simulated.py``) into
NOVEL failure states — random topology, random root cause,
fault-specific telemetry — so every e2e investigation faces an incident
that exists in no checked-in fixture, with machine-checkable ground
truth for the eval suite.

Traffic half (``traffic.py``): the seeded serving-workload scenario mix
(short chat, agentic chains, batch floods, shared-prefix sessions,
spiky tenants) the chaos soak gate drives through the full composed
stack — ``chaos/soak.py`` (docs/robustness.md).
"""

from runbookai_tpu.simulate.generator import (
    ADVERSARIAL_MODES,
    FAULT_TYPES,
    Scenario,
    generate_scenario,
    generate_scenarios,
    to_eval_case,
)
from runbookai_tpu.simulate.traffic import (
    SCENARIO_CLASSES,
    TrafficChain,
    TrafficMix,
    TrafficTurn,
    generate_traffic,
)

__all__ = [
    "ADVERSARIAL_MODES",
    "FAULT_TYPES",
    "SCENARIO_CLASSES",
    "Scenario",
    "TrafficChain",
    "TrafficMix",
    "TrafficTurn",
    "generate_scenario",
    "generate_scenarios",
    "generate_traffic",
    "to_eval_case",
]
