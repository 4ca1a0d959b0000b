"""Speculative rounds a ``_decode_spec`` dispatch ran on the device between
two token fetches, mean over the polled steps: the step records' ``spec``
field (``engine/flight_recorder.py`` ``OPTIONAL_STEP_FIELDS``). 8 where the
loop of rounds is on the device (``decode_steps``), 1 where every round pays
the host a round trip. A program without the field has nothing to read."""

NAME, UNIT, LAYER = "spec_rounds_per_dispatch", "rounds", "admission and batching"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    rounds = [s["spec"]["rounds"] for s in run["steps"] if s.get("spec")]
    return sum(rounds) / len(rounds) if rounds else None
