"""What the expert-counter readers share: the step records' ``experts``
field (``engine/flight_recorder.py`` ``OPTIONAL_STEP_FIELDS``) — a model
with an expert share reports, in the record of the step that fetched them,
the token-expert pairs of its live tokens that fell on held, identity
(``zero``) and absent experts, the held experts touched, the expert layers
that took the slow path (``overflow``), and the forward passes and programs
they came from. A program without the field (the
parent of the PR that added these readers; a dense model) has none, and
every reader here then returns None."""

from __future__ import annotations


def records(run: dict) -> list[dict]:
    return [s["experts"] for s in run["steps"] if s.get("experts")]
