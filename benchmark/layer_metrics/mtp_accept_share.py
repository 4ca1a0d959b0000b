"""Of the drafts the model's own prediction module made and a round
verified, the share whose second token was served: the growth of
``spec_accepted`` over that of ``spec_drafted`` (``/healthz``) across the
window. On seeded weights a draft is right at chance (one in the
vocabulary), so this reads 0 or next to it for every seed; it is reported
so that a reading on a checkpoint, or a fault that accepts what it should
not, shows. Nothing to read where nothing was drafted."""

from benchmark.layer_metrics._common import delta

NAME, UNIT, LAYER = "mtp_accept_share", "%", "model step"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"


def read(run: dict):
    drafted = delta(run, "spec_drafted")
    return 100.0 * delta(run, "spec_accepted") / drafted if drafted else None
