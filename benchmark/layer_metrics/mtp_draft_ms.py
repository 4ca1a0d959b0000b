"""Device time a round spends in the prediction module and its head, mean
over the rounds of the traced slice: on the "XLA Ops" line, from the start
of the product with the module's projection ``Wp`` — the one fusion with a
``[1, 2 x hidden, hidden]`` operand beside a round's ``[rows, 2, hidden]``
activations (the weight's prefetch, a ``copy-start`` early in the round,
names the same shape and is no fusion; the mixed step's product has other
rows) — to the end of the next fusion that reads the head ``[hidden,
vocabulary]``, which is the module's own (logits and the draft's argmax in
one fusion; the trunk's head ran before ``Wp``). Between the two lie the module's layer: latent attention over its
own cache layer, router, held and shared experts. A round's operations run
one after another on the one core, so the interval is the module's time;
its embedding gather and two norms ahead of ``Wp`` (microseconds) are left
out. The reduced trace keeps totals by name, not order, so this reader
opens the run's trace file itself. Nothing to read in a model without the
module, or in a slice with no round in it."""

import re

from benchmark import serving, trace_reduce

NAME, UNIT, LAYER = "mtp_draft_ms", "ms", "model step"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def intervals(events, rows: int, hidden: int, vocab: int) -> list[float]:
    """Seconds from each round's ``Wp`` product's start to the end of the
    head product after it, over (name, start_s, end_s) events in start order."""
    weight = re.compile(rf" fusion\(.*bf16\[1,{2 * hidden},{hidden}\]")
    fed = re.compile(rf" fusion\(.*bf16\[{rows},2,{hidden}\]")
    head = re.compile(rf" fusion\(.*\w+\[{hidden},{vocab}\]")
    out, t_open = [], None
    for name, start, end in events:
        if t_open is None and weight.search(name) and fed.search(name):
            t_open = start
        elif t_open is not None and head.search(name):
            out.append(end - t_open)
            t_open = None
    return out


def read(run: dict):
    model = run["model"]
    if run["trace"] is None or not model.get("num_nextn_predict_layers"):
        return None
    xplane = trace_reduce.newest_xplane(serving.RUN_DIR / "trace")
    if xplane is None:
        return None
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(str(xplane)).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                took = intervals(trace_reduce._events(line), run["llm"]["max_batch_slots"],
                                 model["hidden_size"], model["vocab_size"])
                return 1e3 * sum(took) / len(took) if took else None
    return None
