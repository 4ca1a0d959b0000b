"""The Mamba-2 rule's recurrent step in the traced slice: its share of the
HBM roofline — the bytes a call must move for the slice's live rows (state
read and written, convolution tail, a row's inputs: ``kernels/ssm_step.py``)
over the peak bytes per second, over the mean device time of a call. A call
is one Mamba layer of one forward pass: the decode programs' passes and the
mixed steps, times the Mamba layers. The step reads and writes whole blocks
of slots (``ops/ssm.py`` ``ROW_BLOCK``), so a block that holds one live row
costs the block, and the convolution runs over every slot's tail: both keep
the share under live rows over slots touched. Nothing to read in a model
with no such layer, or from a program that has none."""

from benchmark.kernels import ssm_step as kernel
from benchmark.layer_metrics._common import decode_steps_traced, live_in_trace, matching

NAME, UNIT, LAYER = "ssm_decode_roofline", "%", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def sizes(run: dict) -> tuple[int, ...]:
    """(Mamba layers, slots, heads, head size, state size, conv channels, width)."""
    model = run["model"]
    heads, p, n = model["mamba_num_heads"], model["mamba_head_dim"], model["ssm_state_size"]
    return (model["hybrid_override_pattern"].count("M"), run["llm"]["max_batch_slots"],
            heads, p, n, heads * p + 2 * model["n_groups"] * n, model["conv_kernel"])


def read(run: dict):
    if "mamba_num_heads" not in run["model"] or run["peaks"] is None:
        return None
    layers, *rest = sizes(run)
    seconds = sum(s for name, _, s in matching(run, "ops", kernel.pools(layers, *rest))
                  if kernel.is_event(name, layers, *rest))
    passes, _ = decode_steps_traced(run)
    mixed = ((run["trace"] or {"modules": {}})["modules"].get("jit__mixed_step")
             or {"count": 0})["count"]
    calls = (passes + mixed) * layers
    live = live_in_trace(run)
    if not seconds or not calls or live is None:
        return None
    need = kernel.bytes_per_call(live[0], *rest[1:]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / (seconds / calls)
