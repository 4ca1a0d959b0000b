"""The chunked Mamba-2 rule in the prefill and mixed programs of the traced
slice: its share of its roofline — the larger of its operations over the
peak bf16 rate and its bytes over the peak bytes per second
(``kernels/ssm_chunk.py``), for the prompt tokens a program of the slice
prefilled on average, over the mean device time of a call (one Mamba layer
of one such program). The rule runs its products in float32 at the highest
precision, several bf16 passes each, materialises a ``Q x Q`` decay mask a
head, and a mixed step lays every prefill row out as a run of the whole
chunk budget: all keep the share low, and all are the next kernel's to
take. Nothing to read in a model with no such layer."""

from benchmark.kernels import ssm_chunk as kernel
from benchmark.layer_metrics._common import matching, traced_delta

NAME, UNIT, LAYER = "ssm_chunk_roofline", "%", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"
PROGRAMS = ("jit__prefill_step", "jit__mixed_step")


def read(run: dict):
    model = run["model"]
    if "mamba_num_heads" not in model or run["peaks"] is None:
        return None
    heads, p, n = model["mamba_num_heads"], model["mamba_head_dim"], model["ssm_state_size"]
    groups, chunk = model["n_groups"], model["chunk_size"]
    conv = heads * p + 2 * groups * n
    sizes = (run["llm"]["max_batch_slots"], heads, p, n, groups, chunk, conv,
             model["conv_kernel"])
    seconds = sum(s for name, _, s in matching(run, "ops", kernel.pattern(*sizes))
                  if kernel.is_event(name, *sizes))
    modules = (run["trace"] or {"modules": {}})["modules"]
    programs = sum(modules[prog]["count"] for prog in PROGRAMS if prog in modules)
    tokens = traced_delta(run, "prefill_tokens")
    if not seconds or not programs or not tokens:
        return None
    layers = model["hybrid_override_pattern"].count("M")
    per_program = tokens / programs
    need = max(kernel.ops_per_call(per_program, heads, p, n, groups, chunk)
               / run["peaks"]["bf16_flops"],
               kernel.bytes_per_call(per_program, 1.0, heads, p, n, conv)
               / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need / (seconds / (programs * layers))
