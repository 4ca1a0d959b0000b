"""The KV pages a call of the decode attention kernel walks: the mean, over
the window's decode dispatches (``_decode_multi``, ``_decode_step``), of the
step records' ``kv_pages_live`` — Σ cdiv(context, page size) over the rows
the dispatch was given, as the engine counted them when it issued it (a
window adds a position a pass, so its later passes walk at most a page a
row more). The kernel's time a call (``attn_decode_roofline``'s) over this
is its time a page: it tells a faster kernel from a slice that held fewer
rows. A program without the field (the parent of the PR that added it)
gives None."""

from benchmark.layer_metrics import _steps

NAME, UNIT, LAYER = "attn_live_pages_per_call", "pages", "kernels"
MOVES, SOURCE = "tpot_p50_ms", "program_counter"

DECODE_PROGRAMS = {"_decode_multi", "_decode_step"}


def read(run: dict):
    pages = [s["kv_pages_live"] for s in _steps.window_steps(run)
             if "kv_pages_live" in s and DECODE_PROGRAMS & set(s["program"])]
    return sum(pages) / len(pages) if pages else None
