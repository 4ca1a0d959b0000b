"""What several readers share: the live rows and tokens of the traced slice."""

from __future__ import annotations

from benchmark import metrics


def delta(run: dict, key: str) -> float:
    """A ``/healthz`` counter's growth over the window."""
    return (run["health_after"]["metrics"].get(key, 0)
            - run["health_before"]["metrics"].get(key, 0))


def traced_delta(run: dict, key: str) -> float | None:
    t = run["traced"]
    if "health_stop" not in t:
        return None
    return t["health_stop"]["metrics"].get(key, 0) - t["health_start"]["metrics"].get(key, 0)


def live_in_trace(run: dict) -> tuple[float, float] | None:
    """(rows, tokens): requests decoding at the middle of the traced slice
    as the client saw them — between first and last streamed token — and
    the tokens of their contexts, counted as prompt tokens only (a lower
    bound of the live context: what was generated is left out)."""
    t = run["traced"]
    if "t_stop" not in t:
        return None
    mid = (t["t_start"] + t["t_stop"]) / 2
    live = [r for r in run["reqs"] if metrics.failure(r) is None and r["times"]
            and r["times"][0] <= mid <= r["times"][-1]]
    if not live:
        return None
    return float(len(live)), float(sum(r["prompt_tokens"] for r in live))


def decode_steps_traced(run: dict) -> tuple[int, float]:
    """(decode steps, their device seconds) of the traced slice: the pure
    decode programs on the "XLA Modules" line, times the steps each runs."""
    from benchmark.kernels import decode_step

    steps, seconds = 0, 0.0
    for name, k in decode_step.PROGRAMS.items():
        t = (run["trace"] or {"modules": {}})["modules"].get(name)
        if t:
            steps += t["count"] * (k or run["llm"]["decode_steps"])
            seconds += t["seconds"]
    return steps, seconds


def events_matching(run: dict, line: str, pattern) -> tuple[int, float]:
    """(count, seconds) of the trace's events on ``line`` ("ops"/"modules")
    whose text matches ``pattern`` (an operation's text is its HLO
    instruction, layouts removed: anchor the pattern on its own name)."""
    n, s = 0, 0.0
    for _, count, seconds in matching(run, line, pattern):
        n += count
        s += seconds
    return n, s


def matching(run: dict, line: str, pattern) -> list[tuple[str, int, float]]:
    if run["trace"] is None:
        return []
    return [(name, t["count"], t["seconds"])
            for name, t in run["trace"][line].items() if pattern.search(name)]


def load_metric_file(path):
    """A metric file as a module, by path (a name may hold dots)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
