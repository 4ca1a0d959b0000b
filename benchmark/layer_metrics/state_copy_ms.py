"""What the snapshot mechanism costs the device: milliseconds a second of
the traced slice spent in the programs that copy recurrent state between
the slot pool and the snapshot pool — ``jit__state_admit`` (a slot zeroed or
restored at admission; a fork of a sequence is a restore into another slot)
and ``jit__state_snapshot`` (a boundary kept) on the "XLA Modules" line. 0
where the slice held none; nothing to read from a program that has no such
state (no ``state_*`` counter in ``/healthz``)."""

NAME, UNIT, LAYER = "state_copy_ms", "ms", "KV manager"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"
PROGRAMS = ("jit__state_admit", "jit__state_snapshot")


def read(run: dict):
    t = run["traced"]
    if (run["trace"] is None or "t_stop" not in t
            or "state_snapshots_taken" not in run["health_after"]["metrics"]
            or not run["runtime"].get("state_pool_bytes")):
        return None
    seconds = sum(run["trace"]["modules"].get(p, {"seconds": 0.0})["seconds"] for p in PROGRAMS)
    return 1e3 * seconds / (t["t_stop"] - t["t_start"])
