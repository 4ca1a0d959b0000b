"""Share of the traced slice in which no operation ran on the device:
1 - (union of the busy intervals on the device plane) / slice."""

NAME, UNIT, LAYER = "device_idle_share", "%", "device"
MOVES, SOURCE = "tpot_p50_ms", "device_trace"


def read(run: dict):
    t = run["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
