"""Share of the traced window in which no operation ran on the device:
1 - (union of the device plane's op intervals) / window (trace_reduce.py)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["device_planes"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
