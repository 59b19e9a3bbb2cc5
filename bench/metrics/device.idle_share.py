"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / window, from the profiler
trace.  Moves ``points_per_s``: idle time is host work the device waits
on."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
