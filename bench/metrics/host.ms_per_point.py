"""Host milliseconds per recorded grid point: (traced window - device busy
time) / points.  The runner's host path (planning, per-point preparation,
transfer, record conversion) plus whatever else keeps the device idle.
Moves ``points_per_s``."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["points"] or tr["busy_s"] <= 0:
        return None
    return (tr["window_s"] - tr["busy_s"]) * 1e3 / ctx["points"]
