"""Host milliseconds per single-point campaign: (traced window - device
busy time) / points.  The runner's host path (planning, per-point
preparation, transfer, record conversion) that every lone point pays.
Moves ``point_p95_s``."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["points"] or tr["busy_s"] <= 0:
        return None
    return (tr["window_s"] - tr["busy_s"]) * 1e3 / ctx["points"]
