"""Host milliseconds per single-point campaign spent planning it and
preparing its dispatch: the runner's ``plan_s`` plus the dispatch's
``prep_s``, as the program records them (``repro.obs.stages``).  Nothing,
on a program without those fields.  Moves ``point_p95_s``."""

KEYS = ("plan_s", "prep_s")


def read(ctx):
    secs = [sp[k] for spans in ctx["spans"] for sp in spans for k in KEYS
            if k in sp]
    if not secs or not ctx["points"]:
        return None
    return sum(secs) * 1e3 / ctx["points"]
