"""Host milliseconds per recorded grid point spent planning the campaign and
preparing its dispatches: the runner's ``plan_s`` (set-up and planning)
plus each dispatch's ``prep_s`` (grid-point tables, seed draws, re-padding,
stacking), as the program records them in its plan and dispatch spans
(``repro.obs.stages``).  Nothing, on a program without those fields.  Moves
``points_per_s``."""

KEYS = ("plan_s", "prep_s")


def read(ctx):
    secs = [sp[k] for spans in ctx["spans"] for sp in spans for k in KEYS
            if k in sp]
    if not secs or not ctx["points"]:
        return None
    return sum(secs) * 1e3 / ctx["points"]
