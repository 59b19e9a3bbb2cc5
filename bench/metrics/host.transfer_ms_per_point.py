"""Host milliseconds per recorded grid point spent copying the dispatches'
outputs back to the host: each dispatch's ``fetch_s``, as the program
records it (``repro.obs.stages``).  The operands go to the device inside
the jitted call, so their copy counts in ``execute_s``, which no host
metric reads.  Nothing, on a program without the field.  Moves
``points_per_s``."""

KEYS = ("fetch_s",)


def read(ctx):
    secs = [sp[k] for spans in ctx["spans"] for sp in spans for k in KEYS
            if k in sp]
    if not secs or not ctx["points"]:
        return None
    return sum(secs) * 1e3 / ctx["points"]
