"""Host milliseconds per recorded grid point spent turning a dispatch's
outputs into results and records: each dispatch's ``post_s``, ``retry_s``
(the fast engine's JSQ pad-overflow re-run) and ``record_s``, as the
program records them (``repro.obs.stages``).  Nothing, on a program
without those fields.  Moves ``points_per_s``."""

KEYS = ("post_s", "retry_s", "record_s")


def read(ctx):
    secs = [sp[k] for spans in ctx["spans"] for sp in spans for k in KEYS
            if k in sp]
    if not secs or not ctx["points"]:
        return None
    return sum(secs) * 1e3 / ctx["points"]
