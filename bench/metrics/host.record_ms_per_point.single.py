"""Host milliseconds per single-point campaign spent turning its outputs into
a result and a record: the dispatch's ``post_s``, ``retry_s`` and
``record_s``, as the program records them (``repro.obs.stages``).
Nothing, on a program without those fields.  Moves ``point_p95_s``."""

KEYS = ("post_s", "retry_s", "record_s")


def read(ctx):
    secs = [sp[k] for spans in ctx["spans"] for sp in spans for k in KEYS
            if k in sp]
    if not secs or not ctx["points"]:
        return None
    return sum(secs) * 1e3 / ctx["points"]
