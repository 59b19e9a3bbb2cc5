"""Device milliseconds per single-point campaign spent in the fast
engine's executables (``jax.jit`` of its ``pipeline``), from the profiler
trace.  Moves ``point_p95_s``."""

ENGINE = "pipeline"


def read(ctx):
    tr = ctx["trace"]
    if not tr or ctx["engine"] != "fast" or not ctx["points"]:
        return None
    s = sum(v for n, v in tr["module_s"].items() if ENGINE in n)
    return s * 1e3 / ctx["points"] if s > 0 else None
