"""Device microseconds per row-slot in the loop engine's executables
(``jax.jit`` of its fused ``while_loop``): their device time from the
profiler trace over the sum, over dispatches, of fused rows x slots the
loop ran.  Moves ``points_per_s``."""

ENGINE = "jit_fn"


def read(ctx):
    tr = ctx["trace"]
    if not tr or ctx["engine"] != "loop":
        return None
    s = sum(v for n, v in tr["module_s"].items() if ENGINE in n)
    row_slots = sum(sp["n_points"] * sp["slots_run"]
                    for spans in ctx["spans"] for sp in spans
                    if sp["kind"] == "dispatch")
    return s * 1e6 / row_slots if s > 0 and row_slots else None
