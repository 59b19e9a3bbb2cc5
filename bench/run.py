"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
(``bench/configs``: fabric, engine, loss recovery, the reference module
and the comparison's limits)
and a traffic file (``bench/traffic``: the campaign grid).  The harness
drives ``repro.sweep.run_campaign``, the code behind ``python -m repro.sweep
run``, as one closed-loop caller: one warm-up campaign (set-up), then
campaigns back to back for at least ``--seconds`` (the window, whole
campaigns from the first start to the last end), then the comparison of a
seeded sample of the window's grid points with the plain reference
(``bench/check.py``).  With ``--trace 1`` the window's last campaigns run
under the JAX profiler and the per-layer metrics
(``bench/metrics/<name>.py``) are read from them and reported instead of
the end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` are also the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, the run exits 2 and prints no
result (``--rehearse`` runs on any backend, for tests and rehearsals).
The persistent compile cache is ``bench/jax-cache`` in the checkout.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / "jax-cache"
# A traced run profiles the whole campaigns of the window's last this many
# seconds; the campaigns before them run untraced, the check of what
# tracing costs.  The profiler logs every operation of every slot of the
# loop engine, and a longer trace would not be read within the run's time
# limit.
TRACE_SECONDS = 5.0


def load_cell(name: str, spec: dict | None = None):
    """(cell, config, traffic, end-to-end metrics, per-layer metrics)."""
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    import check
    check.module(config)    # its reference module, before any set-up
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(ms):
        return [m for m in ms if name in m.get("workloads", [name])]
    return (cell, config, traffic, mine(spec["end_to_end"]),
            mine(spec["per_layer"]))


class Events:
    """Counts of JAX's persistent-cache and compile events."""

    def __init__(self):
        self.n = collections.Counter()

    def event(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            self.n[name.rsplit("_", 1)[-1]] += 1

    def duration(self, name, *_, **__):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n["compiles"] += 1


def run_campaign(campaign):
    """One campaign: (records, dispatch and fault spans, wall seconds)."""
    import jax
    from repro.obs.trace import TraceWriter
    from repro.sweep import run_campaign as _run
    tw = TraceWriter(None)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.campaign"):
        records, _ = _run(campaign, trace=tw,
                          compile_cache_dir=str(CACHE_DIR))
    return records, tw.spans, time.perf_counter() - t0


def failed_points(planned: int, records, spans) -> int:
    """Points without a record, or whose dispatch took the retry or
    degradation path (the path asked for did not run)."""
    sizes = {s["dispatch"]: s["n_points"] for s in spans
             if s["kind"] == "dispatch"}
    bad = {s["dispatch"] for s in spans
           if s["kind"] in ("error", "retry", "degrade")}
    return max(planned - len(records), sum(sizes.get(d, 0) for d in bad))


def read_metric(name: str, ctx: dict):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def measure(config: dict, traffic: dict, *, seed: int, seconds: float,
            trace: bool, end_to_end, per_layer, chips: int = 1,
            rehearse: bool = False, log=print) -> dict | None:
    """Set-up, window, comparison; the result object, or None when the
    device is not the one the cell needs."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import check
    import devtrace
    from grid import Grid

    events = Events()
    jax.monitoring.register_event_listener(events.event)
    jax.monitoring.register_event_duration_secs_listener(events.duration)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not rehearse and (device["platform"] != "tpu"
                         or device["count"] < chips):
        print(f"bench: needs {chips} TPU chip(s), JAX sees {device}",
              file=sys.stderr)
        return None

    grid = Grid(config, traffic, seed)
    records, spans, _ = run_campaign(grid.warmup())
    warm_failed = failed_points(grid.points_per_campaign, records, spans)
    setup_s = time.perf_counter() - T0
    setup_events = dict(events.n)
    log(f"setup: {setup_s:.3f}s; persistent cache hits "
        f"{setup_events.get('hits', 0)}, misses "
        f"{setup_events.get('misses', 0)}; compiles "
        f"{setup_events.get('compiles', 0)}; cache {CACHE_DIR}")

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    traced_from = None             # index of the first traced campaign
    done = []                      # (records, spans, wall s) per campaign
    planned = 0
    t_start = t_end = time.perf_counter()
    while True:
        if (trace and traced_from is None
                and t_end - t_start >= seconds - TRACE_SECONDS):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            span = jax.profiler.TraceAnnotation(devtrace.WINDOW)
            span.__enter__()
            traced_from = len(done)
        campaign = grid.next()
        planned += campaign.n_points
        done.append(run_campaign(campaign))
        t_end = time.perf_counter()
        if t_end - t_start >= seconds:
            break
    window_s = t_end - t_start
    reduced = None
    if trace:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        ev = devtrace.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        reduced = devtrace.reduce(ev)
    compiles = events.n["compiles"] - setup_events.get("compiles", 0)
    log(f"window: {window_s:.3f}s, {len(done)} campaigns, compiles in the "
        f"window {compiles}")

    records = [r for recs, _, _ in done for r in recs]
    failed = warm_failed + sum(
        failed_points(grid.points_per_campaign, recs, sp)
        for recs, sp, _ in done)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])
    device["memory_peak_bytes"] = int(peak)

    ctx = {"engine": config["engine"], "window_s": window_s,
           "points": len(records), "records": records,
           "spans": [sp for _, sp, _ in done],
           "campaign_s": [w for _, _, w in done], "setup_s": setup_s,
           "setup_events": setup_events, "trace": reduced}
    if trace:   # per-layer metrics read the traced campaigns only
        log(trace_overhead(reduced, done[traced_from:], done[:traced_from]))
        ctx["records"] = [r for recs, _, _ in done[traced_from:]
                          for r in recs]
        ctx["points"] = len(ctx["records"])
        ctx["spans"] = ctx["spans"][traced_from:]
    metrics = {}
    for m in (per_layer if trace else end_to_end):
        v = (_end_to_end(m["name"], ctx) if not trace
             else read_metric(m["name"], ctx))
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    t_check = time.perf_counter()
    checks = check.compare(config, traffic, records, seed, log=log)
    checks.append(("failed_points", failed, "<=", 0))
    log(f"comparison: {time.perf_counter() - t_check:.3f}s")
    ok = all((v <= lim) if op == "<=" else (v >= lim)
             for _, v, op, lim in checks)
    out = {"correct": bool(ok), "attempted": planned
           + grid.points_per_campaign, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {n: {"value": v, "limit": f"{op} {lim}"}
                     for n, v, op, lim in checks}
    for n, v, op, lim in checks:
        print(f"check {n}: {v!r} (limit {op} {lim})", file=sys.stderr)
    return out


def _work(campaigns) -> float:
    """Units of engine work: fused rows x slots run for loop dispatches,
    rows for fast ones."""
    return sum(sp["n_points"] * sp.get("slots_run", 1)
               for _, spans, _ in campaigns for sp in spans
               if sp["kind"] == "dispatch")


def trace_overhead(reduced: dict, traced, untraced) -> str:
    """What tracing cost, from the same run: the traced campaigns' device
    busy time and host time per unit of work against the untraced
    campaigns' dispatch wall time (host clock, up to the outputs on the
    host) and host time per unit.  Busy under tracing above the untraced
    wall time means the profiler slowed the device."""
    if not traced or not untraced:
        return "trace overhead: no untraced campaigns to compare"
    w_tr, w_un = _work(traced), _work(untraced)
    busy = reduced["busy_s"] / w_tr
    wall = sum(sp["wall_s"] for _, spans, _ in untraced for sp in spans
               if sp["kind"] == "dispatch") / w_un
    host_tr = sum(s for _, _, s in traced) / w_tr
    host_un = sum(s for _, _, s in untraced) / w_un
    return (f"trace overhead: traced device busy {busy * 1e6:.3f} us per "
            f"unit vs untraced dispatch wall {wall * 1e6:.3f} us "
            f"(ratio {busy / wall:.4f}); campaign wall traced "
            f"{host_tr * 1e6:.3f} vs untraced {host_un * 1e6:.3f} us per "
            f"unit (ratio {host_tr / host_un:.4f}); "
            f"{len(traced)} traced, {len(untraced)} untraced campaigns")


def _end_to_end(name: str, ctx: dict):
    import numpy as np
    if name == "setup_s":
        return ctx["setup_s"]
    if name == "points_per_s":
        return ctx["points"] / ctx["window_s"]
    if name == "point_p95_s":
        return float(np.percentile(ctx["campaign_s"], 95))
    raise ValueError(f"no end-to-end metric {name!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever backend JAX has (no result off a "
                    "TPU is a benchmark number)")
    args = ap.parse_args(argv)
    cell, config, traffic, e2e, per_layer = load_cell(args.workload)
    out = measure(config, traffic, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), end_to_end=e2e,
                  per_layer=per_layer, chips=cell["chips"],
                  rehearse=args.rehearse)
    if out is None:
        return 2
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
