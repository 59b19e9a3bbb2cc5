"""Bring-up smoke run of the campaign engine on a TPU, at the paper's scale.

    python chip_smoke.py                # one TPU chip (the default run)
    python chip_smoke.py --four-chips   # a 4-chip host: the sharded path only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --k 4   # CPU rehearsal

Everything runs in this one process, through ``repro.sweep.run_campaign``
(the code behind ``python -m repro.sweep run``).  One-chip phases, in order:

  fast       the ``table2`` preset at k=8: 7 schemes x {permutation of 256
             packets, all-to-all of 8 packets} x 4 seeds (fast engine);
  loop       the ``fig12`` preset at k=8: SACK loss recovery, 5 schemes x 2
             seeds, permutation of 256 packets (loop engine);
  kernels    ``fig12`` again with the compiled slot-step kernels
             (``impl="pallas"``), and the ``table2`` permutation rows of one
             fused shape with the compiled Lindley scan
             (``backend="pallas"``): records bitwise-equal to the XLA runs;
  reference  serial per-point ``simulate`` on the chip for two compiled
             shapes (the fast engine's JSQ pipeline, with its pad-overflow
             retry, and the loop engine's host-spray one), bitwise-equal to
             the fused points; and the same points on this process's CPU
             backend: integers equal, floats within ``FLOAT_TOL``.

Each campaign phase asserts one record per planned point and no
error/degrade/retry span, and prints cold and warm wall seconds (a warm run
ends when its outputs are on the host), the dispatch count and the
in-process compile-cache hits.  These are bring-up observations, not
benchmark numbers.  ``--four-chips`` runs ``fig12`` and the ``table2``
permutation rows of ``switch_pkt_ar`` (the fast engine's JSQ shape)
sharded over four chips (``shard="auto"``) against ``shard="off"``, and
checks that each dispatch's outputs span the devices it was sharded over.
Every fast-engine shape costs over a minute of TPU compile per sharding,
so the cold one-chip run spends most of its ~15 minutes compiling, and the
four-chip run takes one fast-engine shape, not all eight.

Any failure exits non-zero.  On success the last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/jax-cache``; no XLA or libtpu flag is set here.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import os
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
# The reference phase compares against this process's CPU backend, so keep
# it loaded when the platform list is pinned (the TPU stays the default).
if os.environ.get("JAX_PLATFORMS") and "cpu" not in os.environ[
        "JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"

# CPU vs TPU: float fields of a serial point agree within this (slots or
# packets); integer fields must be equal.
FLOAT_TOL = dict(rtol=1e-6, atol=1e-3)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


# Persistent compile-cache events (jax.monitoring), counted per run.
EVENTS = collections.Counter()


def _count_event(name, **_):
    if name.startswith("/jax/compilation_cache/cache_"):
        EVENTS[name.rsplit("_", 1)[-1]] += 1


# ---------------------------------------------------------------------------
# Campaign runs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    records: list
    full: dict
    secs: float
    n_dispatches: int
    cache_hits: int
    persistent: dict
    spans: list


def run(campaign, keep_full=False):
    """One ``run_campaign`` call, checked for lost points and degraded or
    retried dispatches."""
    from repro.obs.trace import TraceWriter
    from repro.sweep import compile_cache, plan, run_campaign
    trace = TraceWriter(None)
    events0 = dict(EVENTS)
    t0 = time.perf_counter()
    records, full = run_campaign(campaign, trace=trace, keep_full=keep_full,
                                 compile_cache_dir=compile_cache.DEFAULT_DIR)
    secs = time.perf_counter() - t0
    p = plan(campaign)
    check(len(records) == p.n_points,
          f"{campaign.name}: {len(records)} records for {p.n_points} points")
    bad = [s for s in trace.spans if s["kind"] in ("error", "degrade",
                                                   "retry")]
    check(not bad, f"{campaign.name}: {bad}")
    dispatch = [s for s in trace.spans if s["kind"] == "dispatch"]
    persistent = {k: v - events0.get(k, 0) for k, v in EVENTS.items()}
    return Run(records, full, secs, p.n_dispatches,
               sum(s["cache"] == "hit" for s in dispatch), persistent,
               dispatch)


def cold_warm(name, campaign, keep_full=False):
    cold = run(campaign, keep_full=keep_full)
    warm = run(campaign)
    check(warm.records == cold.records, f"{name}: warm records differ")
    say(f"[{name}] {campaign.name}: {len(cold.records)} points, "
        f"{cold.n_dispatches} dispatches, cold {cold.secs:.3f}s "
        f"({cold.cache_hits} in-process cache hits, persistent cache "
        f"{cold.persistent}), warm {warm.secs:.3f}s "
        f"({warm.cache_hits} hits)")
    return cold


def same_records(name, got, want):
    check(len(got) == len(want), f"{name}: {len(got)} vs {len(want)} records")
    diff = [(g, w) for g, w in zip(got, want) if g != w]
    for g, w in diff[:3]:
        keys = sorted(k for k in set(g) | set(w) if g.get(k) != w.get(k))
        say(f"[{name}] {g['scheme']} seed {g['seed']}: "
            + ", ".join(f"{k} {g.get(k)!r} vs {w.get(k)!r}" for k in keys))
    check(not diff, f"{name}: {len(diff)} of {len(got)} records differ")


# ---------------------------------------------------------------------------
# Result comparison
# ---------------------------------------------------------------------------

def flatten(res, prefix=""):
    """A result dataclass as {field path: numpy array}."""
    import numpy as np
    out = {}
    items = (res.items() if isinstance(res, dict)
             else ((f.name, getattr(res, f.name))
                   for f in dataclasses.fields(res)))
    for key, val in items:
        path = f"{prefix}{key}"
        if val is None:
            continue
        if isinstance(val, dict) or dataclasses.is_dataclass(val):
            out.update(flatten(val, path + "."))
        else:
            out[path] = np.asarray(val)
    return out


def bitwise_diffs(a, b):
    import numpy as np
    fa, fb = flatten(a), flatten(b)
    return sorted(k for k in set(fa) | set(fb)
                  if k not in fa or k not in fb or fa[k].shape != fb[k].shape
                  or not np.array_equal(fa[k], fb[k], equal_nan=True))


def tolerance_diffs(name, tpu, cpu):
    """Integer fields exact, float fields within FLOAT_TOL; every
    difference is printed."""
    import numpy as np
    ft, fc = flatten(tpu), flatten(cpu)
    bad = []
    for k in sorted(set(ft) | set(fc)):
        t, c = ft.get(k), fc.get(k)
        if t is None or c is None or t.shape != c.shape:
            bad.append(k)
            say(f"[{name}] {k}: present/shape differs")
            continue
        if np.array_equal(t, c, equal_nan=True):
            continue
        if np.issubdtype(t.dtype, np.floating):
            err = np.max(np.abs(t.astype(np.float64) - c.astype(np.float64)))
            ok = np.allclose(t, c, equal_nan=True, **FLOAT_TOL)
            say(f"[{name}] {k}: float difference, max |tpu-cpu| = {err!r} "
                f"({'within' if ok else 'OUTSIDE'} tolerance)")
            if not ok:
                bad.append(k)
        else:
            n = int(np.sum(t != c))
            say(f"[{name}] {k}: {n} integer entries differ")
            bad.append(k)
    return bad


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_fast(ctx):
    ctx["table2"] = cold_warm("fast", ctx["table2_c"], keep_full=True)


def phase_loop(ctx):
    ctx["fig12"] = cold_warm("loop", ctx["fig12_c"], keep_full=True)


def phase_kernels(ctx):
    from repro.kernels import _common
    say(f"[kernels] Pallas interpret mode: {_common.use_interpret()}")
    f12 = ctx["fig12_c"]
    c = dataclasses.replace(f12, loop_opts=tuple(sorted(
        {**dict(f12.loop_opts), "impl": "pallas"}.items())))
    pal = cold_warm("kernels", c)
    check(all(s["impl"] == "pallas" for s in pal.spans),
          "impl=pallas dispatches did not run the kernels")
    same_records("kernels/slot_step", pal.records, ctx["fig12"].records)
    t2 = ctx["table2_c"]
    c = dataclasses.replace(t2, backend="pallas", loads=t2.loads[:1],
                            schemes=("flow_ecmp", "subflow_mptcp",
                                     "host_pkt", "host_dr"))
    pal = cold_warm("kernels", c)
    want = [r for r in ctx["table2"].records
            if r["workload"] == t2.loads[0].label()
            and r["scheme"] in c.schemes]
    same_records("kernels/lindley", pal.records, want)


def phase_reference(ctx):
    """Serial per-point simulation on the chip == the fused point; the same
    points on the CPU backend within FLOAT_TOL."""
    import jax
    from repro.core import lb_schemes as lbs
    from repro.net import fastsim, loopsim
    from repro.net.topology import FatTree
    from repro.sweep.runner import build_workload
    cpu = jax.devices("cpu")[0]
    bad = []
    for engine, scheme in (("fast", "switch_pkt_ar"), ("loop", "host_pkt")):
        c = ctx["table2_c" if engine == "fast" else "fig12_c"]
        full = ctx["table2" if engine == "fast" else "fig12"].full
        point = next(p for p in full if p.scheme == scheme and p.seed == 0
                     and p.load == c.loads[0])
        tree = FatTree(point.k)
        wl = build_workload(tree, point.load)
        sch = lbs.by_name(scheme)
        if engine == "fast":
            def serial():
                return fastsim.simulate(tree, wl, sch, seed=point.seed,
                                        prop_slots=c.prop_slots,
                                        backend=c.backend)
        else:
            def serial():
                return loopsim.simulate(tree, wl, sch, c.loop_config(),
                                        seed=point.seed)
        name = f"reference/{engine}/{scheme}"
        t0 = time.perf_counter()
        chip = serial()
        secs = time.perf_counter() - t0
        diffs = bitwise_diffs(chip, full[point])
        say(f"[{name}] serial {secs:.3f}s on {jax.devices()[0].platform}; "
            f"fields differing from the fused point: {diffs or 'none'}")
        if diffs:
            bad.append(name)
        with jax.default_device(cpu):
            host = serial()
        off = tolerance_diffs(name, chip, host)
        say(f"[{name}] vs CPU backend: "
            f"{'all fields equal' if not off else f'{len(off)} fields off'}"
            f" (float tolerance {FLOAT_TOL})")
        if off:
            bad.append(name + "/cpu")
    check(not bad, f"reference mismatches: {bad}")


@contextlib.contextmanager
def output_devices():
    """Yields a list that collects, for each fused ("mega") dispatch of
    either engine in dispatch order, the ids of the devices its outputs
    live on."""
    import jax
    from repro.net import fastsim, loopsim
    seen = []

    def devices(out):
        seen.append({d.id for leaf in jax.tree_util.tree_leaves(out)
                     for d in leaf.sharding.device_set})
        return out

    loop_run, fast_build = loopsim._run, fastsim._build_run

    def spy_loop(static, tables, batch=False, n_shards=1):
        out = loop_run(static, tables, batch, n_shards)
        return devices(out) if batch == "mega" else out

    def spy_build(**kw):
        run_fn = fast_build(**kw)
        if kw["batch"] != "mega":
            return run_fn
        return lambda args: devices(run_fn(args))

    spy_build.cache_info = fast_build.cache_info
    loopsim._run, fastsim._build_run = spy_loop, spy_build
    try:
        yield seen
    finally:
        loopsim._run, fastsim._build_run = loop_run, fast_build


def phase_four_chips(ctx):
    """Sharded == unsharded, with each sharded dispatch's outputs spread
    over the devices it was split over: ``fig12`` (loop engine) and
    ``table2``'s permutation rows of ``switch_pkt_ar`` (the fast engine's
    JSQ shape, whose sharded dispatch needs ``check_vma=False``)."""
    import jax
    t2 = ctx["table2_c"]
    fast = dataclasses.replace(t2, loads=t2.loads[:1],
                               schemes=("switch_pkt_ar",))
    for c in (ctx["fig12_c"], fast):
        with output_devices() as seen:
            on = run(dataclasses.replace(c, shard="auto"))
        off = run(dataclasses.replace(c, shard="off"))
        shards = [s["n_shards"] for s in on.spans]
        say(f"[four-chips] {c.name} ({c.engine}): {len(on.records)} points, "
            f"sharded {on.secs:.3f}s over {shards} devices per dispatch "
            f"(outputs on {[sorted(d) for d in seen]}), unsharded "
            f"{off.secs:.3f}s")
        check([len(d) for d in seen] == shards,
              f"{c.name}: outputs on {seen}, expected {shards} devices")
        check(max(shards) == len(jax.devices()),
              f"{c.name}: no dispatch spans all devices")
        same_records(f"four-chips/{c.name}", on.records, off.records)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip path (needs 4 chips)")
    ap.add_argument("--k", type=int, default=8,
                    help="fat-tree size (default: the paper's k=8)")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a non-TPU backend; prints no result line")
    args = ap.parse_args(argv)

    import jax
    from repro.sweep import compile_cache, preset
    jax.monitoring.register_event_listener(_count_event)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    cache = compile_cache.resolve(compile_cache.DEFAULT_DIR)
    say(f"devices: {dev}; compile cache {cache}")
    if dev["platform"] != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX platform {dev['platform']!r})",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if dev["count"] != want and not args.rehearse:
        print(f"chip_smoke: expected {want} chip(s), JAX sees "
              f"{dev['count']}", file=sys.stderr)
        return 1

    ctx = {"table2_c": dataclasses.replace(preset("table2"),
                                           trees=(args.k,)),
           "fig12_c": dataclasses.replace(preset("fig12"), trees=(args.k,))}
    phases = ([phase_four_chips] if args.four_chips else
              [phase_fast, phase_loop, phase_kernels, phase_reference])
    failed = []
    t0 = time.perf_counter()
    for ph in phases:
        name = ph.__name__[len("phase_"):]
        needs = {"kernels": ("fast", "loop"), "reference": ("fast", "loop")}
        if any(n in failed for n in needs.get(name, ())):
            say(f"[{name}] skipped: an earlier phase it compares with failed")
            failed.append(name)
            continue
        try:
            ph(ctx)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    say(f"total {time.perf_counter() - t0:.3f}s")
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        say("rehearsal passed (no result line off the chip)")
        return 0
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
