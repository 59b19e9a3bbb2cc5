"""Tests of the benchmark's yardstick, on the CPU at small sizes.

    python -m pytest bench

* the trace reduction on a trace recorded on the chip (``bench/data``),
  against a slower, independent count of the same events;
* the replicate-seed pool: every run seed does the same work in another
  order;
* the comparison that decides ``correct``: the program passes it, the
  control (the reference in bfloat16 in the program's place) fails it;
* a whole run with the timed path broken underneath fails it, once for
  each fault a one-chip cell can have, and once more for a fault in the
  fast engine's queue accounting alone.

``bench/data/fast-point-ofan.json`` holds the events of the first 20 ms
of a traced ``fast-point-ofan`` window on a TPU v5 lite: the output of
``devtrace.load`` with every event cut to that span and the
``bench.window`` span cut to it.
"""
from __future__ import annotations

import copy
import json
import pathlib

import numpy as np
import pytest

import check
import devtrace
import run

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 2**31 + 11


def small(cell: str):
    """The cell's configuration and traffic on a k=4 fat tree (16 hosts)."""
    _, config, traffic, e2e, per_layer = run.load_cell(cell)
    config = copy.deepcopy(config)
    config["k"] = 4
    return config, traffic, e2e, per_layer


def measure(cell: str, seconds=1.0):
    config, traffic, e2e, per_layer = small(cell)
    return run.measure(config, traffic, seed=SEED, seconds=seconds,
                       trace=False, end_to_end=e2e, per_layer=per_layer,
                       rehearse=True, log=lambda s: None)


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_reduction_matches_an_independent_count(name):
    events = json.loads((DATA / name).read_text())
    got = devtrace.reduce(events)
    lo, hi = devtrace.window(events)
    # Busy time by marking every microsecond an operation covers.
    n_dev, busy = 0, 0
    for dev in events["devices"].values():
        mark = np.zeros(int((hi - lo) // 1000) + 1, bool)
        for _, s, d in dev["ops"]:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                mark[int((a - lo) // 1000):int(-(-(b - lo) // 1000))] = True
        if mark.any():
            n_dev += 1
            busy += mark.sum() * 1e-6
    assert n_dev >= 1
    assert got["busy_s"] == pytest.approx(busy / n_dev, abs=2e-6 * len(
        events["devices"]["/device:TPU:0"]["ops"]) + 1e-6)
    assert 0 < got["busy_s"] <= got["window_s"] == pytest.approx(
        (hi - lo) * 1e-9)
    # Idle gaps sum to the idle time, and operations to at least busy.
    idle = sum(v for _, v in got["idle_gaps"])
    assert idle <= got["window_s"] - got["busy_s"] + 1e-9
    assert sum(v for _, v in got["device_ops"]) <= sum(
        d * 1e-9 for dev in events["devices"].values()
        for _, _, d in dev["ops"]) + 1e-9
    assert got["module_s"] and all(v > 0 for v in got["module_s"].values())


def test_union_and_innermost_label():
    assert devtrace.union([(0, 5), (3, 8), (10, 12), (11, 11)], 1, 11) == [
        [1, 8], [10, 11]]
    host = [["outer", 0, 100], ["inner", 10, 20], ["bench.window", 0, 100]]
    assert list(devtrace._innermost(host, [5, 15, 50])) == [
        "outer", "inner", "outer"]
    events = {"host": host, "devices": {"/device:TPU:0": {
        "ops": [["a", 0, 10], ["b", 30, 70]], "modules": [["m", 0, 100]]}}}
    got = devtrace.reduce(events)
    assert got["busy_s"] == pytest.approx(80e-9)
    assert dict(got["idle_gaps"]) == pytest.approx({"inner": 20e-9})


def test_trace_overhead_compares_busy_with_untraced_wall():
    def campaign(wall_s, slots):
        spans = [{"kind": "dispatch", "n_points": 2, "slots_run": slots,
                  "wall_s": wall_s}]
        return [], spans, wall_s
    line = run.trace_overhead({"busy_s": 3.0}, [campaign(3.5, 500)],
                              [campaign(1.0, 500), campaign(1.0, 500)])
    assert "(ratio 3.0000)" in line and "(ratio 3.5000)" in line


# ---------------------------------------------------------------------------
# The replicate-seed pool
# ---------------------------------------------------------------------------

def test_seed_pool_is_the_same_work_in_another_order():
    from grid import Grid
    _, config, traffic, _, _ = run.load_cell("loop-fig5-fail")
    n = traffic["seed_pool"]

    def window_seeds(seed, campaigns):
        grid = Grid(config, traffic, seed)
        warm = grid.warmup().seeds
        return warm, [s for _ in range(campaigns) for s in grid.next().seeds]
    warm_a, a = window_seeds(SEED, n + 3)
    warm_b, b = window_seeds(7, n + 3)
    assert sorted(a[:n]) == sorted(b[:n]) and len(set(a[:n])) == n
    assert a[:n] != b[:n]
    assert set(a[n:]) <= set(a[:n])
    assert not set(warm_a + warm_b) & set(a)


# ---------------------------------------------------------------------------
# The comparison and its control
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", ["loop-fig12-sack", "loop-fig5-fail",
                                  "fast-table2-perm", "fast-point-ofan"])
def test_program_passes_and_control_fails(cell):
    import ml_dtypes
    out = measure(cell)
    assert out["correct"], out["checks"]
    config, traffic, _, _ = small(cell)
    # Grid points named as the program's records name them; the control
    # stands in for the program's record of each.
    fails = [None if f is None else f"fail{f['p_fail']:g}-r{f['rng_seed']}"
             for f in traffic.get("failures", [None])]
    records = [{"scheme": s, "seed": int(x), "failure": f,
                "g_converge": traffic.get("g_converge", [None])[0],
                "workload": f"permutation-m256-r{17 + i}"}
               for i, x in enumerate((3, 2**31 + 5, 77))
               for s in traffic["schemes"] for f in fails]
    checks = check.compare(
        config, traffic, records, SEED,
        stand_in=lambda r: check.reference(config, traffic, r,
                                           dtype=ml_dtypes.bfloat16),
        log=lambda s: None)
    failing = [(n, v, lim) for n, v, op, lim in checks[1:]
               if op == "<=" and not v <= lim]
    assert failing, checks


# ---------------------------------------------------------------------------
# Faults planted in the timed path
# ---------------------------------------------------------------------------

def _unchanged_state(monkeypatch, engine):
    """A step that returns its state unchanged: the loop engine's slot
    loop never steps; the fast engine's queues never serve."""
    import jax
    from repro.net import fastsim, loopsim
    if engine == "loop":
        monkeypatch.setattr(jax.lax, "while_loop", lambda c, b, s: s)
        loopsim._compiled.cache_clear()
    else:
        def no_service(qid, a, tie, n_queues, backend):
            import jax.numpy as jnp
            return (a, jnp.zeros((n_queues,), jnp.int32),
                    jnp.zeros(a.shape))
        monkeypatch.setattr(fastsim, "_lindley_layer", no_service)
        fastsim._build_run.cache_clear()


def _half_batch(monkeypatch, engine):
    """Half of each fused batch left out, its rows filled from the rest."""
    from repro.net import fastsim, loopsim
    mod = loopsim if engine == "loop" else fastsim
    real = mod.simulate_megabatch
    seeds_at = 4 if engine == "loop" else 3

    def half(items, **kw):
        rows = [(i, s) for i, it in enumerate(items) for s in it[seeds_at]]
        keep = set(rows[:max(1, len(rows) // 2)])
        cut = [tuple(list(it[:seeds_at]) + [[s for s in it[seeds_at]
                                             if (i, s) in keep]]
                     + list(it[seeds_at + 1:]))
               for i, it in enumerate(items)]
        got = real([c for c in cut if c[seeds_at]], **kw)
        pool = [r for rs in got for r in rs]
        it_got = iter(got)
        out = []
        for c, it in zip(cut, items):
            rs = next(it_got) if c[seeds_at] else []
            by_seed = dict(zip(c[seeds_at], rs))
            out.append([by_seed.get(s, pool[0]) for s in it[seeds_at]])
        return out
    monkeypatch.setattr(mod, "simulate_megabatch", half)


def _altered_answer(monkeypatch, engine):
    """An answer altered where it is produced: one more drop per loop
    point, every fast-engine delivery six slots late (twice the limit of
    the fast comparison's times)."""
    import dataclasses
    from repro.net import fastsim, loopsim
    if engine == "loop":
        real = loopsim._postprocess
        monkeypatch.setattr(loopsim, "_postprocess", lambda *a, **k: (
            lambda r: dataclasses.replace(r, drops=r.drops + 1))(
                real(*a, **k)))
    else:
        real = fastsim._postprocess

        def late(out, wl, probes=None):
            out = dict(out)
            out["delivery"] = np.asarray(out["delivery"]) + np.float32(6.0)
            return real(out, wl, probes)
        monkeypatch.setattr(fastsim, "_postprocess", late)


def _occupancy_off_by_one(monkeypatch, engine):
    """The fast engine's queue accounting alone off by one: every packet
    sees one more packet queued than there is; its times are right."""
    import jax.numpy as jnp
    from repro.net import fastsim
    real = fastsim._lindley_layer

    def more(qid, a, tie, n_queues, backend):
        d, counts, occ = real(qid, a, tie, n_queues, backend)
        return d, counts, jnp.where(qid >= 0, occ + 1.0, occ)
    monkeypatch.setattr(fastsim, "_lindley_layer", more)
    fastsim._build_run.cache_clear()


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_answer])
@pytest.mark.parametrize("cell", ["loop-fig12-sack", "loop-fig5-fail",
                                  "fast-table2-perm"])
def test_fault_in_timed_path_is_not_correct(monkeypatch, cell, fault):
    from repro.net import fastsim, loopsim
    engine = "loop" if cell.startswith("loop") else "fast"
    fault(monkeypatch, engine)
    try:
        out = measure(cell, seconds=0.5)
    finally:
        monkeypatch.undo()
        loopsim._compiled.cache_clear()
        fastsim._build_run.cache_clear()
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["fast-table2-perm", "fast-point-ofan"])
def test_queue_accounting_fault_is_caught_by_the_mean_gap(monkeypatch, cell):
    from repro.net import fastsim
    _occupancy_off_by_one(monkeypatch, "fast")
    try:
        out = measure(cell, seconds=0.5)
    finally:
        monkeypatch.undo()
        fastsim._build_run.cache_clear()
    checks, limits = out["checks"], small(cell)[0]["check"]["limits"]
    assert not out["correct"], checks
    # Times stay within their limit; only the averages' limit sees it.
    assert checks["fast_time_gap"]["value"] <= limits["fast_time_gap"]
    assert not checks["fast_mean_gap"]["value"] <= limits["fast_mean_gap"]
