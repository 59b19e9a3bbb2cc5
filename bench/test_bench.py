"""Tests of the benchmark's yardstick, on the CPU at small sizes.

    python -m pytest bench

* the trace reduction on a trace recorded on the chip (``bench/data``),
  against a slower, independent count of the same events;
* the replicate-seed pool: every run seed does the same work in another
  order;
* the comparison that decides ``correct``: the program passes it, the
  control (the reference in bfloat16 in the program's place) fails it, in
  every cell of ``BENCHMARK.json``;
* the reference module a configuration names is found by that name: a
  module ``check.py`` has never seen decides ``correct`` once a
  configuration names it, and a configuration naming none fails;
* a whole run with the timed path broken underneath fails it, once for
  each fault a one-chip cell can have in every cell whose campaign fuses
  more than one point, and once more for a fault in the fast engine's
  queue accounting alone in every cell whose limits hold the mean gap.

The cells come from ``BENCHMARK.json`` as the tests are collected, so a
cell that a later change adds is tested with no edit here.

``bench/data/fast-point-ofan.json`` holds the events of the first 20 ms
of a traced ``fast-point-ofan`` window on a TPU v5 lite: the output of
``devtrace.load`` with every event cut to that span and the
``bench.window`` span cut to it.
"""
from __future__ import annotations

import copy
import json
import pathlib
import sys

import numpy as np
import pytest

import check
import devtrace
import run
from grid import Grid

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 2**31 + 11
SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def cells(keep):
    """The cells whose configuration and traffic ``keep(config, traffic)``
    accepts."""
    return [c for c in CELLS if keep(*run.load_cell(c)[1:3])]


def small(cell: str):
    """The cell's configuration and traffic on a k=4 fat tree (16 hosts)."""
    _, config, traffic, e2e, per_layer = run.load_cell(cell)
    config = copy.deepcopy(config)
    config["k"] = 4
    return config, traffic, e2e, per_layer


def measure(cell: str, seconds=1.0, config=None):
    small_config, traffic, e2e, per_layer = small(cell)
    return run.measure(config or small_config, traffic, seed=SEED,
                       seconds=seconds, trace=False, end_to_end=e2e,
                       per_layer=per_layer, rehearse=True,
                       log=lambda s: None)


def grid_records(config: dict, traffic: dict):
    """Records of the cell's own grid points, three campaigns of one
    replicate seed each, each campaign from its own run seed (so its own
    traffic matrix, where the mix pins none), named as the runner names
    them."""
    out = []
    for i, x in enumerate((3, 2**31 + 5, 77)):
        campaign = Grid(config, traffic, SEED + i).campaign((x,))
        out += [{"scheme": p.scheme, "seed": p.seed,
                 "workload": p.load.label(),
                 "failure": p.failure.label() if p.failure else None,
                 "g_converge": p.g_converge} for p in campaign.points()]
    return out


def control_fails(config: dict, traffic: dict) -> bool:
    """Whether the control, standing in for the program's record of each
    grid point, fails one of the comparison's limits."""
    import ml_dtypes
    checks = check.compare(
        config, traffic, grid_records(config, traffic), SEED,
        stand_in=lambda r: check.reference(config, traffic, r,
                                           dtype=ml_dtypes.bfloat16),
        log=lambda s: None)
    return any(op == "<=" and not v <= lim for _, v, op, lim in checks[1:])


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

@pytest.mark.parametrize("cell", cells(lambda c, t: "seed_pool" in t))
def test_seed_pool_is_the_same_work_in_another_order(cell):
    _, config, traffic, _, _ = run.load_cell(cell)
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

@pytest.mark.parametrize("cell", CELLS)
def test_program_passes_and_control_fails(cell):
    out = measure(cell)
    assert out["correct"], out["checks"]
    assert control_fails(*small(cell)[:2])


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_a_new_reference_module_is_found_by_name(monkeypatch, tmp_path,
                                                 name):
    """A cell brings its reference as a new file: a copy of the
    configuration's reference module under a name ``check.py`` never saw,
    in a directory of the ``ref`` package, is used once the configuration
    names it, and the module it was copied from is not."""
    import ref
    cell = next(w["name"] for w in SPEC["workloads"] if w["config"] == name)
    config, traffic, _, _ = small(cell)
    own = check.module(config)
    new = f"copy_of_{config['reference']}"
    path = tmp_path / f"{new}.py"
    path.write_text(pathlib.Path(own.__file__).read_text())
    monkeypatch.setattr(ref, "__path__", [*ref.__path__, str(tmp_path)])

    def unused(*args, **kwargs):
        raise AssertionError(f"{own.__name__} compared a point")
    monkeypatch.setattr(own, "point", unused)
    config = dict(config, reference=new)
    try:
        out = measure(cell, seconds=0.5, config=config)
        assert out["correct"], out["checks"]
        assert control_fails(config, traffic)
        assert pathlib.Path(check.module(config).__file__) == path
    finally:
        sys.modules.pop(f"ref.{new}", None)


@pytest.mark.parametrize("reference", [None, "no_such_module"])
def test_configuration_without_its_reference_module_fails(reference):
    config, _, _, _ = small(CELLS[0])
    config.pop("reference")
    if reference:
        config["reference"] = reference
    with pytest.raises(ValueError, match="reference"):
        check.module(config)


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
@pytest.mark.parametrize("cell", cells(
    lambda c, t: Grid(c, t, SEED).points_per_campaign > 1))
def test_fault_in_timed_path_is_not_correct(monkeypatch, cell, fault):
    from repro.net import fastsim, loopsim
    fault(monkeypatch, small(cell)[0]["engine"])
    try:
        out = measure(cell, seconds=0.5)
    finally:
        monkeypatch.undo()
        loopsim._compiled.cache_clear()
        fastsim._build_run.cache_clear()
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", cells(
    lambda c, t: "fast_mean_gap" in c["check"]["limits"]))
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
