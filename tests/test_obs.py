"""Observability layer: probe invariants, bitwise probes-off safety, trace
determinism, logger/report rendering, and schema tolerance."""
import dataclasses
import importlib.util
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.net.topology import FatTree, LAYER_NAMES
from repro.net import workloads, fastsim, loopsim
from repro.core import lb_schemes as lbs
from repro import sweep
from repro.obs import (ProbeSpec, QueueProbe, SweepLogger, TIMING_KEYS,
                       TraceWriter, dispatch_line, load_trace, probe_shape,
                       render_report, strip_timing)
from repro.obs.stages import COUNTERS, STAGE_KEYS, collect, count, stage

SEEDS = (0, 1)
PROBES = ProbeSpec(stride=8, samples=64)


def _fast_campaign(**kw):
    base = dict(name="obs", schemes=("host_pkt", "simple_rr"),
                loads=(sweep.WorkloadSpec("permutation", 32,
                                          inter_pod_only=True),),
                trees=(4,), seeds=SEEDS)
    base.update(kw)
    return sweep.Campaign(**base)


def _loop_campaign(**kw):
    base = dict(name="obs_loop", schemes=("host_pkt",),
                loads=(sweep.WorkloadSpec("permutation", 16,
                                          inter_pod_only=True),),
                trees=(4,), seeds=SEEDS, engine="loop", max_slots=8000)
    base.update(kw)
    return sweep.Campaign(**base)


@pytest.fixture(scope="module")
def fast_off():
    return sweep.run_campaign(_fast_campaign(), keep_full=True)


@pytest.fixture(scope="module")
def fast_on():
    return sweep.run_campaign(_fast_campaign(probes=PROBES), keep_full=True)


@pytest.fixture(scope="module")
def loop_off():
    return sweep.run_campaign(_loop_campaign(), keep_full=True)


@pytest.fixture(scope="module")
def loop_on():
    return sweep.run_campaign(_loop_campaign(probes=PROBES), keep_full=True)


# ---------------------------------------------------------------------------
# Probe spec plumbing
# ---------------------------------------------------------------------------

def test_probe_spec_validation():
    with pytest.raises(ValueError):
        ProbeSpec(stride=0)
    with pytest.raises(ValueError):
        ProbeSpec(stride=4, samples=0)
    assert ProbeSpec(stride=4, samples=16).horizon_slots == 64
    assert probe_shape(None) == (0, 0)
    assert probe_shape(PROBES) == (8, 64)
    assert probe_shape((8, 64)) == (8, 64)


def test_campaign_probes_json_roundtrip():
    c = _fast_campaign(probes=PROBES)
    c2 = sweep.Campaign.from_dict(json.loads(json.dumps(c.to_dict())))
    assert c2 == c
    assert c2.probes == PROBES
    # probes-off specs round-trip too (and old spec files lack the key)
    d = _fast_campaign().to_dict()
    del d["probes"]
    assert sweep.Campaign.from_dict(d).probes is None


def test_probe_shape_in_fused_key():
    """Probes are part of the compiled identity: a probed campaign plans to
    the same dispatch count but different fused keys."""
    k_off = {m.key for m in sweep.plan(_fast_campaign()).megabatches}
    k_on = {m.key for m in sweep.plan(
        _fast_campaign(probes=PROBES)).megabatches}
    assert len(k_off) == len(k_on)
    assert k_off.isdisjoint(k_on)


# ---------------------------------------------------------------------------
# Bitwise invariance: probes off == pre-telemetry behavior
# ---------------------------------------------------------------------------

def test_probes_off_records_byte_identical_with_observers(fast_off, tmp_path):
    """Telemetry observers (trace + debug logger) must not perturb a single
    output byte of a probes-off run."""
    base_records, _ = fast_off
    lines = []
    tw = TraceWriter(tmp_path / "trace.jsonl")
    records, _ = sweep.run_campaign(
        _fast_campaign(), trace=tw, log=SweepLogger("debug",
                                                    sink=lines.append),
        keep_full=False)
    tw.close()
    assert [sweep.encode_record(r) for r in records] \
        == [sweep.encode_record(r) for r in base_records]
    assert not any(k.startswith("probe_") for r in records for k in r)
    assert lines  # the logger did observe the run
    assert (tmp_path / "trace.jsonl").exists()


def test_probes_on_non_probe_fields_identical(fast_off, fast_on):
    off_records, _ = fast_off
    on_records, _ = fast_on
    for a, b in zip(off_records, on_records):
        assert a == {k: v for k, v in b.items()
                     if not k.startswith("probe_")}
        assert b["probe_stride"] == PROBES.stride


def test_loop_probes_on_non_probe_fields_identical(loop_off, loop_on):
    off_records, _ = loop_off
    on_records, _ = loop_on
    for a, b in zip(off_records, on_records):
        assert a == {k: v for k, v in b.items()
                     if not k.startswith("probe_")}


# ---------------------------------------------------------------------------
# Probe series semantics: window maxima reduce to the engine scalars
# ---------------------------------------------------------------------------

def test_fast_probe_layer_max_equals_max_queue(fast_on):
    _, full = fast_on
    assert full
    for point, res in full.items():
        assert isinstance(res.probe, QueueProbe)
        assert res.probe.series.shape == (len(LAYER_NAMES), PROBES.samples)
        lm = res.probe.layer_max()
        for i, name in enumerate(LAYER_NAMES):
            assert lm[i] == res.layers[name].max_queue, (point, name)
        assert res.probe.overall_max() == res.max_queue


def test_loop_probe_overall_max_equals_max_queue(loop_on):
    _, full = loop_on
    assert full
    for point, res in full.items():
        assert res.probe.series.shape == (5, PROBES.samples)
        assert res.probe.overall_max() == res.max_queue, point


def test_fast_probe_series_matches_serial(fast_on):
    """The fused megabatch carries the same series a standalone probed
    simulate produces."""
    _, full = fast_on
    tree = FatTree(4)
    wl = workloads.permutation(tree, 32, np.random.default_rng(1),
                               inter_pod_only=True)
    for point, res in full.items():
        serial = fastsim.simulate(tree, wl, lbs.by_name(point.scheme),
                                  seed=point.seed, probes=PROBES)
        np.testing.assert_array_equal(res.probe.series, serial.probe.series)


def test_loop_probe_series_matches_serial(loop_on):
    _, full = loop_on
    tree = FatTree(4)
    wl = workloads.permutation(tree, 16, np.random.default_rng(1),
                               inter_pod_only=True)
    cfg = _loop_campaign().loop_config()
    for point, res in full.items():
        serial = loopsim.simulate(tree, wl, lbs.by_name(point.scheme), cfg,
                                  seed=point.seed, probes=PROBES)
        np.testing.assert_array_equal(res.probe.series, serial.probe.series)


# ---------------------------------------------------------------------------
# Trace determinism and rendering
# ---------------------------------------------------------------------------

def test_trace_deterministic_modulo_timing(tmp_path):
    traces = []
    for i in range(2):
        tw = TraceWriter(tmp_path / f"t{i}.jsonl")
        sweep.run_campaign(_fast_campaign(), trace=tw)
        tw.close()
        traces.append([strip_timing(s)
                       for s in load_trace(tmp_path / f"t{i}.jsonl")])
    assert traces[0] == traces[1]
    kinds = [s["kind"] for s in traces[0]]
    assert kinds[0] == "plan" and kinds[-1] == "campaign"
    assert kinds.count("dispatch") == sweep.plan(_fast_campaign()).n_dispatches
    for s in traces[0]:
        assert s["schema"] == 1
        assert not TIMING_KEYS & set(s)


def test_dispatch_spans_carry_cost_fields(monkeypatch):
    """execute_s / compile_s come from the stages of the one dispatch each
    megabatch gets: nothing runs a megabatch twice to time it."""
    calls = []
    mega = fastsim.simulate_megabatch

    def spy(items, **kw):
        calls.append(len(items))
        return mega(items, **kw)
    monkeypatch.setattr(fastsim, "simulate_megabatch", spy)
    tw = TraceWriter()
    sweep.run_campaign(_fast_campaign(), trace=tw)
    disp = [s for s in tw.spans if s["kind"] == "dispatch"]
    assert disp
    assert len(calls) == len(disp) == sweep.plan(_fast_campaign()).n_dispatches
    for s in disp:
        assert 0 < s["pkt_fill"] <= 1.0
        assert s["pkt_rows_real"] <= s["pkt_rows_padded"]
        assert s["cache"] in ("hit", "miss")
        assert s["wall_s"] > 0
        assert s["execute_s"] > 0 and s["compile_s"] >= 0
        assert s["execute_s"] + s["compile_s"] <= s["wall_s"]
    end = tw.spans[-1]
    assert end["kind"] == "campaign" and end["emit_s"] >= 0


def test_loop_dispatch_span_slot_budget(tmp_path):
    """``row_slot_fill`` is the benchmark's ``loop.row_fill``, computed where
    the work happens."""
    tw = TraceWriter()
    records, _ = sweep.run_campaign(_loop_campaign(), trace=tw)
    disp = [s for s in tw.spans if s["kind"] == "dispatch"]
    assert all(s["slot_budget"] == 8000 for s in disp)
    slots_run = max(s["slots_run"] for s in disp)
    assert slots_run == int(max(r["cct_acked"] for r in records))
    assert all("slot_fill" not in s for s in disp)
    rows = sum(s["n_points"] * s["slots_run"] for s in disp)
    fill = sum(s["row_slot_fill"] * s["n_points"] * s["slots_run"]
               for s in disp) / rows
    ctx = {"engine": "loop", "records": records, "spans": [tw.spans]}
    assert 0 < fill <= 1.0
    assert fill == pytest.approx(_bench_metric("loop.row_fill").read(ctx),
                                 rel=1e-12)


# ---------------------------------------------------------------------------
# Stage spans (repro.obs.stages)
# ---------------------------------------------------------------------------

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAST_ORDER = ["prep", "prep", "execute", "fetch", "post", "record"]
HOST_METRICS = ("host.prep_ms_per_point", "host.transfer_ms_per_point",
                "host.record_ms_per_point")


def _bench_metric(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _campaign_of(engine):
    return _fast_campaign() if engine == "fast" else _loop_campaign()


@pytest.fixture(scope="module")
def staged():
    """(records, spans) of one traced fast and one traced loop campaign."""
    out = {}
    for engine in ("fast", "loop"):
        tw = TraceWriter()
        records, _ = sweep.run_campaign(_campaign_of(engine), trace=tw)
        out[engine] = (records, tw.spans)
    return out


@pytest.mark.parametrize("engine", ["fast", "loop"])
def test_dispatch_spans_carry_every_stage(staged, engine):
    records, spans = staged[engine]
    (plan_span,) = [s for s in spans if s["kind"] == "plan"]
    assert plan_span["plan_s"] > 0
    disp = [s for s in spans if s["kind"] == "dispatch"]
    assert disp
    for s in disp:
        for key in STAGE_KEYS:
            assert s[key] >= 0, key
        for key in ("prep_s", "execute_s", "fetch_s", "post_s", "record_s"):
            assert s[key] > 0, key
        assert s["retry_s"] == 0 and s["jsq_retries"] == 0
        assert s["bytes_in"] > 0 and s["bytes_out"] > 0
        assert set(COUNTERS) <= set(s)
        # The stages inside the dispatch's wall time add up to no more.
        inside = sum(s[k] for k in STAGE_KEYS if k != "record_s")
        assert inside <= s["wall_s"]


@pytest.mark.parametrize("engine", ["fast", "loop"])
def test_byte_counters_match_the_transfers(monkeypatch, engine):
    """bytes_in / bytes_out are the nbytes of the stacked operands as the
    device holds them and of the outputs the jitted call returns."""
    seen = {"in": 0, "out": 0}
    mod = fastsim if engine == "fast" else loopsim
    execute = mod.execute

    def spy(fn, *args):
        seen["in"] += sum(jnp.asarray(x).nbytes
                          for x in jax.tree_util.tree_leaves(args))
        out = execute(fn, *args)
        seen["out"] += sum(x.nbytes for x in jax.tree_util.tree_leaves(out))
        return out
    monkeypatch.setattr(mod, "execute", spy)
    tw = TraceWriter()
    sweep.run_campaign(_campaign_of(engine), trace=tw)
    disp = [s for s in tw.spans if s["kind"] == "dispatch"]
    assert seen["in"] > 0 and seen["out"] > 0
    assert sum(s["bytes_in"] for s in disp) == seen["in"]
    assert sum(s["bytes_out"] for s in disp) == seen["out"]


def test_jsq_pad_overflow_counts_retried_rows(monkeypatch):
    mega = fastsim.simulate_megabatch
    monkeypatch.setattr(fastsim, "simulate_megabatch",
                        lambda items, **kw: mega(items, jsq_pad_factor=0.01,
                                                 **kw))
    tw = TraceWriter()
    load = sweep.WorkloadSpec("permutation", 64, inter_pod_only=True)
    records, _ = sweep.run_campaign(
        _fast_campaign(schemes=("jsq",), loads=(load,)), trace=tw)
    (s,) = [s for s in tw.spans if s["kind"] == "dispatch"]
    assert 0 < s["jsq_retries"] <= len(records) == len(SEEDS)
    assert s["retry_s"] > 0


def test_profile_nests_stages_in_each_dispatch(tmp_path):
    """On the profiler's clock, every dispatch is one ``sweep.dispatch``
    span naming its campaign and index, holding its stages in order."""
    sweep.run_campaign(_fast_campaign(), profile_dir=str(tmp_path))
    from jax.profiler import ProfileData
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host")
              for line in plane.lines for e in line.events
              if e.name.startswith("sweep.")]
    disp = sorted((e for e in events if e[0] == "sweep.dispatch"),
                  key=lambda e: e[1])
    assert [e[3]["dispatch"] for e in disp] == list(
        range(sweep.plan(_fast_campaign()).n_dispatches))
    assert all(e[3]["campaign"] == "obs" for e in disp)
    stages = [e for e in events if e[0] != "sweep.dispatch"]
    for _, lo, hi, _ in disp:
        inner = sorted((e for e in stages if lo <= e[1] and e[2] <= hi),
                       key=lambda e: e[1])
        assert [e[0] for e in inner] == [f"sweep.{n}" for n in FAST_ORDER]
    assert all(any(lo <= e[1] and e[2] <= hi for _, lo, hi, _ in disp)
               for e in stages)


def test_lowered_fast_pipeline_names_its_layers():
    tree = FatTree(4)
    wl = workloads.permutation(tree, 8, np.random.default_rng(1),
                               inter_pod_only=True)
    plan = fastsim._prepare(tree, wl, lbs.by_name("host_pkt"), 12.0, None,
                            "auto", 4.0)
    kw = {**plan.static_args, **fastsim._draw_seed_inputs(plan, 0)}
    run = plan.build_run(batch=False)
    text = run.jitted.lower(*(kw[k] for k in fastsim._ARG_ORDER)).as_text(
        debug_info=True)
    for name in ("up_e", "up_a", "dn_c", "dn_a", "dn_e"):
        assert f"jit(pipeline)/{name}/" in text, name


def test_lowered_loop_engine_names_its_slot_stages():
    tree = FatTree(4)
    wl = workloads.permutation(tree, 8, np.random.default_rng(1),
                               inter_pod_only=True)
    plan = loopsim._prepare(tree, wl, lbs.by_name("host_pkt"),
                            _loop_campaign().loop_config(), None, None)
    tables = {**plan.tables, **loopsim._draw_seed_inputs(plan, 0)}
    fn = loopsim._compiled(plan.static, loopsim._shapes(tables), False, 1)
    text = fn.lower(*(tables[k] for k in loopsim._ARG_ORDER)).as_text(
        debug_info=True)
    for name in ("serve", "route", "deliver", "move", "inject", "edge_pick",
                 "agg_pick", "enqueue", "ack", "timeout", "complete"):
        assert f"/slot/while/body/{name}/" in text, name


@pytest.mark.parametrize("name", [m + suffix for m in HOST_METRICS
                                  for suffix in ("", ".single")])
def test_host_stage_metrics_read_the_spans(staged, name):
    """Each reader sums its stages over the campaigns' plan and dispatch
    spans per point, and reads nothing from spans without them."""
    metric = _bench_metric(name)
    records, spans = staged["fast"]
    ctx = {"spans": [spans, spans], "points": 2 * len(records)}
    one = metric.read({"spans": [spans], "points": len(records)})
    assert metric.read(ctx) > 0
    assert metric.read(ctx) == pytest.approx(one)
    bare = [strip_timing(s) for s in spans]
    assert metric.read({"spans": [bare], "points": len(records)}) is None


def test_stage_seconds_leave_out_compile_time():
    """A stage that compiles adds its compile to compile_s, the rest to
    its own field; outside a dispatch a stage only annotates."""
    f = jax.jit(lambda x: jnp.cumsum(x * 3) - 1)
    x = jnp.arange(1000)
    with collect() as fields:
        t0 = time.perf_counter()
        with stage("execute"):
            jax.block_until_ready(f(x))
        elapsed = time.perf_counter() - t0
        count("bytes_in", 5)
        count("bytes_in", 2)
    assert fields["compile_s"] > 0
    assert 0 <= fields["execute_s"] < elapsed
    assert fields["execute_s"] + fields["compile_s"] == pytest.approx(
        elapsed, abs=2e-3)
    assert fields["bytes_in"] == 7 and fields["post_s"] == 0
    with stage("execute"):      # no dispatch collecting: nothing to add to
        jax.block_until_ready(f(x))
    count("bytes_in", 1)
    assert fields["bytes_in"] == 7


def test_report_renders_trace_and_probes(fast_on, tmp_path):
    records, _ = fast_on
    tw = TraceWriter()
    sweep.run_campaign(_fast_campaign(probes=PROBES), trace=tw)
    text = render_report(tw.spans, records, top=2)
    assert "dispatch timeline" in text
    assert "top queue trajectories" in text
    assert "padding:" in text
    no_probe = render_report(tw.spans, [
        {k: v for k, v in r.items() if not k.startswith("probe_")}
        for r in records])
    assert "no probe series" in no_probe


def test_dispatch_line_format():
    span = {"dispatch": 0, "engine": "fast", "schemes": ["host_pkt"],
            "trees": [4, 8], "n_points": 6, "pkt_fill": 0.75,
            "wall_s": 1.5, "cache": "hit"}
    line = dispatch_line(span, 3)
    assert "[1/3]" in line and "k={4,8}" in line
    assert "x6" in line and "fill=0.75" in line and "[cached]" in line


# ---------------------------------------------------------------------------
# Schema tolerance
# ---------------------------------------------------------------------------

def test_summarize_tolerates_extra_and_foreign_records(fast_off):
    records, _ = fast_off
    base = sweep.summarize(records)
    extra = [dict(r, probe_queue=[[1, 2]], future_key="x") for r in records]
    mixed = extra + [{"kind": "note"}, {"campaign": "obs"}]
    rows = sweep.summarize(mixed)
    assert [{k: v for k, v in r.items()} for r in rows] == base


def test_ratio_label_bad_samples():
    """Non-finite / non-positive ratios are bad data, not absurd slowdowns
    (a failed bench run writing 0.0 used to render as
    '1000000000.0x slower')."""
    from repro.obs.report import ratio_label
    for bad in (0.0, -1.0, float("nan"), float("inf"), float("-inf")):
        assert ratio_label(bad) == "n/a (bad sample)"
    assert ratio_label(2.0) == "2.00x speedup"
    label = ratio_label(0.5)
    assert "SLOWDOWN" in label and "2.0x slower" in label


def test_bench_json_merge(tmp_path, monkeypatch):
    sweep_bench = pytest.importorskip(
        "benchmarks.sweep_bench",
        reason="benchmarks/ needs the repo root on sys.path")
    path = tmp_path / "BENCH_sweep.json"
    path.write_text(json.dumps({"schema": 1, "other_tool": {"keep": True},
                                "megabatch_s": 99.0}))
    monkeypatch.setattr(sweep_bench, "BENCH_JSON", path)
    sweep_bench._merge_bench_json({"megabatch_s": 1.5, "plan": {"n": 2}})
    merged = json.loads(path.read_text())
    assert merged["schema"] == 2
    assert merged["other_tool"] == {"keep": True}   # foreign section survives
    assert merged["megabatch_s"] == 1.5             # ours overwrites
