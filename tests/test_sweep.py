"""Campaign subsystem: batched-vs-serial equivalence, planner grouping,
result-store determinism, and spec round-trips."""
import dataclasses
import json

import numpy as np
import pytest

from repro.net.topology import FatTree
from repro.net import workloads, fastsim
from repro.core import lb_schemes as lbs
from repro import sweep


SCHEMES = ("host_pkt", "simple_rr", "ofan")   # pre/pre, rr/rr, ofan/ofan
SEEDS = (0, 1, 2, 3)


@pytest.fixture(scope="module")
def tree():
    return FatTree(4)


@pytest.fixture(scope="module")
def perm_wl(tree):
    return workloads.permutation(tree, 32, np.random.default_rng(1),
                                 inter_pod_only=True)


@pytest.mark.parametrize("scheme", SCHEMES)
def test_batch_bitwise_identical_to_serial(tree, perm_wl, scheme):
    """simulate_batch must reproduce serial simulate exactly, per seed."""
    sch = lbs.by_name(scheme)
    serial = [fastsim.simulate(tree, perm_wl, sch, seed=s) for s in SEEDS]
    batch = fastsim.simulate_batch(tree, perm_wl, sch, SEEDS)
    for a, b in zip(serial, batch):
        np.testing.assert_array_equal(a.delivery, b.delivery)
        np.testing.assert_array_equal(a.flow_completion, b.flow_completion)
        assert a.cct == b.cct
        assert a.max_queue == b.max_queue
        np.testing.assert_array_equal(a.a_used, b.a_used)
        np.testing.assert_array_equal(a.c_used, b.c_used)
        for name in a.layers:
            np.testing.assert_array_equal(a.layers[name].counts,
                                          b.layers[name].counts)
            assert a.layers[name].max_queue == b.layers[name].max_queue
            assert a.layers[name].avg_wait == b.layers[name].avg_wait


def _campaign(**kw):
    base = dict(name="t", schemes=SCHEMES,
                loads=(sweep.WorkloadSpec("permutation", 32,
                                          inter_pod_only=True),),
                trees=(4,), seeds=SEEDS)
    base.update(kw)
    return sweep.Campaign(**base)


def test_campaign_matches_standalone_simulate(tree, perm_wl):
    """End-to-end: campaign point results == standalone fastsim calls."""
    _, full = sweep.run_campaign(_campaign(), keep_full=True)
    assert len(full) == len(SCHEMES) * len(SEEDS)
    for point, res in full.items():
        ref = fastsim.simulate(tree, perm_wl, lbs.by_name(point.scheme),
                               seed=point.seed)
        np.testing.assert_array_equal(res.delivery, ref.delivery)
        assert res.cct == ref.cct


def test_planner_fuses_schemes_into_megabatches():
    c = sweep.Campaign(
        name="t", schemes=("host_pkt", "simple_rr", "host_dr"),
        loads=(sweep.WorkloadSpec("permutation", 16),), trees=(4,),
        seeds=SEEDS)
    p = sweep.plan(c)
    assert p.n_points == 12
    # host_pkt and host_dr share the 'pre/pre' pipeline and fuse into ONE
    # dispatch; simple_rr compiles its own shape.
    assert p.n_dispatches == 2
    assert p.n_dispatches == p.n_shapes
    for b in p.batches:
        assert b.seeds == SEEDS
    fused = {frozenset(b.scheme for b in m.members) for m in p.megabatches}
    assert frozenset({"host_pkt", "host_dr"}) in fused


def test_planner_dispatches_equal_shapes_on_fig1_grid():
    """The fig1/table2 grid: the scheme axis is fully fused -- exactly one
    dispatch per compiled pipeline shape (pre/pre, rr_reset, jsq_quant,
    ofan), per traffic matrix."""
    c = sweep.preset("table2")
    p = sweep.plan(c)
    assert p.n_dispatches == p.n_shapes
    assert p.n_dispatches == 4 * len(c.loads)
    pre = [m for m in p.megabatches
           if {b.scheme for b in m.members} >= {"flow_ecmp", "host_pkt"}]
    assert len(pre) == len(c.loads)     # 4 pre/pre schemes fused per load


def test_planner_buckets_message_sizes_into_one_shape():
    """Loads whose packet counts land in one power-of-two bucket share a
    compiled shape and fuse into one dispatch."""
    c = sweep.Campaign(
        name="t", schemes=("host_pkt",),
        loads=(sweep.WorkloadSpec("permutation", 24),
               sweep.WorkloadSpec("permutation", 32)),
        trees=(4,), seeds=(0,))
    p = sweep.plan(c)
    assert sweep.bucket_packets(16 * 24) == sweep.bucket_packets(16 * 32)
    assert p.n_dispatches == 1
    assert p.megabatches[0].npk_pad == 512


def test_result_store_deterministic(tmp_path):
    """Re-running a campaign must produce byte-identical JSONL."""
    paths = []
    for i in (1, 2):
        path = tmp_path / f"run{i}.jsonl"
        sweep.run_campaign(_campaign(seeds=(0, 1)),
                           store=sweep.ResultStore(path))
        paths.append(path)
    b1, b2 = (p.read_bytes() for p in paths)
    assert b1 == b2
    assert len(b1.splitlines()) == len(SCHEMES) * 2


def test_summarize_aggregates_seeds():
    records, _ = sweep.run_campaign(_campaign(seeds=(0, 1)))
    rows = sweep.summarize(records)
    assert len(rows) == len(SCHEMES)
    for row in rows:
        assert row["n_seeds"] == 2
        assert row["cct_min"] <= row["cct_mean"] <= row["cct_max"]


def test_campaign_json_roundtrip():
    c = _campaign(failures=(sweep.FailureSpec(0.02, rng_seed=3), None),
                  loop_opts=(("g_converge", 0), ("max_slots", 1000)))
    c2 = sweep.Campaign.from_dict(json.loads(json.dumps(c.to_dict())))
    assert c2 == c


def test_campaign_rejects_unknown_scheme():
    with pytest.raises(KeyError):
        _campaign(schemes=("definitely_not_a_scheme",))


def _assert_bitwise_equal(res, ref):
    np.testing.assert_array_equal(res.delivery, ref.delivery)
    np.testing.assert_array_equal(res.flow_completion, ref.flow_completion)
    assert res.cct == ref.cct
    assert res.max_queue == ref.max_queue
    for name in ref.layers:
        np.testing.assert_array_equal(res.layers[name].counts,
                                      ref.layers[name].counts)
        assert res.layers[name].max_queue == ref.layers[name].max_queue
        assert res.layers[name].avg_wait == ref.layers[name].avg_wait


@pytest.mark.parametrize("scheme", ("host_pkt", "switch_pkt_ar", "ofan"))
def test_megabatch_bitwise_identical_to_serial(tree, perm_wl, scheme):
    """One fused dispatch over two workloads x seeds must reproduce serial
    simulate exactly, per point -- including shape-bucketing padding (the
    second workload is padded from 384 to 512 packets)."""
    sch = lbs.by_name(scheme)
    wl_b = workloads.permutation(tree, 24, np.random.default_rng(3))
    items = [(tree, perm_wl, sch, list(SEEDS), None),
             (tree, wl_b, sch, [0, 1], None)]
    out = fastsim.simulate_megabatch(items, npk_pad=512)
    for (t, w, s_, seeds, _), results in zip(items, out):
        for seed, res in zip(seeds, results):
            assert res.delivery.shape[0] == w.n_packets
            _assert_bitwise_equal(res, fastsim.simulate(t, w, s_, seed=seed))


def test_megabatch_fuses_schemes_bitwise(tree, perm_wl):
    """flow_ecmp / host_pkt / host_dr stack onto one fused axis; every
    (scheme, seed) cell stays bitwise-identical to standalone simulate."""
    items = [(tree, perm_wl, lbs.by_name(n), list(SEEDS), None)
             for n in ("flow_ecmp", "host_pkt", "host_dr")]
    out = fastsim.simulate_megabatch(items)
    for (t, w, s_, seeds, _), results in zip(items, out):
        for seed, res in zip(seeds, results):
            _assert_bitwise_equal(res, fastsim.simulate(t, w, s_, seed=seed))
            np.testing.assert_array_equal(
                res.a_used, fastsim.simulate(t, w, s_, seed=seed).a_used)


def test_megabatch_sharded_bitwise_identical(tree, perm_wl, two_devices):
    """shard_map over the fused axis (2 virtual devices from conftest's
    XLA_FLAGS) must not change results; the 3x3=9-element batch also forces
    the divisibility padding path (9 -> 10)."""
    items = [(tree, perm_wl, lbs.by_name(n), [0, 1, 2], None)
             for n in ("flow_ecmp", "host_pkt", "host_dr")]
    sharded = fastsim.simulate_megabatch(items, n_shards="auto")
    for (t, w, s_, seeds, _), results in zip(items, sharded):
        for seed, res in zip(seeds, results):
            _assert_bitwise_equal(res, fastsim.simulate(t, w, s_, seed=seed))


def test_megabatch_sharded_jsq_bitwise_identical(tree, perm_wl, two_devices):
    """The JSQ pipeline, whose per-switch scan starts from a replicated
    carry, shards too.  Called directly (no runner ladder to degrade
    through), a sharding fault raises here instead of being retried
    serially."""
    sch = lbs.by_name("switch_pkt_ar")
    sharded, = fastsim.simulate_megabatch(
        [(tree, perm_wl, sch, [0, 1, 2], None)], n_shards="auto")
    for seed, res in zip([0, 1, 2], sharded):
        _assert_bitwise_equal(res, fastsim.simulate(tree, perm_wl, sch,
                                                    seed=seed))


def test_padding_preserves_delivered_packet_counts(tree):
    """Shape-bucketing pad packets are inert: per-layer delivered-packet
    counts match the unpadded run exactly."""
    wl = workloads.permutation(tree, 24, np.random.default_rng(3))
    sch = lbs.by_name("switch_pkt")
    (padded,), = fastsim.simulate_megabatch([(tree, wl, sch, [0], None)],
                                            npk_pad=1024)
    ref = fastsim.simulate(tree, wl, sch, seed=0)
    for name in ref.layers:
        assert padded.layers[name].counts.sum() == ref.layers[name].counts.sum()
        np.testing.assert_array_equal(padded.layers[name].counts,
                                      ref.layers[name].counts)
    assert padded.delivery.shape[0] == wl.n_packets


def test_megabatch_jsq_overflow_retry_matches_serial(tree, perm_wl):
    """A tiny jsq_pad_factor forces the pad-overflow retry ladder; the
    megabatch must take exactly the serial retry decisions (per element)
    and land on bitwise-identical results."""
    sch = lbs.by_name("jsq")
    (results,) = fastsim.simulate_megabatch(
        [(tree, perm_wl, sch, [0, 1], None)], jsq_pad_factor=0.01)
    for seed, res in zip([0, 1], results):
        _assert_bitwise_equal(res, fastsim.simulate(
            tree, perm_wl, sch, seed=seed, jsq_pad_factor=0.01))


def test_campaign_shard_off_matches_auto(tree, perm_wl):
    recs_auto, _ = sweep.run_campaign(_campaign(seeds=(0, 1)))
    recs_off, _ = sweep.run_campaign(
        _campaign(seeds=(0, 1), shard="off"))
    assert recs_auto == recs_off


def test_g_converge_is_a_grid_axis():
    c = sweep.Campaign(
        name="g", schemes=("host_pkt_ar",),
        loads=(sweep.WorkloadSpec("permutation", 8, inter_pod_only=True),),
        trees=(4,), seeds=(0,), engine="loop",
        g_converge=(0, None),
        failures=(sweep.FailureSpec(0.05, rng_seed=3),),
        loop_opts=(("max_slots", 4000), ("rho", 0.9)))
    assert c.n_points == 2
    records, _ = sweep.run_campaign(c)
    gs = [r["g_converge"] for r in records]
    assert gs == [0, None]
    assert len({r["cct"] for r in records}) == 2   # G changes the outcome


def test_legacy_loop_opts_g_converge_migrates():
    c = sweep.Campaign(
        name="legacy", schemes=("host_pkt_ar",),
        loads=(sweep.WorkloadSpec("permutation", 8),), trees=(4,),
        engine="loop", loop_opts=(("g_converge", 7), ("max_slots", 100)))
    assert c.g_converge == (7,)
    assert "g_converge" not in dict(c.loop_opts)
    c2 = sweep.Campaign.from_dict(json.loads(json.dumps(c.to_dict())))
    assert c2 == c


def test_legacy_loop_opts_max_slots_migrates():
    """max_slots is a first-class Campaign field; legacy specs that carried
    it inside loop_opts auto-migrate and round-trip."""
    c = sweep.Campaign(
        name="legacy", schemes=("host_pkt_ar",),
        loads=(sweep.WorkloadSpec("permutation", 8),), trees=(4,),
        engine="loop", loop_opts=(("max_slots", 123), ("rto_slots", 50)))
    assert c.max_slots == 123
    assert dict(c.loop_opts) == {"rto_slots": 50}
    assert c.loop_config().max_slots == 123
    assert c.loop_config().rto_slots == 50
    c2 = sweep.Campaign.from_dict(json.loads(json.dumps(c.to_dict())))
    assert c2 == c
    # An explicit field value wins over a legacy loop_opts entry.
    c3 = sweep.Campaign(
        name="legacy2", schemes=("host_pkt_ar",),
        loads=(sweep.WorkloadSpec("permutation", 8),), trees=(4,),
        engine="loop", max_slots=777, loop_opts=(("max_slots", 123),))
    assert c3.max_slots == 777 and dict(c3.loop_opts) == {}


def _loop_campaign(**kw):
    base = dict(name="loop", schemes=("host_pkt", "host_dr", "ofan"),
                loads=(sweep.WorkloadSpec("permutation", 32,
                                          inter_pod_only=True),),
                trees=(4,), seeds=(0, 1), engine="loop", max_slots=4000)
    base.update(kw)
    return sweep.Campaign(**base)


def test_planner_fuses_loop_schemes_into_megabatches():
    """Loop-engine grids fuse like fast ones: host_pkt and host_dr share the
    'pre/pre' slotted engine (ONE dispatch); ofan compiles its own shape.
    g_converge and failure values ride as operands, not keys."""
    c = _loop_campaign(g_converge=(0, None),
                       failures=(None, sweep.FailureSpec(0.05, rng_seed=3)))
    p = sweep.plan(c)
    assert p.n_points == 3 * 2 * 2 * 2
    assert p.n_dispatches == p.n_shapes == 2
    fused = {frozenset(b.scheme for b in m.members) for m in p.megabatches}
    assert frozenset({"host_pkt", "host_dr"}) in fused


def test_planner_loop_keys_on_static_loop_config():
    """Static LoopConfig fields split compiled shapes; rho and bucketed
    max_slots do not."""
    base = _loop_campaign()
    assert sweep.plan(base).n_dispatches == 2
    sack = _loop_campaign(loop_opts=(("loss", "sack"),))
    k0 = sweep.plan(base).megabatches[0].key
    k1 = sweep.plan(sack).megabatches[0].key
    assert k0 != k1
    rho = _loop_campaign(loop_opts=(("rho", 0.9),), max_slots=4095)
    assert sweep.plan(rho).megabatches[0].key == k0


def test_fig12_preset_plans_one_dispatch_per_shape():
    """The acceptance grid: a fig12-style scheme x load x seed campaign on
    the loop engine runs as fused dispatches, one per compiled shape."""
    c = sweep.preset("fig12")
    p = sweep.plan(c)
    assert p.n_dispatches == p.n_shapes
    # host_pkt + host_dr fuse ('pre/pre'); switch_pkt_ar, host_pkt_ar and
    # ofan each compile their own slotted pipeline.
    assert p.n_dispatches == 4
    fused = {frozenset(b.scheme for b in m.members) for m in p.megabatches}
    assert frozenset({"host_pkt", "host_dr"}) in fused


def test_loop_campaign_matches_standalone_simulate(tree, perm_wl):
    """End-to-end: fused loop-engine campaign results == standalone
    loopsim.simulate calls (the acceptance bitwise-parity criterion)."""
    from repro.net import loopsim
    c = _loop_campaign(loop_opts=(("loss", "sack"),))
    p = sweep.plan(c)
    assert p.n_dispatches == p.n_shapes == 2
    _, full = sweep.run_campaign(c, keep_full=True)
    assert len(full) == 6
    cfg = c.loop_config()
    for point, res in full.items():
        ref = loopsim.simulate(tree, perm_wl, lbs.by_name(point.scheme),
                               cfg, seed=point.seed)
        np.testing.assert_array_equal(res.delivered_slot, ref.delivered_slot)
        np.testing.assert_array_equal(res.flow_complete_slot,
                                      ref.flow_complete_slot)
        assert res.cct_slots == ref.cct_slots
        assert res.drops == ref.drops
        assert res.retransmissions == ref.retransmissions


@pytest.fixture
def cache_env(monkeypatch):
    """No JAX_COMPILATION_CACHE_DIR from the caller's environment; the
    process-wide cache is switched off again after the test."""
    from repro.sweep import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    yield monkeypatch
    compile_cache.disable()


def test_compile_cache_persists_executables(tmp_path, cache_env):
    cache_dir = tmp_path / "jax-cache"
    # Drop in-process compile reuse so the dispatch actually compiles (and
    # therefore writes a persistent entry) inside this test.
    fastsim._build_run.cache_clear()
    sweep.run_campaign(_campaign(seeds=(0,), schemes=("host_pkt",)),
                       compile_cache_dir=str(cache_dir))
    entries = list(cache_dir.iterdir())
    assert entries, "persistent compile cache left no entries"


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_dir_precedence(tmp_path, cache_env, env_set):
    """JAX_COMPILATION_CACHE_DIR wins over the caller's path; without it
    the caller's path is used, and nothing is set up without either."""
    from repro.sweep import compile_cache
    asked, env_dir = tmp_path / "asked", tmp_path / "env"
    if env_set:
        cache_env.setenv(compile_cache.ENV_VAR, str(env_dir))
    want = env_dir if env_set else asked
    assert compile_cache.enable(str(asked)) == str(want)
    assert want.is_dir() and not (env_set and asked.exists())
    assert compile_cache.active_dir() == str(want)
    assert compile_cache.resolve(None) == (str(env_dir) if env_set
                                           else None)


def test_compile_cache_off_is_process_wide(tmp_path, cache_env):
    """``compile_cache_dir=False`` switches JAX's persistent cache off for
    the process, as documented, and it stays off after the campaign."""
    import jax
    from repro.sweep import compile_cache
    compile_cache.enable(str(tmp_path / "jax-cache"))
    sweep.run_campaign(_campaign(seeds=(0,), schemes=("host_pkt",)),
                       compile_cache_dir=False)
    assert compile_cache.active_dir() is None
    assert not jax.config.jax_enable_compilation_cache


def test_compile_cache_default_is_fixed_checkout_path():
    import pathlib
    from repro.sweep import compile_cache
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(compile_cache.DEFAULT_DIR) == root / "jax-cache"


@pytest.mark.parametrize("flag", [[], ["--no-compile-cache"]])
def test_cli_compile_cache_choice(tmp_path, monkeypatch, flag):
    """The CLI asks for the fixed checkout cache, never <out>/jax-cache;
    --no-compile-cache asks for none."""
    from repro.sweep import __main__ as cli, compile_cache
    seen = {}

    def fake_run(c, **kw):
        seen.update(kw)
        return [], {}

    monkeypatch.setattr(cli, "run_campaign", fake_run)
    cli.main(["run", "--preset", "table2", "--out", str(tmp_path / "out"),
              "--quiet", *flag])
    want = False if flag else compile_cache.DEFAULT_DIR
    assert seen["compile_cache_dir"] == want


def test_compile_cache_unusable_dir_raises(tmp_path, cache_env):
    from repro.sweep import compile_cache
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    with pytest.raises(OSError):
        compile_cache.enable(str(blocker))


@pytest.mark.parametrize("poisoned", [False, True])
def test_cli_exit_code_counts_lost_points(tmp_path, monkeypatch, poisoned):
    """``run`` exits non-zero when any planned point has no record, even
    though the runner degraded past the failure instead of raising."""
    from repro.sweep import __main__ as cli, runner

    def always_raises(mega, campaign, cache):
        raise RuntimeError("dispatch failed")

    if poisoned:
        monkeypatch.setattr(runner, "_run_fast_mega", always_raises)
    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps(_campaign(seeds=(0,),
                                         schemes=("host_pkt",)).to_dict()))
    rc = cli.main(["run", "--spec", str(spec), "--out",
                   str(tmp_path / "out"), "--quiet", "--no-compile-cache"])
    assert rc == (1 if poisoned else 0)


def test_profile_without_profiler_raises(tmp_path, monkeypatch):
    import jax

    def no_profiler(*a, **kw):
        raise RuntimeError("no profiler on this backend")

    monkeypatch.setattr(jax.profiler, "trace", no_profiler)
    with pytest.raises(RuntimeError, match="no profiler"):
        sweep.run_campaign(_campaign(seeds=(0,), schemes=("host_pkt",)),
                           compile_cache_dir=False,
                           profile_dir=str(tmp_path / "prof"))


def test_cross_k_grid_one_dispatch_per_engine():
    """Acceptance: a grid sweeping k in {4, 6, 8} with fixed schemes/loads
    runs as ONE fused dispatch per (engine, packet-bucket) -- n_dispatches
    no longer scales with the number of tree sizes (the whole bucket pads
    to k=8 and the packet bucket is taken at the bucket head)."""
    for extra in ({}, dict(engine="loop", max_slots=4000)):
        c = sweep.Campaign(name="kk", schemes=("host_pkt", "host_dr"),
                           loads=(sweep.WorkloadSpec("permutation", 4),),
                           trees=(4, 6, 8), seeds=(0,), **extra)
        p = sweep.plan(c)
        assert p.n_dispatches == p.n_shapes == 1
        assert {b.k for m in p.megabatches for b in m.members} == {4, 6, 8}
        assert p.megabatches[0].k_pad == 8


def test_cross_k_rand_jsq_loop_grid_one_dispatch_per_shape():
    """Acceptance (counter-stream randomness): a mixed-k loop campaign made
    ENTIRELY of rand/JSQ schemes -- the modes that used to key on raw k --
    plans to one dispatch per compiled shape, each fused across all three
    tree sizes at the bucket head."""
    c = sweep.Campaign(name="kk_rand",
                       schemes=("rsq", "jsq", "switch_pkt_ar"),
                       loads=(sweep.WorkloadSpec("permutation", 4),),
                       trees=(4, 6, 8), seeds=(0,),
                       engine="loop", max_slots=4000)
    p = sweep.plan(c)
    # rsq and jsq compile distinct port-choice branches; switch_pkt_ar is
    # jsq_quant.  Three shapes, three dispatches, each spanning all ks.
    assert p.n_dispatches == p.n_shapes == 3
    for m in p.megabatches:
        assert m.k_pad == 8
        assert {b.k for b in m.members} == {4, 6, 8}


def _axes_reversed(c):
    return dataclasses.replace(
        c, schemes=tuple(reversed(c.schemes)), loads=tuple(reversed(c.loads)),
        trees=tuple(reversed(c.trees)), seeds=tuple(reversed(c.seeds)),
        failures=tuple(reversed(c.failures)),
        g_converge=tuple(reversed(c.g_converge)))


@pytest.mark.parametrize("name", sorted(sweep.PRESETS))
def test_preset_planner_invariants(name):
    """Every CLI preset plans one dispatch per compiled shape, covers the
    full grid, and its fused keys are stable under grid permutation."""
    c = sweep.preset(name)
    p = sweep.plan(c)
    assert p.n_dispatches == p.n_shapes
    assert p.n_points == c.n_points
    assert sum(len(b.seeds) for m in p.megabatches
               for b in m.members) == c.n_points
    p2 = sweep.plan(_axes_reversed(c))
    assert {m.key for m in p2.megabatches} == {m.key for m in p.megabatches}
    assert p2.n_dispatches == p.n_dispatches


@pytest.mark.parametrize("name", sorted(sweep.PRESETS))
def test_preset_dispatches_independent_of_k_bucket_population(name):
    """How many k values share a bucket must not change the dispatch count:
    EVERY scheme (counter-stream randomness made rand/JSQ loop modes
    k-fusable too) keeps the *identical* fused keys whether the bucket
    holds one tree or three."""
    c = sweep.preset(name)
    base_k = max(c.trees)
    ks = tuple(k for k in (base_k, base_k - 2, base_k - 4)
               if k >= max(4, -(-base_k // 2)))
    p1 = sweep.plan(dataclasses.replace(c, trees=(base_k,)))
    pn = sweep.plan(dataclasses.replace(c, trees=ks))
    assert ({m.key for m in pn.megabatches}
            == {m.key for m in p1.megabatches})
    assert pn.n_dispatches == p1.n_dispatches


@pytest.mark.parametrize("name", sorted(sweep.PRESETS))
def test_preset_no_raw_k_fused_keys(name):
    """No fused key anywhere carries a raw tree size: every member's k maps
    to its campaign k-bucket head, which is what the key records -- even
    with rand/JSQ loop schemes spliced into the preset's grid."""
    c = sweep.preset(name)
    if c.engine == "loop":
        c = dataclasses.replace(
            c, schemes=tuple(c.schemes) + ("rsq", "jsq"))
    kmap = sweep.planner._kmap(c.trees)
    p = sweep.plan(c)
    assert p.n_dispatches == p.n_shapes
    for m in p.megabatches:
        assert {kmap[b.k] for b in m.members} == {m.k_pad}
    # The k recorded in a fused key is always a bucket head.
    heads = set(kmap.values())
    assert {m.k_pad for m in p.megabatches} <= heads


def test_scheme_shape_key_groups_pre_modes():
    assert lbs.host_pkt().shape_key() == lbs.ecmp().shape_key()
    assert lbs.host_pkt().shape_key() == lbs.host_dr().shape_key()
    assert lbs.simple_rr().shape_key() != lbs.host_pkt().shape_key()
    assert lbs.switch_pkt_ar().shape_key() != lbs.jsq().shape_key()
