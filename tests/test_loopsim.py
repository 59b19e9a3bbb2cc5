"""Slotted feedback engine: cross-validation against fastsim, transport
behavior (SACK/erasure/MSwift), and failure handling."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.net.topology import FatTree, LinkState, rho_max
from repro.net import workloads, fastsim, loopsim
from repro.core import lb_schemes as lbs


@pytest.fixture(scope="module")
def tree():
    return FatTree(4)


@pytest.fixture(scope="module")
def wl(tree):
    return workloads.permutation(tree, 32, np.random.default_rng(1),
                                 inter_pod_only=True)


CFG = loopsim.LoopConfig(max_slots=4000)


def test_all_flows_complete(tree, wl):
    res = loopsim.simulate(tree, wl, lbs.ofan(), CFG, seed=0)
    assert res.finished
    assert (res.flow_complete_slot >= 0).all()
    assert res.drops == 0


def test_engines_agree_on_ranking(tree, wl):
    """fastsim and loopsim must rank schemes identically (their dynamics
    differ in ACK modeling, so we compare orderings, not exact CCTs)."""
    ccts_fast, ccts_loop = {}, {}
    for name in ["simple_rr", "host_pkt", "ofan"]:
        ccts_fast[name] = fastsim.simulate(tree, wl, lbs.by_name(name),
                                           seed=2).cct
        ccts_loop[name] = loopsim.simulate(tree, wl, lbs.by_name(name),
                                           CFG, seed=2).cct_slots
    assert (ccts_fast["ofan"] < ccts_fast["host_pkt"]
            < ccts_fast["simple_rr"])
    assert (ccts_loop["ofan"] < ccts_loop["host_pkt"]
            < ccts_loop["simple_rr"])


def test_ofan_queue_bounded(tree, wl):
    res = loopsim.simulate(tree, wl, lbs.ofan(), CFG, seed=0)
    assert res.max_queue <= 6       # Theta(1) discipline


def test_sack_completes_and_counts_rtx(tree, wl):
    cfg = loopsim.LoopConfig(loss="sack", max_slots=4000, sack_thresh=8)
    res = loopsim.simulate(tree, wl, lbs.host_pkt(), cfg, seed=0)
    assert res.finished
    assert (res.delivered_slot >= 0).all()


def test_mswift_reins_in_rate(tree):
    """With a long message MSwift must keep queues near target (paper §8.3:
    the CCA throttles spraying schemes; OFAN needs no throttling)."""
    wl = workloads.permutation(tree, 256, np.random.default_rng(3),
                               inter_pod_only=True)
    cfg = loopsim.LoopConfig(cca="mswift", loss="sack", max_slots=20000,
                             sw_target_slots=80.0)
    spray = loopsim.simulate(tree, wl, lbs.host_pkt(), cfg, seed=0)
    ofan = loopsim.simulate(tree, wl, lbs.ofan(), cfg, seed=0)
    assert spray.finished and ofan.finished
    assert ofan.cct_slots <= spray.cct_slots
    assert ofan.mean_cwnd >= spray.mean_cwnd - 1e-6   # OFAN not throttled


def _links_with_failures(tree, p, seed0):
    for s in range(seed0, seed0 + 50):
        links = LinkState.random_failures(tree, p, np.random.default_rng(s))
        if links.any_failure():
            return links
    raise RuntimeError("no failures sampled")


def test_failures_blackhole_before_convergence(tree, wl):
    links = _links_with_failures(tree, 0.08, 4)
    res_inf = loopsim.simulate(tree, wl, lbs.host_pkt(),
                               loopsim.LoopConfig(max_slots=12000,
                                                  rto_slots=300),
                               seed=0, links=links, g_converge=None)
    res_0 = loopsim.simulate(tree, wl, lbs.host_pkt(),
                             loopsim.LoopConfig(max_slots=12000,
                                                rto_slots=300),
                             seed=0, links=links, g_converge=0)
    assert res_0.drops < res_inf.drops
    assert res_0.cct_slots <= res_inf.cct_slots


def test_host_ar_beats_switch_ar_under_slow_convergence(tree, wl):
    """§5.2 headline: HOST PKT AR (REPS) dominates SWITCH PKT AR at
    G = infinity because end-to-end label feedback routes around failures."""
    links = _links_with_failures(tree, 0.08, 7)
    cfg = loopsim.LoopConfig(max_slots=12000, rto_slots=250)
    host = loopsim.simulate(tree, wl, lbs.host_pkt_ar(), cfg, seed=1,
                            links=links, g_converge=None)
    switch = loopsim.simulate(tree, wl, lbs.switch_pkt_ar(), cfg, seed=1,
                              links=links, g_converge=None)
    assert host.finished
    assert host.cct_slots <= switch.cct_slots


def test_rho_max_prevents_overload(tree):
    links = LinkState.random_failures(tree, 0.15, np.random.default_rng(9))
    wl2 = workloads.permutation(tree, 48, np.random.default_rng(2),
                                inter_pod_only=True)
    rho = rho_max(tree, links, wl2.flow_src, wl2.flow_dst)
    if rho == 0.0:
        pytest.skip("disconnected flow in sampled failure")
    cfg = loopsim.LoopConfig(max_slots=20000, rho=float(rho), rto_slots=400)
    res = loopsim.simulate(tree, wl2, lbs.host_dr(), cfg, seed=0,
                           links=links, g_converge=0)
    assert res.finished


def test_ack_debt_slows_bidirectional_hosts(tree):
    """App. B: hosts that both send and receive pay the ACK serialization
    tax; CCT must exceed the pure one-way bound."""
    wl2 = workloads.permutation(tree, 64, np.random.default_rng(5),
                                inter_pod_only=True)
    res = loopsim.simulate(tree, wl2, lbs.ofan(), CFG, seed=0)
    # one-way send time is 64 slots; with ack debt ~2% and pipeline ~5 hops
    assert res.cct_slots >= 64 * 1.01


# ---------------------------------------------------------------------------
# Batched dispatch: bitwise parity with serial simulate.
# ---------------------------------------------------------------------------

def _assert_loop_equal(res, ref):
    np.testing.assert_array_equal(res.delivered_slot, ref.delivered_slot)
    np.testing.assert_array_equal(res.flow_complete_slot,
                                  ref.flow_complete_slot)
    np.testing.assert_array_equal(res.flow_data_done_slot,
                                  ref.flow_data_done_slot)
    assert res.cct_slots == ref.cct_slots
    assert res.cct_acked_slots == ref.cct_acked_slots
    assert res.drops == ref.drops
    assert res.retransmissions == ref.retransmissions
    assert res.max_queue == ref.max_queue
    assert res.avg_queue == ref.avg_queue
    assert res.finished == ref.finished
    assert res.mean_cwnd == ref.mean_cwnd


_CFGS = {
    "erasure": loopsim.LoopConfig(max_slots=4000),
    "sack": loopsim.LoopConfig(loss="sack", sack_thresh=8, max_slots=4000),
    "short_buffer": loopsim.LoopConfig(loss="sack", sack_thresh=8,
                                       buffer_pkts=20, max_slots=4000),
    "mswift": loopsim.LoopConfig(cca="mswift", loss="sack", max_slots=8000,
                                 sw_target_slots=80.0),
}


@pytest.mark.parametrize("cfg_name", sorted(_CFGS))
@pytest.mark.parametrize("scheme", ("host_pkt", "ofan"))
def test_batch_bitwise_identical_to_serial(tree, wl, cfg_name, scheme):
    """simulate_batch must reproduce serial simulate exactly per seed across
    the erasure / SACK / short-buffer / MSwift paths (rows finish at
    different slot counts; the fused while_loop masks finished rows)."""
    cfg = _CFGS[cfg_name]
    seeds = [0, 1, 2]
    batch = loopsim.simulate_batch(tree, wl, lbs.by_name(scheme), seeds, cfg)
    for s, res in zip(seeds, batch):
        _assert_loop_equal(res, loopsim.simulate(tree, wl,
                                                 lbs.by_name(scheme), cfg,
                                                 seed=s))


@pytest.mark.parametrize("cfg_name", ("sack", "mswift"))
def test_megabatch_bitwise_identical_to_serial(tree, wl, cfg_name):
    """One fused dispatch over two workloads with different packet AND flow
    counts (permutation vs all-to-all: the flow axis, host_flows columns and
    pkt_base all pad) must reproduce serial simulate exactly, per point."""
    cfg = _CFGS[cfg_name]
    wl_b = workloads.all_to_all(tree, 2)
    items = [(tree, wl, lbs.host_pkt(), cfg, [0, 1], None, None),
             (tree, wl_b, lbs.host_dr(), cfg, [0], None, None)]
    out = loopsim.simulate_megabatch(items, npk_pad=1024)
    for (t, w, sch, c, seeds, l, g), results in zip(items, out):
        for s, res in zip(seeds, results):
            assert res.delivered_slot.shape[0] == w.n_packets
            assert res.flow_complete_slot.shape[0] == w.n_flows
            _assert_loop_equal(res, loopsim.simulate(t, w, sch, c, seed=s))


def test_megabatch_fuses_failure_and_g_axes_bitwise(tree, wl):
    """Failure pattern, g_converge, rho and max_slots are per-row operands:
    points differing only in them share one fused dispatch and stay
    bitwise-identical to serial."""
    links = _links_with_failures(tree, 0.08, 4)
    cfg_a = loopsim.LoopConfig(max_slots=12000, rto_slots=300, rho=0.8)
    cfg_b = loopsim.LoopConfig(max_slots=9000, rto_slots=300, rho=1.0)
    items = [(tree, wl, lbs.host_pkt_ar(), cfg_a, [0], links, 0),
             (tree, wl, lbs.host_pkt_ar(), cfg_a, [0], links, None),
             (tree, wl, lbs.host_pkt_ar(), cfg_b, [0, 1], None, None)]
    out = loopsim.simulate_megabatch(items)
    for (t, w, sch, c, seeds, l, g), results in zip(items, out):
        for s, res in zip(seeds, results):
            _assert_loop_equal(res, loopsim.simulate(t, w, sch, c, seed=s,
                                                     links=l, g_converge=g))


def test_megabatch_sharded_bitwise_identical(tree, wl, two_devices):
    """shard_map over the fused axis (2 virtual devices from conftest's
    XLA_FLAGS) must not change results; the 3-element batch also forces the
    shard-divisibility padding path (3 -> 4)."""
    cfg = _CFGS["sack"]
    items = [(tree, wl, lbs.ofan(), cfg, [0, 1, 2], None, None)]
    (results,) = loopsim.simulate_megabatch(items, n_shards="auto")
    for s, res in zip([0, 1, 2], results):
        _assert_loop_equal(res, loopsim.simulate(tree, wl, lbs.ofan(), cfg,
                                                 seed=s))


def test_megabatch_rejects_mixed_pipeline_identities(tree, wl):
    with pytest.raises(ValueError, match="pipeline identities"):
        loopsim.simulate_megabatch(
            [(tree, wl, lbs.host_pkt(), _CFGS["erasure"], [0], None, None),
             (tree, wl, lbs.host_pkt(), _CFGS["sack"], [0], None, None)])


# ---- zero-packet flows (msg_packets=0, degenerate phases) ------------------

def test_zero_packet_workload(tree):
    """An all-empty workload (every flow size 0) runs, finishes, and
    reports CCT 0 -- not the pipeline latency of the first delivery
    check, and not a crash on the empty maxima."""
    wl = workloads.permutation(tree, 0, np.random.default_rng(1))
    assert wl.n_packets == 0 and wl.n_flows > 0
    res = loopsim.simulate(tree, wl, lbs.host_pkt(),
                           loopsim.LoopConfig(max_slots=500), seed=0)
    assert res.finished
    assert res.cct_slots == 0.0 and res.cct_acked_slots == 0.0
    assert res.delivered_slot.shape == (0,)
    assert (res.flow_complete_slot == 0).all()
    assert (res.flow_data_done_slot == 0).all()


def test_mixed_zero_flows_inert(tree):
    """Flows of size 0 mixed into a real workload are inert: they complete
    at slot 0 and the nonzero flows run exactly as if the empty ones were
    absent (same packet layout contract the phase compiler relies on)."""
    fsize = np.array([3, 0, 2, 0, 1, 4, 0, 2])
    src = np.arange(8)
    dst = (np.arange(8) + 3) % tree.n_hosts
    mixed = workloads._packets_from_flows("mix", tree.n_hosts, src, dst,
                                          fsize)
    np.testing.assert_array_equal(
        np.asarray(mixed.flow), np.repeat(np.arange(8), fsize))
    cfg = loopsim.LoopConfig(max_slots=500)
    res = loopsim.simulate(tree, mixed, lbs.host_pkt(), cfg, seed=0)
    assert res.finished
    assert (res.flow_complete_slot[fsize == 0] == 0).all()
    assert (res.flow_data_done_slot[fsize == 0] == 0).all()
    keep = fsize > 0
    dense = workloads._packets_from_flows("dense", tree.n_hosts, src[keep],
                                          dst[keep], fsize[keep])
    ref = loopsim.simulate(tree, dense, lbs.host_pkt(), cfg, seed=0)
    np.testing.assert_array_equal(res.delivered_slot, ref.delivered_slot)
    assert res.cct_slots == ref.cct_slots


# ---- packet->flow table ------------------------------------------------------

def _mixed_zero_workload(tree):
    fsize = np.array([3, 0, 2, 0, 1, 4, 0, 2])
    src = np.arange(8)
    dst = (np.arange(8) + 3) % tree.n_hosts
    return workloads._packets_from_flows("mix", tree.n_hosts, src, dst, fsize)


@pytest.mark.parametrize("layout", ("serial", "megabatch"))
def test_packet_flow_table_matches_search(tree, wl, monkeypatch, layout):
    """The engine's packet->flow table gives, for every id of the padded
    packet axis, what a binary search of pkt_base gives, and the real flow
    of every real packet: zero-size flows (duplicate bases), an edge-padded
    pkt_base and a packet axis padded past the real packet count."""
    mixed = _mixed_zero_workload(tree)
    cfg = _CFGS["sack"]
    if layout == "serial":
        plan = loopsim._prepare(tree, mixed, lbs.host_pkt(), cfg)
        bases = plan.tables["pkt_base"][None]
        P, members = plan.static.P, [mixed]
    else:
        seen = {}
        real = loopsim.execute

        def spy(fn, *args):             # the dispatch's stacked operands
            seen.update(zip(loopsim._ARG_ORDER, args))
            return real(fn, *args)

        monkeypatch.setattr(loopsim, "execute", spy)
        loopsim.simulate_megabatch(
            [(tree, mixed, lbs.host_pkt(), cfg, [0], None, None),
             (tree, wl, lbs.host_dr(), cfg, [0], None, None)], npk_pad=1024)
        bases, P = seen["pkt_base"], seen["a_stale"].shape[-1]
        members = [mixed, wl]
        # The padding this test is about: the mixed row's pkt_base is
        # edge-padded to the wider workload's flow count, and the packet
        # axis runs past both rows' real packets.
        assert bases.shape[1] > mixed.n_flows + 1
        assert P > max(w.n_packets for w in members)
    assert (np.diff(bases[0]) == 0).any()     # zero-size flows share bases
    ids = np.arange(P)
    for pkt_base, w in zip(bases, members):
        table = np.asarray(loopsim.packet_flows(pkt_base, P))
        assert table.shape == (P,)
        np.testing.assert_array_equal(
            table, np.searchsorted(pkt_base, ids, side="right") - 1)
        np.testing.assert_array_equal(table[:w.n_packets], w.flow)


# ---- slot body structure ------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(x, "jaxpr", x)
            if hasattr(j, "eqns"):
                yield j


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for j in _sub_jaxprs(e):
            yield from _all_eqns(j)


@pytest.mark.parametrize("cfg_name", ("erasure", "sack", "mswift"))
@pytest.mark.parametrize("scheme", ("host_pkt_ar", "switch_pkt_ar", "ofan"))
def test_slot_body_holds_no_nested_loop(tree, wl, cfg_name, scheme):
    """The slot loop's body runs straight through: no inner ``while`` or
    ``scan`` (a binary search, or any library call that lowers to a loop,
    would repeat its trips in every slot of every row)."""
    cfg = dataclasses.replace(_CFGS[cfg_name], impl="lax")
    plan = loopsim._prepare(tree, wl, lbs.by_name(scheme), cfg)
    tables = {**plan.tables, **loopsim._draw_seed_inputs(plan, 0)}
    closed = jax.make_jaxpr(
        lambda *a: loopsim._engine(plan.static,
                                   **dict(zip(loopsim._ARG_ORDER, a))))(
        *(tables[k] for k in loopsim._ARG_ORDER))
    whiles = [e for e in _all_eqns(closed.jaxpr)
              if e.primitive.name == "while"]
    assert whiles
    slot = max(whiles, key=lambda e: len(e.params["body_jaxpr"].jaxpr.eqns))
    inner = [e.primitive.name
             for part in ("cond_jaxpr", "body_jaxpr")
             for e in _all_eqns(slot.params[part].jaxpr)
             if e.primitive.name in ("while", "scan")]
    assert inner == []
