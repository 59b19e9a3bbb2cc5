"""Slotted feedback engine (the "loop" simulator).

Complements ``fastsim``: a time-stepped ``lax.while_loop`` simulation carrying
the *feedback* the layered max-plus engine cannot: ECN-marked ACKs (REPS,
PLB), windowed congestion control (MSwift), SACK loss recovery, link failures
with routing-convergence time ``G``, and finite buffers with drops.

Model (one step = one data-packet slot):

  * every queue (5 fat-tree layers, finite capacity) serves one packet/slot;
  * served packets travel ``prop_slots`` and are enqueued at the next stage;
    edge/aggregation port choices follow the scheme (host labels / RR or OFAN
    pointers / (quantized) JSQ on live queue lengths);
  * queues mark ECN on enqueue above the marking threshold and drop when full;
  * deliveries generate ACKs returning after a constant ``ack_delay``.  ACKs
    are assumed never to queue (they are ~1.5% of a slot) but they consume the
    host NIC byte budget: hosts accumulate 'ack debt' and skip a data slot
    when it reaches one packet -- the App.-B interleaving to first order;
  * hosts pace with the ideal fixed-rate CCA at ``rho`` (§4 decoupling;
    ``rho = rho_max`` under failures) or with MSwift;
  * loss recovery: ideal rateless erasure coding (§4) or SACK with reordering
    threshold ``x`` (§8.2).

Failures: dead links black-hole packets silently before the convergence slot
``G``; from ``G`` on, switches use post-failure state (OFAN IWRR over W-ECMP
weights, RR/JSQ over locally-alive ports) and hosts re-draw labels among
valid paths.  Host-adaptive REPS additionally avoids dead paths *before*
convergence because labels that black-hole never return ACKs and hence are
never recycled into the pool -- the paper's key failure-resilience mechanism.
Dynamic fault schedules (``repro.faults.FaultSchedule``) generalize this to
E link-state *epochs*: every link-derived operand carries a leading epoch
axis the loop gathers by current slot, the physical state switching exactly
at each epoch start and the routing state a per-scheme reaction delay later
(host-visible schemes react with ``host_react``, switch-local ones with
``switch_react``); the static (links, g_converge) pair is the one-epoch
special case and stays bitwise-identical.

Dispatch granularities (mirroring ``fastsim``):

  * :func:`simulate` -- one (tree, workload, scheme, cfg, links, G) point,
    one seed;
  * :func:`simulate_batch` -- one point, many seeds, vmapped into a single
    jitted dispatch;
  * :func:`simulate_megabatch` -- many points sharing a pipeline identity
    fused onto one batch axis (scheme tables, DR/OFAN state, SACK
    scoreboards, MSwift cwnd state and buffer occupancy are all vmappable
    operands), optionally ``shard_map``-sharded across devices.

All three are bitwise-identical per point.  Batched variants run ONE
``lax.while_loop`` whose termination is ``jnp.all`` over per-row done flags
(the vmap batching rule for ``while_loop``): rows that finish early get
their slot updates masked out, so padding and co-batched slower rows never
perturb a finished row's state.  Shape padding (packet/flow axes to the
planner's power-of-two buckets, ``host_flows`` columns, OFAN order widths)
is bitwise-safe: pad flows have ``fsize = 0`` and therefore never become
sendable, pad packets are never referenced by any live flow, and padded
``host_flows`` slots rank below every real flow in the host round-robin.

In-loop randomness (rand spraying, JSQ tie-break noise) comes from the
stateless counter streams of :mod:`repro.core.entropy`: every draw is a
pure function of (seed, draw site, *logical* host/packet id, slot, port),
never of array shapes or batch position.  Hosts and packets are dense
prefixes of any padded id space, so a point padded onto a larger tree's
compiled engine -- or onto a fused megabatch axis -- draws bitwise-identical
values, which is what lets rand/JSQ switch schemes cross-tree-size fuse
like every other scheme (padded port columns are masked out of JSQ argmins
via :func:`~._batching.port_pad_penalty`).

Documented approximations (vs. an event-driven byte-level simulator):
  * ACK return time is constant (no ACK queueing);
  * the SACK sender picks retransmit sequence numbers from the receiver
    bitmap directly (its *trigger* is still ACK-driven);
  * same-slot arrivals at a switch are ranked by a consistent arbitration
    order for pointer schemes; JSQ choices within a slot see start-of-slot
    queue lengths.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .topology import FatTree, LinkState
from .workloads import Workload
from ._batching import (TreePad, pad_tail, pad_to_group_max,
                        port_pad_penalty, pow2_bucket, rank_by, shard_pad)
from ..core.lb_schemes import LBScheme, precompute_host_choices
from ..core import entropy as ent
from ..core import ofan as ofan_mod
from ..obs.probes import QueueProbe, probe_shape
from ..obs.stages import execute, fetch, scopes, stage

INT = jnp.int32


@dataclasses.dataclass
class LoopSimResult:
    delivered_slot: np.ndarray      # per-packet first-delivery slot (-1 never)
    flow_complete_slot: np.ndarray  # per-flow full-message-ACKed slot
    flow_data_done_slot: np.ndarray  # per-flow all-data-delivered slot
    cct_slots: float                # data CCT (max flow_data_done)
    cct_acked_slots: float          # ACK-complete CCT
    drops: int
    retransmissions: int
    max_queue: int
    avg_queue: float
    finished: bool
    mean_cwnd: float
    # Queue-occupancy time series (5 layers x samples windows), present only
    # when the point ran with a probe spec (repro.obs.probes); its max over
    # layers and time equals ``max_queue`` exactly.
    probe: Optional[QueueProbe] = None


@dataclasses.dataclass(frozen=True)
class LoopConfig:
    cca: str = "ideal"             # 'ideal' | 'mswift'
    loss: str = "erasure"          # 'erasure' | 'sack'
    rho: float = 1.0               # ideal CCA rate (rho_max under failures)
    prop_slots: int = 12
    ack_delay: int = 74            # return path: ~6*prop + serialization
    buffer_pkts: int = 195
    ecn_frac: float = 0.5          # marking threshold (fraction of buffer)
    sack_thresh: int = 32          # reordering threshold x (§8.2)
    rto_slots: int = 400
    ack_cost: float = 0.0206       # ack bytes / slot bytes (86/4178)
    bdp_pkts: int = 150
    max_slots: int = 200_000
    plb_alpha: int = 64            # PLB: min packets between label changes
    plb_beta: float = 0.4          # PLB: EWMA mark fraction trigger
    # MSwift (App. H): target delay = BDP + queueing component.
    sw_target_slots: float = 180.0
    sw_ai: float = 1.0
    sw_beta: float = 0.8
    sw_max_cwnd: float = 384.0
    # Engine body implementation: 'lax' (inline while_loop body), 'pallas'
    # (fused slot-step kernels, repro.kernels.slot_step; interpret-mode
    # off-TPU) or 'auto' (pallas where it wins: on TPU, or under
    # REPRO_PALLAS=interpret).  Bitwise-identical on integer outputs.
    impl: str = "lax"


def static_config(cfg: LoopConfig) -> LoopConfig:
    """The compile-relevant normalization of a LoopConfig.

    ``rho`` and ``max_slots`` ride as per-row *operands* in the jitted
    engine (so an rho_max axis or differing slot budgets share one
    executable), and the timing constants ``prop_slots``/``ack_delay``
    bucket to the next power of two: they only set the ``DELAY``/``ADELAY``
    ring-buffer *shapes*, while every ring index is taken modulo the
    point's real constants (per-row operands), so a timing sweep shares
    one compiled pipeline per bucket instead of compiling per point --
    rows past a point's real modulus stay at their init value and are
    never read, keeping results bitwise-identical to serial.  Every other
    field is baked into the compiled pipeline -- either through shapes
    (``buffer_pkts``) or through Python branches (``cca``, ``loss``,
    ``impl``).  Two points whose ``static_config`` are equal can fuse into
    one megabatch dispatch (mixed-``impl`` grids therefore plan one
    dispatch per impl).
    """
    return dataclasses.replace(
        cfg, rho=0.0, max_slots=0,
        prop_slots=pow2_bucket(max(int(cfg.prop_slots), 1)),
        ack_delay=pow2_bucket(max(int(cfg.ack_delay), 1)))


@dataclasses.dataclass(frozen=True)
class _Static:
    n: int; h: int; mid: int; F: int; P: int; Fh: int
    n_edges: int; n_aggs: int; n_pods: int
    edge_mode: str; agg_mode: str
    quanta: Optional[Tuple[float, ...]]
    adaptive_host: bool
    plb: bool
    cfg: LoopConfig                 # normalized via static_config()
    # Probe grid (stride, samples); (0, 0) = probes off.  Static: the series
    # buffer shape is baked into the compiled engine, so probed campaigns
    # still fuse into one dispatch per pipeline shape.
    probe: Tuple[int, int] = (0, 0)


@dataclasses.dataclass
class LoopPlan:
    """Seed-independent preparation of one (tree, workload, scheme, cfg,
    links, g_converge | fault) simulation point.

    Splitting this out of :func:`simulate` is what makes seed replication
    and point fusion batchable: everything here is identical across seeds,
    while :func:`_draw_seed_inputs` produces the per-seed operands that
    become the leading ``vmap`` axis in :func:`simulate_batch` /
    :func:`simulate_megabatch`.

    ``ep_links`` is the fault-epoch timeline (one entry, the static link
    state, when no schedule was given); every link-derived table carries a
    leading epoch axis the engine gathers by current slot.  ``pv`` mirrors
    it: one per-flow path-validity stack per epoch (or None).
    """
    tree: FatTree
    wl: Workload
    scheme: LBScheme
    cfg: LoopConfig
    links: LinkState                 # epoch-0 link state
    ep_links: list
    any_fail: bool
    pv: Optional[list]
    fsrc: np.ndarray
    fdst: np.ndarray
    static: _Static
    tables: dict

    @property
    def n_epochs(self) -> int:
        return len(self.ep_links)


def _prepare(tree: FatTree, wl: Workload, scheme: LBScheme,
             cfg: LoopConfig = LoopConfig(),
             links: Optional[LinkState] = None,
             g_converge: Optional[int] = None, probes=None,
             fault=None) -> LoopPlan:
    """Host-side precomputation shared by every seed of a simulation point.

    ``fault`` (a ``repro.faults.FaultSchedule``) is the dynamic alternative
    to the static ``links``/``g_converge`` pair: it compiles to an epoch
    timeline whose link states become stacked, slot-gathered operands, with
    per-scheme reaction delays replacing the single convergence slot.  The
    static pair lowers to the identical machinery with one epoch starting
    at slot 0 and reacting at ``g_converge``.
    """
    if cfg.impl not in ("lax", "pallas", "auto"):
        raise ValueError(f"LoopConfig.impl {cfg.impl!r}: expected "
                         f"'lax', 'pallas' or 'auto'")
    h = tree.half
    n = tree.n_hosts
    P = wl.n_packets
    F = wl.n_flows
    mid = tree.queues_per_mid_layer

    fsrc = wl.flow_src.astype(np.int32)
    fdst = wl.flow_dst.astype(np.int32)
    fsize = wl.flow_size.astype(np.int32)
    pkt_base = np.zeros(F + 1, dtype=np.int64)
    np.cumsum(fsize, out=pkt_base[1:])
    if not (wl.flow == np.repeat(np.arange(F), fsize)).all():
        raise ValueError("loopsim expects flow-contiguous packet layout")
    # Per-flow start gate (collective-phase schedules): a flow may not send
    # before its phase's start slot.  All-zero (every static workload) is
    # bitwise-inert in the engine's send mask.
    f_start = (np.zeros(F, dtype=np.int32) if wl.flow_start is None
               else np.asarray(wl.flow_start, dtype=np.int32))

    fp1 = tree.host_pod(fsrc).astype(np.int32)
    fe1 = tree.host_edge(fsrc).astype(np.int32)
    fp2 = tree.host_pod(fdst).astype(np.int32)
    fe2 = tree.host_edge(fdst).astype(np.int32)
    f_inter = fp1 != fp2
    f_leaves = f_inter | (fe1 != fe2)

    Fh = int(np.bincount(fsrc, minlength=n).max()) if F else 1
    host_flows = np.full((n, Fh), -1, dtype=np.int32)
    cnt = np.zeros(n, dtype=np.int64)
    for f, sh in enumerate(fsrc.tolist()):
        host_flows[sh, cnt[sh]] = f
        cnt[sh] += 1

    # ---- fault-epoch timeline ---------------------------------------------
    # Static (links, g_converge) lowers to a single epoch starting at slot 0
    # whose routing reacts at g_converge; a FaultSchedule compiles to E
    # epochs with per-scheme reaction delays.  Every link-derived table
    # below carries a leading epoch axis the engine gathers by slot.
    if fault is not None:
        if links is not None or g_converge is not None:
            raise ValueError("pass either fault= or links=/g_converge=, "
                             "not both")
        comp = fault.compile(tree)
        ep_links = list(comp.links)
        ep_start = np.asarray(comp.ep_start, np.int32)
        r_start = comp.react_starts(scheme.reaction_class())
    else:
        ep_links = [links if links is not None else LinkState.all_up(tree)]
        ep_start = np.zeros(1, np.int32)
        r_start = np.asarray(
            [g_converge if g_converge is not None else 2**30], np.int32)
    E = len(ep_links)
    links = ep_links[0]
    any_fail = any(l.any_failure() for l in ep_links)

    alive = np.stack([np.concatenate([
        l.ea.reshape(-1),                           # UP_E (pod,edge,agg)
        l.ac.reshape(-1),                           # UP_A (pod,agg,sub)
        l.ac.reshape(-1),                           # DN_C (pod,agg,sub)
        np.transpose(l.ea, (0, 2, 1)).reshape(-1),  # DN_A (pod,agg,edge)
        np.ones(n, bool)]) for l in ep_links])

    # Per-(switch, destination-group) valid port sets (W-ECMP reachability):
    # used by switch schemes after routing convergence.  Edge switches group
    # destinations by destination edge switch, aggregation switches by
    # destination pod (the same consolidation OFAN exploits).
    n_edges = tree.n_edge_switches
    n_aggs = tree.n_agg_switches

    def _port_lists(valid3d):  # (S, Gd, h) bool -> padded lists + counts
        S, Gd, _ = valid3d.shape
        ports = np.zeros((S * Gd, h), np.int32)
        cnts = np.zeros(S * Gd, np.int32)
        flat = valid3d.reshape(S * Gd, h)
        for i in range(S * Gd):
            alive_p = np.flatnonzero(flat[i])
            if len(alive_p) == 0:
                alive_p = np.arange(h)
            reps = int(np.ceil(h / len(alive_p)))
            ports[i] = np.tile(alive_p, reps)[:h]
            cnts[i] = len(alive_p)
        return ports, cnts

    def _wecmp_valid(l):
        # edge: valid uplink a for (src edge (p1,e1), dst edge (p2,e2))
        valid_e = np.zeros((n_edges, n_edges, h), bool)
        for se in range(n_edges):
            sp, sei = divmod(se, h)
            for de in range(n_edges):
                dp, dei = divmod(de, h)
                if se == de:
                    valid_e[se, de] = l.ea[sp, sei, :]
                    continue
                valid_e[se, de] = l.wecmp_edge_weights(sp, sei, dp, dei) > 0
        # agg: valid core sub-link c for (agg (p,a), dst pod)
        valid_a = np.zeros((n_aggs, tree.n_pods, h), bool)
        for ga in range(n_aggs):
            sp, ai = divmod(ga, h)
            for dp in range(tree.n_pods):
                if dp == sp:
                    valid_a[ga, dp] = l.ac[sp, ai, :]  # unused southbound
                else:
                    valid_a[ga, dp] = l.ac[sp, ai, :] & l.ac[dp, ai, :]
        return valid_e, valid_a

    e_ports = np.zeros((E, n_edges * n_edges, h), np.int32)
    e_pcnt = np.zeros((E, n_edges * n_edges), np.int32)
    a_ports = np.zeros((E, n_aggs * tree.n_pods, h), np.int32)
    a_pcnt = np.zeros((E, n_aggs * tree.n_pods), np.int32)
    e_dead = np.zeros((E, n_edges, n_edges, h), bool)
    a_dead = np.zeros((E, n_aggs, tree.n_pods, h), bool)
    for e_i, l in enumerate(ep_links):
        valid_e, valid_a = _wecmp_valid(l)
        e_ports[e_i], e_pcnt[e_i] = _port_lists(valid_e)
        a_ports[e_i], a_pcnt[e_i] = _port_lists(valid_a)
        e_dead[e_i] = ~valid_e
        a_dead[e_i] = ~valid_a

    # Path-validity matrices (seed-independent, rng-free): consumed by the
    # per-seed host-choice precompute and the REPS/PLB valid-label lists.
    # One (F, h, h) stack per epoch.
    pv = None
    if any_fail and (scheme.edge_mode == "pre" or scheme.adaptive_host):
        pv = [np.stack([l.path_matrix(int(s_), int(d_))
                        for s_, d_ in zip(fsrc, fdst)]) for l in ep_links]

    # Valid-path list per flow and epoch: post-convergence the W-ECMP rehash
    # maps any flow label onto an alive path (paper §5.2).  REPS/PLB labels.
    f_vpaths = np.tile(np.arange(h * h, dtype=np.int32), (E, F, 1))
    f_vcnt = np.full((E, F), h * h, dtype=np.int32)
    if any_fail and scheme.adaptive_host:
        for e_i in range(E):
            for fi in range(F):
                cand = np.flatnonzero(pv[e_i][fi].reshape(-1))
                if len(cand) == 0:
                    cand = np.arange(h * h)
                reps = int(np.ceil(h * h / len(cand)))
                f_vpaths[e_i, fi] = np.tile(cand, reps)[:h * h]
                f_vcnt[e_i, fi] = len(cand)

    static = _Static(
        n=n, h=h, mid=mid, F=F, P=P, Fh=Fh,
        n_edges=n_edges, n_aggs=n_aggs, n_pods=tree.n_pods,
        edge_mode=scheme.edge_mode, agg_mode=scheme.agg_mode,
        quanta=(tuple(scheme.quanta) if scheme.edge_mode == "jsq_quant"
                else None),
        adaptive_host=scheme.adaptive_host,
        plb=scheme.name == "host_flowlet_ar",
        cfg=static_config(cfg),
        probe=probe_shape(probes))

    tables = dict(
        fsrc=fsrc, fdst=fdst, fsize=fsize, pkt_base=pkt_base,
        fp1=fp1, fe1=fe1, fp2=fp2, fe2=fe2, f_start=f_start,
        f_inter=f_inter, f_leaves=f_leaves, host_flows=host_flows,
        alive=alive, ep_start=ep_start, r_start=r_start,
        e_ports=e_ports, e_pcnt=e_pcnt, a_ports=a_ports, a_pcnt=a_pcnt,
        e_dead=e_dead, a_dead=a_dead,
        f_vpaths=f_vpaths, f_vcnt=f_vcnt,
        rho=np.float32(cfg.rho), max_slots=np.int32(cfg.max_slots),
        # Logical port count: an operand, so a point padded onto a larger
        # tree's compiled engine still decodes labels / rotates pointers
        # over its own k/2 ports.
        h_log=np.int32(h),
        # Real timing constants: the compiled engine sizes its delay rings
        # from the pow2-bucketed static_config but indexes them modulo
        # these per-row values, so a timing sweep rides one compile.
        prop_slots=np.int32(cfg.prop_slots),
        ack_delay=np.int32(cfg.ack_delay),
    )
    return LoopPlan(tree=tree, wl=wl, scheme=scheme, cfg=cfg, links=links,
                    ep_links=ep_links, any_fail=any_fail, pv=pv,
                    fsrc=fsrc, fdst=fdst, static=static, tables=tables)


def _draw_seed_inputs(plan: LoopPlan, seed: int) -> dict:
    """Per-seed randomness, drawn in the exact order the pre-batching engine
    used so results stay bit-identical run-to-run and serial-to-batched.

    Fault epochs extend the sequential ``np.random`` stream *in epoch
    order* at the exact positions the static path draws its converged
    state: stale host choices first, then one converged draw per epoch,
    then the label pool / RR starts, then the stale OFAN tables, then one
    converged OFAN build per epoch.  A one-epoch plan therefore consumes
    the identical stream as the pre-schedule engine (bitwise goldens), and
    a failure-free plan aliases its converged state to the stale draw
    without consuming anything, as before.
    """
    tree, wl, scheme = plan.tree, plan.wl, plan.scheme
    h = tree.half
    P = wl.n_packets
    E = plan.n_epochs
    rng = np.random.default_rng(seed)
    key_lo, key_hi = ent.key_words(seed)

    a_stale = c_stale = a_conv = c_conv = None
    if scheme.edge_mode == "pre":
        pre_kw = dict(tree=tree, flow=wl.flow, seq=wl.seq, flow_src=plan.fsrc,
                      flow_dst=plan.fdst, rng=rng)
        a_stale, c_stale = precompute_host_choices(scheme, path_valid=None,
                                                   **pre_kw)
        if plan.any_fail:
            per_ep = [precompute_host_choices(scheme, path_valid=pv_e,
                                              **pre_kw) for pv_e in plan.pv]
            a_conv = np.stack([a for a, _ in per_ep])
            c_conv = np.stack([c for _, c in per_ep])
        else:
            a_conv = np.stack([a_stale] * E)
            c_conv = np.stack([c_stale] * E)

    rand_pool = rng.integers(0, h * h, size=65536).astype(np.int32)

    ofan_stale = None
    ofan_eps: list = []
    rr_starts_e = rng.integers(0, h, tree.n_edge_switches).astype(np.int32)
    rr_starts_a = rng.integers(0, h, tree.n_agg_switches).astype(np.int32)
    if scheme.edge_mode == "ofan":
        ofan_stale = ofan_mod.build_tables(tree, rng, links=None)
        ofan_eps = ([ofan_mod.build_tables(tree, rng, links=l)
                     for l in plan.ep_links]
                    if plan.any_fail else [ofan_stale] * E)

    return dict(
        a_stale=_z(a_stale, P), c_stale=_z(c_stale, P),
        a_conv=_ze(a_conv, E, P), c_conv=_ze(c_conv, E, P),
        rand_pool=rand_pool,
        rr_starts_e=rr_starts_e, rr_starts_a=rr_starts_a,
        ofan_e_orders=_tbl(ofan_stale, ofan_eps, "edge_orders", E),
        ofan_e_starts=_tbl(ofan_stale, ofan_eps, "edge_starts", E),
        ofan_e_len=_tbl(ofan_stale, ofan_eps, "edge_len", E),
        ofan_a_orders=_tbl(ofan_stale, ofan_eps, "agg_orders", E),
        ofan_a_starts=_tbl(ofan_stale, ofan_eps, "agg_starts", E),
        ofan_a_len=_tbl(ofan_stale, ofan_eps, "agg_len", E),
        # Counter-stream key words: the in-loop randomness operands.  Draws
        # are pure functions of (seed, site, logical id, slot), so they ride
        # any padding/batching unchanged (core.entropy).
        seed_lo=key_lo, seed_hi=key_hi,
    )


def _postprocess(out: dict, cfg: LoopConfig, n_packets: int,
                 n_flows: int, probes=None) -> LoopSimResult:
    """Assemble a LoopSimResult from one (unbatched) engine output tree,
    slicing off any shape-bucketing padding."""
    comp = out["flow_complete"][:n_flows]
    data_done = out["f_data_done"][:n_flows]
    f_cwnd = np.asarray(out["f_cwnd"][:n_flows], np.float32)
    finished = bool((comp >= 0).all())
    # Zero-flow workloads (msg_packets=0, empty phases): vacuously finished
    # at slot 0 -- the empty maxima below would raise.
    return LoopSimResult(
        delivered_slot=out["delivered_slot"][:n_packets],
        flow_complete_slot=comp,
        flow_data_done_slot=data_done,
        cct_slots=0.0 if n_flows == 0
        else float(data_done.max()) if (data_done >= 0).all()
        else float(cfg.max_slots),
        cct_acked_slots=0.0 if n_flows == 0
        else float(comp.max()) if finished else float(cfg.max_slots),
        drops=int(out["drops"]),
        retransmissions=int(out["rtx"]),
        max_queue=int(out["max_q"]),
        avg_queue=float(out["sum_q"]) / max(float(out["enq_events"]), 1.0),
        finished=finished,
        mean_cwnd=float(f_cwnd.mean()) if n_flows else 0.0,
        probe=(QueueProbe(probe_shape(probes)[0], np.asarray(out["q_probe"]))
               if "q_probe" in out else None),
    )


def simulate(tree: FatTree, wl: Workload, scheme: LBScheme,
             cfg: LoopConfig = LoopConfig(), seed: int = 0,
             links: Optional[LinkState] = None,
             g_converge: Optional[int] = None,
             probes=None, fault=None) -> LoopSimResult:
    """Run one collective on the slotted engine.

    ``links``: failed-link state (None = all up).  ``g_converge``: slot at
    which routing state converges; None => G = infinity (never converges).
    ``fault``: a ``repro.faults.FaultSchedule`` -- the dynamic alternative
    to the (links, g_converge) pair (mutually exclusive with it).
    """
    if wl.n_packets == 0:
        # The slotted engine gathers per-packet state each step, which
        # needs a packet axis of at least 1.  An all-degenerate workload
        # (msg_packets=0, or a phase schedule whose collectives are all
        # n<=1/zero-byte) runs as a one-point megabatch padded to one
        # inert packet row -- bitwise what the fused runner path does.
        return simulate_megabatch(
            [(tree, wl, scheme, cfg, [seed], links, g_converge, fault)],
            npk_pad=1, probes=probes)[0][0]
    plan = _prepare(tree, wl, scheme, cfg, links, g_converge, probes=probes,
                    fault=fault)
    tables = {**plan.tables, **_draw_seed_inputs(plan, seed)}
    out = jax.tree_util.tree_map(np.asarray, _run(plan.static, tables))
    return _postprocess(out, cfg, wl.n_packets, wl.n_flows, probes)


def simulate_batch(tree: FatTree, wl: Workload, scheme: LBScheme,
                   seeds, cfg: LoopConfig = LoopConfig(),
                   links: Optional[LinkState] = None,
                   g_converge: Optional[int] = None, probes=None,
                   fault=None) -> list:
    """Run one simulation point for many seeds as a single vmapped dispatch.

    Per-seed randomness (host labels, spray entropy, RR starts, OFAN
    rotation orders) is drawn host-side exactly as :func:`simulate` draws it
    and stacked onto a leading batch axis; seed-independent operands are
    broadcast.  The fused ``while_loop`` steps until every row's flows have
    completed (or hit ``max_slots``); finished rows freeze.  Results are
    bitwise-identical, per seed, to serial :func:`simulate` calls.
    """
    seeds = list(seeds)
    if not seeds:
        return []
    plan = _prepare(tree, wl, scheme, cfg, links, g_converge, probes=probes,
                    fault=fault)
    per_seed = [_draw_seed_inputs(plan, s) for s in seeds]
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *per_seed)
    out = jax.tree_util.tree_map(
        np.asarray, _run(plan.static, {**plan.tables, **stacked},
                         batch="seed"))
    return [_postprocess(jax.tree_util.tree_map(lambda x: x[i], out),
                         cfg, wl.n_packets, wl.n_flows, probes)
            for i in range(len(seeds))]


def _pipeline_identity(plan: LoopPlan) -> _Static:
    """Everything two plans must agree on to share one megabatched dispatch:
    scheme modes and the static LoopConfig fields.  Packet/flow/host-flow
    axes are padded, and tree dims pad to the group's largest k for EVERY
    scheme -- in-loop randomness comes from counter streams keyed on logical
    ids (``core.entropy``), so the draws survive padding."""
    return dataclasses.replace(plan.static, P=0, F=0, Fh=0, n=0, h=0, mid=0,
                               n_edges=0, n_aggs=0, n_pods=0)


def _repad_tables(st: dict, plan: LoopPlan, tp: TreePad) -> dict:
    """Re-lay one point's switch-/queue-id-indexed operands into the padded
    tree's id space (:class:`~._batching.TreePad`).  Host ids and per-flow
    coordinates are unchanged: real hosts are a dense prefix of the padded
    host space, and real (pod, edge/agg, port) coordinates are sparse in
    the padded switch/queue id spaces.  Padded queues stay empty (no real
    packet ever routes to one) and padded table rows are never indexed by a
    live flow, so dynamics match the standalone run exactly."""
    if tp.noop:
        return st
    pt = tp.padded
    st = dict(st)
    n_sw = pt.n_edge_switches            # == n_agg_switches
    mid_r = plan.tree.queues_per_mid_layer
    mid_p = pt.queues_per_mid_layer
    E = st["alive"].shape[0]

    # Per-queue aliveness (epoch-stacked): 4 mid layers scatter through the
    # queue-id map; padded queues read True, which is inert (nothing is
    # enqueued there).
    alive = np.ones((E, 4 * mid_p + pt.n_hosts), dtype=bool)
    for L in range(4):
        alive[:, L * mid_p + tp.mid] = st["alive"][:, L * mid_r:
                                                   (L + 1) * mid_r]
    st["alive"] = alive

    st["host_flows"] = pad_tail(st["host_flows"], 0, pt.n_hosts, fill=-1)
    # Valid-label lists keep their raw h_log-encoded entries; only the pool
    # axis widens (entries past a flow's own f_vcnt are never indexed).
    st["f_vpaths"] = pad_tail(st["f_vpaths"], 2, pt.half * pt.half)
    # W-ECMP valid-port lists: (switch, dst-group) rows scatter; the port
    # axis pads with zeros that sit beyond every row's count operand.
    # All carry a leading epoch axis, so table axes shift by one.
    st["e_ports"] = pad_tail(
        tp.scatter(st["e_ports"], tp.edge_pair, n_sw * n_sw, axis=1),
        2, pt.half)
    st["e_pcnt"] = tp.scatter(st["e_pcnt"], tp.edge_pair, n_sw * n_sw,
                              axis=1, fill=1)
    st["a_ports"] = pad_tail(
        tp.scatter(st["a_ports"], tp.agg_pod, n_sw * pt.n_pods, axis=1),
        2, pt.half)
    st["a_pcnt"] = tp.scatter(st["a_pcnt"], tp.agg_pod, n_sw * pt.n_pods,
                              axis=1, fill=1)
    st["e_dead"] = pad_tail(tp.scatter(
        tp.scatter(st["e_dead"], tp.switch, n_sw, axis=1, fill=True),
        tp.switch, n_sw, axis=2, fill=True), 3, pt.half, fill=True)
    st["a_dead"] = pad_tail(pad_tail(
        tp.scatter(st["a_dead"], tp.switch, n_sw, axis=1, fill=True),
        2, pt.n_pods, fill=True), 3, pt.half, fill=True)
    return st


def _repad_seed(d: dict, plan: LoopPlan, tp: TreePad) -> dict:
    """Scatter the per-seed switch tables (RR starts, OFAN pointer tables)
    into the padded tree's id space."""
    if tp.noop:
        return d
    pt = tp.padded
    d = dict(d)
    n_sw = pt.n_edge_switches
    d["rr_starts_e"] = tp.scatter(d["rr_starts_e"], tp.switch, n_sw)
    d["rr_starts_a"] = tp.scatter(d["rr_starts_a"], tp.switch, n_sw)
    if plan.scheme.edge_mode == "ofan":
        for pre, idx, n_ptr in (("ofan_e", tp.edge_pair, n_sw * n_sw),
                                ("ofan_a", tp.agg_pod, n_sw * pt.n_pods)):
            for suf in ("orders", "starts", "len"):
                d[f"{pre}_{suf}"] = tp.scatter(d[f"{pre}_{suf}"], idx,
                                               n_ptr, axis=1)
    return d


# Seed-independent per-point operands that carry a padded flow/packet axis.
# (f_start pads with 0; pad flows have fsize 0 and complete at slot 0, so
# their gate value never matters.)
_F_PAD0 = ("fsrc", "fdst", "fsize", "fp1", "fe1", "fp2", "fe2", "f_start")


def simulate_megabatch(items, *, npk_pad: Optional[int] = None,
                       n_shards=1, k_pad: Optional[int] = None,
                       probes=None) -> list:
    """Run many loop-engine simulation points as ONE fused, jitted dispatch.

    ``items`` is a sequence of ``(tree, wl, scheme, cfg, seeds, links,
    g_converge)`` tuples whose points lower to the same compiled engine
    (equal :func:`_pipeline_identity`: scheme modes and static LoopConfig
    fields -- ``rho``, ``max_slots`` and ``g_converge`` ride as per-row
    operands).  Per-seed inputs are drawn host-side exactly as
    :func:`simulate` draws them, padded to shared shapes (packet arrays up
    to ``npk_pad``, flow arrays and ``host_flows`` columns to group-wide
    maxima, OFAN order widths to the group maximum, switch/queue tables
    scattered into the padded ``k_pad`` tree's id space; pad flows have
    size 0 and are inert, padded switches and queues never see traffic),
    stacked onto one fused (scheme x load x failure x seed) batch axis, and
    executed by a single vmapped -- and, with ``n_shards > 1`` (or
    ``"auto"``), ``shard_map``-sharded -- dispatch whose ``while_loop``
    terminates once every row is done.

    ``k_pad`` (default: the largest tree among the items) is the fat-tree
    size every member's topology operands pad to; the planner passes the
    k-bucket head so campaigns sweeping tree size share one compile.
    Tree-size padding holds for EVERY scheme, including rand/JSQ switch
    modes: their in-loop draws come from the counter streams of
    ``core.entropy`` (keyed on seed, draw site, logical host/packet id and
    slot), so padding extends the id range the stream is evaluated over
    without perturbing any real entity's draws, and padded JSQ port columns
    are masked out of the argmin (``_batching.port_pad_penalty``).

    Items may also carry a trailing ``fault`` entry (a
    ``repro.faults.FaultSchedule``; 8-tuples) mixed freely with 7-tuple
    static items: fault-epoch axes pad to the group maximum (pad epochs
    repeat the last real epoch and start at an unreachable sentinel slot,
    so they are bitwise-inert), which is how static and flapping campaign
    rows fuse into one dispatch.

    Returns one list of :class:`LoopSimResult` per item (aligned with its
    ``seeds``); every result is bitwise-identical to the standalone
    :func:`simulate` call with the same arguments (tested in
    ``tests/test_loopsim.py`` and ``tests/test_differential.py``).
    """
    items = [(it[0], it[1], it[2], it[3], list(it[4]), it[5], it[6],
              it[7] if len(it) > 7 else None) for it in items]
    if not items or all(not it[4] for it in items):
        return [[] for _ in items]

    with stage("prep"):
        plans = [_prepare(t, w, s, c, l, g, probes=probes, fault=fz)
                 for (t, w, s, c, _, l, g, fz) in items]
        idents = {_pipeline_identity(p) for p in plans}
        if len(idents) > 1:
            raise ValueError(f"megabatch items span {len(idents)} pipeline "
                             f"identities; group by tree size, scheme loop "
                             f"shape and static LoopConfig first")

        k_max = max(p.tree.k for p in plans)
        k_pad = k_max if k_pad is None else max(int(k_pad), k_max)
        tree_pad = next((p.tree for p in plans if p.tree.k == k_pad),
                        FatTree(k_pad))
        pads = [TreePad(p.tree, tree_pad) for p in plans]

        P_max = max(p.wl.n_packets for p in plans)
        # The engine's per-step packet gathers need a non-empty packet axis
        # even when every member is degenerate (all-empty phase schedules).
        npk_pad = max(P_max if npk_pad is None
                      else max(int(npk_pad), P_max), 1)
        F_pad = max(p.wl.n_flows for p in plans)
        Fh_pad = max(p.static.Fh for p in plans)
        E_pad = max(p.n_epochs for p in plans)

        elems: list = []          # merged (static + per-seed) dicts, padded
        spans: list = []          # (item index, seed) per fused-axis element
        for i, ((tree, wl, scheme, cfg, seeds, links, g, fz),
                plan) in enumerate(zip(items, plans)):
            st = _repad_tables(plan.tables, plan, pads[i])
            # Fault-epoch padding: tables repeat their last real epoch; the
            # start operands pad with an unreachable sentinel slot, so the
            # epoch/reaction counters never index a pad epoch -- padded rows
            # are bitwise-inert, letting static and flapping points fuse.
            for k in ("alive", "e_ports", "e_pcnt", "a_ports", "a_pcnt",
                      "e_dead", "a_dead", "f_vpaths", "f_vcnt"):
                st[k] = _pad_epochs(st[k], E_pad)
            for k in ("ep_start", "r_start"):
                st[k] = pad_tail(st[k], 0, E_pad, fill=2**30)
            # Flow-axis padding: pad flows have fsize 0, so they complete at
            # the first slot, never send, and never reference a packet;
            # pkt_base is edge-padded, so every pad flow's base equals the
            # real packet count and the engine's packet->flow table
            # (packet_flows) still maps each real packet to its real flow.
            st["pkt_base"] = pad_tail(st["pkt_base"], 0, F_pad + 1,
                                      fill=int(st["pkt_base"][-1]))
            for k in _F_PAD0:
                st[k] = pad_tail(st[k], 0, F_pad)
            st["f_inter"] = pad_tail(st["f_inter"], 0, F_pad, fill=False)
            st["f_leaves"] = pad_tail(st["f_leaves"], 0, F_pad, fill=False)
            st["f_vpaths"] = pad_tail(st["f_vpaths"], 1, F_pad)
            st["f_vcnt"] = pad_tail(st["f_vcnt"], 1, F_pad, fill=1)
            # Padded host_flows columns hold -1 and rank below every real flow
            # in the host round-robin, so picks (and hence all sends) match the
            # unpadded point exactly.
            st["host_flows"] = pad_tail(st["host_flows"], 1, Fh_pad, fill=-1)
            for s in seeds:
                d = {**st, **_repad_seed(_draw_seed_inputs(plan, s), plan,
                                         pads[i])}
                for k in ("a_stale", "c_stale"):
                    d[k] = pad_tail(d[k], 0, npk_pad)
                for k in ("a_conv", "c_conv"):
                    d[k] = pad_tail(_pad_epochs(d[k], E_pad), 1, npk_pad)
                # OFAN stacks lead with the [stale, epoch...] axis: 1 + E.
                for k in ("ofan_e_orders", "ofan_e_starts", "ofan_e_len",
                          "ofan_a_orders", "ofan_a_starts", "ofan_a_len"):
                    d[k] = _pad_epochs(d[k], 1 + E_pad)
                elems.append(d)
                spans.append((i, s))

        # OFAN rotation orders are padded to the group-wide width; entries past
        # a row's own table length are never indexed (pointers wrap modulo the
        # per-group length operand).
        for key in ("ofan_e_orders", "ofan_a_orders"):
            widths = pad_to_group_max([d[key] for d in elems])
            for d, arr in zip(elems, widths):
                d[key] = arr

        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *elems)

        n_batch = len(elems)
        if n_shards == "auto":
            n_shards = max(1, min(len(jax.devices()), n_batch))
        n_shards = int(n_shards)
        stacked = shard_pad(stacked, n_batch, n_shards)

        static = dataclasses.replace(
            plans[0].static, P=npk_pad, F=F_pad, Fh=Fh_pad,
            n=tree_pad.n_hosts, h=tree_pad.half,
            mid=tree_pad.queues_per_mid_layer,
            n_edges=tree_pad.n_edge_switches, n_aggs=tree_pad.n_agg_switches,
            n_pods=tree_pad.n_pods)
        fn = _compiled(static, _shapes(stacked), "mega", n_shards)
    out = fetch(execute(fn, *(stacked[k] for k in _ARG_ORDER)))

    results = [dict() for _ in items]
    with stage("post"):
        for b, (i, s) in enumerate(spans):
            out_b = jax.tree_util.tree_map(lambda x: x[b], out)
            results[i][s] = _postprocess(out_b, items[i][3],
                                         plans[i].wl.n_packets,
                                         plans[i].wl.n_flows, probes)
    return [[results[i][s] for s in seeds]
            for i, (_, _, _, _, seeds, _, _, _) in enumerate(items)]


def _pad_epochs(x, e_pad, axis=0):
    """Pad an epoch-stacked table to ``e_pad`` epochs by repeating its last
    real epoch (inert: the sentinel-padded start operands guarantee the
    epoch counters never index past the real epochs)."""
    E = x.shape[axis]
    if E >= e_pad:
        return x
    last = np.take(x, [E - 1], axis=axis)
    return np.concatenate([x, np.repeat(last, e_pad - E, axis=axis)],
                          axis=axis)


def _z(x, P):
    return np.zeros(P, np.int32) if x is None else x.astype(np.int32)


def _ze(x, E, P):
    return np.zeros((E, P), np.int32) if x is None else x.astype(np.int32)


def _tbl(stale, eps, attr, n_ep):
    """Stack OFAN tables as [stale, epoch_0, ..., epoch_{E-1}] (the engine
    indexes this axis with the reaction-epoch counter directly: 0 = stale,
    1+e = converged on epoch e's links), width-padding ragged IWRR orders
    by tiling (entries past a group's ``len`` are never indexed)."""
    if stale is None:
        return np.zeros((1 + n_ep, 1, 1) if attr.endswith("orders")
                        else (1 + n_ep, 1), np.int32)
    arrs = [getattr(stale, attr)] + [getattr(e, attr) for e in eps]
    if arrs[0].ndim == 2 and len({a.shape[1] for a in arrs}) > 1:
        w = max(a.shape[1] for a in arrs)
        def padw(x):
            reps = int(np.ceil(w / x.shape[1]))
            return np.tile(x, (1, reps))[:, :w]
        arrs = [padw(a) for a in arrs]
    return np.stack(arrs)


# Positional order of the engine arguments; the first block is
# seed-independent (vmap in_axes=None in the seed-batched variant), the
# rest carry the seed batch axis.  In the megabatched variant *every*
# argument carries the fused (scheme x load x failure x seed) axis.
_STATIC_KEYS = ("fsrc", "fdst", "fsize", "pkt_base", "fp1", "fe1", "fp2",
                "fe2", "f_start", "f_inter", "f_leaves", "host_flows",
                "alive", "ep_start", "r_start",
                "e_ports", "e_pcnt", "a_ports", "a_pcnt", "e_dead", "a_dead",
                "f_vpaths", "f_vcnt", "rho", "max_slots", "h_log",
                "prop_slots", "ack_delay")
_SEED_KEYS = ("a_stale", "c_stale", "a_conv", "c_conv", "rand_pool",
              "rr_starts_e", "rr_starts_a",
              "ofan_e_orders", "ofan_e_starts", "ofan_e_len",
              "ofan_a_orders", "ofan_a_starts", "ofan_a_len",
              "seed_lo", "seed_hi")
_ARG_ORDER = _STATIC_KEYS + _SEED_KEYS


@functools.lru_cache(maxsize=32)
def _compiled(static: _Static, shapes: tuple, batch, n_shards: int):
    def fn(*args):
        return _engine(static, **dict(zip(_ARG_ORDER, args)))
    if batch == "mega":
        f = jax.vmap(fn, in_axes=(0,) * len(_ARG_ORDER))
        if n_shards > 1:
            from jax.sharding import Mesh, PartitionSpec
            mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("b",))
            # check_vma=False: every operand and output is sharded on the
            # fused axis, so there is no replication to track.
            f = jax.shard_map(f, mesh=mesh, in_specs=PartitionSpec("b"),
                              out_specs=PartitionSpec("b"), check_vma=False)
        return jax.jit(f)
    if batch == "seed":
        in_axes = tuple(0 if k in _SEED_KEYS else None for k in _ARG_ORDER)
        return jax.jit(jax.vmap(fn, in_axes=in_axes))
    return jax.jit(fn)


def _shapes(tables: dict) -> tuple:
    return tuple(sorted((k, np.shape(v)) for k, v in tables.items()))


def _run(static: _Static, tables: dict, batch=False, n_shards: int = 1):
    fn = _compiled(static, _shapes(tables), batch, int(n_shards))
    return fn(*(jnp.asarray(tables[k]) for k in _ARG_ORDER))


def packet_flows(pkt_base, P: int):
    """Flow of every packet id in ``[0, P)``: the last flow whose first
    packet id (``pkt_base``, ascending, one entry per flow plus the end)
    is at most the id.  Built once per dispatch, so the slot body maps a
    packet to its flow with one gather instead of a binary search."""
    ids = jnp.arange(P, dtype=INT)
    return (jnp.searchsorted(pkt_base, ids, side="right") - 1).astype(INT)


def _engine(s: _Static, *, fsrc, fdst, fsize, pkt_base, fp1, fe1, fp2, fe2,
            f_start, f_inter, f_leaves, host_flows, alive, ep_start, r_start,
            e_ports, e_pcnt, a_ports, a_pcnt, e_dead, a_dead,
            f_vpaths, f_vcnt, rho, max_slots, h_log, prop_slots, ack_delay,
            a_stale, c_stale, a_conv, c_conv, rand_pool,
            rr_starts_e, rr_starts_a,
            ofan_e_orders, ofan_e_starts, ofan_e_len,
            ofan_a_orders, ofan_a_starts, ofan_a_len, seed_lo, seed_hi):
    cfg = s.cfg
    n, h, mid, F, P, Fh = s.n, s.h, s.mid, s.F, s.P, s.Fh
    CAP = cfg.buffer_pkts
    NQ = 4 * mid + n
    # Delay rings: *shapes* come from the pow2-bucketed static config
    # (DELAY_PAD/ADELAY_PAD rows), but every index is taken modulo the
    # point's real timing constants (per-row operands), so the real
    # modulus is always <= the ring size, rows past it keep their init
    # value and are never read, and a prop_slots/ack_delay sweep shares
    # one compiled pipeline per bucket -- bitwise-identical to serial.
    DELAY_PAD = max(cfg.prop_slots, 1) + 1
    DELAY = jnp.maximum(prop_slots, 1) + 1
    MOVE = 4 * mid + n
    ADELAY_PAD = cfg.ack_delay + 1
    ADELAY = ack_delay + 1
    ecn_t = max(1, int(cfg.ecn_frac * CAP))
    ecn_thresh = jnp.int32(ecn_t)
    # LoopConfig.impl: trace the inline lax body or the fused Pallas
    # slot-step kernels (repro.kernels.slot_step; 'auto' resolves to pallas
    # on TPU or under REPRO_PALLAS=interpret, lax elsewhere).  The kernels
    # are bitwise-identical to the inline code on integer outputs.
    use_pallas = False
    if cfg.impl != "lax":
        from ..kernels.slot_step import ops as _slot
        use_pallas = _slot.resolve_impl(cfg.impl) == "pallas"
    OFF = (0, mid, 2 * mid, 3 * mid, 4 * mid)
    PBASE = pkt_base[:F]
    with jax.named_scope("pkt_flow"):
        pkt_flow = packet_flows(pkt_base, P)
    # JSQ guard for tree-size padding: +1e9 on port columns >= h_log (the
    # all-zero no-op when this point runs unpadded).
    pad_pen = port_pad_penalty(h, h_log)

    st0 = dict(
        t=jnp.int32(0),
        qbuf=jnp.full((NQ, CAP), -1, INT),
        qhead=jnp.zeros((NQ,), INT),
        qcnt=jnp.zeros((NQ,), INT),
        dl_pkt=jnp.full((DELAY_PAD, MOVE), -1, INT),
        dl_q=jnp.zeros((DELAY_PAD, MOVE), INT),
        al_pkt=jnp.full((ADELAY_PAD, n), -1, INT),
        p_sent_t=jnp.full((P,), -1, INT),
        p_ecn=jnp.zeros((P,), bool),
        p_recv=jnp.zeros((P,), bool),
        p_deliv=jnp.full((P,), -1, INT),
        p_a=jnp.zeros((P,), INT),
        p_c=jnp.zeros((P,), INT),
        f_next=jnp.zeros((F,), INT),
        f_sent=jnp.zeros((F,), INT),
        f_acked=jnp.zeros((F,), INT),
        f_delivered=jnp.zeros((F,), INT),
        f_cum=jnp.zeros((F,), INT),
        f_hi=jnp.full((F,), -1, INT),
        f_complete=jnp.full((F,), -1, INT),
        # Zero-size flows (phase padding, msg_packets=0) are data-done at
        # slot 0, not at the first slot the delivery check can fire
        # (t + prop_slots).
        f_data_done=jnp.where(fsize > 0, INT(-1), INT(0)),
        f_last_ack_t=jnp.full((F,), -1, INT),
        f_lost=jnp.zeros((F,), INT),
        f_cwnd=jnp.full((F,), jnp.float32(min(cfg.bdp_pkts * 2.0,
                                              cfg.sw_max_cwnd))),
        f_last_dec=jnp.full((F,), -10**6, INT),
        f_label=(rand_pool[jnp.arange(F) % rand_pool.shape[0]]).astype(INT),
        f_label_cnt=jnp.zeros((F,), INT),
        f_mark_ewma=jnp.zeros((F,), jnp.float32),
        f_draw=jnp.arange(F, dtype=INT) * 31 + 1,
        pool_lab=jnp.zeros((F, 64), INT),
        pool_cnt=jnp.zeros((F,), INT),
        h_rr=jnp.zeros((n,), INT),
        h_credit=jnp.zeros((n,), jnp.float32),
        h_ackdebt=jnp.zeros((n,), jnp.float32),
        ptr_e=jnp.zeros((s.n_edges * s.n_edges,) if s.edge_mode == "ofan"
                        else (s.n_edges,), INT),
        ptr_a=jnp.zeros((s.n_aggs * s.n_pods,) if s.agg_mode == "ofan"
                        else (s.n_aggs,), INT),
        drops=jnp.int32(0),
        rtx=jnp.int32(0),
        max_q=jnp.int32(0),
        sum_q=jnp.float32(0.0),
        enq_events=jnp.int32(0),
    )
    if s.probe[1]:
        # Per-layer windowed queue maxima (repro.obs.probes); padded queues
        # are never enqueued to and read 0, so the series is
        # padding-invariant like every other output.
        st0["q_probe"] = jnp.zeros((5, s.probe[1]), INT)

    def slot(at, st_in):
        st = dict(st_in)
        t = st["t"]
        # Fault-epoch counters.  ``pe``: the *physical* epoch (whose links
        # black-hole packets) -- the number of epoch starts reached, minus
        # one.  ``cvg_i``: how many epochs the *routing* has reacted to
        # (r_start[e] = ep_start[e] + reaction delay, saturated host-side);
        # 0 means stale/failure-unaware, 1+e means converged on epoch e.
        # Pad epochs start at a 2**30 sentinel and never count.  The static
        # single-epoch path reduces to the old ``t >= G`` gate bitwise.
        pe = jnp.maximum(jnp.sum((t >= ep_start).astype(INT)) - 1, 0)
        cvg_i = jnp.sum((t >= r_start).astype(INT))
        converged = cvg_i > 0
        ci = cvg_i                       # OFAN [stale, epoch...] table index
        ric = jnp.maximum(cvg_i - 1, 0)  # index into converged epoch stacks

        # ---- 1. serve all queues -------------------------------------------
        at("serve")
        qcnt = st["qcnt"]
        has = qcnt > 0
        headpos = st["qhead"]
        popped = jnp.where(has, st["qbuf"][jnp.arange(NQ), headpos], -1)
        st["qhead"] = jnp.where(has, (headpos + 1) % CAP, headpos)
        st["qcnt"] = jnp.where(has, qcnt - 1, qcnt)

        # ---- 2. route popped packets ---------------------------------------
        at("route")
        qids = jnp.arange(NQ)
        stg = jnp.clip(qids // mid, 0, 4)
        pk = popped
        valid = pk >= 0
        pkc = jnp.maximum(pk, 0)
        pf = jnp.where(valid, pkt_flow[pkc], 0)
        a_ch = st["p_a"][pkc]
        c_ch = st["p_c"][pkc]
        p2 = fp2[pf]
        e2 = fe2[pf]
        nq_from_0 = jnp.where(f_inter[pf],
                              OFF[1] + (fp1[pf] * h + a_ch) * h + c_ch,
                              OFF[3] + (p2 * h + a_ch) * h + e2)
        nq_from_1 = OFF[2] + (p2 * h + a_ch) * h + c_ch
        nq_from_2 = OFF[3] + (p2 * h + a_ch) * h + e2
        nq_from_3 = OFF[4] + fdst[pf]
        nxt = jnp.select([stg == 0, stg == 1, stg == 2, stg == 3],
                         [nq_from_0, nq_from_1, nq_from_2, nq_from_3], -2)
        nxt = jnp.where(valid, nxt, -1)

        # ---- 3. deliveries (stage-4 pops) ----------------------------------
        at("deliver")
        deliv = valid & (nxt == -2)
        dt = t + prop_slots
        first_del = deliv & ~st["p_recv"][pkc]
        st["p_deliv"] = st["p_deliv"].at[jnp.where(first_del, pk, P)].set(
            dt, mode="drop")
        if use_pallas and cfg.loss == "sack":
            # Fused SACK scoreboard kernel: bitmap scatter + per-flow
            # first-missing window scan in one launch.  Legal here because
            # step 5's retransmit candidate reads the post-update bitmap
            # and nothing between writes ``p_recv`` or ``f_cum``; the
            # per-flow scan gathered at ``[sfv]`` below is bitwise-equal
            # to the inline per-lane scan.
            st["p_recv"], fm_flow = _slot.sack_update_scan(
                st["p_recv"], pk, deliv, st["f_cum"], fsize, PBASE,
                backend="pallas")
        else:
            st["p_recv"] = st["p_recv"].at[jnp.where(deliv, pk, P)].set(
                True, mode="drop")
        # Erasure coding is rateless: every delivered symbol counts toward
        # decoding; SACK needs unique packets.
        counts_delivery = deliv if cfg.loss == "erasure" else first_del
        st["f_delivered"] = st["f_delivered"].at[
            jnp.where(counts_delivery, pf, F)].add(1, mode="drop")
        data_done_now = (st["f_data_done"] < 0) & (st["f_delivered"] >= fsize)
        st["f_data_done"] = jnp.where(data_done_now, dt, st["f_data_done"])
        # ACKs: deliveries only come from DN_E pops (<= n)
        dn_pk = popped[OFF[4]:]
        dn_ok = deliv[OFF[4]:]
        st["al_pkt"] = st["al_pkt"].at[t % ADELAY, :].set(
            jnp.where(dn_ok, dn_pk, -1))

        # ---- 4. fabric moves ------------------------------------------------
        at("move")
        mover = valid & (nxt >= 0)
        dslot = (t + prop_slots) % DELAY
        st["dl_pkt"] = st["dl_pkt"].at[dslot, :4 * mid].set(
            jnp.where(mover, pk, -1)[:4 * mid])
        st["dl_q"] = st["dl_q"].at[dslot, :4 * mid].set(
            jnp.where(mover, nxt, 0)[:4 * mid])

        # ---- 5. host injection ----------------------------------------------
        at("inject")
        inflight = st["f_sent"] - st["f_acked"] - st["f_lost"]
        if cfg.cca == "ideal":
            window_ok = jnp.ones((F,), bool)
        else:
            window_ok = inflight.astype(jnp.float32) < st["f_cwnd"]
        if cfg.loss == "erasure":
            remaining = ((st["f_acked"] < fsize)
                         & (inflight < (fsize - st["f_acked"]) + cfg.bdp_pkts))
            need_rtx = jnp.zeros((F,), bool)
        else:
            gap = st["f_hi"] + 1 - st["f_cum"]
            need_rtx = (st["f_hi"] >= 0) & (gap > cfg.sack_thresh) & (
                st["f_cum"] < fsize)
            remaining = (st["f_next"] < fsize) | need_rtx
        # Phase gate (collective-phase schedules): a flow may not send
        # before its phase's start slot.  f_start == 0 everywhere (every
        # static workload) keeps the mask all-true -- bitwise-inert.
        sendable = (window_ok & remaining & (st["f_complete"] < 0)
                    & (t >= f_start))

        hf = host_flows
        hf_ok = jnp.where(hf >= 0, sendable[jnp.maximum(hf, 0)], False)
        rrp = st["h_rr"][:, None]
        prio = (jnp.arange(Fh)[None, :] - rrp) % Fh
        prio = jnp.where(hf_ok, prio, Fh + 1)
        pick = jnp.argmin(prio, axis=1)
        can_send = jnp.take_along_axis(hf_ok, pick[:, None], axis=1)[:, 0]
        st["h_credit"] = jnp.minimum(st["h_credit"] + rho, 4.0)
        debt_ok = st["h_ackdebt"] < 1.0
        st["h_ackdebt"] = jnp.where(~debt_ok, st["h_ackdebt"] - 1.0,
                                    st["h_ackdebt"])
        do_send = can_send & (st["h_credit"] >= 1.0) & debt_ok
        st["h_credit"] = jnp.where(do_send, st["h_credit"] - 1.0,
                                   st["h_credit"])
        st["h_rr"] = jnp.where(do_send, (pick + 1) % Fh,
                               st["h_rr"]).astype(INT)

        sf = jnp.where(do_send, hf[jnp.arange(n), pick], -1)
        sfv = jnp.maximum(sf, 0)
        seq_fresh = st["f_next"][sfv]
        if cfg.loss == "sack":
            if use_pallas:
                first_missing = fm_flow[sfv]
            else:
                base = st["f_cum"][sfv]
                offs = jnp.arange(64)[None, :]
                cand = jnp.minimum(base[:, None] + offs,
                                   fsize[sfv][:, None] - 1)
                got = st["p_recv"][PBASE[sfv][:, None] + cand]
                first_missing = cand[jnp.arange(n), jnp.argmin(got, axis=1)]
            is_rtx = need_rtx[sfv] & do_send
            seq = jnp.where(is_rtx, first_missing,
                            jnp.minimum(seq_fresh, fsize[sfv] - 1))
            # if no fresh left and not rtx-triggered, resend first missing too
            exhausted = (seq_fresh >= fsize[sfv]) & ~is_rtx & do_send
            seq = jnp.where(exhausted, first_missing, seq)
            is_rtx = is_rtx | exhausted
            st["rtx"] = st["rtx"] + is_rtx.sum()
        else:
            is_rtx = jnp.zeros((n,), bool)
            seq = jnp.where(seq_fresh < fsize[sfv], seq_fresh,
                            st["f_sent"][sfv] % jnp.maximum(fsize[sfv], 1))
        pid = (PBASE[sfv] + jnp.clip(seq, 0, fsize[sfv] - 1)).astype(INT)

        fresh_ok = do_send & ~is_rtx & (seq_fresh < fsize[sfv])
        st["f_next"] = st["f_next"].at[jnp.where(fresh_ok, sf, F)].add(
            1, mode="drop")
        first_send = do_send & (st["f_sent"][sfv] == 0)
        st["f_last_ack_t"] = st["f_last_ack_t"].at[
            jnp.where(first_send, sf, F)].set(t, mode="drop")
        st["f_sent"] = st["f_sent"].at[jnp.where(do_send, sf, F)].add(
            1, mode="drop")
        st["p_sent_t"] = st["p_sent_t"].at[jnp.where(do_send, pid, P)].set(
            t, mode="drop")

        # ---- 6. edge port choice for injected packets -----------------------
        at("edge_pick")
        # REPS / PLB label machinery
        draw_idx = (st["f_draw"][sfv] * 48271 + 12345) % rand_pool.shape[0]
        fresh_lab = rand_pool[draw_idx]
        has_pool = st["pool_cnt"][sfv] > 0
        pooled = st["pool_lab"][sfv, jnp.maximum(st["pool_cnt"][sfv] - 1, 0)]
        if s.adaptive_host and not s.plb:      # REPS
            lab = jnp.where(has_pool, pooled, fresh_lab)
            st["pool_cnt"] = st["pool_cnt"].at[
                jnp.where(do_send & has_pool, sf, F)].add(-1, mode="drop")
        elif s.plb:
            lab = st["f_label"][sfv]
        else:
            lab = fresh_lab
        st["f_draw"] = st["f_draw"] + jnp.zeros_like(st["f_draw"]).at[
            jnp.where(do_send, sf, F)].add(7, mode="drop")

        if s.edge_mode == "pre":
            if s.adaptive_host:
                # post-convergence W-ECMP rehash: labels land on valid paths.
                # Labels stay encoded in the point's own h_log port space so
                # the draw/recycle stream matches the standalone run even
                # when the point rides a larger padded tree's engine.
                eff = jnp.where(converged,
                                f_vpaths[ric, sfv, lab % f_vcnt[ric, sfv]],
                                lab)
                a_new = ((eff // h_log) % h_log).astype(INT)
                c_new = (eff % h_log).astype(INT)
            else:
                a_new = jnp.where(converged, a_conv[ric, pid], a_stale[pid])
                c_new = jnp.where(converged, c_conv[ric, pid], c_stale[pid])
        elif s.edge_mode == "rand":
            sw = (fp1[sfv] * h + fe1[sfv]).astype(INT)
            de = (fp2[sfv] * h + fe2[sfv]).astype(INT)
            gp = sw * s.n_edges + de
            # Per-host spray draw over the LOGICAL (a, c) label space, from
            # the counter stream keyed on (seed, host id, slot): identical
            # for every real host at any padding (hosts are a dense prefix;
            # padded hosts never send, so their draws are inert).
            r = ent.draw_int(seed_lo, seed_hi, ent.SITE_EDGE_RAND,
                             jnp.arange(n), t, h_log * h_log)
            a_naive = (r // h_log).astype(INT)
            a_live = e_ports[ric, gp,
                             r % jnp.maximum(e_pcnt[ric, gp], 1)].astype(INT)
            a_new = jnp.where(converged, a_live, a_naive)
            c_new = (r % h_log).astype(INT)
        elif s.edge_mode in ("rr", "rr_reset", "ofan"):
            sw = (fp1[sfv] * h + fe1[sfv]).astype(INT)
            north = do_send & f_leaves[sfv]
            de = (fp2[sfv] * h + fe2[sfv]).astype(INT)
            gp = sw * s.n_edges + de
            if s.edge_mode == "ofan":
                gid = gp
                rk = rank_by(gid, north)
                ctr = st["ptr_e"][gid] + rk
                L = jnp.maximum(ofan_e_len[ci, gid], 1)
                a_new = ofan_e_orders[
                    ci, gid, (ofan_e_starts[ci, gid] + ctr) % L].astype(INT)
                st["ptr_e"] = st["ptr_e"].at[
                    jnp.where(north, gid, st["ptr_e"].shape[0])].add(
                    1, mode="drop")
            else:
                rk = rank_by(sw, north)
                ctr = st["ptr_e"][sw] + rk
                # pre-convergence: all ports; post: W-ECMP-valid for dest
                naive = ((rr_starts_e[sw] + ctr) % h_log).astype(INT)
                pcn = jnp.maximum(e_pcnt[ric, gp], 1)
                live = e_ports[ric, gp,
                               (rr_starts_e[sw] + ctr) % pcn].astype(INT)
                a_new = jnp.where(converged, live, naive)
                st["ptr_e"] = st["ptr_e"].at[
                    jnp.where(north, sw, s.n_edges)].add(1, mode="drop")
            c_new = jnp.zeros((n,), INT)
        else:  # jsq / jsq_quant at edge
            sw = (fp1[sfv] * h + fe1[sfv]).astype(INT)
            de = (fp2[sfv] * h + fe2[sfv]).astype(INT)
            if use_pallas:
                # Fused occupancy-gather + in-kernel tie-break noise +
                # masked-argmin kernel (one VMEM-resident pass).
                a_new = _slot.jsq_pick(
                    st["qcnt"], OFF[0] + sw * h, jnp.arange(n, dtype=INT),
                    converged & e_dead[ric, sw, de], pad_pen,
                    seed_lo, seed_hi, t, site=ent.SITE_EDGE_JSQ,
                    quanta=s.quanta, cap=CAP, backend="pallas")
            else:
                qbase = OFF[0] + sw * h
                lens = st["qcnt"][qbase[:, None] + jnp.arange(h)[None, :]]
                # Tie-break noise from the counter stream keyed on (seed,
                # host id, slot, port lane): shape-independent, so the same
                # host sees the same noise at any padding/batch position.
                nz = ent.draw_uniform(seed_lo, seed_hi, ent.SITE_EDGE_JSQ,
                                      jnp.arange(n)[:, None], t,
                                      lane=jnp.arange(h)[None, :])
                if s.quanta is None:
                    score = lens.astype(jnp.float32) + nz * 1e-3
                else:
                    thr = jnp.asarray(s.quanta, jnp.float32) * CAP
                    bins = jnp.sum(lens[:, :, None] > thr[None, None, :],
                                   axis=2)
                    score = bins.astype(jnp.float32) + nz * 0.5
                score = score + pad_pen[None, :]
                score = score + jnp.where(converged & e_dead[ric, sw, de],
                                          1e9, 0.0)
                a_new = jnp.argmin(score, axis=1).astype(INT)
            c_new = jnp.zeros((n,), INT)

        st["p_a"] = st["p_a"].at[jnp.where(do_send, pid, P)].set(
            a_new, mode="drop")
        st["p_c"] = st["p_c"].at[jnp.where(do_send, pid, P)].set(
            c_new, mode="drop")
        st["f_label_cnt"] = st["f_label_cnt"].at[
            jnp.where(do_send, sf, F)].add(1, mode="drop")

        inj_q = jnp.where(f_leaves[sfv],
                          OFF[0] + (fp1[sfv] * h + fe1[sfv]) * h + a_new,
                          OFF[4] + fdst[sfv])
        st["dl_pkt"] = st["dl_pkt"].at[dslot, 4 * mid:].set(
            jnp.where(do_send, pid, -1))
        st["dl_q"] = st["dl_q"].at[dslot, 4 * mid:].set(
            jnp.where(do_send, inj_q, 0))

        # ---- 7. arrivals: agg uplink choice then enqueue ---------------------
        at("agg_pick")
        arr_slot = t % DELAY
        apk = st["dl_pkt"][arr_slot]
        aq = st["dl_q"][arr_slot]
        avalid = apk >= 0
        apkc = jnp.maximum(apk, 0)
        af = jnp.where(avalid, pkt_flow[apkc], 0)
        to_agg = avalid & (aq >= OFF[1]) & (aq < OFF[2])
        asw = jnp.clip((aq - OFF[1]) // h, 0, s.n_aggs - 1).astype(INT)
        gpa = asw * s.n_pods + fp2[af]
        if s.agg_mode in ("pre", "rand"):
            c_fin = st["p_c"][apkc]
            if s.agg_mode == "rand":
                # Per-packet draw over the LOGICAL core sub-links, keyed on
                # (seed, packet id, slot): the packet's identity -- not its
                # position in the (padding-sized) move list -- selects the
                # stream value, so draws survive any tree/batch padding.
                r = ent.draw_int(seed_lo, seed_hi, ent.SITE_AGG_RAND,
                                 apkc, t, h_log)
                c_live = a_ports[ric, gpa,
                                 r % jnp.maximum(a_pcnt[ric, gpa], 1)]
                c_fin = jnp.where(converged, c_live, r).astype(INT)
        elif s.agg_mode in ("rr", "rr_reset", "ofan"):
            if s.agg_mode == "ofan":
                gid = gpa
                rk = rank_by(gid, to_agg)
                ctr = st["ptr_a"][gid] + rk
                L = jnp.maximum(ofan_a_len[ci, gid], 1)
                c_fin = ofan_a_orders[
                    ci, gid, (ofan_a_starts[ci, gid] + ctr) % L].astype(INT)
                st["ptr_a"] = st["ptr_a"].at[
                    jnp.where(to_agg, gid, st["ptr_a"].shape[0])].add(
                    1, mode="drop")
            else:
                rk = rank_by(asw, to_agg)
                ctr = st["ptr_a"][asw] + rk
                naive = ((rr_starts_a[asw] + ctr) % h_log).astype(INT)
                pcn = jnp.maximum(a_pcnt[ric, gpa], 1)
                live = a_ports[ric, gpa,
                               (rr_starts_a[asw] + ctr) % pcn].astype(INT)
                c_fin = jnp.where(converged, live, naive)
                st["ptr_a"] = st["ptr_a"].at[
                    jnp.where(to_agg, asw, s.n_aggs)].add(1, mode="drop")
        elif not use_pallas:  # jsq at agg (inline; pallas fuses it below)
            qbase = OFF[1] + asw * h
            lens = st["qcnt"][qbase[:, None] + jnp.arange(h)[None, :]]
            # Noise keyed on (seed, arriving packet id, slot, port lane).
            nz = ent.draw_uniform(seed_lo, seed_hi, ent.SITE_AGG_JSQ,
                                  apkc[:, None], t,
                                  lane=jnp.arange(h)[None, :])
            if s.quanta is None:
                score = lens.astype(jnp.float32) + nz * 1e-3
            else:
                thr = jnp.asarray(s.quanta, jnp.float32) * CAP
                bins = jnp.sum(lens[:, :, None] > thr[None, None, :], axis=2)
                score = bins.astype(jnp.float32) + nz * 0.5
            score = score + pad_pen[None, :]
            score = score + jnp.where(converged & a_dead[ric, asw, fp2[af]],
                                      1e9, 0.0)
            c_fin = jnp.argmin(score, axis=1).astype(INT)
        fuse_agg = use_pallas and s.agg_mode not in ("pre", "rand", "rr",
                                                     "rr_reset", "ofan")
        if fuse_agg:
            # ---- 7+8 fused: agg JSQ pick + enqueue in one kernel pass ----
            (st["qbuf"], qcnt2, c_fin, enq_try, do_enq, occ_after,
             marked) = _slot.agg_jsq_enqueue(
                st["qbuf"], st["qhead"], st["qcnt"], alive[pe], apk, aq,
                to_agg, asw, converged & a_dead[ric, asw, fp2[af]], pad_pen,
                seed_lo, seed_hi, t, site=ent.SITE_AGG_JSQ, quanta=s.quanta,
                cap=CAP, ecn_thresh=ecn_t, off1=OFF[1], h=h,
                backend="pallas")
            st["p_c"] = st["p_c"].at[jnp.where(to_agg, apk, P)].set(
                c_fin, mode="drop")
        else:
            st["p_c"] = st["p_c"].at[jnp.where(to_agg, apk, P)].set(
                c_fin, mode="drop")
            aq = jnp.where(to_agg, OFF[1] + asw * h + c_fin, aq)

        # ---- 8. enqueue (drops, ECN, failure black-holing) -------------------
        at("enqueue")
        if use_pallas:
            if not fuse_agg:
                (st["qbuf"], qcnt2, enq_try, do_enq, occ_after,
                 marked) = _slot.enqueue(
                    st["qbuf"], st["qhead"], st["qcnt"], alive[pe], apk, aq,
                    avalid, cap=CAP, ecn_thresh=ecn_t, backend="pallas")
            st["drops"] = st["drops"] + (avalid & ~enq_try).sum()
            st["drops"] = st["drops"] + (enq_try & ~do_enq).sum()
            st["p_ecn"] = st["p_ecn"].at[jnp.where(marked, apk, P)].set(
                True, mode="drop")
            st["qcnt"] = qcnt2
        else:
            aqc = jnp.clip(aq, 0, NQ - 1)
            dead = ~alive[pe, aqc]
            enq_try = avalid & ~dead
            st["drops"] = st["drops"] + (avalid & dead).sum()
            rkq = rank_by(aq, enq_try)
            room = st["qcnt"][aqc] + rkq < CAP
            do_enq = enq_try & room
            st["drops"] = st["drops"] + (enq_try & ~room).sum()
            pos = (st["qhead"][aqc] + st["qcnt"][aqc] + rkq) % CAP
            st["qbuf"] = st["qbuf"].at[jnp.where(do_enq, aq, NQ),
                                       jnp.where(do_enq, pos, 0)].set(
                jnp.where(do_enq, apk, -1), mode="drop")
            occ_after = st["qcnt"][aqc] + rkq + 1
            marked = do_enq & (occ_after > ecn_thresh)
            st["p_ecn"] = st["p_ecn"].at[jnp.where(marked, apk, P)].set(
                True, mode="drop")
            st["qcnt"] = st["qcnt"].at[jnp.where(do_enq, aq, NQ)].add(
                1, mode="drop")
        st["max_q"] = jnp.maximum(st["max_q"], st["qcnt"].max())
        if s.probe[1]:
            # Same reduction point as max_q, split per fat-tree layer and
            # scattered into the slot's stride window (slots past the probe
            # horizon clamp into the last window), so the series max over
            # layers and time equals max_q exactly.
            p_stride, p_samples = s.probe
            si = jnp.minimum(t // p_stride, p_samples - 1)
            qc = st["qcnt"]
            lay = jnp.stack([qc[OFF[0]:OFF[1]].max(), qc[OFF[1]:OFF[2]].max(),
                             qc[OFF[2]:OFF[3]].max(), qc[OFF[3]:OFF[4]].max(),
                             qc[OFF[4]:].max()])
            st["q_probe"] = st["q_probe"].at[:, si].max(lay)
        st["sum_q"] = st["sum_q"] + jnp.where(do_enq, occ_after, 0).sum()
        st["enq_events"] = st["enq_events"] + do_enq.sum()
        st["dl_pkt"] = st["dl_pkt"].at[arr_slot].set(-1)

        # ---- 9. ACK processing -----------------------------------------------
        at("ack")
        ak = st["al_pkt"][(t + 1) % ADELAY]   # written ack_delay slots ago
        aok = ak >= 0
        akc = jnp.maximum(ak, 0)
        akf = jnp.where(aok, pkt_flow[akc], 0)
        st["al_pkt"] = st["al_pkt"].at[(t + 1) % ADELAY].set(-1)
        st["h_ackdebt"] = st["h_ackdebt"].at[
            jnp.where(aok, fsrc[akf], n)].add(cfg.ack_cost, mode="drop")
        st["f_acked"] = st["f_acked"].at[jnp.where(aok, akf, F)].add(
            1, mode="drop")
        st["f_last_ack_t"] = st["f_last_ack_t"].at[
            jnp.where(aok, akf, F)].set(t, mode="drop")
        aseq = (ak - PBASE[akf]).astype(INT)
        st["f_hi"] = st["f_hi"].at[jnp.where(aok, akf, F)].max(
            jnp.where(aok, aseq, -1), mode="drop")
        if cfg.loss == "sack":
            if use_pallas:
                st["f_cum"] = _slot.sack_advance(
                    st["p_recv"], st["f_cum"], fsize, PBASE,
                    backend="pallas")
            else:
                for _ in range(2):
                    cum = st["f_cum"]
                    offs = jnp.arange(4)[None, :]
                    cand = jnp.minimum(cum[:, None] + offs,
                                       fsize[:, None] - 1)
                    got = st["p_recv"][PBASE[:, None] + cand] & (
                        cum[:, None] + offs < fsize[:, None])
                    adv = jnp.sum(jnp.cumprod(got, axis=1),
                                  axis=1).astype(INT)
                    st["f_cum"] = jnp.minimum(cum + adv, fsize)
        mk = st["p_ecn"][akc]
        if s.adaptive_host and not s.plb:      # REPS recycle
            lab_back = st["p_a"][akc] * h_log + st["p_c"][akc]
            good = aok & ~mk
            pc0 = st["pool_cnt"][jnp.maximum(akf, 0)]
            st["pool_lab"] = st["pool_lab"].at[
                jnp.where(good, akf, F), jnp.minimum(pc0, 63)].set(
                lab_back, mode="drop")
            st["pool_cnt"] = jnp.minimum(
                st["pool_cnt"].at[jnp.where(good, akf, F)].add(
                    1, mode="drop"), 64)
        if s.plb:
            w = jnp.float32(0.125)
            dec = jnp.zeros((F,), jnp.float32).at[
                jnp.where(aok, akf, F)].add(1.0, mode="drop")
            inc = jnp.zeros((F,), jnp.float32).at[
                jnp.where(aok & mk, akf, F)].add(1.0, mode="drop")
            st["f_mark_ewma"] = (st["f_mark_ewma"] * (1 - w * dec)
                                 + w * inc)
            change = ((st["f_mark_ewma"] > cfg.plb_beta)
                      & (st["f_label_cnt"] > cfg.plb_alpha))
            newlab = rand_pool[(st["f_draw"] * 104729 + 13)
                               % rand_pool.shape[0]]
            st["f_label"] = jnp.where(change, newlab,
                                      st["f_label"]).astype(INT)
            st["f_label_cnt"] = jnp.where(change, 0,
                                          st["f_label_cnt"]).astype(INT)
            st["f_draw"] = st["f_draw"] + change.astype(INT)
        if cfg.cca == "mswift":
            delay = (t - st["p_sent_t"][akc]).astype(jnp.float32)
            over = delay > cfg.sw_target_slots
            cw = st["f_cwnd"]
            inc = jnp.where(aok & ~over,
                            cfg.sw_ai / jnp.maximum(cw[akf], 1.0), 0.0)
            cw = cw.at[jnp.where(aok, akf, F)].add(inc, mode="drop")
            can_dec = (t - st["f_last_dec"][akf]) > (ack_delay + prop_slots)
            factor = jnp.clip(1.0 - cfg.sw_beta
                              * (delay - cfg.sw_target_slots)
                              / jnp.maximum(delay, 1.0), 0.5, 1.0)
            dec_sel = aok & over & can_dec
            cw = cw.at[jnp.where(dec_sel, akf, F)].multiply(
                jnp.where(dec_sel, factor, 1.0), mode="drop")
            st["f_cwnd"] = jnp.clip(cw, 1.0, cfg.sw_max_cwnd)
            st["f_last_dec"] = st["f_last_dec"].at[
                jnp.where(dec_sel, akf, F)].set(t, mode="drop")

        # ---- 10. timeouts -----------------------------------------------------
        at("timeout")
        inflight2 = st["f_sent"] - st["f_acked"] - st["f_lost"]
        rto_fire = ((st["f_sent"] > 0) & (st["f_complete"] < 0)
                    & (inflight2 > 0)
                    & (t - st["f_last_ack_t"] > cfg.rto_slots))
        st["f_lost"] = st["f_lost"] + jnp.where(rto_fire, inflight2, 0)
        st["f_last_ack_t"] = jnp.where(rto_fire, t, st["f_last_ack_t"])
        if cfg.loss == "sack":
            st["f_next"] = jnp.where(rto_fire,
                                     jnp.minimum(st["f_next"], st["f_cum"]),
                                     st["f_next"])
        if cfg.cca == "mswift":
            st["f_cwnd"] = jnp.where(rto_fire, 1.0, st["f_cwnd"])  # freeze

        # ---- 11. flow completion ----------------------------------------------
        at("complete")
        if cfg.loss == "sack":
            done_now = (st["f_complete"] < 0) & (st["f_cum"] >= fsize)
        else:
            done_now = (st["f_complete"] < 0) & (st["f_acked"] >= fsize)
        st["f_complete"] = jnp.where(done_now, t, st["f_complete"])

        st["t"] = t + 1
        return st

    def step(st_in):
        # Each numbered stage's device operations carry its name (serve ...
        # complete) under ``slot`` in their HLO metadata.
        with scopes() as at:
            return slot(at, st_in)

    def cond(st):
        return (st["f_complete"] < 0).any() & (st["t"] < max_slots)

    with jax.named_scope("slot"):
        final = jax.lax.while_loop(cond, step, st0)
    out = {
        "delivered_slot": final["p_deliv"],
        "flow_complete": final["f_complete"],
        "f_data_done": final["f_data_done"],
        "drops": final["drops"],
        "rtx": final["rtx"],
        "max_q": final["max_q"],
        "sum_q": final["sum_q"],
        "enq_events": final["enq_events"],
        "f_cwnd": final["f_cwnd"],
    }
    if s.probe[1]:
        out["q_probe"] = final["q_probe"]
    return out
