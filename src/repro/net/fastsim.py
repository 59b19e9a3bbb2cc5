"""Layered max-plus fabric engine (the "fast" simulator).

TPU-idiomatic reformulation of a packet-level fat-tree simulation: with the
paper's uniform workloads (identical packet sizes, synchronized line-rate
senders) every queue is FIFO with unit service time (1 slot = one data-packet
serialization), so per-queue departure times obey the Lindley recursion

    d_i = max(a_i, d_{i-1}) + 1

which is an *associative* segmented max-plus scan: expanding,
``d_i = i + 1 + max_{j<=i, same queue}(a_j - j)``.  A 5-hop fat-tree traversal
therefore becomes five rounds of (lexsort by (queue, arrival), segmented
cumulative max, gather) -- dense, parallel, jit-compiled array ops instead of
an event loop.  The segmented cummax is the compute hot spot and has a Pallas
TPU kernel (``repro.kernels.lindley``); the default backend is
``jax.lax.associative_scan``.

Timing model
------------
* time unit: one data-packet slot ( (payload+header+gap) / line-rate );
* hosts pace at line rate (ideal fixed-rate CCA, §4) and carry a random
  fractional *phase* in [0,1): synchronized-but-not-atomically-aligned
  senders.  Phases are what give switch-local schemes (JSQ, RR) their
  "sticky flow" behavior (paper App. C) -- without sub-slot phases the
  arbitration would be ambiguous;
* propagation adds ``prop_slots`` per traversed link; it shifts arrival
  times but never changes queue dynamics;
* queue length seen by an arriving packet equals its waiting time in slots
  (unit service): ``occ_i = d_i - a_i - 1``.  Max/avg queue sizes and
  per-queue packet counts are derived from it.

Supported schemes: everything without ACK/ECN feedback -- ECMP, subflows,
host packet spraying, HOST DR, SIMPLE RR, SWITCH PKT (periodic re-permute),
RSQ, JSQ, SWITCH PKT AR (quantized JSQ), OFAN.  Feedback schemes (REPS, PLB,
MSwift) run on ``net.loopsim``.

Dynamic fault schedules (``repro.faults.FaultSchedule``, the ``fault=``
argument) time-slice the fabric into link-state epochs.  On this engine
failures act purely through *routing* (the max-plus pipeline has no drops):
each packet binds to the epoch whose reaction slot its integer release time
``wl.t_release`` has passed -- ``host_react`` delayed for host-visible
"pre" label choices (gathered host-side from per-epoch draws, so the
pipeline is unchanged) and ``switch_react`` delayed for switch-local OFAN
tables (an epoch axis on the pointer tables plus a per-packet seed-
independent ``ep_sw`` operand).  Binding at the seed-independent release
slot -- not the phase-adjusted arrival -- keeps the epoch map a static
operand shared by every seed.  rand/RR/JSQ port choices ignore link state
(exactly as they do under static failures here), so schedules are inert for
them by construction.  A single-epoch schedule is bitwise-identical to the
static ``links=`` path (tested in ``tests/test_faults.py``).

Dispatch granularities: :func:`simulate` (one point),
:func:`simulate_batch` (one point, seeds vmapped), and
:func:`simulate_megabatch` (many points sharing a pipeline shape fused onto
one batch axis, optionally ``shard_map``-sharded across devices) -- all
bitwise-identical per point.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .topology import (FatTree, LinkState, N_LAYERS, LAYER_NAMES,
                       UP_E, UP_A, DN_C, DN_A, DN_E)
from .workloads import Workload
from ._batching import (TreePad, pad_tail as _pad_tail, pad_to_group_max,
                        port_pad_penalty, shard_pad)
from ..core.lb_schemes import LBScheme, precompute_host_choices
from ..core import entropy as ent
from ..core import ofan as ofan_mod
from ..obs.probes import QueueProbe, probe_shape
from ..obs.stages import count, execute, fetch, scopes, stage

_NEG = -1.0e9


# ---------------------------------------------------------------------------
# Segmented max-plus scan.
# ---------------------------------------------------------------------------

def _segmented_cummax_ref(v: jnp.ndarray, seg_start: jnp.ndarray) -> jnp.ndarray:
    """Running max of ``v`` resetting wherever ``seg_start`` is True."""
    def combine(l, r):
        vl, fl = l
        vr, fr = r
        return jnp.where(fr, vr, jnp.maximum(vl, vr)), fl | fr
    out, _ = jax.lax.associative_scan(combine, (v, seg_start))
    return out


def segmented_cummax(v, seg_start, backend: str = "auto"):
    if backend in ("auto", "xla"):
        return _segmented_cummax_ref(v, seg_start)
    if backend == "pallas":
        from ..kernels.lindley import ops as _lops
        return _lops.segmented_cummax(v, seg_start, backend="pallas")
    raise ValueError(backend)


def _ranks_and_starts(sorted_gkey: jnp.ndarray,
                      backend: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Given group keys sorted ascending, return (rank within group, segment
    start flags)."""
    n = sorted_gkey.shape[0]
    if n == 0:      # zero-packet workload: no groups, no scan
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool))
    idx = jnp.arange(n, dtype=jnp.float32)
    flag = jnp.concatenate([jnp.ones((1,), bool),
                            sorted_gkey[1:] != sorted_gkey[:-1]])
    start = segmented_cummax(jnp.where(flag, idx, _NEG), flag, backend)
    rank = (idx - start).astype(jnp.int32)
    return rank, flag


# ---------------------------------------------------------------------------
# One queueing layer: Lindley over explicit queue ids.
# ---------------------------------------------------------------------------

def _lindley_layer(qid, a, tie, n_queues: int, backend: str):
    """FIFO service of one layer.  ``qid`` int32 (-1 => bypass).

    Returns (departure, counts[n_queues], occ): ``occ`` is the per-packet
    queue length seen on arrival (0 for bypass rows).  Occupancy sums are
    taken host-side over the unpadded packet slice so padding can never
    perturb the float reduction order (see :func:`_postprocess`).
    """
    npk = qid.shape[0]
    if npk == 0:    # zero-packet workload: the leading seg-start flag of
        # the scan below would be 1-long against 0-long values
        return a, jnp.zeros((n_queues,), jnp.int32), jnp.zeros((0,))
    real = qid >= 0
    qkey = jnp.where(real, qid, jnp.int32(2**30))
    order = jnp.lexsort((tie, a, qkey))
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(npk))
    qs = qkey[order]
    av = a[order]
    idx = jnp.arange(npk, dtype=jnp.float32)
    flag = jnp.concatenate([jnp.ones((1,), bool), qs[1:] != qs[:-1]])
    m = segmented_cummax(av - idx, flag, backend)
    d_sorted = m + idx + 1.0
    real_s = qs < 2**30
    d_sorted = jnp.where(real_s, d_sorted, av)   # bypass: no service
    d = d_sorted[inv]
    occ = jnp.where(real, d - a - 1.0, 0.0)      # queue length seen on arrival
    counts = jnp.zeros((n_queues,), jnp.int32).at[
        jnp.where(real, qid, 0)].add(jnp.where(real, 1, 0))
    return d, counts, occ


# ---------------------------------------------------------------------------
# Rank-based switch port selection (SIMPLE RR / SWITCH PKT / OFAN).
# ---------------------------------------------------------------------------

def _ranked_ports(gkey, a, tie, active, select_fn, backend, extra=None):
    """Sort active packets by (group pointer key, arrival), compute the rank of
    each packet within its group, and map rank -> port via ``select_fn(gid,
    rank)``.  Inactive packets get port 0 (unused): masking them -- rather
    than letting them keep the pseudo-rank of the discard group -- keeps the
    reported per-packet ports deterministic under shape-bucketing padding
    (pad rows join the discard group and would otherwise shift the ranks,
    and hence the garbage ports, of real bypass packets).  ``extra`` (an
    optional per-packet operand, e.g. the fault-epoch index) is carried
    through the sort and handed to ``select_fn(gid, rank, extra)``."""
    npk = gkey.shape[0]
    g = jnp.where(active, gkey, jnp.int32(2**30))
    order = jnp.lexsort((tie, a, g))
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(npk))
    gs = g[order]
    rank, _ = _ranks_and_starts(gs, backend)
    gid = jnp.where(gs < 2**30, gs, 0)
    if extra is None:
        port_sorted = select_fn(gid, rank)
    else:
        port_sorted = select_fn(gid, rank, extra[order])
    return jnp.where(active, port_sorted[inv], 0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# JSQ layers (adaptive switch): padded per-switch scan.
# ---------------------------------------------------------------------------

def _jsq_layer(switch, a, tie, active, *, n_switches: int, pad: int, h: int,
               h_log, quanta: Optional[Tuple[float, ...]], buffer_pkts: int,
               noise, backend: str):
    """Joint port-choice + FIFO service for one adaptive layer.

    Returns (port, departure, occ_seen, max_rank).  ``noise`` is
    (n_switches, pad, h) pre-drawn uniforms for random tie-breaking.
    ``max_rank`` is the deepest per-switch arrival rank seen; the caller
    compares it against the *logical* pad limit (an operand, so megabatched
    runs padded to a group-wide grid can still flag exactly the elements a
    standalone run would re-pad).
    """
    npk = switch.shape[0]
    skey = jnp.where(active, switch, jnp.int32(2**30))
    order = jnp.lexsort((tie, a, skey))
    ss = skey[order]
    av = a[order]
    rank, _ = _ranks_and_starts(ss, backend)
    max_rank = (jnp.max(jnp.where(ss < 2**30, rank, 0)) if npk
                else jnp.int32(0))

    valid = ss < 2**30
    # Inactive packets scatter to row n_switches, which is out of bounds and
    # therefore dropped -- they must never clobber grid cells owned by real
    # packets of switch 0.
    rows = jnp.where(valid, ss, jnp.int32(n_switches))
    cols = jnp.clip(rank, 0, pad - 1)
    t_grid = jnp.full((n_switches, pad), jnp.float32(_NEG)).at[rows, cols].set(
        jnp.where(valid, av, _NEG))
    v_grid = jnp.zeros((n_switches, pad), bool).at[rows, cols].set(valid)

    thresholds = None
    if quanta is not None:
        thresholds = jnp.asarray(quanta, jnp.float32) * buffer_pkts
    # Ports beyond the point's logical k/2 exist only because the grid is
    # padded to a larger tree's width (shared guard with the slotted engine).
    port_pen = port_pad_penalty(h, h_log)

    def step(d_last, inp):
        t, ok, nz = inp
        qlen = jnp.ceil(jnp.maximum(d_last - t, 0.0))
        if thresholds is None:
            score = qlen + nz * 1e-3          # JSQ, random tie-break
        else:
            bin_ = jnp.sum(qlen[:, None] > thresholds[None, :], axis=1)
            score = bin_.astype(jnp.float32) + nz * 0.5
        p = jnp.argmin(score + port_pen)
        d_new = jnp.maximum(t, d_last[p]) + 1.0
        d_next = jnp.where(ok, d_last.at[p].set(d_new), d_last)
        return d_next, (p.astype(jnp.int32), jnp.where(ok, d_new, t),
                        qlen[p])

    def per_switch(times, oks, nzs):
        init = jnp.full((h,), jnp.float32(_NEG))
        _, (ports, deps, occs) = jax.lax.scan(step, init, (times, oks, nzs))
        return ports, deps, occs

    ports_g, deps_g, occs_g = jax.vmap(per_switch)(t_grid, v_grid, noise)
    port_sorted = ports_g[rows, cols]
    dep_sorted = deps_g[rows, cols]
    occ_sorted = occs_g[rows, cols]
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(npk))
    port = jnp.where(active, port_sorted[inv], 0).astype(jnp.int32)
    dep = jnp.where(active, dep_sorted[inv], a)
    occ = jnp.where(active, occ_sorted[inv], 0.0)
    return port, dep, occ, max_rank


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LayerStats:
    counts: np.ndarray
    max_queue: float
    avg_wait: float


@dataclasses.dataclass
class FastSimResult:
    delivery: np.ndarray            # per-packet delivery time (slots)
    flow_completion: np.ndarray     # per-flow last-delivery (slots)
    cct: float                      # max over flows (slots)
    layers: Dict[str, LayerStats]
    max_queue: float                # max over all layers (packets)
    a_used: np.ndarray
    c_used: np.ndarray
    # Queue-occupancy time series, present only when the point ran with a
    # probe spec (see repro.obs.probes); per-layer max over the series
    # equals the corresponding LayerStats.max_queue exactly.
    probe: Optional[QueueProbe] = None

    def max_queue_layer(self, layer: int) -> float:
        return self.layers[LAYER_NAMES[layer]].max_queue


def _select_fn_for(mode: str, h, tables: dict):
    """Build select_fn(gid, rank)->port for rank-based modes.

    ``h`` is the *logical* port count of the point being simulated -- a
    per-row operand, not the compiled grid width: a point padded onto a
    larger tree's pipeline must still rotate over its own k/2 ports.
    """
    if mode == "rr":
        starts = tables["rr_starts"]          # (n_groups,)
        def f(gid, rank):
            return (starts[gid] + rank) % h
        return f
    if mode == "rr_reset":
        perms = tables["rr_perms"]            # (n_groups, n_epochs, h)
        starts = tables["rr_starts"]
        wraps = tables["reset_wraps"]
        n_epochs = perms.shape[1]
        def f(gid, rank):
            epoch = jnp.minimum(rank // (wraps * h), n_epochs - 1)
            return perms[gid, epoch, (starts[gid] + rank) % h]
        return f
    if mode == "ofan":
        orders = tables["orders"]             # (n_epochs, n_ptrs, W)
        starts = tables["starts"]             # (n_epochs, n_ptrs)
        lens = tables["lens"]                 # (n_epochs, n_ptrs)
        def f(gid, rank, ep):
            L = jnp.maximum(lens[ep, gid], 1)
            return orders[ep, gid, (starts[ep, gid] + rank) % L]
        return f
    raise ValueError(mode)


@dataclasses.dataclass
class SimPlan:
    """Seed-independent preparation of one (tree, workload, scheme, links)
    simulation point.

    Splitting this out of :func:`simulate` is what makes seed replication
    batchable: everything here is identical across seeds, while
    :func:`_draw_seed_inputs` produces the per-seed arrays that become the
    leading ``vmap`` axis in :func:`simulate_batch`.
    """
    tree: FatTree
    wl: Workload
    scheme: LBScheme
    prop_slots: float
    links: Optional[LinkState]
    backend: str
    jsq_pad_factor: float
    static_args: dict = dataclasses.field(default_factory=dict)
    # Fault-epoch state: one LinkState per epoch ([links] for static points),
    # per-epoch flow path matrices (None entries for failure-free epochs) and
    # the host-reaction epoch index of each packet (see _prepare).
    ep_links: list = dataclasses.field(default_factory=list)
    pv: Optional[list] = None
    ep_host: Optional[np.ndarray] = None
    n_reset_epochs: int = 1
    pad_e: int = 0
    pad_a: int = 0
    quanta: Optional[Tuple[float, ...]] = None
    tables_e_keys: Tuple[str, ...] = ()
    tables_a_keys: Tuple[str, ...] = ()

    @property
    def jsq(self) -> bool:
        return self.scheme.edge_mode in ("jsq", "jsq_quant")

    def build_run(self, batch, *, pad_e=None, pad_a=None, n_shards=1,
                  tree=None, probes=None):
        """``batch``: False | "seed" | "mega" (see :func:`_build_run`).
        ``pad_e``/``pad_a`` override the plan's own JSQ grid padding when a
        megabatch pads members to a group-wide maximum; ``tree`` overrides
        the plan's own tree when a megabatch pads members onto a k-bucket's
        largest fat tree.  ``probes`` (a ProbeSpec / (stride, samples)
        tuple) adds the per-layer queue-occupancy series output."""
        tree = self.tree if tree is None else tree
        scheme = self.scheme
        if batch is True:
            batch = "seed"
        probe_stride, probe_samples = probe_shape(probes)
        return _build_run(h=tree.half, n_pods=tree.n_pods,
                          n_edges=tree.n_edge_switches,
                          n_aggs=tree.n_agg_switches, n_hosts=tree.n_hosts,
                          edge_mode=scheme.edge_mode, agg_mode=scheme.agg_mode,
                          quanta=self.quanta, buffer_pkts=scheme.buffer_pkts,
                          reset_wraps=scheme.reset_wraps,
                          pad_e=self.pad_e if pad_e is None else pad_e,
                          pad_a=self.pad_a if pad_a is None else pad_a,
                          prop=float(self.prop_slots), backend=self.backend,
                          tables_e_keys=self.tables_e_keys,
                          tables_a_keys=self.tables_a_keys, batch=batch,
                          n_shards=n_shards, probe_stride=probe_stride,
                          probe_samples=probe_samples)


def _prepare(tree: FatTree, wl: Workload, scheme: LBScheme, prop_slots: float,
             links: Optional[LinkState], backend: str,
             jsq_pad_factor: float, fault=None) -> SimPlan:
    """Host-side precomputation shared by every seed of a simulation point."""
    if scheme.needs_feedback:
        raise ValueError(f"{scheme.name} needs ACK feedback; use net.loopsim")
    if fault is not None:
        if links is not None:
            raise ValueError("pass either links= or fault=, not both")
        comp = fault.compile(tree)
        ep_links = list(comp.links)
        links = ep_links[0]             # epoch-0 state for host-side consumers
        host_starts = comp.react_starts("host")
        switch_starts = comp.react_starts("switch")
    else:
        ep_links = [links]
        host_starts = switch_starts = np.zeros(1, np.int32)
    plan = SimPlan(tree=tree, wl=wl, scheme=scheme, prop_slots=prop_slots,
                   links=links, backend=backend, jsq_pad_factor=jsq_pad_factor)
    plan.ep_links = ep_links
    src, dst = wl.src, wl.dst
    p1 = tree.host_pod(src).astype(np.int32)
    e1 = tree.host_edge(src).astype(np.int32)
    p2 = tree.host_pod(dst).astype(np.int32)
    e2 = tree.host_edge(dst).astype(np.int32)
    inter_pod = (p1 != p2)
    leaves_edge = inter_pod | (e1 != e2)
    # Per-packet fault-epoch binding at the seed-independent integer release
    # slot: react starts are nondecreasing, so the epoch visible to packet p
    # is the last one whose reaction slot its release has passed (floored at
    # 0 -- pre-reaction routing sees the base epoch).  Static points get the
    # all-zeros map.
    ep_host = np.maximum(
        np.searchsorted(host_starts, wl.t_release, side="right") - 1,
        0).astype(np.int32)
    ep_sw = np.maximum(
        np.searchsorted(switch_starts, wl.t_release, side="right") - 1,
        0).astype(np.int32)
    plan.ep_host = ep_host
    plan.static_args = dict(p1=p1, e1=e1, p2=p2, e2=e2,
                            dst=dst.astype(np.int32), inter_pod=inter_pod,
                            leaves_edge=leaves_edge, ep_sw=ep_sw,
                            # Logical port count: an operand, so a point
                            # padded onto a larger tree's pipeline still
                            # rotates/sprays over its own k/2 ports.
                            h_log=np.int32(tree.half))

    # ---- path validity under failures (host visibility: converged state) --
    if scheme.edge_mode == "pre":
        pv = [np.stack([l.path_matrix(int(s), int(d))
                        for s, d in zip(wl.flow_src, wl.flow_dst)])
              if (l is not None and l.any_failure()) else None
              for l in ep_links]
        if any(x is not None for x in pv):
            plan.pv = pv

    h = tree.half
    plan.tables_e_keys = plan.tables_a_keys = scheme.table_keys()
    if scheme.edge_mode == "rr_reset":
        max_cnt = int(np.bincount(tree.host_global_edge(src)[leaves_edge],
                                  minlength=tree.n_edge_switches).max()
                      ) if leaves_edge.any() else 1
        plan.n_reset_epochs = max(
            1, int(np.ceil(max_cnt / (scheme.reset_wraps * h))))

    # ---- JSQ padding (workload-dependent, seed-independent) ----------------
    if plan.jsq:
        cnt_e = np.bincount(tree.host_global_edge(src)[leaves_edge],
                            minlength=tree.n_edge_switches)
        plan.pad_e = max(int(cnt_e.max()), 1)
        per_pod = np.bincount(p1[inter_pod], minlength=tree.n_pods)
        plan.pad_a = max(int(np.ceil(jsq_pad_factor * per_pod.max() / h)) + 64,
                         64)
    plan.quanta = (tuple(scheme.quanta) if scheme.edge_mode == "jsq_quant"
                   else None)
    # Logical JSQ pad limits travel as operands: a megabatch may execute this
    # point on a grid padded to a *group-wide* maximum, yet the overflow-and-
    # retry decision must match what a standalone run with this plan's own
    # padding would do.
    plan.static_args["pad_lim_e"] = np.int32(plan.pad_e if plan.jsq else 2**30)
    plan.static_args["pad_lim_a"] = np.int32(plan.pad_a if plan.jsq else 2**30)
    return plan


def _draw_seed_inputs(plan: SimPlan, seed: int) -> dict:
    """Per-seed randomness, drawn in the exact order the pre-batching engine
    used so results stay bit-identical run-to-run and serial-to-batched."""
    tree, wl, scheme = plan.tree, plan.wl, plan.scheme
    h = tree.half
    npk = wl.n_packets
    rng = np.random.default_rng(seed)

    phases = rng.random(wl.n_hosts).astype(np.float32)
    t_rel = (wl.t_release + phases[wl.src]).astype(np.float32)
    # Flow-static tie keys: consistent switch arbitration across slots (gives
    # RR/JSQ their sticky-flow behavior, App. C).
    tie = rng.random(wl.n_flows).astype(np.float32)[wl.flow]

    a_pre = c_pre = None
    if scheme.edge_mode == "pre":
        if plan.pv is None:
            a_pre, c_pre = precompute_host_choices(
                scheme, tree, wl.flow, wl.seq, wl.flow_src, wl.flow_dst, rng)
        else:
            # One sequential draw per epoch (epoch order extends the static
            # stream: a single-epoch schedule consumes exactly the static
            # path's draws), then gather each packet's host-reaction epoch.
            per_ep = [precompute_host_choices(
                scheme, tree, wl.flow, wl.seq, wl.flow_src, wl.flow_dst, rng,
                path_valid=pv_e) for pv_e in plan.pv]
            pk = np.arange(npk)
            a_pre = np.stack([a for a, _ in per_ep])[plan.ep_host, pk]
            c_pre = np.stack([c for _, c in per_ep])[plan.ep_host, pk]
        a_pre = a_pre.astype(np.int32)
        c_pre = c_pre.astype(np.int32)
    rand_a = rng.integers(0, h, npk).astype(np.int32)
    rand_c = rng.integers(0, h, npk).astype(np.int32)

    # ---- switch tables ------------------------------------------------------
    n_edges = tree.n_edge_switches
    n_aggs = tree.n_agg_switches
    tables_e: dict = {}
    tables_a: dict = {}
    if scheme.edge_mode in ("rr", "rr_reset"):
        tables_e["rr_starts"] = rng.integers(0, h, n_edges).astype(np.int32)
        tables_a["rr_starts"] = rng.integers(0, h, n_aggs).astype(np.int32)
        if scheme.edge_mode == "rr_reset":
            n_ep = plan.n_reset_epochs
            tables_e["rr_perms"] = np.argsort(
                rng.random((n_edges, n_ep, h)), axis=-1).astype(np.int32)
            tables_a["rr_perms"] = np.argsort(
                rng.random((n_aggs, n_ep, h)), axis=-1).astype(np.int32)
    elif scheme.edge_mode == "ofan":
        # One table build per fault epoch (epoch order; [links] for static
        # points, so E=1 consumes the static stream).  Pointer tables carry
        # an epoch axis -- width-padded to the widest epoch, pad columns
        # sit beyond every epoch's ``lens`` modulo and are never selected.
        ots = [ofan_mod.build_tables(tree, rng, links=l)
               for l in plan.ep_links]
        def _eps(arrs):
            return np.stack(pad_to_group_max([np.asarray(a) for a in arrs]))
        tables_e = {"orders": _eps([ot.edge_orders for ot in ots]),
                    "starts": _eps([ot.edge_starts for ot in ots]),
                    "lens": _eps([ot.edge_len for ot in ots])}
        tables_a = {"orders": _eps([ot.agg_orders for ot in ots]),
                    "starts": _eps([ot.agg_starts for ot in ots]),
                    "lens": _eps([ot.agg_len for ot in ots])}

    # JSQ tie-break noise comes from the counter streams (core.entropy),
    # keyed on (seed, site, logical switch id, arrival rank, port): the
    # same function the slotted engine evaluates in-loop, precomputed here
    # because the fast engine knows its arrival ranks host-side.  Growing
    # the rank axis (pad-overflow retry, megabatch group-wide padding)
    # extends the grid without perturbing existing entries.
    noise_e = noise_a = np.zeros((1, 1, 1), np.float32)
    if plan.jsq:
        noise_e = ent.uniform_grid(seed, ent.SITE_FAST_EDGE_JSQ,
                                   n_edges, plan.pad_e, h)
        noise_a = ent.uniform_grid(seed, ent.SITE_FAST_AGG_JSQ,
                                   n_aggs, plan.pad_a, h)

    return dict(t_rel=t_rel, tie=tie,
                a_pre=a_pre if a_pre is not None else np.zeros(npk, np.int32),
                c_pre=c_pre if c_pre is not None else np.zeros(npk, np.int32),
                rand_a=rand_a, rand_c=rand_c,
                noise_e=noise_e, noise_a=noise_a,
                te=tuple(np.asarray(tables_e[k]) for k in plan.tables_e_keys),
                ta=tuple(np.asarray(tables_a[k]) for k in plan.tables_a_keys))


def _postprocess(out: dict, wl: Workload, probes=None) -> FastSimResult:
    """Assemble a FastSimResult from one (unbatched) pipeline output tree."""
    delivery = out["delivery"]
    flow_completion = np.full(wl.n_flows, -np.inf)
    np.maximum.at(flow_completion, wl.flow, delivery)
    # Zero-packet flows (msg_packets=0, empty phases) receive no delivery
    # and would stay -inf; they complete instantly by definition.
    flow_completion[np.isneginf(flow_completion)] = 0.0
    layers = {}
    max_q = 0.0
    for li, name in enumerate(LAYER_NAMES):
        cnts = out["counts"][li]
        occ = np.asarray(out["occ"][li])
        mq = float(occ.max()) if occ.size else 0.0
        n_real = int(out["n_real"][li])
        # Host-side f64 sum over the (already unpadded) per-packet occupancy:
        # every dispatch granularity reduces the identical array, so padding
        # and fusion can never perturb the average through reduction order.
        aw = float(occ.sum(dtype=np.float64)) / max(n_real, 1)
        layers[name] = LayerStats(counts=cnts, max_queue=mq, avg_wait=aw)
        max_q = max(max_q, mq)
    probe = (QueueProbe(probe_shape(probes)[0], np.asarray(out["probe_q"]))
             if "probe_q" in out else None)
    return FastSimResult(delivery=delivery, flow_completion=flow_completion,
                         cct=float(delivery.max()) if delivery.size else 0.0,
                         layers=layers,
                         max_queue=max_q, a_used=out["a_used"],
                         c_used=out["c_used"], probe=probe)


def simulate(tree: FatTree, wl: Workload, scheme: LBScheme, seed: int = 0,
             prop_slots: float = 12.0, collect_stats: bool = True,
             links: Optional[LinkState] = None,
             backend: str = "auto", jsq_pad_factor: float = 4.0,
             probes=None, fault=None) -> FastSimResult:
    """Run one collective under ``scheme`` on the fast engine.

    ``fault`` (a ``repro.faults.FaultSchedule``) is the dynamic alternative
    to a static ``links`` pattern -- see the module docstring for the
    epoch-binding semantics on this engine.
    """
    plan = _prepare(tree, wl, scheme, prop_slots, links, backend,
                    jsq_pad_factor, fault=fault)
    run = plan.build_run(batch=False, probes=probes)
    out = run({**plan.static_args, **_draw_seed_inputs(plan, seed)})
    out = jax.tree_util.tree_map(np.asarray, out)
    if bool(out["overflow"]):
        if jsq_pad_factor > 64:
            raise RuntimeError("JSQ pad overflow even with huge padding")
        return simulate(tree, wl, scheme, seed=seed, prop_slots=prop_slots,
                        collect_stats=collect_stats, links=links,
                        backend=backend, jsq_pad_factor=jsq_pad_factor * 2,
                        probes=probes, fault=fault)
    return _postprocess(out, wl, probes)


def simulate_batch(tree: FatTree, wl: Workload, scheme: LBScheme,
                   seeds, prop_slots: float = 12.0,
                   collect_stats: bool = True,
                   links: Optional[LinkState] = None, backend: str = "auto",
                   jsq_pad_factor: float = 4.0, probes=None,
                   fault=None) -> list:
    """Run one simulation point for many seeds as a single vmapped dispatch.

    Per-seed randomness is drawn host-side exactly as :func:`simulate` draws
    it and stacked into a leading batch axis; the jitted pipeline is then
    ``jax.vmap``-ed over that axis, so the whole replicate set costs one
    compile + one dispatch.  Results are identical (bitwise, per seed) to
    serial :func:`simulate` calls; JSQ pad overflows are re-run with a larger
    pad only for the seeds that overflowed, matching the serial retry.
    """
    seeds = list(seeds)
    if not seeds:
        return []
    plan = _prepare(tree, wl, scheme, prop_slots, links, backend,
                    jsq_pad_factor, fault=fault)
    per_seed = [_draw_seed_inputs(plan, s) for s in seeds]
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *per_seed)
    run = plan.build_run(batch=True, probes=probes)
    out = run({**plan.static_args, **stacked})
    out = jax.tree_util.tree_map(np.asarray, out)

    results: dict = {}
    retry = []
    for i, s in enumerate(seeds):
        if bool(out["overflow"][i]):
            retry.append(s)
        else:
            out_i = jax.tree_util.tree_map(lambda x: x[i], out)
            results[s] = _postprocess(out_i, wl, probes)
    if retry:
        if jsq_pad_factor > 64:
            raise RuntimeError("JSQ pad overflow even with huge padding")
        redone = simulate_batch(tree, wl, scheme, retry,
                                prop_slots=prop_slots,
                                collect_stats=collect_stats, links=links,
                                backend=backend,
                                jsq_pad_factor=jsq_pad_factor * 2,
                                probes=probes, fault=fault)
        results.update(dict(zip(retry, redone)))
    return [results[s] for s in seeds]


# ---------------------------------------------------------------------------
# Megabatch: fuse (scheme x load x failure x seed) onto one batch axis.
# ---------------------------------------------------------------------------

# Per-packet pipeline arguments (padded to the bucketed packet count).
_PKT_KEYS = ("p1", "e1", "p2", "e2", "dst", "inter_pod", "leaves_edge",
             "ep_sw", "t_rel", "tie", "a_pre", "c_pre", "rand_a", "rand_c")


def _pipeline_identity(plan: SimPlan) -> Tuple:
    """Everything two plans must agree on to share one megabatched dispatch
    (shapes of per-packet arrays and JSQ grids are padded, and tree sizes
    pad to the group's largest k; this is the rest)."""
    return (plan.scheme.shape_key(), plan.tables_e_keys, plan.tables_a_keys,
            float(plan.prop_slots), plan.backend)


def _repad_elem(d: dict, plan: SimPlan, tp: TreePad) -> dict:
    """Re-lay one point's switch-id-indexed operands into the padded tree's
    id space (:class:`~._batching.TreePad`).  Per-packet coordinate arrays
    are untouched: real (pod, edge, port) coordinates are simply sparse in
    the padded id space, and the scatter maps are monotone, so every
    sort-based arbitration sees the same relative order as the standalone
    run.  Padded table rows are only ever indexed by inert pad packets."""
    if tp.noop:
        return d
    pt = tp.padded
    d = dict(d)
    n_sw = pt.n_edge_switches            # == n_agg_switches

    def _sw(x):
        return tp.scatter(x, tp.switch, n_sw)

    for key, keys, ptr_idx, n_ptr in (
            ("te", plan.tables_e_keys, tp.edge_pair, n_sw * n_sw),
            ("ta", plan.tables_a_keys, tp.agg_pod, n_sw * pt.n_pods)):
        tbl = dict(zip(keys, d[key]))
        if "rr_starts" in tbl:
            tbl["rr_starts"] = _sw(tbl["rr_starts"])
        if "rr_perms" in tbl:
            tbl["rr_perms"] = _sw(_pad_tail(tbl["rr_perms"], 2, pt.half))
        if "orders" in tbl:      # OFAN pointer tables, (n_epochs, n_ptr, W)
            tbl["orders"] = tp.scatter(tbl["orders"], ptr_idx, n_ptr, axis=1)
            tbl["starts"] = tp.scatter(tbl["starts"], ptr_idx, n_ptr, axis=1)
            tbl["lens"] = tp.scatter(tbl["lens"], ptr_idx, n_ptr, axis=1)
        d[key] = tuple(tbl[k] for k in keys)
    if plan.jsq:
        for k in ("noise_e", "noise_a"):
            d[k] = _sw(_pad_tail(d[k], 2, pt.half))
    return d


def simulate_megabatch(items, *, prop_slots: float = 12.0,
                       backend: str = "auto", jsq_pad_factor: float = 4.0,
                       npk_pad: Optional[int] = None, n_shards=1,
                       k_pad: Optional[int] = None, probes=None) -> list:
    """Run many simulation points as ONE fused, jitted dispatch.

    ``items`` is a sequence of ``(tree, wl, scheme, seeds, links)`` tuples
    -- optionally ``(tree, wl, scheme, seeds, links, fault)`` with a
    ``repro.faults.FaultSchedule`` sixth element (mixed freely with
    5-tuples; ``links`` must then be None) -- whose points lower to the
    same compiled pipeline (equal ``LBScheme.shape_key()``, same backend)
    -- e.g. flow_ecmp, subflow_mptcp, host_pkt and host_dr grids on any
    mix of workloads, failure patterns, fault schedules and tree sizes.
    Fault epochs are per-packet gather indices bounded by each member's
    own epoch count, so epoch axes simply zero-pad to the group maximum
    alongside the other table axes and static/flapping members fuse.  Per-seed inputs are drawn host-side
    exactly as :func:`simulate` draws them, padded to shared shapes (packet
    arrays up to ``npk_pad``, JSQ noise grids and scheme tables up to
    group-wide maxima, switch-indexed tables scattered into the padded
    ``k_pad`` tree's id space; pad packets are inert bypass rows with
    ``dst = -1`` and padded switches never receive traffic), stacked onto
    one fused batch axis, and executed by a single ``vmap``-ed -- and, with
    ``n_shards > 1`` (or ``"auto"``), ``shard_map``-sharded -- dispatch.

    ``k_pad`` (default: the largest tree among the items) is the fat-tree
    size every member's topology operands pad to; the planner passes the
    k-bucket head so campaigns sweeping tree size share one compile.

    Returns one list of :class:`FastSimResult` per item (aligned with its
    ``seeds``); every result is bitwise-identical to the standalone
    :func:`simulate` call with the same arguments, including the JSQ
    pad-overflow retry decision (tested in ``tests/test_sweep.py`` and
    ``tests/test_differential.py``).
    """
    items = [(it[0], it[1], it[2], list(it[3]), it[4],
              it[5] if len(it) > 5 else None) for it in items]
    if not items or all(not it[3] for it in items):
        return [[] for _ in items]

    with stage("prep"):
        plans = [_prepare(tree, wl, scheme, prop_slots, links, backend,
                          jsq_pad_factor, fault=fz)
                 for (tree, wl, scheme, _, links, fz) in items]
        idents = {_pipeline_identity(p) for p in plans}
        if len(idents) > 1:
            raise ValueError(f"megabatch items span {len(idents)} "
                             f"pipeline identities; group by "
                             f"LBScheme.shape_key() first")

        k_max = max(p.tree.k for p in plans)
        k_pad = k_max if k_pad is None else max(int(k_pad), k_max)
        tree_pad = next((p.tree for p in plans if p.tree.k == k_pad),
                        FatTree(k_pad))
        pads = [TreePad(p.tree, tree_pad) for p in plans]

        npk_max = max(p.wl.n_packets for p in plans)
        npk_pad = (npk_max if npk_pad is None
                   else max(int(npk_pad), npk_max))
        pad_e_m = max(p.pad_e for p in plans)
        pad_a_m = max(p.pad_a for p in plans)
        jsq = plans[0].jsq

        elems: list = []          # merged (static + per-seed) dicts, padded
        spans: list = []          # (item index, seed) per fused-axis element
        for i, ((tree, wl, scheme, seeds, links, fz), plan) in enumerate(
                zip(items, plans)):
            for s in seeds:
                d = _repad_elem({**plan.static_args,
                                 **_draw_seed_inputs(plan, s)}, plan,
                                pads[i])
                for k in _PKT_KEYS:
                    d[k] = _pad_tail(d[k], 0, npk_pad,
                                     fill=-1 if k == "dst" else 0)
                if jsq:
                    d["noise_e"] = _pad_tail(d["noise_e"], 1, pad_e_m)
                    d["noise_a"] = _pad_tail(d["noise_a"], 1, pad_a_m)
                elems.append(d)
                spans.append((i, s))

        # Scheme tables (RR permutation epochs, OFAN rotation orders) are
        # padded per-position to the group-wide maximum shape; padded entries
        # are only ever indexed by inert packets, whose outputs are
        # discarded.
        for key in ("te", "ta"):
            for j in range(len(elems[0][key])):
                padded = pad_to_group_max([d[key][j] for d in elems])
                for d, t in zip(elems, padded):
                    d[key] = d[key][:j] + (t,) + d[key][j + 1:]

        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *elems)

        n_batch = len(elems)
        if n_shards == "auto":
            n_shards = max(1, min(len(jax.devices()), n_batch))
        n_shards = int(n_shards)
        stacked = shard_pad(stacked, n_batch, n_shards)

        run = plans[0].build_run("mega", pad_e=pad_e_m, pad_a=pad_a_m,
                                 n_shards=n_shards, tree=tree_pad,
                                 probes=probes)
    out = fetch(execute(run, stacked))

    results = [dict() for _ in items]
    retries: Dict[int, list] = {}
    with stage("post"):
        for b, (i, s) in enumerate(spans):
            if bool(out["overflow"][b]):
                retries.setdefault(i, []).append(s)
                continue
            out_b = jax.tree_util.tree_map(lambda x: x[b], out)
            npk_i = plans[i].wl.n_packets
            for k in ("delivery", "a_used", "c_used"):
                out_b[k] = out_b[k][:npk_i]
            out_b["occ"] = out_b["occ"][:, :npk_i]
            if not pads[i].noop:
                # Gather per-queue packet counts back onto the real tree's
                # queue ids (padded queues hold zero: no real packet ever
                # lands there).
                cnt = out_b["counts"]
                out_b["counts"] = ([c[pads[i].mid] for c in cnt[:4]]
                                   + [cnt[4][:plans[i].tree.n_hosts]])
            results[i][s] = _postprocess(out_b, plans[i].wl, probes)

    # JSQ pad overflow: re-run exactly the (item, seed) cells a standalone
    # run would re-pad, through the seed-batched path (whose retry is itself
    # bitwise-identical to serial simulate).
    for i, retry_seeds in retries.items():
        with stage("jsq_retry", key="retry"):
            count("jsq_retries", len(retry_seeds))
            tree, wl, scheme, _, links, fz = items[i]
            redone = simulate_batch(tree, wl, scheme, retry_seeds,
                                    prop_slots=prop_slots, links=links,
                                    backend=backend,
                                    jsq_pad_factor=jsq_pad_factor * 2,
                                    probes=probes, fault=fz)
            results[i].update(dict(zip(retry_seeds, redone)))

    return [[results[i][s] for s in seeds]
            for i, (_, _, _, seeds, _, _) in enumerate(items)]


# Positional order of the pipeline arguments; the first _N_STATIC are
# seed-independent (vmap in_axes=None in the seed-batched variant), the rest
# carry the seed batch axis.  In the megabatched variant ("mega") *every*
# argument carries the fused (scheme x load x failure x seed) axis.
_ARG_ORDER = ("p1", "e1", "p2", "e2", "dst", "inter_pod", "leaves_edge",
              "ep_sw", "pad_lim_e", "pad_lim_a", "h_log",
              "t_rel", "tie", "a_pre", "c_pre", "rand_a", "rand_c",
              "noise_e", "noise_a", "te", "ta")
_N_STATIC = 11


@functools.lru_cache(maxsize=64)
def _build_run(*, h, n_pods, n_edges, n_aggs, n_hosts, edge_mode, agg_mode,
               quanta, buffer_pkts, reset_wraps, pad_e, pad_a, prop, backend,
               tables_e_keys, tables_a_keys, batch, n_shards=1,
               probe_stride=0, probe_samples=0):
    """Compile the 5-layer pipeline for a given (scheme-shape, tree) config.

    ``batch`` selects the dispatch variant:

      * ``False``  -- one unbatched simulation (the serial baseline);
      * ``"seed"`` -- seed-vmapped: per-seed arguments carry a leading batch
        axis, seed-independent arguments are broadcast (``in_axes=None``);
      * ``"mega"`` -- megabatched: *every* argument carries the fused
        (scheme x load x failure x seed) leading axis, so schemes/loads that
        lower to the same pipeline stack into ONE dispatch.  With
        ``n_shards > 1`` the fused axis is additionally ``shard_map``-ed
        across the first ``n_shards`` devices (the batch size must be a
        multiple of ``n_shards``; the caller pads).

    The cache key is the *pipeline shape*: two schemes with the same
    modes/padding share one compiled executable, which the sweep planner
    exploits when fusing campaign grid points into megabatches.
    """

    mid = n_pods * h * h   # queues per middle layer

    def layers(at, p1, e1, p2, e2, dst, inter_pod, leaves_edge, ep_sw,
               pad_lim_e, pad_lim_a, h_log, t_rel, tie,
               a_pre, c_pre, rand_a, rand_c, noise_e, noise_a, te, ta):
        tbl_e = dict(zip(tables_e_keys, te))
        tbl_a = dict(zip(tables_a_keys, ta))
        if "rr_starts" in tbl_e:
            tbl_e["reset_wraps"] = reset_wraps
            tbl_a["reset_wraps"] = reset_wraps
        overflow = jnp.asarray(False)
        counts, occs, n_real = [], [], []
        # Probe inputs per layer: the arrival times that place each packet's
        # observed occupancy into a stride window, and the active mask that
        # keeps bypass/pad rows out of the series.
        p_arr, p_act = [], []

        a_t = t_rel + prop                      # arrival at source edge switch
        edge_switch = p1 * h + e1

        # ---------- UP_E ----------
        at("up_e")
        if edge_mode == "pre":
            a_used = a_pre
        elif edge_mode == "rand":
            a_used = rand_a
        elif edge_mode in ("rr", "rr_reset"):
            a_used = _ranked_ports(edge_switch, a_t, tie, leaves_edge,
                                   _select_fn_for("rr" if edge_mode == "rr"
                                                  else "rr_reset", h_log,
                                                  tbl_e),
                                   backend)
        elif edge_mode == "ofan":
            dst_edge = p2 * h + e2
            gkey = edge_switch * n_edges + dst_edge
            a_used = _ranked_ports(gkey, a_t, tie, leaves_edge,
                                   _select_fn_for("ofan", h_log, tbl_e),
                                   backend, extra=ep_sw)
        if edge_mode in ("jsq", "jsq_quant"):
            a_used, d, occ, max_rank = _jsq_layer(
                edge_switch, a_t, tie, leaves_edge, n_switches=n_edges,
                pad=pad_e, h=h, h_log=h_log, quanta=quanta,
                buffer_pkts=buffer_pkts, noise=noise_e, backend=backend)
            overflow |= max_rank >= pad_lim_e
            qid = jnp.where(leaves_edge, edge_switch * h + a_used, -1)
            cnt = jnp.zeros((mid,), jnp.int32).at[
                jnp.where(qid >= 0, qid, 0)].add(jnp.where(qid >= 0, 1, 0))
            counts.append(cnt); occs.append(occ)
            n_real.append(jnp.sum(leaves_edge))
        else:
            qid = jnp.where(leaves_edge, edge_switch * h + a_used, -1)
            d, cnt, occ = _lindley_layer(qid, a_t, tie, mid, backend)
            counts.append(cnt); occs.append(occ)
            n_real.append(jnp.sum(leaves_edge))
        p_arr.append(a_t); p_act.append(leaves_edge)
        a_t = jnp.where(leaves_edge, d + prop, a_t)

        # ---------- UP_A ----------
        at("up_a")
        agg_switch = p1 * h + a_used
        if agg_mode == "pre":
            c_used = c_pre
        elif agg_mode == "rand":
            c_used = rand_c
        elif agg_mode in ("rr", "rr_reset"):
            c_used = _ranked_ports(agg_switch, a_t, tie, inter_pod,
                                   _select_fn_for("rr" if agg_mode == "rr"
                                                  else "rr_reset", h_log,
                                                  tbl_a),
                                   backend)
        elif agg_mode == "ofan":
            gkey = agg_switch * n_pods + p2
            c_used = _ranked_ports(gkey, a_t, tie, inter_pod,
                                   _select_fn_for("ofan", h_log, tbl_a),
                                   backend, extra=ep_sw)
        if agg_mode in ("jsq", "jsq_quant"):
            c_used, d, occ, max_rank = _jsq_layer(
                agg_switch, a_t, tie, inter_pod, n_switches=n_aggs,
                pad=pad_a, h=h, h_log=h_log, quanta=quanta,
                buffer_pkts=buffer_pkts, noise=noise_a, backend=backend)
            overflow |= max_rank >= pad_lim_a
            qid = jnp.where(inter_pod, agg_switch * h + c_used, -1)
            cnt = jnp.zeros((mid,), jnp.int32).at[
                jnp.where(qid >= 0, qid, 0)].add(jnp.where(qid >= 0, 1, 0))
            counts.append(cnt); occs.append(occ)
            n_real.append(jnp.sum(inter_pod))
        else:
            qid = jnp.where(inter_pod, agg_switch * h + c_used, -1)
            d, cnt, occ = _lindley_layer(qid, a_t, tie, mid, backend)
            counts.append(cnt); occs.append(occ)
            n_real.append(jnp.sum(inter_pod))
        p_arr.append(a_t); p_act.append(inter_pod)
        a_t = jnp.where(inter_pod, d + prop, a_t)

        # ---------- DN_C (forced: core (a_used, c_used) -> agg a_used of p2) --
        at("dn_c")
        qid = jnp.where(inter_pod, (p2 * h + a_used) * h + c_used, -1)
        d, cnt, occ = _lindley_layer(qid, a_t, tie, mid, backend)
        counts.append(cnt); occs.append(occ)
        n_real.append(jnp.sum(inter_pod))
        p_arr.append(a_t); p_act.append(inter_pod)
        a_t = jnp.where(inter_pod, d + prop, a_t)

        # ---------- DN_A (forced: agg a_used -> edge e2) ----------
        at("dn_a")
        qid = jnp.where(leaves_edge, (p2 * h + a_used) * h + e2, -1)
        d, cnt, occ = _lindley_layer(qid, a_t, tie, mid, backend)
        counts.append(cnt); occs.append(occ)
        n_real.append(jnp.sum(leaves_edge))
        p_arr.append(a_t); p_act.append(leaves_edge)
        a_t = jnp.where(leaves_edge, d + prop, a_t)

        # ---------- DN_E (forced: edge -> host) ----------
        at("dn_e")
        d, cnt, occ = _lindley_layer(dst, a_t, tie, n_hosts, backend)
        counts.append(cnt); occs.append(occ)
        # dst == -1 marks shape-bucketing pad packets (inert bypass rows);
        # without padding this equals dst.shape[0] exactly.
        n_real.append(jnp.sum(dst >= 0))
        p_arr.append(a_t); p_act.append(dst >= 0)
        delivery = d + prop
        at(None)

        out = {"delivery": delivery,
               "counts": counts,
               "occ": jnp.stack(occs),
               "n_real": jnp.stack([jnp.asarray(x, jnp.int32) for x in n_real]),
               "a_used": a_used, "c_used": c_used,
               "overflow": overflow}
        if probe_samples:
            # Scatter-max each packet's observed occupancy into the stride
            # window of its arrival time; inactive rows drop out entirely
            # (mode="drop"), arrivals past the horizon clamp into the last
            # window.  Per-layer max over the series therefore reduces the
            # exact value set LayerStats.max_queue reduces.
            stride = jnp.float32(probe_stride)
            last = probe_samples - 1
            qsr = jnp.zeros((N_LAYERS, probe_samples), jnp.float32)
            for li in range(N_LAYERS):
                si = jnp.clip((p_arr[li] // stride).astype(jnp.int32),
                              0, last)
                qsr = qsr.at[li, jnp.where(p_act[li], si, probe_samples)].max(
                    jnp.where(p_act[li], occs[li], 0.0), mode="drop")
            out["probe_q"] = qsr
        return out

    def pipeline(*args):
        # Each layer round's device operations carry its name (up_e ...
        # dn_e) in their HLO metadata.
        with scopes() as at:
            return layers(at, *args)

    n_args = len(_ARG_ORDER)
    if batch == "mega":
        fn = jax.vmap(pipeline, in_axes=(0,) * n_args)
        if n_shards > 1:
            from jax.sharding import Mesh, PartitionSpec
            mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("b",))
            # check_vma=False: every operand and output is sharded on the
            # fused axis, and the JSQ scan's replicated initial carry would
            # otherwise be refused as not varying over it.
            fn = jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec("b"),
                               out_specs=PartitionSpec("b"), check_vma=False)
        jitted = jax.jit(fn)
    elif batch:                       # "seed" (True kept for back-compat)
        in_axes = (None,) * _N_STATIC + (0,) * (n_args - _N_STATIC)
        jitted = jax.jit(jax.vmap(pipeline, in_axes=in_axes))
    else:
        jitted = jax.jit(pipeline)

    def run(kw: dict):
        return jitted(*(kw[k] for k in _ARG_ORDER))

    run.jitted = jitted
    return run
