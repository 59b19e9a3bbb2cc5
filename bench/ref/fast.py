"""Plain reference of the layered fabric model without feedback.

Hosts pace at line rate: packet ``j`` of a host's flow is released at slot
``j`` plus the host's random phase in [0, 1).  Every switch port is a FIFO
queue served at one packet per slot, so a packet leaves a queue at
``max(arrival, previous departure) + 1`` and reaches the next queue ``prop``
slots later.  Same-time arrivals at a queue are served in the order of a
per-flow random tie key, then packet id.  A packet crosses up to five
queues: edge uplink, aggregation uplink, core downlink, aggregation
downlink, edge-to-host link; traffic that stays in its pod or under its
edge switch skips the layers it does not cross.

Schemes: flow_ecmp, subflow_mptcp, host_pkt, host_dr (the host fixes both
uplinks) and ofan (the edge switch rotates a pointer per destination edge
switch, the aggregation switch one per destination pod, each in order of
arrival).  All links are up.

``dtype`` is the precision of the arrival and departure times.  The model
states float32; this reference computes in float64 and the control in
bfloat16.

The program computes its times in float32 by a rearranged scan, so its
records are compared by the widest absolute gap, in two classes of fields
with a limit each: the per-layer averages and ratios (mean queue seen on
arrival, overload), which are of order one, and every other field, a time
or a queue maximum (slots or packets, hundreds of them).
"""
from __future__ import annotations

import numpy as np

from . import common

HOST_LABEL = ("flow_ecmp", "subflow_mptcp", "host_pkt", "host_dr")
SCHEMES = HOST_LABEL + ("ofan",)
LAYERS = ("E_A", "A_C", "C_A", "A_E", "E_H")
MEAN_PREFIXES = ("avg_wait_", "overload_")
COMPARE = (
    ("fast_mean_gap", "widest", lambda f: f.startswith(MEAN_PREFIXES)),
    ("fast_time_gap", "widest", lambda f: True))


def point(config: dict, traffic: dict, rec: dict, dtype=None) -> dict:
    """The reference's record of the grid point ``rec`` names."""
    if rec["failure"] is not None:
        raise ValueError("the fast reference covers all links up")
    tree, tr = common.permutation_point(config, traffic, rec)
    kw = {} if dtype is None else {"dtype": dtype}
    return simulate(tree, tr, rec["scheme"], rec["seed"],
                    prop=config["prop_slots"], **kw)


def _fifo(qid, arrive, tie, n_queues, dtype):
    """Departure and queue length seen on arrival of every packet with
    ``qid >= 0``; the others pass through unchanged."""
    dep = arrive.copy()
    occ = np.zeros(len(qid), dtype)
    active = np.flatnonzero(qid >= 0)
    order = active[np.lexsort((tie[active], arrive[active], qid[active]))]
    one = dtype(1.0)
    last_q, last_d = -1, None
    for i in order.tolist():
        q, a = qid[i], arrive[i]
        d = dtype(max(a, last_d) + one) if q == last_q else dtype(a + one)
        dep[i] = d
        occ[i] = dtype(d - a - one)
        last_q, last_d = q, d
    counts = np.bincount(qid[active], minlength=n_queues)
    return dep, occ, counts, len(active)


def _rotate(gkey, arrive, tie, active, orders, starts, h):
    """Port of each active packet: its rank among the same pointer's
    packets, in order of arrival, rotated through the pointer's order."""
    port = np.zeros(len(gkey), np.int64)
    idx = np.flatnonzero(active)
    order = idx[np.lexsort((tie[idx], arrive[idx], gkey[idx]))]
    seen: dict = {}
    for i in order.tolist():
        g = int(gkey[i])
        r = seen.get(g, 0)
        seen[g] = r + 1
        port[i] = orders[g][(starts[g] + r) % h]
    return port


def simulate(tree: common.Tree, tr: common.Traffic, scheme: str, seed: int,
             *, prop=12.0, dtype=np.float64) -> dict:
    if scheme not in SCHEMES:
        raise ValueError(f"reference has no scheme {scheme!r}")
    k, h, n, mid = tree.k, tree.h, tree.n_hosts, tree.mid
    flow, seq = tr.flow, tr.seq
    P = tr.n_packets

    rng = np.random.default_rng(seed)
    phases = rng.random(n).astype(np.float32)
    tie = rng.random(tr.n_flows).astype(np.float32)[flow]
    if scheme in HOST_LABEL:
        a_pre, c_pre = common.host_labels(scheme, tree, tr, rng)
    rng.integers(0, h, P)                 # switch spray draws (unused here)
    rng.integers(0, h, P)
    if scheme == "ofan":
        e_tab = common.pointer_tables(tree.n_edges ** 2, h, rng)
        a_tab = common.pointer_tables(tree.n_edges * k, h, rng)

    src, dst = tr.flow_src[flow], tr.flow_dst[flow]
    p1, e1 = tree.pod(src), tree.edge(src)
    p2, e2 = tree.pod(dst), tree.edge(dst)
    inter = p1 != p2
    leaves = inter | (e1 != e2)
    none = np.int64(-1)
    prop_d = dtype(prop)

    release = (seq.astype(np.float64) + phases[src]).astype(np.float32)
    t = (release.astype(dtype) + prop_d).astype(dtype)
    occs, counts, n_real = [], [], []

    def layer(qid, n_queues, moves):
        nonlocal t
        d, occ, cnt, nr = _fifo(qid, t, tie, n_queues, dtype)
        occs.append(occ)
        counts.append(cnt)
        n_real.append(nr)
        t = np.where(moves, (d + prop_d).astype(dtype), t).astype(dtype)
        return d

    edge_sw = p1 * h + e1
    if scheme == "ofan":
        a_used = _rotate(edge_sw * tree.n_edges + p2 * h + e2, t, tie, leaves,
                         e_tab[0], e_tab[1], h)
    else:
        a_used = a_pre
    layer(np.where(leaves, edge_sw * h + a_used, none), mid, leaves)
    agg_sw = p1 * h + a_used
    if scheme == "ofan":
        c_used = _rotate(agg_sw * k + p2, t, tie, inter, a_tab[0], a_tab[1], h)
    else:
        c_used = c_pre
    layer(np.where(inter, agg_sw * h + c_used, none), mid, inter)
    layer(np.where(inter, (p2 * h + a_used) * h + c_used, none), mid, inter)
    layer(np.where(leaves, (p2 * h + a_used) * h + e2, none), mid, leaves)
    d = layer(dst.astype(np.int64), n, np.zeros(P, bool))
    delivery = (d + prop_d).astype(dtype).astype(np.float64)

    fcomp = np.full(tr.n_flows, -np.inf)
    np.maximum.at(fcomp, flow, delivery)
    rec = {
        "cct": float(delivery.max()),
        "max_queue": max(float(o.max()) for o in occs),
        "delivery_p50": float(np.percentile(delivery, 50)),
        "delivery_p99": float(np.percentile(delivery, 99)),
        "flow_completion_p99": float(np.percentile(fcomp, 99)),
    }
    for tag, occ, cnt, nr in zip(LAYERS, occs, counts, n_real):
        occ = occ.astype(np.float64)
        rec[f"max_queue_{tag}"] = float(occ.max())
        rec[f"avg_wait_{tag}"] = float(occ.sum()) / max(nr, 1)
        used = cnt[cnt > 0]
        rec[f"overload_{tag}"] = (float(used.max() / (cnt.sum() / len(cnt))
                                        - 1.0) if used.size else 0.0)
    return rec
