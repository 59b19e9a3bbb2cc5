"""Plain reference of the simulated deployment's inputs, shared by the
reference modules: fat-tree coordinates, the permutation traffic matrix and
the failed links of a grid point from the names the runner records, the
per-seed draws of each load-balancing scheme, and the Threefry-2x32 counter
stream the switches draw their tie-break noise from.

Written from the model's definition in plain numpy; it imports nothing of
the program under test.  The draws follow the
model's documented order on one ``numpy.random.Generator`` per replicate
seed, so that the same seed gives the same answer.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Tree:
    """k-ary three-level fat tree: k pods of k/2 edge and k/2 aggregation
    switches, k/2 hosts per edge switch, (k/2)^2 cores."""
    k: int

    @property
    def h(self) -> int:
        return self.k // 2

    @property
    def n_hosts(self) -> int:
        return self.k ** 3 // 4

    @property
    def n_edges(self) -> int:
        return self.k * self.h

    @property
    def mid(self) -> int:
        """Queues in each of the four switch-to-switch layers."""
        return self.k * self.h * self.h

    def pod(self, host):
        return host // (self.h * self.h)

    def edge(self, host):
        return (host % (self.h * self.h)) // self.h


@dataclasses.dataclass
class Traffic:
    """Flows and their packets.  Packets of one flow are contiguous and in
    sequence order; packet ``j`` of a host's only flow leaves at slot ``j``."""
    flow_src: np.ndarray
    flow_dst: np.ndarray
    msg: int

    @property
    def n_flows(self) -> int:
        return len(self.flow_src)

    @property
    def n_packets(self) -> int:
        return self.n_flows * self.msg

    @property
    def flow(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_flows), self.msg)

    @property
    def seq(self) -> np.ndarray:
        return np.tile(np.arange(self.msg), self.n_flows)


def permutation(tree: Tree, msg: int, rng_seed: int) -> Traffic:
    """Host i sends ``msg`` packets to host perm(i); perm is the first
    fixed-point-free permutation the traffic seed's generator draws."""
    rng = np.random.default_rng(rng_seed)
    n = tree.n_hosts
    while True:
        perm = rng.permutation(n)
        if (perm != np.arange(n)).all():
            break
    return Traffic(np.arange(n), perm, msg)


# ---------------------------------------------------------------------------
# A grid point as the runner records it: ``workload`` is the load's label,
# which ends in ``-r<traffic-matrix seed>``; ``failure`` is the failure
# pattern's label, ``fail<p_fail>-r<pattern seed>``, or None.
# ---------------------------------------------------------------------------

def traffic_seed(rec: dict) -> int:
    """The traffic-matrix seed of the grid point ``rec``."""
    return int(rec["workload"].rsplit("-r", 1)[1])


def permutation_point(config: dict, traffic: dict, rec: dict):
    """(tree, traffic matrix) of the grid point ``rec``: the configuration's
    fat tree and the traffic file's one load, a permutation."""
    tree = Tree(config["k"])
    (load,) = traffic["loads"]
    if load["kind"] != "permutation":
        raise ValueError("the reference covers permutation traffic")
    return tree, permutation(tree, load["msg_packets"], traffic_seed(rec))


def host_labels(scheme: str, tree: Tree, tr: Traffic, rng, alive=None):
    """Per-packet (aggregation index a, core index c) chosen at the host.

    flow_ecmp: one random label per flow; subflow_mptcp: one per each of 4
    subflows, packet j on subflow j mod 4; host_pkt / host_pkt_ar: one per
    packet; host_dr: a per-flow rotation over the lowest common layer from a
    random start in a random order (cores for inter-pod flows, aggregation
    switches for the rest, whose core index is drawn per packet).
    ``alive[f]`` (``paths`` of each flow) restricts per-packet labels to
    alive paths, all of them where a flow has none."""
    h = tree.h
    flow, seq = tr.flow, tr.seq
    if alive is not None:
        if scheme not in ("host_pkt", "host_pkt_ar"):
            raise ValueError(f"no failure-aware labels for {scheme!r}")
        a = np.empty(tr.n_packets, np.int64)
        c = np.empty(tr.n_packets, np.int64)
        for f in range(tr.n_flows):
            cand = np.argwhere(alive[f])
            if len(cand) == 0:
                cand = np.argwhere(np.ones((h, h), bool))
            pick = cand[rng.integers(0, len(cand), size=tr.msg)]
            sl = slice(f * tr.msg, (f + 1) * tr.msg)
            a[sl], c[sl] = pick[:, 0], pick[:, 1]
        return a, c
    if scheme in ("flow_ecmp", "subflow_mptcp"):
        n_sub = 4 if scheme == "subflow_mptcp" else 1
        a_lab = np.empty((tr.n_flows, n_sub), np.int64)
        c_lab = np.empty((tr.n_flows, n_sub), np.int64)
        for f in range(tr.n_flows):
            a_lab[f] = rng.integers(0, h, size=n_sub)
            c_lab[f] = rng.integers(0, h, size=n_sub)
        return a_lab[flow, seq % n_sub], c_lab[flow, seq % n_sub]
    if scheme in ("host_pkt", "host_pkt_ar"):
        a = rng.integers(0, h, size=tr.n_packets)
        c = rng.integers(0, h, size=tr.n_packets)
        return a, c
    if scheme == "host_dr":
        a = np.empty(tr.n_packets, np.int64)
        c = np.zeros(tr.n_packets, np.int64)
        pairs = np.array([(i, j) for i in range(h) for j in range(h)])
        for f in range(tr.n_flows):
            sl = slice(f * tr.msg, (f + 1) * tr.msg)
            s = np.arange(tr.msg)
            if tree.pod(tr.flow_src[f]) != tree.pod(tr.flow_dst[f]):
                order = pairs[rng.permutation(len(pairs))]
                start = rng.integers(0, len(order))
                sel = order[(start + s) % len(order)]
                a[sl], c[sl] = sel[:, 0], sel[:, 1]
            else:
                order = np.arange(h)[rng.permutation(h)]
                start = rng.integers(0, h)
                a[sl] = order[(start + s) % h]
                c[sl] = rng.integers(0, h, size=tr.msg)
        return a, c
    raise ValueError(f"no host labels for {scheme!r}")


def pointer_tables(n_pointers: int, h: int, rng):
    """OFAN pointers: a random port order and a random start per pointer."""
    orders = np.argsort(rng.random((n_pointers, h)), axis=1)
    starts = rng.integers(0, h, size=n_pointers)
    return orders, starts


# ---------------------------------------------------------------------------
# Threefry-2x32 (Salmon et al., SC'11), 20 rounds: the switches' counter
# stream value = threefry(key(seed, site, lane), counter(slot, id)).
# ---------------------------------------------------------------------------
SITE_EDGE_JSQ = 3
SITE_AGG_JSQ = 4
_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry2x32(k0, k1, c0, c1):
    k0, k1, x0, x1 = (np.asarray(v, np.uint32) for v in (k0, k1, c0, c1))
    k2 = k0 ^ k1 ^ np.uint32(0x1BD11BDA)
    keys = (k0, k1, k2)
    with np.errstate(over="ignore"):
        x0 = x0 + k0
        x1 = x1 + k1
        for block in range(5):
            for r in _ROT[block % 2]:
                x0 = x0 + x1
                x1 = ((x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))) ^ x0
            x0 = x0 + keys[(block + 1) % 3]
            x1 = x1 + keys[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def uniform(seed: int, site: int, ids, slot, lanes):
    """float32 uniforms in [0, 1) with 24-bit resolution, broadcast over
    ``ids`` x ``lanes`` at one slot."""
    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    k0 = np.uint32(s & 0xFFFFFFFF)
    k1 = np.uint32(s >> 32) ^ (np.uint32(site << 16) ^ np.asarray(
        lanes, np.uint32))
    x0, _ = threefry2x32(k0, k1, np.uint32(slot), np.asarray(ids, np.uint32))
    return (x0 >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


# ---------------------------------------------------------------------------
# Link failures: a failed edge-aggregation or aggregation-core link is dead
# in both directions.  ``ea[p, e, a]``, ``ac[p, a, c]``: alive.
# ---------------------------------------------------------------------------
SITE_LINK_FAIL = 7


@dataclasses.dataclass
class Links:
    ea: np.ndarray
    ac: np.ndarray

    @property
    def any_failure(self) -> bool:
        return not (self.ea.all() and self.ac.all())


def random_failures(tree: Tree, p_fail: float, seed: int) -> Links:
    """Each link fails with probability ``p_fail``, by the counter stream at
    (seed, link-failure site, lane k, link id, slot 0 for edge-aggregation
    links and 1 for aggregation-core links)."""
    k, h = tree.k, tree.h
    ids = np.arange(k * h * h, dtype=np.uint32)
    u_ea = uniform(seed, SITE_LINK_FAIL, ids, 0, k)
    u_ac = uniform(seed, SITE_LINK_FAIL, ids, 1, k)
    return Links((u_ea >= p_fail).reshape(k, h, h),
                 (u_ac >= p_fail).reshape(k, h, h))


def point_failure(tree: Tree, traffic: dict, rec: dict) -> Links | None:
    """The failed links of the pattern the grid point ``rec`` names, one of
    the traffic file's ``failures``; None where every link is up."""
    if rec["failure"] is None:
        return None
    (f,) = [f for f in traffic["failures"] if f is not None
            and rec["failure"] == f"fail{f['p_fail']:g}-r{f['rng_seed']}"]
    return random_failures(tree, f["p_fail"], f["rng_seed"])


def paths(tree: Tree, links: Links, src: int, dst: int) -> np.ndarray:
    """(k/2, k/2) bool: is the path through aggregation index a and core
    index c alive?  Intra-pod paths ignore c; same-edge paths are alive."""
    h = tree.h
    p1, e1, p2, e2 = (int(tree.pod(src)), int(tree.edge(src)),
                      int(tree.pod(dst)), int(tree.edge(dst)))
    out = np.ones((h, h), bool)
    for a in range(h):
        for c in range(h):
            if p1 != p2:
                out[a, c] = (links.ea[p1, e1, a] and links.ac[p1, a, c]
                             and links.ac[p2, a, c] and links.ea[p2, e2, a])
            elif e1 != e2:
                out[a, c] = links.ea[p1, e1, a] and links.ea[p2, e2, a]
    return out


def edge_weights(tree: Tree, links: Links, sp, se, dp, de) -> np.ndarray:
    """W-ECMP weight of each uplink a of edge switch (sp, se) toward edge
    switch (dp, de): the number of alive paths through it."""
    w = np.zeros(tree.h, np.int64)
    for a in range(tree.h):
        if not links.ea[sp, se, a] or not links.ea[dp, de, a]:
            continue
        w[a] = (1 if sp == dp
                else int((links.ac[sp, a] & links.ac[dp, a]).sum()))
    return w


def agg_weights(links: Links, sp, a, dp) -> np.ndarray:
    return (links.ac[sp, a] & links.ac[dp, a]).astype(np.int64)


def iwrr(weights, rng) -> np.ndarray:
    """Interleaved weighted round robin over the ports with weight > 0,
    weights divided by their gcd, ports in a random order: round r emits
    every port whose weight exceeds r."""
    w = np.asarray(weights, np.int64)
    if w.sum() == 0:
        return np.zeros(0, np.int64)
    ports = np.flatnonzero(w > 0)
    w = w // np.gcd.reduce(w[ports])
    ports = ports[rng.permutation(len(ports))]
    return np.asarray([p for r in range(int(w[ports].max()))
                       for p in ports if w[p] > r], np.int64)


def rho_max(tree: Tree, links: Links, tr: Traffic) -> float:
    """Largest uniform sending rate at which no link exceeds line rate when
    each flow splits equally over its alive paths (0 if one has none)."""
    k, h = tree.k, tree.h
    up_e, up_a, dn_c, dn_a = (np.zeros((k, h, h)) for _ in range(4))
    dn_e = np.zeros(tree.n_hosts)
    for s, d in zip(tr.flow_src.tolist(), tr.flow_dst.tolist()):
        p1, e1, p2, e2 = (int(tree.pod(s)), int(tree.edge(s)),
                          int(tree.pod(d)), int(tree.edge(d)))
        dn_e[d] += 1.0
        if p1 == p2 and e1 == e2:
            continue
        pm = paths(tree, links, s, d)
        if p1 == p2:
            valid = pm[:, 0]
            if valid.sum() == 0:
                return 0.0
            share = valid / valid.sum()
            up_e[p1, e1, :] += share
            dn_a[p2, :, e2] += share
        else:
            if pm.sum() == 0:
                return 0.0
            share = pm / pm.sum()
            up_e[p1, e1, :] += share.sum(axis=1)
            up_a[p1] += share
            dn_c[p2] += share
            dn_a[p2, :, e2] += share.sum(axis=1)
    worst = max(float(x.max()) for x in (up_e, up_a, dn_c, dn_a, dn_e))
    return 1.0 if worst <= 1.0 else 1.0 / worst
