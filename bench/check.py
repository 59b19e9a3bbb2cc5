"""The comparison that decides ``correct``: records the timed campaigns
produced against the plain reference (``bench/ref``), on a sample of grid
points drawn from the run seed, every sampled field of every sampled point.

Loop-engine records are compared exactly: the model's state is integer
apart from float32 credit, ACK debt and switch scores, which the reference
computes in the same precision.  Fast-engine records are compared by the
widest absolute gap in each of two classes of fields, each with a limit of
its own: times and queue maxima (slots or packets, hundreds of them) and
the per-layer averages and ratios (mean queue seen on arrival, overload),
which are of order one.  The engine computes times in float32 by a
rearranged scan, the reference in float64 by the plain recursion.
"""
from __future__ import annotations

import collections

import numpy as np

from ref import common, fast, loop

LOOP_FIELDS = ("cct", "cct_acked", "max_queue", "avg_queue", "drops",
               "retransmissions", "finished")
# Fast-engine fields of order one; every other field is a time or a queue
# maximum.
FAST_MEAN_PREFIXES = ("avg_wait_", "overload_")


def sample(records, n: int, seed: int):
    """``n`` records drawn from the run seed, spread evenly over schemes."""
    rng = np.random.default_rng([int(seed) & ((1 << 64) - 1), 3])
    by_scheme = collections.defaultdict(list)
    for r in records:
        by_scheme[r["scheme"]].append(r)
    queues = [[by_scheme[s][i] for i in rng.permutation(len(by_scheme[s]))]
              for s in sorted(by_scheme)]
    out = []
    while len(out) < n and any(queues):
        for q in queues:
            if q and len(out) < n:
                out.append(q.pop())
    return out


def reference(config: dict, traffic: dict, rec: dict, dtype=None) -> dict:
    """The reference's record for the grid point ``rec`` names."""
    tree = common.Tree(config["k"])
    (load,) = traffic["loads"]
    if load["kind"] != "permutation":
        raise ValueError("the reference covers permutation traffic")
    rng_seed = int(rec["workload"].rsplit("-r", 1)[1])
    tr = common.permutation(tree, load["msg_packets"], rng_seed)
    kw = {} if dtype is None else {"dtype": dtype}
    if config["engine"] == "fast":
        if rec["failure"] is not None:
            raise ValueError("the fast reference covers all links up")
        return fast.simulate(tree, tr, rec["scheme"], rec["seed"],
                             prop=config["prop_slots"], **kw)
    o = config["loop_opts"]
    if o.get("loss") != "sack":
        raise ValueError("the loop reference covers SACK loss recovery")
    links, rho = None, traffic.get("rho", 1.0)
    if rec["failure"] is not None:
        (f,) = [f for f in traffic["failures"] if f is not None
                and rec["failure"] == f"fail{f['p_fail']:g}-r{f['rng_seed']}"]
        links = common.random_failures(tree, f["p_fail"], f["rng_seed"])
    if rho == "auto":
        rho = 1.0 if links is None else common.rho_max(tree, links, tr)
    return loop.simulate(
        tree, tr, rec["scheme"], rec["seed"], prop=config["prop_slots"],
        ack_delay=o["ack_delay"], buffer_pkts=o["buffer_pkts"],
        sack_thresh=o["sack_thresh"], rto_slots=o["rto_slots"],
        ack_cost=o["ack_cost"], rho=rho, max_slots=config["max_slots"],
        links=links, g_converge=rec.get("g_converge"), **kw)


def compare(config: dict, traffic: dict, records, seed: int,
            stand_in=None, log=print):
    """Checks as ``[(name, value, op, limit)]``, ``op`` being ``"<="`` or
    ``">="``.  ``stand_in(rec)``, when given, replaces the
    program's record of each sampled point (the control)."""
    chk = config["check"]
    lim = chk["limits"]
    picked = sample(records, chk["points"], seed)
    if config["engine"] == "loop":
        mismatches = 0
        for rec in picked:
            got = stand_in(rec) if stand_in else rec
            want = reference(config, traffic, rec)
            bad = [f for f in LOOP_FIELDS if got[f] != want[f]]
            mismatches += len(bad)
            for f in bad:
                log(f"  {rec['scheme']} seed {rec['seed']}: {f} "
                    f"{got[f]!r}, reference {want[f]!r}")
        return [("points_compared", len(picked), ">=", 1),
                ("loop_field_mismatches", mismatches, "<=",
                 lim["loop_field_mismatches"])]
    widest = {"fast_time_gap": 0.0, "fast_mean_gap": 0.0}
    where: dict = {}
    for rec in picked:
        got = stand_in(rec) if stand_in else rec
        want = reference(config, traffic, rec)
        for f, v in want.items():
            name = ("fast_mean_gap" if f.startswith(FAST_MEAN_PREFIXES)
                    else "fast_time_gap")
            gap = abs(float(got[f]) - v)
            w = widest[name]
            if w == w and not gap <= w:                   # NaN sticks
                widest[name] = gap
                where[name] = (rec["scheme"], rec["seed"], f)
    for name, (scheme, s, f) in sorted(where.items()):
        log(f"  {name} {widest[name]!r} at {scheme} seed {s} field {f}")
    return [("points_compared", len(picked), ">=", 1)] + [
        (name, widest[name], "<=", lim[name]) for name in sorted(widest)]
