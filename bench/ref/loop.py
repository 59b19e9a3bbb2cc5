"""Plain reference of the slotted fabric model with SACK loss recovery.

One slot is one data-packet serialization.  In every slot, in this order:

 1. every non-empty FIFO queue (four switch layers and the edge-to-host
    links) sends its head packet;
 2. a packet leaving an edge-to-host queue is delivered ``prop`` slots later
    and its ACK reaches the sender ``ack_delay`` slots after that; every
    other packet arrives at its next queue ``prop`` slots later;
 3. each host with credit (rate ``rho``, capped at 4 packets), no ACK debt
    of a whole packet and a sendable flow sends one packet: the first
    missing one when the SACK gap exceeds ``sack_thresh`` or no fresh one is
    left, else the next fresh one; the edge uplink is chosen by the scheme;
 4. arrivals are enqueued in a fixed arbitration order (sending queue id,
    then host id); the aggregation uplink of an arriving packet is chosen by
    the scheme first; a full queue drops, an enqueue above half the buffer
    marks ECN;
 5. ACKs due now are processed: ACK debt, highest SACKed sequence, the
    cumulative ACK point, and label recycling for REPS;
 6. a flow with unacknowledged packets and no ACK for ``rto`` slots marks
    them lost and rewinds to its cumulative ACK point;
 7. a flow whose cumulative ACK point reaches its size completes.

Schemes: host_pkt, host_dr (labels fixed at the host), host_pkt_ar (REPS:
reuse labels whose ACKs came back unmarked), switch_pkt_ar (quantized
join-shortest-queue at both switch layers, uniform tie-break noise from the
counter stream) and ofan (per-destination rotation pointers at both switch
layers).  A failed link drops what is sent onto it; from slot
``g_converge`` on, hosts draw labels among alive paths (REPS maps its labels
onto them), switches avoid dead uplinks (JSQ) or rotate over a weighted
schedule of the alive ones (OFAN).

``dtype`` is the precision of the model's real-valued state (host credit,
ACK debt, switch scores).  The model states float32; the control runs the
same reference in bfloat16.

The model's state is integer apart from that float32 state, which this
reference computes in the same precision, so the program's records are
compared exactly: every field of ``FIELDS``, the fields that differ counted.
"""
from __future__ import annotations

import collections

import numpy as np

from . import common

HOST_LABEL = ("host_pkt", "host_dr", "host_pkt_ar")
SCHEMES = HOST_LABEL + ("switch_pkt_ar", "ofan")
QUANTA = (0.05, 0.10, 0.20)
FIELDS = ("cct", "cct_acked", "max_queue", "avg_queue", "drops",
          "retransmissions", "finished")
COMPARE = (("loop_field_mismatches", "exact", FIELDS.__contains__),)


def point(config: dict, traffic: dict, rec: dict, dtype=None) -> dict:
    """The reference's record of the grid point ``rec`` names, at the
    traffic file's rate (``"auto"``: the failure pattern's ``rho_max``)."""
    o = config["loop_opts"]
    if o.get("loss") != "sack":
        raise ValueError("the loop reference covers SACK loss recovery")
    tree, tr = common.permutation_point(config, traffic, rec)
    links = common.point_failure(tree, traffic, rec)
    rho = traffic.get("rho", 1.0)
    if rho == "auto":
        rho = 1.0 if links is None else common.rho_max(tree, links, tr)
    kw = {} if dtype is None else {"dtype": dtype}
    return simulate(
        tree, tr, rec["scheme"], rec["seed"], prop=config["prop_slots"],
        ack_delay=o["ack_delay"], buffer_pkts=o["buffer_pkts"],
        sack_thresh=o["sack_thresh"], rto_slots=o["rto_slots"],
        ack_cost=o["ack_cost"], rho=rho, max_slots=config["max_slots"],
        links=links, g_converge=rec.get("g_converge"), **kw)


def simulate(tree: common.Tree, tr: common.Traffic, scheme: str, seed: int,
             *, prop=12, ack_delay=74, buffer_pkts=195, sack_thresh=32,
             rto_slots=400, ack_cost=0.0206, rho=1.0, max_slots=200_000,
             links=None, g_converge=None, dtype=np.float32) -> dict:
    if scheme not in SCHEMES:
        raise ValueError(f"reference has no scheme {scheme!r}")
    k, h, n, mid = tree.k, tree.h, tree.n_hosts, tree.mid
    F, m = tr.n_flows, tr.msg
    P = tr.n_packets
    cap = buffer_pkts
    ecn_t = max(1, int(0.5 * cap))
    OFF0, OFF1, OFF2, OFF3, OFF4 = 0, mid, 2 * mid, 3 * mid, 4 * mid
    NQ = 4 * mid + n

    failed = links is not None and links.any_failure
    alive_paths = ([common.paths(tree, links, s, d) for s, d in
                    zip(tr.flow_src.tolist(), tr.flow_dst.tolist())]
                   if failed else None)

    # ---- per-seed draws, in the model's order ------------------------------
    rng = np.random.default_rng(seed)
    pre = {}
    if scheme in HOST_LABEL:
        pre[False] = [x.tolist() for x in common.host_labels(
            scheme, tree, tr, rng)]
        pre[True] = ([x.tolist() for x in common.host_labels(
            scheme, tree, tr, rng, alive=alive_paths)] if failed
            else pre[False])
    label_pool = rng.integers(0, h * h, size=65536).tolist()
    rng.integers(0, h, tree.n_edges)     # round-robin starts (unused here)
    rng.integers(0, h, tree.n_edges)
    if scheme == "ofan":
        tables = {False: _ofan_tables(tree, None, rng)}
        tables[True] = (_ofan_tables(tree, links, rng) if failed
                        else tables[False])
        ptr_e = [0] * tree.n_edges ** 2
        ptr_a = [0] * (tree.n_edges * k)
    thr = np.asarray(QUANTA, np.float32) * np.float32(cap)
    half, big, zero = dtype(0.5), dtype(1e9), dtype(0.0)
    # Per flow: REPS label -> alive path once converged; per switch and
    # destination: dead uplinks that JSQ avoids once converged.
    vpaths = [np.arange(h * h).tolist()] * F
    e_dead = a_dead = None
    if failed:
        vpaths = []
        for f in range(F):
            cand = np.flatnonzero(alive_paths[f].reshape(-1))
            vpaths.append((cand if len(cand) else np.arange(h * h)).tolist())
        e_dead = np.zeros((tree.n_edges, tree.n_edges, h), bool)
        for se in range(tree.n_edges):
            for de in range(tree.n_edges):
                sp, si = divmod(se, h)
                dp, di = divmod(de, h)
                e_dead[se, de] = (~links.ea[sp, si] if se == de else
                                  common.edge_weights(tree, links, sp, si,
                                                      dp, di) == 0)
        a_dead = np.zeros((tree.n_edges, k, h), bool)
        for ga in range(tree.n_edges):
            sp, ai = divmod(ga, h)
            for dp in range(k):
                a_dead[ga, dp] = ~(links.ac[sp, ai] & links.ac[dp, ai])

    def alive_q(q):
        if not failed or q >= OFF4:
            return True
        layer, i = divmod(q, mid)
        p, x, y = i // (h * h), (i // h) % h, i % h
        if layer in (0, 3):
            return bool(links.ea[p, y, x] if layer == 3 else links.ea[p, x, y])
        return bool(links.ac[p, x, y])

    src = tr.flow_src.tolist()
    dst = tr.flow_dst.tolist()
    p1 = [int(tree.pod(x)) for x in src]
    e1 = [int(tree.edge(x)) for x in src]
    p2 = [int(tree.pod(x)) for x in dst]
    e2 = [int(tree.edge(x)) for x in dst]
    inter = [a != b for a, b in zip(p1, p2)]
    leaves = [inter[f] or e1[f] != e2[f] for f in range(F)]
    host_flows = [[] for _ in range(n)]
    for f, s in enumerate(src):
        host_flows[s].append(f)
    Fh = max(len(x) for x in host_flows)

    # ---- state ----------------------------------------------------------------
    queues = [collections.deque() for _ in range(NQ)]
    arrivals = collections.defaultdict(list)   # slot -> [(packet, queue)]
    acks = collections.defaultdict(list)       # slot -> [packet]
    p_a, p_c = [0] * P, [0] * P
    p_recv, p_ecn = [False] * P, [False] * P
    f_next, f_sent, f_acked, f_lost = [0] * F, [0] * F, [0] * F, [0] * F
    f_delivered, f_cum = [0] * F, [0] * F
    f_hi, f_complete, f_data_done = [-1] * F, [-1] * F, [-1] * F
    f_last_ack = [-1] * F
    f_draw = [31 * f + 1 for f in range(F)]
    pool = [[0] * 64 for _ in range(F)]
    pool_cnt = [0] * F
    h_rr = [0] * n
    credit = np.zeros(n, dtype)
    debt = np.zeros(n, dtype)
    rho_d, cost_d, one = dtype(rho), dtype(ack_cost), dtype(1.0)
    drops = rtx = max_q = enq_events = 0
    sum_q = np.float32(0.0)

    def lane_noise(site, ids, t):
        return common.uniform(seed, site, np.asarray(ids)[:, None], t,
                              np.arange(h)[None, :]).astype(dtype)

    def jsq(lens, noise, dead):
        bins = (np.asarray(lens)[:, :, None] > thr).sum(axis=2)
        score = bins.astype(dtype) + noise * half
        if dead is not None:
            score = score + np.where(dead, big, zero)
        return np.argmin(score, axis=1).tolist()

    t = 0
    n_open = F
    while n_open and t < max_slots:
        conv = g_converge is not None and t >= g_converge
        # 1-2. serve every queue; route or deliver what leaves
        dt = t + prop
        fwd = arrivals[dt]
        for q in range(NQ):
            if not queues[q]:
                continue
            pk = queues[q].popleft()
            f = pk // m
            a, c = p_a[pk], p_c[pk]
            stage = q // mid
            if stage == 0:
                nq = (OFF1 + (p1[f] * h + a) * h + c if inter[f]
                      else OFF3 + (p2[f] * h + a) * h + e2[f])
            elif stage == 1:
                nq = OFF2 + (p2[f] * h + a) * h + c
            elif stage == 2:
                nq = OFF3 + (p2[f] * h + a) * h + e2[f]
            elif stage == 3:
                nq = OFF4 + dst[f]
            else:
                if not p_recv[pk]:
                    p_recv[pk] = True
                    f_delivered[f] += 1
                    if f_data_done[f] < 0 and f_delivered[f] >= m:
                        f_data_done[f] = dt
                acks[t + ack_delay].append(pk)
                continue
            fwd.append((pk, nq))

        # 3. hosts send
        sendable = []
        for f in range(F):
            need = (f_hi[f] >= 0 and f_hi[f] + 1 - f_cum[f] > sack_thresh
                    and f_cum[f] < m)
            sendable.append(((f_next[f] < m) or need) and f_complete[f] < 0)
        credit = np.minimum(credit + rho_d, dtype(4.0)).astype(dtype)
        debt_ok = (debt < one).tolist()
        debt = np.where(debt < one, debt, debt - one).astype(dtype)
        senders = []
        for hst in range(n):
            fl = host_flows[hst]
            ok = [j for j in range(len(fl)) if sendable[fl[j]]]
            if not ok or not debt_ok[hst] or not credit[hst] >= one:
                continue
            pick = min(ok, key=lambda j: (j - h_rr[hst]) % Fh)
            credit[hst] -= one
            h_rr[hst] = (pick + 1) % Fh
            senders.append((hst, fl[pick]))
        if scheme == "switch_pkt_ar" and senders:
            hs = [hst for hst, _ in senders]
            lens = [[len(queues[OFF0 + (p1[f] * h + e1[f]) * h + j])
                     for j in range(h)] for _, f in senders]
            dead = (np.stack([e_dead[p1[f] * h + e1[f], p2[f] * h + e2[f]]
                              for _, f in senders])
                    if conv and failed else None)
            e_pick = dict(zip(hs, jsq(lens, lane_noise(
                common.SITE_EDGE_JSQ, hs, t), dead)))
        e_rank = collections.Counter()
        for hst, f in senders:
            need = (f_hi[f] >= 0 and f_hi[f] + 1 - f_cum[f] > sack_thresh
                    and f_cum[f] < m)
            base = f * m
            first_missing = min(f_cum[f], m - 1)
            for o in range(64):
                cand = min(f_cum[f] + o, m - 1)
                if not p_recv[base + cand]:
                    first_missing = cand
                    break
            fresh = f_next[f]
            is_rtx = need
            seq = first_missing if need else min(fresh, m - 1)
            if fresh >= m and not need:
                seq, is_rtx = first_missing, True
            rtx += is_rtx
            pid = base + seq
            if not is_rtx and fresh < m:
                f_next[f] += 1
            if f_sent[f] == 0:
                f_last_ack[f] = t
            f_sent[f] += 1

            c = 0
            if scheme == "host_pkt_ar":
                if pool_cnt[f] > 0:
                    lab = pool[f][pool_cnt[f] - 1]
                    pool_cnt[f] -= 1
                else:
                    idx = _i32(f_draw[f] * 48271 + 12345) % 65536
                    lab = label_pool[idx]
                if conv:
                    lab = vpaths[f][lab % len(vpaths[f])]
                a, c = (lab // h) % h, lab % h
            elif scheme in HOST_LABEL:
                a, c = pre[conv][0][pid], pre[conv][1][pid]
            elif scheme == "ofan":
                g = (p1[f] * h + e1[f]) * tree.n_edges + p2[f] * h + e2[f]
                orders, starts, lens_ = tables[conv]["edge"]
                a = orders[g][(starts[g] + ptr_e[g] + e_rank[g])
                              % max(lens_[g], 1)]
                if leaves[f]:
                    e_rank[g] += 1
            else:
                a = e_pick[hst]
            f_draw[f] += 7
            p_a[pid], p_c[pid] = a, c
            q = (OFF0 + (p1[f] * h + e1[f]) * h + a if leaves[f]
                 else OFF4 + dst[f])
            fwd.append((pid, q))
        if scheme == "ofan":
            for g, cnt in e_rank.items():
                ptr_e[g] += cnt

        # 4. arrivals: aggregation uplink choice, then enqueue in order.  An
        # arrival at an aggregation switch takes its core index as chosen
        # now: by the switch (JSQ, OFAN) or the packet's current label (a
        # retransmission of the same packet may have relabelled it).
        arr = arrivals.pop(t, [])
        up = [i for i, (pk, q) in enumerate(arr) if OFF1 <= q < OFF2]
        if scheme == "switch_pkt_ar" and up:
            lens = [[len(queues[OFF1 + ((arr[i][1] - OFF1) // h) * h + j])
                     for j in range(h)] for i in up]
            dead = (np.stack([a_dead[(arr[i][1] - OFF1) // h,
                                     p2[arr[i][0] // m]] for i in up])
                    if conv and failed else None)
            picks = jsq(lens, lane_noise(common.SITE_AGG_JSQ,
                                         [arr[i][0] for i in up], t), dead)
        a_rank = collections.Counter()
        for n_i, i in enumerate(up):
            pk, q = arr[i]
            asw = (q - OFF1) // h
            if scheme == "ofan":
                g = asw * k + p2[pk // m]
                orders, starts, lens_ = tables[conv]["agg"]
                c = orders[g][(starts[g] + ptr_a[g] + a_rank[g])
                              % max(lens_[g], 1)]
                a_rank[g] += 1
            elif scheme == "switch_pkt_ar":
                c = picks[n_i]
            else:
                c = p_c[pk]
            p_c[pk] = c
            arr[i] = (pk, OFF1 + asw * h + c)
        if scheme == "ofan":
            for g, cnt in a_rank.items():
                ptr_a[g] += cnt
        slot_sum = 0
        for pk, q in arr:
            if len(queues[q]) >= cap or not alive_q(q):
                drops += 1
                continue
            queues[q].append(pk)
            occ = len(queues[q])
            if occ > ecn_t:
                p_ecn[pk] = True
            max_q = max(max_q, occ)
            slot_sum += occ
            enq_events += 1
        sum_q = np.float32(sum_q + np.float32(slot_sum))

        # 5. ACKs
        for pk in acks.pop(t, []):
            f = pk // m
            debt[src[f]] = dtype(debt[src[f]] + cost_d)
            f_acked[f] += 1
            f_last_ack[f] = t
            f_hi[f] = max(f_hi[f], pk - f * m)
            if scheme == "host_pkt_ar" and not p_ecn[pk]:
                pool[f][min(pool_cnt[f], 63)] = p_a[pk] * h + p_c[pk]
                pool_cnt[f] = min(pool_cnt[f] + 1, 64)
        for f in range(F):
            for _ in range(2):
                base, adv = f * m, 0
                while (adv < 4 and f_cum[f] + adv < m
                       and p_recv[base + f_cum[f] + adv]):
                    adv += 1
                f_cum[f] += adv

        # 6-7. timeouts and completion
        for f in range(F):
            inflight = f_sent[f] - f_acked[f] - f_lost[f]
            if (f_sent[f] > 0 and f_complete[f] < 0 and inflight > 0
                    and t - f_last_ack[f] > rto_slots):
                f_lost[f] += inflight
                f_last_ack[f] = t
                f_next[f] = min(f_next[f], f_cum[f])
            if f_complete[f] < 0 and f_cum[f] >= m:
                f_complete[f] = t
                n_open -= 1
        t += 1

    finished = n_open == 0
    return {
        "cct": float(max(f_data_done)) if min(f_data_done) >= 0
        else float(max_slots),
        "cct_acked": float(max(f_complete)) if finished
        else float(max_slots),
        "max_queue": float(max_q),
        "avg_queue": float(sum_q) / max(float(enq_events), 1.0),
        "drops": drops,
        "retransmissions": int(rtx),
        "finished": finished,
    }


def _i32(x: int) -> int:
    """Two's-complement 32-bit wraparound, as the model's counters have."""
    return (x + 2 ** 31) % 2 ** 32 - 2 ** 31


def _ofan_tables(tree, links, rng):
    """OFAN pointers per (source edge, destination edge) and per
    (aggregation switch, destination pod): a random order and start over
    all ports with every link up, else an interleaved weighted round robin
    over the W-ECMP weights from a random start ((orders, starts, lengths)
    each)."""
    k, h, n_e = tree.k, tree.h, tree.n_edges
    if links is None or not links.any_failure:
        e_o, e_s = common.pointer_tables(n_e * n_e, h, rng)
        a_o, a_s = common.pointer_tables(n_e * k, h, rng)
        return {"edge": (e_o.tolist(), e_s.tolist(), [h] * (n_e * n_e)),
                "agg": (a_o.tolist(), a_s.tolist(), [h] * (n_e * k))}

    def pad(rows):
        width = max((len(r) for r in rows if len(r)), default=h)
        lens = [len(r) for r in rows]
        out = [np.tile(r, -(-width // len(r)))[:width].tolist() if len(r)
               else [0] * width for r in rows]
        return out, lens

    e_rows = []
    for se in range(n_e):
        sp, si = divmod(se, h)
        for de in range(n_e):
            dp, di = divmod(de, h)
            e_rows.append(np.arange(h) if se == de else common.iwrr(
                common.edge_weights(tree, links, sp, si, dp, di), rng))
    e_o, e_len = pad(e_rows)
    e_s = rng.integers(0, np.maximum(e_len, 1)).tolist()
    a_rows = []
    for ga in range(n_e):
        sp, ai = divmod(ga, h)
        for dp in range(k):
            a_rows.append(np.arange(h) if dp == sp else common.iwrr(
                common.agg_weights(links, sp, ai, dp), rng))
    a_o, a_len = pad(a_rows)
    a_s = rng.integers(0, np.maximum(a_len, 1)).tolist()
    return {"edge": (e_o, e_s, e_len), "agg": (a_o, a_s, a_len)}
