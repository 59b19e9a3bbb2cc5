"""Reduction of a profiler trace of the measured window to device busy
time, per-executable and per-operation device time, and idle gaps labelled
by what the host was doing.

``load`` turns the profiler's ``.xplane.pb`` into plain event lists
(``[name, start_ns, duration_ns]``); everything after that works on those
lists alone, so a recorded trace (``bench/data``) checks the arithmetic
without a chip.  The window is the host span ``bench.window`` that the
harness opens around the timed campaigns.
"""
from __future__ import annotations

import collections
import glob
import heapq

WINDOW = "bench.window"


def load(logdir: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}
    from the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
            if dev["ops"] or dev["modules"]:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events if e.duration_ns > 0]
    return {"devices": devices, "host": host}


def window(events: dict):
    spans = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(spans)}")
    return spans[0]


def union(intervals, lo, hi):
    """Sorted disjoint [start, end) pieces of the union, clipped to
    [lo, hi)."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clipped(evs, lo, hi):
    return [(n, max(s, lo), min(s + d, hi)) for n, s, d in evs
            if min(s + d, hi) > max(s, lo)]


def reduce(events: dict, top: int = 10) -> dict:
    """Seconds of the window, of device busy time (union of operation
    intervals, averaged over the devices that ran any), of each executable
    and each operation (summed over devices; an operation by its HLO name,
    a ``while`` including the operations of its body), and of idle time by
    the innermost host span over each gap's midpoint."""
    lo, hi = window(events)
    busy, module_s, op_s = [], collections.Counter(), collections.Counter()
    idle = []
    for dev in events["devices"].values():
        ops = _clipped(dev["ops"], lo, hi)
        if not ops:
            continue
        pieces = union([(s, e) for _, s, e in ops], lo, hi)
        busy.append(sum(e - s for s, e in pieces))
        for n, s, e in ops:
            op_s[n.split(" = ", 1)[0]] += e - s
        for n, s, e in _clipped(dev["modules"], lo, hi):
            module_s[n] += e - s
        edges = [lo] + [x for p in pieces for x in p] + [hi]
        idle += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps = collections.Counter()
    idle.sort(key=lambda g: g[0] + g[1])
    for (s, e), label in zip(idle, _innermost(
            events["host"], [(s + e) / 2 for s, e in idle])):
        gaps[label] += (e - s) / max(len(busy), 1)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) * ns if busy else 0.0,
        "module_s": {n: v * ns for n, v in module_s.items()},
        "device_ops": [[n, v * ns] for n, v in op_s.most_common(top)],
        "idle_gaps": [[n, v * ns] for n, v in gaps.most_common(top)],
    }


def _innermost(host, points):
    """For each of the ascending ``points``, the name of the shortest host
    span that contains it (a sweep with a heap keyed on duration)."""
    spans = sorted((s, s + d, n) for n, s, d in host if n != WINDOW)
    heap: list = []
    i = 0
    for p in points:
        while i < len(spans) and spans[i][0] <= p:
            s, e, n = spans[i]
            heapq.heappush(heap, (e - s, e, n))
            i += 1
        while heap and heap[0][1] <= p:
            heapq.heappop(heap)
        yield heap[0][2] if heap else "(no host span)"

