"""The comparison that decides ``correct``: records the timed campaigns
produced against the plain reference, on a sample of grid points drawn from
the run seed, every compared field of every sampled point.

The configuration names its reference: ``"reference": "<name>"`` is the
module ``bench/ref/<name>.py``, found by that name alone, so a deployment
brings its reference as a file of its own.  The module gives

* ``point(config, traffic, rec, dtype=None)``: the reference's record of the
  grid point ``rec`` names, its real-valued state in ``dtype`` (None: the
  precision the model states);
* ``COMPARE``: ``(number, rule, test)`` triples; a field of the reference's
  record counts under the first number whose ``test(field)`` holds, and a
  field no test takes is not compared.

A number's rule is ``exact`` (how many compared fields differ from the
reference's) or ``widest`` (the widest absolute gap to the reference's
value; a NaN gap sticks).  Each number is held to its limit in the
configuration's ``check.limits``.
"""
from __future__ import annotations

import collections
import importlib

import numpy as np

RULES = ("exact", "widest")


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


def module(config: dict):
    """The reference module the configuration names."""
    name = config.get("reference")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"configuration {config['name']!r} names no "
                         f"reference module (\"reference\": \"<name>\" of "
                         f"bench/ref/<name>.py), got {name!r}")
    try:
        mod = importlib.import_module(f"ref.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"ref.{name}":
            raise
        raise ValueError(f"configuration {config['name']!r}: no reference "
                         f"module bench/ref/{name}.py") from e
    bad = [r for _, r, _ in mod.COMPARE if r not in RULES]
    if bad:
        raise ValueError(f"bench/ref/{name}.py: rules {bad} are not in "
                         f"{RULES}")
    return mod


def reference(config: dict, traffic: dict, rec: dict, dtype=None) -> dict:
    """The reference's record for the grid point ``rec`` names."""
    return module(config).point(config, traffic, rec, dtype=dtype)


def compare(config: dict, traffic: dict, records, seed: int,
            stand_in=None, log=print):
    """Checks as ``[(name, value, op, limit)]``, ``op`` being ``"<="`` or
    ``">="``.  ``stand_in(rec)``, when given, replaces the
    program's record of each sampled point (the control)."""
    ref = module(config)
    chk = config["check"]
    picked = sample(records, chk["points"], seed)
    value = {name: 0 if rule == "exact" else 0.0
             for name, rule, _ in ref.COMPARE}
    where: dict = {}
    for rec in picked:
        got = stand_in(rec) if stand_in else rec
        want = ref.point(config, traffic, rec)
        for f, v in want.items():
            hit = next(((n, r) for n, r, test in ref.COMPARE if test(f)),
                       None)
            if hit is None:
                continue
            name, rule = hit
            if rule == "exact" and got[f] != v:
                value[name] += 1
                log(f"  {rec['scheme']} seed {rec['seed']}: {f} "
                    f"{got[f]!r}, reference {v!r}")
            elif rule == "widest":
                gap = abs(float(got[f]) - v)
                w = value[name]
                if w == w and not gap <= w:               # NaN sticks
                    value[name] = gap
                    where[name] = (rec["scheme"], rec["seed"], f)
    for name, (scheme, s, f) in sorted(where.items()):
        log(f"  {name} {value[name]!r} at {scheme} seed {s} field {f}")
    return [("points_compared", len(picked), ">=", 1)] + [
        (name, value[name], "<=", chk["limits"][name])
        for name in sorted(value)]
