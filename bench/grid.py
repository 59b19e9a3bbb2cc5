"""The benchmark's one traffic generator: a campaign grid from a
configuration file (the deployment: fabric, engine, loss recovery) and a
traffic file (the grid a researcher submits: schemes, loads, failures, seeds
per campaign), with every seed drawn from the run's ``--seed``.

Within one run the traffic matrix is fixed: its ``rng_seed`` comes from the
run seed, or from the traffic file where a mix pins one matrix for every
run (a mix whose time depends on which flows meet the failed links).  Each
campaign takes the next replicate seeds from a stream keyed on the run
seed, so no grid point repeats and every campaign has the same shapes: the
seeds ride as operands and nothing compiles after the warm-up campaign.

A mix whose work depends on the replicate seed (how long its slowest row
runs) names a ``seed_pool``: every run then cycles through the same fixed
set of that many replicate seeds, in an order drawn from the run seed, so
runs of different seeds do the same work in another order.  The warm-up
campaign still draws its seeds from the run seed, outside the pool.
"""
from __future__ import annotations

import numpy as np

_MASK64 = (1 << 64) - 1


class Grid:
    def __init__(self, config: dict, traffic: dict, seed: int):
        unknown = set(traffic["schemes"]) - set(config["schemes"])
        if unknown:
            raise ValueError(f"traffic schemes {sorted(unknown)} are not in "
                             f"configuration {config['name']!r}")
        self.config, self.traffic = config, traffic
        base = int(seed) & _MASK64
        self.rng_seed = int(traffic.get(
            "rng_seed", np.random.default_rng([base, 0]).integers(2**31)))
        self._warm = np.random.default_rng([base, 1])
        self._window = np.random.default_rng([base, 2])
        self._used: set = set()
        self._pool, self._queue = None, []
        n_pool = traffic.get("seed_pool")
        if n_pool:
            if n_pool % traffic["seeds_per_campaign"]:
                raise ValueError("seed_pool must be a multiple of "
                                 "seeds_per_campaign")
            self._pool = np.random.default_rng([n_pool, 4]).choice(
                2**31, size=n_pool, replace=False).tolist()
            self._used.update(self._pool)

    @property
    def points_per_campaign(self) -> int:
        t = self.traffic
        return (len(t["schemes"]) * len(t["loads"])
                * len(t.get("failures", [None]))
                * len(t.get("g_converge", [None])) * t["seeds_per_campaign"])

    def _seeds(self, rng) -> tuple:
        out = []
        while len(out) < self.traffic["seeds_per_campaign"]:
            s = int(rng.integers(2**31))
            if s not in self._used:
                self._used.add(s)
                out.append(s)
        return tuple(out)

    def warmup(self):
        return self.campaign(self._seeds(self._warm))

    def next(self):
        if self._pool is None:
            return self.campaign(self._seeds(self._window))
        seeds = []
        while len(seeds) < self.traffic["seeds_per_campaign"]:
            if not self._queue:
                self._queue = [self._pool[i] for i in
                               self._window.permutation(len(self._pool))]
            seeds.append(self._queue.pop())
        return self.campaign(tuple(seeds))

    def campaign(self, seeds):
        from repro.sweep import Campaign, FailureSpec, WorkloadSpec
        c, t = self.config, self.traffic
        loads = tuple(WorkloadSpec(kind=ld["kind"],
                                   msg_packets=ld["msg_packets"],
                                   rng_seed=self.rng_seed)
                      for ld in t["loads"])
        failures = tuple(None if f is None else FailureSpec(**f)
                         for f in t.get("failures", [None]))
        loop_opts = dict(c.get("loop_opts", {}))
        if "rho" in t:
            loop_opts["rho"] = t["rho"]
        return Campaign(
            name=c["name"], schemes=tuple(t["schemes"]), loads=loads,
            trees=(c["k"],), seeds=seeds, failures=failures,
            g_converge=tuple(t.get("g_converge", [None])),
            prop_slots=float(c["prop_slots"]), backend=c["backend"],
            engine=c["engine"], max_slots=int(c.get("max_slots", 200_000)),
            loop_opts=tuple(sorted(loop_opts.items())))
