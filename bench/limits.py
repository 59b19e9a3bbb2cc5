"""Readings that a cell's comparison limit is set from, in one process.

    python bench/limits.py --workload <cell> --seeds 1,2,... --seconds 3

For each seed: a short window of the cell's own campaigns on the chip, then
the cell's comparison (``bench/check.py``) on the same seeded sample a run
draws, twice: once of the program's records (the lower readings) and once
of the control, the reference computed in bfloat16 in the program's place
(the upper readings), each number compared on its own.  One JSON line per
seed, then a summary line: the largest lower and the least upper reading
of each number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _, config, traffic, _, _ = run.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    import ml_dtypes

    import check
    from grid import Grid
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("limits: no TPU", file=sys.stderr)
        return 2

    def control(rec):
        return check.reference(config, traffic, rec, dtype=ml_dtypes.bfloat16)

    lows, highs = {}, {}
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        grid = Grid(config, traffic, seed)
        records = []
        t = 0.0
        while t < args.seconds or not records:
            recs, _, secs = run.run_campaign(grid.next())
            records += recs
            t += secs
        notes: list = []
        prog = check.compare(config, traffic, records, seed, log=notes.append)
        ctrl = check.compare(config, traffic, records, seed, stand_in=control,
                             log=lambda s: None)
        low = {n: v for n, v, _, _ in prog[1:]}
        high = {n: v for n, v, _, _ in ctrl[1:]}
        for n in low:
            lows.setdefault(n, []).append(low[n])
            highs.setdefault(n, []).append(high[n])
        print(json.dumps({"seed": seed, "points": len(records),
                          "compared": prog[0][1], "program": low,
                          "control": high, "notes": notes}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "lower_reading": {n: max(v) for n, v in lows.items()},
                      "upper_reading": {n: min(v) for n, v in highs.items()}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
