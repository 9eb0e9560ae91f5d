#!/usr/bin/env python3
"""Find the highest offered rate a poisson cell's server sustains.

    python3 bench/sweep.py --workload <name> --rates 2,3,4 --seconds 20

Builds the cell's server once, then offers the cell's traffic at each rate
in turn for `--seconds` (after the mix's warm-up), in a fresh session, and
prints one JSON line per rate: requests offered, finished, the backlog
left when the offering stopped (queued plus in flight), and the latency
quantiles of the requests due in each half of the window.  A rate is
sustained when the backlog stays within the pool and the second half's
latency is not above the first's.  The benchmark's runs do not run this;
it is how a cell's fixed rate was chosen.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(CHECKOUT / "src"))
    import numpy as np

    import harness
    harness.use_checkout_cache(CHECKOUT)
    harness.configure_jax()
    if harness.tpu_devices() is None:
        print("sweep: no TPU", file=sys.stderr)
        return 2
    bench = harness.Bench(CHECKOUT)
    cell = bench.cell(args.workload)
    engine = harness.build_server(bench, cell)[2]
    for rate in [float(r) for r in args.rates.split(",")]:
        run = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate,
                                                 drain_s=0.0))
        t0 = time.perf_counter()
        rec, session, win = harness.serve_cell(run, engine, args.seed,
                                               args.seconds)
        backlog = len(session.sched.queue) + sum(
            s.busy for s in session.sched.slots)
        session.finish()
        mid = 0.5 * (win["t_open"] + win["t_close"])
        halves = []
        for lo, hi in ((win["t_open"], mid), (mid, win["t_close"])):
            lat = [rec.finished_at[r] - d for r, d in win["due"].items()
                   if lo <= d < hi and r in rec.finished_at]
            halves.append([float(np.percentile(lat, q)) if lat else None
                           for q in (50, 90)])
        offered = len(win["measured"])
        done = sum(r in rec.finished_at for r in win["measured"])
        print(json.dumps({"rate_per_s": rate, "offered": offered,
                          "finished": done, "backlog_at_close": backlog,
                          "p50_p90_first_half": halves[0],
                          "p50_p90_second_half": halves[1],
                          "ticks": len(win["ticks"]),
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
