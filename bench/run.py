#!/usr/bin/env python3
"""Run one benchmark cell once on the chip(s) of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's server (weights from the configuration's seed, engine,
warm-up of every program), offers the cell's traffic from `--seed`, lets
the pool reach steady state, measures for `--seconds`, drains the requests
offered in the window, and compares a seeded sample of them with the plain
reference.  The last line of standard output is one JSON object:
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end metrics,
or with `--trace 1` its per-layer metrics), `device`, with `--trace 1`
`breakdown`, and last `checks`, each number compared with its limit.  The
last lines of standard error repeat the checks.  With no TPU, or fewer
chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(CHECKOUT / "src"))
    import harness
    harness.use_checkout_cache(CHECKOUT)
    rc, result = harness.run_cell(harness.Bench(CHECKOUT), args.workload,
                                  args.seed, args.seconds, bool(args.trace),
                                  t_process=T_PROCESS)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
