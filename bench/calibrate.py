#!/usr/bin/env python3
"""Read the two ends a cell's `correct` limits are set between.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 8

Builds the cell's server once and, for each seed, serves the cell's
traffic at its own load for a short window exactly as a benchmark run
does, draws the run's sample of finished requests, and compares it with
the plain reference (`reference.py`): that gives the program's readings.
The control, the reference computed with every matrix product in float8,
is put in the served requests' place: its samples and distances are
compared with the reference's over the same requests.  Both are judged by
the cell's limits file with the rule a benchmark run uses
(`harness.judge`); the control must come out not correct.  Prints one JSON
line per seed.  The benchmark's own runs do not run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the control on the first this many seeds")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(CHECKOUT / "src"))
    import numpy as np

    import harness
    harness.use_checkout_cache(CHECKOUT)
    import reference as ref_mod
    harness.configure_jax()
    if harness.tpu_devices() is None:
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    bench = harness.Bench(CHECKOUT)
    cell = bench.cell(args.workload)
    backbone, params, engine = harness.build_server(bench, cell)
    refs = {kind: ref_mod.Reference(backbone, cell.model, params,
                                    cell.policy,
                                    cell.config["noise_schedule"],
                                    ref_mod.DOTS[kind])
            for kind in ("reference", "control")}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rec, session, win = harness.serve_cell(cell, engine, seed,
                                               args.seconds)
        session.finish()
        served = harness._served(rec, session, win["reqs"], win["measured"])
        sample = ref_mod.draw_sample(served, int(cell.limits["sample"]),
                                     seed)
        t0 = time.perf_counter()
        out = ref_mod.compare(refs["reference"], sample,
                              control=(refs["control"]
                                       if i < args.control_seeds else None))
        nonfinite = sum(1 for s in served if not np.isfinite(s.x0).all())
        numbers = dict(out, window_compiles=win["compiles"],
                       sampled=len(sample),
                       failed=sum(r not in rec.finished_at
                                  for r in win["measured"]) + nonfinite)
        out["correct"] = harness.judge(numbers, cell.limits)[1]
        if "control_x0_rel_l2" in out:
            out["control_correct"] = harness.judge(
                dict(numbers, x0_rel_l2=out["control_x0_rel_l2"],
                     metric_gap=out["control_metric_gap"]), cell.limits)[1]
        out.update(seed=seed, sampled=len(sample),
                   slots=[s.slot for s in sample],
                   steps=[s.num_steps for s in sample],
                   computed=[int(s.want_cond.sum()) for s in sample],
                   compare_s=time.perf_counter() - t0,
                   compiles=win["compiles"])
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
