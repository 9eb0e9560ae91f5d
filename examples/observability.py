"""Observability end to end: trace, metrics, and program profiles from one
mixed-modality serving session.

    PYTHONPATH=src python examples/observability.py [OUTDIR]

Serves a mixed image+video queue (TeaCache cond policy, FasterCacheCFG
uncond reuse on the image pool) with the full repro.obs surface attached,
then writes to OUTDIR (default: a new directory under the system's temp
directory, printed at the end):

  trace.json          Chrome/Perfetto trace — one process per modality
                      sub-pool, plan/backbone tracks, per-slot cache
                      lifecycle spans (admit -> compute/reuse annotated
                      with signal vs threshold -> finish).  Open it at
                      https://ui.perfetto.dev or chrome://tracing.
  cache_events.jsonl  one line per active slot per tick — the durable
                      SignalTraceLog: `signal_trace_from_files` rebuilds
                      a trainable trace from it after the process exits.
  metrics.prom        Prometheus text exposition of every counter/gauge/
                      histogram the engines + schedulers published.
  metrics.json        the same registry as a JSON snapshot (+ event ring).
  profile_*/          the jax.profiler trace the session ran under, in a
                      directory made fresh for this run: each
                      ServeSession.tick is an `engine.tick` span with its
                      nine `engine.*` phase spans (admit, prepare, plan,
                      upload, dispatch, wait, account, harvest, hooks) on
                      the host plane, on the device ops' clock, their
                      counts (requests, arrays, nbytes, bucket) as event
                      stats.  The per-phase host time per tick is printed
                      and reconciled with the registry's
                      repro_engine_phase_seconds_total.

It also prints warmup's per-program compile time + XLA-costed FLOPs and
the measured redundancy ratio (FLOPs the caches avoided over the dense
FLOPs a no-cache pool would have dispatched), and reconciles the JSONL
against ServingTelemetry: per-request computed-step counts must agree
EXACTLY (tests/test_observability.py asserts the same).
"""
import glob
import json
import os
import sys
import tempfile

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.modalities import MixedModalityEngine, make_workload
from repro.obs import (MetricsRegistry, TraceRecorder, flops_per_row,
                       redundancy_ratio, validate_chrome_trace)
from repro.serving.diffusion import DiffusionRequest

NUM_STEPS = 8
SLOTS = 2


def main(outdir: str = ""):
    if outdir:
        os.makedirs(outdir, exist_ok=True)
    else:
        outdir = tempfile.mkdtemp(prefix="repro_obs_")
    workloads = {m: make_workload(m, smoke=True) for m in ("image", "video")}
    from repro.core import FasterCacheCFG
    pools = {
        name: wl.engine("teacache", slots=SLOTS, max_steps=NUM_STEPS,
                        cfg_policy=(FasterCacheCFG(4, NUM_STEPS)
                                    if name == "image" else None))
        for name, wl in workloads.items()}
    engine = MixedModalityEngine(pools)

    # -- warmup doubles as the program profiler ------------------------
    profiles = engine.warmup()
    print("== program profiles (per-bucket jit compile + XLA cost) ==")
    for modality, prof in sorted(profiles.items()):
        for key, p in sorted(prof.items(), key=lambda kv: str(kv[0])):
            print(f"  {modality:6s} program {str(key):>5s}: "
                  f"compile {p.compile_seconds:6.2f}s  "
                  f"flops {p.flops:12.3e}  bytes {p.bytes_accessed:10.3e}")
        print(f"  {modality:6s} marginal FLOPs/row: "
              f"{flops_per_row(prof):.3e}")

    # -- serve with the full observability surface attached ------------
    registry = MetricsRegistry()
    recorders = {m: TraceRecorder(policy=pools[m].policy)
                 for m in pools}
    mods = ("image", "video")
    # stagger num_steps WITHIN each pool: uniform queues tick in lockstep
    # (every slot wants compute on the same ticks), which hides the row
    # savings the redundancy ratio below prices
    reqs = [DiffusionRequest(i, num_steps=NUM_STEPS - 2 * ((i // 2) % 2),
                             seed=i, class_label=i % 5, modality=mods[i % 2],
                             cfg_scale=3.0 if mods[i % 2] == "image" else 0.0)
            for i in range(8)]
    # a directory of this run's own, so the one trace read back below is
    # the one this session wrote
    profile_dir = tempfile.mkdtemp(prefix="profile_", dir=outdir)
    with jax.profiler.trace(profile_dir):
        results = engine.serve(reqs, hooks={m: [rec] for m, rec
                                            in recorders.items()},
                               metrics=registry)
    assert all(np.isfinite(r.x0).all() for r in results)
    for m, tele in engine.telemetry.pools.items():
        tele.publish(registry, modality=m)     # telemetry as a metrics view

    # -- artifacts -----------------------------------------------------
    # merge the per-pool recorders into one Perfetto trace (events carry
    # their own pid per modality, so concatenation is safe after remapping
    # pids to stay distinct)
    merged = {"traceEvents": [], "displayTimeUnit": "ms"}
    pid_base = 0
    for m in sorted(recorders):
        rec = recorders[m]
        rec.finish()
        trace = rec.chrome_trace()
        problems = validate_chrome_trace(trace)
        assert not problems, (m, problems)
        for ev in trace["traceEvents"]:
            ev = dict(ev)
            ev["pid"] += pid_base
            merged["traceEvents"].append(ev)
        pid_base += 1 + max(
            (e["pid"] for e in trace["traceEvents"]), default=0)
    trace_path = os.path.join(outdir, "trace.json")
    with open(trace_path, "w") as f:
        json.dump(merged, f, default=float)

    jsonl_path = os.path.join(outdir, "cache_events.jsonl")
    with open(jsonl_path, "w") as f:
        for m in sorted(recorders):
            for ev in recorders[m].cache_events:
                f.write(json.dumps(ev, default=float) + "\n")

    registry.write_prometheus(os.path.join(outdir, "metrics.prom"))
    registry.write_snapshot(os.path.join(outdir, "metrics.json"))

    # -- reconcile: JSONL == telemetry, exactly ------------------------
    print("\n== reconciliation (cache-event JSONL vs ServingTelemetry) ==")
    ok = True
    for m, rec in sorted(recorders.items()):
        by_req = rec.computed_steps_by_request()
        tele = engine.telemetry.pools[m]
        for r in tele.records:
            traced = by_req.get(r.request_id)
            match = traced == r.computed_steps
            ok &= match
            print(f"  {m:6s} req {r.request_id}: telemetry "
                  f"{r.computed_steps} computed steps, trace {traced} "
                  f"{'OK' if match else 'MISMATCH'}")
    assert ok, "cache-event log diverged from telemetry"

    # -- the survey's redundancy claim, measured in FLOPs --------------
    print("\n== measured redundancy ratio ==")
    for m, tele in sorted(engine.telemetry.pools.items()):
        rr = redundancy_ratio(profiles[m], tele.backbone_rows_computed,
                              tele.backbone_rows_padding,
                              tele.backbone_rows_saved)
        print(f"  {m:6s} {rr['redundancy_ratio']:.3f} "
              f"({rr['flops_avoided']:.3e} of {rr['dense_flops']:.3e} "
              f"dense FLOPs avoided)")

    # -- the engine's host spans, read back from the profiler trace ----
    print("\n== engine.* host spans (profiler trace vs registry) ==")
    (xplane,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                          recursive=True)
    span_ms, span_n = {}, {}
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    span_ms[ev.name] = (span_ms.get(ev.name, 0.0)
                                        + ev.duration_ns * 1e-6)
                    span_n[ev.name] = span_n.get(ev.name, 0) + 1
    phase_s = registry.counter("repro_engine_phase_seconds_total")
    ticks = span_n["engine.tick"]
    print(f"  {'engine.tick':16s} {span_ms['engine.tick'] / ticks:8.3f} "
          f"ms per tick ({ticks} ticks)")
    for name in sorted(span_ms, key=lambda n: -span_ms[n])[1:]:
        counted = sum(phase_s.value(phase=name, modality=m) for m in pools)
        print(f"  {name:16s} {span_ms[name] / ticks:8.3f} ms per tick "
              f"(registry {1e3 * counted / ticks:8.3f})")

    s = engine.telemetry.summary()
    print(f"\nserved {s['requests']} requests "
          f"({s['throughput_rps']:.2f} req/s); wrote")
    for path in ("trace.json", "cache_events.jsonl", "metrics.prom",
                 "metrics.json", profile_dir):
        print(f"  {os.path.join(outdir, path)}")
    print("open trace.json at https://ui.perfetto.dev")


if __name__ == "__main__":
    main(*sys.argv[1:2])
