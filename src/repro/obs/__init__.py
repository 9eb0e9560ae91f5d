"""repro.obs — unified tracing, metrics, and program profiling.

The serving stack grew three half-observability mechanisms — aggregate
ServingTelemetry counters, ServeSession TickEvent hooks, and the control
plane's TelemetryWindow.  This package unifies them behind one
instrumentation surface and adds what none of them provided:

  clock      — the one monotonic clock helper (`monotonic()`); every wall
               time measured under serving/ and modalities/ goes through
               it (repro.analysis' clock-discipline rule lints this in CI)
  trace      — `span(name, sink=None, **counts)`: the one source of host
               spans.  It enters `jax.profiler.TraceAnnotation`, so in a
               running profiler trace the span sits on the host plane, on
               the device's clock, with its counts as event stats; and it
               times the interval with `clock.monotonic`, adding it to
               `sink[name]`.  ServeSession.tick's `engine.*` phase spans
               share one sink per tick, which feeds the registry's
               `repro_engine_phase_seconds_total` and, frozen before the
               hooks run, `TickEvent.phases` (read by the TraceRecorder).
               No switch: with no profiler running a span costs one
               annotation and two clock reads.
               TraceRecorder: TickEvents -> Chrome/Perfetto trace (per
               sub-pool tracks, plan/backbone phases, per-slot cache
               lifecycle spans annotated with signal vs threshold) + a
               cache-event JSONL that rebuilds a SignalTraceLog from disk
  metrics    — MetricsRegistry: labelled counters / gauges / histograms,
               Prometheus text exposition + JSON snapshots, an event ring
               for discrete occurrences (policy swaps, retunes)
  profiling  — per-program compile time + XLA cost analysis captured by
               engine.warmup(), the measured redundancy ratio
               (FLOPs avoided / dense FLOPs), opt-in jax.profiler traces

Metric naming convention
------------------------
All metric names follow  `repro_<subsystem>_<metric>_<unit>`:

  * `<subsystem>`: `engine` (tick paths), `scheduler` (admission),
    `serving` (telemetry views), `window` (sliding-window views),
    `control` (tuner/plane), `autotune` (pricing).
  * `<metric>`: snake_case noun phrase (`ticks`, `rows_computed`,
    `phase_seconds`, `queue_depth`).
  * `<unit>` suffix where the value has one: `_seconds`, `_ms`, `_bytes`,
    `_rows`; monotonic counters additionally end in `_total`
    (Prometheus convention), e.g. `repro_engine_rows_computed_total`.
  * Labels carry dimensions, never name suffixes: `{modality="video",
    kind="full"}`, not `repro_engine_ticks_video_full`.

Registries and recorders are opt-in: no registry is consulted unless one
is passed (`ServeSession(..., metrics=...)`, `OnlineTuner(registry=...)`).
The tick's spans always run; with no profiler they cost about 18 µs a tick
on a TPU v5e host.
"""
from .clock import monotonic, monotonic_ns, wall
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      default_registry)
from .profiling import (ProgramIR, ProgramProfile, capture_ir,
                        compile_program, flops_per_row, profiler_trace,
                        program_cost, redundancy_ratio)
from .trace import (TraceRecorder, load_cache_events, load_probes,
                    policy_signature, signal_trace_from_files, span,
                    validate_chrome_trace)

__all__ = [
    "monotonic", "monotonic_ns", "wall",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_registry",
    "ProgramIR", "ProgramProfile", "capture_ir", "compile_program",
    "flops_per_row", "profiler_trace", "program_cost", "redundancy_ratio",
    "TraceRecorder", "load_cache_events", "load_probes", "policy_signature",
    "signal_trace_from_files", "span", "validate_chrome_trace",
]
