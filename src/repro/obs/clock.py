"""The one host-side clock for the serving stack.

Every wall-time measurement in `repro.serving` and `repro.modalities` —
engine tick phase spans (repro.obs.span), TickEvent seconds, TelemetryWindow
statistics, benchmark harness timings — must come from this module, not
from ad-hoc `time.time()` / `time.perf_counter()` calls (the CI lint's
clock-discipline rule, repro.analysis, enforces this for serving/ and
modalities/).

Why one helper instead of "everyone calls perf_counter":

  * mixing `time.time()` (wall, NTP-steppable, ~ms resolution on some
    hosts) with `time.perf_counter()` (monotonic, ns resolution) in one
    subtraction silently produces garbage durations;
  * trace tooling needs every span on ONE monotonic axis — the Chrome
    trace exporter (repro.obs.trace) timestamps events with this clock,
    so engine timings and recorder spans line up without conversion;
  * tests can monkeypatch a single symbol to make timing deterministic.

`monotonic()` is the measurement clock (seconds, arbitrary epoch, never
steps backwards).  `wall()` is for human-facing timestamps only (log
lines, file names) and must never be subtracted from `monotonic()`.
"""
from __future__ import annotations

import time

__all__ = ["monotonic", "monotonic_ns", "wall"]


def monotonic() -> float:
    """Monotonic seconds (arbitrary epoch) — use for ALL duration math."""
    return time.perf_counter()


def monotonic_ns() -> int:
    """Monotonic nanoseconds — for exporters that want integer ticks."""
    return time.perf_counter_ns()


def wall() -> float:
    """Wall-clock epoch seconds — human-facing timestamps ONLY (subject to
    NTP steps; never mix with `monotonic()` in a subtraction)."""
    return time.time()
