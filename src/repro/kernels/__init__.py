"""Pallas TPU kernels for the perf-critical hot spots.

  flash_attention — tiled online-softmax attention (prefill hot spot)
  forecast        — fused polynomial feature forecast (predictive caching's
                    per-skipped-step evaluation, §2.3 of DESIGN.md)
  ssd             — Mamba2 chunked state-space-dual scan (zamba2 hot spot)

Each module ships `<name>.py` (pl.pallas_call + BlockSpec), `ops.py` (jit'd
public wrapper choosing kernel vs reference) and `ref.py` (pure-jnp oracle).
The wrappers run the Pallas interpreter when the default backend is the CPU
and the compiled Mosaic kernel otherwise; an explicit `interpret=` argument
overrides that.  tests/test_tpu_compile.py compiles every kernel for a
described TPU v5e, which interpret mode cannot check.
"""
from .flash_attention.ops import flash_attention
from .forecast.ops import forecast
from .ssd.ops import ssd_scan

__all__ = ["flash_attention", "forecast", "ssd_scan"]
