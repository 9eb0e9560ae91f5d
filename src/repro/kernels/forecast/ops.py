"""Public jit'd wrapper for the forecast kernel."""
from __future__ import annotations

from functools import partial

import jax

from .forecast import forecast_pallas
from .ref import basis_coeffs, forecast_ref


@partial(jax.jit, static_argnames=("block_n", "interpret", "use_kernel"))
def forecast(diffs, coeffs, *, block_n=4096, interpret=None, use_kernel=True):
    """Fused `sum_i coeffs[i] * diffs[i]` (the Cache-Then-Forecast hot loop)."""
    if interpret is None:                 # decided when the call is traced
        interpret = jax.default_backend() == "cpu"
    if not use_kernel:
        return forecast_ref(diffs, coeffs)
    return forecast_pallas(diffs, coeffs, block_n=block_n, interpret=interpret)
