"""Public jit'd wrapper for the SSD scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from .ref import ssd_ref
from .ssd import ssd_pallas


@partial(jax.jit, static_argnames=("chunk", "interpret", "use_kernel"))
def ssd_scan(x, dt, A, B_, C_, *, chunk=64, interpret=None, use_kernel=True):
    """Mamba2 SSD scan. Returns (y, h_final). See ssd.py for layout."""
    if interpret is None:                 # decided when the call is traced
        interpret = jax.default_backend() == "cpu"
    if not use_kernel:
        return ssd_ref(x, dt, A, B_, C_, chunk=chunk)
    return ssd_pallas(x, dt, A, B_, C_, chunk=chunk, interpret=interpret)
