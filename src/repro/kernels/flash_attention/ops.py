"""Public jit'd wrapper for the flash-attention kernel."""
from __future__ import annotations

from functools import partial

import jax

from .flash_attention import flash_attention_pallas
from .ref import attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret", "use_kernel"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=None, use_kernel=True):
    """Drop-in attention: Pallas kernel on TPU, interpret-mode on CPU."""
    if interpret is None:                 # decided when the call is traced
        interpret = jax.default_backend() == "cpu"
    if not use_kernel:
        return attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)
