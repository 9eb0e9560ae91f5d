"""Mamba2 SSD chunked scan (Pallas).

TPU-native formulation (DESIGN §6): the selective scan is recast as the
state-space-dual *matmul* form so the MXU does the heavy lifting:

  per chunk (L = chunk length, P = head dim, N = state dim):
    scores = (C B^T) ⊙ exp(segsum(dA))          (L,L)  — MXU + VPU mask
    Y_diag = scores @ (x ⊙ dt)                  (L,P)  — MXU
    Y_off  = (C ⊙ exp(cumsum dA)) @ h_prev^T    (L,P)  — MXU
    h_new  = h_prev ⊙ exp(Σ dA) + (x ⊙ decay dt)^T B   (P,N) — MXU

The inter-chunk state h lives in VMEM scratch and is carried across grid
steps: the TPU grid is executed sequentially with the last dimension
innermost, so for each (batch, head) program column the chunk index walks
0..nc-1 in order and the scratch acts as the recurrence register.  This is
the part a GPU implementation does with a separate kernel launch + global
memory round-trip; on TPU it is free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr,
                *, nc: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[...].astype(jnp.float32)        # (L, P)
    dt = dt_ref[pl.ds(ci, 1), :].astype(jnp.float32)   # (1, L) this chunk
    A = a_ref[hi].astype(jnp.float32)         # scalar (negative), from SMEM
    B = b_ref[...].astype(jnp.float32)        # (L, N)
    C = c_ref[...].astype(jnp.float32)        # (L, N)
    L = x.shape[0]

    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = jj <= ii
    diag = ii == jj
    dA = dt * A                               # (1, L) <= 0
    # inclusive cumsum as a masked lane reduction (cs_i = sum_{k<=i} dA_k);
    # the diagonal picks move the column vectors back into row layout
    cs_col = jnp.sum(jnp.where(causal, dA, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(diag, cs_col, 0.0), axis=0, keepdims=True)
    dt_col = jnp.sum(jnp.where(diag, dt, 0.0), axis=1, keepdims=True)
    # segsum decay: contribution of token j to token i decays by
    # exp(sum_{k=j+1..i} dA_k) = exp(cs_i - cs_j), j <= i
    Lmat = jnp.where(causal, jnp.exp(cs_col - cs_row), 0.0)

    scores = (C @ B.T) * Lmat                 # (L, L)
    y = scores @ (x * dt_col)                 # intra-chunk (L, P)

    h_prev = h_scr[...]                       # (P, N)
    y = y + (C * jnp.exp(cs_col)) @ h_prev.T

    total = jnp.sum(dA, axis=1, keepdims=True)        # (1, 1) = cs[-1]
    decay_dt = jnp.exp(total - cs_col) * dt_col       # (L, 1)
    h_new = h_prev * jnp.exp(total) + (x * decay_dt).T @ B
    h_scr[...] = h_new

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        hout_ref[...] = h_new.astype(hout_ref.dtype)


def ssd_pallas(x, dt, A, B_, C_, *, chunk: int = 64, interpret: bool = True):
    """x: (b,s,h,p); dt: (b,s,h); A: (h,); B_/C_: (b,s,n).

    Returns (y (b,s,h,p) f32, h_final (b,h,p,n) f32)."""
    b, s, h, p = x.shape
    n = B_.shape[-1]
    if s % chunk != 0:
        chunk = s
    nc = s // chunk

    xt = x.transpose(0, 2, 1, 3)              # (b,h,s,p)
    # (b,h,nc,chunk): each (b,h) program column holds all of its dt rows
    # in one tile-aligned block and slices its chunk's row in-kernel
    dtt = dt.transpose(0, 2, 1).reshape(b, h, nc, chunk)

    grid = (b, h, nc)
    y, h_fin = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, nc, chunk),
                         lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),       # A: (h,) scalars
            pl.BlockSpec((None, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, A, B_, C_)
    return y.transpose(0, 2, 1, 3), h_fin
