"""Tiled online-softmax attention for TPU (Pallas).

TPU-native design (not a CUDA port):
  * grid = (B, KH, Sq/BQ); each program owns one (128-ish, D) Q tile for one
    KV head group, resident in VMEM.
  * K/V are streamed through VMEM in (BK, D) tiles by an inner fori_loop
    over `pl.ds` ref slices of the full-(Sk) VMEM block — HBM->VMEM movement
    is expressed by the BlockSpec, tile iteration stays on-chip.
  * online softmax: running (m, l, acc) in f32 VREGs; one store per Q tile.
  * GQA: the `group` dimension is folded into the Q-tile rows (BQ rows hold
    BQ//group query positions x group heads) so the MXU matmul contraction
    is always (BQ, D) x (D, BK) — hardware-aligned when BQ, BK, D are
    multiples of 128/8.
  * causal + sliding-window masking from absolute positions computed off
    the grid indices; fully-masked K tiles are skipped by bounding the
    fori_loop, which is where the causal 2x FLOP saving comes from.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, BQ: int, BK: int, Sk: int,
               causal: bool, window: int, scale: float, q_offset: int):
    """One (b, kh, qi) program: q_ref (BQ, G, D); k/v_ref (Sk, D) streamed."""
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale          # (BQ, G, D)
    BQr, G, D = q.shape
    q2 = q.reshape(BQr * G, D)

    m = jnp.full((BQr * G,), NEG_INF, jnp.float32)
    l = jnp.zeros((BQr * G,), jnp.float32)
    acc = jnp.zeros((BQr * G, D), jnp.float32)

    q_pos = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQr, G), 0) + q_offset
    q_pos = q_pos.reshape(BQr * G)

    # bound the KV walk: causal -> only tiles with k_start <= max(q_pos)
    if causal:
        hi = jnp.minimum((qi * BQ + BQ + q_offset + BK - 1) // BK, Sk // BK)
    else:
        hi = Sk // BK

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(ki * BK, BK), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * BK, BK), :].astype(jnp.float32)
        s = q2 @ k.T                                    # (BQ*G, BK)
        k_pos = ki * BK + jax.lax.broadcasted_iota(jnp.int32, (1, BK), 1)
        ok = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos[:, None]
        if window > 0:
            ok &= (q_pos[:, None] - k_pos) < window
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, hi, body, (m, l, acc))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[...] = out.reshape(BQr, G, D).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0, scale=None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True):
    """q: (B, Sq, H, D); k/v: (B, Sk, KH, D). Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    BQ = min(block_q, Sq)
    BK = min(block_k, Sk)
    assert Sq % BQ == 0 and Sk % BK == 0, (Sq, BQ, Sk, BK)
    q_offset = Sk - Sq               # q occupies the tail of the K sequence

    # (B, Sq, H, D) -> (B, KH, Sq, G, D): group dim rides with the Q tile
    qg = q.reshape(B, Sq, KH, G, D).transpose(0, 2, 1, 3, 4)
    kt = k.transpose(0, 2, 1, 3)     # (B, KH, Sk, D)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, KH, Sq // BQ)
    kern = functools.partial(_fa_kernel, BQ=BQ, BK=BK, Sk=Sk, causal=causal,
                             window=window, scale=scale, q_offset=q_offset)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, BQ, G, D), lambda b, h, i: (b, h, i, 0, 0)),
            pl.BlockSpec((None, None, Sk, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((None, None, Sk, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, BQ, G, D),
                               lambda b, h, i: (b, h, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, Sq, G, D), q.dtype),
        interpret=interpret,
    )(qg, kt, vt)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, D)
