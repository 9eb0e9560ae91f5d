"""Backbone operations of one factorised video-DiT row (one latent clip of
F frames x P patches through every block), counted from the shapes.

Per block and row, in MACs, with N = F P tokens of width d, MLP width Fd:

  spatial attention projections    4 N d^2
  spatial attention scores         2 N P d     (each frame attends alone)
  temporal attention projections   4 N d^2
  temporal attention scores        2 N F d     (each patch position alone)
  MLP                              2 N d Fd
  AdaLN modulation                 9 d^2       (once per row)

plus the patch embedding and output projection (2 N d in_dim), the
timestep MLP (2 d^2) and the final AdaLN (2 d^2).  One MAC is 2 FLOPs;
element-wise work is not counted.  Latte-XL/2 at 16 x 256 tokens comes to
about 2.51 T MACs (5.0 TFLOP) per clip row.
"""
from __future__ import annotations


def macs_per_row(cfg: dict) -> float:
    d, Fd, L = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    P, F = cfg["dit_patch_tokens"], cfg["dit_num_frames"]
    din = cfg["dit_in_dim"]
    N = F * P
    per_block = (8 * N * d * d + 2 * N * P * d + 2 * N * F * d
                 + 2 * N * d * Fd + 9 * d * d)
    return float(L * per_block + 2 * N * d * din + 4 * d * d)


def flops_per_row(cfg: dict) -> float:
    return 2.0 * macs_per_row(cfg)
