"""Backbone operations of one DiT row (one latent image through every
block), counted from the configuration's shapes.

Per block and row, in multiply-accumulates (MACs), for T tokens of width d
with MLP width F:

  attention projections   4 T d^2        (q, k, v, o)
  attention scores        2 T^2 d        (q k^T and weights @ v)
  MLP                     2 T d F
  AdaLN modulation        6 d^2          (once per row, not per token)

plus the patch embedding and output projection (2 T d in_dim), the
timestep MLP (2 d^2) and the final AdaLN (2 d^2).  One MAC is 2 FLOPs.
Element-wise work (norms, softmax, GELU) is not counted.  DiT-XL/2 at
256 px comes to 118.6 G MACs per row, the figure the DiT paper reports
as "Gflops" (arXiv:2212.09748, Table 4).
"""
from __future__ import annotations


def macs_per_row(cfg: dict) -> float:
    d, F, L = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    T, din = cfg["dit_patch_tokens"], cfg["dit_in_dim"]
    per_block = 4 * T * d * d + 2 * T * T * d + 2 * T * d * F + 6 * d * d
    return float(L * per_block + 2 * T * d * din + 4 * d * d)


def flops_per_row(cfg: dict) -> float:
    return 2.0 * macs_per_row(cfg)
