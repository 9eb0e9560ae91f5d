"""Latte-XL/2 (Ma et al., arXiv:2401.03048), factorised-attention variant,
for the benchmark: the weights it serves and the plain reference.

A clip of F frames x P patches is flattened to (B, F*P, in_dim).  Each
block applies three AdaLN-zero gated residual branches in order, with
(shift, scale, gate) triples from silu(c) @ ada_w + ada_b split nine ways:

  x += g_s * SpatialAttn(LN(x) * (1 + sc_s) + s_s)    over the P patches
                                                      of each frame
  x += g_t * TemporalAttn(LN(x) * (1 + sc_t) + s_t)   over the F frames at
                                                      each patch position
  x += g_m * MLP_gelu_tanh(LN(x) * (1 + sc_m) + s_m)

Positions are sincos(patch index) + sincos(frame index).  Embedding,
conditioning and the final layer are those of `dit.py`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from backbones import dit

__all__ = ["param_specs", "forward", "signal"]


def param_specs(cfg):
    d, L, F = cfg["d_model"], cfg["num_layers"], cfg["d_ff"]
    din, C = cfg["dit_in_dim"], cfg["dit_num_classes"]
    sq = 1 / math.sqrt(d)
    attn = [(("blocks", branch, w), (L, d, d), sq)
            for branch in ("spatial", "temporal")
            for w in ("wq", "wk", "wv", "wo")]
    return [
        (("patch_in",), (din, d), 1 / math.sqrt(din)),
        (("t_mlp1",), (d, d), sq),
        (("t_mlp2",), (d, d), sq),
        (("class_embed",), (C + 1, d), dit.CLASS_STD),
        *attn,
        (("blocks", "mlp", "w_up"), (L, d, F), sq),
        (("blocks", "mlp", "w_down"), (L, F, d), 1 / math.sqrt(F)),
        (("blocks", "ada_w"), (L, d, 9 * d), dit.ADA_STD),
        (("blocks", "ada_b"), (L, 9 * d), dit.ADA_STD),
        (("final_ada_w",), (d, 2 * d), dit.ADA_STD),
        (("final_ada_b",), (2 * d,), dit.ADA_STD),
        (("patch_out",), (d, din), sq),
    ]


def positions(T, cfg):
    F = cfg["dit_num_frames"]
    P = T // F
    d = cfg["d_model"]
    spat = dit.sincos(jnp.arange(P)[None], d)
    temp = dit.sincos(jnp.arange(F)[None], d)
    return jnp.tile(spat, (1, F, 1)) + jnp.repeat(temp, P, axis=1)


def _mods(dot, c, p):
    return jnp.split(dot(jax.nn.silu(c), p["ada_w"]) + p["ada_b"], 9, axis=-1)


def block(dot, x, c, p, cfg):
    B, T, d = x.shape
    F = cfg["dit_num_frames"]
    P = T // F
    H = cfg["num_heads"]
    mod = _mods(dot, c, p)
    s, sc, g = mod[0:3]
    h = dit.modulate(x, s, sc).reshape(B * F, P, d)
    o = dit.attention(dot, h, p["spatial"], H).reshape(B, T, d)
    x = x + g[:, None] * o
    s, sc, g = mod[3:6]
    h = dit.modulate(x, s, sc).reshape(B, F, P, d).transpose(0, 2, 1, 3)
    o = dit.attention(dot, h.reshape(B * P, F, d), p["temporal"], H)
    x = x + g[:, None] * o.reshape(B, P, F, d).transpose(0, 2, 1, 3) \
        .reshape(B, T, d)
    s, sc, g = mod[6:9]
    return x + g[:, None] * dit.mlp(dot, dit.modulate(x, s, sc), p["mlp"])


def forward(dot, params, latents, t, y, cfg):
    return dit.forward(dot, params, latents, t, y, cfg, block_fn=block,
                       positions_fn=positions)


def signal(dot, params, latents, t, y, cfg):
    """The first block's spatial-branch modulated input, (B, T, d)."""
    x, c = dit.embed(dot, params, latents, t, dit.class_rows(params, y), cfg,
                     positions)
    p0 = dit.f32(jax.tree_util.tree_map(lambda a: a[0], params["blocks"]))
    mod = _mods(dot, c, p0)
    return dit.modulate(x, mod[0], mod[1])

