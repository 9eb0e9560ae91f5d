"""DiT-XL/2 (Peebles & Xie, arXiv:2212.09748) for the benchmark: the
weights it serves and the plain reference it is checked against.

`param_specs` lays the weights out as the serving program reads them
(the AdaLN-zero block stacked over layers).  `forward` is the model in
straightforward jax.numpy: float32 throughout, every matrix product
through the `dot` it is given (float32 at HIGHEST precision for the
reference, rounded to a lower precision for the control).  The equations:

  x   = latents @ patch_in + sincos(positions)
  c   = silu(sincos(t) @ t_mlp1) @ t_mlp2 + class_embed[y]
  per block: (s1, sc1, g1, s2, sc2, g2) = silu(c) @ ada_w + ada_b
    x += g1 * Attn(LN(x) * (1 + sc1) + s1)
    x += g2 * MLP_gelu_tanh(LN(x) * (1 + sc2) + s2)
  out = (LN(x) * (1 + sc) + s) @ patch_out,  (s, sc) from final_ada

LN has no affine part and eps 1e-5; attention is full softmax attention
over the image's tokens with 1/sqrt(head_dim) scaling.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: standard deviation of the AdaLN modulation weights; the published init
#: is all zero (AdaLN-zero), which makes an untrained model output exactly
#: zero, so the benchmark's random weights use this small scale instead
ADA_STD = 0.02
#: standard deviation of the class-embedding table
CLASS_STD = 0.5


def param_specs(cfg):
    """[(path, shape, std)] of every weight, in the serving layout."""
    d, L, F = cfg["d_model"], cfg["num_layers"], cfg["d_ff"]
    din, C = cfg["dit_in_dim"], cfg["dit_num_classes"]
    return [
        (("patch_in",), (din, d), 1 / math.sqrt(din)),
        (("t_mlp1",), (d, d), 1 / math.sqrt(d)),
        (("t_mlp2",), (d, d), 1 / math.sqrt(d)),
        (("class_embed",), (C + 1, d), CLASS_STD),
        (("blocks", "attn", "wq"), (L, d, d), 1 / math.sqrt(d)),
        (("blocks", "attn", "wk"), (L, d, d), 1 / math.sqrt(d)),
        (("blocks", "attn", "wv"), (L, d, d), 1 / math.sqrt(d)),
        (("blocks", "attn", "wo"), (L, d, d), 1 / math.sqrt(d)),
        (("blocks", "mlp", "w_up"), (L, d, F), 1 / math.sqrt(d)),
        (("blocks", "mlp", "w_down"), (L, F, d), 1 / math.sqrt(F)),
        (("blocks", "ada_w"), (L, d, 6 * d), ADA_STD),
        (("blocks", "ada_b"), (L, 6 * d), ADA_STD),
        (("final_ada_w",), (d, 2 * d), ADA_STD),
        (("final_ada_b",), (2 * d,), ADA_STD),
        (("patch_out",), (d, din), 1 / math.sqrt(d)),
    ]


def sincos(pos, d):
    """(..., S) positions -> (..., S, d): [sin | cos] over d/2 frequencies
    exp(-ln(1e4) * i / (d/2 - 1))."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(half - 1, 1))
    ang = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def layer_norm(x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def modulate(x, shift, scale):
    return layer_norm(x) * (1.0 + scale[:, None, :]) + shift[:, None, :]


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def attention(dot, h, p, heads):
    """Full softmax self-attention over axis 1 of h: (B, S, d)."""
    B, S, d = h.shape
    hd = d // heads
    q = dot(h, p["wq"]).reshape(B, S, heads, hd).transpose(0, 2, 1, 3)
    k = dot(h, p["wk"]).reshape(B, S, heads, hd).transpose(0, 2, 3, 1)
    v = dot(h, p["wv"]).reshape(B, S, heads, hd).transpose(0, 2, 1, 3)
    w = jax.nn.softmax(dot(q, k) / math.sqrt(hd), axis=-1)
    o = dot(w, v).transpose(0, 2, 1, 3).reshape(B, S, d)
    return dot(o, p["wo"])


def mlp(dot, h, p):
    return dot(jax.nn.gelu(dot(h, p["w_up"]), approximate=True), p["w_down"])


def embed(dot, params, latents, t, y_embed, cfg, positions_fn=None):
    """Token embeddings x (B, T, d) and conditioning c (B, d)."""
    T = latents.shape[1]
    x = dot(latents, f32(params["patch_in"])) + \
        (positions_fn or positions)(T, cfg)
    te = sincos(t, cfg["d_model"])
    te = dot(jax.nn.silu(dot(te, f32(params["t_mlp1"]))),
             f32(params["t_mlp2"]))
    return x, te + y_embed


def positions(T, cfg):
    return sincos(jnp.arange(T)[None], cfg["d_model"])


def block(dot, x, c, p, cfg):
    mod = dot(jax.nn.silu(c), p["ada_w"]) + p["ada_b"]
    s1, sc1, g1, s2, sc2, g2 = jnp.split(mod, 6, axis=-1)
    x = x + g1[:, None] * attention(dot, modulate(x, s1, sc1), p["attn"],
                                    cfg["num_heads"])
    return x + g2[:, None] * mlp(dot, modulate(x, s2, sc2), p["mlp"])


def first_block_shift_scale(dot, c, params):
    """(shift, scale) of the first block's attention input."""
    p0 = f32(jax.tree_util.tree_map(lambda a: a[0], params["blocks"]))
    mod = dot(jax.nn.silu(c), p0["ada_w"]) + p0["ada_b"]
    return jnp.split(mod, 6, axis=-1)[:2]


def class_rows(params, y):
    return f32(params["class_embed"])[y]


def forward(dot, params, latents, t, y, cfg, block_fn=block,
            positions_fn=None):
    """latents (B, T, in_dim), t (B,) float, y (B,) int -> eps (B, T, in).
    `block_fn` and `positions_fn` let a backbone of the same family swap
    its block and its token positions."""
    x, c = embed(dot, params, latents, t, class_rows(params, y), cfg,
                 positions_fn)

    def body(x, p):
        return block_fn(dot, x, c, f32(p), cfg), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    mod = dot(jax.nn.silu(c), f32(params["final_ada_w"])) + \
        f32(params["final_ada_b"])
    s, sc = jnp.split(mod, 2, axis=-1)
    return dot(modulate(x, s, sc), f32(params["patch_out"]))


def signal(dot, params, latents, t, y, cfg):
    """TeaCache's input-side signal (TeaCache Eq. 22 as the repo serves
    it): the first block's AdaLN-modulated input, (B, T, d)."""
    x, c = embed(dot, params, latents, t, class_rows(params, y), cfg)
    s, sc = first_block_shift_scale(dot, c, params)
    return modulate(x, s, sc)

